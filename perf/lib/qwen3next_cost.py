"""Required operations and bytes of Qwen3-Next as one chip's share runs it:
the LEAST a prefill and a decode step must do, from the configuration's
sizes, so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmaxes, norms, gates, the convolution's four taps, the l2 norms, the
  decay's ``softplus`` and ``exp``, the router's top-k and sampling are not
  counted;
* the prefill's delta rule counts the chunked form's products over a row's
  REAL tokens, cut into chunks of ``chunk``: ``K K^T`` and ``Q K^T`` once a
  KEY head over the pairs ``j <= i`` of a chunk (the causal half, not the
  square the program computes), the triangular solve as ONE forward
  substitution a value head (``C^2 / 2`` multiply-adds a column of its
  ``Dk + Dv`` right-hand sides: the pairs again; the program's six
  squarings and six products of whole ``C x C`` matrices in float32 are its
  way, not the requirement), the in-chunk hand-over ``lower[Q K^T] V'`` over
  the pairs, and the three products that read or write the carry (``W S``,
  ``Q S``, ``K^T V'``: ``2 Dk Dv`` a value head and token each);
* prefill attention counts the query-key pairs the causal mask allows, ``n
  (n + 1) / 2`` a row of ``n`` tokens, each ``2 * 2 * head_dim`` operations
  a query head;
* the experts count the assignments to HELD experts that the program's
  counter saw (2.5 a token a layer at 128 of 512 held), three products each;
  the router and the shared expert every token;
* padding up to the prefill bucket, whole chunks past a row's length and
  unused rows of an admission run are the program's waste and are not
  counted;
* a decode step must read every weight outside the routed experts once (the
  mixers' projections, each layer's router and shared expert with its gate,
  the head; norm scales, ``A``, ``dt_bias`` and the convolution's weights
  not), the three matrices of each expert it TOUCHES (the program's counter:
  never all held), of each LIVE row its carry READ AND WRITTEN once in
  float32 in each delta layer (the program's ``gdn.state_bytes``), its
  convolution tails read and written, and its keys and values up to its
  length in the two full layers (``attn.context_tokens``); the embedding's
  one row a token and activations are not counted;
* what the program moves beyond that — the carry of slots that are not
  live, the un-donated state copied once a chunk — is its waste and is not
  counted.
"""

from __future__ import annotations

BF16, F32 = 2, 4  # bytes


def full_layers(c: dict) -> int:
    return c["num_hidden_layers"] // c["full_attention_interval"]


def delta_layers(c: dict) -> int:
    return c["num_hidden_layers"] - full_layers(c)


def key_width(c: dict) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_width(c: dict) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def conv_channels(c: dict) -> int:
    return 2 * key_width(c) + value_width(c)


def delta_matrices(c: dict) -> int:
    """A delta layer's three projections."""
    h = c["hidden_size"]
    return (h * (conv_channels(c) + value_width(c))
            + h * 2 * c["linear_num_value_heads"] + value_width(c) * h)


def delta_small(c: dict) -> int:
    """Its convolution, ``dt_bias``, ``A_log`` and the gated norm's weight."""
    return (conv_channels(c) * c["linear_conv_kernel_dim"]
            + 2 * c["linear_num_value_heads"] + c["linear_value_head_dim"])


def attention_matrices(c: dict) -> int:
    h = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return h * 2 * q + 2 * h * kv + q * h


def expert_params(c: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_layer_outside(c: dict) -> int:
    """What every token passes in an expert layer: the router, the shared
    expert and its gate."""
    h = c["hidden_size"]
    return (h * c["num_experts"]
            + 3 * h * c["shared_expert_intermediate_size"] + h)


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded."""
    return (delta_layers(c) * delta_matrices(c)
            + full_layers(c) * attention_matrices(c)
            + c["num_hidden_layers"] * expert_layer_outside(c))


def total_params(c: dict) -> int:
    """Every parameter the chip holds, as ``init_params`` makes them: the
    matrices, the embedding and the untied head, and the small ones (norms,
    the convolutions, ``dt_bias``, ``A_log``)."""
    h, layers = c["hidden_size"], c["num_hidden_layers"]
    held = c.get("experts_held", c["num_experts"])
    small = (delta_layers(c) * delta_small(c)
             + full_layers(c) * 2 * c["head_dim"] + layers * 2 * h + h)
    return (params_outside_experts(c) + layers * held * expert_params(c)
            + 2 * c["vocab_size"] * h + small)


def carry_bytes_per_row(c: dict) -> int:
    """One slot's carry in one delta layer, float32."""
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"] * F32)


def tail_bytes_per_row(c: dict) -> int:
    return (c["linear_conv_kernel_dim"] - 1) * conv_channels(c) * BF16


def kv_bytes_per_row(c: dict) -> int:
    """One token's key and value in one full layer's cache."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def slot_bytes(c: dict, max_len: int) -> int:
    """One slot's state in an engine of ``max_len``."""
    return (delta_layers(c) * (carry_bytes_per_row(c) + tail_bytes_per_row(c))
            + full_layers(c) * max_len * kv_bytes_per_row(c))


def chunk_pairs(n: int, chunk: int) -> float:
    """Pairs ``j <= i`` inside the chunks of a row of ``n`` tokens."""
    whole, rest = divmod(n, chunk)
    return whole * chunk * (chunk + 1) / 2 + rest * (rest + 1) / 2


def scan_flops(c: dict, n: int) -> float:
    """The chunked delta rule's products over ``n`` real tokens of one row
    in one delta layer."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    pairs = chunk_pairs(n, c.get("chunk", 64))
    scores = 2 * 2 * hk * dk * pairs            # K K^T and Q K^T a key head
    solve = 2 * hv * (dk + dv) * pairs          # forward substitution
    within = 2 * hv * dv * pairs                # lower[Q K^T] V'
    carry = 3 * 2 * hv * dk * dv * n            # W S, Q S, K^T V'
    return scores + solve + within + carry


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    pair = 2 * 2 * c["num_attention_heads"] * c["head_dim"]
    mixers = sum(
        delta_layers(c) * scan_flops(c, n)
        + full_layers(c) * pair * n * (n + 1) / 2
        for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + mixers
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 state_bytes: float, context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and layers of held experts
    with an assignment, ``state_bytes`` the program's ``gdn.state_bytes``
    (each live row's carry read and written once a delta layer a step),
    ``context_tokens`` the sum over steps of the live rows' lengths."""
    return {
        "delta_projections": steps * delta_layers(c) * delta_matrices(c)
        * BF16,
        "attention": steps * full_layers(c) * attention_matrices(c) * BF16,
        "expert_layers_outside": steps * c["num_hidden_layers"]
        * expert_layer_outside(c) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "carry": state_bytes,
        "conv_tails": state_bytes / carry_bytes_per_row(c)
        * tail_bytes_per_row(c),
        "grown_rows": context_tokens * full_layers(c) * kv_bytes_per_row(c),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 state_bytes: float, context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched, state_bytes,
                                  context_tokens).values()))
