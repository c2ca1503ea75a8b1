"""Required operations and bytes of Ling-3.0-flash as one chip's share runs
it: the LEAST a prefill and a decode step must do, from the configuration's
sizes, so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmaxes, norms, gates' sigmoids, the convolution's four taps, the l2
  norms, the decay's ``exp``, the SwiGLU clip, the router's groups and
  top-k and sampling are not counted;
* the prefill's delta rule counts the chunked form's products over a row's
  REAL tokens, cut into chunks of ``chunk``: the two decayed products ``sum_d
  K_id K_jd exp(..)`` and ``sum_d Q_id K_jd exp(..)`` over the pairs ``j <=
  i`` of a chunk, ``2 Dk`` operations a pair and head each — what a block
  product over the causal half takes, neither the square the program's
  off-diagonal block products compute nor the elementwise form of its
  diagonal blocks —, the triangular solve as ONE forward substitution a head
  (``C^2 / 2`` multiply-adds a column of its ``Dk + Dv`` right-hand sides:
  the pairs again), the in-chunk hand-over ``lower[..] V'`` over the pairs,
  and the three products that read or write the carry (``W S``, ``Q S``,
  ``K^T V'``: ``2 Dk Dv`` a head and token each);
* the latent layer's prefill counts the expansion of keys and values from
  the latent (``c_kv W_kvb``, a token) among the matrices and the query-key
  pairs the causal mask allows, ``n (n + 1) / 2`` a row of ``n`` tokens, each
  ``2 (nope + rope + v)`` operations a head;
* the experts count the assignments to HELD experts that the program's
  counter saw (2.0 a token a layer at 128 of 512 held), three products each;
  the router and the shared expert every token;
* padding up to the prefill bucket, whole chunks past a row's length and
  unused rows of an admission run are the program's waste and are not
  counted;
* a decode step must read every weight outside the routed experts once (the
  mixers' projections, the dense layer's FFN, each expert layer's router and
  shared expert, the head; norm scales, ``A_log``, ``dt_bias`` and the
  convolution's weights not), the three matrices of each expert it TOUCHES
  (the program's counter: never all held), of each LIVE row its carry READ
  AND WRITTEN once in float32 in each delta layer (the program's
  ``kda.state_bytes``), its convolution tails read and written, and its
  latent rows up to its length in the one latent layer
  (``mla.context_tokens``); the embedding's one row a token and activations
  are not counted;
* what the program moves beyond that — the carry of slots that are not
  live, the un-donated state copied once a chunk — is its waste and is not
  counted.
"""

from __future__ import annotations

BF16, F32 = 2, 4  # bytes


def layer_ids(c: dict) -> list:
    return list(c.get("layer_ids") or range(c["num_hidden_layers"]))


def latent_layers(c: dict) -> int:
    return sum((i + 1) % c["layer_group_size"] == 0 for i in layer_ids(c))


def delta_layers(c: dict) -> int:
    return c["num_hidden_layers"] - latent_layers(c)


def dense_layers(c: dict) -> int:
    return sum(i < c["first_k_dense_replace"] for i in layer_ids(c))


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - dense_layers(c)


def key_width(c: dict) -> int:
    return c["num_attention_heads"] * c["head_dim"]


def conv_channels(c: dict) -> int:
    return 3 * key_width(c)


def delta_matrices(c: dict) -> int:
    """A delta layer's projections: ``[q | k | v]``, the decay's full-rank
    ``W_f``, the two gates a head, the output."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    return (h * conv_channels(c) + h * key_width(c) + 2 * h * heads
            + key_width(c) * h)


def delta_small(c: dict) -> int:
    """Its convolution, ``A_log``, ``dt_bias`` and the norm's weight."""
    return (conv_channels(c) * c["short_conv_kernel_size"]
            + c["num_attention_heads"] + key_width(c) + c["head_dim"])


def latent_matrices(c: dict) -> int:
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * heads * qk + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + h * heads + heads * c["v_head_dim"] * h)


def dense_ffn(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_layer_outside(c: dict) -> int:
    """What every token passes in an expert layer: the router and the
    shared expert (the selection bias is not a matrix)."""
    h = c["hidden_size"]
    return (h * c["num_experts"]
            + 3 * h * c["moe_shared_expert_intermediate_size"])


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded."""
    return (delta_layers(c) * delta_matrices(c)
            + latent_layers(c) * latent_matrices(c)
            + dense_layers(c) * dense_ffn(c)
            + expert_layers(c) * expert_layer_outside(c))


def total_params(c: dict) -> int:
    """Every parameter the chip holds, as ``init_params`` makes them: the
    matrices, the embedding and the untied head, and the small ones (norms,
    the convolutions, ``A_log``, ``dt_bias``, the routers' biases)."""
    h, layers = c["hidden_size"], c["num_hidden_layers"]
    held = c.get("experts_held", c["num_experts"])
    small = (delta_layers(c) * delta_small(c)
             + latent_layers(c) * c["kv_lora_rank"]
             + expert_layers(c) * c["num_experts"] + layers * 2 * h + h)
    return (params_outside_experts(c)
            + expert_layers(c) * held * expert_params(c)
            + 2 * c["vocab_size"] * h + small)


def carry_bytes_per_row(c: dict) -> int:
    """One slot's carry in one delta layer, float32."""
    return c["num_attention_heads"] * c["head_dim"] ** 2 * F32


def tail_bytes_per_row(c: dict) -> int:
    return (c["short_conv_kernel_size"] - 1) * conv_channels(c) * BF16


def latent_bytes_per_row(c: dict) -> int:
    """One token's row in the latent layer's cache."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16


def slot_bytes(c: dict, max_len: int) -> int:
    """One slot's state in an engine of ``max_len``."""
    return (delta_layers(c) * (carry_bytes_per_row(c) + tail_bytes_per_row(c))
            + latent_layers(c) * max_len * latent_bytes_per_row(c))


def chunk_pairs(n: int, chunk: int) -> float:
    """Pairs ``j <= i`` inside the chunks of a row of ``n`` tokens."""
    whole, rest = divmod(n, chunk)
    return whole * chunk * (chunk + 1) / 2 + rest * (rest + 1) / 2


def scan_flops(c: dict, n: int) -> float:
    """The chunked channel-decay rule's products over ``n`` real tokens of
    one row in one delta layer."""
    heads, d = c["num_attention_heads"], c["head_dim"]
    pairs = chunk_pairs(n, c.get("chunk", 64))
    scores = 2 * 2 * heads * d * pairs          # the two decayed products
    solve = 2 * heads * (d + d) * pairs         # forward substitution
    within = 2 * heads * d * pairs              # lower[..] V'
    carry = 3 * 2 * heads * d * d * n           # W S, Q S, K^T V'
    return scores + solve + within + carry


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    pair = 2 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    mixers = sum(
        delta_layers(c) * scan_flops(c, n)
        + latent_layers(c) * pair * n * (n + 1) / 2
        for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + mixers
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 state_bytes: float, context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and layers of held experts
    with an assignment, ``state_bytes`` the program's ``kda.state_bytes``
    (each live row's carry read and written once a delta layer a step),
    ``context_tokens`` the sum over steps of the live rows' lengths."""
    return {
        "delta_projections": steps * delta_layers(c) * delta_matrices(c)
        * BF16,
        "latent_attention": steps * latent_layers(c) * latent_matrices(c)
        * BF16,
        "dense_ffn": steps * dense_layers(c) * dense_ffn(c) * BF16,
        "expert_layers_outside": steps * expert_layers(c)
        * expert_layer_outside(c) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "carry": state_bytes,
        "conv_tails": state_bytes / carry_bytes_per_row(c)
        * tail_bytes_per_row(c),
        "latent_rows": context_tokens * latent_layers(c)
        * latent_bytes_per_row(c),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 state_bytes: float, context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched, state_bytes,
                                  context_tokens).values()))
