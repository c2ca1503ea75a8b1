"""Required operations and bytes of Nemotron-H as one chip of its
deployment runs its share: the LEAST a prefill and a decode step must do,
from the configuration's sizes, so that a share of a peak computed from them
cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmaxes, norms, gates, ``relu^2``, the convolution's four taps, the
  step's ``softplus`` and ``exp``, the router's top-k and sampling are not
  counted;
* the prefill's recurrence counts the chunked form's FOUR products over a
  row's REAL tokens, cut into chunks of ``chunk_size``: ``C B^T`` once a
  GROUP (``n_groups`` of them, not once a head) and the in-chunk hand-over
  over the pairs ``j <= i`` of a chunk (the causal half, not the square the
  program computes), the chunk's addition to the carry and the carry's
  hand-over to the chunk's tokens (``2 D N`` a head and token each);
* prefill attention counts the query-key pairs the causal mask allows,
  ``n (n + 1) / 2`` a row of ``n`` tokens, each ``2 * 2 * head_dim``
  operations a query head;
* the experts count the assignments to HELD experts that the program's
  counter saw (5.5 a token an expert layer at 128 of 512 held), two
  products each; the latent projections, the router and the shared expert
  every token;
* padding up to the prefill bucket, whole chunks past a row's length and
  unused rows of an admission run are the program's waste and are not
  counted;
* a decode step must read every weight outside the routed experts once
  (the mixers' projections, the attention block, each expert layer's
  router, latent projections and shared expert, the head; norm scales,
  ``A``, ``dt_bias``, ``D``, the router's bias and the convolution's
  weights not), the TWO matrices of each expert it TOUCHES (the program's
  counter: never all held), of each LIVE row its carry READ AND WRITTEN
  once in float32 in each state layer, its convolution tails read and
  written, and its keys and values up to its length in the attention layer
  (the program's ``ssm.step_rows`` / ``attn.context_tokens``); the
  embedding's one row a token and activations are not counted;
* what the program moves beyond that — the carry of slots that are not
  live, the un-donated state copied once a chunk — is its waste and is not
  counted.
"""

from __future__ import annotations

BF16, F32 = 2, 4  # bytes


def layers_of(c: dict, kind: str) -> int:
    return c["hybrid_override_pattern"].count(kind)


def mamba_inner(c: dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_channels(c: dict) -> int:
    return mamba_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def mamba_params(c: dict) -> int:
    """A state layer's two projections."""
    h, inner = c["hidden_size"], mamba_inner(c)
    return (h * (inner + conv_channels(c) + c["mamba_num_heads"])
            + inner * h)


def attention_params(c: dict) -> int:
    h = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 2 * h * q + 2 * h * kv


def expert_params(c: dict) -> int:
    """One routed expert's two matrices, in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def expert_layer_params_outside(c: dict) -> int:
    """What every token passes in an expert layer: the router, the two
    latent projections, the shared expert on the full width."""
    h = c["hidden_size"]
    return (h * c["n_routed_experts"] + 2 * h * c["moe_latent_size"]
            + 2 * h * c["moe_shared_expert_intermediate_size"])


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded."""
    return (layers_of(c, "M") * mamba_params(c)
            + layers_of(c, "*") * attention_params(c)
            + layers_of(c, "E") * expert_layer_params_outside(c))


def total_params(c: dict) -> int:
    """The matrices the chip holds: the embedding and the untied head."""
    return (params_outside_experts(c)
            + layers_of(c, "E") * c.get("experts_held", c["n_routed_experts"])
            * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def carry_bytes_per_row(c: dict) -> int:
    """One slot's carry in one state layer, float32."""
    return mamba_inner(c) * c["ssm_state_size"] * F32


def tail_bytes_per_row(c: dict) -> int:
    return (c["conv_kernel"] - 1) * conv_channels(c) * BF16


def kv_bytes_per_row(c: dict) -> int:
    """One token's key and value in one attention layer's cache."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def chunk_pairs(n: int, chunk: int) -> float:
    """Pairs ``j <= i`` inside the chunks of a row of ``n`` tokens."""
    whole, rest = divmod(n, chunk)
    return whole * chunk * (chunk + 1) / 2 + rest * (rest + 1) / 2


def scan_flops(c: dict, n: int) -> float:
    """The chunked recurrence's four products over ``n`` real tokens of one
    row in one state layer."""
    heads, d, state = (c["mamba_num_heads"], c["mamba_head_dim"],
                       c["ssm_state_size"])
    pairs = chunk_pairs(n, c["chunk_size"])
    return (2 * c["n_groups"] * state * pairs + 2 * heads * d * pairs
            + 2 * 2 * heads * d * state * n)


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    pair = 2 * 2 * c["num_attention_heads"] * c["head_dim"]
    mixers = sum(
        layers_of(c, "M") * scan_flops(c, n)
        + layers_of(c, "*") * pair * n * (n + 1) / 2
        for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + mixers
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 state_rows: float, context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``state_rows`` the sum over steps of live
    rows times state layers (``ssm.step_rows``), ``context_tokens`` the sum
    over steps of the live rows' lengths."""
    return {
        "mamba_projections": steps * layers_of(c, "M") * mamba_params(c)
        * BF16,
        "attention": steps * layers_of(c, "*") * attention_params(c) * BF16,
        "expert_layers_outside": steps * layers_of(c, "E")
        * expert_layer_params_outside(c) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "carry": state_rows * 2 * carry_bytes_per_row(c),
        "conv_tails": state_rows * 2 * tail_bytes_per_row(c),
        "grown_rows": context_tokens * layers_of(c, "*")
        * kv_bytes_per_row(c),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 state_rows: float, context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched, state_rows,
                                  context_tokens).values()))


def kernel_bytes(c: dict, expert_passes: float, calls: float,
                 rows: int) -> float:
    """Bytes the two-matrix decode kernel's ``calls`` calls must move:
    ``expert_passes`` experts' two matrices streamed once each, and a
    call's ``rows`` token rows read in the compute dtype and written in
    float32 (the routing weights, 4 B a row and listed expert, not)."""
    return (expert_passes * expert_params(c) * BF16
            + calls * rows * c["moe_latent_size"] * (BF16 + F32))
