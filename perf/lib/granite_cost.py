"""Required operations and bytes of Granite 4.0-H as one chip runs it whole:
the LEAST a prefill and a decode step must do, from the configuration's
sizes, so that a share of a peak computed from them cannot read above
100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmaxes, norms, gates, the convolution's four taps, the step's
  ``softplus`` and ``exp`` and sampling are not counted;
* the prefill's recurrence counts the chunked form's FOUR products over a
  row's REAL tokens, cut into chunks of ``mamba_chunk_size``: ``C B^T``
  (once for all heads) and the in-chunk hand-over over the pairs ``j <= i``
  of a chunk (the causal half, not the square the program computes), the
  chunk's addition to the carry and the carry's hand-over to the chunk's
  tokens (``2 D N`` a head and token each);
* prefill attention counts the query-key pairs the causal mask allows,
  ``n (n + 1) / 2`` a row of ``n`` tokens, each ``2 * 2 * head_dim``
  operations a query head;
* padding up to the prefill bucket, whole chunks past a row's length and
  unused rows of an admission run are the program's waste and are not
  counted;
* a decode step must read every weight once (the mixers, the MLPs, the
  embedding as the tied head; norm scales, ``A``, ``dt_bias``, ``D`` and
  the convolution's weights not: 0.8 M of 3,191 M), and of each LIVE row
  its carry READ AND WRITTEN once in float32, its convolution tails read
  and written, and its keys and values up to its length in each attention
  layer (the program's ``ssm.step_rows`` / ``attn.context_tokens``);
  activations are not counted;
* what the program moves beyond that — the carry of slots that are not
  live, every row of every slot's keys under the XLA decode core, the
  un-donated state copied once a chunk — is its waste and is not counted.
"""

from __future__ import annotations

BF16, F32 = 2, 4  # bytes


def layers_of(c: dict, kind: str) -> int:
    return sum(1 for k in c["layer_types"] if k == kind)


def mamba_inner(c: dict) -> int:
    return c["mamba_n_heads"] * c["mamba_d_head"]


def conv_channels(c: dict) -> int:
    return mamba_inner(c) + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mamba_params(c: dict) -> int:
    """A state layer's two projections."""
    h, inner = c["hidden_size"], mamba_inner(c)
    return (h * (inner + conv_channels(c) + c["mamba_n_heads"])
            + inner * h)


def attention_params(c: dict) -> int:
    h = c["hidden_size"]
    d = h // c["num_attention_heads"]
    return 2 * h * h + 2 * h * c["num_key_value_heads"] * d


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["shared_intermediate_size"]


def layer_params(c: dict) -> int:
    """Every matrix a token passes, the head excluded."""
    return (layers_of(c, "mamba") * mamba_params(c)
            + layers_of(c, "attention") * attention_params(c)
            + c["num_hidden_layers"] * mlp_params(c))


def total_params(c: dict) -> int:
    """The matrices the chip holds; the embedding is the head too."""
    return layer_params(c) + c["vocab_size"] * c["hidden_size"]


def carry_bytes_per_row(c: dict) -> int:
    """One slot's carry in one state layer, float32."""
    return mamba_inner(c) * c["mamba_d_state"] * F32


def tail_bytes_per_row(c: dict) -> int:
    return (c["mamba_d_conv"] - 1) * conv_channels(c) * BF16


def kv_bytes_per_row(c: dict) -> int:
    """One token's key and value in one attention layer's cache."""
    d = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_key_value_heads"] * d * BF16


def chunk_pairs(n: int, chunk: int) -> float:
    """Pairs ``j <= i`` inside the chunks of a row of ``n`` tokens."""
    whole, rest = divmod(n, chunk)
    return whole * chunk * (chunk + 1) / 2 + rest * (rest + 1) / 2


def scan_flops(c: dict, n: int) -> float:
    """The chunked recurrence's four products over ``n`` real tokens of one
    row in one state layer."""
    heads, d, state = (c["mamba_n_heads"], c["mamba_d_head"],
                       c["mamba_d_state"])
    pairs = chunk_pairs(n, c["mamba_chunk_size"])
    return (2 * state * pairs + 2 * heads * d * pairs
            + 2 * 2 * heads * d * state * n)


def prefill_flops(c: dict, prime_lengths) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires."""
    tokens = float(sum(prime_lengths))
    d = c["hidden_size"] // c["num_attention_heads"]
    pair = 2 * 2 * c["num_attention_heads"] * d
    mixers = sum(
        layers_of(c, "mamba") * scan_flops(c, n)
        + layers_of(c, "attention") * pair * n * (n + 1) / 2
        for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return tokens * 2 * layer_params(c) + mixers + head


def decode_terms(c: dict, steps: float, state_rows: float,
                 context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``state_rows`` is the sum over steps of live rows times state layers
    (``ssm.step_rows``), ``context_tokens`` the sum over steps of the live
    rows' lengths."""
    return {
        "mamba_projections": steps * layers_of(c, "mamba") * mamba_params(c)
        * BF16,
        "attention": steps * layers_of(c, "attention") * attention_params(c)
        * BF16,
        "mlps": steps * c["num_hidden_layers"] * mlp_params(c) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "carry": state_rows * 2 * carry_bytes_per_row(c),
        "conv_tails": state_rows * 2 * tail_bytes_per_row(c),
        "grown_rows": context_tokens * layers_of(c, "attention")
        * kv_bytes_per_row(c),
    }


def decode_bytes(c: dict, steps: float, state_rows: float,
                 context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, state_rows,
                                  context_tokens).values()))
