"""Required operations and bytes of LFM2 as one pipeline stage runs it: the
LEAST a prefill and a decode step must do, from the configuration's sizes,
so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, norms, rotations, the two gates and the three taps of a short
  convolution (elementwise, 6 operations a channel), the router's top-k and
  sampling are not counted;
* prefill attention counts the query-key pairs the causal MASK allows, ``n
  (n + 1) / 2`` a row of ``n`` tokens, each ``2 * 2 * head_dim`` operations
  a query head (scores and values) at the PUBLISHED head width of 64 — the
  zeros of the program's two-heads-wide cache rows are its own;
* padding up to the prefill bucket and unused rows of an admission run are
  the program's waste and are not counted;
* the experts count the assignments to HELD experts that the program's
  counter saw (4 a token an expert layer with every expert held);
* a decode step must read every weight outside the experts once — the
  short convolutions' and the attention blocks' matrices, the leading dense
  layers, the router of every expert layer, and the head, which is the
  embedding read whole (norm scales and the taps not) —, the three matrices
  of each expert it TOUCHES (the program's counter), of each live row its
  keys and values, ``length`` rows of every attention layer's cache (the
  program's ``attn.context_tokens``), and of each live row and short
  convolution (the program's ``conv.tokens``) its tail: ``conv_L_cache -
  1`` rows read and one row written; activations are not counted;
* what the program reads beyond that — every row of every slot's keys under
  the XLA decode core, the un-donated state copied once a chunk — is its
  waste and is not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def conv_params(c: dict) -> int:
    """One short convolution's matrices: ``h -> 3 h`` in, ``h -> h`` out."""
    return 4 * c["hidden_size"] ** 2


def attention_params(c: dict) -> int:
    """One attention block's matrices: q, k, v, the output."""
    h = c["hidden_size"]
    kv = c["num_key_value_heads"] * head_dim(c)
    return 2 * h * h + 2 * h * kv


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def layers_of(c: dict, kind: str) -> int:
    return sum(1 for k in c["layer_types"] if k == kind)


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded."""
    return (layers_of(c, "conv") * conv_params(c)
            + layers_of(c, "full_attention") * attention_params(c)
            + c["num_dense_layers"] * dense_ffn_params(c)
            + expert_layers(c) * router_params(c))


def total_params(c: dict) -> int:
    """The matrices the chip holds, the tied embedding once (norm scales,
    taps and the router's bias left out: 0.1 million beside 3,929)."""
    return (params_outside_experts(c)
            + expert_layers(c) * c.get("experts_held", c["num_experts"])
            * expert_params(c)
            + c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_row(c: dict) -> int:
    """One token's key and value in one attention layer's cache."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * BF16


def tail_bytes_per_row(c: dict) -> int:
    """One row of one short convolution's tail."""
    return c["hidden_size"] * BF16


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    pair = 2 * 2 * c["num_attention_heads"] * head_dim(c)
    pairs = layers_of(c, "full_attention") * sum(
        n * (n + 1) / 2 for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + pair * pairs
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 context_tokens: float, conv_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``context_tokens`` the sum over steps of
    the live rows' lengths, ``conv_tokens`` the sum over steps and short
    convolutions of live rows (each reads the tail, ``conv_L_cache - 1``
    rows, and writes one row of it)."""
    return {
        "short_convolutions": steps * layers_of(c, "conv") * conv_params(c)
        * BF16,
        "attention": steps * layers_of(c, "full_attention")
        * attention_params(c) * BF16,
        "dense_layers": steps * c["num_dense_layers"] * dense_ffn_params(c)
        * BF16,
        "routers": steps * expert_layers(c) * router_params(c) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "grown_rows": context_tokens * layers_of(c, "full_attention")
        * kv_bytes_per_row(c),
        "tails": conv_tokens * c["conv_L_cache"] * tail_bytes_per_row(c),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 context_tokens: float, conv_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched, context_tokens,
                                  conv_tokens).values()))
