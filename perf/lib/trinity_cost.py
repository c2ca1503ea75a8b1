"""Required operations and bytes of Trinity as one chip's share runs it: the
LEAST a prefill and a decode step must do, from the configuration's sizes,
so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, norms, rotations, gates, the router's top-k and sampling are not
  counted;
* prefill attention counts the query-key pairs the MASK allows, each ``2 *
  2 * head_dim`` operations a query head (scores and values): a full layer
  the causal half, ``n (n + 1) / 2`` a row of ``n`` tokens; a sliding layer
  ``sum_i min(i + 1, window)``, which is the same up to ``window`` tokens
  and ``window`` a token after;
* padding up to the prefill bucket and unused rows of an admission run are
  the program's waste and are not counted;
* the experts count the assignments to HELD experts that the program's
  counter saw;
* a decode step must read every weight outside the routed experts once —
  attention in every layer, the leading dense layer, the shared expert and
  the router of every expert layer, the head (the embedding not: it is a
  gather of a few rows; norm scales not) —, the three matrices of each
  routed expert it TOUCHES (the program's counter), and of each live row
  its keys and values: ``min(length, window)`` rows of every sliding
  layer's ring and ``length`` rows of every full layer's cache (the
  program's ``attn.window_tokens`` / ``attn.context_tokens``); activations
  are not counted;
* what the program reads beyond that — every row of every slot's caches
  under the XLA decode core, the un-donated state copied once a chunk — is
  its waste and is not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes


def attention_params(c: dict) -> int:
    """One attention block's matrices: q, k, v, the gate, the output."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 3 * h * q + 2 * h * kv


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    return (3 * c["hidden_size"] * c["num_shared_experts"]
            * c["moe_intermediate_size"])


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
    return (c["num_hidden_layers"] * attention_params(c)
            + c["num_dense_layers"] * dense_ffn_params(c)
            + expert_layers(c) * (shared_params(c) + router_params(c)))


def total_params(c: dict) -> int:
    """The matrices the chip holds, embedding and head included (norm
    scales and the router's bias left out: 76 thousand beside 1,243
    million)."""
    return (params_outside_experts(c)
            + expert_layers(c) * c["experts_held"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_row(c: dict) -> int:
    """One token's key and value in one block's cache."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def attention_pairs(n: int, window: int | None) -> float:
    """Query-key pairs the mask allows in a row of ``n`` tokens."""
    if window is None or n <= window:
        return n * (n + 1) / 2
    return window * (window + 1) / 2 + (n - window) * window


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    pair = 2 * 2 * c["num_attention_heads"] * c["head_dim"]
    pairs = sum(
        layers_of(c, "full_attention") * attention_pairs(n, None)
        + layers_of(c, "sliding_attention") * attention_pairs(
            n, c["sliding_window"]) for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + pair * pairs
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 window_tokens: float, context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``context_tokens`` the sum over steps of
    the live rows' lengths and ``window_tokens`` that of ``min(length,
    window)``."""
    return {
        "attention": steps * c["num_hidden_layers"] * attention_params(c)
        * BF16,
        "dense_layer": steps * c["num_dense_layers"] * dense_ffn_params(c)
        * BF16,
        "shared_and_router": steps * expert_layers(c)
        * (shared_params(c) + router_params(c)) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "routed_experts_touched": experts_touched * expert_params(c) * BF16,
        "ring_rows": window_tokens * layers_of(c, "sliding_attention")
        * kv_bytes_per_row(c),
        "grown_rows": context_tokens * layers_of(c, "full_attention")
        * kv_bytes_per_row(c),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 window_tokens: float, context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched, window_tokens,
                                  context_tokens).values()))
