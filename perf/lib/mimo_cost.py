"""Required operations and bytes of MiMo-V2 as one chip's share runs it: the
LEAST a prefill and a decode step must do, from the configuration's sizes,
so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, sinks, norms, rotations, the value scale, the router's top-k and
  sampling are not counted;
* prefill attention counts the query-key pairs the MASK allows, each ``2 *
  (d + dv)`` operations a query head (a score over ``d`` = 192 columns, a
  value product over ``dv`` = 128): a full layer the causal half, ``n (n +
  1) / 2`` a row of ``n`` tokens; a sliding layer ``sum_i min(i + 1,
  window)``.  What the blocked XLA form computes beyond the mask (a block of
  256 rows against 384 keys under a window of 128; whole blocks above the
  diagonal's; the padding up to the bucket) is the program's waste;
* the experts count the assignments to HELD experts that the program's
  counter saw;
* a decode step must read every weight outside the experts once —
  attention in every layer at ITS KIND's shapes, the dense layer, the router
  of every expert layer, the head (the embedding not: it is a gather of a
  few rows; norm scales and sinks not) —, the three matrices of each expert
  it TOUCHES (the program's counter), and of each live row its keys and
  values at EACH KIND'S OWN ROW BYTES: ``min(length, window)`` rows of every
  sliding layer's ring at ``8 x (192 + 128) x 2`` B and ``length`` rows of
  every full layer's cache at ``4 x (192 + 128) x 2`` B (the program's
  ``attn.window_tokens`` / ``attn.context_tokens``); activations are not
  counted;
* what the program reads beyond that — every row of every slot's caches
  under the XLA decode core (``attn.*_bytes_read``), the un-donated state
  copied once a chunk — is its waste and is not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes
FULL, SLIDING = 0, 1


def heads_of(c: dict, kind: int) -> tuple:
    """``(H, KV, d, dv)`` of an attention kind."""
    if kind == SLIDING:
        return (c["swa_num_attention_heads"], c["swa_num_key_value_heads"],
                c["swa_head_dim"], c["swa_v_head_dim"])
    return (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["v_head_dim"])


def attention_params(c: dict, kind: int) -> int:
    """One attention block's matrices: q, k, v, the output."""
    h = c["hidden_size"]
    heads, kv, d, dv = heads_of(c, kind)
    return h * heads * d + h * kv * d + h * kv * dv + heads * dv * h


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["n_routed_experts"]


def layers_of(c: dict, kind: int) -> int:
    return sum(1 for k in c["hybrid_layer_pattern"] if k == kind)


def expert_layers(c: dict) -> int:
    return sum(1 for f in c["moe_layer_freq"] if f)


def dense_layers(c: dict) -> int:
    return len(c["moe_layer_freq"]) - expert_layers(c)


def attention_params_all(c: dict) -> int:
    return sum(layers_of(c, kind) * attention_params(c, kind)
               for kind in (FULL, SLIDING))


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded."""
    return (attention_params_all(c) + dense_layers(c) * dense_ffn_params(c)
            + expert_layers(c) * router_params(c))


def total_params(c: dict) -> int:
    """The matrices the chip holds, embedding and head included (norm
    scales, sinks and the routers' biases left out: 59 thousand beside
    3,430 million)."""
    return (params_outside_experts(c)
            + expert_layers(c) * c["experts_held"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def kv_bytes_per_row(c: dict, kind: int) -> int:
    """One token's key and value in one block's cache of that kind."""
    _, kv, d, dv = heads_of(c, kind)
    return kv * (d + dv) * BF16


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
    pairs = 0.0
    for kind, window in ((FULL, None), (SLIDING, c["sliding_window"])):
        heads, _, d, dv = heads_of(c, kind)
        pairs += 2 * (d + dv) * heads * layers_of(c, kind) * sum(
            attention_pairs(n, window) for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + pairs
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 window_tokens: float, context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``context_tokens`` the sum over steps of
    the live rows' lengths and ``window_tokens`` that of ``min(length,
    window)``."""
    return {
        "attention": steps * attention_params_all(c) * BF16,
        "dense_layer": steps * dense_layers(c) * dense_ffn_params(c) * BF16,
        "routers": steps * expert_layers(c) * router_params(c) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "ring_rows": window_tokens * layers_of(c, SLIDING)
        * kv_bytes_per_row(c, SLIDING),
        "grown_rows": context_tokens * layers_of(c, FULL)
        * kv_bytes_per_row(c, FULL),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 window_tokens: float, context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched, window_tokens,
                                  context_tokens).values()))
