"""Required operations and bytes of dots3 as one chip's share runs it: the
LEAST a prefill and a decode step must do, from the configuration's sizes,
so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, gates' sigmoids, norms, rotations, the routers' and the indexer's
  top-k and sampling are not counted;
* prefill attention counts the query-key pairs the equations ATTEND, each
  ``2 * (nope + rope + v)`` operations a query head in the expanded form: a
  full layer ``sum_t min(t + 1, index_topk)`` a row (the selected keys, not
  the causal half), a sliding layer ``sum_t min(t + 1, window)``; and the
  indexer's score of EVERY visible key, ``n (n + 1) / 2`` pairs a row at ``2
  * index_n_heads * index_head_dim`` each (the selection has to score what
  it drops).  What the XLA forms compute beyond that (every pair under a
  segment's span, selected or not; a block of 256 rows against 769 keys
  under the window; the padding up to the bucket) is the program's waste;
* the experts count the assignments to HELD experts that the program's
  counter saw, and the shared expert every token;
* a decode step must read every weight outside the routed experts once —
  attention in every layer at ITS KIND's shapes (the indexer's and the
  gate's matrices among them), the dense layer, the router and the shared
  expert of every expert layer, the head (the embedding not: it is a gather
  of a few rows; norm scales not) —, the three matrices of each routed
  expert it TOUCHES (the program's counter), and of each live row: the
  indexer key of every token it could see (``dsa.context_tokens``: the
  score has to read them all) in every full layer, the latent rows the
  selection keeps (``dsa.keys_selected``) in every full layer, and
  ``min(length, window)`` ring rows (``mla.window_tokens``) in every sliding
  layer, each at its own row bytes; activations are not counted;
* what the program reads beyond that — the indexer rows of every slot up to
  ``max_len`` under the XLA score (``dsa.index_bytes_read``), the gathered
  copy of the selected rows, the un-donated state copied once a chunk — is
  its waste and is not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes
FULL, SLIDING = "full_attention", "sliding_attention"


def sizes_of(c: dict, kind: str) -> tuple:
    """``(H, q_lora, kv_lora, nope, rope, v)`` of an attention kind."""
    if kind == SLIDING:
        return (c["swa_num_attention_heads"], c["swa_q_lora_rank"],
                c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
                c["swa_qk_rope_head_dim"], c["swa_v_head_dim"])
    return (c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def indexer_params(c: dict) -> int:
    d = c["index_head_dim"]
    return (c["q_lora_rank"] * c["index_n_heads"] * d
            + c["hidden_size"] * d + c["hidden_size"] * c["index_n_heads"])


def attention_params(c: dict, kind: str) -> int:
    """One attention block's matrices: the two down-projections, the two
    up-projections, the output, the gate, and a full block's indexer."""
    h = c["hidden_size"]
    heads, q_lora, kv_lora, nope, rope, v = sizes_of(c, kind)
    own = (h * q_lora + q_lora * heads * (nope + rope) + h * (kv_lora + rope)
           + kv_lora * heads * (nope + v) + heads * v * h + h * heads)
    return own + (indexer_params(c) if kind == FULL else 0)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["n_routed_experts"]


def layers_of(c: dict, kind: str) -> int:
    return sum(1 for k in c["layer_types"] if k == kind)


def dense_layers(c: dict) -> int:
    return min(c["first_k_dense_replace"], c["num_hidden_layers"])


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - dense_layers(c)


def attention_params_all(c: dict) -> int:
    return sum(layers_of(c, kind) * attention_params(c, kind)
               for kind in (FULL, SLIDING))


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded: the
    shared expert among them."""
    return (attention_params_all(c) + dense_layers(c) * dense_ffn_params(c)
            + expert_layers(c) * (router_params(c) + c["n_shared_experts"]
                                  * expert_params(c)))


def total_params(c: dict) -> int:
    """The matrices the chip holds, embedding and head included (norm
    scales, the indexer's LayerNorm and the routers' biases left out: 67
    thousand beside 4,087 million)."""
    return (params_outside_experts(c)
            + expert_layers(c) * c["experts_held"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_row(c: dict, kind: str) -> int:
    """One token's latent row in one block's cache of that kind."""
    _, _, kv_lora, _, rope, _ = sizes_of(c, kind)
    return (kv_lora + rope) * BF16


def index_bytes_per_row(c: dict) -> int:
    return c["index_head_dim"] * BF16


def kept_pairs(n: int, keep: int) -> float:
    """``sum_{t < n} min(t + 1, keep)``: the pairs a row of ``n`` tokens
    attends when a query keeps at most ``keep`` keys."""
    if n <= keep:
        return n * (n + 1) / 2
    return keep * (keep + 1) / 2 + (n - keep) * keep


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    pairs = 0.0
    for kind, keep in ((FULL, c["index_topk"]),
                       (SLIDING, c["sliding_window_size"])):
        heads, _, _, nope, rope, v = sizes_of(c, kind)
        pairs += 2 * (nope + rope + v) * heads * layers_of(c, kind) * sum(
            kept_pairs(n, keep) for n in prime_lengths)
    scored = 2 * c["index_n_heads"] * c["index_head_dim"] * layers_of(
        c, FULL) * sum(n * (n + 1) / 2 for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * 2 * params_outside_experts(c) + pairs + scored
            + 2 * expert_params(c) * held_assignments + head)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 window_tokens: float, context_tokens: float,
                 keys_selected: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``context_tokens`` the sum over steps of the
    live rows' lengths, ``keys_selected`` that of ``min(length,
    index_topk)`` and ``window_tokens`` that of ``min(length, window)``."""
    return {
        "attention": steps * attention_params_all(c) * BF16,
        "dense_layer": steps * dense_layers(c) * dense_ffn_params(c) * BF16,
        "routers_and_shared": steps * expert_layers(c) * (
            router_params(c) + c["n_shared_experts"] * expert_params(c))
        * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "index_rows": context_tokens * layers_of(c, FULL)
        * index_bytes_per_row(c),
        "selected_rows": keys_selected * layers_of(c, FULL)
        * latent_bytes_per_row(c, FULL),
        "ring_rows": window_tokens * layers_of(c, SLIDING)
        * latent_bytes_per_row(c, SLIDING),
    }


def decode_bytes(c: dict, *counts: float) -> float:
    return float(sum(decode_terms(c, *counts).values()))
