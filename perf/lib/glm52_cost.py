"""Required operations and bytes of GLM-5.2 as one chip's share runs it: the
LEAST a prefill and a decode step must do, from the configuration's sizes,
so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, sigmoids, norms, rotations, the routers' and the indexers' top-k
  and sampling are not counted;
* prefill attention counts the query-key pairs the equations ATTEND, each
  ``2 * (nope + rope + v)`` operations a query head in the expanded form, in
  EVERY layer (all attend under a selection): ``sum_t min(t + 1,
  index_topk)`` a row — the selected keys, not the causal half —; and the
  indexer's score of EVERY visible key, ``n (n + 1) / 2`` pairs a row at ``2
  * index_n_heads * index_head_dim`` each, in the FULL layers alone (the
  selection has to score what it drops; a shared layer scores nothing).
  What the lowerings compute beyond that (every pair of a visited tile,
  selected or not; the padding up to the bucket) is the program's waste;
* the experts count the assignments to HELD experts that the program's
  counter saw, and the shared expert every token;
* a decode step must read every weight outside the routed experts once —
  attention in every layer (an indexer's matrices in the full layers alone),
  the dense layers, the router and the shared expert of every expert layer,
  the head (the embedding not: it is a gather of a few rows; norm scales
  not) —, the three matrices of each routed expert it TOUCHES (the
  program's counter), and of each live row: the indexer key of every token
  it could see in every FULL layer (the score has to read them all), and
  the latent rows the selection keeps in EVERY layer (``dsa.keys_selected``,
  which the program sums over the layers), each at its own row bytes;
  activations are not counted;
* what the program reads beyond that — the indexer rows of every slot up to
  ``max_len`` under the XLA score (``dsa.index_bytes_read``), the gathered
  copy of the selected rows a layer, the un-donated state copied once a
  chunk — is its waste and is not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes
FULL, SHARED = "full", "shared"


def indexer_params(c: dict) -> int:
    d = c["index_head_dim"]
    return (c["q_lora_rank"] * c["index_n_heads"] * d
            + c["hidden_size"] * d + c["hidden_size"] * c["index_n_heads"])


def attention_params(c: dict) -> int:
    """One attention block's matrices without an indexer: the two
    down-projections, the two up-projections, the output."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * (nope + rope)
            + h * (c["kv_lora_rank"] + rope)
            + c["kv_lora_rank"] * heads * (nope + v) + heads * v * h)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["n_routed_experts"]


def full_layers(c: dict) -> int:
    return sum(1 for k in c["indexer_types"] if k == FULL)


def dense_layers(c: dict) -> int:
    return sum(1 for k in c["mlp_layer_types"] if k == "dense")


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - dense_layers(c)


def attention_params_all(c: dict) -> int:
    return (c["num_hidden_layers"] * attention_params(c)
            + full_layers(c) * indexer_params(c))


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded: the
    shared expert among them."""
    return (attention_params_all(c) + dense_layers(c) * dense_ffn_params(c)
            + expert_layers(c) * (router_params(c) + c["n_shared_experts"]
                                  * expert_params(c)))


def total_params(c: dict) -> int:
    """The matrices the chip holds, embedding and head included (norm
    scales, the indexers' LayerNorm and the routers' biases left out: 68
    thousand beside 3,881 million)."""
    return (params_outside_experts(c)
            + expert_layers(c) * c["experts_held"] * expert_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_row(c: dict) -> int:
    """One token's latent row in one block's cache."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16


def index_bytes_per_row(c: dict) -> int:
    return c["index_head_dim"] * BF16


def kept_pairs(n: int, keep: int) -> float:
    """``sum_{t < n} min(t + 1, keep)``: the pairs a row of ``n`` tokens
    attends when a query keeps at most ``keep`` keys."""
    if n <= keep:
        return n * (n + 1) / 2
    return keep * (keep + 1) / 2 + (n - keep) * keep


def prefill_terms(c: dict, prime_lengths, held_assignments: float) -> dict:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, by what they are, with ``held_assignments`` (token, held
    expert) pairs in all layers together."""
    tokens = float(sum(prime_lengths))
    per_pair = 2 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                    + c["v_head_dim"]) * c["num_attention_heads"]
    return {
        "matrices": tokens * 2 * params_outside_experts(c),
        "attended_pairs": per_pair * c["num_hidden_layers"] * sum(
            kept_pairs(n, c["index_topk"]) for n in prime_lengths),
        "scored_pairs": 2 * c["index_n_heads"] * c["index_head_dim"]
        * full_layers(c) * sum(n * (n + 1) / 2 for n in prime_lengths),
        "experts": 2 * expert_params(c) * held_assignments,
        "head": 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths),
    }


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    return float(sum(prefill_terms(c, prime_lengths,
                                   held_assignments).values()))


def decode_terms(c: dict, steps: float, experts_touched: float,
                 context_tokens: float, keys_selected: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``context_tokens`` the sum over steps of the
    live rows' lengths (``mla.context_tokens``: once, not a layer),
    ``keys_selected`` that of ``min(length, index_topk)`` summed over EVERY
    layer (``dsa.keys_selected``)."""
    return {
        "attention": steps * attention_params_all(c) * BF16,
        "dense_layers": steps * dense_layers(c) * dense_ffn_params(c) * BF16,
        "routers_and_shared": steps * expert_layers(c) * (
            router_params(c) + c["n_shared_experts"] * expert_params(c))
        * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "index_rows": context_tokens * full_layers(c)
        * index_bytes_per_row(c),
        "selected_rows": keys_selected * latent_bytes_per_row(c),
    }


def decode_bytes(c: dict, *counts: float) -> float:
    return float(sum(decode_terms(c, *counts).values()))
