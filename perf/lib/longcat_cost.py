"""Required operations and bytes of LongCat-Flash as one chip's share runs
it: the LEAST a prefill and a decode step must do, from the configuration's
sizes, so that a share of a peak computed from them cannot read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, norms, rotations, the router's top-k and sampling are not
  counted;
* prefill attention is the causal half in the NON-absorbed form (the
  cheaper one when every position is a query): ``n (n + 1) / 2`` query-key
  pairs a row, each ``2 (192 + 128)`` operations a head;
* padding up to the prefill bucket and unused rows of an admission run are
  the program's waste and are not counted;
* the experts count the assignments to HELD experts that the program's
  counter saw (compute per token varies); identity experts cost nothing;
* a decode step must read every weight outside the experts once (the head
  included, the embedding not: it is a gather of a few rows), the three
  matrices of each expert it TOUCHES, and the latent cache of the live rows
  at their lengths once per attention block; activations are not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes


def attention_params(c: dict) -> int:
    """One latent-attention block's matrices."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + heads * c["v_head_dim"] * h)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * (c["n_routed_experts"] + c["zero_expert_num"])


def layer_params_outside_experts(c: dict) -> int:
    """Two attention blocks, two dense FFNs and the router (norm scales
    left out: 30 thousand beside 639 million)."""
    return (2 * attention_params(c) + 2 * dense_ffn_params(c)
            + router_params(c))


def total_params(c: dict) -> int:
    """Everything the chip holds, embedding and head included."""
    return (c["num_layers"] * (layer_params_outside_experts(c)
                               + c["experts_held"] * expert_params(c))
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_token(c: dict) -> int:
    """Cache bytes a token occupies over all attention blocks."""
    return (2 * c["num_layers"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            * BF16)


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of rows of ``prime_lengths`` real tokens
    requires, with ``held_assignments`` (token, held expert) pairs in all
    layers together."""
    tokens = float(sum(prime_lengths))
    per_token = 2 * c["num_layers"] * layer_params_outside_experts(c)
    heads = c["num_attention_heads"]
    pair = 2 * heads * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                        + c["v_head_dim"])
    pairs = sum(n * (n + 1) / 2 for n in prime_lengths)
    head = 2 * c["hidden_size"] * c["vocab_size"] * len(prime_lengths)
    return (tokens * per_token + 2 * c["num_layers"] * pair * pairs
            + 2 * expert_params(c) * held_assignments + head)


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 context_tokens: float) -> float:
    """Bytes ``steps`` decode steps must move: ``experts_touched`` is the
    sum over steps and layers of held experts with an assignment,
    ``context_tokens`` the sum over steps of the live rows' lengths."""
    weights = (c["num_layers"] * layer_params_outside_experts(c)
               + c["hidden_size"] * c["vocab_size"]) * BF16
    return (steps * weights + experts_touched * expert_params(c) * BF16
            + context_tokens * latent_bytes_per_token(c))
