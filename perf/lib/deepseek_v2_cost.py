"""Required bytes of DeepSeek-V2's decode step as one chip's share runs it:
the LEAST a step must move, from the configuration's sizes, so that a share
of the chip's bandwidth computed from them cannot read above 100 %.

What is counted and what is not:

* a decode step must read every weight outside the routed experts once —
  attention in every layer, the leading dense layer, the shared experts and
  the router of every expert layer, the head (the embedding not: it is a
  gather of a few rows; norm scales not: 10 thousand a layer beside 149
  million) —, the three matrices of each routed expert it TOUCHES (from the
  program's counter: experts with an assignment, summed over steps and
  expert layers), and the latent cache of the live rows at their lengths
  once per layer; activations are not counted;
* what the program reads beyond that — the whole ``max_len`` rows of every
  slot's cache, twice; the un-donated state copied once a chunk — is its
  waste and is not counted.

No kernel is new with this configuration, so there are no operation counts
here; the parameter counts are those of ISSUE 32's arithmetic.
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
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    return (3 * c["hidden_size"] * c["n_shared_experts"]
            * c["moe_intermediate_size"])


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["n_routed_experts"]


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def total_params(c: dict) -> int:
    """Everything the chip holds, embedding and head included."""
    return (c["num_hidden_layers"] * attention_params(c)
            + c["first_k_dense_replace"] * dense_ffn_params(c)
            + expert_layers(c) * (shared_params(c) + router_params(c)
                                  + c["experts_held"] * expert_params(c))
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_token(c: dict) -> int:
    """Cache bytes a token occupies over all layers."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16)


def decode_terms(c: dict, steps: float, experts_touched: float,
                 context_tokens: float) -> dict:
    """Bytes ``steps`` decode steps must move, by what they are:
    ``experts_touched`` is the sum over steps and expert layers of held
    experts with an assignment, ``context_tokens`` the sum over steps of
    the live rows' lengths."""
    return {
        "attention": steps * c["num_hidden_layers"] * attention_params(c)
        * BF16,
        "dense_layer": steps * c["first_k_dense_replace"]
        * dense_ffn_params(c) * BF16,
        "shared_and_router": steps * expert_layers(c)
        * (shared_params(c) + router_params(c)) * BF16,
        "head": steps * c["hidden_size"] * c["vocab_size"] * BF16,
        "routed_experts_touched": experts_touched * expert_params(c) * BF16,
        "latent_cache": context_tokens * latent_bytes_per_token(c),
    }


def decode_bytes(c: dict, steps: float, experts_touched: float,
                 context_tokens: float) -> float:
    return float(sum(decode_terms(c, steps, experts_touched,
                                  context_tokens).values()))
