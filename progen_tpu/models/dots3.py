"""dots3-note (``model_type`` ``dots3_note``: dots3-note-prev's language
model): a stack whose FULL layers run latent attention (MLA) over the
``index_topk`` cached tokens a learned INDEXER selects for each query, and
whose SLIDING layers run latent attention OF ANOTHER SHAPE over the last
``sliding_window_size`` tokens kept in a ring, both under a per-head sigmoid
gate; a leading dense layer, then expert layers with sigmoid top-k routing
under a selection bias beside one shared expert — as ONE CHIP'S SHARE of an
expert-parallel deployment.

What dots3 alone has: its config, the two latent SHAPES and which options of
``models/latent.py:LatentBlock`` each kind takes, the layer's wiring and the
seeded weights' layout.  The model driver and the engine's seam are
``models/driver.py``; latent attention, the indexer's projection, the ring
and the gate are ``models/latent.py`` (shared with LongCat and DeepSeek-V2,
which take none of the options); the indexer's score, the selection and the
sparse cores are ``ops/dsa.py``; the held experts' product, its counters and
the sigmoid router are ``models/experts.py``.

``x0 = E[token]``.  Layer ``l`` (pre-norm, ``N_*`` RMSNorms with a learned
scale, statistics in float32, eps ``rms_norm_eps`` 1e-5)::

    x   = x + Attn_l(N_in(x))
    out = x + FFN_l(N_post(x))

``logits = N_f(x_L) W_head`` (untied).  Every departure from these
equations is a bug or an entry of ``assumed`` in
``perf/configs/dots3-note-prev-ep8.json``.

**Attention**, kind from ``layer_types[l]`` (13 ``full_attention`` and 33
``sliding_attention`` of 46: layers 0, 1, 5, 9, ... full).  Both kinds are
``models/latent.py``'s MLA at their own sizes, with ``u = N_in(x)``: ``c_q =
RMSNorm(u W_qa)``, ``q = c_q W_qb * q_gain`` (``H`` heads of ``[nope |
rope]``), ``[c_kv | k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv) * kv_gain``,
``[k_nope | v] = c_kv W_kvb``; the rope parts rotated (half-split) at the
kind's base, unscaled; scores ``(nope + rope)^-1/2 q . k``;
``apply_mla_qkv_lora_rescale``: ``q_gain = sqrt(h / q_lora_rank)``,
``kv_gain = sqrt(h / kv_lora_rank)``, each kind with its own ranks; the cache
row of a token is ``[c_kv | rope(k_r)]``.  The per-head gate: ``o_h <- o_h *
sigmoid(u W_g)_h`` before ``W_o``.

* **full** (``q_lora_rank`` 1024, ``kv_lora_rank`` 512, 128 heads of [128 |
  64], values 128, base ``rope_theta`` 8e7): the indexer (``index_n_heads``
  64 of ``index_head_dim`` 128; ``latent.index_project``,
  ``ops/dsa.py``) scores every earlier token and the softmax runs over the
  ``index_topk`` 2,048 best only.  Cache: ``max_len`` latent rows of 576 and
  ``max_len`` indexer keys of 128, 1,408 B a token.
* **sliding** (``swa_q_lora_rank`` 1024, ``swa_kv_lora_rank`` 1024, 64 heads
  of [192 | 64], values 128, base ``swa_rope_theta`` 5e4): ``i - j <
  sliding_window_size`` 513, the token itself counted; no indexer.  Cache: a
  RING of 513 latent rows of 1,088, 1.1 MB a slot whatever the context.

**FFN**: layers below ``first_k_dense_replace`` (layer 0) a dense SwiGLU of
``intermediate_size``; the others the experts: router in float32, ``s =
sigmoid(u W_r)`` (-> 256); the 8 largest of ``s + b`` are chosen
(``noaux_tc``; ``n_group = topk_group = 1``); weights ``s_chosen / (sum
s_chosen + 1e-20)`` times ``routed_scaling_factor`` 1; expert ``e`` a SwiGLU
5120 -> 1536 -> 5120; plus ``n_shared_experts`` = 1 shared expert of the same
width on every token.

**The share.**  The router keeps its width and top-k whatever is held; the
layer adds the terms of the held experts (``first_expert .. first_expert +
experts_held - 1``) and leaves out the absent ones'; attention, the dense
layer and the shared expert are whole on every chip.

**On the chip** the full layers' decode core is the kernel
``mla_decode_fwd`` over the 2,048 gathered rows, the sliding layers' the XLA
form over the ring (513 rows are no multiple of the kernel's 128-row tile),
both admission cores XLA forms (``ops/dsa.py``'s masked blocks;
``ops/gqa.py``'s windowed blocks, whose kernel declines two widths):
``ServingEngine.status()`` reads ``"mla_decode": "pallas+xla"``,
``"gqa_prefill": "xla"``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, experts
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    mm,
    residual,
    rms_norm,
    stack_norm,
    swiglu,
)
from progen_tpu.models.experts import held_experts, kernel_counters
from progen_tpu.models.latent import LatentBlock
from progen_tpu.ops import dsa, mla_prefill
from progen_tpu.ops.mla_decode import rows_visited

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class LatentShape:
    """One kind's sizes under the names ``models/latent.py`` reads."""

    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    rescale: bool
    # the indexer's, where the kind has one
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_norm_eps: float = 1e-6

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_gain(self) -> float:
        return (math.sqrt(self.hidden_size / self.q_lora_rank)
                if self.rescale else 1.0)

    @property
    def kv_gain(self) -> float:
        return (math.sqrt(self.hidden_size / self.kv_lora_rank)
                if self.rescale else 1.0)

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    # FULL / SLIDING a layer; empty: the published pattern (layers 0 and 1
    # and every fourth from layer 5 full, the others sliding)
    layer_types: tuple = ()
    # the full kind's latent shape ...
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    # ... its indexer ...
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # ... and the sliding kind's
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    max_position_embeddings: int = 524288
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 256
    first_expert: int = 0
    # seeded weights (``init_params``): the router logits' spread a token and
    # the selection bias's (in units of a score: it moves some choices)
    router_logit_std: float = 1.0
    router_bias_std: float = 0.02
    # the engine pads primes to ``prefill_bucket * 2^k`` tokens
    prefill_bucket: int = 512

    # what the shared code reads under its own names
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def moe_topk(self) -> int:
        return self.num_experts_per_tok

    @property
    def router_width(self) -> int:
        return self.n_routed_experts

    @property
    def seq_len(self) -> int:
        return self.max_position_embeddings

    embed_gain = 1.0            # no multiplier on the embedding

    def shape_of(self, kind: str) -> LatentShape:
        common = {"hidden_size": self.hidden_size,
                  "rms_norm_eps": self.rms_norm_eps,
                  "rescale": self.apply_mla_qkv_lora_rescale}
        if kind == SLIDING:
            return LatentShape(
                num_attention_heads=self.swa_num_attention_heads,
                q_lora_rank=self.swa_q_lora_rank,
                kv_lora_rank=self.swa_kv_lora_rank,
                qk_nope_head_dim=self.swa_qk_nope_head_dim,
                qk_rope_head_dim=self.swa_qk_rope_head_dim,
                v_head_dim=self.swa_v_head_dim,
                rope_theta=self.swa_rope_theta, **common)
        return LatentShape(
            num_attention_heads=self.num_attention_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            index_n_heads=self.index_n_heads,
            index_head_dim=self.index_head_dim, index_topk=self.index_topk,
            index_norm_eps=self.index_norm_eps, **common)

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @classmethod
    def from_dict(cls, d) -> "Dots3Config":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        if "layer_types" in d:
            d["layer_types"] = tuple(d["layer_types"])
        return cls(**d)

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.layer_types:
            object.__setattr__(self, "layer_types", tuple(
                FULL if i < 2 or i % 4 == 1 else SLIDING for i in range(n)))
        if len(self.layer_types) != n or set(self.layer_types) - {
                FULL, SLIDING}:
            raise ValueError(
                f"layer_types must name {n} layers {FULL!r} or {SLIDING!r}: "
                f"{self.layer_types}")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")
        if (self.scoring_func != "sigmoid" or self.n_shared_experts != 1
                or {self.attention_gate_type, self.swa_attention_gate_type}
                != {"headwise"}):
            raise ValueError(
                "the router is sigmoid top-k beside one shared expert and "
                "both kinds of attention are gated a head: scoring_func "
                f"{self.scoring_func!r}, n_shared_experts "
                f"{self.n_shared_experts}, gates {self.attention_gate_type!r}"
                f" / {self.swa_attention_gate_type!r} are not supported")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                f"the indexer rotates its leading {self.qk_rope_head_dim} "
                f"columns of {self.index_head_dim}")


# ------------------------------------------------------------------ weights


def _init_attn(key, c: Dots3Config, dt, kind: str):
    """Scales chosen so that q, k and v have unit spread an entry — the
    rescale's gains divided out of ``W_qb`` / ``W_kvb`` — and with them the
    scaled attention scores and the indexer's scores."""
    s = c.shape_of(kind)
    h, heads = s.hidden_size, s.num_attention_heads
    qk = s.qk_nope_head_dim + s.qk_rope_head_dim
    ks = jax.random.split(key, 12)
    normal, norm = driver.normal, driver.init_norm
    p = {
        "wqa": normal(ks[0], (h, s.q_lora_rank), h ** -0.5, dt),
        "q_norm": norm(ks[1], (s.q_lora_rank,), dt),
        "wqb": normal(ks[2], (s.q_lora_rank, heads * qk),
                      s.q_lora_rank ** -0.5 / s.q_gain, dt),
        "wkva": normal(ks[3], (h, s.latent_width), h ** -0.5, dt),
        "kv_norm": norm(ks[4], (s.kv_lora_rank,), dt),
        "wkvb": normal(
            ks[5], (s.kv_lora_rank, heads * (s.qk_nope_head_dim
                                             + s.v_head_dim)),
            s.kv_lora_rank ** -0.5 / s.kv_gain, dt),
        "wo": normal(ks[6], (heads * s.v_head_dim, h),
                     (heads * s.v_head_dim) ** -0.5, dt),
        # gates spread around a half: a head left ungated doubles
        "wgate": normal(ks[7], (h, heads), h ** -0.5, dt),
    }
    if kind == FULL:
        d = s.index_head_dim
        p.update({
            "wiq": normal(ks[8], (s.q_lora_rank, s.index_n_heads * d),
                          s.q_lora_rank ** -0.5 / s.q_gain, dt),
            "wik": normal(ks[9], (h, d), h ** -0.5, dt),
            "ik_scale": norm(ks[10], (d,), dt),
            "ik_bias": normal(ks[10], (d,), 0.05, dt),
            "wiw": normal(ks[11], (h, s.index_n_heads), h ** -0.5, dt),
        })
    return p


def _init_layer(key, c: Dots3Config, dt, kind: str, dense: bool):
    ks = jax.random.split(key, 7)
    h = c.hidden_size
    layer = {"norm": driver.init_norm(ks[0], (2, h), dt),
             "attn": _init_attn(ks[1], c, dt, kind)}
    if dense:
        layer["ffn"] = driver.init_ffn(ks[2], h, c.intermediate_size, 1.0, dt)
        return layer
    # logits spread by ``router_logit_std`` per token, so choices differ
    # between tokens; the bias is a float32 buffer, as the release keeps it
    layer["router"] = {
        "w": driver.normal(ks[3], (h, c.n_routed_experts),
                           c.router_logit_std * h ** -0.5, dt),
        "bias": driver.normal(ks[4], (c.n_routed_experts,),
                              c.router_bias_std, F32)}
    layer["experts"] = driver.init_ffn(ks[5], h, c.moe_intermediate_size,
                                       1.0, dt, lead=(c.experts_held,))
    layer["shared"] = driver.init_ffn(
        ks[6], h, c.n_shared_experts * c.moe_intermediate_size, 1.0, dt)
    return layer


def init_params(config: Dots3Config, key, policy: Policy | None = None):
    policy = policy or bf16_policy()
    c = config
    made = {}

    def layer(k, i):
        kind = (c.layer_types[i], c.is_dense(i))
        if kind not in made:        # one program a kind of layer
            made[kind] = jax.jit(partial(
                _init_layer, c=c, dt=policy.param_dtype, kind=kind[0],
                dense=kind[1]))
        return made[kind](k)

    return driver.init_params(config, key, policy, layer)


# ---------------------------------------------------------------- attention


def blocks_of(c: Dots3Config) -> dict:
    """One block a layer by name, ONE instance a kind: the full kind with
    its indexer, the sliding kind in its ring, both gated."""
    kinds = {
        FULL: LatentBlock(c.shape_of(FULL), indexer=True, gate=True),
        SLIDING: LatentBlock(c.shape_of(SLIDING),
                             window=c.sliding_window_size, gate=True)}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.layer_types)}


# a decode step's counters (``attention_stats``) and an admission's
# (``prefill_attention_stats``); docs/OBSERVABILITY.md section 3
ATTN_STAT_KEYS = (
    "mla.decode_rows", "mla.context_tokens", "mla.cache_rows_read",
    "mla.window_tokens", "mla.window_rows_read", "dsa.context_tokens",
    "dsa.index_rows_read", "dsa.keys_selected", "dsa.prefill_pairs_scored",
    "dsa.prefill_pairs_attended", "dsa.prefill_pairs_selected")


def attention_stats(blocks: dict, dt, caches, pos, live) -> dict:
    """A decode step's counters, each the rows ONE block of its kind reads
    or keeps: ``mla.context_tokens`` / ``dsa.context_tokens`` the keys a live
    row could see, ``dsa.index_rows_read`` the indexer rows the step's score
    reads (every row of every slot: the XLA form), ``dsa.keys_selected`` the
    rows the selection keeps, ``mla.cache_rows_read`` the latent rows the
    SPARSE core reads (``ops/mla_decode.py:rows_visited`` over the gathered
    rows), ``mla.window_tokens`` / ``mla.window_rows_read`` the ring rows a
    live row has and the ring rows the sliding core reads."""
    seen = jnp.where(live, pos + 1, 0)
    any_live = jnp.any(live)
    out = driver.zero_scalars(ATTN_STAT_KEYS)
    out["mla.decode_rows"] = jnp.sum(live).astype(F32)
    out["mla.context_tokens"] = jnp.sum(seen).astype(F32)
    full = next((n for n, b in blocks.items() if b.indexer), None)
    if full is not None:
        latent, index = caches[full]["latent"], caches[full]["index"]
        k = min(blocks[full].config.index_topk, latent.shape[1])
        kept = jnp.minimum(pos + 1, k)
        out["dsa.context_tokens"] = out["mla.context_tokens"]
        out["dsa.index_rows_read"] = jnp.asarray(
            index.shape[0] * index.shape[1], F32) * any_live
        out["dsa.keys_selected"] = jnp.sum(
            jnp.where(live, kept, 0)).astype(F32)
        out["mla.cache_rows_read"] = rows_visited(
            dt, latent[:, :k], kept,
            blocks[full].config.kv_lora_rank) * any_live
    ring = next((n for n, b in blocks.items() if b.window), None)
    if ring is not None:
        have = jnp.minimum(pos + 1, caches[ring].shape[1])
        out["mla.window_tokens"] = jnp.sum(
            jnp.where(live, have, 0)).astype(F32)
        out["mla.window_rows_read"] = rows_visited(
            dt, caches[ring], have,
            blocks[ring].config.kv_lora_rank) * any_live
    return out


def prefill_attention_stats(blocks: dict, n: int, lengths, dt) -> dict:
    """An admission's ``dsa.prefill_pairs_*`` over rows of ``lengths (R,)``
    padded to ``n``, summed over the full layers: the (query, key) pairs the
    indexer scored, the pairs the sparse core computed a head under the
    lowering that runs (``ops/dsa.py:prefill_pairs``: the XLA form's whole
    segments, the kernel's visited tiles), and the pairs the selection
    allows at real positions, ``sum_t min(t + 1, index_topk)``."""
    scored = attended = 0.0
    selected = jnp.zeros((), F32)
    for block in blocks.values():
        if block.indexer:
            c = block.config
            top_k = c.index_topk
            lowering = mla_prefill.prefill_lowering(
                n, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, dt)
            a, b = dsa.prefill_pairs(n, top_k, lowering, lengths)
            scored += a * lengths.shape[0]
            attended += (b * lengths.shape[0] if lowering == "xla"
                         else jnp.sum(b))
            m = lengths.astype(F32)
            past = jnp.maximum(m - top_k, 0)
            selected += jnp.sum(m * (m + 1) / 2 - past * (past + 1) / 2)
    return {"dsa.prefill_pairs_scored": jnp.asarray(scored, F32),
            "dsa.prefill_pairs_attended": jnp.asarray(attended, F32),
            "dsa.prefill_pairs_selected": selected}


def byte_gauges(blocks: dict, gauges: dict, dtype) -> dict:
    """``dsa.index_bytes_read`` / ``mla.cache_bytes_read`` /
    ``mla.window_bytes_read`` from the published row counters (host side):
    the rows ONE block of a kind reads times the kind's row bytes times its
    blocks; nothing rides in the scan for them."""
    size = jnp.dtype(dtype).itemsize
    full = [b for b in blocks.values() if b.indexer]
    ring = [b for b in blocks.values() if b.window]
    out = {}
    if full and "dsa.index_rows_read" in gauges:
        out["dsa.index_bytes_read"] = (
            gauges["dsa.index_rows_read"] * len(full)
            * full[0].config.index_head_dim * size)
        out["mla.cache_bytes_read"] = (
            gauges["mla.cache_rows_read"] * len(full)
            * full[0].config.latent_width * size)
    if ring and "mla.window_rows_read" in gauges:
        out["mla.window_bytes_read"] = (
            gauges["mla.window_rows_read"] * len(ring)
            * ring[0].config.latent_width * size)
    return out


# ------------------------------------------------------------------ experts


def route(u, router, c: Dots3Config):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:sigmoid_route``)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.norm_topk_prob,
        scale=float(c.routed_scaling_factor), eps=1e-20)


def moe_share(u, layer, c: Dots3Config, live):
    """This chip's share of the routed experts over ``u (T, h)`` (the
    shared expert is the caller's: every chip computes it alike) and what
    it counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    y, load = held_experts(u, ids, w, live, layer["experts"], c)
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


STAT_KEYS = experts.STAT_KEYS + ATTN_STAT_KEYS


def zero_stats(c: Dots3Config) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    eps = c.rms_norm_eps
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        x = residual(x, attend(stack_norm(x, n[0], eps), f"l{i}",
                               layer["attn"]))
        u = stack_norm(x, n[1], eps)
        if "experts" not in layer:
            x = residual(x, swiglu(u, layer["ffn"]))
            continue
        m, ids, s = moe_share(u, layer, c, live)
        stats = experts.add_stats(stats, s)
        touched += jnp.sum(s["moe.held_load"] > 0).astype(F32)
        chosen.append(ids)
        x = residual(residual(x, m),
                     swiglu(u, layer["shared"], scope="moe.shared"))
    return x, stats, chosen, touched


def prefill(params, tokens, lengths, config: Dots3Config,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over dots3's stack and blocks: the per-token cache
    rows are ``{block: {"latent": (R, P, 576), "index": (R, P, 128)}}`` of a
    full layer and ``{block: (R, P, 1088)}`` of a sliding one; the stats
    gain the admission's ``dsa.prefill_pairs_*``."""
    policy = policy or bf16_policy()
    blocks = blocks_of(config)
    out = driver.prefill(_layers, blocks, params, tokens, lengths, config,
                         policy, **kwargs)
    out[2].update(prefill_attention_stats(blocks, tokens.shape[1], lengths,
                                          policy.compute_dtype))
    return out


def caches_from(rows, lengths, config: Dots3Config, max_len: int):
    """The per-token rows :func:`prefill` returned, as the caches of R
    slots in an engine of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: Dots3Config,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over dots3's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks, partial(attention_stats, blocks), params, tok, pos,
        caches, live, config, policy or bf16_policy(), **kwargs)


class Dots3Family(driver.Family):
    name = "dots3"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return attention_stats(self.blocks, dt, caches, pos, live)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats

    def publish(self, stats: dict) -> dict:
        out = super().publish(stats)
        out.update(byte_gauges(self.blocks, out, self.policy.compute_dtype))
        return out
