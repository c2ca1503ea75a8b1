"""GLM-5.2 (``model_type`` ``glm_moe_dsa``): a stack whose EVERY layer runs
latent attention (MLA) over the ``index_topk`` cached tokens a learned
selection keeps for each query, and in which only one layer in four COMPUTES
that selection: a ``full`` layer has the indexer (its weights, a second cache
leaf of one key a token) and hands what it selected to the ``shared`` layers
that follow it, which have no indexer at all and attend the same keys for the
same query token (IndexShare); leading dense layers, then expert layers with
sigmoid top-k routing under a selection bias beside one shared expert — as
ONE CHIP'S SHARE of an expert-parallel deployment.

What GLM alone has: its config, which option of
``models/latent.py:LatentBlock`` each layer takes (``selection="own"`` or
``"borrow"``), the layer's wiring and the seeded weights' layout.  The model
driver, the engine's seam and the hand-over of a selection from block to
block are ``models/driver.py``; latent attention, the indexer's projection
and the two kinds of cache are ``models/latent.py`` (shared with LongCat,
DeepSeek-V2 and dots3); the indexer's score, the selection and the gathered
decode core are ``ops/dsa.py``; the held experts' product, its counters and
the sigmoid router are ``models/experts.py``.

``x0 = E[token]``.  Layer ``l`` (pre-norm, ``N_*`` RMSNorms with a learned
scale, statistics in float32, eps ``rms_norm_eps`` 1e-5)::

    x   = x + Attn_l(N_in(x))
    out = x + FFN_l(N_post(x))

``logits = N_f(x_L) W_head`` (untied).  Every departure from these
equations is a bug or an entry of ``assumed`` in
``perf/configs/glm-5.2-ep16.json``.

**Attention**, ``u = N_in(x)``: ``c_q = RMSNorm(u W_qa)`` (``q_lora_rank``
2,048); ``q = c_q W_qb`` (64 heads of ``[nope 192 | rope 64]``); ``[c_kv |
k_r] = u W_kva`` (512 | 64), ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] = c_kv
W_kvb`` (64 x [192 | 256]); the rope parts rotated at base ``rope_theta``
8e6, unscaled, in INTERLEAVED pairs ``(2i, 2i + 1)`` (``rope_interleave``);
scores ``256^-1/2 q . k``; the softmax over the selected keys ``S_t`` only;
``W_o`` 16,384 -> 6,144.  No gate, no rescale, no bias.  The cache row of a
token is ``[c_kv | rope(k_r)]``, 576 wide.

**The indexer**, ``indexer_types[l] == "full"`` only (DeepSeek-V3.2's,
``latent.index_project`` and ``ops/dsa.py``, at 32 heads): ``q^I = c_q
W^I_q`` (32 x 128), ``k^I = LayerNorm(u W^I_k)`` (128; one key a token,
cached), ``w = u W^I_w * 32^-1/2 * 128^-1/2``, the leading 64 columns of
``q^I`` and ``k^I`` rotated in pairs ``(2i, 2i + 1)``
(``indexer_rope_interleave``); ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] .
k^I[s])`` for ``s <= t``, float32; ``S_t`` the ``index_topk`` 2,048 largest
(every visible key while ``t < 2,048``).  Cache: ``max_len`` latent rows of
576 and ``max_len`` indexer keys of 128, 1,408 B a token.

**A shared layer** (``indexer_types[l] == "shared"``): no ``W^I_*``, no
indexer cache — 1,152 B a token —; ``S_t`` of layer ``l`` is ``S_t`` of the
nearest ``full`` layer below ``l``, for an admission (the keep mask, computed
once, read by every core until the next ``full`` layer) and a decode step
(``select_rows`` once a ``full`` layer; each layer gathers ITS OWN latent
rows at those numbers) alike.  ``indexer_types`` is the authority; the
published pattern (full below ``index_skip_topk_offset`` 3, then the last of
every ``index_topk_freq`` 4) is only what an empty list stands for.

**FFN**: ``mlp_layer_types[l] == "dense"`` a SwiGLU of ``intermediate_size``
12,288; the others the experts: router in float32, ``s = sigmoid(u W_r)``
(-> 256); the 8 largest of ``s + b`` are chosen (``noaux_tc``; ``n_group =
topk_group = 1``); weights ``s_chosen / (sum s_chosen + 1e-20)`` times
``routed_scaling_factor`` 2.5; expert ``e`` a SwiGLU 6,144 -> 2,048 -> 6,144;
plus ``n_shared_experts`` = 1 shared expert of the same width on every token.

**The share.**  The router keeps its width and top-k whatever is held; the
layer adds the terms of the held experts (``first_expert .. first_expert +
experts_held - 1``) and leaves out the absent ones'; attention, the dense
layers and the shared expert are whole on every chip.

**On the chip** every layer's admission core is the flash kernel
``gqa_prefill_fwd`` over the heads JOINED to ``[nope | rope]`` 256 beside
values of 256, under the selection as its keep mask, and every layer's decode
core the kernel ``mla_decode_fwd`` over the 2,048 gathered rows:
``ServingEngine.status()`` reads ``"gqa_prefill": "pallas"``, ``"mla_decode":
"pallas"``.  (``mla_prefill_fwd`` taught a 192-wide ``nope`` was timed beside
it and lost: PERF.md section 6, PR 60.)
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, experts
from progen_tpu.models.driver import (  # noqa: F401
    F32,
    bf16_policy,
    residual,
    rms_norm,
    stack_norm,
    swiglu,
)
from progen_tpu.models.experts import held_experts, kernel_counters
from progen_tpu.models.latent import LatentBlock
from progen_tpu.ops import dsa, gqa
from progen_tpu.ops.mla_decode import rows_visited

FULL, SHARED = "full", "shared"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class GLMDSAConfig:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights.  It is the one latent shape of
    every block, under the names ``models/latent.py`` reads."""

    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    # FULL / SHARED and DENSE / SPARSE a layer; empty: the published
    # patterns, from the four numbers below them
    indexer_types: tuple = ()
    mlp_layer_types: tuple = ()
    index_skip_topk_offset: int = 3
    index_topk_freq: int = 4
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 8000000.0
    rope_interleave: bool = True
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_rope_interleave: bool = True
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
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

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    embed_gain = 1.0            # no multiplier on the embedding
    q_gain = kv_gain = 1.0      # no rescale of the latents

    def rope_inv_freq(self, d: int):
        return 1.0 / (self.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d))

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == DENSE

    @classmethod
    def from_dict(cls, d) -> "GLMDSAConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        rope = d.get("rope_parameters") or {}
        d = {**({"rope_theta": rope["rope_theta"]}
                if "rope_theta" in rope else {}),
             **{k: v for k, v in d.items() if k in names}}
        for key in ("indexer_types", "mlp_layer_types"):
            if key in d:
                d[key] = tuple(d[key])
        return cls(**d)

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.indexer_types:
            offset, freq = self.index_skip_topk_offset, self.index_topk_freq
            object.__setattr__(self, "indexer_types", tuple(
                FULL if i < offset or (i - offset) % freq == freq - 1
                else SHARED for i in range(n)))
        if not self.mlp_layer_types:
            object.__setattr__(self, "mlp_layer_types", tuple(
                DENSE if i < self.first_k_dense_replace else SPARSE
                for i in range(n)))
        for key, kinds in (("indexer_types", {FULL, SHARED}),
                           ("mlp_layer_types", {DENSE, SPARSE})):
            value = getattr(self, key)
            if len(value) != n or set(value) - kinds:
                raise ValueError(f"{key} must name {n} layers from "
                                 f"{sorted(kinds)}: {value}")
        if self.indexer_types[0] != FULL:
            raise ValueError("the first layer has no layer below it to "
                             "borrow a selection from")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")
        if (self.scoring_func != "sigmoid" or self.n_shared_experts != 1
                or (self.n_group, self.topk_group) != (1, 1)):
            raise ValueError(
                "the router is sigmoid top-k over one group beside one "
                f"shared expert: scoring_func {self.scoring_func!r}, "
                f"n_shared_experts {self.n_shared_experts}, n_group "
                f"{self.n_group}, topk_group {self.topk_group} are not "
                "supported")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                f"the indexer rotates its leading {self.qk_rope_head_dim} "
                f"columns of {self.index_head_dim}")


# ------------------------------------------------------------------ weights


def _init_attn(key, c: GLMDSAConfig, dt, kind: str):
    """Scales chosen so that q, k and v have unit spread an entry, and with
    them the scaled attention scores and the indexer's scores."""
    h, heads = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    ks = jax.random.split(key, 11)
    normal, norm = driver.normal, driver.init_norm
    p = {
        "wqa": normal(ks[0], (h, c.q_lora_rank), h ** -0.5, dt),
        "q_norm": norm(ks[1], (c.q_lora_rank,), dt),
        "wqb": normal(ks[2], (c.q_lora_rank, heads * qk),
                      c.q_lora_rank ** -0.5, dt),
        "wkva": normal(ks[3], (h, c.latent_width), h ** -0.5, dt),
        "kv_norm": norm(ks[4], (c.kv_lora_rank,), dt),
        "wkvb": normal(
            ks[5], (c.kv_lora_rank, heads * (c.qk_nope_head_dim
                                             + c.v_head_dim)),
            c.kv_lora_rank ** -0.5, dt),
        "wo": normal(ks[6], (heads * c.v_head_dim, h),
                     (heads * c.v_head_dim) ** -0.5, dt),
    }
    if kind == FULL:        # a shared layer has none of these
        d = c.index_head_dim
        p.update({
            "wiq": normal(ks[7], (c.q_lora_rank, c.index_n_heads * d),
                          c.q_lora_rank ** -0.5, dt),
            "wik": normal(ks[8], (h, d), h ** -0.5, dt),
            "ik_scale": norm(ks[9], (d,), dt),
            "ik_bias": normal(ks[9], (d,), 0.05, dt),
            "wiw": normal(ks[10], (h, c.index_n_heads), h ** -0.5, dt),
        })
    return p


def _init_layer(key, c: GLMDSAConfig, dt, kind: str, dense: bool):
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


def init_params(config: GLMDSAConfig, key, policy: Policy | None = None):
    policy = policy or bf16_policy()
    c = config
    made = {}

    def layer(k, i):
        kind = (c.indexer_types[i], c.is_dense(i))
        if kind not in made:        # one program a kind of layer
            made[kind] = jax.jit(partial(
                _init_layer, c=c, dt=policy.param_dtype, kind=kind[0],
                dense=kind[1]))
        return made[kind](k)

    return driver.init_params(config, key, policy, layer)


# ---------------------------------------------------------------- attention


def blocks_of(c: GLMDSAConfig) -> dict:
    """One block a layer by name, ONE instance a kind: a full layer owns its
    selection, a shared layer borrows the last owner's."""
    kinds = {FULL: LatentBlock(c, selection="own"),
             SHARED: LatentBlock(c, selection="borrow")}
    return {f"l{i}": kinds[kind] for i, kind in enumerate(c.indexer_types)}


def _owners(blocks: dict) -> list:
    return [n for n, b in blocks.items() if b.selection == "own"]


# a decode step's counters (``attention_stats``) and an admission's
# (``prefill_attention_stats``); docs/OBSERVABILITY.md section 3
ATTN_STAT_KEYS = (
    "mla.decode_rows", "mla.context_tokens", "mla.cache_rows_read",
    "dsa.context_tokens", "dsa.index_rows_read", "dsa.keys_selected",
    "dsa.selections_computed", "dsa.selections_borrowed",
    "dsa.prefill_pairs_scored", "dsa.prefill_pairs_attended",
    "dsa.prefill_pairs_selected")


def attention_stats(blocks: dict, computed: list, dt, caches, pos,
                    live) -> dict:
    """A decode step's counters.  The ``mla.*`` are ``models/latent.py``'s,
    of ONE block (every block's core has the same shapes):
    ``mla.cache_rows_read`` the latent rows its sparse core reads
    (``ops/mla_decode.py:rows_visited`` over the gathered rows).  The
    ``dsa.*`` are summed over the layers they cover: ``dsa.context_tokens``
    the keys a live row could see and ``dsa.keys_selected`` the rows it
    attends, over EVERY layer (all attend under a selection);
    ``dsa.index_rows_read`` the indexer rows the step's scores read (every
    row of every slot: the XLA form), over the FULL layers alone — a shared
    layer reads none; ``dsa.selections_computed`` / ``_borrowed`` the (live
    row, layer) pairs whose selection was computed there or handed over.
    Who computed one is ``computed``, ``ops/dsa.py:count_selections``'s
    tally of the step's trace (one entry a ``select_rows`` that ran), not
    the blocks' labels: a shared layer that selected for itself counts as
    computing."""
    own = _owners(blocks)
    latent, index = caches[own[0]]["latent"], caches[own[0]]["index"]
    c = blocks[own[0]].config
    k = min(c.index_topk, latent.shape[1])
    kept = jnp.minimum(pos + 1, k)
    rows = jnp.sum(live).astype(F32)
    seen = jnp.sum(jnp.where(live, pos + 1, 0)).astype(F32)
    any_live = jnp.any(live)
    out = driver.zero_scalars(ATTN_STAT_KEYS)
    out.update({
        "mla.decode_rows": rows,
        "mla.context_tokens": seen,
        "mla.cache_rows_read": rows_visited(dt, latent[:, :k], kept,
                                            c.kv_lora_rank) * any_live,
        "dsa.context_tokens": seen * len(blocks),
        "dsa.keys_selected": jnp.sum(
            jnp.where(live, kept, 0)).astype(F32) * len(blocks),
        "dsa.index_rows_read": jnp.asarray(
            index.shape[0] * index.shape[1] * len(computed), F32) * any_live,
        "dsa.selections_computed": rows * len(computed),
        "dsa.selections_borrowed": rows * (len(blocks) - len(computed)),
    })
    return out


def prefill_attention_stats(blocks: dict, computed: list, n: int, lengths,
                            dt) -> dict:
    """An admission's counters over rows of ``lengths (R,)`` padded to
    ``n``: the (query, key) pairs the indexers scored, over the layers that
    made a mask (``computed``, as :func:`attention_stats`'s: one entry a
    ``prefill_keep`` that made one; ``ops/dsa.py:prefill_pairs``); the
    pairs the cores computed a head,
    over every layer, under the lowering that runs
    (``ops/gqa.py:pairs_visited``: the kernel's visited tiles; the blocked
    XLA form's every block at its group's key span); the pairs the
    selection allows at real positions, ``sum_t min(t + 1, index_topk)``,
    over every layer; and the (row, layer) selections computed and borrowed
    — none where no row can see more than ``index_topk`` keys (``n <=
    index_topk``: the causal rule alone)."""
    c = next(iter(blocks.values())).config
    own, top_k, count = len(computed), c.index_topk, lengths.shape[0]
    attended = gqa.pairs_visited(lengths, n, None, gqa.prefill_lowering(
        n, c.qk_nope_head_dim + c.qk_rope_head_dim, dt, None,
        dv=c.v_head_dim, keep=n > top_k))
    m = lengths.astype(F32)
    past = jnp.maximum(m - top_k, 0)
    real = jnp.sum(lengths > 0).astype(F32) * (n > top_k)
    return {
        "dsa.prefill_pairs_scored": jnp.asarray(
            dsa.prefill_pairs(n, top_k)[0] * count * own, F32),
        "dsa.prefill_pairs_attended": attended * len(blocks),
        "dsa.prefill_pairs_selected": jnp.sum(
            m * (m + 1) / 2 - past * (past + 1) / 2) * len(blocks),
        "dsa.selections_computed": real * own,
        "dsa.selections_borrowed": real * (len(blocks) - own)}


def byte_gauges(blocks: dict, gauges: dict, dtype) -> dict:
    """``dsa.index_bytes_read`` / ``mla.cache_bytes_read`` from the
    published row counters (host side; nothing rides in the scan for them):
    the indexer rows the FULL layers read at an indexer key's bytes, the
    latent rows ONE block reads times the blocks at a latent row's; and
    ``dsa.selections_read``, every (row, layer) that attended under a
    selection, computed there or borrowed."""
    size = jnp.dtype(dtype).itemsize
    c = next(iter(blocks.values())).config
    out = {}
    if "dsa.index_rows_read" in gauges:
        out["dsa.index_bytes_read"] = (
            gauges["dsa.index_rows_read"] * c.index_head_dim * size)
        out["mla.cache_bytes_read"] = (
            gauges["mla.cache_rows_read"] * len(blocks) * c.latent_width
            * size)
        out["dsa.selections_read"] = (gauges["dsa.selections_computed"]
                                      + gauges["dsa.selections_borrowed"])
    return out


# ------------------------------------------------------------------ experts


def route(u, router, c: GLMDSAConfig):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:sigmoid_route``)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.norm_topk_prob,
        scale=float(c.routed_scaling_factor), eps=1e-20)


def moe_share(u, layer, c: GLMDSAConfig, live):
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


def zero_stats(c: GLMDSAConfig) -> dict:
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


def prefill(params, tokens, lengths, config: GLMDSAConfig,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over GLM's stack and blocks: the per-token cache
    rows are ``{block: {"latent": (R, P, 576), "index": (R, P, 128)}}`` of a
    full layer and ``{block: (R, P, 576)}`` of a shared one; the stats gain
    the admission's own counters."""
    policy = policy or bf16_policy()
    blocks = blocks_of(config)
    with dsa.count_selections() as computed:
        out = driver.prefill(_layers, blocks, params, tokens, lengths,
                             config, policy, **kwargs)
    out[2].update(prefill_attention_stats(
        blocks, computed, tokens.shape[1], lengths, policy.compute_dtype))
    return out


def caches_from(rows, lengths, config: GLMDSAConfig, max_len: int):
    """The per-token rows :func:`prefill` returned, as the caches of R
    slots in an engine of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: GLMDSAConfig,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over GLM's stack and blocks; its attention
    counters read the tally of the selections the step's own trace computed
    (the driver asks for them after the stack has run)."""
    blocks = blocks_of(config)
    with dsa.count_selections() as computed:
        return driver.decode_step(
            _layers, blocks, partial(attention_stats, blocks, computed),
            params, tok, pos, caches, live, config, policy or bf16_policy(),
            **kwargs)


class GLMDSAFamily(driver.Family):
    name = "glm_dsa"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        return decode_step(params, tok, pos, caches, live, self.config,
                           self.policy)

    def publish(self, stats: dict) -> dict:
        out = super().publish(stats)
        out.update(byte_gauges(self.blocks, out, self.policy.compute_dtype))
        return out
