"""MiMo-V2 (``model_type`` ``mimo_v2``: MiMo-V2-Flash / MiMo-V2.5's language
model): a stack in which five layers of six keep a SHORT RING of keys under
a learned per-head SINK and the sixth keeps keys that grow with the
request, the two kinds with DIFFERENT HEAD SHAPES (8 | 4 key/value heads)
and rotary bases, keys 192 wide beside values 128 wide, a rotation over the
first third of a head only; a leading dense layer, then expert layers with
sigmoid top-k routing under a selection bias and NO shared expert — as ONE
CHIP'S SHARE of an expert-parallel deployment.

What MiMo alone has: its config, the two kinds of attention block and what
each states about its cache, the partial rotation, the value scale, the
two-norm layer's wiring and the seeded weights' layout.  The model driver
and the engine's seam are ``models/driver.py``; the ring, the grown keys and
the decode step's write-then-attend are ``models/kv.py`` (shared with
Trinity, Granite, SDAR, LFM2 and Nemotron-H: MiMo is the block's first user
of ``v_head_dim`` and ``sink``); the held experts' product, its counters
and the sigmoid router are ``models/experts.py`` (``experts.sigmoid_route``
as it stands, at Trinity's ``1e-20``).  The attention cores are
``ops/gqa.py``: its kernels for the full layers, its XLA forms for the
sliding ones, on the chip too (below).

``x0 = E[token]``.  Layer ``l`` (pre-norm, ``N_*`` RMSNorms with a learned
scale, statistics in float32, eps ``layernorm_epsilon`` 1e-5)::

    x   = x + Attn_l(N_in(x))
    out = x + FFN_l(N_post(x))

``logits = N_f(x_L) W_head`` (untied).  Every departure from these
equations is a bug or an entry of ``assumed`` in
``perf/configs/mimo-v2.5-ep16.json``.

**Attention**, kind from ``hybrid_layer_pattern[l]``: 0 = FULL, 1 = SLIDING
(39 ones and 9 zeros of 48: "5 SWA : 1 global").  Both kinds: ``H`` = 64
query heads, ``d`` = 192 (q and k), ``dv`` = 128 (v); ``q = u W_q`` (4096 ->
64 x 192), ``k = u W_k`` (-> KV x 192), ``v = u W_v`` (-> KV x 128), no
bias, no q/k norm; the first ``int(192 * 0.334)`` = 64 columns of each q and
k head are rotated (half-split pairs ``(i, i + 32)``: ``driver.rope`` on
the slice), the other 128 are not; ``v <- 0.707 v``
(``attention_value_scale``); scores ``s_ij = 192^-1/2 q_i . k_j`` in float32
for ``j <= i``; ``out = concat_h(o_h) W_o`` (64 x 128 = 8192 -> 4096).

* **full** (``KV = num_key_value_heads`` 4, base ``rope_theta`` 1e7, no
  sink): ``p_ij = softmax_j(s_ij)``, ``o_i = sum_j p_ij v_j``.  Cache: grown
  keys, ``4 x (192 + 128) x 2 B`` = 2,560 B a token.
* **sliding** (``KV = swa_num_key_value_heads`` 8, base ``swa_rope_theta``
  1e4, window 128: ``i - j < 128``, the token itself counted; a learned
  ``sink_h``, one float a head, ``add_swa_attention_sink_bias``): ``m =
  max(max_j s_ij, sink_h)``, ``p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m)
  + exp(sink_h - m))``, ``o_i = sum_j p_ij v_j`` — the sink takes mass and
  has no value.  Cache: a RING of 128 rows, ``8 x 320 x 2 B x 128`` = 655 KB
  a slot whatever the context.

**FFN**, from ``moe_layer_freq[l]``: 0 a dense SwiGLU ``(silu(u W_g) * u
W_u) W_d`` of ``intermediate_size`` (layer 0 alone, 4096 -> 16384 -> 4096);
1 the experts: router in float32, ``s = sigmoid(u W_r)`` (4096 -> 256); the
8 largest of ``s + b`` are chosen (``b`` picks and does not weigh;
``n_group = topk_group = 1``: no group limit); weights ``s_chosen / (sum
s_chosen + 1e-20)`` (``norm_topk_prob``) times ``routed_scaling_factor``
(null: 1.0); expert ``e`` a SwiGLU 4096 -> 2048 -> 4096; no shared expert.

**The share.**  The router keeps its width and top-k whatever is held; the
layer adds the terms of the held experts (``first_expert .. first_expert +
experts_held - 1``) and leaves out the absent ones'; attention and the dense
layer are whole on every chip.

**On the chip the FULL layers' cores are the kernels**: the decode step's
``gqa_decode_fwd`` (PR 55: it takes keys 192 wide beside values of 128 and
reads a slot's grown rows up to its count) and the prefill's
``gqa_prefill_fwd`` (PR 62: values of 128 as they are, q's and k's heads
padded with zero columns from 192 to 256 on the way in — the scores are the
same numbers, the cache rows stay 192 wide, and no float32 score block
passes through HBM: 50 ms a layer at 1 x 16,384 where the blocked form
took 160).  **The SLIDING layers keep the XLA forms**, both cores: a sink,
and a window of 128 — a ring under ``gqa.MIN_TILE`` rows in a step, no
multiple of the key tile in a prefill — each alone is enough
(``ops/gqa.py``'s docstring).  ``ServingEngine.status()["gqa_decode"]`` and
``["gqa_prefill"]`` both read ``"pallas+xla"``.  So a decode step reads
every row of the RINGS only, and a prefill of a sliding layer computes
``QUERY_BLOCK + 128`` keys for every ``QUERY_BLOCK`` rows, of a full layer
the key tiles under the diagonal up to the row's length; the counters
(``attn.*_rows_read``, ``attn.*_bytes_read``,
``attn.prefill_pairs_visited``) say what that costs.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy
from progen_tpu.models import driver, experts, kv
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

FULL, SLIDING = 0, 1        # ``hybrid_layer_pattern``'s two values


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    """The published keys (catalog names) plus the share this chip holds
    and the scales of the seeded weights."""

    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    # the full-attention kind's heads ...
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    # ... and the sliding kind's
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    # FULL / SLIDING a layer; empty: the published period (layer 0 and every
    # sixth from layer 5 full, the others sliding)
    hybrid_layer_pattern: tuple = ()
    # 0 (dense) / 1 (experts) a layer; empty: layer 0 dense, the rest experts
    moe_layer_freq: tuple = ()
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 10000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float | None = None
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    n_shared_experts: int | None = None
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    # the share: experts ``first_expert .. first_expert + held - 1``
    experts_held: int = 256
    first_expert: int = 0
    # seeded weights (``init_params``): the router logits' spread a token,
    # the selection bias's (in units of a score: it moves some choices), and
    # the sinks' — a sink of 4 takes about a fifth of the mass of a full
    # window of unit-spread scores, so leaving it out, or giving it to the
    # full layers, moves the logits well past any rounding
    router_logit_std: float = 1.0
    router_bias_std: float = 0.02
    sink_mean: float = 4.0
    sink_std: float = 1.0
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
    def rms_norm_eps(self) -> float:
        return self.layernorm_epsilon

    embed_gain = 1.0            # no multiplier on the embedding

    @property
    def route_scale(self) -> float:
        s = self.routed_scaling_factor
        return 1.0 if s is None else float(s)

    def heads_of(self, kind: int) -> tuple:
        """``(H, KV, d, dv)`` of an attention kind."""
        if kind == SLIDING:
            return (self.swa_num_attention_heads,
                    self.swa_num_key_value_heads, self.swa_head_dim,
                    self.swa_v_head_dim)
        return (self.num_attention_heads, self.num_key_value_heads,
                self.head_dim, self.v_head_dim)

    def rotary_dim(self, kind: int) -> int:
        """Leading columns of a q or k head that are rotated."""
        return int(self.heads_of(kind)[2] * self.partial_rotary_factor)

    def theta_of(self, kind: int) -> float:
        return self.swa_rope_theta if kind == SLIDING else self.rope_theta

    def sink_of(self, kind: int) -> bool:
        return (self.add_swa_attention_sink_bias if kind == SLIDING
                else self.add_full_attention_sink_bias)

    @classmethod
    def from_dict(cls, d) -> "MiMoV2Config":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        for key in ("hybrid_layer_pattern", "moe_layer_freq"):
            if key in d:
                d[key] = tuple(d[key])
        return cls(**d)

    def __post_init__(self):
        n = self.num_hidden_layers
        if not self.hybrid_layer_pattern:
            object.__setattr__(self, "hybrid_layer_pattern", tuple(
                FULL if i == 0 or i % 6 == 5 else SLIDING for i in range(n)))
        if not self.moe_layer_freq:
            object.__setattr__(self, "moe_layer_freq",
                               (0,) + (1,) * (n - 1))
        for key in ("hybrid_layer_pattern", "moe_layer_freq"):
            value = getattr(self, key)
            if len(value) != n or set(value) - {0, 1}:
                raise ValueError(
                    f"{key} must give {n} layers a 0 or a 1 each: {value}")
        for kind in (FULL, SLIDING):
            heads, kv_heads, d, _ = self.heads_of(kind)
            if heads % kv_heads:
                raise ValueError(
                    f"{heads} query heads do not split over {kv_heads} "
                    "key/value heads")
            if self.rotary_dim(kind) % 2 or not self.rotary_dim(kind):
                raise ValueError(
                    f"partial_rotary_factor {self.partial_rotary_factor} of "
                    f"a head of {d} rotates {self.rotary_dim(kind)} "
                    "columns: not a whole number of pairs")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the {self.n_routed_experts} routed experts")
        if self.scoring_func != "sigmoid" or self.n_group != 1 \
                or self.topk_group != 1 or self.n_shared_experts:
            raise ValueError(
                "the router is sigmoid top-k with no group limit and no "
                f"shared expert: scoring_func {self.scoring_func!r}, n_group "
                f"{self.n_group}, topk_group {self.topk_group}, "
                f"n_shared_experts {self.n_shared_experts} are not supported")


# ------------------------------------------------------------------ weights


def _init_attn(key, c: MiMoV2Config, dt, kind: int):
    h = c.hidden_size
    heads, kv_heads, d, dv = c.heads_of(kind)
    ks = jax.random.split(key, 5)
    # q and k of unit spread an entry (the normed input has unit RMS), so
    # that the scaled scores ``d^-1/2 q . k`` have unit spread too
    p = {
        "wq": driver.normal(ks[0], (h, heads * d), h ** -0.5, dt),
        "wk": driver.normal(ks[1], (h, kv_heads * d), h ** -0.5, dt),
        "wv": driver.normal(ks[2], (h, kv_heads * dv), h ** -0.5, dt),
        "wo": driver.normal(ks[3], (heads * dv, h), (heads * dv) ** -0.5, dt),
    }
    if c.sink_of(kind):
        p["sink"] = (c.sink_mean + driver.normal(ks[4], (heads,), c.sink_std,
                                                 F32)).astype(dt)
    return p


def _init_layer(key, c: MiMoV2Config, dt, kind: int, moe: bool):
    ks = jax.random.split(key, 6)
    h = c.hidden_size
    layer = {"norm": driver.init_norm(ks[0], (2, h), dt),
             "attn": _init_attn(ks[1], c, dt, kind)}
    if not moe:
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
    return layer


def init_params(config: MiMoV2Config, key, policy: Policy | None = None):
    policy = policy or bf16_policy()
    c = config
    made = {}

    def layer(k, i):
        kind = (c.hybrid_layer_pattern[i], bool(c.moe_layer_freq[i]))
        if kind not in made:        # one program a kind of layer
            made[kind] = jax.jit(partial(
                _init_layer, c=c, dt=policy.param_dtype, kind=kind[0],
                moe=kind[1]))
        return made[kind](k)

    return driver.init_params(config, key, policy, layer)


# ---------------------------------------------------------------- attention


def _inv_freq(theta: float, d: int):
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))


def project(x, p, c: MiMoV2Config, positions, kind: int):
    """``x (..., n, h)`` at ``positions (..., n)`` -> ``q (..., n, H, d)``,
    ``k (..., n, KV, d)`` (the first ``rotary_dim`` columns of each head
    rotated at the kind's base, the rest as projected) and ``v (..., n, KV,
    dv)`` times ``attention_value_scale``."""
    heads, kv_heads, d, dv = c.heads_of(kind)
    rot = c.rotary_dim(kind)
    inv = partial(_inv_freq, c.theta_of(kind))

    def rotated(a):
        return jnp.concatenate(
            [driver.rope(a[..., :rot], positions, inv), a[..., rot:]],
            axis=-1)

    with jax.named_scope("attn.project"):
        q = mm(x, p["wq"])
        q = rotated(q.reshape(q.shape[:-1] + (heads, d)))
        k = mm(x, p["wk"])
        k = rotated(k.reshape(k.shape[:-1] + (kv_heads, d)))
        v = mm(x, p["wv"])
        v = v.reshape(v.shape[:-1] + (kv_heads, dv))
        # linear in v: the same as scaling the core's output, up to rounding
        v = v * jnp.asarray(c.attention_value_scale, v.dtype)
    return q, k, v, None


class KVBlock(kv.KVBlock):
    """One KIND of MiMo's attention blocks (``models/kv.py`` has the cache's
    two layouts and the step): SLIDING — 8 key/value heads, a ring of
    ``sliding_window`` rows, a sink in the layer's weights, base 1e4 — or
    FULL — 4 key/value heads, grown rows, no sink, base 1e7; keys ``d`` and
    values ``dv`` wide in both."""

    def __init__(self, config: MiMoV2Config, kind: int):
        _, kv_heads, d, dv = config.heads_of(kind)
        super().__init__(
            kv_heads, d, 1.0 / math.sqrt(d),
            config.sliding_window if kind == SLIDING else None,
            v_head_dim=dv, sink=config.sink_of(kind))
        self.config = config
        self.kind = kind

    def project(self, x, p, positions):
        return project(x, p, self.config, positions, self.kind)

    def finish(self, o, rest, p):
        return mm(o, p["wo"])


def blocks_of(c: MiMoV2Config) -> dict:
    """One block a layer by name, ONE instance a kind."""
    kinds = {kind: KVBlock(c, kind) for kind in (FULL, SLIDING)}
    return {f"l{i}": kinds[kind]
            for i, kind in enumerate(c.hybrid_layer_pattern)}


ATTN_STAT_KEYS = kv.DECODE_STAT_KEYS + kv.PREFILL_STAT_KEYS
attention_stats = kv.decode_stats
prefill_attention_stats = kv.prefill_stats


# ------------------------------------------------------------------ experts


def route(u, router, c: MiMoV2Config):
    """``(ids (T, k), weights (T, k))``, float32 throughout
    (``models/experts.py:sigmoid_route``)."""
    return experts.sigmoid_route(
        u, router, c.num_experts_per_tok, norm=c.norm_topk_prob,
        scale=c.route_scale, eps=1e-20)


def moe_share(u, layer, c: MiMoV2Config, live):
    """This chip's share of the experts over ``u (T, h)`` and what it
    counted over the ``live`` tokens."""
    ids, w = route(u, layer["router"], c)
    y, load = held_experts(u, ids, w, live, layer["experts"], c)
    stats = {"moe.tokens": jnp.sum(live).astype(F32),
             "moe.held_load": load.astype(F32),
             **kernel_counters(u, layer["experts"], load, c)}
    with jax.named_scope("moe.experts"):    # the terms' own rounding
        y = y.astype(u.dtype)
    return y, ids, stats


STAT_KEYS = experts.STAT_KEYS + ATTN_STAT_KEYS


def zero_stats(c: MiMoV2Config) -> dict:
    """Device-side counters, all float32 sums (docs/OBSERVABILITY.md §3)."""
    return experts.zero_stats(STAT_KEYS, c.experts_held)


# -------------------------------------------------------------------- model


def _layers(x, params, c, attend, live):
    """The stack over ``x (T, h)`` flat tokens (``driver.prefill`` says
    what the driver asks of it)."""
    stats = zero_stats(c)
    chosen, touched = [], 0.0
    eps = c.layernorm_epsilon
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
        x = residual(x, m)
    return x, stats, chosen, touched


def prefill(params, tokens, lengths, config: MiMoV2Config,
            policy: Policy | None = None, **kwargs):
    """``driver.prefill`` over MiMo's stack and blocks: the per-token cache
    rows are ``{block: {"k": (R, KV, P, d), "v": (R, KV, P, dv)}}``; the
    stats gain the attention cores' pair counters."""
    policy = policy or bf16_policy()
    blocks = blocks_of(config)
    out = driver.prefill(_layers, blocks, params, tokens, lengths, config,
                         policy, **kwargs)
    out[2].update(prefill_attention_stats(
        blocks, tokens.shape[1], lengths, policy.compute_dtype))
    return out


def caches_from(rows, lengths, config: MiMoV2Config, max_len: int):
    """The per-token rows :func:`prefill` returned, as the caches of R
    slots in an engine of ``max_len``."""
    return driver.cache_rows(blocks_of(config), rows, lengths, max_len)


def decode_step(params, tok, pos, caches, live, config: MiMoV2Config,
                policy: Policy | None = None, **kwargs):
    """``driver.decode_step`` over MiMo's stack and blocks."""
    blocks = blocks_of(config)
    return driver.decode_step(
        _layers, blocks,
        lambda dt, caches, pos, live: attention_stats(blocks, caches, pos,
                                                      live),
        params, tok, pos, caches, live, config, policy or bf16_policy(),
        **kwargs)


class MiMoV2Family(driver.Family):
    name = "mimo_v2"
    stat_keys = STAT_KEYS
    stack = staticmethod(_layers)
    blocks_of = staticmethod(blocks_of)

    def attention_stats(self, dt, caches, pos, live):
        return attention_stats(self.blocks, caches, pos, live)

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(params, tokens, lengths, self.config,
                                      self.policy)
        return logits[:, 0], caches_from(rows, lengths, self.config,
                                         max_len), stats
