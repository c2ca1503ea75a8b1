"""The mixers that carry a recurrent state, and what each states about its
cache (``models/driver.py`` says what a block is): :class:`StateBlock`, a
Mamba-2 mixer, :class:`DeltaBlock`, a gated delta-rule mixer (below it,
with its own equations), and :class:`ChannelDeltaBlock`, the delta rule with
a decay a CHANNEL under a bound (below that).

**StateBlock.**  Shared by the
families with such layers (``models/granite_hybrid.py``: one B/C group,
beside an MLP in every layer; ``models/nemotron_h.py``: eight groups, the
layer's only sublayer), as ``models/kv.py`` is by those whose attention is
grouped-query: a family brings SIZES — heads, head size, state, groups,
taps, eps, the scan's chunk — and the weights in the layout below; the
projection's split, the convolution, the recurrence (``ops/ssd.py``), the
gated norm and the cache are here.

``I = heads * head_dim``, ``G`` groups of ``N`` states, ``K`` taps, ``u (...,
h)`` the normed stream::

    [z (I) | xBC (I + 2 G N) | dt (heads)] = u W_in         (no bias)
    xBC_t <- silu(sum_j w_conv[:, j] * xBC_{t-(K-1)+j} + b_conv)
    [x (heads, head_dim) | B (G, N) | C (G, N)] = xBC_t
    dt = softplus(dt + dt_bias_h),  a_h = -exp(A_log_h)     (float32, no clamp)
    S_t = exp(dt a_h) S_{t-1} + dt x_t (x) B_t,g,  y_t = S_t C_t,g + D_h x_t
    out = RMSNorm_w(y * silu(z)) W_out

with ``g = h // (heads / G)`` the head's group, the convolution depthwise
and causal with zeros before the row's first token, and the norm — the gate
BEFORE it — over each group of ``I / G`` channels by itself (all ``I`` where
``G`` is 1).  With one group no group axis is made anywhere: Granite's
programs are the ops they were before the block was shared
(``tests/test_program_identity.py``).

**The cache**: ``{"ssm": (slots, heads, head_dim, N) float32, "conv":
(slots, K - 1, I + 2 G N)}``.  It does not depend on ``max_len``: 2.1 MB a
slot and layer at Granite's widths, 4.19 MB at Nemotron-H's, whatever the
request's length, read AND written every token.  The carry is float32 (a
bfloat16 one would re-round the whole state every token); the convolution
tail, like keys and values, is in the compute dtype.  A prefill hands over
the carry at each row's TRUE length and the row's last ``K - 1`` real
convolution inputs (zeros where the row is shorter); an admission
overwrites all of a slot's state, so a slot that idled serves its next
request as a fresh one does.  Rows that are not live run (the batch is
static): their state is garbage but finite (every decay is at most 1 and
the input is normed).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from progen_tpu.models.driver import F32, init_norm, mm, normal, rms_norm
from progen_tpu.ops import gdn, ssd


def _log_uniform(key, shape, lo, hi):
    return jnp.exp(jax.random.uniform(key, shape, F32, math.log(lo),
                                      math.log(hi)))


class StateBlock:
    """One Mamba-2 mixer of ``heads`` heads of ``head_dim`` over ``groups``
    B/C groups of ``state`` states, a convolution of ``conv`` taps, norms at
    ``eps`` and a prefill scan in chunks of ``chunk`` tokens."""

    def __init__(self, heads: int, head_dim: int, state: int, groups: int,
                 conv: int, eps: float, chunk: int):
        if heads % groups:
            raise ValueError(
                f"{heads} heads do not split over {groups} groups")
        self.heads, self.head_dim, self.state = heads, head_dim, state
        self.groups, self.conv, self.eps, self.chunk = (groups, conv, eps,
                                                        chunk)
        self.inner = heads * head_dim
        self.conv_channels = self.inner + 2 * groups * state

    def init_weights(self, key, hidden: int, dt, dt_range, a_range) -> dict:
        """Seeded weights: the per-head step ``softplus(dt_bias)`` and ``A``
        drawn log-uniform from ``dt_range`` / ``a_range``."""
        inner, heads = self.inner, self.heads
        ks = jax.random.split(key, 7)
        step = _log_uniform(ks[3], (heads,), *dt_range)
        return {
            "in_proj": normal(
                ks[0], (hidden, inner + self.conv_channels + heads),
                hidden ** -0.5, dt),
            "conv_w": normal(ks[1], (self.conv_channels, self.conv),
                             self.conv ** -0.5, dt),
            "conv_b": normal(ks[2], (self.conv_channels,), 0.05, dt),
            # the recurrence's own parameters stay float32, as the releases
            # keep them; softplus(dt_bias) is the drawn step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(_log_uniform(ks[4], (heads,), *a_range)),
            "d": jnp.ones((heads,), F32),
            "norm": init_norm(ks[5], (inner,), dt),
            "out_proj": normal(ks[6], (inner, hidden), inner ** -0.5, dt),
        }

    def init_cache(self, slots: int, max_len: int, dtype):
        return {"ssm": jnp.zeros((slots, self.heads, self.head_dim,
                                  self.state), F32),
                "conv": jnp.zeros((slots, self.conv - 1,
                                   self.conv_channels), dtype)}

    def _split_in(self, x, p):
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = mm(x, p["in_proj"])
            inner = self.inner
            z = zxbcdt[..., :inner]
            xbc = zxbcdt[..., inner:inner + self.conv_channels]
            dt = jax.nn.softplus(
                zxbcdt[..., inner + self.conv_channels:].astype(F32)
                + p["dt_bias"])
            return z, xbc, dt

    def _split_conv(self, xbc):
        inner, width = self.inner, self.groups * self.state
        x = xbc[..., :inner]
        x = x.reshape(x.shape[:-1] + (self.heads, self.head_dim))
        b, c = xbc[..., inner:inner + width], xbc[..., inner + width:]
        if self.groups > 1:
            b, c = (v.reshape(v.shape[:-1] + (self.groups, self.state))
                    for v in (b, c))
        return x, b, c

    def _out(self, y, x, z, p):
        """``y`` float32 from the recurrence: the skip, the gate, the norm
        a group of channels, the output projection."""
        with jax.named_scope("ssm.norm"):
            y = y + p["d"][:, None] * x.astype(F32)
            y = y.reshape(y.shape[:-2] + (self.inner,)).astype(z.dtype)
            y, scale = y * jax.nn.silu(z), p["norm"]
            if self.groups > 1:
                split = (self.groups, self.inner // self.groups)
                y = rms_norm(y.reshape(y.shape[:-1] + split),
                             scale.reshape(split),
                             self.eps).reshape(y.shape)
            else:
                y = rms_norm(y, scale, self.eps)
        with jax.named_scope("ssm.out_proj"):
            return mm(y, p["out_proj"])

    def prefill(self, u, p, lengths):
        """The mixer over ``u (R, P, h)``; what the slot will hold is the
        carry at each row's true length and its last ``K - 1`` real
        convolution inputs."""
        z, xbc, dt = self._split_in(u, p)
        with jax.named_scope("ssm.conv"):
            tail = ssd.conv_tail(xbc, lengths, self.conv)
            xbc = jax.nn.silu(ssd.causal_conv(
                xbc, p["conv_w"], p["conv_b"])).astype(u.dtype)
            x, b, cc = self._split_conv(xbc)
        with jax.named_scope("ssm.scan"):
            y, state = ssd.ssd_scan(x, dt, -jnp.exp(p["a_log"]), b, cc,
                                    lengths, self.chunk)
        return self._out(y, x, z, p), {"ssm": state, "conv": tail}

    def cache_rows(self, rows, lengths, max_len: int):
        return rows

    def decode(self, u, pos, cache, p):
        """One token a row: the tail shifted, the carry updated."""
        z, xbc, dt = self._split_in(u, p)
        with jax.named_scope("ssm.conv"):
            xbc, tail = ssd.conv_step(cache["conv"], xbc, p["conv_w"],
                                      p["conv_b"])
            xbc = jax.nn.silu(xbc).astype(u.dtype)
            x, b, cc = self._split_conv(xbc)
        with jax.named_scope("ssm.step"):
            y, state = ssd.ssd_step(cache["ssm"], x, dt,
                                    -jnp.exp(p["a_log"]), b, cc)
        return self._out(y, x, z, p), {"ssm": state, "conv": tail}


# the block's device counters, all float32 sums (docs/OBSERVABILITY.md
# section 3): decode steps that had a live row, and live rows x state layers
# they updated; real prime tokens x state layers a prefill scanned, and the
# token slots it computed for them (padding and partial chunks included)
STAT_KEYS = ("ssm.decode_steps", "ssm.step_rows", "ssm.prefill_tokens",
             "ssm.prefill_slots")


def _state_blocks(blocks: dict) -> list:
    return [b for b in blocks.values() if isinstance(b, StateBlock)]


def decode_stats(blocks: dict, live) -> dict:
    """A decode step's ``ssm.*`` counters."""
    return {"ssm.decode_steps": jnp.any(live).astype(F32),
            "ssm.step_rows": len(_state_blocks(blocks)) * jnp.sum(
                live).astype(F32)}


def prefill_stats(blocks: dict, tokens_shape, lengths) -> dict:
    """A prefill's ``ssm.*`` counters over rows of ``lengths`` padded to
    ``tokens_shape = (R, P)``."""
    mine = _state_blocks(blocks)
    slots = sum(ssd.scanned_slots(*tokens_shape, b.chunk) for b in mine)
    return {"ssm.prefill_tokens": len(mine) * jnp.sum(lengths).astype(F32),
            "ssm.prefill_slots": jnp.asarray(slots, F32)}


# --------------------------------------------------------- the delta rule


class DeltaBlock:
    """One gated delta-rule mixer (``ops/gdn.py`` has the recurrence):
    ``key_heads`` key heads of ``key_dim`` feeding ``value_heads`` value
    heads of ``value_dim`` (value head ``j`` reads key head ``j //
    (value_heads / key_heads)``), a convolution of ``conv`` taps with no
    bias, a norm at ``eps`` and a prefill in chunks of ``chunk`` tokens.
    ``Kw = key_heads * key_dim``, ``Vw = value_heads * value_dim``, ``u (...,
    h)`` the normed stream::

        [q (Kw) | k (Kw) | v (Vw) | z (Vw)] = u W_qkvz       (no bias)
        [b (value_heads) | a (value_heads)] = u W_ba
        [q|k|v]_t <- silu(sum_j w_conv[:, j] * [q|k|v]_{t-(K-1)+j})
        q <- q rsqrt(sum q^2 + 1e-6) key_dim^-1/2,  k <- k rsqrt(sum k^2 + 1e-6)
        beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)   (float32)
        S_t = exp(g_t) S_{t-1} + k_t (x) beta_t (v_t - exp(g_t) S_{t-1}^T k_t)
        o_t = S_t^T q_t
        out = [RMSNorm_w(o_t) * silu(z_t)] W_out

    the l2 norms a head and in float32, the gated norm over each value
    head's ``value_dim`` channels with ONE plain weight ``w (value_dim,)``
    for every head and the gate AFTER it (Mamba-2's gate comes before its
    norm: :class:`StateBlock`'s is not this one).

    **The cache**: ``{"state": (slots, value_heads, key_dim, value_dim)
    float32, "conv": (slots, K - 1, 2 Kw + Vw)}``, whatever ``max_len``:
    2.10 MB + 49 KB a slot and layer at Qwen3-Next's widths, read AND
    written every token.  What a prefill hands over, what an admission
    overwrites and what rows that are not live do is as
    :class:`StateBlock`'s (every decay is at most 1, every key a unit
    vector and every write strength under 1)."""

    L2_EPS = 1e-6

    def __init__(self, key_heads: int, value_heads: int, key_dim: int,
                 value_dim: int, conv: int, eps: float, chunk: int):
        if value_heads % key_heads:
            raise ValueError(f"{value_heads} value heads do not split over "
                             f"{key_heads} key heads")
        self.key_heads, self.value_heads = key_heads, value_heads
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv, self.eps, self.chunk = conv, eps, chunk
        self.key_width = key_heads * key_dim
        self.value_width = value_heads * value_dim
        self.conv_channels = 2 * self.key_width + self.value_width

    def init_weights(self, key, hidden: int, dt, dt_range, a_range) -> dict:
        """Seeded weights: per value head ``softplus(dt_bias)`` and ``A``
        drawn log-uniform from ``dt_range`` / ``a_range`` (their product is
        minus the log of a step's decay where ``a`` is 0)."""
        heads = self.value_heads
        ks = jax.random.split(key, 7)
        step = _log_uniform(ks[3], (heads,), *dt_range)
        return {
            "in_proj": normal(
                ks[0], (hidden, self.conv_channels + self.value_width),
                hidden ** -0.5, dt),
            "ba_proj": normal(ks[1], (hidden, 2 * heads), hidden ** -0.5, dt),
            "conv_w": normal(ks[2], (self.conv_channels, self.conv),
                             self.conv ** -0.5, dt),
            # the recurrence's own parameters stay float32, as the releases
            # keep them; softplus(dt_bias) is the drawn step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "a_log": jnp.log(_log_uniform(ks[4], (heads,), *a_range)),
            "norm": init_norm(ks[5], (self.value_dim,), dt),
            "out_proj": normal(ks[6], (self.value_width, hidden),
                               self.value_width ** -0.5, dt),
        }

    def state_bytes(self) -> int:
        """One slot's float32 carry."""
        return self.value_heads * self.key_dim * self.value_dim * 4

    def init_cache(self, slots: int, max_len: int, dtype):
        return {"state": jnp.zeros((slots, self.value_heads, self.key_dim,
                                    self.value_dim), F32),
                "conv": jnp.zeros((slots, self.conv - 1,
                                   self.conv_channels), dtype)}

    def _project(self, x, p, columns=None):
        """``columns`` of ``u W_qkvz``: all of them a step; an admission
        takes ``[q | k | v]`` first and the gate ``z`` when the recurrence
        is done (two rows of 16,384 tokens' ``z`` are half a gigabyte that
        nothing reads until then)."""
        w = p["in_proj"] if columns is None else p["in_proj"][:, columns]
        with jax.named_scope("gdn.in_proj"):
            return mm(x, w)

    def _strengths(self, x, p):
        """``(beta, g)``: the write strength and the log of the decay a
        value head, float32."""
        with jax.named_scope("gdn.in_proj"):
            ba = mm(x, p["ba_proj"]).astype(F32)
            heads = self.value_heads
            return jax.nn.sigmoid(ba[..., :heads]), -jnp.exp(
                p["a_log"]) * jax.nn.softplus(ba[..., heads:] + p["dt_bias"])

    def _split_conv(self, qkv):
        """``[q | k | v]`` after the convolution as heads: q and k unit
        vectors in float32, rounded once."""
        kw = self.key_width
        keys = (self.key_heads, self.key_dim)

        def unit(x):
            x = x.reshape(x.shape[:-1] + keys).astype(F32)
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + self.L2_EPS)

        q = unit(qkv[..., :kw]) * self.key_dim ** -0.5
        k = unit(qkv[..., kw:2 * kw])
        v = qkv[..., 2 * kw:]
        v = v.reshape(v.shape[:-1] + (self.value_heads, self.value_dim))
        return q.astype(qkv.dtype), k.astype(qkv.dtype), v

    def _out(self, o, z, p):
        """``o`` from the recurrence (float32 from a step, rounded once
        from the chunked form): the norm a head, THEN the gate, the output
        projection."""
        with jax.named_scope("gdn.norm"):
            o = rms_norm(o.astype(z.dtype), p["norm"], self.eps)
            o = o.reshape(z.shape) * jax.nn.silu(z)
        with jax.named_scope("gdn.out_proj"):
            return mm(o, p["out_proj"])

    def prefill(self, u, p, lengths):
        """The mixer over ``u (R, P, h)``; what the slot will hold is the
        carry at each row's true length and its last ``K - 1`` real
        convolution inputs."""
        split = self.conv_channels
        qkv = self._project(u, p, slice(None, split))
        beta, g = self._strengths(u, p)
        with jax.named_scope("gdn.conv"):
            tail = ssd.conv_tail(qkv, lengths, self.conv)
            qkv = jax.nn.silu(ssd.causal_conv(
                qkv, p["conv_w"], None)).astype(u.dtype)
            q, k, v = self._split_conv(qkv)
        with jax.named_scope("gdn.scan"):
            o, state = gdn.gdn_scan(q, k, v, g, beta, lengths, self.chunk)
        z = self._project(u, p, slice(split, None))
        return self._out(o, z, p), {"state": state, "conv": tail}

    def scan_lowering(self, p: int) -> str:
        """What ``gdn.gdn_scan`` takes for this block's rows padded to
        ``p``, traced here and now."""
        return gdn.scan_lowering(p, self.key_heads, self.value_heads,
                                 self.key_dim, self.value_dim, self.chunk)

    def cache_rows(self, rows, lengths, max_len: int):
        return rows

    def decode(self, u, pos, cache, p):
        """One token a row: the tail shifted, the carry decayed, erased
        under the new key and written."""
        qkvz = self._project(u, p)
        qkv, z = (qkvz[..., :self.conv_channels],
                  qkvz[..., self.conv_channels:])
        beta, g = self._strengths(u, p)
        with jax.named_scope("gdn.conv"):
            qkv, tail = ssd.conv_step(cache["conv"], qkv, p["conv_w"], None)
            qkv = jax.nn.silu(qkv).astype(u.dtype)
            q, k, v = self._split_conv(qkv)
        with jax.named_scope("gdn.step"):
            o, state = gdn.gdn_step(cache["state"], q, k, v, g, beta)
        return self._out(o, z, p), {"state": state, "conv": tail}


# the delta block's device counters, all float32 sums
# (docs/OBSERVABILITY.md section 3): the token slots the chunked form
# computed in all delta layers of a prefill (the XLA form: every chunk of
# the bucket, padding included; the kernel: whole chunks up to each row's
# length) and the real prime tokens x delta layers among them; the carry
# bytes the decode steps had to read and write: each LIVE row's carry once
# each way in every delta layer (the static batch moves the idle slots' too)
DELTA_STAT_KEYS = ("gdn.scan_slots", "gdn.real_tokens", "gdn.state_bytes")


def _delta_blocks(blocks: dict, kind=DeltaBlock) -> list:
    """The blocks of exactly ``kind`` (a :class:`ChannelDeltaBlock` counts
    under its own keys)."""
    return [b for b in blocks.values() if type(b) is kind]


def delta_decode_stats(blocks: dict, live) -> dict:
    """A decode step's ``gdn.*`` counter."""
    moved = sum(2 * b.state_bytes() for b in _delta_blocks(blocks))
    return {"gdn.state_bytes": moved * jnp.sum(live).astype(F32)}


def delta_prefill_stats(blocks: dict, tokens_shape, lengths) -> dict:
    """A prefill's ``gdn.*`` counters over rows of ``lengths`` padded to
    ``tokens_shape = (R, P)``."""
    mine = _delta_blocks(blocks)
    slots = sum(gdn.computed_slots(lengths, tokens_shape[1], b.chunk,
                                   b.scan_lowering(tokens_shape[1]))
                for b in mine)
    return {"gdn.real_tokens": len(mine) * jnp.sum(lengths).astype(F32),
            "gdn.scan_slots": jnp.asarray(slots, F32)}


# ----------------------------------------- the delta rule, a decay a channel


class ChannelDeltaBlock(DeltaBlock):
    """One delta-rule mixer whose decay is a key CHANNEL's (Kimi Delta
    Attention; ``ops/gdn.py:kda_scan`` / ``kda_step`` have the recurrence):
    ``heads`` heads, as many key heads as value heads, ``Kw = heads *
    key_dim``, ``Vw = heads * value_dim``, ``u (..., h)`` the normed stream::

        [q (Kw) | k (Kw) | v (Vw)] = u W_qkv                 (no bias)
        [q|k|v]_t <- silu(sum_j w_conv[:, j] * [q|k|v]_{t-(K-1)+j})
        q <- q rsqrt(sum q^2 + 1e-6) key_dim^-1/2,  k <- k rsqrt(sum k^2 + 1e-6)
        f = u W_f  (Kw: full rank),  beta = sigmoid(u W_b)  (heads)
        g = bound * sigmoid(exp(A_log)_head * (f + dt_bias))     (float32)
        S_t = diag(exp(g_t)) S_{t-1} + k_t (x) beta_t (v_t - (diag(exp(g_t))
              S_{t-1})^T k_t),   o_t = S_t^T q_t
        out = [RMSNorm_w(o_t) * sigmoid(u W_og)_head] W_out

    ``g`` lies in ``(bound, 0)`` (``bound`` < 0: the published kernels'
    lower-bound gate), a vector over ``key_dim`` a head and token, ``A_log``
    a head and ``dt_bias`` a channel; the norm over each head's ``value_dim``
    channels with one weight ``w (value_dim,)``; the output gate a HEAD and
    AFTER the norm (an RMS norm forgets a positive scale before it).  The
    chunked form's products are made in blocks of ``block`` rows.

    **The cache** is :class:`DeltaBlock`'s: ``{"state": (slots, heads,
    key_dim, value_dim) float32, "conv": (slots, K - 1, 2 Kw + Vw)}``."""

    def __init__(self, heads: int, key_dim: int, value_dim: int, conv: int,
                 eps: float, chunk: int, block: int, bound: float):
        super().__init__(heads, heads, key_dim, value_dim, conv, eps, chunk)
        if not bound < 0:
            raise ValueError(f"the gate's bound {bound} is not negative")
        self.block, self.bound = block, float(bound)

    def init_weights(self, key, hidden: int, dt, bias_range, a_range) -> dict:
        """Seeded weights: ``exp(A_log)`` a head log-uniform from
        ``a_range``, ``dt_bias`` a channel uniform from ``bias_range``."""
        heads, kw = self.value_heads, self.key_width
        ks = jax.random.split(key, 9)
        return {
            "in_proj": normal(ks[0], (hidden, self.conv_channels),
                              hidden ** -0.5, dt),
            "f_proj": normal(ks[1], (hidden, kw), hidden ** -0.5, dt),
            "b_proj": normal(ks[2], (hidden, heads), hidden ** -0.5, dt),
            "g_proj": normal(ks[3], (hidden, heads), hidden ** -0.5, dt),
            "conv_w": normal(ks[4], (self.conv_channels, self.conv),
                             self.conv ** -0.5, dt),
            # the recurrence's own parameters stay float32
            "a_log": jnp.log(_log_uniform(ks[5], (heads,), *a_range)),
            "dt_bias": jax.random.uniform(ks[6], (heads, self.key_dim), F32,
                                          *bias_range),
            "norm": init_norm(ks[7], (self.value_dim,), dt),
            "out_proj": normal(ks[8], (self.value_width, hidden),
                               self.value_width ** -0.5, dt),
        }

    def _gates(self, x, p):
        """``(beta (..., heads), g (..., heads, key_dim))``: the write
        strength and the log of the decay a channel, float32."""
        with jax.named_scope("kda.gate"):
            f = jnp.dot(x, p["f_proj"].astype(x.dtype),
                        preferred_element_type=F32)
            f = f.reshape(f.shape[:-1] + (self.value_heads, self.key_dim))
            g = self.bound * jax.nn.sigmoid(
                jnp.exp(p["a_log"])[:, None] * (f + p["dt_bias"]))
            return jax.nn.sigmoid(mm(x, p["b_proj"]).astype(F32)), g

    def _out(self, o, x, p):
        """``o`` from the recurrence: the norm a head, THEN the gate a head
        (float32 inside the sigmoid), the output projection."""
        with jax.named_scope("kda.norm"):
            o = rms_norm(o.astype(x.dtype), p["norm"], self.eps)
            gate = jax.nn.sigmoid(mm(x, p["g_proj"]).astype(F32))
            o = o * gate.astype(o.dtype)[..., None]
        with jax.named_scope("kda.out_proj"):
            return mm(o.reshape(o.shape[:-2] + (-1,)), p["out_proj"])

    def _row(self, u, p, lengths):
        with jax.named_scope("kda.in_proj"):
            qkv = mm(u, p["in_proj"])
        beta, g = self._gates(u, p)
        with jax.named_scope("kda.conv"):
            tail = ssd.conv_tail(qkv, lengths, self.conv)
            qkv = jax.nn.silu(ssd.causal_conv(
                qkv, p["conv_w"], None)).astype(u.dtype)
            q, k, v = self._split_conv(qkv)
        with jax.named_scope("kda.scan"):
            o, state = gdn.kda_scan(q, k, v, g, beta, lengths, self.chunk,
                                    self.block)
        return self._out(o, u, p), {"state": state, "conv": tail}

    def prefill(self, u, p, lengths):
        """The mixer over ``u (R, P, h)``, ONE ROW AT A TIME: a token's
        ``[q | k | v]`` twice over and its float32 ``g`` are 77 KB, 5 GB at
        four rows of 16,384, and a row shares nothing with the next.  What
        the slot will hold is as :class:`DeltaBlock`'s."""
        if u.shape[0] == 1:
            return self._row(u, p, lengths)
        out, rows = jax.lax.map(
            lambda x: self._row(x[0][None], p, x[1][None]), (u, lengths))
        return out[:, 0], {name: a[:, 0] for name, a in rows.items()}

    def scan_lowering(self, p: int) -> str:
        """What ``gdn.kda_scan`` takes for this block's rows padded to
        ``p``, traced here and now."""
        return gdn.kda_scan_lowering(p, self.key_dim, self.value_dim,
                                     self.chunk, self.block)

    def decode(self, u, pos, cache, p):
        """One token a row: the tail shifted, the carry decayed a channel,
        erased under the new key and written."""
        with jax.named_scope("kda.in_proj"):
            qkv = mm(u, p["in_proj"])
        beta, g = self._gates(u, p)
        with jax.named_scope("kda.conv"):
            qkv, tail = ssd.conv_step(cache["conv"], qkv, p["conv_w"], None)
            qkv = jax.nn.silu(qkv).astype(u.dtype)
            q, k, v = self._split_conv(qkv)
        with jax.named_scope("kda.step"):
            o, state = gdn.kda_step(cache["state"], q, k, v, g, beta)
        return self._out(o, u, p), {"state": state, "conv": tail}


# the channel-decay block's device counters: :data:`DELTA_STAT_KEYS` under
# its own names
KDA_STAT_KEYS = ("kda.scan_slots", "kda.real_tokens", "kda.state_bytes")


def kda_decode_stats(blocks: dict, live) -> dict:
    """A decode step's ``kda.*`` counter."""
    moved = sum(2 * b.state_bytes()
                for b in _delta_blocks(blocks, ChannelDeltaBlock))
    return {"kda.state_bytes": moved * jnp.sum(live).astype(F32)}


def kda_prefill_stats(blocks: dict, tokens_shape, lengths) -> dict:
    """A prefill's ``kda.*`` counters over rows of ``lengths`` padded to
    ``tokens_shape = (R, P)``."""
    mine = _delta_blocks(blocks, ChannelDeltaBlock)
    slots = sum(gdn.computed_slots(lengths, tokens_shape[1], b.chunk,
                                   b.scan_lowering(tokens_shape[1]))
                for b in mine)
    return {"kda.real_tokens": len(mine) * jnp.sum(lengths).astype(F32),
            "kda.scan_slots": jnp.asarray(slots, F32)}
