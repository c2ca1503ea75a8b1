"""Incremental (single-token) decode step with O(window) attention cache.

The reference samples by re-running the FULL forward over the whole padded
sequence for every generated token (``/root/reference/progen_transformer/
utils.py:106-135``) — O(L) full forwards, O(L²·w) total attention work.
SURVEY.md §2.c calls for a scan-based cached decoder; this module is the
per-token step, designed around the model's three kinds of sequence state:

* **token shift** needs the previous position's POST-NORM activations in
  each block -> one ``(B, dim)`` carry per block;
* **local windowed attention** at position i attends keys in
  ``[prev_window_start(i), i]`` — at most ``2*window`` positions -> a RING
  BUFFER of post-rotary k/v per layer, slot ``pos % (2*window)``.  Which
  slots are valid is closed-form from (pos, slot), no position cache:
  slot s holds ``p_s = pos - ((pos - s) mod 2w)``; it is attendable iff
  ``p_s >= window_start(pos) - window`` (negative p_s = the reference's
  phantom zero-pad window before position 0, reproduced by the zero-
  initialized ring slots);
* **SGU/gMLP** mixes ALL previous positions through a learned causal row
  -> a ``(B, seq_len, hidden/2)`` cache of normed gate activations per
  gMLP layer; step m contracts the cache with weight row m (masked to
  ``n <= m``).

Module/parameter names exactly mirror ``progen_tpu.models.progen.ProGen``
(``attn{i}``/``ff{i}``/``embed``/``norm_out``/``to_logits`` with identical
submodule names), so trained parameters bind directly to the decode graph.

The step trusts ``pos`` to index the SGU weight rows and never
bounds-checks it: callers keep positions in ``[0, decode_len)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.models.progen import ProGenConfig, _dense, _norm, apply_lora
from progen_tpu.ops.local_attention import ATTN_MASK_VALUE
from progen_tpu.ops.rotary import fixed_pos_embedding, rotate_every_two
from progen_tpu.ops.row_write import write_rows


def _shift_with_carry(h, prev):
    """Token shift at one position: the first ceil(d/2) channels come from
    the previous position (``ops/shift.py`` semantics, incremental)."""
    d = h.shape[-1]
    split = d - d // 2
    return jnp.concatenate([prev[..., :split], h[..., split:]], axis=-1)


def _rotate_at(x, sin_row, cos_row):
    """Rotary for one position per row: ``x (B, h, d)``, table rows
    ``(B, d)`` (each row at its own position)."""
    sin_row = sin_row[:, None, :]
    cos_row = cos_row[:, None, :]
    return x * cos_row + rotate_every_two(x) * sin_row


def init_caches(config: ProGenConfig, batch_size: int,
                policy: Policy | None = None,
                decode_len: int | None = None,
                with_sgu: bool = True) -> dict:
    """Zero caches for a fresh decode (a plain pytree, scan-friendly).

    ``decode_len``: positions the decode will actually visit (default
    ``seq_len``).  The attention ring is O(window) regardless; the SGU gate
    cache — the one seq_len-sized buffer — shrinks to ``decode_len`` rows,
    so a 200-token sample from a 4096-seq_len config allocates (and
    contracts per step) 200 rows, not 4096.  Exact because SGU row ``pos``
    is causally masked to columns ``<= pos < decode_len``.

    ``with_sgu=False`` drops the per-slot gate cache entirely — the paged
    engine keeps gate rows in a global page pool (see
    :func:`init_gate_pool`) instead of ``batch x n_rows`` dense slabs.
    """
    c = config
    pol = policy or make_policy()
    dt = pol.compute_dtype
    ring = 2 * c.window_size
    n_rows = min(decode_len or c.seq_len, c.seq_len)
    return {
        "attn_prev": [jnp.zeros((batch_size, c.dim), dt) for _ in range(c.depth)],
        "ff_prev": [jnp.zeros((batch_size, c.dim), dt) for _ in range(c.depth)],
        "k": [jnp.zeros((batch_size, c.heads, ring, c.dim_head), dt)
              for _ in range(c.depth)],
        "v": [jnp.zeros((batch_size, c.heads, ring, c.dim_head), dt)
              for _ in range(c.depth)],
        "sgu_gate": {
            str(i): jnp.zeros((batch_size, n_rows, (c.dim * c.ff_mult) // 2), dt)
            for i in range(c.depth) if c.layer_uses_gmlp(i)
        } if with_sgu else {},
    }


def init_gate_pool(config: ProGenConfig, num_pages: int, page_size: int,
                   policy: Policy | None = None,
                   gate_dtype: str = "bf16") -> dict:
    """Zero global gate-row pool, one ``(num_pages, page_size, hidden/2)``
    array per gMLP layer (keyed like ``sgu_gate``).  Page 0 is the
    all-zeros NULL page and stays zero forever (reads of unowned table
    entries land here and match the dense engine's zero-initialized
    cache); page 1 is the write-sink DUMP page.

    ``gate_dtype="int8"`` allocates the pool in int8 (the 8-bit page
    format); rows are quantized per-row on scatter against the parallel
    f32 scale pool from :func:`init_gate_scale`.  NULL-page reads stay
    exact zeros (0 * scale == 0.0)."""
    c = config
    pol = policy or make_policy()
    if gate_dtype == "int8":
        dt = jnp.int8
    elif gate_dtype == "bf16":
        dt = pol.compute_dtype
    else:
        raise ValueError(f"unknown gate_dtype {gate_dtype!r}; "
                         "use 'bf16' or 'int8'")
    half = (c.dim * c.ff_mult) // 2
    return {
        str(i): jnp.zeros((num_pages, page_size, half), dt)
        for i in range(c.depth) if c.layer_uses_gmlp(i)
    }


def init_gate_scale(config: ProGenConfig, num_pages: int,
                    page_size: int) -> dict:
    """Per-row f32 scale pool for the int8 gate pages: one
    ``(num_pages, page_size)`` array per gMLP layer, mirroring
    :func:`init_gate_pool`'s page layout.  Ones-initialized so a
    never-written row dequantizes to exact zeros."""
    c = config
    return {
        str(i): jnp.ones((num_pages, page_size), jnp.float32)
        for i in range(c.depth) if c.layer_uses_gmlp(i)
    }


class LocalAttentionDecode(nn.Module):
    """One-position attention against the k/v ring buffer."""

    dim: int
    window_size: int
    heads: int
    dim_head: int
    shift: bool
    policy: Policy
    weights: str = "bf16"

    @nn.compact
    def __call__(self, x, sin_row, cos_row, slot, valid, prev, k_cache, v_cache,
                 adapters=None, tenant=None):
        h, d = self.heads, self.dim_head
        inner = h * d
        b = x.shape[0]

        with jax.named_scope("norm.layer"):
            normed = _norm(self.policy, name="norm")(x)
        new_prev = normed
        with jax.named_scope("attn.project"):
            if self.shift:
                normed = _shift_with_carry(normed, prev)

            qkv = _dense(inner * 3, use_bias=False, axes=("embed", "qkv"),
                         policy=self.policy, name="to_qkv",
                         weights=self.weights)(normed)
            if adapters is not None:
                qkv = apply_lora(qkv, normed, adapters["qkv"], tenant)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q, k, v = (t.reshape(b, h, d) for t in (q, k, v))
            q, k, v = (_rotate_at(t, sin_row, cos_row) for t in (q, k, v))

        with jax.named_scope("attn.local"):
            # per-row ring slot (rows may sit at different positions — the
            # continuous-batching engine drives one step with a (B,) pos
            # vector)
            k_cache, v_cache = write_rows((k_cache, v_cache), (k, v), slot,
                                          axis=1)

            sim = jnp.einsum("bhd,bhsd->bhs", q, k_cache,
                             preferred_element_type=jnp.float32) * (d ** -0.5)
            sim = jnp.where(valid[:, None, :], sim, ATTN_MASK_VALUE)
            attn = jax.nn.softmax(sim, axis=-1).astype(v_cache.dtype)
            out = jnp.einsum(
                "bhs,bhsd->bhd", attn, v_cache,
                preferred_element_type=jnp.float32,
            ).astype(v_cache.dtype).reshape(b, inner)
        with jax.named_scope("attn.out"):
            proj = _dense(self.dim, use_bias=True, axes=("qkv", "embed"),
                          policy=self.policy, name="to_out",
                          weights=self.weights)(out)
            if adapters is not None:
                proj = apply_lora(proj, out, adapters["out"], tenant)
        return proj, new_prev, k_cache, v_cache


class SGUDecode(nn.Module):
    """One-position spatial gate: contract the gate cache with weight row m."""

    seq_len: int
    dim_out: int
    policy: Policy
    eps: float = 1e-3
    weights: str = "bf16"

    @nn.compact
    def __call__(self, x, pos, gate_cache, adapters=None, tenant=None):
        n = self.seq_len
        with jax.named_scope("sgu.gate"):
            x, gate = jnp.split(x, 2, axis=-1)
            gate = _norm(self.policy, name="norm")(gate)

        init_scale = self.eps / n

        def symmetric_uniform(key, shape, dtype):
            return jax.random.uniform(key, shape, dtype,
                                      minval=-init_scale, maxval=init_scale)

        if self.weights == "int8":
            weights = self.param("spatial_weights", nn.initializers.zeros,
                                 (n, n), jnp.int8)
            w_scale = self.variable(
                "qscale", "spatial_weights_scale",
                lambda: jnp.ones((n,), jnp.float32)).value
        else:
            weights = self.param("spatial_weights", symmetric_uniform, (n, n),
                                 self.policy.param_dtype)
            w_scale = None
        biases = self.param("spatial_biases", nn.initializers.ones, (n, 1),
                            self.policy.param_dtype)

        # the cache may be shorter than seq_len (short-decode fast path);
        # only weight columns < n_cache can be causally live since pos
        # stays < n_cache for the whole decode.  ``pos`` is (B,): each row
        # reads its own weight row / bias and masks at its own position.
        with jax.named_scope("sgu.spatial"):
            n_cache = gate_cache.shape[1]
            gate_cache = write_rows(gate_cache, gate, pos, axis=0)
            w_rows = weights.astype(jnp.float32)[pos][:, :n_cache]  # (B, n_cache)
            if w_scale is not None:
                # per-ROW scale: each batch row reads weight row pos[b]
                w_rows = w_rows * w_scale[pos][:, None]
            causal = (jnp.arange(n_cache)[None, :] <= pos[:, None])
            w_rows = w_rows * causal.astype(jnp.float32)
            mixed = jnp.einsum("bnd,bn->bd", gate_cache.astype(jnp.float32),
                               w_rows, preferred_element_type=jnp.float32)
            bias_m = biases.astype(jnp.float32)[pos]  # (B, 1)
            mixed = (mixed + bias_m).astype(x.dtype)

        with jax.named_scope("sgu.gate"):
            x = x * mixed
        with jax.named_scope("sgu.proj"):
            out = _dense(self.dim_out, use_bias=True, axes=("mlp_in", "mlp"),
                         policy=self.policy, name="proj_out",
                         weights=self.weights)(x)
            if adapters is not None:
                out = apply_lora(out, x, adapters, tenant)
        return out, gate_cache


class FeedForwardDecode(nn.Module):
    dim: int
    seq_len: int
    ff_mult: int
    glu: bool
    use_sgu: bool
    shift: bool
    policy: Policy
    weights: str = "bf16"

    @nn.compact
    def __call__(self, x, pos, prev, gate_cache, adapters=None, tenant=None):
        hidden = self.dim * self.ff_mult * (2 if self.glu else 1)

        with jax.named_scope("norm.layer"):
            normed = _norm(self.policy, name="norm")(x)
        new_prev = normed
        with jax.named_scope("ffn.dense"):
            if self.shift:
                normed = _shift_with_carry(normed, prev)

            h = _dense(hidden, use_bias=True, axes=("embed", "mlp"),
                       policy=self.policy, name="proj_in",
                       weights=self.weights)(normed)
            if self.glu:
                h, gate = jnp.split(h, 2, axis=-1)
                h = h * nn.gelu(gate)
            else:
                h = nn.gelu(h)

        if self.use_sgu:
            h, gate_cache = SGUDecode(
                seq_len=self.seq_len, dim_out=hidden // 2,
                policy=self.policy, weights=self.weights, name="sgu",
            )(h, pos, gate_cache,
              None if adapters is None else adapters["sgu"], tenant)

        with jax.named_scope("ffn.dense"):
            out = _dense(self.dim, use_bias=True, axes=("mlp", "embed"),
                         policy=self.policy, name="proj_out",
                         weights=self.weights)(h)
        return out, new_prev, gate_cache


class ProGenDecodeStep(nn.Module):
    """One decode step: ``(tok (B,), pos, caches) -> (logits (B, V), caches)``.

    ``pos`` is a traced scalar OR a ``(B,)`` vector — the serving engine
    steps a batch of slots each at its OWN position (continuous batching);
    a scalar broadcasts to all rows.  Every shape is static, so the step
    nests under ``lax.scan``/``jit`` without retracing.
    """

    config: ProGenConfig
    policy: Policy = dataclasses.field(default_factory=make_policy)
    weights: str = "bf16"

    @nn.compact
    def __call__(self, tok, pos, caches, adapters=None, tenant=None):
        cfg, pol = self.config, self.policy
        wsz = cfg.window_size
        ring = 2 * wsz
        b = tok.shape[0]

        with jax.named_scope("embed.tokens"):
            x = nn.Embed(
                cfg.num_tokens, cfg.dim,
                dtype=pol.compute_dtype, param_dtype=pol.param_dtype,
                embedding_init=nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", out_axis=0),
                name="embed",
            )(tok)

        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        with jax.named_scope("attn.rotary"):
            sin_t, cos_t = fixed_pos_embedding(cfg.seq_len, cfg.dim_head)
            sin_row = sin_t[pos].astype(pol.compute_dtype)  # (B, dim_head)
            cos_row = cos_t[pos].astype(pol.compute_dtype)
            slot = pos % ring

            s = jnp.arange(ring)[None, :]
            p_s = pos[:, None] - jnp.mod(pos[:, None] - s, ring)
            w_start = ((pos // wsz) * wsz)[:, None]
            # NOTE no ``p_s >= 0`` clause: in window 0 the reference attends a
            # phantom ZERO-pad previous window (progen.py:90-95) whose keys
            # contribute exp(0 - max) to the softmax denominator; ring slots
            # with negative p_s are untouched zeros, which reproduces that
            # exactly.
            valid = p_s >= w_start - wsz  # (B, ring)

        new: dict[str, Any] = {
            "attn_prev": list(caches["attn_prev"]),
            "ff_prev": list(caches["ff_prev"]),
            "k": list(caches["k"]),
            "v": list(caches["v"]),
            "sgu_gate": dict(caches["sgu_gate"]),
        }

        for i in range(cfg.depth):
            use_gmlp = cfg.layer_uses_gmlp(i)
            attn_ad = None if adapters is None else adapters.get(f"attn{i}")
            ff_ad = None if adapters is None else adapters.get(f"ff{i}")
            attn_out, new["attn_prev"][i], new["k"][i], new["v"][i] = (
                LocalAttentionDecode(
                    dim=cfg.dim, window_size=wsz, heads=cfg.heads,
                    dim_head=cfg.dim_head, shift=cfg.shift_tokens,
                    policy=pol, weights=self.weights, name=f"attn{i}",
                )(x, sin_row, cos_row, slot, valid,
                  caches["attn_prev"][i], caches["k"][i], caches["v"][i],
                  attn_ad, tenant)
            )
            with jax.named_scope("attn.out"):
                x = x + attn_out

            gate_cache = caches["sgu_gate"].get(str(i))
            ff_out, new["ff_prev"][i], gate_cache = FeedForwardDecode(
                dim=cfg.dim, seq_len=cfg.seq_len, ff_mult=cfg.ff_mult,
                glu=(not use_gmlp) and cfg.ff_glu, use_sgu=use_gmlp,
                shift=cfg.shift_tokens, policy=pol, weights=self.weights,
                name=f"ff{i}",
            )(x, pos, caches["ff_prev"][i],
              gate_cache if gate_cache is not None else jnp.zeros(()),
              ff_ad, tenant)
            with jax.named_scope("ffn.dense"):
                x = x + ff_out
            if str(i) in new["sgu_gate"]:
                new["sgu_gate"][str(i)] = gate_cache

        with jax.named_scope("head.logits"):
            h = _norm(pol, name="norm_out")(x)
            logits = _dense(cfg.num_tokens, use_bias=True,
                            axes=("embed", "vocab"),
                            policy=pol, name="to_logits")(h)
            return pol.cast_to_output(logits), new


class SGUDecodePaged(nn.Module):
    """One-position spatial gate against the global page pool.

    Identical math and parameter names to :class:`SGUDecode` (trained
    params bind to either graph); the per-slot ``(B, n_rows, d)`` gate
    cache is replaced by a pooled ``(num_pages, page_size, d)`` array plus
    a per-row page table.  The freshly normed gate row is scattered into
    the row's current page (``write_ok`` redirects paused/done/inactive
    rows to the DUMP page), then the ragged paged contraction reproduces
    the dense masked einsum (see ``ops/pallas_paged_attention.py``).
    """

    seq_len: int
    dim_out: int
    n_rows: int
    policy: Policy
    impl: str = "xla"
    eps: float = 1e-3
    weights: str = "bf16"
    gate_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x, pos, pool, table, write_ok, pool_scale=None,
                 adapters=None, tenant=None):
        from progen_tpu.ops.pallas_paged_attention import (
            paged_gate_mix, write_gate_row)

        n = self.seq_len
        with jax.named_scope("sgu.gate"):
            x, gate = jnp.split(x, 2, axis=-1)
            gate = _norm(self.policy, name="norm")(gate)

        init_scale = self.eps / n

        def symmetric_uniform(key, shape, dtype):
            return jax.random.uniform(key, shape, dtype,
                                      minval=-init_scale, maxval=init_scale)

        if self.weights == "int8":
            weights = self.param("spatial_weights", nn.initializers.zeros,
                                 (n, n), jnp.int8)
            w_scale = self.variable(
                "qscale", "spatial_weights_scale",
                lambda: jnp.ones((n,), jnp.float32)).value
        else:
            weights = self.param("spatial_weights", symmetric_uniform, (n, n),
                                 self.policy.param_dtype)
            w_scale = None
        biases = self.param("spatial_biases", nn.initializers.ones, (n, 1),
                            self.policy.param_dtype)

        with jax.named_scope("sgu.spatial"):
            if self.gate_dtype == "int8":
                # quantize-on-scatter: the row's int8 code and its f32 scale
                # land in twin pools through the same dump-redirected target
                pool, pool_scale = write_gate_row(pool, table, pos, gate,
                                                  write_ok, scale=pool_scale)
            else:
                pool = write_gate_row(pool, table, pos, gate, write_ok)
            mixed = paged_gate_mix(weights, biases, pool, table, pos,
                                   n_rows=self.n_rows, impl=self.impl,
                                   w_scale=w_scale, pool_scale=pool_scale)
            mixed = mixed.astype(x.dtype)

        with jax.named_scope("sgu.gate"):
            x = x * mixed
        with jax.named_scope("sgu.proj"):
            out = _dense(self.dim_out, use_bias=True, axes=("mlp_in", "mlp"),
                         policy=self.policy, name="proj_out",
                         weights=self.weights)(x)
            if adapters is not None:
                out = apply_lora(out, x, adapters, tenant)
        return out, pool, pool_scale


class FeedForwardDecodePaged(nn.Module):
    """gMLP feed-forward step over the paged gate pool (parameter-name
    compatible with :class:`FeedForwardDecode`)."""

    dim: int
    seq_len: int
    ff_mult: int
    n_rows: int
    shift: bool
    policy: Policy
    impl: str = "xla"
    weights: str = "bf16"
    gate_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x, pos, prev, pool, table, write_ok, pool_scale=None,
                 adapters=None, tenant=None):
        hidden = self.dim * self.ff_mult

        with jax.named_scope("norm.layer"):
            normed = _norm(self.policy, name="norm")(x)
        new_prev = normed
        with jax.named_scope("ffn.dense"):
            if self.shift:
                normed = _shift_with_carry(normed, prev)

            h = _dense(hidden, use_bias=True, axes=("embed", "mlp"),
                       policy=self.policy, name="proj_in",
                       weights=self.weights)(normed)
            h = nn.gelu(h)

        h, pool, pool_scale = SGUDecodePaged(
            seq_len=self.seq_len, dim_out=hidden // 2, n_rows=self.n_rows,
            policy=self.policy, impl=self.impl, weights=self.weights,
            gate_dtype=self.gate_dtype, name="sgu",
        )(h, pos, pool, table, write_ok, pool_scale,
          None if adapters is None else adapters["sgu"], tenant)

        with jax.named_scope("ffn.dense"):
            out = _dense(self.dim, use_bias=True, axes=("mlp", "embed"),
                         policy=self.policy, name="proj_out",
                         weights=self.weights)(h)
        return out, new_prev, pool, pool_scale


class ProGenPagedDecodeStep(nn.Module):
    """One paged decode step: ``(tok, pos, caches, table, write_ok) ->
    (logits, caches)``.

    Same graph as :class:`ProGenDecodeStep` except gMLP layers read/write
    the global gate-row pool (``caches["sgu_pool"]``) through the per-row
    page ``table`` instead of a per-slot dense cache.  ``write_ok`` masks
    the pool scatter only — ring/carry writes are merged by liveness in
    the engine's chunk body (a paused row must not clobber its carries
    with a masked step's values, since its ``pos`` does not advance).
    """

    config: ProGenConfig
    n_rows: int
    policy: Policy = dataclasses.field(default_factory=make_policy)
    impl: str = "xla"
    weights: str = "bf16"
    gate_dtype: str = "bf16"

    @nn.compact
    def __call__(self, tok, pos, caches, table, write_ok, adapters=None,
                 tenant=None):
        cfg, pol = self.config, self.policy
        wsz = cfg.window_size
        ring = 2 * wsz
        b = tok.shape[0]

        with jax.named_scope("embed.tokens"):
            x = nn.Embed(
                cfg.num_tokens, cfg.dim,
                dtype=pol.compute_dtype, param_dtype=pol.param_dtype,
                embedding_init=nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", out_axis=0),
                name="embed",
            )(tok)

        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        with jax.named_scope("attn.rotary"):
            sin_t, cos_t = fixed_pos_embedding(cfg.seq_len, cfg.dim_head)
            sin_row = sin_t[pos].astype(pol.compute_dtype)
            cos_row = cos_t[pos].astype(pol.compute_dtype)
            slot = pos % ring

            s = jnp.arange(ring)[None, :]
            p_s = pos[:, None] - jnp.mod(pos[:, None] - s, ring)
            w_start = ((pos // wsz) * wsz)[:, None]
            valid = p_s >= w_start - wsz  # (B, ring); see ProGenDecodeStep

        new: dict[str, Any] = {
            "attn_prev": list(caches["attn_prev"]),
            "ff_prev": list(caches["ff_prev"]),
            "k": list(caches["k"]),
            "v": list(caches["v"]),
            "sgu_pool": dict(caches["sgu_pool"]),
        }
        if self.gate_dtype == "int8":
            new["sgu_pool_scale"] = dict(caches["sgu_pool_scale"])

        for i in range(cfg.depth):
            use_gmlp = cfg.layer_uses_gmlp(i)
            attn_ad = None if adapters is None else adapters.get(f"attn{i}")
            ff_ad = None if adapters is None else adapters.get(f"ff{i}")
            attn_out, new["attn_prev"][i], new["k"][i], new["v"][i] = (
                LocalAttentionDecode(
                    dim=cfg.dim, window_size=wsz, heads=cfg.heads,
                    dim_head=cfg.dim_head, shift=cfg.shift_tokens,
                    policy=pol, weights=self.weights, name=f"attn{i}",
                )(x, sin_row, cos_row, slot, valid,
                  caches["attn_prev"][i], caches["k"][i], caches["v"][i],
                  attn_ad, tenant)
            )
            with jax.named_scope("attn.out"):
                x = x + attn_out

            if use_gmlp:
                pool_scale = (caches["sgu_pool_scale"][str(i)]
                              if self.gate_dtype == "int8" else None)
                ff_out, new["ff_prev"][i], new_pool, new_scale = (
                    FeedForwardDecodePaged(
                        dim=cfg.dim, seq_len=cfg.seq_len, ff_mult=cfg.ff_mult,
                        n_rows=self.n_rows, shift=cfg.shift_tokens,
                        policy=pol, impl=self.impl, weights=self.weights,
                        gate_dtype=self.gate_dtype, name=f"ff{i}",
                    )(x, pos, caches["ff_prev"][i],
                      caches["sgu_pool"][str(i)], table, write_ok,
                      pool_scale, ff_ad, tenant)
                )
                new["sgu_pool"][str(i)] = new_pool
                if self.gate_dtype == "int8":
                    new["sgu_pool_scale"][str(i)] = new_scale
            else:
                ff_out, new["ff_prev"][i], _ = FeedForwardDecode(
                    dim=cfg.dim, seq_len=cfg.seq_len, ff_mult=cfg.ff_mult,
                    glu=cfg.ff_glu, use_sgu=False,
                    shift=cfg.shift_tokens, policy=pol, weights=self.weights,
                    name=f"ff{i}",
                )(x, pos, caches["ff_prev"][i], jnp.zeros(()), ff_ad, tenant)
            with jax.named_scope("ffn.dense"):
                x = x + ff_out

        with jax.named_scope("head.logits"):
            h = _norm(pol, name="norm_out")(x)
            logits = _dense(cfg.num_tokens, use_bias=True,
                            axes=("embed", "vocab"),
                            policy=pol, name="to_logits")(h)
            return pol.cast_to_output(logits), new
