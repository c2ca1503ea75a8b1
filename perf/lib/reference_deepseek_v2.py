"""DeepSeek-V2's forward pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 32, PERF.md
section 4), not from ``progen_tpu.models``: nothing of the program is
imported.  No cache, no kernels, the NON-absorbed latent attention (keys
and values are expanded from the latent for every position), the YaRN
frequency table from its closed form, group-limited routing by reshape /
max / top-k, a dense loop over the experts the chip holds (every held
expert runs on every token and is weighted by what the router gave it, zero
where it was not chosen), the shared experts, the leading dense layer.
Callers wrap calls in ``jax.default_matmul_precision("highest")``.

Departures from the release (``modeling_deepseek.py``), each noted where it
is made: (1) the rotation is the half-split form on the stored column
order, where the release de-interleaves pairs first — with seeded weights a
column permutation of ``W_qb`` / ``W_kva``; (2) the chip's SHARE: the
router is 160 wide with 8 groups whatever is held, and the layer adds the
terms of the held experts (``first_expert <= i < first_expert +
experts_held``) only; (3) no auxiliary loss (``seq_aux``, training only);
(4) weights are upcast where used, one matrix at a time, and attention runs
over blocks of query rows so that no ``(heads, T, T)`` tensor exists.

There is ONE path and it is float32.  Its arithmetic goes through three
named operations — :func:`product` (every matrix product), :func:`softmax`
and :func:`rms_norm` — so that ``perf/tools/deepseek_v2_lowp.py`` can wrap
them and show that the cell's limits refuse the same equations computed
one notch below the stated precision.  Nothing here knows of that.

Layer ``l``: ``a = x + MLA(N(x))``; ``out = a + F_l(N'(a))``; ``F_l`` the
dense SwiGLU for ``l < first_k_dense_replace``, else ``sum_i w_i E_i(u) +
S(u)`` with ``S`` the shared experts as one SwiGLU.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def product(spec, a, b):
    """Every matrix product of the forward pass (``jnp.einsum`` over two
    operands), in float32."""
    return jnp.einsum(spec, a.astype(F32), b.astype(F32))


def softmax(x):
    """Over the last axis, in float32."""
    return jax.nn.softmax(x.astype(F32), axis=-1)


def rms_norm(x, scale, eps):
    xs = x.astype(F32)
    var = jnp.mean(xs * xs, axis=-1, keepdims=True)
    return xs * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def yarn_correction_dim(rotations, dim, base, original_max):
    """The (fractional) index of the frequency whose period fits the
    original context ``rotations`` times."""
    return (dim * math.log(original_max / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_inv_freq(dim, base, scaling):
    """``inv_freq (dim / 2,)`` float64: ``f_i = base^(-2i/dim)`` kept
    below ``low``, ``f_i / factor`` above ``high``, a linear ramp between."""
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    g = f / scaling["factor"]
    orig = scaling["original_max_position_embeddings"]
    low = max(math.floor(yarn_correction_dim(
        scaling["beta_fast"], dim, base, orig)), 0)
    high = min(math.ceil(yarn_correction_dim(
        scaling["beta_slow"], dim, base, orig)), dim - 1)
    span = high - low if high != low else 0.001
    r = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / span, 0, 1)
    return g * r + f * (1 - r)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(positions, dim, base, scaling):
    """``positions (T,)`` -> ``sin, cos (T, dim / 2)`` in float32, times
    ``mscale / mscale_all_dim`` of the scaling (1 at the published keys)."""
    inv = jnp.asarray(yarn_inv_freq(dim, base, scaling), F32)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    m = (yarn_mscale(scaling["factor"], scaling["mscale"])
         / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
    return jnp.sin(ang) * m, jnp.cos(ang) * m


def rope(x, sin, cos):
    """Half-split rotation over the last axis of ``x (T, ..., dim)``
    (departure 1); the tables broadcast over the axes between."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = sin.reshape(shape), cos.reshape(shape)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mla(x, p, cfg, q_block):
    """Non-absorbed latent attention over one row ``x (T, h)``."""
    t, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    scaling = cfg["rope_scaling"]
    c_q = rms_norm(product("th,hr->tr", x, p["wqa"]), p["q_norm"], eps)
    q = product("tr,rd->td", c_q, p["wqb"]).reshape(t, heads, nope + rot)
    kva = product("th,hr->tr", x, p["wkva"])
    c_kv = rms_norm(kva[:, :rank], p["kv_norm"], eps)
    kv = product("tr,rd->td", c_kv, p["wkvb"]).reshape(t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    sin, cos = rope_tables(jnp.arange(t), rot, cfg["rope_theta"], scaling)
    q_rot = rope(q[..., nope:], sin, cos)
    k_rot = rope(kva[:, rank:], sin, cos)            # shared by the heads
    m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    scale = (nope + rot) ** -0.5 * m * m
    outs = []
    for s in range(0, t, q_block):
        e = min(s + q_block, t)
        logits = (product("qhd,khd->hqk", q[s:e, :, :nope], k_nope[:e])
                  + product("qhd,kd->hqk", q_rot[s:e], k_rot[:e])) * scale
        causal = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        probs = softmax(jnp.where(causal[None], logits, -jnp.inf))
        outs.append(product("hqk,khd->qhd", probs, v[:e]))
    o = jnp.concatenate(outs, axis=0).reshape(t, heads * vd)
    return product("td,dh->th", o, p["wo"])


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: group-limited greedy.  The best
    probability of each of ``n_group`` groups of consecutive experts, the
    ``topk_group`` best groups, the ``num_experts_per_tok`` largest
    probabilities inside them; weights ``routed_scaling_factor * p`` from
    the unmasked softmax (``norm_topk_prob`` false)."""
    probs = softmax(product("th,he->te", u, p["w"]))
    t, n = probs.shape
    groups = cfg["n_group"]
    score = jnp.max(probs.reshape(t, groups, n // groups), axis=-1)
    _, best = jax.lax.top_k(score, cfg["topk_group"])
    group_of = jnp.arange(n) // (n // groups)
    allowed = jnp.any(group_of[None, :, None] == best[:, None, :], axis=-1)
    _, ids = jax.lax.top_k(jnp.where(allowed, probs, 0.0),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(probs, ids, axis=-1)
    return ids, w * cfg["routed_scaling_factor"]


def routed(u, router, experts, cfg):
    """This chip's share of the ROUTED experts over ``u (T, h)``
    (departure 2) and the router's choices."""
    ids, w = route(u, router, cfg)
    first = cfg.get("first_expert", 0)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")})
        return y + w_e[:, None] * out

    return jax.lax.fori_loop(0, cfg["experts_held"], add_expert,
                             jnp.zeros(u.shape, F32)), ids


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(expert layers, T, k)``."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    chosen = []
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        a = x + mla(rms_norm(x, n[0], eps), layer["attn"], cfg, q_block)
        u = rms_norm(a, n[1], eps)
        if i < cfg["first_k_dense_replace"]:
            x = a + swiglu(u, layer["ffn"])
            continue
        m, ids = routed(u, layer["router"], layer["experts"], cfg)
        chosen.append(ids)
        x = a + m + swiglu(u, layer["shared"])
    x = rms_norm(x, params["final_norm"], eps)
    if logit_positions is not None:
        x = x[logit_positions]
    logits = product("td,dv->tv", x, params["head"])
    return logits.astype(F32), jnp.stack(chosen)


def forward(params, tokens, cfg, **kwargs):
    """``tokens (B, T)`` -> logits ``(B, T or K, V)``, one row at a time."""
    positions = kwargs.pop("logit_positions", None)
    rows = [forward_row(params, tokens[i], cfg,
                        logit_positions=None if positions is None
                        else positions[i], **kwargs)[0]
            for i in range(tokens.shape[0])]
    return jnp.stack(rows)
