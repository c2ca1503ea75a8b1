"""LongCat-Flash's forward pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 28, PERF.md
section 4), not from ``progen_tpu.models.longcat``: nothing of the program
is imported.  No cache, no kernels, the NON-absorbed latent attention (keys
and values are expanded from the latent for every position), a dense loop
over the experts the chip holds (every held expert runs on every token and
is weighted by what the router gave it, zero where it was not chosen).
Callers wrap calls in ``jax.default_matmul_precision("highest")``.

The weights are data: the nested dict the program stores, in the dtype it
stores them in (bfloat16).  Each matrix is upcast to float32 where it is
used, one at a time (a float32 copy of the whole cut is 20.7 GB), and
attention runs over blocks of query rows so that no ``(heads, T, T)`` score
tensor exists.

There is ONE path and it is float32.  Its arithmetic goes through three
named operations — :func:`product` (every matrix product), :func:`softmax`
and :func:`rms_norm` — so that ``perf/tools/longcat_lowp.py`` can wrap them
and show that the cell's limits refuse the same equations computed one
notch below the stated precision.  Nothing here knows of that.

One layer (a shortcut-connected "double layer")::

    a = x + MLA0(N0(x));  u = N1(a);  m = MoE(u);  b = a + FFN0(u)
    c = b + MLA1(N2(b));  out = c + FFN1(N3(c)) + m

The expert layer is ONE CHIP'S SHARE: the router is 768 wide and picks 12
whatever is held; the layer adds the terms of the held real experts
(``first_expert <= i < first_expert + experts_held``) and of all identity
experts (``i >= n_routed_experts``), and leaves out what absent experts
would add.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

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


def rope_tables(positions, dim, theta):
    """``positions (T,)`` -> ``sin, cos (T, dim / 2)`` in float32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    return jnp.sin(ang), jnp.cos(ang)


def rope(x, sin, cos):
    """Half-split rotation over the last axis of ``x (T, ..., dim)``; the
    tables broadcast over the axes between."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = sin.reshape(shape), cos.reshape(shape)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mla(x, p, cfg, q_block):
    """Non-absorbed latent attention over one row ``x (T, h)``."""
    t, h = x.shape
    heads = cfg["num_attention_heads"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    c_q = rms_norm(product("th,hr->tr", x, p["wqa"]), p["q_norm"], eps)
    q = product("tr,rd->td", c_q, p["wqb"])
    if cfg["mla_scale_q_lora"]:
        q = q * math.sqrt(h / cfg["q_lora_rank"])
    q = q.reshape(t, heads, nope + rot)
    kva = product("th,hr->tr", x, p["wkva"])
    c_kv = rms_norm(kva[:, :rank], p["kv_norm"], eps)
    if cfg["mla_scale_kv_lora"]:
        c_kv = c_kv * math.sqrt(h / rank)
    kv = product("tr,rd->td", c_kv, p["wkvb"]).reshape(t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    sin, cos = rope_tables(jnp.arange(t), rot, cfg["rope_theta"])
    q_rot = rope(q[..., nope:], sin, cos)
    k_rot = rope(kva[:, rank:], sin, cos)            # shared by the heads
    scale = 1.0 / math.sqrt(nope + rot)
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
    """``(ids (T, k), weights (T, k))``: the ``moe_topk`` largest of ``p +
    b`` over all ``n_routed_experts + zero_expert_num`` outputs, weighted
    by ``routed_scaling_factor * p`` (not renormalised)."""
    probs = softmax(product("th,he->te", u, p["w"]))
    _, ids = jax.lax.top_k(probs + p["bias"].astype(probs.dtype),
                           cfg["moe_topk"])
    w = jnp.take_along_axis(probs, ids, axis=-1)
    return ids, w * cfg["routed_scaling_factor"]


def moe(u, router, experts, cfg):
    """This chip's share of the expert layer over ``u (T, h)``."""
    ids, w = route(u, router, cfg)
    n_real = cfg["n_routed_experts"]
    w_identity = jnp.sum(jnp.where(ids >= n_real, w, 0.0), axis=-1)
    y = w_identity[:, None] * u
    first = cfg.get("first_expert", 0)
    for e in range(cfg["experts_held"]):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")})
        y = y + w_e[:, None] * out
    return y, ids


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(layers, T, k)``."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    chosen = []
    for layer in params["layers"]:
        n = layer["norm"]
        a = x + mla(rms_norm(x, n[0], eps), layer["attn"][0], cfg, q_block)
        u = rms_norm(a, n[1], eps)
        m, ids = moe(u, layer["router"], layer["experts"], cfg)
        chosen.append(ids)
        b = a + swiglu(u, layer["ffn"][0])
        c = b + mla(rms_norm(b, n[2], eps), layer["attn"][1], cfg, q_block)
        x = c + swiglu(rms_norm(c, n[3], eps), layer["ffn"][1]) + m
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
