"""Trinity's (``afmoe``) forward pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 34, PERF.md
section 4), not from ``progen_tpu.models``: nothing of the program is
imported.  No cache (every position attends over the keys and values of the
whole row under a mask), no kernels, the sliding window as a mask on ``i -
j``, routing by a top-k of ``sigmoid + bias``, a dense loop over the
experts the chip holds (every held expert runs on every token and is
weighted by what the router gave it, zero where it was not chosen), the
shared expert, the leading dense layer.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.

Departures from the release (``modeling_afmoe.py``), each noted where it is
made: (1) the rotation is the half-split form on the stored column order —
the release's own form; with seeded weights nothing hangs on it; (2) the
chip's SHARE: the router is ``num_experts`` wide whatever is held, and the
layer adds the terms of the held experts (``first_expert <= i <
first_expert + experts_held``) only, before the post-MLP norm; (3) no
load-balancing loss or bias update (``load_balance_coeff``, training only);
(4) weights are upcast where used, one matrix at a time, and attention runs
over blocks of query rows (one ``lax.map`` body a layer, each block against
every key under the mask) so that no ``(heads, T, T)`` tensor exists;
(5) the embedding's muP factor is ``sqrt(hidden_size)``.

There is ONE path and it is float32.  Its arithmetic goes through four
named operations — :func:`product` (every matrix product), :func:`softmax`,
:func:`rms_norm` and :func:`sigmoid` — so that
``perf/tools/trinity_lowp.py`` can wrap them and show that the cell's limits
refuse the same equations computed one notch below the stated precision.
Nothing here knows of that.

Layer ``l``: ``a = x + N_post_attn(Attn_l(N_in(x)))``; ``out = a +
N_post_mlp(F_l(N_pre_mlp(a)))``; ``F_l`` the dense SwiGLU for ``l <
num_dense_layers``, else ``S(u) + sum_i w_i E_i(u)``.
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


def sigmoid(x):
    return jax.nn.sigmoid(x.astype(F32))


def rms_norm(x, scale, eps):
    xs = x.astype(F32)
    var = jnp.mean(xs * xs, axis=-1, keepdims=True)
    return xs * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """Half-split rotation over the last axis of ``x (T, heads, d)`` at
    ``positions (T,)`` (departure 1)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attention(x, p, cfg, kind, q_block):
    """Gated grouped-query attention over one row ``x (T, h)``; ``kind`` is
    the layer's entry of ``layer_types``."""
    t, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = product("th,hd->td", x, p["wq"]).reshape(t, heads, d)
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, d)
    gate = sigmoid(product("th,hd->td", x, p["wgate"]))
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    sliding = kind == "sliding_attention"
    if sliding:                 # a full-attention layer has no rotation
        at = jnp.arange(t)
        q, k = (rope(q, at, cfg["rope_theta"]),
                rope(k, at, cfg["rope_theta"]))
    # query head h reads key/value head h // (heads / kv)
    q = q.reshape(t, kv, heads // kv, d)
    scale = d ** -0.5
    # blocks of query rows, each against every key under the mask
    # (departure 4); the last block is padded with rows nothing reads
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - t), (0, 0), (0, 0), (0, 0)))

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        logits = product("qkgd,tkd->kgqt", rows, k) * scale
        gap = s + jnp.arange(q_block)[:, None] - jnp.arange(t)[None, :]
        seen = gap >= 0
        if sliding:
            seen = seen & (gap < cfg["sliding_window"])
        probs = softmax(jnp.where(seen, logits, -jnp.inf))
        return product("kgqt,tkd->qkgd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads * d)[:t]
    return product("td,dh->th", o * gate, p["wo"])


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok``
    largest of ``sigmoid(u W_r) + bias``; the weights are the chosen
    sigmoids alone, normalised to sum to 1 (``route_norm``) and times
    ``route_scale``."""
    scores = sigmoid(product("th,he->te", u, p["w"]))
    _, ids = jax.lax.top_k(scores + p["bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg["route_scale"]


def routed(u, router, experts, cfg):
    """This chip's share of the ROUTED experts over ``u (T, h)``
    (departure 2) and the router's choices."""
    ids, w = route(u, router, cfg)
    first = cfg.get("first_expert", 0)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")})
        return y + w_e[:, None] * out

    held = cfg.get("experts_held", cfg["num_experts"])
    return jax.lax.fori_loop(0, held, add_expert,
                             jnp.zeros(u.shape, F32)), ids


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(expert layers, T, k)``."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])       # departure 5
    chosen = []
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        attn = attention(rms_norm(x, n[0], eps), layer["attn"], cfg,
                         cfg["layer_types"][i], q_block)
        a = x + rms_norm(attn, n[1], eps)
        u = rms_norm(a, n[2], eps)
        if i < cfg["num_dense_layers"]:
            f = swiglu(u, layer["ffn"])
        else:
            m, ids = routed(u, layer["router"], layer["experts"], cfg)
            chosen.append(ids)
            f = m + swiglu(u, layer["shared"])
        x = a + rms_norm(f, n[3], eps)
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
