"""LFM2's (``lfm2_moe``) forward pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 46, PERF.md
section 4), not from ``progen_tpu``: nothing of the program is imported.
No cache (the short convolution is ``conv_L_cache`` shifted copies of the
row's gated input; every attention position attends over the keys and
values of the whole row under a causal mask), no kernels, routing by a
top-k of ``sigmoid + bias``, a dense loop over the experts the chip holds
(every held expert runs on every token and is weighted by what the router
gave it, zero where it was not chosen), the leading dense layers, the
``embedding_norm`` and the tied head.  One row at a time, attention over
blocks of query rows, weights upcast where used one matrix at a time, so
that it fits beside the program on the chip.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.

The equations, letter for letter as ISSUE 46 states them (``N`` an RMSNorm
``x * rsqrt(mean(x^2) + norm_eps) * w``, ``w`` as it is)::

    r = x + Mixer_l(N_op(x));   x' = r + FFN_l(N_ffn(r))
    conv:  [B | C | X] = u W_in;  z = B * X;
           c_t = sum_j w[:, j] * z_{t-(K-1)+j};  out = (C * c) W_out
    attn:  q, k RMS-normed per head over d = h / H, then rotated
           (half-split pairing, theta, positions from 0); scores
           q k^T / sqrt(d), causal, softmax; H / KV query heads a key head
    moe:   s = sigmoid(u W_r); the top-k of s + b are chosen;
           w_i = scale * s_i / (sum_chosen s + 1e-6);
           y = sum_i w_i W2_i(silu(W1_i u) * W3_i u)
    head:  logits = N_emb(x_L) E^T

Departures from the release (``modeling_lfm2_moe.py``), each what the
configuration file lists under ``assumed``: (1) the rotation is the
half-split form on the stored column order — with seeded weights nothing
hangs on it; (2) ``[B | C | X]`` is the order of the split of ``W_in``'s
columns; (3) the ``1e-6`` of the renormalisation; (4) the head is the
embedding; (5) the chip's SHARE: the router is ``num_experts`` wide
whatever is held, and the layer adds the terms of the held experts
(``first_expert <= i < first_expert + experts_held``) only — the uncut
layer is ``experts_held == num_experts``.

There is ONE path and it is float32.  Its arithmetic goes through five
named operations — :func:`product` (every matrix product), :func:`softmax`,
:func:`rms_norm`, :func:`sigmoid` and :func:`island` (the elementwise
values the program keeps in its compute dtype or sums in float32: the two
gates and the convolution's taps) — so that ``perf/tools/lfm2_lowp.py`` can
wrap them and show that the cell's limits refuse the same equations
computed one notch below the stated precision.  Nothing here knows of that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROUTE_EPS = 1e-6        # departure 3


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


def island(x):
    """A value of the short convolution's elementwise arithmetic."""
    return x.astype(F32)


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


def gated_input(x, p):
    """``(z = B * X, C)`` of one row ``x (T, h)`` (departure 2)."""
    b, c, xs = jnp.split(product("th,hd->td", x, p["in_proj"]), 3, axis=-1)
    return island(island(b) * island(xs)), island(c)


def short_conv(x, p, cfg):
    """The double-gated short convolution over one row ``x (T, h)``."""
    t, _ = x.shape
    taps = cfg["conv_L_cache"]
    z, c = gated_input(x, p)
    # depthwise, causal: tap j reads the input taps - 1 - j tokens back
    front = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    conv = island(sum(island(front[j:j + t]) * island(p["conv_w"][:, j])
                      for j in range(taps)))
    return product("td,dh->th", island(c * conv), p["out_proj"])


def attention(x, p, cfg, q_block):
    """Grouped-query attention over one row ``x (T, h)``: q and k normed
    per head, rotated, causal."""
    t, h = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    eps = cfg["norm_eps"]
    q = product("th,hd->td", x, p["wq"]).reshape(t, heads, d)
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, d)
    at = jnp.arange(t)
    q = rope(rms_norm(q, p["q_norm"], eps), at, cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"], eps), at, cfg["rope_theta"])
    # query head h reads key/value head h // (heads / kv)
    q = q.reshape(t, kv, heads // kv, d)
    scale = d ** -0.5
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - t), (0, 0), (0, 0), (0, 0)))

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        logits = product("qkgd,tkd->kgqt", rows, k) * scale
        seen = s + jnp.arange(q_block)[:, None] >= jnp.arange(t)[None, :]
        probs = softmax(jnp.where(seen, logits, -jnp.inf))
        return product("kgqt,tkd->qkgd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads * d)[:t]
    return product("td,dh->th", o, p["wo"])


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok``
    largest of ``sigmoid(u W_r) + bias``; the weights are the chosen
    sigmoids alone, over their sum plus ``1e-6`` (``norm_topk_prob``),
    times ``routed_scaling_factor``."""
    scores = sigmoid(product("th,he->te", u, p["w"]))
    _, ids = jax.lax.top_k(scores + p["bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return ids, w * cfg["routed_scaling_factor"]


def routed(u, router, experts, cfg):
    """This chip's share of the experts over ``u (T, h)`` (departure 5) and
    the router's choices."""
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
    eps = cfg["norm_eps"]
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        u = rms_norm(x, n[0], eps)
        mixed = (short_conv(u, layer["mixer"], cfg)
                 if cfg["layer_types"][i] == "conv"
                 else attention(u, layer["mixer"], cfg, q_block))
        r = x + mixed
        u = rms_norm(r, n[1], eps)
        if i < cfg["num_dense_layers"]:
            x = r + swiglu(u, layer["ffn"])
            continue
        y, ids = routed(u, layer["router"], layer["experts"], cfg)
        chosen.append(ids)
        x = r + y
    x = rms_norm(x, params["final_norm"], eps)
    if logit_positions is not None:
        x = x[logit_positions]
    logits = product("td,vd->tv", x, params["embed"])       # departure 4
    return logits.astype(F32), jnp.stack(chosen)


def forward(params, tokens, cfg, **kwargs):
    """``tokens (B, T)`` -> logits ``(B, T or K, V)``, one row at a time."""
    positions = kwargs.pop("logit_positions", None)
    rows = [forward_row(params, tokens[i], cfg,
                        logit_positions=None if positions is None
                        else positions[i], **kwargs)[0]
            for i in range(tokens.shape[0])]
    return jnp.stack(rows)
