"""Granite 4.0-H's (``granitemoehybrid``, no experts) forward pass in plain
``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 38, PERF.md
section 4), not from ``progen_tpu``: nothing of the program is imported.
No cache, no chunks: the Mamba-2 recurrence is a sequential ``lax.scan``
over the row's tokens, one ``(heads, d_head, N)`` state carried from token
to token; the convolution is four shifted copies of the row; attention is a
causal mask over the whole row, in blocks of query rows.  Callers wrap
calls in ``jax.default_matmul_precision("highest")``.

Departures from the release (``modeling_granitemoehybrid.py``): none in the
equations.  In the layout: the MLP's ``input_linear`` is read as its two
halves ``wg | wu`` (the same product); weights are upcast where used, one
matrix at a time, and attention runs over blocks of query rows so that no
``(heads, T, T)`` tensor exists.

There is ONE path and it is float32.  Its arithmetic goes through five
named operations — :func:`product` (every matrix product), :func:`softmax`,
:func:`rms_norm`, :func:`island` (the float32 elementwise islands: the
step ``dt``, the decay, the convolution's sum) and :func:`carry` (the state
as it is handed from one token to the next) — so that
``perf/tools/granite_lowp.py`` can wrap them and show that the cell's limits
refuse the same equations computed one notch below the stated precision.
Nothing here knows of that.

Layer ``l``: ``a = x + r * Mixer_l(N_in(x))``; ``out = a + r * W_d(silu(u
W_g) * (u W_u))``, ``u = N_post(a)``, ``r`` the ``residual_multiplier``;
``x0 = E[token] * embedding_multiplier``; ``logits = N_f(x) E^T /
logits_scaling``.
"""

from __future__ import annotations

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


def island(x):
    """A value of the recurrence's float32 islands."""
    return x.astype(F32)


def carry(state):
    """The state as one token hands it to the next."""
    return state.astype(F32)


def attention(x, p, cfg, q_block):
    """Grouped-query attention over one row ``x (T, h)``: causal, no
    positional embedding, scores times ``attention_multiplier``."""
    t, h = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    q = product("th,hd->td", x, p["wq"]).reshape(t, kv, heads // kv, d)
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, d)
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - t), (0, 0), (0, 0), (0, 0)))

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        logits = product("qkgd,tkd->kgqt", rows, k) * cfg[
            "attention_multiplier"]
        seen = s + jnp.arange(q_block)[:, None] >= jnp.arange(t)[None, :]
        probs = softmax(jnp.where(seen, logits, -jnp.inf))
        return product("kgqt,tkd->qkgd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads * d)[:t]
    return product("td,dh->th", o, p["wo"])


def mamba(x, p, cfg):
    """The Mamba-2 mixer over one row ``x (T, h)``, token by token."""
    t, _ = x.shape
    heads, d, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    inner, width = heads * d, cfg["mamba_d_conv"]
    zxbcdt = product("th,hd->td", x, p["in_proj"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + inner + 2 * n],
                  zxbcdt[:, inner + inner + 2 * n:])
    # depthwise, causal: tap j reads the input width - 1 - j tokens back
    front = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = island(p["conv_b"]) + sum(
        island(front[j:j + t]) * island(p["conv_w"][:, j])
        for j in range(width))
    xbc = jax.nn.silu(island(conv))
    xs = xbc[:, :inner].reshape(t, heads, d)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = island(jax.nn.softplus(island(dt) + island(p["dt_bias"])))
    a = -jnp.exp(island(p["a_log"]))

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        keep = island(jnp.exp(dt_t * a))                       # (heads,)
        add = island((dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        state = carry(carry(state) * keep[:, None, None] + add)
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(token, carry(jnp.zeros((heads, d, n), F32)),
                        (xs, b, c, dt))
    y = y + island(p["d"])[None, :, None] * xs
    y = rms_norm(y.reshape(t, inner) * jax.nn.silu(z), p["norm"],
                 cfg["rms_norm_eps"])
    return product("td,dh->th", y, p["out_proj"])


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = params["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    for kind, layer in zip(cfg["layer_types"], params["layers"]):
        n = layer["norm"]
        u = rms_norm(x, n[0], eps)
        mixed = (mamba(u, layer["mixer"], cfg) if kind == "mamba"
                 else attention(u, layer["mixer"], cfg, q_block))
        a = x + r * mixed
        x = a + r * swiglu(rms_norm(a, n[1], eps), layer["ffn"])
    x = rms_norm(x, params["final_norm"], eps)
    if logit_positions is not None:
        x = x[logit_positions]
    logits = product("td,vd->tv", x, params["embed"])
    return logits.astype(F32) / cfg["logits_scaling"]


def forward(params, tokens, cfg, **kwargs):
    """``tokens (B, T)`` -> logits ``(B, T or K, V)``, one row at a time."""
    positions = kwargs.pop("logit_positions", None)
    rows = [forward_row(params, tokens[i], cfg,
                        logit_positions=None if positions is None
                        else positions[i], **kwargs)
            for i in range(tokens.shape[0])]
    return jnp.stack(rows)
