"""Qwen3-Next's (``qwen3_next``) forward pass in plain ``jax.numpy``,
float32.

Written from the published configuration's equations (ISSUE 63, PERF.md
section 4), not from ``progen_tpu``: nothing of the program is imported.
No cache, no chunks, no batching: the gated delta rule is a sequential
``lax.scan`` over the row's tokens, one ``(value heads, Dk, Dv)`` state
carried from token to token; the convolution is four shifted copies of the
row; attention is a causal mask over the whole row, in blocks of query rows;
routing is a top-k of a softmax and the experts a dense loop over the ones
the chip holds (every held expert runs on every token and is weighted by
what the router gave it, zero where it was not chosen).  One row at a time,
weights upcast where used one matrix at a time, so that it fits beside the
program on the chip.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.

The equations, letter for letter as ISSUE 63 states them (``N_w(x) = x *
rsqrt(mean(x^2) + eps) * (1 + w)``, a zero-centred weight, eps
``rms_norm_eps``)::

    x <- x + mixer_i(N(x)),  x <- x + moe(N(x))
    mixer_i: full attention where (i + 1) % full_attention_interval == 0,
             the gated delta rule otherwise
    delta:  [q (Hk Dk) | k (Hk Dk) | v (Hv Dv) | z (Hv Dv)] = u W_qkvz
            [b (Hv) | a (Hv)] = u W_ba
            [q|k|v]_t <- silu(sum_j w[:, j] * [q|k|v]_{t-3+j})
            q <- q rsqrt(sum q^2 + 1e-6) Dk^-1/2,  k <- k rsqrt(sum k^2 + 1e-6)
            value head j reads key head j // (Hv / Hk)
            beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
            S_t = exp(g_t) S_{t-1} + k_t (x) beta_t (v_t - exp(g_t) S_{t-1}^T k_t)
            o_t = S_t^T q_t
            out = [N'_w(o_t) * silu(z_t)] W_out    N' a head, PLAIN weight
    full:   [q_j | gate_j] = u W_q,  k, v = u W_k, u W_v
            q <- N_wq(q), k <- N_wk(k); the FIRST rotary columns rotated
            o = softmax(q k^T d^-1/2) v;  out = [o * sigmoid(gate)] W_o
    moe:    p = softmax(u W_r); the top-k, renormalised to sum 1
            sum_e p_e (silu(u W_g,e) * u W_u,e) W_d,e
            + sigmoid(u . w_s) * (silu(u W_g) * u W_u) W_d
    head:   logits = N_f(x) W_head

Departures from the release, each what the configuration file lists under
``assumed``: (1) the projection's columns in the flat order ``[q | k | v |
z]`` and ``[b | a]`` (the release interleaves them a key head; with seeded
weights that is a permutation of columns); (2) the chip's SHARE: the router
is ``num_experts`` wide whatever is held, and the layer adds the terms of the
held experts (``first_expert <= i < first_expert + experts_held``) only —
the uncut layer is ``experts_held == num_experts`` —, the gated shared
expert whole; (3) the multi-token-prediction module is not part of the
forward.

There is ONE path and it is float32.  Its arithmetic goes through six named
operations — :func:`product` (every matrix product), :func:`softmax`,
:func:`sigmoid`, :func:`rms_norm`, :func:`island` (the float32 elementwise
islands: the decay, the write strength, the convolution's sum, the l2
norms) and :func:`carry` (the state as it is handed from one token to the
next) — and the family's own choices under names — :func:`delta_token`,
:func:`recurrence`, :func:`unit`, :func:`gated_norm`, ``cfg["rotary_dim"]``,
:func:`attention_gate` and :func:`shared_gate` — so that
``perf/tools/qwen3next_lowp.py`` can wrap them and show that the cell's
limits refuse the same equations computed one notch below the stated
precision, or with one of the family's own choices left out.  Nothing here
knows of that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6


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
    """``scale`` multiplies as it is: a zero-centred weight comes as ``1 +
    w``."""
    xs = x.astype(F32)
    var = jnp.mean(xs * xs, axis=-1, keepdims=True)
    return xs * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def island(x):
    """A value of the recurrence's float32 islands."""
    return x.astype(F32)


def carry(state):
    """The state as one token hands it to the next."""
    return state.astype(F32)


def one_plus(w):
    return 1.0 + w.astype(F32)


def unit(x):
    """``x`` over the sum of its squares plus ``1e-6``, a head."""
    x = island(x)
    return island(x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                    + L2_EPS))


def delta_token(state, q, k, v, alpha, beta):
    """One token of the gated delta rule over ``state (Hv, Dk, Dv)``: the
    decay, the erase under ``k``, the write; and the read-out."""
    state = carry(state) * alpha[:, None, None]
    held = jnp.sum(state * k[:, :, None], axis=1)               # S^T k
    write = beta[:, None] * (v - held)
    state = carry(state + k[:, :, None] * write[:, None, :])
    return state, jnp.sum(state * q[:, :, None], axis=1)


def recurrence(q, k, v, alpha, beta):
    """The gated delta rule over one row, TOKEN BY TOKEN: ``q, k (T, Hv,
    Dk)``, ``v (T, Hv, Dv)``, ``alpha, beta (T, Hv)`` -> ``o (T, Hv, Dv)``."""
    def token(state, at):
        return delta_token(state, *at)

    zero = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, carry(zero), (q, k, v, alpha, beta))[1]


def gated_norm(o, z, w, eps):
    """``N'_w(o) * silu(z)``: the norm a head with a plain weight, the gate
    AFTER it."""
    return rms_norm(o, w, eps) * jax.nn.silu(island(z))


def attention_gate(o, gate):
    return o * sigmoid(gate)


def shared_gate(u, w):
    return sigmoid(product("th,h->t", u, w))


def delta(x, p, cfg):
    """The gated delta-rule mixer over one row ``x (T, h)``, token by
    token."""
    t, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    kw, vw = hk * dk, hv * dv
    qkvz = product("th,hd->td", x, p["in_proj"])             # departure 1
    qkv, z = qkvz[:, :2 * kw + vw], qkvz[:, 2 * kw + vw:]
    ba = product("th,hd->td", x, p["ba_proj"])
    # depthwise, causal, no bias: tap j reads the input width - 1 - j back
    front = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    conv = sum(island(front[j:j + t]) * island(p["conv_w"][:, j])
               for j in range(width))
    qkv = jax.nn.silu(island(conv))
    q = unit(qkv[:, :kw].reshape(t, hk, dk)) * dk ** -0.5
    k = unit(qkv[:, kw:2 * kw].reshape(t, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    v = qkv[:, 2 * kw:].reshape(t, hv, dv)
    beta = island(sigmoid(ba[:, :hv]))
    g = -jnp.exp(island(p["a_log"])) * jax.nn.softplus(
        island(ba[:, hv:]) + island(p["dt_bias"]))
    alpha = island(jnp.exp(island(g)))
    o = recurrence(q, k, v, alpha, beta)
    y = gated_norm(o, z.reshape(t, hv, dv), p["norm"], cfg["rms_norm_eps"])
    return product("td,dh->th", y.reshape(t, vw), p["out_proj"])


def rotate(x, positions, rotary, theta):
    """Half-split rotation of the first ``rotary`` columns of ``x (T, heads,
    d)``; the rest pass through."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary))
    ang = positions.astype(F32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], axis=-1)


def attention(x, p, cfg, q_block):
    """Gated grouped-query attention over one row ``x (T, h)``: causal,
    scores times ``head_dim^-1/2``."""
    t, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    rotary = cfg.get("rotary_dim", int(d * cfg["partial_rotary_factor"]))
    at = jnp.arange(t)
    qg = product("th,hd->td", x, p["wq"]).reshape(t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, d)
    q = rotate(rms_norm(q, one_plus(p["q_norm"]), eps), at, rotary, theta)
    k = rotate(rms_norm(k, one_plus(p["k_norm"]), eps), at, rotary, theta)
    q = q.reshape(t, kv, heads // kv, d)
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - t), (0, 0), (0, 0), (0, 0)))

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        logits = product("qkgd,tkd->kgqt", rows, k) * d ** -0.5
        seen = s + jnp.arange(q_block)[:, None] >= jnp.arange(t)[None, :]
        probs = softmax(jnp.where(seen, logits, -jnp.inf))
        return product("kgqt,tkd->qkgd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads * d)[:t]
    o = attention_gate(o, gate.reshape(t, heads * d))
    return product("td,dh->th", o, p["wo"])


def swiglu(u, p):
    gate = jax.nn.silu(product("th,hf->tf", u, p["wg"]))
    return product("tf,fh->th", gate * product("th,hf->tf", u, p["wu"]),
                   p["wd"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok`` largest
    of ``softmax(u W_r)``, renormalised to sum 1 (``norm_topk_prob``)."""
    probs = softmax(product("th,he->te", u, p["w"]))
    w, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w


def routed(u, layer, cfg):
    """This chip's share of the routed experts over ``u (T, h)`` (departure
    2), and the router's choices."""
    ids, w = route(u, layer["router"], cfg)
    first = cfg.get("first_expert", 0)
    held = cfg.get("experts_held", cfg["num_experts"])
    experts = layer["experts"]

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")})
        return y + w_e[:, None] * out

    y = jax.lax.fori_loop(0, held, add_expert,
                          jnp.zeros(u.shape, F32))
    return y, ids


def gated_shared(u, layer):
    """``sigmoid(u . w_s) * shared(u)``."""
    return shared_gate(u, layer["shared_gate"])[:, None] * swiglu(
        u, layer["shared"])


def moe(u, layer, cfg):
    """The expert layer over ``u (T, h)``: the held routed experts plus the
    gated shared expert; and the router's choices."""
    y, ids = routed(u, layer, cfg)
    return y + gated_shared(u, layer), ids


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(layers, T, k)``."""
    eps, every = cfg["rms_norm_eps"], cfg["full_attention_interval"]
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for i, layer in enumerate(params["layers"]):
        u = rms_norm(x, one_plus(layer["norm"][0]), eps)
        if (i + 1) % every == 0:
            x = x + attention(u, layer["mixer"], cfg, q_block)
        else:
            x = x + delta(u, layer["mixer"], cfg)
        y, ids = moe(rms_norm(x, one_plus(layer["norm"][1]), eps), layer, cfg)
        chosen.append(ids)
        x = x + y
    x = rms_norm(x, one_plus(params["final_norm"]), eps)
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
