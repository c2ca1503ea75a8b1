"""Nemotron-H's (``nemotron_h``, as Nemotron-3-Super publishes it) forward
pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 49, PERF.md
section 4), not from ``progen_tpu``: nothing of the program is imported.
No cache, no chunks, no batching: the Mamba-2 recurrence is a sequential
``lax.scan`` over the row's tokens, one ``(heads, d_head, N)`` state carried
from token to token; the convolution is four shifted copies of the row;
attention is a causal mask over the whole row, in blocks of query rows;
routing is a top-k of ``sigmoid + bias`` and the experts a dense loop over
the ones the chip holds (every held expert runs on every token and is
weighted by what the router gave it, zero where it was not chosen).  One row
at a time, weights upcast where used one matrix at a time, so that it fits
beside the program on the chip.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.

The equations, letter for letter as ISSUE 49 states them (``N`` an RMSNorm
``x * rsqrt(mean(x^2) + eps) * w``, eps ``layer_norm_epsilon``)::

    x <- x + Mixer_l(N_l(x)),  the kind hybrid_override_pattern[l], ONCE
    M:  [z (I) | xBC (I + 2 G N) | dt (heads)] = u W_in
        xBC_t <- silu(sum_j w[:, j] * xBC_{t-3+j} + b)
        [x (heads, d) | B (G, N) | C (G, N)] = xBC_t;  g = h // (heads / G)
        dt = softplus(dt + dt_bias_h);  a_h = -exp(A_log_h)
        S_t = exp(dt a_h) S_{t-1} + dt x_t (x) B_t,g
        y_t = S_t C_t,g + D_h x_t
        out = N_w(y * silu(z)) per group of I / G channels, then W_out
    *:  q (H heads of d), k, v (KV heads of d) = u W_q, u W_k, u W_v;
        no rotation; scores q k^T / sqrt(d), causal, softmax; H / KV query
        heads a key head; W_o
    E:  s = sigmoid(u W_r); the top-k of s + b are chosen;
        w = scale * s_chosen / (sum s_chosen + 1e-20)
        v = u W_down;  f_e(v) = relu(v W_up,e)^2 W_dn,e
        out = (sum_e w_e f_e(v)) W_up + relu(u W_su)^2 W_sd
    head: logits = N_f(x) W_head

Departures from the release, each what the configuration file lists under
``assumed``: (1) no rotation in the attention layers (``rope_theta`` is
unused); (2) the ``1e-20`` of the renormalisation; (3) the split orders
``[z | xBC | dt]`` and ``[x | B | C]``; (4) the gate before a norm over
each group of channels; (5) the latent projections around the ROUTED
experts only — router and shared expert on the full width; (6) no clamp on
``dt``; (7) the head untied; (8) the chip's SHARE: the router is
``n_routed_experts`` wide whatever is held, and the layer adds the terms of
the held experts (``first_expert <= i < first_expert + experts_held``) only,
summed in the latent before ``W_up`` — the uncut layer is ``experts_held ==
n_routed_experts``; (9) the draft module (``num_nextn_predict_layers``) is
not part of the forward.

There is ONE path and it is float32.  Its arithmetic goes through six
named operations — :func:`product` (every matrix product), :func:`softmax`,
:func:`sigmoid`, :func:`rms_norm`, :func:`island` (the float32 elementwise
islands: the step ``dt``, the decay, the convolution's sum) and
:func:`carry` (the state as it is handed from one token to the next) — so
that ``perf/tools/nemotron3_lowp.py`` can wrap them and show that the cell's
limits refuse the same equations computed one notch below the stated
precision.  Nothing here knows of that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROUTE_EPS = 1e-20       # departure 2


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
    """A value of the recurrence's float32 islands."""
    return x.astype(F32)


def carry(state):
    """The state as one token hands it to the next."""
    return state.astype(F32)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def attention(x, p, cfg, q_block):
    """Grouped-query attention over one row ``x (T, h)``: causal, no
    positional embedding (departure 1), scores times ``head_dim^-1/2``."""
    t, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    q = product("th,hd->td", x, p["wq"]).reshape(t, kv, heads // kv, d)
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, d)
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
    return product("td,dh->th", o, p["wo"])


def mamba(x, p, cfg):
    """The Mamba-2 mixer over one row ``x (T, h)``, token by token."""
    t, _ = x.shape
    heads, d, n, groups = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                           cfg["ssm_state_size"], cfg["n_groups"])
    inner, width = heads * d, cfg["conv_kernel"]
    gn = groups * n
    zxbcdt = product("th,hd->td", x, p["in_proj"])           # departure 3
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * gn],
                  zxbcdt[:, 2 * inner + 2 * gn:])
    # depthwise, causal: tap j reads the input width - 1 - j tokens back
    front = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = island(p["conv_b"]) + sum(
        island(front[j:j + t]) * island(p["conv_w"][:, j])
        for j in range(width))
    xbc = jax.nn.silu(island(conv))
    xs = xbc[:, :inner].reshape(t, groups, heads // groups, d)
    b = xbc[:, inner:inner + gn].reshape(t, groups, n)
    c = xbc[:, inner + gn:].reshape(t, groups, n)
    dt = island(jax.nn.softplus(island(dt) + island(p["dt_bias"])))
    dt = dt.reshape(t, groups, heads // groups)              # departure 6
    a = -jnp.exp(island(p["a_log"])).reshape(groups, heads // groups)

    def token(state, at):
        """``state (G, heads / G, d, N)``: head ``(g, e)`` reads ``B_g``,
        ``C_g``."""
        x_t, b_t, c_t, dt_t = at
        keep = island(jnp.exp(dt_t * a))
        add = island((dt_t[..., None] * x_t)[..., None]
                     * b_t[:, None, None, :])
        state = carry(carry(state) * keep[..., None, None] + add)
        return state, jnp.sum(state * c_t[:, None, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, carry(jnp.zeros((groups, heads // groups, d, n), F32)),
        (xs, b, c, dt))
    y = y + island(p["d"]).reshape(groups, heads // groups)[..., None] * xs
    # the gate, then the norm over each group's channels (departure 4)
    y = y.reshape(t, groups, inner // groups) * jax.nn.silu(
        z.reshape(t, groups, inner // groups))
    y = rms_norm(y, p["norm"].reshape(groups, inner // groups),
                 cfg["layer_norm_epsilon"]).reshape(t, inner)
    return product("td,dh->th", y, p["out_proj"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok``
    largest of ``sigmoid(u W_r) + bias``; the weights are the chosen
    sigmoids alone, over their sum plus ``1e-20`` (``norm_topk_prob``),
    times ``routed_scaling_factor``."""
    scores = sigmoid(product("th,he->te", u, p["w"]))
    _, ids = jax.lax.top_k(scores + p["bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    return ids, w * cfg["routed_scaling_factor"]


def latent_moe(u, layer, cfg):
    """The expert layer over ``u (T, h)``: this chip's share of the routed
    experts, summed in the latent and projected back once (departures 5 and
    8), plus the shared expert on the full width; and the router's
    choices."""
    ids, w = route(u, layer["router"], cfg)
    first = cfg.get("first_expert", 0)
    held = cfg.get("experts_held", cfg["n_routed_experts"])
    v = product("th,hl->tl", u, layer["latent_in"])
    experts = layer["experts"]

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = product("tf,fl->tl",
                      relu2(product("tl,lf->tf", v, experts["wu"][e])),
                      experts["wd"][e])
        return y + w_e[:, None] * out

    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros(v.shape, F32))
    routed = product("tl,lh->th", y, layer["latent_out"])
    shared = product("tf,fh->th",
                     relu2(product("th,hf->tf", u, layer["shared"]["wu"])),
                     layer["shared"]["wd"])
    return routed + shared, ids


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(expert layers, T, k)``."""
    eps = cfg["layer_norm_epsilon"]
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for kind, layer in zip(cfg["hybrid_override_pattern"], params["layers"]):
        u = rms_norm(x, layer["norm"], eps)
        if kind == "E":
            y, ids = latent_moe(u, layer, cfg)
            chosen.append(ids)
        elif kind == "M":
            y = mamba(u, layer["mixer"], cfg)
        else:
            y = attention(u, layer["mixer"], cfg, q_block)
        x = x + y
    x = rms_norm(x, params["final_norm"], eps)
    if logit_positions is not None:
        x = x[logit_positions]
    logits = product("td,dv->tv", x, params["head"])         # departure 7
    return logits.astype(F32), jnp.stack(chosen)


def forward(params, tokens, cfg, **kwargs):
    """``tokens (B, T)`` -> logits ``(B, T or K, V)``, one row at a time."""
    positions = kwargs.pop("logit_positions", None)
    rows = [forward_row(params, tokens[i], cfg,
                        logit_positions=None if positions is None
                        else positions[i], **kwargs)[0]
            for i in range(tokens.shape[0])]
    return jnp.stack(rows)
