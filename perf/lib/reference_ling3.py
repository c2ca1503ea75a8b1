"""Ling-3.0-flash's (``bailing_hybrid``) forward pass in plain
``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 65, PERF.md
section 4), not from ``progen_tpu``: nothing of the program is imported.
No cache, no chunks, no blocks, no batching: the delta rule is a sequential
``lax.scan`` over the row's tokens, one ``(heads, Dk, Dv)`` state carried
from token to token and decayed a CHANNEL; the convolution is four shifted
copies of the row; latent attention is UNABSORBED — keys and values
expanded from the latent, one causal mask over the whole row in blocks of
query rows —; the router's groups are a plain loop and the experts a dense
loop over the ones the chip holds (every held expert runs on every token
and is weighted by what the router gave it, zero where it was not chosen).
One row at a time, weights upcast where used one matrix at a time, so that
it fits beside the program on the chip.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.

The equations, letter for letter as ISSUE 65 states them (``N_w(x) = x *
rsqrt(mean(x^2) + eps) * w``, eps ``rms_norm_eps``; ``i`` a layer's
PUBLISHED number, ``cfg["layer_ids"][j]`` of held layer ``j``)::

    x <- x + mixer_i(N(x)),  x <- x + ffn_i(N(x))
    mixer_i: latent attention where (i + 1) % layer_group_size == 0, the
             channel-decay delta rule otherwise
    ffn_i:   a dense SwiGLU for i < first_k_dense_replace, the expert layer
             after
    delta:  [q | k | v] (H Dk, H Dk, H Dv) = u W_qkv
            [q|k|v]_t <- silu(sum_j w[:, j] * [q|k|v]_{t-3+j})
            q <- q rsqrt(sum q^2 + 1e-6) Dk^-1/2,  k <- k rsqrt(sum k^2 + 1e-6)
            f = u W_f (H, Dk),  beta = sigmoid(u W_b) (H)
            g = kda_lower_bound * sigmoid(exp(A_log)_head * (f + dt_bias))
            S_t = diag(exp(g_t)) S_{t-1} + k_t (x) beta_t (v_t -
                  (diag(exp(g_t)) S_{t-1})^T k_t);   o_t = S_t^T q_t
            out = [N'_w(o_t) * sigmoid(u W_og)_head] W_out    N' a head
    latent: q = u W_q (H x [nope | rope]);  [c_kv | k_r] = u W_kva;  c_kv <-
            N_w(c_kv);  [k_nope | v] = c_kv W_kvb;  INTERLEAVED rotary pairs
            (2i, 2i + 1) on q's rope part and on k_r
            o = softmax(q . [k_nope | k_r] (nope + rope)^-1/2) v
            out = [o_head * sigmoid(u W_g)_head] W_o
    experts: s = sigmoid(u W_r); c = s + b; n_group groups of consecutive
            experts, a group scores the sum of its 2 largest c, the
            topk_group best stay; the k largest c among them; weights
            s / (sum s + 1e-20) * routed_scaling_factor
            expert e: a = u W_g,e; b = u W_u,e; under the layer's limit l:
            a <- min(a, l), b <- clip(b, -l, l); (silu(a) * b) W_d,e
            + the shared expert, the same form under its own limit
    head:   logits = N_f(x) W_head

Departures from the release, each what the configuration file lists under
``assumed``: (1) the chip's SHARE: the layers ``layer_ids``, the router
``num_experts`` wide whatever is held, the terms of the held experts
(``first_expert <= e < first_expert + experts_held``) only — the uncut layer
is ``experts_held == num_experts`` —, the shared expert whole; (2) the
multi-token-prediction module is not part of the forward.

There is ONE path and it is float32.  Its arithmetic goes through six named
operations — :func:`product`, :func:`softmax`, :func:`sigmoid`,
:func:`rms_norm`, :func:`island` (the float32 elementwise islands: the
decay, the write strength, the convolution's sum, the l2 norms) and
:func:`carry` (the state as it is handed from one token to the next) — and
the family's own choices under names — :func:`delta_token`,
:func:`log_decay`, :func:`unit`, :func:`delta_gate`, :func:`latent_gate`,
:func:`kept_groups`, :func:`clipped` — so that ``perf/tools/ling3_lowp.py``
can wrap them and show that the cell's limits refuse the same equations
computed one notch below the stated precision, or with one of the family's
own choices left out.  Nothing here knows of that.
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
    xs = x.astype(F32)
    var = jnp.mean(xs * xs, axis=-1, keepdims=True)
    return xs * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def island(x):
    """A value of the recurrence's float32 islands."""
    return x.astype(F32)


def carry(state):
    """The state as one token hands it to the next."""
    return state.astype(F32)


def unit(x):
    """``x`` over the sum of its squares plus ``1e-6``, a head."""
    x = island(x)
    return island(x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                    + L2_EPS))


def log_decay(f, a_log, dt_bias, bound):
    """``g (T, H, Dk)``: the log of a step's decay a channel, in ``(bound,
    0)``: the published kernels' lower-bound gate."""
    return bound * sigmoid(jnp.exp(island(a_log))[:, None]
                           * (island(f) + island(dt_bias)))


def delta_token(state, q, k, v, alpha, beta):
    """One token of the delta rule over ``state (H, Dk, Dv)`` with a decay
    a channel ``alpha (H, Dk)``: the decay, the erase under ``k``, the
    write; and the read-out."""
    state = carry(state) * alpha[:, :, None]
    held = jnp.sum(state * k[:, :, None], axis=1)               # S^T k
    write = beta[:, None] * (v - held)
    state = carry(state + k[:, :, None] * write[:, None, :])
    return state, jnp.sum(state * q[:, :, None], axis=1)


def recurrence(q, k, v, alpha, beta):
    """The delta rule over one row, TOKEN BY TOKEN: ``q, k, alpha (T, H,
    Dk)``, ``v (T, H, Dv)``, ``beta (T, H)`` -> ``o (T, H, Dv)``."""
    def token(state, at):
        return delta_token(state, *at)

    zero = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, carry(zero), (q, k, v, alpha, beta))[1]


def delta_gate(o, gate):
    """A head's normed output times its own sigmoid: ``o (T, H, Dv)``,
    ``gate (T, H)``."""
    return o * sigmoid(gate)[..., None]


def latent_gate(o, gate):
    return o * sigmoid(gate)[..., None]


def delta(x, p, cfg):
    """The channel-decay delta-rule mixer over one row ``x (T, h)``, token
    by token."""
    t, _ = x.shape
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    width = cfg["short_conv_kernel_size"]
    kw = heads * d
    qkv = product("th,hd->td", x, p["in_proj"])
    # depthwise, causal, no bias: tap j reads the input width - 1 - j back
    front = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    conv = sum(island(front[j:j + t]) * island(p["conv_w"][:, j])
               for j in range(width))
    qkv = jax.nn.silu(island(conv))
    q = unit(qkv[:, :kw].reshape(t, heads, d)) * d ** -0.5
    k = unit(qkv[:, kw:2 * kw].reshape(t, heads, d))
    v = qkv[:, 2 * kw:].reshape(t, heads, d)
    f = product("th,hd->td", x, p["f_proj"]).reshape(t, heads, d)
    beta = island(sigmoid(product("th,hd->td", x, p["b_proj"])))
    g = log_decay(f, p["a_log"], p["dt_bias"], cfg["kda_lower_bound"])
    alpha = island(jnp.exp(island(g)))
    o = recurrence(q, k, v, alpha, beta)
    y = delta_gate(rms_norm(o, p["norm"], cfg["rms_norm_eps"]),
                   product("th,hd->td", x, p["g_proj"]))
    return product("td,dh->th", y.reshape(t, kw), p["out_proj"])


def rotate_pairs(x, positions, theta):
    """Rotation of the INTERLEAVED pairs ``(2i, 2i + 1)`` of ``x (T, ...,
    d)``'s last axis."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(x, p, cfg, q_block):
    """Gated latent attention over one row ``x (T, h)``, UNABSORBED:
    causal, scores times ``(nope + rope)^-1/2``."""
    t, _ = x.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    theta, at = cfg["rope_theta"], jnp.arange(t)
    q = product("th,hd->td", x, p["wq"]).reshape(t, heads, nope + rot)
    kva = product("th,hd->td", x, p["wkva"])
    c_kv = rms_norm(kva[:, :rank], p["kv_norm"], cfg["rms_norm_eps"])
    kvb = product("tl,ld->td", c_kv, p["wkvb"]).reshape(t, heads, nope + dv)
    k_r = rotate_pairs(kva[:, rank:], at, theta)
    q = jnp.concatenate([q[..., :nope],
                         rotate_pairs(q[..., nope:], at, theta)], axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r[:, None], (t, heads, rot))],
        axis=-1)
    v = kvb[..., nope:]
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - t), (0, 0), (0, 0)))

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        logits = product("qhd,thd->hqt", rows, k) * (nope + rot) ** -0.5
        seen = s + jnp.arange(q_block)[:, None] >= jnp.arange(t)[None, :]
        probs = softmax(jnp.where(seen, logits, -jnp.inf))
        return product("hqt,thd->qhd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads, dv)[:t]
    o = latent_gate(o, product("th,hd->td", x, p["wgate"]))
    return product("td,dh->th", o.reshape(t, heads * dv), p["wo"])


def clipped(a, b, limit):
    """A SwiGLU's two products under a layer's ``limit``, BEFORE the
    activation; as they are where it is 0."""
    if not limit:
        return a, b
    return jnp.minimum(a, limit), jnp.clip(b, -limit, limit)


def swiglu(u, p, limit=0):
    a, b = clipped(product("th,hf->tf", u, p["wg"]),
                   product("th,hf->tf", u, p["wu"]), limit)
    return product("tf,fh->th", jax.nn.silu(a) * b, p["wd"])


def kept_groups(c, cfg):
    """``(T, n_group)`` bool: the ``topk_group`` groups a token keeps, a
    group scoring the sum of its two largest ``c``; a plain loop over the
    groups."""
    groups = cfg["n_group"]
    size = c.shape[1] // groups
    scores = []
    for gi in range(groups):
        top2 = jax.lax.top_k(c[:, gi * size:(gi + 1) * size], 2)[0]
        scores.append(top2[:, 0] + top2[:, 1])
    scores = jnp.stack(scores, axis=1)
    _, best = jax.lax.top_k(scores, cfg["topk_group"])
    return jnp.any(best[:, :, None] == jnp.arange(groups)[None, None, :],
                   axis=1)


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: ``noaux_tc`` under the group
    limit."""
    s = sigmoid(product("th,he->te", u, p["w"]))
    c = s + p["bias"].astype(F32)
    size = c.shape[1] // cfg["n_group"]
    allowed = jnp.repeat(kept_groups(c, cfg), size, axis=1)
    _, ids = jax.lax.top_k(jnp.where(allowed, c, -jnp.inf),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg["routed_scaling_factor"]


def routed(u, layer, cfg, limit):
    """This chip's share of the routed experts over ``u (T, h)`` (departure
    1), and the router's choices."""
    ids, w = route(u, layer["router"], cfg)
    first = cfg.get("first_expert", 0)
    held = cfg.get("experts_held", cfg["num_experts"])
    experts = layer["experts"]

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")}, limit)
        return y + w_e[:, None] * out

    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros(u.shape, F32))
    return y, ids


def moe(u, layer, cfg, limits):
    """The expert layer over ``u (T, h)``: the held routed experts plus the
    shared expert, each under its limit; and the router's choices."""
    y, ids = routed(u, layer, cfg, limits[0])
    return y + swiglu(u, layer["shared"], limits[1]), ids


def layer_ids(cfg):
    return list(cfg.get("layer_ids") or range(cfg["num_hidden_layers"]))


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(expert layers, T, k)``."""
    eps, every = cfg["rms_norm_eps"], cfg["layer_group_size"]
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for layer, i in zip(params["layers"], layer_ids(cfg)):
        u = rms_norm(x, layer["norm"][0], eps)
        if (i + 1) % every == 0:
            x = x + attention(u, layer["mixer"], cfg, q_block)
        else:
            x = x + delta(u, layer["mixer"], cfg)
        u = rms_norm(x, layer["norm"][1], eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + swiglu(u, layer["ffn"])
            continue
        y, ids = moe(u, layer, cfg,
                     (cfg["expert_swiglu_limit_list"][i],
                      cfg["share_expert_swiglu_limit_list"][i]))
        chosen.append(ids)
        x = x + y
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
