"""MiMo-V2's (``mimo_v2``) forward pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 54, PERF.md
section 4), not from ``progen_tpu.models`` or ``progen_tpu.ops``: nothing
of the program is imported.  No cache and no ring (every position attends
over the keys and values of the whole row under a dense mask), no tiles, no
kernels, the sliding window as a mask on ``i - j``, THE SINK AS A LITERAL
EXTRA COLUMN of the score matrix that is dropped after the softmax, routing
by a top-k of ``sigmoid + bias``, a dense loop over the experts the chip
holds (every held expert runs on every token and is weighted by what the
router gave it, zero where it was not chosen), the leading dense layer.
Callers wrap calls in ``jax.default_matmul_precision("highest")``.

Layer ``l``: ``x = x + Attn_l(N_in(x))``; ``x = x + FFN_l(N_post(x))``;
``logits = N_f(x) W_head``.  Attention of kind ``hybrid_layer_pattern[l]``
(0 full, 1 sliding), each kind with its own head counts and rotary base:
``q, k`` heads ``d`` wide of which the first ``int(d *
partial_rotary_factor)`` columns are rotated (half-split pairs), ``v`` heads
``dv`` wide times ``attention_value_scale``; scores ``d^-1/2 q . k`` for ``j
<= i`` (and ``i - j < sliding_window`` in a sliding layer); a sliding
layer's softmax runs over the row's scores AND ``sink_h``, whose
probability is thrown away.  ``FFN_l`` the dense SwiGLU where
``moe_layer_freq[l] == 0``, else ``sum_i w_i E_i(u)`` with ``w`` the chosen
sigmoids over their sum + 1e-20, times ``routed_scaling_factor`` (null: 1).

Departures from the release, each noted where it is made: (1) the chip's
SHARE: the router is ``n_routed_experts`` wide whatever is held, and the
layer adds the terms of the held experts (``first_expert <= i <
first_expert + experts_held``) only; (2) weights are upcast where used, one
matrix at a time, and attention runs over blocks of ``q_block`` query rows
(one ``lax.map`` body a layer, each block against EVERY key of the row
under the dense mask) so that no ``(heads, T, T)`` tensor exists and 16,384
positions fit the chip, and the feed-forward layers, which are token-wise,
may run over blocks of ``row_block`` rows; (3) no multi-token-prediction layers and no
encoders: token ids in, the 48-layer stack's logits out.

There is ONE path and it is float32.  Its arithmetic goes through four
named operations — :func:`product` (every matrix product), :func:`softmax`,
:func:`rms_norm` and :func:`sigmoid` — so that ``perf/tools/mimo_lowp.py``
can wrap them and show that the cell's limits refuse the same equations
computed one notch below the stated precision; and every one of the
family's own choices (the sink, the value scale, the window, the bases) is
read from ``cfg``, so that the same tool can plant each omission.  Nothing
here knows of that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL, SLIDING = 0, 1


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
    """Half-split rotation over ALL of the last axis of ``x (T, heads, r)``
    at ``positions (T,)``: pairs ``(i, i + r / 2)``."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = positions.astype(F32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : r // 2].astype(F32), x[..., r // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def heads_of(cfg, kind):
    """``(H, KV, d, dv, theta, sink)`` of an attention kind."""
    if kind == SLIDING:
        return (cfg["swa_num_attention_heads"],
                cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"], cfg["swa_rope_theta"],
                cfg["add_swa_attention_sink_bias"])
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["v_head_dim"], cfg["rope_theta"],
            cfg["add_full_attention_sink_bias"])


def attention(x, p, cfg, kind, q_block):
    """Grouped-query attention of ``kind`` over one row ``x (T, h)``."""
    t, _ = x.shape
    heads, kv, d, dv, theta, sink = heads_of(cfg, kind)
    rot = int(d * cfg["partial_rotary_factor"])
    q = product("th,hd->td", x, p["wq"]).reshape(t, heads, d)
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, dv)
    at = jnp.arange(t)
    q, k = (jnp.concatenate([rope(a[..., :rot], at, theta), a[..., rot:]],
                            axis=-1) for a in (q, k))
    v = cfg["attention_value_scale"] * v
    # query head h reads key/value head h // (heads / kv)
    q = q.reshape(t, kv, heads // kv, d)
    scale = d ** -0.5
    # blocks of query rows, each against every key under the dense mask
    # (departure 2); the last block is padded with rows nothing reads
    blocks = -(-t // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - t), (0, 0), (0, 0), (0, 0)))

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        logits = product("qkgd,tkd->kgqt", rows, k) * scale
        gap = s + jnp.arange(q_block)[:, None] - jnp.arange(t)[None, :]
        seen = gap >= 0
        if kind == SLIDING:
            seen = seen & (gap < cfg["sliding_window"])
        logits = jnp.where(seen, logits, -jnp.inf)
        if sink:        # one more column: it takes mass and has no value
            column = jnp.broadcast_to(
                p["sink"].astype(F32).reshape(kv, heads // kv, 1, 1),
                logits.shape[:3] + (1,))
            probs = softmax(jnp.concatenate([logits, column], -1))[..., :t]
        else:
            probs = softmax(logits)
        return product("kgqt,tkd->qkgd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads * dv)[:t]
    return product("td,dh->th", o, p["wo"])


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok``
    largest of ``sigmoid(u W_r) + bias``; the weights are the chosen
    sigmoids alone, over their sum + 1e-20 (``norm_topk_prob``), times
    ``routed_scaling_factor`` (null: 1)."""
    scores = sigmoid(product("th,he->te", u, p["w"]))
    _, ids = jax.lax.top_k(scores + p["bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    scale = cfg.get("routed_scaling_factor")
    return ids, w * (1.0 if scale is None else scale)


def routed(u, router, experts, cfg):
    """This chip's share of the experts over ``u (T, h)`` (departure 1) and
    the router's choices."""
    ids, w = route(u, router, cfg)
    first = cfg.get("first_expert", 0)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")})
        return y + w_e[:, None] * out

    held = cfg.get("experts_held", cfg["n_routed_experts"])
    return jax.lax.fori_loop(0, held, add_expert,
                             jnp.zeros(u.shape, F32)), ids


def by_rows(fn, x, block):
    """``fn`` (token-wise: each row of its result depends on that row of
    ``x (T, h)`` alone) over blocks of ``block`` rows (departure 2); the
    last block is padded with rows nothing reads."""
    t = x.shape[0]
    if block is None or t <= block:
        return fn(x)
    n = -(-t // block)
    out = jax.lax.map(fn, jnp.pad(x, ((0, n * block - t), (0, 0))).reshape(
        n, block, -1))
    return jax.tree.map(
        lambda a: a.reshape((n * block,) + a.shape[2:])[:t], out)


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None,
                row_block=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), and the routers'
    choices ``(expert layers, T, k)``.  ``row_block``: the feed-forward
    layers run over blocks of so many rows (default: the whole row)."""
    eps = cfg["layernorm_epsilon"]
    x = params["embed"][tokens].astype(F32)
    chosen = []
    for i, layer in enumerate(params["layers"]):
        n = layer["norm"]
        x = x + attention(rms_norm(x, n[0], eps), layer["attn"], cfg,
                          cfg["hybrid_layer_pattern"][i], q_block)
        u = rms_norm(x, n[1], eps)
        if not cfg["moe_layer_freq"][i]:
            x = x + by_rows(lambda r, p=layer["ffn"]: swiglu(r, p), u,
                            row_block)
            continue
        m, ids = by_rows(
            lambda r, p=layer: routed(r, p["router"], p["experts"], cfg),
            u, row_block)
        chosen.append(ids)
        x = x + m
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
