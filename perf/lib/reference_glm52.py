"""GLM-5.2's (``glm_moe_dsa``) forward pass in plain ``jax.numpy``, float32.

Written from the published configuration's equations (ISSUE 60, PERF.md
section 4), not from ``progen_tpu.models`` or ``progen_tpu.ops``: nothing
of the program is imported.  No cache, no absorbed form, no gathered rows:
every position attends over the expanded keys and values of the whole row
under a DENSE MASK — causal and the indexer's selection, scattered into the
mask from the reference's own ``top_k`` of its own scores; a ``shared`` layer
reads the mask of the last ``full`` layer before it —, routing by a top-k of
``sigmoid + bias``, a dense loop over the experts the chip holds, the shared
expert, the leading dense layers.  Callers wrap calls in
``jax.default_matmul_precision("highest")``.

Layer ``l``: ``x = x + Attn_l(N_in(x))``; ``x = x + FFN_l(N_post(x))``;
``logits = N_f(x) W_head``.  Attention, ``u = N_in(x)``::

    c_q = RMSNorm(u W_qa)                   q = c_q W_qb      (H x [nope | rope])
    [c_kv | k_r] = u W_kva                  c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb               (H x [nope | v])
    s_ij = (nope + rope)^-1/2 ([q_nope | rope(q_rope)]_i . [k_nope | rope(k_r)]_j)
    o_i = sum_{j in S_i} softmax_j(s_ij) v_j

the rotation of INTERLEAVED pairs ``(2i, 2i + 1)`` at ``rope_theta``,
written here from its definition.  A layer with ``indexer_types[l] ==
"full"`` has the indexer: ``q^I = c_q W^I_q`` (J x d), ``k^I = LayerNorm(u
W^I_k)`` (d), the leading ``qk_rope_head_dim`` columns of both rotated in
the same pairs, ``w = u W^I_w * J^-1/2 * d^-1/2``, ``I_ij = sum_h w_ih
relu(q^I_ih . k^I_j)`` for ``j <= i``; ``S_i`` the ``index_topk`` largest
(all while ``i < index_topk``).  A ``"shared"`` layer has none and ``S_i`` is
the last full layer's.  ``FFN_l`` the dense SwiGLU where
``mlp_layer_types[l] == "dense"``, else ``sum_i w_i E_i(u) + E_shared(u)``
with ``w`` the chosen sigmoids over their sum + 1e-20, times
``routed_scaling_factor``.

Departures from the release, each noted where it is made: (1) the chip's
SHARE: the router is ``n_routed_experts`` wide whatever is held, and the
layer adds the terms of the held experts (``first_expert <= i <
first_expert + experts_held``) only; (2) weights are upcast where used, and
attention runs over blocks of ``q_block`` query rows (one ``lax.map`` body a
layer, each block against EVERY key of the row under the dense mask) and,
where ``head_block`` is given, over so many heads at a time (the selection,
which all heads and up to four layers share, is made first), so that no
``(heads, T, T)`` tensor exists and 16,384 positions fit the chip beside the
weights, and the feed-forward layers may run over blocks of ``row_block``
rows; (3) the indexer's Hadamard rotation and FP8 storage are left out
(``assumed`` in the configuration file); (4) no multi-token-prediction layer.

There is ONE path and it is float32.  Its arithmetic goes through four
named operations — :func:`product`, :func:`softmax`, :func:`rms_norm` and
:func:`sigmoid` — so that ``perf/tools/glm52_lowp.py`` can wrap them, and
every one of the family's own choices is read from ``cfg`` (the selection's
size, its ReLU, its head weights, the pairs a rotation takes, whose selection
a shared and a full layer read), so that the same tool can plant each
omission.  Nothing here knows of that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL, SHARED = "full", "shared"


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


def layer_norm(x, scale, bias, eps):
    xs = x.astype(F32)
    xs = xs - jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.mean(xs * xs, axis=-1, keepdims=True)
    return xs * jax.lax.rsqrt(var + eps) * scale.astype(F32) + bias.astype(
        F32)


def rope(x, positions, theta, interleave=True):
    """Rotation over ALL of the last axis of ``x (T, heads, r)`` at
    ``positions (T,)``: pair ``i`` of ``r / 2`` turns by ``position *
    theta^(-2i / r)``.  ``interleave``: the pair is columns ``(2i, 2i + 1)``
    (as published); without, ``(i, i + r / 2)``.  The columns stay where
    they were."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = positions.astype(F32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    xs = x.astype(F32)
    if interleave:
        a, b = xs[..., 0::2], xs[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(xs.shape)
    a, b = xs[..., : r // 2], xs[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def theta_of(cfg):
    """The rotary base: the published ``rope_parameters.rope_theta`` (or a
    flat ``rope_theta``, as the program's own configuration has it)."""
    return cfg.get("rope_theta") or cfg["rope_parameters"]["rope_theta"]


def indexer(u, c_q, p, cfg, at):
    """``(q^I (T, J, d), k^I (T, d), w (T, J))`` of a full layer."""
    heads, d = cfg["index_n_heads"], cfg["index_head_dim"]
    rot, theta = cfg["qk_rope_head_dim"], theta_of(cfg)
    pairs = cfg.get("indexer_rope_interleave", True)
    t = u.shape[0]
    q = product("tr,rd->td", c_q, p["wiq"]).reshape(t, heads, d)
    k = layer_norm(product("th,hd->td", u, p["wik"]), p["ik_scale"],
                   p["ik_bias"], cfg.get("index_norm_eps", 1e-6))[:, None]
    q = jnp.concatenate([rope(q[..., :rot], at, theta, pairs), q[..., rot:]],
                        -1)
    k = jnp.concatenate([rope(k[..., :rot], at, theta, pairs), k[..., rot:]],
                        -1)
    w = product("th,hj->tj", u, p["wiw"])
    if not cfg.get("index_head_weights", True):
        w = jnp.ones_like(w)
    return q, k[:, 0], w * (heads * d) ** -0.5


def attention(u, p, cfg, q_block, head_block=None, handed=None,
              p_index=None):
    """Latent attention over one row ``u (T, h)`` -> ``(out (T, h), selected
    (T', T) bool)``: ``selected[i, j]`` says that query ``i`` attended key
    ``j`` (``T'``: ``T`` padded to whole blocks).  ``handed``: a selection
    to attend under in place of the layer's own; ``p_index``: the indexer's
    weights (default the layer's own; ``None`` and no ``handed``: the causal
    mask alone).  ``head_block``: the heads are expanded and attended so
    many at a time (default all at once)."""
    t, _ = u.shape
    heads, q_lora, kv_lora = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                              cfg["kv_lora_rank"])
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    theta, pairs = theta_of(cfg), cfg.get("rope_interleave", True)
    eps = cfg["rms_norm_eps"]
    at = jnp.arange(t)
    c_q = rms_norm(product("th,hr->tr", u, p["wqa"]), p["q_norm"], eps)
    kva = product("th,hl->tl", u, p["wkva"])
    c_kv = rms_norm(kva[:, :kv_lora], p["kv_norm"], eps)
    k_r = rope(kva[:, None, kv_lora:], at, theta, pairs)
    scale = (nope + rot) ** -0.5
    # blocks of query rows, each against every key under the dense mask
    # (departure 2); the last block is padded with rows nothing reads
    blocks = -(-t // q_block)
    pad = blocks * q_block - t
    starts = jnp.arange(blocks) * q_block

    def rows_of(x, s):
        return jax.lax.dynamic_slice_in_dim(x, s, q_block, axis=0)

    def mask_of(s):
        return s + jnp.arange(q_block)[:, None] - at[None, :] >= 0

    selected = handed
    if selected is None and p_index is not None:
        top_k = min(cfg["index_topk"], t)
        q_i, k_i, w_i = indexer(u, c_q, p_index, cfg, at)
        q_i = jnp.pad(q_i, ((0, pad), (0, 0), (0, 0)))
        w_i = jnp.pad(w_i, ((0, pad), (0, 0)))

        def select(s):
            seen = mask_of(s)
            dots = product("qjd,td->jqt", rows_of(q_i, s), k_i)
            if cfg.get("index_relu", True):
                dots = jax.nn.relu(dots)
            scores = jnp.sum(dots * rows_of(w_i, s).T[:, :, None], axis=0)
            # the ``index_topk`` best visible keys, scattered into the mask
            # (a row that sees fewer picks masked ones too: ``& seen``)
            _, best = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), top_k)
            return seen & jnp.zeros(seen.shape, bool).at[
                jnp.arange(q_block)[:, None], best].set(True)

        selected = jax.lax.map(select, starts).reshape(blocks * q_block, t)
    elif selected is None:
        selected = jax.lax.map(mask_of, starts).reshape(blocks * q_block, t)

    hb = head_block or heads
    wqb = p["wqb"].reshape(q_lora, heads, nope + rot)
    wkvb = p["wkvb"].reshape(kv_lora, heads, nope + vd)
    c_q = jnp.pad(c_q, ((0, pad), (0, 0)))
    at_q = jnp.arange(blocks * q_block)

    def group(g):
        """Heads ``g * hb .. g * hb + hb - 1``: expanded, then attended."""
        q = product("tr,rhd->thd", c_q, jax.lax.dynamic_slice_in_dim(
            wqb, g * hb, hb, axis=1))
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], at_q, theta, pairs)], -1)
        kv = product("tl,lhd->thd", c_kv, jax.lax.dynamic_slice_in_dim(
            wkvb, g * hb, hb, axis=1))
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (t, hb, rot))], -1)
        v = kv[..., nope:]

        def block(s):
            logits = product("qhd,thd->hqt", rows_of(q, s), k) * scale
            probs = softmax(jnp.where(rows_of(selected, s), logits, -jnp.inf))
            return product("hqt,thd->qhd", probs, v)

        return jax.lax.map(block, starts)       # (blocks, q_block, hb, vd)

    outs = jax.lax.map(group, jnp.arange(heads // hb))
    o = outs.transpose(1, 2, 0, 3, 4).reshape(blocks * q_block, heads, vd)[:t]
    out = product("td,dh->th", o.reshape(t, heads * vd), p["wo"])
    return out, selected


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def route(u, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok``
    largest of ``sigmoid(u W_r) + bias``; the weights are the chosen
    sigmoids alone, over their sum + 1e-20 (``norm_topk_prob``), times
    ``routed_scaling_factor``."""
    scores = sigmoid(product("th,he->te", u, p["w"]))
    _, ids = jax.lax.top_k(scores + p["bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg["routed_scaling_factor"]


def routed(u, layer, cfg):
    """This chip's share of the routed experts over ``u (T, h)``
    (departure 1) plus the shared expert, and the router's choices."""
    ids, w = route(u, layer["router"], cfg)
    first = cfg.get("first_expert", 0)
    experts = layer["experts"]

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = swiglu(u, {k: experts[k][e] for k in ("wg", "wu", "wd")})
        return y + w_e[:, None] * out

    held = cfg.get("experts_held", cfg["n_routed_experts"])
    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros(u.shape, F32))
    if cfg.get("shared_expert", True):
        y = y + swiglu(u, layer["shared"])
    return y, ids


def by_rows(fn, x, block):
    """``fn`` (token-wise) over blocks of ``block`` rows (departure 2); the
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
                row_block=None, head_block=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at
    ``logit_positions (K,)`` (default every position), the routers' choices
    ``(expert layers, T, k)`` and the FULL layers' selections ``(full
    layers, K, T)`` bool at the same positions (a shared layer attends its
    full layer's).  ``cfg["shared_selection"]`` / ``cfg["full_selection"]``
    (default ``"borrow"`` / ``"own"``, the published rule) let a control
    plant another: a shared layer with ``"none"`` attends every visible key,
    with ``"own"`` runs the last full layer's indexer WEIGHTS on its own
    input; a full layer past the first with ``"borrow"`` attends under the
    selection before it and drops its own."""
    eps = cfg["rms_norm_eps"]
    t = tokens.shape[0]
    x = params["embed"][tokens].astype(F32)
    chosen, selected = [], []
    handed = p_index = None
    for i, layer in enumerate(params["layers"]):
        n, p = layer["norm"], layer["attn"]
        u = rms_norm(x, n[0], eps)
        if cfg["indexer_types"][i] == FULL:
            borrow = (cfg.get("full_selection", "own") == "borrow"
                      and handed is not None)
            out, seen = attention(u, p, cfg, q_block, head_block,
                                  handed if borrow else None, p)
            handed, p_index = seen, p
            selected.append(seen[:t] if logit_positions is None
                            else seen[logit_positions])
        else:
            rule = cfg.get("shared_selection", "borrow")
            out, _ = attention(
                u, p, cfg, q_block, head_block,
                handed if rule == "borrow" else None,
                p_index if rule == "own" else None)
        x = x + out
        u = rms_norm(x, n[1], eps)
        if cfg["mlp_layer_types"][i] == "dense":
            x = x + by_rows(lambda r, p=layer["ffn"]: swiglu(r, p), u,
                            row_block)
            continue
        m, ids = by_rows(lambda r, p=layer: routed(r, p, cfg), u, row_block)
        chosen.append(ids)
        x = x + m
    x = rms_norm(x, params["final_norm"], eps)
    if logit_positions is not None:
        x = x[logit_positions]
    logits = product("td,dv->tv", x, params["head"])
    return logits.astype(F32), jnp.stack(chosen), jnp.stack(selected)
