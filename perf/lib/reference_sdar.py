"""SDAR's (``sdar_moe``) forward pass and its generation by diffusion over
blocks, in plain ``jax.numpy`` / ``numpy``, float32.

Written from the published configuration's equations and the family's
sampler (ISSUE 41, PERF.md section 4), not from ``progen_tpu``: nothing of
the program is imported.  No cache (every position attends over the keys
and values of the whole row under a mask given as a ``(T, T)`` boolean),
no kernels, routing by a top-k of a softmax renormalised over the chosen, a
dense loop over all the experts (every expert runs on every token and is
weighted by what the router gave it, zero where it was not chosen).
Callers wrap calls in ``jax.default_matmul_precision("highest")``.

Departures from the release (``modeling_sdar_moe.py`` and the family's
``generate.py``), each noted where it is made: (1) the rotation is the
half-split form on the stored column order — the release's own; (2) weights
are upcast where used, one matrix at a time, and attention runs over blocks
of query rows (one ``lax.map`` body a layer, each block against every key
under its rows of the mask) so that no ``(heads, T, T)`` tensor exists;
(3) no load-balancing loss; (4) a denoise forward keeps only MASKED
positions (the release's ``topk`` over confidences of ``-inf`` can re-draw a
prompt token of a first block; here a position that holds a token is never
taken); (5) a greedy draw (temperature 0) reads its confidence at
temperature 1; (6) the training-time noise schedule is no part of serving.

There is ONE path and it is float32.  Its arithmetic goes through three
named operations — :func:`product` (every matrix product), :func:`softmax`
and :func:`rms_norm` — so that ``perf/tools/sdar_lowp.py`` can wrap them and
show that the cell's limits refuse the same equations computed one notch
below the stated precision; the mask is an argument, so the same tool can
hand in a causal one.  Nothing here knows of that.

Layer ``l``: ``a = x + W_o Attn(rope(N_q(u W_q)), rope(N_k(u W_k)), u W_v)``
with ``u = N_in(x)``; ``out = a + sum_{e in top8(p)} (p_e / sum_top8 p)
E_e(t)`` with ``t = N_post(a)``, ``p = softmax(t W_r)``.  The logits at a
position predict that position's own token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EOS_ID = 0


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


def block_mask(t: int, block: int):
    """``(T, T)`` bool: query ``i`` sees key ``j`` iff ``j // block <= i //
    block`` — causal across blocks, every key inside one."""
    at = np.arange(t) // block
    return at[None, :] <= at[:, None]


def attention(x, p, cfg, positions, allowed, q_block):
    """Grouped-query attention over one row ``x (T, h)`` at ``positions
    (T,)`` under ``allowed (T, T)``; also the keys and values ``(T, KV, d)``
    as a cache would hold them."""
    t, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = product("th,hd->td", x, p["wq"]).reshape(t, heads, d)
    k = product("th,hd->td", x, p["wk"]).reshape(t, kv, d)
    v = product("th,hd->td", x, p["wv"]).reshape(t, kv, d)
    q = rope(rms_norm(q, p["q_norm"], eps), positions, cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"], eps), positions, cfg["rope_theta"])
    # query head h reads key/value head h // (heads / kv)
    q = q.reshape(t, kv, heads // kv, d)
    scale = d ** -0.5
    # blocks of query rows, each against every key under its rows of the
    # mask (departure 2); the last block is padded with rows nothing reads
    blocks = -(-t // q_block)
    pad = blocks * q_block - t
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    seen = jnp.pad(jnp.asarray(allowed), ((0, pad), (0, 0)),
                   constant_values=True)

    def block(s):
        rows = jax.lax.dynamic_slice_in_dim(q, s, q_block, axis=0)
        mask = jax.lax.dynamic_slice_in_dim(seen, s, q_block, axis=0)
        logits = product("qkgd,tkd->kgqt", rows, k) * scale
        probs = softmax(jnp.where(mask[None, None], logits, -jnp.inf))
        return product("kgqt,tkd->qkgd", probs, v)

    outs = jax.lax.map(block, jnp.arange(blocks) * q_block)
    o = outs.reshape(blocks * q_block, heads * d)[:t]
    return product("td,dh->th", o, p["wo"]), k, v


def swiglu(x, p):
    g = product("th,hf->tf", x, p["wg"])
    u = product("th,hf->tf", x, p["wu"])
    return product("tf,fh->th", jax.nn.silu(g) * u, p["wd"])


def route(t, p, cfg):
    """``(ids (T, k), weights (T, k))``: the ``num_experts_per_tok``
    largest of ``softmax(t W_r)``, renormalised to sum to 1
    (``norm_topk_prob``)."""
    w, ids = jax.lax.top_k(softmax(product("th,he->te", t, p["w"])),
                           cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w


def experts(t, router, weights, cfg):
    """The expert layer over ``t (T, h)`` and the router's choices."""
    ids, w = route(t, router, cfg)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = swiglu(t, {k: weights[k][e] for k in ("wg", "wu", "wd")})
        return y + w_e[:, None] * out

    return jax.lax.fori_loop(0, cfg["num_experts"], add_expert,
                             jnp.zeros(t.shape, F32)), ids


def forward_row(params, tokens, cfg, q_block=256, logit_positions=None,
                positions=None, allowed=None, key_positions=None):
    """Logits ``(K, V)`` float32 of one row ``tokens (T,)`` at the indices
    ``logit_positions (K,)`` (default every one), the routers' choices
    ``(layers, T, k)`` and, where ``key_positions (J,)`` is given, the keys
    and values of every layer at those indices ``(layers, 2, J, KV, d)``.
    ``positions (T,)``: where each token stands (default its index);
    ``allowed (T, T)``: which keys each query sees (default the block mask
    of ``cfg["block_length"]``)."""
    eps = cfg["rms_norm_eps"]
    t = tokens.shape[0]
    if positions is None:
        positions = jnp.arange(t)
    if allowed is None:
        allowed = block_mask(t, cfg["block_length"])
    x = params["embed"][tokens].astype(F32)
    chosen, kept = [], []
    for layer in params["layers"]:
        n = layer["norm"]
        attn, k, v = attention(rms_norm(x, n[0], eps), layer["attn"], cfg,
                               positions, allowed, q_block)
        if key_positions is not None:
            kept.append(jnp.stack([k[key_positions], v[key_positions]]))
        a = x + attn
        m, ids = experts(rms_norm(a, n[1], eps), layer["router"],
                         layer["experts"], cfg)
        chosen.append(ids)
        x = a + m
    x = rms_norm(x, params["final_norm"], eps)
    if logit_positions is not None:
        x = x[logit_positions]
    out = (product("td,dv->tv", x, params["head"]).astype(F32),
           jnp.stack(chosen))
    return out + (jnp.stack(kept),) if key_positions is not None else out


# ------------------------------------------------- generation, block by block


def transfer_counts(block: int, steps: int) -> list:
    """Positions the static rule fills at each denoise forward."""
    return [block // steps + (i < block % steps) for i in range(steps)]


def draw(logits, allowed, top_k, temperature, rng=None):
    """One position's draw from its ``logits (V,)``: ``(token, confidence,
    the filtered distribution (V,))``.  ``allowed (V,)`` bool; the ``top_k``
    largest allowed logits are kept (ties at the k-th all; ``top_k`` None or
    0: every allowed one); the token is the argmax at temperature 0
    (confidence read at temperature 1, departure 5), else drawn from the
    softmax of the kept ``logits / temperature`` by ``rng``."""
    scaled = np.where(allowed, np.asarray(logits, np.float64), -np.inf)
    if temperature:
        scaled = scaled / temperature
    if top_k:
        scaled = np.where(scaled >= np.sort(scaled)[-top_k], scaled, -np.inf)
    probs = np.exp(scaled - scaled.max())
    probs /= probs.sum()
    token = (int(np.argmax(scaled)) if not temperature
             else int(rng.choice(len(probs), p=probs)))
    return token, float(probs[token]), probs


def keep(confidence, masked, count, threshold=None):
    """Which positions of a block take their draw: the ``count`` masked
    ones of highest ``confidence`` (ties to the lower index), every masked
    one whose confidence is over ``threshold`` besides (the dynamic rule);
    never one that holds a token (departure 4)."""
    conf = np.where(masked, confidence, -np.inf)
    order = np.argsort(-conf, kind="stable")
    take = np.zeros(len(conf), bool)
    take[order[:count]] = True
    if threshold is not None:
        take |= conf > threshold
    return take & masked


def generate_block(params, prime, cfg, max_new, *, forward=None,
                   width=None, denoising_steps=None, remasking=None,
                   threshold=None, top_k=None, temperature=0.0,
                   allowed_tokens=None, rng=None):
    """The family's sampler, token by token, the WHOLE sequence recomputed
    at every forward: ``(generated tokens, the denoise forward that filled
    each)``.  For each block at ``[p0, p0 + B)``: (1) a denoise forward of
    the tokens so far and the block (mask tokens among them); (2) a draw
    and its confidence at every position; (3) the masked positions the rule
    picks keep their draw; (4) when no mask is left the block is committed
    (without a cache there is nothing to write) and ``p0 += B``.  Tokens
    after an end-of-sequence token inside a committed block, and past
    ``max_new``, are dropped.  ``forward(params, tokens (width,),
    logit_positions (B,)) -> logits (B, V)`` defaults to
    :func:`forward_row` over rows padded to ``width`` (one shape, so one
    compiled program; the block mask keeps what follows a block out of
    it)."""
    b, mask_id = cfg["block_length"], cfg["mask_token_id"]
    steps = denoising_steps or cfg["denoising_steps"]
    remasking = remasking or cfg["remasking"]
    if remasking == "low_confidence_static":
        threshold = None
    elif threshold is None:
        threshold = cfg["confidence_threshold"]
    counts = transfer_counts(b, steps)
    prime = [int(t) for t in prime]
    p, stop = len(prime), len(prime) + max_new
    width = width or -(-stop // b) * b
    allowed = np.ones(cfg["vocab_size"], bool) if allowed_tokens is None \
        else np.array(allowed_tokens, bool)
    allowed[mask_id] = False
    if forward is None:
        fwd = jax.jit(lambda params, tokens, at: forward_row(
            params, tokens, cfg, logit_positions=at)[0])

        def forward(params, tokens, at):
            with jax.default_matmul_precision("highest"):
                return fwd(params, tokens, at)

    p0 = p // b * b
    seq = prime[:p0]
    block = prime[p0:] + [mask_id] * (b - (p - p0))
    out, fills = [], []
    fill = [-1] * b
    step = 0
    while True:
        masked = np.array([t == mask_id for t in block])
        if not masked.any():                                    # (4)
            done = False
            for j, (tok, s) in enumerate(zip(block, fill)):
                if p0 + j < p:
                    continue
                if p0 + j >= stop:
                    done = True
                    break
                out.append(tok)
                fills.append(s)
                if tok == EOS_ID:
                    done = True
                    break
            if done or p0 + b >= stop:
                return out, fills
            seq, p0 = seq + block, p0 + b
            block, fill, step = [mask_id] * b, [-1] * b, 0
            continue
        row = np.zeros(width, np.int32)                         # (1)
        row[:p0 + b] = seq + block
        logits = np.asarray(forward(params, row, np.arange(p0, p0 + b)))
        draws = [draw(logits[j], allowed, top_k, temperature, rng)
                 for j in range(b)]                             # (2)
        take = keep(np.array([c for _, c, _ in draws]), masked,
                    counts[min(step, steps - 1)], threshold)    # (3)
        for j in np.flatnonzero(take):
            block[j], fill[j] = draws[j][0], step
        step += 1


# --------------------------------------------- a trajectory in one forward


def replay_row(prime, generated, fills, cfg, steps: int, width=None):
    """A served trajectory laid out so that ONE forward computes every
    denoise forward of it: the clean row (prime and generated tokens, whole
    blocks only) followed by one NOISY copy of the generated blocks for each
    denoise forward ``s`` — a position holds its token if it was filled
    before ``s`` (prime tokens always) and the mask token if not.  A noisy
    block sees the clean blocks before it and itself; a clean block sees the
    clean blocks up to itself.  That is exactly what the family's sampler
    feeds forward ``s`` of that block, at the same positions.

    ``-> (tokens, positions, allowed, index)``: the row (padded to
    ``width``), where each token stands, the ``(T, T)`` mask, and ``index
    (len(generated),)`` — for each generated token the row's index of the
    position that PREDICTED it, in the noisy copy of the forward that
    filled it (-1 for a token of a last, partial block: its block's dropped
    tokens are not known).  ``tests/test_sdar_model.py`` holds this equal to
    :func:`generate_block`'s forwards."""
    b, mask_id = cfg["block_length"], cfg["mask_token_id"]
    p = len(prime)
    row = np.concatenate([np.asarray(prime, np.int64),
                          np.asarray(generated, np.int64)])
    filled = np.concatenate([np.full(p, -1), np.asarray(fills)])
    whole, end = p // b * b, len(row) // b * b
    span = end - whole
    total = end + steps * span
    width = width or total
    tokens = np.zeros(width, np.int32)
    positions = np.zeros(width, np.int32)
    tokens[:end], positions[:end] = row[:end], np.arange(end)
    blk = np.full(width, -1)            # block of a clean token
    noisy = np.full(width, -1)          # (forward, block) of a noisy one
    blk[:end] = np.arange(end) // b
    for s in range(steps):
        at = slice(end + s * span, end + (s + 1) * span)
        tokens[at] = np.where(filled[whole:end] < s, row[whole:end], mask_id)
        positions[at] = np.arange(whole, end)
        noisy[at] = s * (end // b + 1) + np.arange(whole, end) // b
    q_blk = np.where(noisy >= 0, positions // b, blk)
    clean_key = blk >= 0
    allowed = np.where(
        (noisy >= 0)[:, None],
        (clean_key[None, :] & (blk[None, :] < q_blk[:, None]))
        | (noisy[None, :] == noisy[:, None]),
        clean_key[None, :] & (blk[None, :] <= q_blk[:, None]))
    allowed |= np.eye(width, dtype=bool)        # padding sees itself
    at = np.arange(p, len(row))
    index = np.where(at < end, end + filled[p:] * span + at - whole, -1)
    return tokens, positions, allowed, index
