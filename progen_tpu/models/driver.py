"""The model driver of the families served as plain functions over a
parameter dict (no flax): embedding -> a family's layer stack -> logits,
the seeded weights' common pieces, and the seam ``ServingEngine`` calls
(``decode/family.py``).  What a family brings is its config, its ``stack``
and **its mixer blocks, each of which states its own cache**
(``blocks_of(config)``):
``models/latent.py`` has one kind (a latent row per token, ``max_len``
long), ``models/trinity.py`` two in one model (a ring of ``sliding_window``
rows beside keys and values that grow with the request, both
``models/kv.py``'s), ``models/granite_hybrid.py`` a RECURRENT STATE (a
carry and a convolution tail a slot, as large at token 1 as at token
100,000: ``models/state.py``'s block) beside grown keys, ``models/sdar.py``
grown keys that take a BLOCK of tokens a step and are written only when the
block is committed (:func:`block_step`), ``models/lfm2.py`` blocks whose
WHOLE cache is a convolution's two-row tail beside a few blocks of grown
keys, ``models/nemotron_h.py`` layers that are ONE sublayer each — the state
block at eight groups, a grown-key block, or an expert layer that states NO
cache: ``blocks_of`` names a block only where a layer has one.

**A block** (``blocks_of(config)`` gives ``{name: block}``, one per
mixer of the stack that keeps a cache, in the stack's order) is an object
with

``init_cache(slots, max_len, dtype)``
    the block's cache of ``slots`` idle rows: a pytree whose every leaf
    has the slot as its leading axis.  Its other axes are the block's own
    business — rows per token, a ring, a state that does not depend on
    ``max_len`` at all: the driver, the family and the engine never look
    inside
``prefill(x (R, P, h), weights, lengths (R,))``
    ``(out (R, P, h), rows)``: the mixer over R right-padded rows, and
    whatever the block's own ``cache_rows`` lays out (leaves with R
    leading): per TOKEN what the cache will hold of it for a block that
    caches rows; for a state block the state at each row's TRUE length
``cache_rows(rows, lengths, max_len)``
    what ``prefill`` returned laid out as the block's cache of R slots:
    where a token's row goes and how many rows a slot has is said here and
    in ``decode`` alone
``decode(x (S, h), pos (S,), cache, weights)``
    ``(out (S, h), cache)``: one token a row at position ``pos``, written
    into the cache (or folded into the state) and mixed up to it
``selection``
    only of a block that attends under a SELECTION of keys which one block
    computes and the blocks after it reuse (``models/latent.py``'s option,
    ``models/glm_dsa.py``'s layers): ``"own"`` or ``"borrow"``.  Such a
    block's ``prefill`` and ``decode`` take one more argument, what the last
    owner before it HANDED ON (``None`` before the first), and return it as
    a third result: an owner hands on its own, a borrower what it was
    given.  The driver carries it from block to block inside the one
    program and never looks inside; a block without the attribute is called
    as above
``decode_block(x (S, B or 2B, h), pos0 (S,), cache, weights, commit (S,), queries=None)``
    only of a block whose family generates B tokens a row a step
    (:func:`block_step`; ``models/kv.py``'s grown keys have it): ``(out,
    cache)`` of ``x``'s shape (of its last ``queries`` rows alone where
    that is given), the B tokens at ``pos0 .. pos0 + B - 1``
    mixed over the slot's committed rows and each other — behind, where
    ``x`` is ``2B`` long, the finished block at ``pos0 - B`` whose keys are
    this forward's to write.  The FIRST B rows of ``x`` are written into
    the cache where ``commit`` and nothing where not

**Experts are a family's statement**, not the driver's assumption.  A
family with a share of an expert layer (``models/experts.py``: experts of
three matrices, a gated SwiGLU, or of two with ``relu^2`` between them) names
``"moe.held_load"`` among its ``stat_keys``, its stack returns the
counters, the routers' choices and the held experts touched, and its config
has ``experts_held``; the driver then adds the ``moe.*`` counters that are
a whole call's.  A family without experts returns no such counter, no
choices and 0 touched, and nothing here reads ``experts_held``.  Likewise
the head: ``params["head"]`` where the family unties it, the embedding's
transpose where ``params`` has none (``tie_word_embeddings``), and the
logits divided by the config's ``logits_scaling`` where it has one.

Precision: parameters and matrix products in the policy's dtypes (bfloat16
as published); the routers, every softmax, the norms' statistics and the
logits in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.models import kv
from progen_tpu.models.experts import zero_stats
from progen_tpu.ops.moe_decode import clipped

F32 = jnp.float32


def bf16_policy() -> Policy:
    """Parameters stored in bfloat16, as the sources publish them."""
    return make_policy(True, param_dtype=jnp.bfloat16)


# ------------------------------------------------------------------ weights


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def init_norm(key, shape, dt):
    return normal(key, shape, 0.05, F32).astype(dt) + 1


def init_ffn(key, h, width, gain, dt, lead=()):
    ks = jax.random.split(key, 3)
    return {
        "wg": normal(ks[0], lead + (h, width), h ** -0.5, dt),
        "wu": normal(ks[1], lead + (h, width), h ** -0.5, dt),
        "wd": normal(ks[2], lead + (width, h), gain * width ** -0.5, dt),
    }


def init_params(config, key, policy: Policy, init_layer, *,
                embed_std=None, tied_head: bool = False,
                final_norm_gain: float = 1.0):
    """Seeded weights, made on the device one layer per program so that no
    more than a layer's random bits are live beside the weights.
    ``init_layer(key, index)`` makes one layer's dict.  The embedding's
    rows are ``normal(0, embed_std)``, by default ``1 / embed_gain`` so
    that the stream starts at unit scale whatever factor the family puts on
    the embedding.  ``tied_head``: no ``"head"`` (the logits read the
    embedding); ``final_norm_gain`` multiplies the last norm's scale."""
    c, dt, h = config, policy.param_dtype, config.hidden_size
    keys = jax.random.split(key, c.num_layers + 3)
    std = 1.0 / c.embed_gain if embed_std is None else embed_std
    params = {
        "embed": jax.jit(lambda k: normal(
            k, (c.vocab_size, h), std, dt))(keys[0]),
        "final_norm": jax.jit(lambda k: init_norm(k, (h,), dt) * jnp.asarray(
            final_norm_gain, dt))(keys[2]),
        "layers": [init_layer(keys[3 + i], i) for i in range(c.num_layers)],
    }
    if not tied_head:
        params["head"] = jax.jit(lambda k: normal(
            k, (h, c.vocab_size), h ** -0.5, dt))(keys[1])
    return params


# ------------------------------------------------------------------- pieces


def rms_norm(x, scale, eps):
    """Statistics in float32, the result in ``x``'s dtype."""
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(
        x.dtype)


def stack_norm(x, scale, eps):
    """:func:`rms_norm` where a STACK calls it, between two blocks, under a
    name of its own; a norm inside a block (a head's, a state's) calls
    :func:`rms_norm` and keeps its block's name."""
    with jax.named_scope("norm.rms"):
        return rms_norm(x, scale, eps)


def residual(x, branch):
    """``x + branch`` where a stack ends a branch: the add the compiler
    fuses with the next norm's statistics, under the norm's group.  Two
    branches nest, ``residual(residual(x, a), b())``: Python then traces
    ``x + a`` before ``b()``, as ``x + a + b()`` did."""
    with jax.named_scope("norm.residual"):
        return x + branch


def rope(x, positions, inv_freq):
    """Half-split rotation of ``x (..., n, [heads,] d)`` at ``positions
    (..., n)``; ``inv_freq(d)`` gives the ``d / 2`` frequencies; tables in
    float32."""
    d = x.shape[-1]
    inv = inv_freq(d)
    ang = positions.astype(F32)[..., None] * inv
    if x.ndim == ang.ndim + 1:          # a heads axis between n and d
        ang = ang[..., None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_pairs(x, positions, inv_freq):
    """Rotation of the INTERLEAVED pairs ``(2i, 2i + 1)`` of ``x``'s last
    axis (``rope_interleave``), arguments as :func:`rope`'s.  The result's
    columns are in the program's own order — the pairs' first members, then
    their second: every product of two vectors rotated here is the product
    of the same two in the published order, and nothing else reads a
    rotated column."""
    return rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                positions, inv_freq)


def mm(x, w):
    return jnp.dot(x, w.astype(x.dtype))


def swiglu(x, p, scope="ffn.dense", limit: float = 0.0):
    """``(silu(x W_g) * (x W_u)) W_d``; under a ``limit`` the two products
    are clipped BEFORE the activation (``ops/moe_decode.py:clipped``;
    ``models/bailing_hybrid.py``'s last layers)."""
    with jax.named_scope(scope):
        if not limit:
            return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wu"]), p["wd"])
        with jax.named_scope("moe.clip"):
            gate, up = clipped(mm(x, p["wg"]), mm(x, p["wu"]), limit)
        return mm(jax.nn.silu(gate) * up, p["wd"])


def _embed(params, tokens, c, dt):
    with jax.named_scope("embed.tokens"):
        x = params["embed"][tokens].astype(dt)
        if c.embed_gain != 1:
            x = x * jnp.asarray(c.embed_gain, dt)
        return x


def _logits(x, params, c):
    with jax.named_scope("head.logits"):
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        if "head" in params:
            out = jnp.dot(x, params["head"].astype(x.dtype),
                          preferred_element_type=F32)
        else:   # tied: the embedding (V, h) read along h, never transposed
            out = jnp.einsum("...h,vh->...v", x,
                             params["embed"].astype(x.dtype),
                             preferred_element_type=F32)
        scaling = getattr(c, "logits_scaling", 1)
        return out / scaling if scaling != 1 else out


# --------------------------------------------------------------- the driver


def prefill(stack, blocks, params, tokens, lengths, config, policy: Policy,
            *, logit_positions=None, with_choices: bool = False):
    """``tokens (R, P)`` right-padded rows of ``lengths (R,)`` real tokens
    -> ``(logits (R, K, V) float32 at logit_positions (R, K)`` (default the
    last real position, K = 1), ``per-token cache rows {block: rows},
    stats)``: what each block's ``prefill`` returned, not yet laid out as
    a cache (``block.cache_rows`` does that).  ``stack(x, params, config,
    attend, live)`` is the family's layers over flat tokens, ``attend(x,
    block name, weights)`` the one thing prefill and decode differ in; it
    returns ``(x, stats, chosen ids per expert layer, held experts
    touched)`` (no ``moe.*`` counter, no choices and 0 touched from a family
    without experts).  Padding, and the whole of a row of length 0 (an
    admission row that carries no request), is computed by the dense FFNs
    (the shapes are static) but not by the experts, and by attention only
    where a blocked XLA form runs; it is not counted, and no real position's
    output depends on what it holds."""
    c = config
    dt = policy.compute_dtype
    r, n = tokens.shape
    live = (jnp.arange(n)[None, :] < lengths[:, None]).reshape(-1)
    rows = {}
    handed = [None]     # a selection on its way from its owner to borrowers

    def attend(x, name, p):
        block, x = blocks[name], x.reshape(r, n, -1)
        if getattr(block, "selection", None):
            out, rows[name], handed[0] = block.prefill(x, p, lengths,
                                                       handed[0])
        else:
            out, rows[name] = block.prefill(x, p, lengths)
        return out.reshape(r * n, -1)

    x = _embed(params, tokens.reshape(-1), c, dt)
    x, stats, chosen, _ = stack(x, params, c, attend, live)
    if "moe.held_load" in stats:
        with jax.named_scope("engine.stats"):
            stats["moe.prefill_held"] = jnp.sum(stats["moe.held_load"])
        # the rows the lowering passed through the experts for them
        stats["moe.prefill_rows_computed"] = stats["moe.rows_computed"]
        # a decode step's counters, as ``moe.experts_touched`` beside them
        stats["moe.expert_passes"] = jnp.zeros((), F32)
        stats["moe.rows_computed"] = jnp.zeros((), F32)
    if logit_positions is None:       # a row of no tokens reads position 0
        logit_positions = jnp.maximum(lengths - 1, 0)[:, None]
    with jax.named_scope("head.logits"):
        x = jnp.take_along_axis(x.reshape(r, n, -1),
                                logit_positions[..., None], axis=1)
    out = _logits(x, params, c), rows, stats
    if with_choices:
        return out + (jnp.stack(chosen).reshape(len(chosen), r, n, -1),)
    return out


def cache_rows(blocks, rows, lengths, max_len: int) -> dict:
    """What :func:`prefill` returned (``{block: rows}``), laid out as the
    blocks' caches of R slots in an engine of ``max_len``."""
    with jax.named_scope("engine.rows"):
        return {name: blocks[name].cache_rows(v, lengths, max_len)
                for name, v in rows.items()}


def _step_moe_stats(stats, chosen, touched, live) -> None:
    """A step's own ``moe.*`` counters, for a family with experts: the
    expert layers it ran (if any row was live) and the held experts it
    touched."""
    if "moe.held_load" in stats:
        with jax.named_scope("engine.stats"):
            stats["moe.decode_layers"] = jnp.asarray(
                len(chosen), F32) * jnp.any(live)
        stats["moe.experts_touched"] = touched


def decode_step(stack, blocks, attention_stats, params, tok, pos, caches,
                live, config, policy: Policy, *, with_choices: bool = False):
    """One token per row: ``tok (S,)`` at ``pos (S,)`` -> ``(logits (S, V)
    float32, caches, stats)``.  Rows that are not ``live`` run (the batch
    is static) but are not counted and reach no expert.
    ``attention_stats(dtype, caches, pos, live)`` gives the family's
    counters of the step's attention (its rows, their contexts, the cache
    rows the lowering that ran reads)."""
    c = config
    dt = policy.compute_dtype
    caches = dict(caches)
    handed = [None]     # as :func:`prefill`'s

    def attend(x, name, p):
        block = blocks[name]
        if getattr(block, "selection", None):
            out, caches[name], handed[0] = block.decode(
                x, pos, caches[name], p, handed[0])
        else:
            out, caches[name] = block.decode(x, pos, caches[name], p)
        return out

    x = _embed(params, tok, c, dt)
    x, stats, chosen, touched = stack(x, params, c, attend, live)
    _step_moe_stats(stats, chosen, touched, live)
    with jax.named_scope("engine.stats"):
        stats.update(attention_stats(dt, caches, pos, live))
    out = _logits(x, params, c), caches, stats
    if with_choices:
        return out + (jnp.stack(chosen),)
    return out


def block_step(stack, blocks, attention_stats, params, tok, pos0, caches,
               live, commit, config, policy: Policy, *, pending=None,
               with_choices: bool = False):
    """``B`` tokens per row, for a family that generates by diffusion over
    blocks: ``tok (S, B)`` (mask tokens among them) at ``pos0 .. pos0 + B
    - 1`` -> ``(logits (S, B, V) float32, caches, stats)``.  ``commit (S,)``
    says in which rows keys and values enter the cache IN THIS FORWARD, and
    nothing is stored where not.  They are ``tok``'s own where ``pending``
    is None.  With ``pending (S, B)`` — each slot's finished block, final
    tokens at ``pos0 - B .. pos0 - 1`` whose keys no forward has written —
    they are the pending block's: it rides in front of ``tok``, every layer
    is one forward of all ``S * 2B`` tokens (``decode_block`` says what
    each half sees) but the last, which needs the pending rows' keys and
    values only — its attention returns ``tok``'s rows and the stack goes
    on with them (``stack(..., tail=)``: what takes ``tok``'s rows of an
    array of all) — and the head runs over ``tok``'s rows alone.  A slot
    whose ``commit`` is false has no pending block: its front half is
    filler that no other row sees, written nowhere, reaching no expert and
    counted by no counter.  Rows that are not ``live`` run but are not
    counted and reach no expert (``commit`` is false there).
    ``attention_stats(dtype, caches, pos0, live, riding)`` as
    :func:`decode_step`'s, of a step of B query rows a slot and B more
    where a pending block rides (``riding``: ``commit`` with a pending
    block, None without)."""
    c = config
    dt = policy.compute_dtype
    caches = dict(caches)
    s, b = tok.shape
    rows_live, riding = live[:, None], None
    if pending is not None:
        riding = commit
        tok = jnp.concatenate([pending, tok], axis=1)
        rows_live = jnp.stack([commit, live], axis=1)
    rows_live = jnp.repeat(rows_live, b, axis=1).reshape(-1)
    n = tok.shape[1]
    last = list(blocks)[-1]

    def mine(x):
        """``tok``'s rows of ``x (S * n, ...)``."""
        return x.reshape((s, n) + x.shape[1:])[:, n - b:].reshape(
            (s * b,) + x.shape[1:])

    def attend(x, name, p):
        # the last layer asks nothing of a pending block but its keys
        out, caches[name] = blocks[name].decode_block(
            x.reshape(s, n, -1), pos0, caches[name], p, commit,
            b if name == last else None)
        return out.reshape(-1, out.shape[-1])

    x = _embed(params, tok.reshape(-1), c, dt)
    x, stats, chosen, touched = stack(x, params, c, attend, rows_live,
                                      tail=mine)
    _step_moe_stats(stats, chosen, touched, live)
    with jax.named_scope("engine.stats"):
        stats.update(attention_stats(dt, caches, pos0, live, riding))
    out = _logits(x, params, c).reshape(s, b, -1), caches, stats
    if with_choices:
        return out + (jnp.stack([
            ids.reshape(s, -1, ids.shape[-1])[:, -b:] for ids in chosen]),)
    return out


# ------------------------------------------------------- the engine's seam


def zero_scalars(keys) -> dict:
    """The counters of a family without experts, all scalars, at zero."""
    return {k: jnp.zeros((), F32) for k in keys}


class Family:
    """What ``ServingEngine``'s plain dense path calls
    (``decode/family.py``), for a family of this driver.  A family names
    itself and brings ``stack`` (its layers), ``stat_keys`` (its device
    counters; ``"moe.held_load"`` among them says it has experts),
    ``blocks_of(config)`` (its mixer blocks by name, each stating its
    cache) and ``attention_stats``."""

    name: str
    stat_keys: tuple
    position_masks = False      # the state holds an (S, V) mask, not (S, L, V)
    idle_length = 0             # a row without a request has no token
    modes = frozenset()         # the plain dense path only
    block_length = None         # one token a row a step (decode/family.py)
    step_model = prefill_model = None

    def __init__(self, config, policy: Policy):
        self.config = config
        self.policy = policy
        self.blocks = self.blocks_of(config)
        self.bucket_base = config.prefill_bucket
        self.vocab = config.vocab_size
        self.seq_len = config.seq_len

    def embedder(self, mesh=None, strategies=()):
        return None

    def init_caches(self, slots: int, max_len: int):
        return {name: block.init_cache(slots, max_len,
                                       self.policy.compute_dtype)
                for name, block in self.blocks.items()}

    def init_stats(self) -> dict:
        if "moe.held_load" in self.stat_keys:
            return zero_stats(self.stat_keys, self.config.experts_held)
        return zero_scalars(self.stat_keys)

    def bucket(self, prime_len: int, max_len: int) -> int:
        b = self.bucket_base
        while b < prime_len:
            b *= 2
        return min(b, -(-max_len // self.bucket_base) * self.bucket_base)

    def buckets(self, cap: int, max_len: int) -> list[int]:
        out = []
        p = 1
        while p <= cap:
            out.append(self.bucket(p, max_len))
            p = out[-1] + 1
        return out

    def prefill(self, params, tokens, lengths, max_len, adapters=None,
                tenant=None):
        logits, rows, stats = prefill(self.stack, self.blocks, params,
                                      tokens, lengths, self.config,
                                      self.policy)
        caches = cache_rows(self.blocks, rows, lengths, max_len)
        return logits[:, 0], caches, stats

    def decode_step(self, params, tok, pos, caches, live, adapters=None,
                    tenant=None):
        return decode_step(self.stack, self.blocks, self.attention_stats,
                           params, tok, pos, caches, live, self.config,
                           self.policy)

    def publish(self, stats: dict) -> dict:
        """Registry gauges from the fetched counters (cumulative since the
        engine was built): name -> value."""
        out = {k: float(v) for k, v in stats.items() if k != "moe.held_load"}
        # the cache bytes the grouped-query blocks read, from their rows
        out.update(kv.byte_gauges(self.blocks, out,
                                  self.policy.compute_dtype))
        load = stats.get("moe.held_load")
        if load is not None:
            out["moe.held_assignments"] = float(load.sum())
            out["moe.held_load_max"] = float(load.max())
            out["moe.held_load_mean"] = float(load.mean())
        return out
