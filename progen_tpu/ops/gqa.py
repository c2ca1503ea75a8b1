"""Grouped-query attention cores: ``H`` query heads in groups of ``H / KV``
that share one of ``KV`` key/value heads (query head ``h`` reads key/value
head ``h // (H / KV)``), with a causal mask and an optional per-token
sliding window.  Both are plain XLA: the baseline a kernel would start from.

:func:`prefill_attention` — ``q (R, P, H, d)`` against ``k, v (R, KV, P,
d)``: position ``i`` sees ``j`` iff ``j <= i`` and, under a ``window``,
``i - j < window``.  Blocks of ``QUERY_BLOCK`` query rows against the keys
they can see, float32 softmax (divided by its sum after the value product);
no ``(P, P)`` tensor exists.  Under a window
every block has ONE shape — its own rows and the ``window`` before them, the
keys padded in front so that the first blocks have it too — and the blocks
are a ``lax.map`` over one body; without one a block's keys grow with it, so
``FULL_GROUP`` consecutive blocks share the keys of the last of them (a map
over one body a group, a tenth more keys than the causal half) and the
groups are unrolled: an 8192-token prefill is 8 bodies a full layer and 1 a
sliding layer, not 32 each, which is what its compile time and the size of
its cache entry follow.  Pad positions are computed; a real position sees
real keys only (the mask is causal), so its output does not depend on them.

:func:`decode_attention` — one query a slot, ``q (S, H, d)``, against the
first ``counts (S,)`` rows of ``k, v (S, KV, T, d)`` in whatever order they
lie: a ring of the last ``T`` tokens and a cache that grows with the
request differ only in where the caller wrote the row and in ``counts``
(keys carry their own rotary phase, and a softmax does not care for the
order of its terms).  ``counts`` is at least 1 everywhere (a decode step
has just written the row it stands on).  The whole cache is read: scores
``(S, H, T)`` in float32, a masked softmax, the value product.
:func:`rows_visited` says how many rows that is (the counters
``attn.window_rows_read`` / ``attn.full_rows_read``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256     # prefill: query rows per score block
FULL_GROUP = 4        # prefill, no window: blocks that share one key span


def _score_block(q, k, v, first_row, first_key, scale, window):
    """``q (R, KV, G, bq, d)`` at rows ``first_row + arange(bq)`` against
    ``k, v (R, KV, t, d)`` at positions ``first_key + arange(t)`` (a
    position below 0 is padding): ``(R, KV, G, bq, d)``."""
    logits = jnp.einsum("rkgqd,rktd->rkgqt", q, k,
                        preferred_element_type=F32) * scale
    at = first_key + jnp.arange(k.shape[2])[None, :]
    gap = first_row + jnp.arange(q.shape[3])[:, None] - at
    seen = (gap >= 0) & (at >= 0)
    if window is not None:
        seen = seen & (gap < window)
    # float32 scores, maximum and sum; the unnormalised probabilities are
    # cast for the value product and the ONE division by the sum comes
    # after it, on (bq, d) numbers and not (bq, t) — the repo's kernels'
    # convention, and here what keeps a second float32 score tensor out of
    # memory: 4 x 8192 x 32 heads without a window take 86 ms this way and
    # 1,098 ms through ``jax.nn.softmax`` (PERF.md section 6, PR 34).
    # Every row sees its own key, so the maximum is finite
    logits = jnp.where(seen, logits, -jnp.inf)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    out = jnp.einsum("rkgqt,rktd->rkgqd", p.astype(v.dtype), v,
                     preferred_element_type=F32)
    return (out / jnp.sum(p, axis=-1)[..., None]).astype(q.dtype)


def _rows(x, start, size):
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=x.ndim - 2)


def prefill_attention(q, k, v, scale, window=None):
    """``(R, P, H * d)`` in ``q``'s dtype."""
    r, n, heads, d = q.shape
    kv = k.shape[1]
    bq = min(QUERY_BLOCK, n)
    blocks = -(-n // bq)
    pad = blocks * bq - n
    q = q.reshape(r, n, kv, heads // kv, d).transpose(0, 2, 3, 1, 4)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (k, v))
    if window is not None:
        # the furthest any block looks back: the window, or all there is
        back = min(window, (blocks - 1) * bq)
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (back, 0), (0, 0)))
                for a in (k, v))

        def block(i):       # padded row ``s`` holds position ``s - back``
            s = i * bq
            return _score_block(_rows(q, s, bq), _rows(k, s, back + bq),
                                _rows(v, s, back + bq), s, s - back, scale,
                                window)

        out = jax.lax.map(block, jnp.arange(blocks))
    else:
        outs = []
        for first in range(0, blocks, FULL_GROUP):
            last = min(first + FULL_GROUP, blocks)
            keys, values = k[:, :, :last * bq], v[:, :, :last * bq]

            def block(i, keys=keys, values=values):
                return _score_block(_rows(q, i * bq, bq), keys, values,
                                    i * bq, 0, scale, None)

            outs.append(jax.lax.map(block, jnp.arange(first, last)))
        out = jnp.concatenate(outs, axis=0)
    # (blocks, R, KV, G, bq, d) -> (R, P, H * d)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(r, blocks * bq, heads * d)
    return out[:, :n]


def decode_attention(q, k, v, counts, scale):
    """``(S, H * d)`` in ``q``'s dtype."""
    s, heads, d = q.shape
    kv, t = k.shape[1], k.shape[2]
    q = q.reshape(s, kv, heads // kv, d)
    logits = jnp.einsum("skgd,sktd->skgt", q, k.astype(q.dtype),
                        preferred_element_type=F32) * scale
    seen = jnp.arange(t)[None, :] < counts[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None], logits, -jnp.inf), axis=-1)
    out = jnp.einsum("skgt,sktd->skgd", probs.astype(q.dtype),
                     v.astype(q.dtype), preferred_element_type=F32)
    return out.astype(q.dtype).reshape(s, heads * d)


def rows_visited(k):
    """Cache rows :func:`decode_attention` reads of ``k`` (and as many of
    ``v``) in one call, as a float32 scalar: every row of every slot,
    whatever the counts."""
    return jnp.asarray(k.shape[0] * k.shape[2], F32)
