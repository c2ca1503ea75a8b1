"""A grouped-query attention block that caches its keys and values, and
what it states about that cache (``models/driver.py`` says what a block
is).  Shared by the families whose attention is grouped-query
(``models/trinity.py``, ``models/granite_hybrid.py``, ``models/sdar.py``,
``models/lfm2.py``, ``models/nemotron_h.py``, ``models/mimo_v2.py``): a
family brings the PROJECTION (:meth:`KVBlock.project` and
:meth:`KVBlock.finish`: its matrices, norms, rotation, gate and output) and
the scale of its scores; the cache's layout, the prefill's rows laid out as
a slot's cache and the decode step's write-then-attend are here.  The
attention cores are ``ops/gqa.py``.

Both kinds of cache hold ``{"k": (slots, KV, rows, d), "v": (slots, KV,
rows, dv)}`` — the token axis second to last, so that the chip tiles
``(rows, d)`` and the step's write is ``ops/row_write.py``'s kernel — and
both are read by one decode core (``ops/gqa.py:decode_attention``) up to a
per-slot count.  ``dv`` is ``d`` unless the family says otherwise
(``v_head_dim``: MiMo's values are 128 wide beside keys of 192); the two
arrays are then written by a call each, since one call of the row write
takes arrays of one shape, and read by the same decode core — on a TPU its
kernel ``gqa_decode_fwd`` takes the two widths (a grown cache's rows up to
a slot's count, in key tiles), and so does the prefill's
(``gqa_prefill_fwd``, over keys padded to the lane tile on the way in: the
rows a prefill hands back are the projected ones, at the published width).
A block with a ``sink`` hands its layer's
``p["sink"] (H,)`` to both cores: one more term of every softmax, which
only their XLA forms have; a ring under ``gqa.MIN_TILE`` rows keeps the XLA
decode core too (``ops/gqa.py``: "Two widths and a sink").  They differ in
two lines:

* a windowed block's cache is a RING of ``min(window, max_len)`` rows: the
  token at position ``p`` lies in row ``p % rows`` and a slot at ``pos``
  has ``min(pos + 1, rows)`` of them (keys are cached as projected, rotated
  where the family rotates them, so their order in the ring does not
  matter);
* a full block's cache (``window`` None) GROWS with the request:
  ``max_len`` rows, the token at ``p`` in row ``p``, ``pos + 1`` of them.

A grown cache also takes a BLOCK of ``B`` tokens a row a step
(:meth:`KVBlock.decode_block`, for a family that generates by diffusion
over blocks, ``models/sdar.py``): the B queries at ``pos0 .. pos0 + B - 1``
see the slot's ``pos0`` committed rows and each other's keys, whatever
order they were filled in, and the write of the B new rows MAY BE WITHHELD
row by row of the batch — a denoise forward leaves the cache as it found
it.  A finished block's keys are written by the forward that OPENS THE NEXT
block: the step then takes ``2 B`` tokens a row, the pending block in front
of the block in progress, under the mask the prefill has — causal across
blocks of ``block`` tokens and open inside one — with the pending keys in
the forward's own tile beside the block's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.ops import gqa
from progen_tpu.ops.row_write import write_row_blocks, write_rows

F32 = jnp.float32

DECODE_STAT_KEYS = ("attn.decode_rows", "attn.context_tokens",
                    "attn.window_tokens", "attn.window_rows_read",
                    "attn.full_rows_read")
# what :func:`byte_gauges` derives from the two ``*_rows_read`` counters
# when they are published: no counter of their own rides in the scan
BYTE_GAUGES = ("attn.window_bytes_read", "attn.full_bytes_read")


class KVBlock:
    """One attention block of ``kv_heads`` key/value heads of ``head_dim``
    (values ``v_head_dim`` wide where that is given), scores scaled by
    ``scale``: ``window`` rows in a ring, or ``max_len`` rows that grow with
    the request (``window`` None).  ``sink``: the layer's weights hold
    ``"sink" (H,)``, a learned term of every softmax.  A family's subclass
    has :meth:`project` and :meth:`finish`."""

    def __init__(self, kv_heads: int, head_dim: int, scale: float,
                 window: int | None = None, block: int = 1, *,
                 v_head_dim: int | None = None, sink: bool = False):
        if block != 1 and window is not None:
            raise ValueError("a block mask goes with grown keys, not a ring")
        if block != 1 and (sink or v_head_dim not in (None, head_dim)):
            raise ValueError("the block form takes one width and no sink")
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.v_head_dim = head_dim if v_head_dim is None else v_head_dim
        self.sink = sink
        self.scale = scale
        self.window = window
        # the prefill's mask: causal (1), or causal across blocks of
        # ``block`` tokens and open inside one
        self.block = block
        self.scope = "attn.full" if window is None else "attn.window"

    def project(self, x, p, positions):
        """``x (R, n, h)`` at ``positions (R, n)`` -> ``(q (R, n, H, d), k
        (R, n, KV, d), v (R, n, KV, dv), rest)``; ``rest`` is whatever else
        of the token :meth:`finish` takes (a gate; None), leaves ``(R, n,
        ...)``."""
        raise NotImplementedError

    def finish(self, o, rest, p):
        """The block's output ``(..., h)`` from the core's ``o (..., H *
        dv)`` and ``rest`` as :meth:`project` gave it (a decode step passes
        both without the token axis)."""
        raise NotImplementedError

    def rows(self, max_len: int) -> int:
        """Rows a slot's cache has in an engine of ``max_len``."""
        return max_len if self.window is None else min(self.window, max_len)

    def place(self, pos, rows: int):
        """``(the row the token at pos lies in, the rows a slot at pos
        has)`` of a cache of ``rows`` rows."""
        if self.window is None:
            return pos, pos + 1
        return pos % rows, jnp.minimum(pos + 1, rows)

    def row_bytes(self, dtype) -> int:
        """One token's key and value in this block's cache."""
        return (self.kv_heads * (self.head_dim + self.v_head_dim)
                * jnp.dtype(dtype).itemsize)

    def _sink(self, p) -> dict:
        """The cores' ``sink`` argument, where the block has one."""
        return {"sink": p["sink"]} if self.sink else {}

    def init_cache(self, slots: int, max_len: int, dtype):
        shape = (slots, self.kv_heads, self.rows(max_len))
        return {"k": jnp.zeros(shape + (self.head_dim,), dtype),
                "v": jnp.zeros(shape + (self.v_head_dim,), dtype)}

    def prefill(self, x, p, lengths):
        """Attention over ``x (R, P, h)``; the per-token rows are ``{"k":
        (R, KV, P, d), "v": (R, KV, P, dv)}``."""
        r, n, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(n), (r, n))
        q, k, v, rest = self.project(x, p, positions)
        with jax.named_scope(self.scope):
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            o = gqa.prefill_attention(q, k, v, self.scale, self.window,
                                      lengths, self.block, **self._sink(p))
        with jax.named_scope("attn.out"):
            return self.finish(o, rest, p), {"k": k, "v": v}

    def cache_rows(self, rows, lengths, max_len: int):
        """The per-token rows of R primes as R slots' caches: a full block
        keeps the first ``max_len`` tokens where they are; a ring takes,
        for each of its rows ``j``, the LAST real token ``p < length`` with
        ``p % rows == j`` (rows no token has reached hold anything: no
        count reaches them)."""
        n = rows["k"].shape[2]
        size = self.rows(max_len)
        if self.window is None:
            return {name: a[:, :, :size] if n >= size else jnp.pad(
                a, ((0, 0), (0, 0), (0, size - n), (0, 0)))
                for name, a in rows.items()}
        j = jnp.arange(size)[None, :]
        last = lengths[:, None] - 1
        at = jnp.clip(j + size * ((last - j) // size), 0, n - 1)
        return {name: jnp.take_along_axis(a, at[:, None, :, None], axis=2)
                for name, a in rows.items()}

    def decode(self, x, pos, cache, p):
        """One token a row: ``x (S, h)`` at ``pos (S,)``; the new key and
        value are written (``ops/row_write.py``) and the slot's rows
        attended."""
        q, k, v, rest = self.project(x[:, None], p, pos[:, None])
        with jax.named_scope(self.scope):
            at, counts = self.place(pos, cache["k"].shape[2])
            k, v = (k[:, 0].astype(cache["k"].dtype),
                    v[:, 0].astype(cache["v"].dtype))
            if self.v_head_dim == self.head_dim:
                keys, values = write_rows((cache["k"], cache["v"]), (k, v),
                                          at, axis=1)
            else:       # one call takes arrays of one shape
                keys = write_rows(cache["k"], k, at, axis=1)
                values = write_rows(cache["v"], v, at, axis=1)
            o = gqa.decode_attention(q[:, 0], keys, values, counts,
                                     self.scale, **self._sink(p))
        with jax.named_scope("attn.out"):
            rest = jax.tree.map(lambda a: a[:, 0], rest)
            return self.finish(o, rest, p), {"k": keys, "v": values}

    def decode_block(self, x, pos0, cache, p, commit, queries=None):
        """``B`` tokens a row (``B`` the mask's ``block``): ``x (S, B, h)``
        at ``pos0 .. pos0 + B - 1`` (``pos0 (S,)``, a multiple of B), mixed
        over the slot's ``pos0`` committed rows and each other; the B new
        keys and values are written at those rows where ``commit (S,)`` and
        nowhere else.

        Or ``2 B`` tokens a row, ``x (S, 2B, h)``: the PENDING block at
        ``pos0 - B .. pos0 - 1`` — final tokens whose keys no forward has
        written yet — in front of the block in progress at ``pos0``.  Where
        ``commit`` the pending keys are this forward's: the slot has ``pos0
        - B`` committed rows, the block in progress sees the pending keys
        beside its own, and they are written at their rows.  Where not, the
        front half is filler: the slot's ``pos0`` committed rows hold that
        block already, no query of the block in progress sees the filler's
        keys, and nothing is written.  Either way the keys that are written
        are the FIRST B rows of ``x``, at the position ``x`` starts at.
        ``queries``: only the last so many rows of ``x`` are queries, and
        the output is theirs alone (the stack's last layer asks nothing of
        a pending block but its keys and values)."""
        if self.window is not None:
            raise NotImplementedError("a ring takes one token a step")
        n, b = x.shape[1], self.block
        if n not in (b, 2 * b):
            raise ValueError(
                f"a step takes a block of {b} tokens a row, or a pending "
                f"block in front of it ({2 * b}): not {n}")
        first = pos0 - (n - b)
        q, k, v, rest = self.project(x, p, first[:, None] + jnp.arange(n))
        with jax.named_scope("attn.block"):
            k = k.transpose(0, 2, 1, 3).astype(cache["k"].dtype)
            v = v.transpose(0, 2, 1, 3).astype(cache["v"].dtype)
            if queries is not None:
                q, rest = jax.tree.map(lambda a: a[:, n - queries:],
                                       (q, rest))
            # the committed rows: all before ``pos0``, less a pending block
            # whose keys are this forward's
            o = gqa.block_decode_attention(
                q, cache["k"], cache["v"], k, v, pos0 - (n - b) * commit,
                self.scale, commit if n > b else None)
        with jax.named_scope("attn.commit"):
            keys, values = write_row_blocks(
                (cache["k"], cache["v"]), (k[:, :, :b], v[:, :, :b]),
                jnp.maximum(first, 0), commit)
        with jax.named_scope("attn.out"):
            return self.finish(o, rest, p), {"k": keys, "v": values}


def decode_stats(blocks: dict, caches, pos, live, block_form=False) -> dict:
    """A decode step's ``attn.*`` counters over the :class:`KVBlock`s among
    ``blocks``: its live rows, their contexts, the part of them a window
    keeps, and the cache rows ONE block of each kind reads under the
    lowering that ran (``block_form``: the step is
    :meth:`KVBlock.decode_block`'s, ``pos + 1`` the rows committed before
    it, and the core ``ops/gqa.py:block_decode_attention``, which has a
    rule of its own: ``gqa.block_decode_lowering``)."""
    seen = jnp.where(live, pos + 1, 0)
    stats = {"attn.decode_rows": jnp.sum(live).astype(F32),
             "attn.context_tokens": jnp.sum(seen).astype(F32),
             "attn.window_tokens": jnp.zeros((), F32),
             "attn.window_rows_read": jnp.zeros((), F32),
             "attn.full_rows_read": jnp.zeros((), F32)}
    kv = {n: b for n, b in blocks.items() if isinstance(b, KVBlock)}
    for kind in ("window", "full"):
        name = next((n for n, b in kv.items()
                     if (b.window is None) == (kind == "full")), None)
        if name is None:
            continue
        k, v = caches[name]["k"], caches[name]["v"]
        # the step's query is in the caches' dtype (``driver.Family``); a
        # sink keeps the XLA form (``gqa.decode_lowering``)
        lowering = (gqa.block_decode_lowering(k.dtype, k, v) if block_form
                    else gqa.decode_lowering(k.dtype, k, v, kv[name].sink))
        counts = None if lowering == "xla" else kv[name].place(
            pos, k.shape[2])[1]
        stats[f"attn.{kind}_rows_read"] = (
            gqa.rows_visited(k, counts, lowering) * jnp.any(live))
        if kind == "window":
            stats["attn.window_tokens"] = jnp.sum(jnp.minimum(
                seen, k.shape[2])).astype(F32)
    return stats


PREFILL_STAT_KEYS = ("attn.prefill_pairs_allowed",
                     "attn.prefill_pairs_visited")


def prefill_stats(blocks: dict, n: int, lengths, dt) -> dict:
    """A prefill's ``attn.prefill_pairs_*`` counters over rows of
    ``lengths (R,)`` padded to ``n``, for a family whose blocks are all
    :class:`KVBlock`s under the causal mask: the query-key pairs the mask
    allows at real positions and the pairs the lowering that runs computes
    (``ops/gqa.py``), summed over the attention blocks, per head."""
    allowed = visited = jnp.zeros((), F32)
    for block in blocks.values():
        lowering = gqa.prefill_lowering(
            n, block.head_dim, dt, block.window, dv=block.v_head_dim,
            sink=block.sink)
        allowed += gqa.pairs_allowed(lengths, block.window)
        visited += gqa.pairs_visited(lengths, n, block.window, lowering)
    return {"attn.prefill_pairs_allowed": allowed,
            "attn.prefill_pairs_visited": visited}


def byte_gauges(blocks: dict, gauges: dict, dtype) -> dict:
    """``attn.window_bytes_read`` / ``attn.full_bytes_read`` from the
    published row counters (host side, when the engine fetches them):
    ``attn.<kind>_rows_read`` counts the cache rows ONE block of a kind
    reads, so the bytes ALL blocks of that kind read are that times the
    kind's own row bytes (:meth:`KVBlock.row_bytes`: keys and values at
    their own widths) times the blocks of the kind.  The row bytes are
    static, so the product needs no counter in the chunk's scan and no
    family's program changes for it.  ``{}`` for a family without a
    :class:`KVBlock` or without the counters."""
    out = {}
    kv = [b for b in blocks.values() if isinstance(b, KVBlock)]
    for kind in ("window", "full"):
        mine = [b for b in kv if (b.window is None) == (kind == "full")]
        rows = gauges.get(f"attn.{kind}_rows_read")
        if kv and rows is not None:
            out[f"attn.{kind}_bytes_read"] = float(rows) * sum(
                b.row_bytes(dtype) for b in mine)
    return out


def block_decode_stats(blocks: dict, caches, pos0, live, b: int,
                       riding=None) -> dict:
    """:func:`decode_stats` of a step of ``b`` tokens a row: ``b`` query
    rows a live slot, each slot's context the rows committed before the
    forward.  ``riding (S,)``: the slots whose pending block is in this
    forward (``KVBlock.decode_block``) — ``b`` more query rows each, and a
    context that ends before the pending block."""
    rows = jnp.sum(live)
    if riding is not None:
        pos0 = pos0 - b * riding
        rows = rows + jnp.sum(riding)
    stats = decode_stats(blocks, caches, pos0 - 1, live, block_form=True)
    stats["attn.decode_rows"] = (b * rows).astype(F32)
    return stats
