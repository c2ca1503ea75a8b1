"""Each row's k-th largest value found by counting, not by ordering the row.

What a cut needs of a row is ONE number, its k-th largest value; sorting the
row (or ``lax.top_k``) to read one element of it is the expensive way.  The
number is built bit by bit in 32 compare-and-count rounds of one
``fori_loop`` whose keys are a loop invariant (:func:`count_rounds`), and
the value handed out is the one a full ascending sort of the row hands out
at ``[v - k]``, ties, ``-inf`` and NaNs and all, so a mask ``x >= kth`` is
the sort's bit for bit.

Two callers: the engine's draw (``decode/sampler.py``: a row's own ``k``,
the note ``"sample_kth"``) and an admission's learned selection
(``ops/dsa.py:selected``: ``top_k`` for every query row of a score block,
the note ``"dsa_kth"``).  Plain XLA, one algorithm everywhere; the SHAPE
decides how many rows a loop holds (:func:`group_rows`), never a knob.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from progen_tpu.ops.lowering import mesh_in_scope as _mesh_in_scope
from progen_tpu.ops.lowering import note
from progen_tpu.ops.lowering import on_tpu as _on_tpu

# The most bytes of float32 rows, ``B * V * 4``, whose 32 rounds are left to
# ONE loop, and the most a row group of the tiled form may hold.  On a v5e the
# compiler keeps a loop's keys on the chip by itself while they are few:
# Granite's 32 x 100,352 (12.8 MB, the largest draw of the token-by-token
# cells) run their 32 rounds in 119 us, 3.5 TB/s; SDAR's 256 x 151,936
# (155.6 MB) do not fit and are read from HBM every round, 6.6 ms a draw;
# in groups of 32 rows (19.4 MB) the rounds take 1.2 ms.  Groups of 16 under
# a budget of 16 MiB read 0.5 % fewer tokens a second end to end, and groups
# of 8 take half as long again as 16 alone (PERF.md section 6, PR 39 and
# PR 43)
ROUNDS_ON_CHIP_BYTES = 32 << 20


def group_rows(b: int, v: int) -> int | None:
    """Rows of a group for the rounds of a ``(b, v)`` block, ``None`` where
    one loop takes them all: groups on a TPU backend with no mesh in scope
    and more rows than ``ROUNDS_ON_CHIP_BYTES`` hold — the largest power of
    two of them that the budget does hold."""
    fit = ROUNDS_ON_CHIP_BYTES // (v * 4)
    if not 0 < fit < b or not _on_tpu() or _mesh_in_scope():
        return None
    return 1 << (fit.bit_length() - 1)


def kth_largest_by_counting(scaled, k, op: str):
    """``(B, 1)``: each row's ``k``-th largest value, the element
    ``jnp.sort(scaled, axis=-1)[v - k]`` of the row, found without ordering
    the row (:func:`count_rounds`).  ``scaled`` is float32 ``(B, V)``, ``k``
    int32 ``(B,)`` in ``1..V``; ``op`` is the name the caller's choice of
    form is noted under (``ops/lowering.py``).

    WHERE the 32 rounds read their keys is what they cost, and the shape
    decides it (:func:`group_rows`).  A ``(B, V)`` that fits on the chip —
    every token-by-token cell's draw, 64 x 256 up to 32 x 100,352, and an
    admission's score block of 128 x 16,384 — is one loop, whose keys the
    compiler keeps on the chip between rounds by itself (``"xla"``).  A
    ``(B, V)`` that does not — a block step's 256 x 151,936, 155.6 MB —
    would be read from HBM again every round; there, on a TPU, the same loop
    runs over one group of rows at a time under ``lax.map``, a group's keys
    small enough to stay on the chip through its 32 rounds, so that HBM is
    read once (``"xla_tiled"``).  Rows are independent: the value handed out
    is the same, bit for bit.
    """
    b, v = scaled.shape
    rows = group_rows(b, v)
    note(op, "xla" if rows is None else "xla_tiled")
    if rows is None:
        return count_rounds(scaled, k)
    pad = -b % rows     # (a last group that is not full counts spare rows)
    groups = (jnp.pad(scaled, ((0, pad), (0, 0))).reshape(-1, rows, v),
              jnp.pad(k, (0, pad), constant_values=1).reshape(-1, rows))
    kth = jax.lax.map(lambda group: count_rounds(*group), groups)
    return kth.reshape(-1, 1)[:b]


def count_rounds(scaled, k):
    """:func:`kth_largest_by_counting` over rows that are on the chip
    together.

    Each float32 becomes a uint32 key whose unsigned order is the sort's
    order (``-inf`` lowest, finite values by value, ``+inf``, every NaN of
    either sign highest).  The k-th largest key is then built bit by bit
    from the top: a bit stays set when at least ``k`` keys of the row are
    still at or above the candidate.  Thirty-two compare-and-count rounds
    over the keys, each one fused reduction of a ``fori_loop`` whose keys
    are a loop invariant, whatever ``k`` is; the largest ``t`` with
    ``count(key >= t) >= k`` is a key the row holds, multiplicity counted
    as the sort counts it.
    """
    bits = jax.lax.bitcast_convert_type(scaled, jnp.uint32)
    top = jnp.uint32(1 << 31)
    key = jnp.where(bits >= top, ~bits, bits | top)
    key = jnp.where(jnp.isnan(scaled), jnp.uint32(0xFFFFFFFF), key)

    def keep_bit(i, t):
        cand = t | (top >> i.astype(jnp.uint32))
        at_or_above = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(at_or_above >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, keep_bit, jnp.zeros(k.shape, jnp.uint32))
    bits = jnp.where(t >= top, t ^ top, ~t)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)[:, None]
