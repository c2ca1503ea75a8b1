"""Learned sparse attention over latent rows: a lightweight INDEXER scores
every cached token for a query and the latent attention (``models/latent.py``)
then runs over the ``top_k`` best of them only (DeepSeek-V3.2's sparse
attention; ``models/dots3.py``'s full layers).

The indexer keeps ONE key ``k^I (d,)`` a token, shared by its ``J`` heads,
and a query brings ``q^I (J, d)`` and a weight a head ``w (J,)``::

    I[t, s] = sum_j w[t, j] * relu(q^I[t, j] . k^I[s])        s <= t

``S_t`` is the ``top_k`` largest ``I[t, .]`` (every visible key while there
are no more than ``top_k``), and the attention's softmax runs over ``S_t``
alone.  The products take the compute dtype's operands and accumulate in
float32; the ReLU, the weighted sum over heads and the selection are float32.

The selection is plain XLA, one form everywhere; the attention it thins is
``ops/mla_decode.py``'s and ``ops/mla_prefill.py``'s, each under its own
choice of lowering:

* **a decode step** — :func:`select_rows` scores a slot's whole indexer
  cache ``(S, T, d)`` (rows past the slot's count masked), ``lax.top_k``
  gives the rows' numbers, and :func:`sparse_decode_attention` gathers those
  latent rows and hands them to ``ops/mla_decode.py:decode_attention`` — on
  a TPU the kernel ``mla_decode_fwd`` over ``top_k`` rows a slot, whatever
  the context.  The indexer reads ``T`` rows a slot (``dsa.index_rows_read``).
* **an admission** — :func:`sparse_prefill_attention`: the selection as a
  dense MASK over the expanded keys, in SEGMENTS of ``top_k`` query rows
  (:func:`segments`): segment ``g`` sees the keys up to its own end, the
  first needs no indexer at all (its queries see ``top_k`` keys or fewer),
  and inside a segment a ``lax.map`` over blocks of ``QUERY_BLOCK`` rows
  keeps ``I`` a block high, ``(J, bq, keys)`` float32.  A row's threshold
  is its ``top_k``-th largest score, found by counting and not by ordering
  the row (``ops/kth.py``: 32 compare-and-count rounds over the block, the
  value a sort of the row hands out; the note ``"dsa_kth"``), the mask ``I
  >= threshold`` (every score tied with the threshold is kept).  What reads
  the mask is ``ops/mla_prefill.py``'s decision
  (``mla_prefill.prefill_lowering``):

  - where the flash kernel ``mla_prefill_fwd`` applies (a TPU, no mesh in
    scope, the published head widths, ``P`` a multiple of 512) the blocks
    emit the mask alone, one byte a pair for all heads, ``(R, P, P)``
    int8, and ONE kernel call a layer takes it as its ``keep`` operand
    with the rows' ``lengths``: a score tile lives in VMEM only, a tile
    past a row's length or above the diagonal is neither computed nor
    fetched.  The selection keeps at least an eighth of the keys under
    these shapes, so no tile under the diagonal is without a kept pair and
    none is skipped for the mask's sake;
  - everywhere else (the CPU, the tests' tiny widths, a mesh) each block
    computes its own masked core in XLA, the score tensor ``(H, bq,
    keys)`` float32: every pair under a segment's key span, selected or
    not, pads included.

  :func:`prefill_pairs` says how many pairs either form computes.

**A selection that several layers read** (``models/glm_dsa.py``: one layer
in four computes it, the three after it BORROW it).  The selection is a
function apart from the cores: :func:`prefill_keep` gives an admission's as
the keep mask, whatever core and lowering read it (GLM's layers:
``ops/gqa.py:prefill_attention`` over the joined heads, its kernel where it
applies and its blocked XLA form under the same mask elsewhere), and a decode
step's :func:`select_rows` result goes to as many
:func:`sparse_decode_attention` calls as there are layers under it, each
gathering ITS OWN latent rows at the same numbers.  With no borrower
(dots3) every function traces what it traced before the split.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from progen_tpu.ops import mla_decode, mla_prefill
from progen_tpu.ops.kth import kth_largest_by_counting

F32 = jnp.float32
QUERY_BLOCK = 128     # an admission's query rows per score block


def index_scores(q_idx, w, k_idx):
    """``I (..., n, T)`` float32 of ``q_idx (..., n, J, d)``, ``w (..., n,
    J)`` float32 and ``k_idx (..., T, d)``: ``sum_j w_j relu(q_j . k)``."""
    dots = jnp.einsum("...njd,...td->...jnt", q_idx, k_idx.astype(q_idx.dtype),
                      preferred_element_type=F32)
    # elementwise, so that float32 stays float32 on the chip's matrix unit
    return jnp.sum(jax.nn.relu(dots) * jnp.swapaxes(w, -1, -2)[..., None],
                   axis=-3)


# ------------------------------------------------------------------- decode

_recorder: contextvars.ContextVar = contextvars.ContextVar(
    "dsa_selections", default=None)


@contextlib.contextmanager
def record_selections():
    """Collect ``[(rows (S, K), kept (S,)), ...]``, one pair a call of
    :func:`select_rows` traced inside the block, in the stack's order (as
    ``ops/lowering.py:record_lowerings``): a caller that traces a decode
    step and returns them can hold the selected sets against a
    reference's."""
    picked: list = []
    token = _recorder.set(picked)
    try:
        yield picked
    finally:
        _recorder.reset(token)


_computed: contextvars.ContextVar = contextvars.ContextVar(
    "dsa_selections_computed", default=None)


@contextlib.contextmanager
def count_selections():
    """Tally the selections COMPUTED while tracing inside the block: one
    entry a call of :func:`select_rows`, one a call of :func:`prefill_keep`
    that made a mask.  A family whose layers share selections counts who
    selected from it — what was traced, not what its blocks are labelled."""
    made: list = []
    token = _computed.set(made)
    try:
        yield made
    finally:
        _computed.reset(token)


def _note_computed(what: str) -> None:
    made = _computed.get()
    if made is not None:
        made.append(what)


def select_rows(q_idx, w, index, counts, top_k: int):
    """One query a slot: ``q_idx (S, J, d)``, ``w (S, J)`` over the first
    ``counts (S,)`` rows of ``index (S, T, d)`` -> ``(rows (S, K) int32,
    kept (S,))``, ``K = min(top_k, T)``: the numbers of the ``kept =
    min(counts, K)`` best-scored rows FIRST (best first), then filler."""
    with jax.named_scope("dsa.index"):
        scores = index_scores(q_idx[:, None], w[:, None], index)[:, 0]
        seen = jnp.arange(index.shape[1])[None, :] < counts[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
    with jax.named_scope("dsa.select"):
        k = min(top_k, index.shape[1])
        _, rows = jax.lax.top_k(scores, k)
        kept = jnp.minimum(counts, k)
    picked = _recorder.get()
    if picked is not None:
        picked.append((rows, kept))
    _note_computed("rows")
    return rows, kept


def sparse_decode_attention(q_cat, cache, rows, kept, rank: int, scale):
    """``ops/mla_decode.py:decode_attention`` over the selected rows:
    ``cache (S, T, latent)`` gathered at ``rows (S, K)``, of which the first
    ``kept (S,)`` count."""
    picked = jnp.take_along_axis(cache, rows[..., None], axis=1)
    return mla_decode.decode_attention(q_cat, picked, kept, rank, scale)


# ---------------------------------------------------------------- admission


def segments(n: int, top_k: int) -> tuple:
    """``(segment length, query block)`` of an admission of ``n`` positions:
    segments of ``top_k`` rows where they tile ``n``, else one of ``n``;
    blocks of ``QUERY_BLOCK`` rows where they tile a segment, else one."""
    seg = top_k if n > top_k and n % top_k == 0 else n
    return seg, QUERY_BLOCK if seg % QUERY_BLOCK == 0 else seg


def prefill_pairs(n: int, top_k: int, lowering: str = "xla",
                  length=None) -> tuple:
    """``(scored, attended)``: the query-key pairs the indexer scores and
    the pairs the attention core computes (one head) for ONE row padded to
    ``n`` positions.  The indexer scores each segment's rows against the
    keys up to its end, pads included, where a segment's span passes
    ``top_k``.  The XLA core (``lowering`` ``"xla"``) computes the same
    spans from the first segment on; the kernel (``"pallas"``) the tiles
    it visits for a row of ``length`` real positions
    (``mla_prefill.pairs_visited``; ``length`` may be an array of rows,
    and so is ``attended`` then)."""
    seg, _ = segments(n, top_k)
    ends = range(seg, n + 1, seg)
    scored = float(sum(seg * e for e in ends if e > top_k))
    if lowering == "xla":
        return scored, float(sum(seg * e for e in ends))
    return scored, mla_prefill.pairs_visited(length, n)


def joined_heads(q_nope, q_rope, k_nope, k_r):
    """The expanded operands of latent attention as whole heads: ``q (R, P,
    H, nope + rope)`` and ``k (R, H, P, nope + rope)``, the one rotated key
    ``k_r (R, P, rope)`` repeated for every head."""
    r, _, heads, _ = q_nope.shape
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None], (r, heads) + k_r.shape[1:])],
        axis=-1)
    return jnp.concatenate([q_nope, q_rope], axis=-1), k


def selected(q_idx, w, k_idx, top_k: int, first, bq: int, end: int):
    """``(R, bq, end)`` bool: the keys ``0 .. end - 1`` that query rows
    ``first .. first + bq - 1`` attend (``q_idx (R, P, J, d)``, ``w (R, P,
    J)``, ``k_idx (R, P, d)``); the selection is computed only where a row
    can see more than ``top_k`` keys (``end > top_k``: a static fact of the
    segment)."""
    r = q_idx.shape[0]

    def rows(x):
        return jax.lax.dynamic_slice_in_dim(x, first, bq, axis=1)

    gap = first + jnp.arange(bq)[:, None] - jnp.arange(end)[None, :]
    seen = jnp.broadcast_to(gap >= 0, (r, bq, end))
    if end > top_k:
        with jax.named_scope("dsa.index"):
            scores = jnp.where(seen, index_scores(
                rows(q_idx), rows(w), k_idx[:, :end]), -jnp.inf)
        with jax.named_scope("dsa.select"):
            kth = kth_largest_by_counting(
                scores.reshape(r * bq, end),
                jnp.full((r * bq,), top_k, jnp.int32), "dsa_kth")
            seen = seen & (scores >= kth.reshape(r, bq, 1))
    return seen


def prefill_keep(q_idx, w, k_idx, top_k: int):
    """An admission's selection as a function of its own: the keep mask
    ``(R, P, P)`` int8 that ``ops/mla_prefill.py:prefill_attention`` and
    ``ops/gqa.py:prefill_attention`` take, one byte a pair for all heads,
    which ONE core or several read (a layer that
    borrows its selection reads the mask of the layer that computed it);
    ``None`` where no row can see more than ``top_k`` keys (``P <= top_k``:
    the causal rule alone)."""
    r, n = q_idx.shape[:2]
    if n <= top_k:
        return None
    _note_computed("mask")
    seg, bq = segments(n, top_k)
    outs = []
    for start in range(0, n, seg):
        end = start + seg
        if end <= top_k:
            # every visible key: the core's own causal rule suffices
            outs.append(jnp.ones((r, seg, n), jnp.int8))
            continue
        firsts = start + bq * jnp.arange(seg // bq)
        out = jax.lax.map(
            lambda f, e=end: selected(q_idx, w, k_idx, top_k, f, bq,
                                      e).astype(jnp.int8), firsts)
        # (blocks, R, bq, end) -> (R, seg, P): no key past the span
        out = out.transpose(1, 0, 2, 3).reshape(r, seg, end)
        outs.append(jnp.pad(out, ((0, 0), (0, 0), (0, n - end))))
    return jnp.concatenate(outs, axis=1)


def sparse_prefill_attention(q_nope, q_rope, k_nope, k_r, v, q_idx, w, k_idx,
                             top_k: int, lengths=None):
    """Causal latent attention in the expanded form under the indexer's
    selection: ``q_nope (R, P, H, nope)``, ``q_rope (R, P, H, rope)``,
    ``k_nope (R, H, P, nope)``, ``k_r (R, P, rope)``, ``v (R, H, P, vd)``
    as ``ops/mla_prefill.py`` takes them, scores scaled by ``(nope +
    rope)^-1/2``; ``q_idx (R, P, J, d)``, ``w (R, P, J)`` float32, ``k_idx
    (R, P, d)`` the indexer's.  ``(R, P, H * vd)``, exact at the first
    ``lengths (R,)`` positions of each row (default all): a real position
    sees real keys only (attention is causal); the XLA core computes the
    pads too, the kernel writes zeros past a row's last live tile."""
    n = q_nope.shape[1]
    if mla_prefill.prefill_lowering(
            n, q_nope.shape[-1], q_rope.shape[-1], v.shape[-1],
            v.dtype) == "pallas":
        keep = prefill_keep(q_idx, w, k_idx, top_k)
        with jax.named_scope("attn.sparse"):
            return mla_prefill.prefill_attention(q_nope, q_rope, k_nope, k_r,
                                                 v, lengths, keep)
    seg, bq = segments(n, top_k)
    q, k = joined_heads(q_nope, q_rope, k_nope, k_r)
    q = q.transpose(0, 2, 1, 3)
    scale = q.shape[-1] ** -0.5

    def block(first, end):
        """The XLA core of one block of query rows over its segment's keys,
        under the selection computed beside it."""
        seen = selected(q_idx, w, k_idx, top_k, first, bq, end)
        with jax.named_scope("attn.sparse"):
            logits = jnp.einsum(
                "rhqd,rhkd->rhqk",
                jax.lax.dynamic_slice_in_dim(q, first, bq, axis=2),
                k[:, :, :end], preferred_element_type=F32) * scale
            # unnormalised probabilities and ONE division after the value
            # product, as ``ops/gqa.py``'s blocked form: every row keeps a
            # key, so the maximum is finite
            logits = jnp.where(seen[:, None], logits, -jnp.inf)
            p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
            out = jnp.einsum("rhqk,rhkd->rqhd", p.astype(v.dtype),
                             v[:, :, :end], preferred_element_type=F32)
            total = jnp.sum(p, axis=-1).transpose(0, 2, 1)
            return (out / total[..., None]).astype(v.dtype)

    outs = []
    for end in range(seg, n + 1, seg):
        firsts = end - seg + bq * jnp.arange(seg // bq)
        out = jax.lax.map(lambda f, e=end: block(f, e), firsts)
        # (blocks, R, bq, H, vd) -> (R, seg, H * vd)
        outs.append(out.transpose(1, 0, 2, 3, 4).reshape(
            q.shape[0], seg, -1))
    return jnp.concatenate(outs, axis=1)
