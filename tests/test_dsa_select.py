"""An admission's learned selection (``ops/dsa.py:selected`` and
``prefill_keep``) against the plain form, written here as the reference: every
query row's scores over its segment's keys SORTED, the threshold the element
``jnp.sort(row)[end - top_k]``, the mask ``seen & (score >= threshold)`` —
and against ``lax.top_k``'s threshold, the form the code had.  The code
finds the threshold by counting (``ops/kth.py``) and orders nothing; the
masks are the same bit for bit: over several segments, a length that is no
multiple of ``top_k`` (one segment whose first rows see fewer than
``top_k`` keys), scores that are all ties (exact zeros of either sign under
the ReLU), two admission rows of different content, several blocks a
segment, and the counting by groups of rows forced by a small budget.

The operands are small whole numbers (weights in eighths), so that every
product and sum is exact in float32 whatever order a lowering adds them in:
the reference and the code see the same scores to the bit, and many of them
tie."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops import dsa, kth
from progen_tpu.ops.lowering import record_lowerings

HEADS, WIDTH = 4, 8
# name -> (R, n, top_k, the query block the test sets (None: the module's))
SHAPES = {
    "segments": (1, 32, 8, 4),           # n = 4 top_k: four segments of 8
    "ragged": (1, 20, 8, None),          # 20 = 2.5 top_k: one block of 20
    "ragged_blocks": (1, 20, 8, 4),      # the same in five blocks
    "two_rows": (2, 32, 8, 4),
    "blocks": (1, 64, 16, 4),            # four blocks a segment
    "one_past": (1, 9, 8, 4),            # one segment, one row past top_k
    "module_block": (1, 1024, 256, None)}   # QUERY_BLOCK rows, 2 a segment
CONTENTS = ("varied", "all_zero", "minus_zero", "coarse")


def _operands(shape, content):
    r, n, _, _ = SHAPES[shape]
    rng = np.random.default_rng(
        61 + 7 * list(SHAPES).index(shape) + CONTENTS.index(content))
    q = rng.integers(-8, 9, size=(r, n, HEADS, WIDTH))
    k = rng.integers(-8, 9, size=(r, n, WIDTH))
    w = rng.integers(-8, 9, size=(r, n, HEADS)) / 8.0
    if content == "all_zero":
        # every product negative or zero: the ReLU leaves exact zeros only
        q, k = -np.abs(q), np.abs(k)
    elif content == "minus_zero":
        # zeros under NEGATIVE head weights: every product is -0.0 (their
        # sum over heads starts from +0.0 and is +0.0 on the CPU)
        q, k, w = -np.abs(q), np.abs(k), -np.abs(w) - 0.125
    elif content == "coarse":
        # a handful of distinct values: ties at every threshold
        q, k = np.sign(q), np.sign(k)
    if r > 1 and content == "varied":
        k[1] = -k[1] // 2       # the second row's keys are not the first's
    return (jnp.asarray(q, jnp.float32), jnp.asarray(w, jnp.float32),
            jnp.asarray(k, jnp.float32))


def reference_selected(q_idx, w, k_idx, top_k, first, bq, end):
    """``dsa.selected`` by a full sort of every row, and the same threshold
    as ``lax.top_k`` hands it out (the parent's form)."""
    scores = np.asarray(dsa.index_scores(
        q_idx[:, first:first + bq], w[:, first:first + bq], k_idx[:, :end]))
    seen = (first + np.arange(bq)[:, None] - np.arange(end)[None, :]) >= 0
    seen = np.broadcast_to(seen, scores.shape)
    if end <= top_k:
        return seen
    scores = np.where(seen, scores, -np.inf)
    kth_sorted = np.sort(scores, axis=-1)[..., end - top_k][..., None]
    kth_top_k = np.asarray(jax.lax.top_k(jnp.asarray(scores), top_k)[0])
    np.testing.assert_array_equal(kth_sorted, kth_top_k[..., -1:])
    return seen & (scores >= kth_sorted)


def reference_keep(q_idx, w, k_idx, top_k):
    """``dsa.prefill_keep`` a query row at a time: row ``t`` of a segment
    that ends at ``e`` keeps, of the keys ``0 .. e - 1``, the seen ones at or
    above its ``top_k``-th largest score; every key where ``e <= top_k``."""
    r, n = q_idx.shape[:2]
    seg, _ = dsa.segments(n, top_k)
    keep = np.zeros((r, n, n), np.int8)
    for t in range(n):
        end = (t // seg + 1) * seg
        if end <= top_k:
            keep[:, t] = 1
        else:
            keep[:, t, :end] = reference_selected(
                q_idx, w, k_idx, top_k, t, 1, end)[:, 0]
    return keep


def _set_block(monkeypatch, shape):
    r, n, top_k, block = SHAPES[shape]
    if block is not None:
        monkeypatch.setattr(dsa, "QUERY_BLOCK", block)
    return r, n, top_k


def _force_groups(monkeypatch, rows, end):
    """The chip's choice on the CPU, under a budget of ``rows`` rows of
    ``end`` scores."""
    monkeypatch.setattr(kth, "_on_tpu", lambda: True)
    monkeypatch.setattr(kth, "ROUNDS_ON_CHIP_BYTES", rows * end * 4)


@pytest.mark.parametrize("content", CONTENTS)
@pytest.mark.parametrize("shape", SHAPES)
def test_prefill_keep_is_the_sorted_references_mask(monkeypatch, shape,
                                                    content):
    _, n, top_k = _set_block(monkeypatch, shape)
    q_idx, w, k_idx = _operands(shape, content)
    with record_lowerings() as chosen:
        text = str(jax.make_jaxpr(
            lambda *a: dsa.prefill_keep(*a, top_k))(q_idx, w, k_idx))
    assert chosen == {"dsa_kth": {"xla"}}
    assert "sort" not in text and "top_k" not in text
    got = np.asarray(jax.jit(
        lambda *a: dsa.prefill_keep(*a, top_k))(q_idx, w, k_idx))
    want = reference_keep(q_idx, w, k_idx, top_k)
    assert got.dtype == np.int8 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    below = np.tril(np.ones((n, n), bool))
    kept = (got.astype(bool) & below).sum(axis=-1)
    # a row keeps every key it sees up to top_k of them and never fewer than
    # top_k past that (more where scores tie with the threshold)
    np.testing.assert_array_equal(
        kept >= np.minimum(np.arange(n) + 1, top_k), True)
    if content in ("all_zero", "minus_zero"):
        # every score ties with the threshold: the causal rule is all there
        # is, in every segment
        np.testing.assert_array_equal(kept, np.broadcast_to(
            np.arange(n) + 1, kept.shape))
        assert not np.asarray(dsa.index_scores(q_idx, w, k_idx)).any()
    elif shape != "one_past":
        # a row that sees more than top_k keys has some cut
        assert (kept[:, top_k:] < np.arange(n)[top_k:] + 1).any()


@pytest.mark.parametrize("content", CONTENTS)
@pytest.mark.parametrize("shape", SHAPES)
def test_selected_is_the_sorted_references_block(monkeypatch, shape, content):
    """Block by block, as the XLA core of ``sparse_prefill_attention`` takes
    it: every block of every segment, the first segment's (no selection)
    among them."""
    r, n, top_k = _set_block(monkeypatch, shape)
    q_idx, w, k_idx = _operands(shape, content)
    seg, bq = dsa.segments(n, top_k)
    fn = jax.jit(dsa.selected, static_argnums=(3, 5, 6))
    for end in range(seg, n + 1, seg):
        for first in range(end - seg, end, bq):
            got = np.asarray(fn(q_idx, w, k_idx, top_k, first, bq, end))
            assert got.dtype == bool and got.shape == (r, bq, end)
            np.testing.assert_array_equal(got, reference_selected(
                q_idx, w, k_idx, top_k, first, bq, end))


@pytest.mark.parametrize("content", CONTENTS)
@pytest.mark.parametrize(("shape", "fit"), [
    ("two_rows", 3), ("two_rows", 4), ("blocks", 1), ("ragged", 12),
    ("module_block", 48)])
def test_the_counting_by_groups_gives_the_one_loops_mask(monkeypatch, shape,
                                                         content, fit):
    """A budget of ``fit`` rows of the widest span: the block's ``R * bq``
    rows go by groups of the power of two under it (2, 4, 1, 8 and 32: whole
    groups and a last one that is not full), noted ``"xla_tiled"``, and the
    mask is the one loop's and the reference's."""
    r, n, top_k = _set_block(monkeypatch, shape)
    q_idx, w, k_idx = _operands(shape, content)
    one_loop = np.asarray(jax.jit(
        lambda *a: dsa.prefill_keep(*a, top_k))(q_idx, w, k_idx))
    _force_groups(monkeypatch, fit, n)
    _, bq = dsa.segments(n, top_k)
    assert kth.group_rows(r * bq, n) == 1 << (fit.bit_length() - 1) < r * bq
    with record_lowerings() as chosen:
        tiled = np.asarray(jax.jit(
            lambda *a: dsa.prefill_keep(*a, top_k))(q_idx, w, k_idx))
    assert "xla_tiled" in chosen["dsa_kth"]
    np.testing.assert_array_equal(tiled, one_loop)
    np.testing.assert_array_equal(tiled, reference_keep(q_idx, w, k_idx,
                                                        top_k))


@pytest.mark.parametrize("shape", ("segments", "ragged", "two_rows"))
def test_the_xla_core_attends_under_the_references_mask(monkeypatch, shape):
    """``sparse_prefill_attention``'s blocked XLA form (the CPU's, the tiny
    widths') reads the same ``selected``: its result is a dense masked
    softmax under the reference's mask."""
    r, n, top_k = _set_block(monkeypatch, shape)
    q_idx, w, k_idx = _operands(shape, "varied")
    rng = np.random.default_rng(5)
    heads, nope, rope, vd = 2, 8, 4, 8

    def normal(*dims):
        return jnp.asarray(rng.normal(size=dims), jnp.float32)

    q_nope, q_rope = normal(r, n, heads, nope), normal(r, n, heads, rope)
    k_nope, k_r = normal(r, heads, n, nope), normal(r, n, rope)
    v = normal(r, heads, n, vd)
    with record_lowerings() as chosen:
        got = jax.jit(lambda *a: dsa.sparse_prefill_attention(*a, top_k))(
            q_nope, q_rope, k_nope, k_r, v, q_idx, w, k_idx)
    assert chosen["dsa_kth"] == {"xla"}
    keep = reference_keep(q_idx, w, k_idx, top_k).astype(bool) & np.tril(
        np.ones((n, n), bool))
    q, k = dsa.joined_heads(q_nope, q_rope, k_nope, k_r)
    logits = np.einsum("rqhd,rhkd->rhqk", np.asarray(q), np.asarray(k),
                       dtype=np.float64) * (nope + rope) ** -0.5
    logits = np.where(keep[:, None], logits, -np.inf)
    p = np.exp(logits - logits.max(axis=-1, keepdims=True))
    want = np.einsum("rhqk,rhkd->rqhd", p / p.sum(axis=-1, keepdims=True),
                     np.asarray(v, np.float64)).reshape(r, n, heads * vd)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", ("segments", "ragged", "two_rows"))
def test_zeros_of_either_sign_tie_at_the_threshold(monkeypatch, shape):
    """Scores that are ``-0.0`` and ``+0.0`` in equal parts beside a few
    ``-1`` and ``1`` (planted: the indexer is patched to read them from a
    table by the query's number): whichever zero the counting hands out as
    the threshold, both kinds are kept, as under the sort."""
    r, n, top_k = _set_block(monkeypatch, shape)
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.choice(
        np.array([-1.0, -0.0, 0.0, 1.0], np.float32), size=(r, n, n),
        p=[0.05, 0.45, 0.45, 0.05]))

    def planted(q_idx, w, k_idx):
        rows = q_idx[..., 0, 0].astype(jnp.int32)
        return table[jnp.arange(r)[:, None], rows][..., :k_idx.shape[-2]]

    monkeypatch.setattr(dsa, "index_scores", planted)
    q_idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.float32)[
        None, :, None, None], (r, n, HEADS, WIDTH))
    w, k_idx = jnp.ones((r, n, HEADS)), jnp.ones((r, n, WIDTH))
    got = np.asarray(jax.jit(
        lambda *a: dsa.prefill_keep(*a, top_k))(q_idx, w, k_idx))
    np.testing.assert_array_equal(got, reference_keep(q_idx, w, k_idx, top_k))
    # the rows that see more than top_k keys
    live = np.tril(np.ones((n, n), bool))[top_k:]
    zeros = (np.asarray(table)[:, top_k:] == 0) & live
    signs = np.signbit(np.asarray(table)[:, top_k:])
    kept = got[:, top_k:].astype(bool)
    # where a row keeps one zero it keeps them all, of either sign
    some = (kept & zeros).any(axis=-1)
    assert some.any() and (kept | ~zeros)[some].all()
    assert (kept & zeros & signs).any() and (kept & zeros & ~signs).any()


def test_no_selection_no_note():
    """``P <= top_k``: no mask, no counting, nothing noted."""
    q_idx, w, k_idx = _operands("segments", "varied")
    with record_lowerings() as chosen:
        assert dsa.prefill_keep(q_idx[:, :8], w[:, :8], k_idx[:, :8],
                                8) is None
    assert chosen == {}
