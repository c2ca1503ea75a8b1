"""``ops/gqa.py:prefill_attention``: the causal grouped-query attention
core with and without a window, two lowerings, one contract.  The Pallas
flash kernel (``gqa_prefill_fwd``) runs under the interpreter here, at the
head width Trinity publishes (128) and small tiles: against the blocked XLA
form and a dense masked float32 reference at every real position, for
windows under, at and over a tile, groups of 1 and 8, lengths at 0, 1, a
tile's edge, the window's edge and ``P``; real positions bit-equal whatever
the padding holds; skipped tiles written as zeros; the tile-visit rule
against a brute-force count; the pair counters; the choice of lowering
from backend, mesh and shape, as ``status()`` shows it; and (PR 60) the
``keep`` operand: one byte a pair that thins the causal pairs, in both
lowerings against a dense masked softmax, with rows whose first tiles keep
nothing; and (PR 62) keys 192 wide beside values of 128 — MiMo's full
layers: the kernel pads the keys to 256 on the way in —, the rule that sends
such a block to the kernel, MiMo's engine through it, and the kernel's
jaxpr text at ONE width held to the parent's."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_trinity as ref
from progen_tpu.models import trinity as tr
from progen_tpu.ops import gqa
from progen_tpu.ops.lowering import record_lowerings
from tests import mimo_v2_tiny
from tests.families import fresh, reference
from tests.trinity_tiny import TINY, make
from tools import program_hash

D, R, P, TILE = 128, 2, 512, 128
SCALE = D ** -0.5
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(p, group, dtype, seed=0, rows=R):
    """``q (R, P, H, d)``, ``k, v (R, KV, P, d)`` with O(1) logits: one
    key/value head under 8 query heads, or two under 2."""
    kv = 1 if group > 1 else 2
    ks = jax.random.split(jax.random.key(seed), 3)

    def normal(k, shape, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * gain).astype(dtype)

    return (normal(ks[0], (rows, p, kv * group, D)),
            normal(ks[1], (rows, kv, p, D), 3.0),
            normal(ks[2], (rows, kv, p, D)))


def _kernel(q, k, v, lengths, window, scale=SCALE, **tiles):
    r, p, heads, d = q.shape
    with jax.default_matmul_precision("highest"):
        return gqa.pallas_prefill_attention(
            q.reshape(r, p, heads * d), k, v, jnp.asarray(lengths, jnp.int32),
            scale, window, interpret=True, **tiles)


def _blocked(q, k, v, window, scale=SCALE):
    """One compiled program a shape and window (eagerly the blocks are
    dispatched op by op)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(gqa.blocked_prefill_attention, static_argnums=(3, 4))(
            q, k, v, scale, window)


def _dense(q, k, v, window, scale=SCALE):
    """Every position against every key under the mask, float64."""
    q, k, v = (np.asarray(a.astype(jnp.float32), np.float64)
               for a in (q, k, v))
    r, p, heads, _ = q.shape
    group, dv = heads // k.shape[1], v.shape[-1]
    gap = np.arange(p)[:, None] - np.arange(p)[None, :]
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    out = np.zeros((r, p, heads, dv))
    for h in range(heads):
        s = np.einsum("rqd,rkd->rqk", q[:, :, h], k[:, h // group]) * scale
        s = np.where(seen, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, h] = np.einsum("rqk,rkd->rqd", w / w.sum(-1, keepdims=True),
                                 v[:, h // group])
    return out.reshape(r, p, heads * dv)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@functools.lru_cache(maxsize=1)
def _references(group, dtype, window):
    """``(blocked, dense)`` over ``_operands(P, group, dtype)``: the lengths
    reach the kernel alone, so the cases of one window, which follow each
    other, compare against the same two arrays."""
    q, k, v = _operands(P, group, jnp.dtype(dtype))
    return _f32(_blocked(q, k, v, window)), _dense(q, k, v, window)


WINDOWS = {"no-window": None, "under-a-tile": 40, "one-tile": TILE,
           "three-tiles": 3 * TILE, "over-P": 4 * P}
# lengths of the two rows
LENGTHS = {
    "0-and-1": lambda w: [0, 1],
    "a-tile-edge-1": lambda w: [2 * TILE - 1, 2 * TILE + 1],
    "a-tile-edge-and-P": lambda w: [TILE, P],
    "the-window-1": lambda w: [min(max((w or 300) - 1, 1), P),
                                min((w or 300) + 1, P)],
}


@pytest.mark.parametrize("case", list(LENGTHS))
@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("group,dtype,tiles", [
    (8, "bfloat16", (128, 128)), (8, "float32", (256, 128)),
    (1, "bfloat16", (128, 256)), (1, "float32", (128, 128))],
    ids=lambda v: str(v))
def test_kernel_equals_the_blocked_form_and_a_dense_reference(
        group, dtype, tiles, window, case):
    window = WINDOWS[window]
    q, k, v = _operands(P, group, jnp.dtype(dtype))
    lengths = LENGTHS[case](window)
    got = _f32(_kernel(q, k, v, lengths, window, block_q=tiles[0],
                       block_k=tiles[1]))
    blocked, dense = _references(group, dtype, window)
    assert got.shape == (R, P, q.shape[2] * D) and np.isfinite(got).all()
    assert float(np.abs(dense).max()) > 1.0      # not a vacuous bound
    for row, n in enumerate(lengths):
        if n:
            assert float(np.abs(got[row, :n] - blocked[row, :n]).max()) \
                < TOL[dtype]
            assert float(np.abs(got[row, :n] - dense[row, :n]).max()) \
                < TOL[dtype]
        # a query tile that starts at or past the length reads as zeros
        first_dead = -(-n // tiles[0]) * tiles[0]
        assert not got[row, first_dead:].any()


def test_the_window_bites():
    """The agreement above is not that of masks that never matter."""
    q, k, v = _operands(P, 8, jnp.float32)
    full = _f32(_kernel(q, k, v, [P, P], None, block_q=128, block_k=128))
    windowed = _f32(_kernel(q, k, v, [P, P], 40, block_q=128, block_k=128))
    np.testing.assert_allclose(full[:, :40], windowed[:, :40], atol=2e-5)
    assert float(np.abs(full - windowed)[:, 40:].max()) > 0.5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 40, 2 * TILE])
def test_real_positions_are_bit_equal_whatever_the_padding_holds(dtype,
                                                                 window):
    """Junk in the operands at pad positions changes no bit at a real one,
    inside a partly real tile or elsewhere."""
    lengths = [300, 129]
    q, k, v = _operands(P, 8, jnp.dtype(dtype))
    pad = jnp.arange(P)[None, :] >= jnp.asarray(lengths)[:, None]  # (R, P)

    def junk(x, axis):
        shape = [1] * x.ndim
        shape[0], shape[axis] = R, P
        return jnp.where(pad.reshape(shape), jnp.asarray(37.5, x.dtype), x)

    kw = dict(block_q=128, block_k=256)
    got = _kernel(q, k, v, lengths, window, **kw)
    again = _kernel(junk(q, 1), junk(k, 2), junk(v, 2), lengths, window, **kw)
    for row, n in enumerate(lengths):
        np.testing.assert_array_equal(_f32(got[row, :n]),
                                      _f32(again[row, :n]))
    assert np.isfinite(_f32(again)).all()


@pytest.mark.parametrize("window", [None, TILE])
def test_rows_of_length_0_cost_nothing_and_read_as_zeros(window):
    """Every row empty: no tile is visited (NaN operands would show), and
    every output is written, as zeros."""
    ops = [jnp.full_like(x, jnp.nan) for x in _operands(P, 8, jnp.bfloat16)]
    got = _f32(_kernel(*ops, [0, 0], window, block_q=128, block_k=128))
    assert got.shape == (R, P, 8 * D) and not got.any()


# ---- the tile-visit rule and the counters ----------------------------------


@pytest.mark.parametrize("bq,bk", [(4, 4), (8, 4), (4, 8)])
@pytest.mark.parametrize("window", [None, 1, 3, 4, 8, 11, 100])
def test_the_visit_rule_visits_exactly_the_tiles_that_hold_an_allowed_pair(
        bq, bk, window):
    """Brute force over every length of a 32-position row: the tiles
    between ``first`` and ``last`` of a live query tile are those with an
    allowed pair, the kernel's key axis is long enough to reach them all,
    and :func:`tiles_visited` counts what its ``pl.when`` conditions
    admit."""
    n = 32
    steps = gqa.key_steps(n, bq, bk, window)
    i, j = np.arange(n)[:, None], np.arange(n)[None, :]
    for length in range(n + 1):
        allowed = (j <= i) & (i < length) & (j < length)
        if window is not None:
            allowed &= i - j < window
        holds = allowed.reshape(n // bq, bq, n // bk, bk).any(axis=(1, 3))
        admitted = np.zeros_like(holds)
        for qi in range(n // bq):
            live, first, last = (np.asarray(x) for x in gqa.key_tiles(
                qi, length, bq, bk, window))
            assert not live or last - first + 1 <= steps
            for ki in range(steps):         # the kernel's grid and condition
                if live and first + ki <= last:
                    admitted[qi, first + ki] = True
        np.testing.assert_array_equal(admitted, holds)
        assert int(gqa.tiles_visited(jnp.array([length]), n, bq, bk,
                                     window)) == holds.sum()
    # under a window the axis spans the window's tiles, not the row's
    assert steps == n // bk if window is None or window >= n else (
        steps <= -(-(bq + window - 1) // bk) + 1)


@pytest.mark.parametrize("window", [None, 1, 5, 16, 700])
def test_pairs_allowed_is_a_count_of_the_mask(window):
    lengths = [0, 1, 5, 6, 16, 17, 40]
    for n in lengths:
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        seen = j <= i if window is None else (j <= i) & (i - j < window)
        assert float(gqa.pairs_allowed(jnp.array([n]), window)) == seen.sum()
    assert float(gqa.pairs_allowed(jnp.array(lengths), window)) == sum(
        float(gqa.pairs_allowed(jnp.array([n]), window)) for n in lengths)


@pytest.mark.parametrize("window", [None, 1024, 2048])
def test_both_lowerings_visit_more_than_allowed_and_the_kernel_less(window):
    """A run of unequal lengths, one row empty: the kernel's tiles follow
    the lengths, the blocked form's blocks do not."""
    n, lengths = 4096, jnp.array([4000, 1023, 2500, 0])
    allowed = float(gqa.pairs_allowed(lengths, window))
    xla = float(gqa.pairs_visited(lengths, n, window, "xla"))
    kernel = float(gqa.pairs_visited(lengths, n, window, "pallas"))
    assert allowed <= kernel < xla
    assert kernel / allowed < 2.5 < xla / allowed
    # the blocked form computes every row alike, whatever it holds
    assert xla == float(gqa.pairs_visited(jnp.full((4,), n), n, window,
                                          "xla"))
    full = jnp.full((4,), n)
    assert float(gqa.pairs_visited(full, n, window, "pallas")) >= float(
        gqa.pairs_allowed(full, window))


def test_the_blocked_forms_pair_count_is_its_score_tensors():
    """``pairs_visited(..., "xla")`` against the float32 score blocks the
    traced blocked form holds."""
    for n, window, want in [(40, None, 40 * 40), (40, 16, 40 * 40),
                            (600, 16, 3 * 256 * 272),
                            (1024, None, 4 * 256 * 1024),
                            (2048, None, 4 * 256 * 1024 + 4 * 256 * 2048),
                            (2048, 512, 8 * 256 * 768)]:
        assert float(gqa.pairs_visited(jnp.array([3]), n, window,
                                       "xla")) == want
        keys = sorted({span for _, _, span in gqa._blocked_bodies(n, window)})
        q = jax.ShapeDtypeStruct((1, n, 2, 8), jnp.float32)
        k = jax.ShapeDtypeStruct((1, 1, n, 8), jnp.float32)
        jaxpr = str(jax.make_jaxpr(lambda q, k, v: (
            gqa.blocked_prefill_attention(q, k, v, 0.3, window)))(q, k, k))
        for t in keys:
            assert f"f32[1,1,2,{min(256, n)},{t}]" in jaxpr


# ---- which lowering, and where it is stated --------------------------------


# ---------------------------------------------------------------- a keep mask


def _keep(p, seed, rows=R, share=0.3, late=False):
    """``(rows, p, p)`` int8: a share of the causal pairs at random; every
    row keeps at least one key it can see — its own, or with ``late`` (rows
    past the first tile) ONLY keys of its last tile, so that its first key
    tiles hold nothing kept."""
    gap = np.arange(p)[:, None] - np.arange(p)[None, :]
    rng = np.random.default_rng(seed)
    keep = (rng.random((rows, p, p)) < share) | (gap == 0)
    if late:
        keep = keep & ((gap < TILE // 2) | (gap == 0))
        keep[:, :TILE] = gap[:TILE] >= 0
    return jnp.asarray(keep, jnp.int8)


def _dense_kept(q, k, v, keep):
    q, k, v = (np.asarray(a.astype(jnp.float32), np.float64)
               for a in (q, k, v))
    r, p, heads, d = q.shape
    group = heads // k.shape[1]
    seen = (np.arange(p)[:, None] >= np.arange(p)[None, :]) & (
        np.asarray(keep) != 0)
    out = np.zeros((r, p, heads, v.shape[-1]))
    for h in range(heads):
        s = np.einsum("rqd,rkd->rqk", q[:, :, h], k[:, h // group]) * SCALE
        s = np.where(seen, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, h] = np.einsum("rqk,rkd->rqd", w / w.sum(-1, keepdims=True),
                                 v[:, h // group])
    return out.reshape(r, p, -1)


@pytest.mark.parametrize("late", [False, True], ids=["random", "late-keys"])
@pytest.mark.parametrize("lengths", [[P, P], [2 * TILE + 1, 1], [TILE, 0]],
                         ids=str)
@pytest.mark.parametrize("group,dtype,tiles", [
    (1, "float32", (128, 128)), (8, "bfloat16", (256, 128)),
    (1, "bfloat16", (128, 256))], ids=lambda v: str(v))
def test_both_lowerings_attend_under_a_keep_mask(group, dtype, tiles,
                                                 lengths, late):
    """The kernel with the ``keep`` operand and the blocked form with it
    against a dense softmax over exactly the kept causal pairs, at every
    real position; ``late``: rows that keep no key of their first key
    tiles (the running maximum rides on ``MASKED`` until the last)."""
    q, k, v = _operands(P, group, jnp.dtype(dtype))
    keep = _keep(P, 3, late=late)
    r, p, heads, d = q.shape
    with jax.default_matmul_precision("highest"):
        got = _f32(gqa.pallas_prefill_attention(
            q.reshape(r, p, heads * d), k, v, jnp.asarray(lengths, jnp.int32),
            SCALE, keep=keep, block_q=tiles[0], block_k=tiles[1],
            interpret=True))
        blocked = _f32(jax.jit(
            lambda *a: gqa.blocked_prefill_attention(*a[:3], SCALE,
                                                     keep=a[3]))(
            q, k, v, keep))
    dense = _dense_kept(q, k, v, keep)
    assert np.isfinite(got).all() and float(np.abs(dense).max()) > 1.0
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], dense[row, :n],
                                   atol=TOL[dtype])
        np.testing.assert_allclose(blocked[row, :n], dense[row, :n],
                                   atol=TOL[dtype])
        # a query tile past the row's length reads as zeros
        first_dead = -(-n // tiles[0]) * tiles[0]
        assert not got[row, first_dead:].any()
    # the mask bites (the late rows' first tile keeps every key): the
    # unmasked core reads elsewhere
    if not late or lengths[0] > TILE:
        plain = _f32(_kernel(q, k, v, lengths, None, block_q=tiles[0],
                             block_k=tiles[1]))
        assert float(np.abs(plain[0, :lengths[0]]
                            - got[0, :lengths[0]]).max()) > 0.1


def test_a_keep_mask_of_ones_is_the_causal_core_and_takes_no_window():
    q, k, v = _operands(P, 8, jnp.float32)
    ones = jnp.ones((R, P, P), jnp.int8)
    r, p, heads, d = q.shape
    with jax.default_matmul_precision("highest"):
        got = gqa.pallas_prefill_attention(
            q.reshape(r, p, heads * d), k, v, jnp.array([P, 300], jnp.int32),
            SCALE, keep=ones, block_q=TILE, block_k=TILE, interpret=True)
    want = _kernel(q, k, v, [P, 300], None, block_q=TILE, block_k=TILE)
    np.testing.assert_allclose(_f32(got), _f32(want), atol=1e-6)
    for fn in (gqa.pallas_prefill_attention, gqa.blocked_prefill_attention):
        with pytest.raises(ValueError, match="keep mask"):
            if fn is gqa.blocked_prefill_attention:
                fn(q, k, v, SCALE, TILE, keep=ones)
            else:
                fn(q.reshape(r, p, heads * d), k, v, jnp.array([P, P]),
                   SCALE, TILE, keep=ones, interpret=True)


@pytest.mark.parametrize("shape,window,want", [
    ((1, 1024, 4, 4, 256), None, "pallas"),     # GLM-5.2's joined heads
    ((1, 1024, 4, 4, 256), 512, "xla"),         # no window beside a mask
    ((1, 1024, 4, 4, 192), None, "xla"),        # 192 is no lane multiple
], ids=["joined-256", "window", "d-192"])
def test_on_tpu_a_keep_mask_takes_the_kernel_without_a_window(
        monkeypatch, shape, window, want):
    r, n, heads, kv, d = shape
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    assert gqa.prefill_lowering(n, d, jnp.bfloat16, window, keep=True) == want
    if window is not None:
        return
    q = jax.ShapeDtypeStruct((r, n, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((r, kv, n, d), jnp.bfloat16)
    keep = jax.ShapeDtypeStruct((r, n, n), jnp.int8)
    with record_lowerings() as chosen:
        masked = str(jax.make_jaxpr(lambda q, k, v, m: gqa.prefill_attention(
            q, k, v, 0.1, keep=m))(q, k, k, keep))
        plain = str(jax.make_jaxpr(lambda q, k, v: gqa.prefill_attention(
            q, k, v, 0.1))(q, k, k))
    assert chosen["gqa_prefill"] == {want}
    assert ("pallas_call" in masked) == (want == "pallas")
    if want == "pallas":
        # the mask is one more operand of the same kernel; without it the
        # call has the operands it had
        assert masked.count("name=gqa_prefill_fwd") == 1
        assert f"i8[{r},{n},{n}]" in masked and "i8[" not in plain


def _lowering(shape, window, dtype=jnp.bfloat16, monkeypatch=None,
              on_tpu=False):
    r, p, heads, kv, d = shape
    if monkeypatch is not None:
        monkeypatch.setattr(gqa, "_on_tpu", lambda: on_tpu)
    args = [jax.ShapeDtypeStruct(s, dtype) for s in (
        (r, p, heads, d), (r, kv, p, d), (r, kv, p, d))]
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda q, k, v, n: gqa.prefill_attention(
            q, k, v, 0.1, window, n))(
                *args, jax.ShapeDtypeStruct((r,), jnp.int32)))
    return chosen["gqa_prefill"], jaxpr


def test_cpu_default_is_the_blocked_form():
    paths, jaxpr = _lowering((4, 512, 32, 4, 128), 2048)
    assert paths == {"xla"} and "pallas_call" not in jaxpr


@pytest.mark.parametrize("shape,window,dtype,want", [
    ((4, 8192, 32, 4, 128), 2048, jnp.bfloat16, "pallas"),
    ((4, 512, 32, 4, 128), None, jnp.bfloat16, "pallas"),
    ((2, 1536, 8, 8, 128), 512, jnp.float32, "pallas"),
    ((1, 1024, 4, 2, 256), None, jnp.bfloat16, "pallas"),
    ((2, 384, 8, 2, 128), None, jnp.bfloat16, "xla"),    # P off the tile
    ((2, 512, 8, 2, 64), None, jnp.bfloat16, "xla"),     # d
    ((2, 1024, 8, 2, 128), 700, jnp.bfloat16, "xla"),    # window off the tile
    ((2, 16, 4, 2, 8), 8, jnp.float32, "xla"),           # the tests' TINY
], ids=["published-sliding", "published-full", "f32-P1536", "wider", "P-384",
        "d-64", "window-700", "tiny"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, window, dtype, want):
    paths, jaxpr = _lowering(shape, window, dtype, monkeypatch, on_tpu=True)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")
    # the kernel takes q and gives its output where ``wo`` reads it: no
    # transposed copy, no padded keys, no stacked blocks
    assert ("transpose" in jaxpr) == (want == "xla")
    assert ("pad" in jaxpr) == (want == "xla")


def test_a_mesh_in_scope_keeps_the_blocked_form(monkeypatch, devices8):
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering((2, 512, 8, 2, 128), None,
                                 monkeypatch=monkeypatch, on_tpu=True)
    assert paths == {"xla"} and "pallas_call" not in jaxpr


# Trinity's tiny model at the published head width, a window of one (test)
# tile and a prefill of four: the sliding blocks skip tiles on every side
WIDE = dataclasses.replace(TINY, head_dim=D, sliding_window=TILE,
                           max_position_embeddings=1024, prefill_bucket=P)


def _force_kernel(monkeypatch):
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    monkeypatch.setattr(gqa, "TILE", TILE)
    monkeypatch.setattr(gqa, "MIN_TILE", TILE)
    monkeypatch.setattr(
        gqa, "pallas_prefill_attention",
        lambda *a, _f=gqa.pallas_prefill_attention: _f(*a, interpret=True))


def test_prefill_through_the_kernel(monkeypatch):
    """``trinity.prefill`` at the published head width with the kernel
    forced (interpreter): one call a block and no score block, transposed
    query or stacked output in the trace; logits at real positions the
    reference's and the blocked form's; bit-equal whatever the padding
    holds; the pair counters fall; an empty row's attention reads as
    zeros."""
    params, policy = make(WIDE)
    toks = jax.random.randint(jax.random.key(1), (R, P), 1, WIDE.vocab_size)
    lengths = jnp.array([P - 100, 140])
    at = jnp.broadcast_to(jnp.arange(0, P, 4), (R, P // 4))

    def lowered():
        """``tr.prefill`` as ONE program traced under what is patched NOW:
        a fresh function per lowering (``jax.jit`` would keep the trace;
        eagerly the interpreter runs the kernel's grid op by op)."""
        prefill = fresh(tr.prefill)

        def run(tokens, lens):
            with jax.default_matmul_precision("highest"):
                logits, _, stats = prefill(params, tokens, lens, WIDE,
                                           policy, logit_positions=at)
            return logits, stats

        return run

    with jax.default_matmul_precision("highest"):
        want = reference(ref, WIDE)(params, toks)[:, ::4]
    blocked, blocked_stats = lowered()(toks, lengths)
    _force_kernel(monkeypatch)
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda t, n: tr.prefill(
            params, t, n, WIDE, policy)[0])(toks, lengths))
    # (the experts of a prefill keep today's form whatever the backend)
    assert chosen == {"gqa_prefill": {"pallas"}, "moe_experts": {"xla"}}
    # one call a block, ONE traced kernel a kind of block
    assert jaxpr.count("name=_flash_call") == WIDE.num_layers
    assert jaxpr.count("pallas_call") == 2
    assert "dynamic_update_slice" not in jaxpr
    assert f"f32[{R},{WIDE.num_key_value_heads},2,256," not in jaxpr

    run = lowered()
    got, stats = run(toks, lengths)
    junk = jnp.where(jnp.arange(P)[None, :] < lengths[:, None], toks, 5)
    again, _ = run(junk, lengths)
    for row, n in enumerate(np.asarray(lengths)):
        real = np.asarray(at[row]) < n
        assert float(jnp.abs(got[row, real] - blocked[row, real]).max()) < 2e-4
        assert float(jnp.abs(got[row, real] - want[row, real]).max()) < 2e-4
        np.testing.assert_array_equal(np.asarray(got[row, real]),
                                      np.asarray(again[row, real]))
    assert float(want.std()) > 0.3

    allowed = float(stats["attn.prefill_pairs_allowed"])
    assert allowed == float(blocked_stats["attn.prefill_pairs_allowed"])
    assert allowed == sum(
        float(gqa.pairs_allowed(lengths, b.window))
        for b in tr.blocks_of(WIDE).values())
    assert allowed <= float(stats["attn.prefill_pairs_visited"]) < float(
        blocked_stats["attn.prefill_pairs_visited"])

    x = jax.random.normal(jax.random.key(2), (R, P, WIDE.hidden_size))
    for name in ("l0", "l3"):       # a sliding block and the full one
        out, _ = jax.jit(lambda x, p, n, block=tr.blocks_of(WIDE)[name]:
                         block.prefill(x, p, n))(
            x, params["layers"][int(name[1])]["attn"], jnp.array([P, 0]))
        assert not np.asarray(out[1]).any() and np.asarray(out[0]).any()


def test_engine_states_the_lowering_and_publishes_the_pair_counters():
    """``status()["gqa_prefill"]``: ``None`` before an admission program is
    traced, then what the trace chose — the blocked form on the CPU; the
    two pair counters reach the registry with the flags fetch."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.observe.metrics import get_registry

    params, policy = make()
    eng = ServingEngine(TINY, params, policy=policy,
                        num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                        max_len=32)
    assert eng.status()["gqa_prefill"] is None
    primes = ([3, 4, 5], list(range(1, 12)))
    for uid, tokens in enumerate(primes):
        eng.submit(Request(uid=uid, tokens=tokens, max_new_tokens=3,
                           temperature=0.0, seed=1))
    assert len(eng.run_until_idle(max_chunks=20)) == 2
    status = eng.status()
    assert status["gqa_prefill"] == "xla"
    assert status["mla_prefill"] is None
    # one admission run a request (one row an admission at these slots):
    # three sliding blocks of window 8, one full, one more sliding
    blocks = tr.blocks_of(TINY).values()
    allowed = sum(float(gqa.pairs_allowed(jnp.array([len(t)]), b.window))
                  for t in primes for b in blocks)
    visited = sum(float(gqa.pairs_visited(
        jnp.array([len(t)]), eng.family.bucket(len(t), 32), b.window, "xla"))
        for t in primes for b in blocks)
    stats = eng.model_stats
    assert stats["attn.prefill_pairs_allowed"] == allowed
    assert stats["attn.prefill_pairs_visited"] == visited > allowed
    snap = get_registry().snapshot()
    assert snap["attn.prefill_pairs_allowed"]["value"] == allowed
    assert snap["attn.prefill_pairs_visited"]["value"] == visited


# ---- two widths: keys 192 beside values of 128 (MiMo's full layers; PR 62) --

D2, DV2, GROUP2 = 192, 128, 16
SCALE2 = D2 ** -0.5


@functools.lru_cache(maxsize=4)      # the two dtypes under the two windows
def _two_widths(dtype, window):
    """``(q (3, P, 16, 192), k (3, 1, P, 192), v (3, 1, P, 128))`` with O(1)
    logits, and the blocked form's and the dense reference's outputs."""
    ks = jax.random.split(jax.random.key(62), 3)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(ks[0], (3, P, GROUP2, D2), jnp.float32).astype(dt)
    k = (3.0 * jax.random.normal(ks[1], (3, 1, P, D2), jnp.float32)).astype(dt)
    v = jax.random.normal(ks[2], (3, 1, P, DV2), jnp.float32).astype(dt)
    return (q, k, v), _f32(_blocked(q, k, v, window, SCALE2)), _dense(
        q, k, v, window, SCALE2)


@pytest.mark.parametrize("lengths", [[P - 100, 129, 0], [P, 1, 2 * TILE]],
                         ids=str)
@pytest.mark.parametrize("window", [None, TILE], ids=["no-window", "one-tile"])
@pytest.mark.parametrize("dtype,tiles", [
    ("bfloat16", (128, 128)), ("float32", (256, 128)),
    ("bfloat16", (128, 256))], ids=lambda v: str(v))
def test_the_kernel_takes_keys_192_wide_beside_values_of_128(
        dtype, tiles, window, lengths):
    """MiMo's full head shape, 16 query heads a key/value head: ragged rows
    with pads and an empty row against the blocked XLA form and a dense
    reference at every real position, within the file's tolerance; the
    output is ``H * 128`` wide; tiles past a row's length read as zeros.
    (The window beside two widths is no cell's today; the kernel takes it.)"""
    (q, k, v), blocked, dense = _two_widths(dtype, window)
    got = _f32(_kernel(q, k, v, lengths, window, SCALE2, block_q=tiles[0],
                       block_k=tiles[1]))
    assert got.shape == (3, P, GROUP2 * DV2) == blocked.shape
    assert np.isfinite(got).all() and float(np.abs(dense).max()) > 1.0
    for row, n in enumerate(lengths):
        if n:
            assert float(np.abs(got[row, :n] - blocked[row, :n]).max()) \
                < TOL[dtype]
            assert float(np.abs(got[row, :n] - dense[row, :n]).max()) \
                < TOL[dtype]
        first_dead = -(-n // tiles[0]) * tiles[0]
        assert not got[row, first_dead:].any()


def test_padded_key_columns_change_no_bit():
    """The kernel over keys 192 wide IS the kernel over the same heads with
    64 zero columns written behind each by hand, bit for bit."""
    (q, k, v), _, _ = _two_widths("bfloat16", None)
    lengths = [P - 100, 129, 0]
    columns = ((0, 0),) * 3 + ((0, 256 - D2),)
    kw = dict(block_q=128, block_k=256)
    got = _kernel(q, k, v, lengths, None, SCALE2, **kw)
    by_hand = _kernel(jnp.pad(q, columns), jnp.pad(k, columns), v, lengths,
                      None, SCALE2, **kw)
    np.testing.assert_array_equal(_f32(got), _f32(by_hand))


# what ``prefill_lowering`` answers on a TPU with no mesh in scope, bfloat16:
# (n, d, window, further arguments) -> the lowering
LOWERINGS = {
    "mimo-full-16384": ((16384, 192, None, dict(dv=128)), "pallas"),
    "mimo-full-512": ((512, 192, None, dict(dv=128)), "pallas"),
    # a sink; a window of 128 under a tile: each alone is enough
    "mimo-sliding": ((16384, 192, 128, dict(dv=128, sink=True)), "xla"),
    "mimo-sliding-without-its-sink": ((16384, 192, 128, dict(dv=128)), "xla"),
    "mimo-full-with-a-sink": ((16384, 192, None, dict(dv=128, sink=True)),
                              "xla"),
    "two-widths-under-a-tiled-window": ((2048, 192, 1024, dict(dv=128)),
                                        "pallas"),
    "granite-64": ((1024, 64, None, {}), "xla"),
    "trinity-sliding": ((8192, 128, 2048, {}), "pallas"),
    "glm52-joined": ((16384, 256, None, dict(keep=True)), "pallas"),
    # joined heads of 256 beside values of 128: the window of 513 declines
    "dots3-sliding": ((16384, 256, 513, dict(dv=128)), "xla"),
    # an output block is one head's dv columns: 192 of them are off the lane
    "192-at-one-width": ((1024, 192, None, {}), "xla"),
    "values-of-192": ((1024, 256, None, dict(dv=192)), "xla"),
    # keys under a lane tile would be padded to twice their width and more
    "64-beside-128": ((1024, 64, None, dict(dv=128)), "xla"),
}


@pytest.mark.parametrize("case", list(LOWERINGS))
def test_the_lowering_table(monkeypatch, case):
    (n, d, window, more), want = LOWERINGS[case]
    assert gqa.prefill_lowering(n, d, jnp.bfloat16, window, **more) == "xla"
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    assert gqa.prefill_lowering(n, d, jnp.bfloat16, window, **more) == want


def test_on_tpu_mimos_full_block_traces_one_kernel_over_padded_keys(
        monkeypatch):
    """The cell's own 16,384 bucket: the call is ``gqa_prefill_fwd`` over q
    of ``64 x 256`` columns and keys of 256, values of 128 as they came, no
    float32 score block, the output where ``wo`` reads it; the sliding kind
    keeps the blocked form."""
    r, n, heads, kv = 1, 16384, 64, 4
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    sd, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    q, k, v = (sd((r, n, heads, D2), bf), sd((r, kv, n, D2), bf),
               sd((r, kv, n, DV2), bf))
    lengths = sd((r,), jnp.int32)
    with record_lowerings() as chosen:
        closed = jax.make_jaxpr(lambda q, k, v, m: gqa.prefill_attention(
            q, k, v, SCALE2, lengths=m))(q, k, v, lengths)
    jaxpr = str(closed)
    assert chosen["gqa_prefill"] == {"pallas"}
    assert jaxpr.count("pallas_call") == jaxpr.count(
        "name=gqa_prefill_fwd") == 1
    assert closed.out_avals[0].shape == (r, n, heads * DV2)
    for operand in (f"bf16[{r},{n},{heads * 256}]", f"bf16[{r},{kv},{n},256]",
                    f"bf16[{r},{kv},{n},{DV2}]"):
        assert operand in jaxpr, operand
    assert f"f32[{r},{kv},{heads // kv},256," not in jaxpr
    assert "transpose" not in jaxpr
    sink = sd((heads,), bf)
    with record_lowerings() as chosen:
        jax.make_jaxpr(lambda q, k, v, m, s: gqa.prefill_attention(
            q, k, v, SCALE2, 128, m, sink=s))(q, k, v, lengths, sink)
    assert chosen["gqa_prefill"] == {"xla"}


MIMO_WIDE = dataclasses.replace(
    mimo_v2_tiny.TINY, head_dim=D2, v_head_dim=DV2, num_key_value_heads=2,
    max_position_embeddings=1024)


def test_mimo_engine_admits_through_the_kernel_and_serves_the_same_tokens(
        monkeypatch):
    """MiMo's engine at the published FULL head widths, a prime of 300 in the
    512 bucket: on the CPU both kinds admit through the blocked form; with
    the kernel forced (interpreter, tiles of 128) the full layers take it and
    the sliding ones — a sink, a window of 4 — do not:
    ``status()["gqa_prefill"]`` names both, the greedy tokens are the same,
    and ``attn.prefill_pairs_visited`` counts the full layers' six visited
    tiles where it counted two blocks of 256 rows against 512 keys."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.models import mimo_v2 as mm

    params, policy = mimo_v2_tiny.make(MIMO_WIDE)
    prime = np.random.default_rng(0).integers(1, MIMO_WIDE.vocab_size, 300)

    def serve():
        eng = ServingEngine(MIMO_WIDE, params, policy=policy,
                            num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                            max_len=P + 8)
        eng.submit(Request(uid=0, tokens=prime.tolist(), max_new_tokens=5,
                           temperature=0.0, seed=1))
        (done,) = eng.run_until_idle(max_chunks=10)
        return list(done.tokens), eng.status(), eng.model_stats

    want, status, stats = serve()
    assert status["gqa_prefill"] == "xla"
    blocks = mm.blocks_of(MIMO_WIDE).values()
    full = [b for b in blocks if b.window is None]
    sliding = float(sum(gqa.pairs_visited(jnp.array([300]), P, b.window, "xla")
                        for b in blocks if b.window is not None))
    assert len(full) == 2 and stats["attn.prefill_pairs_visited"] == (
        sliding + len(full) * P * P)
    _force_kernel(monkeypatch)
    got, status, kernel_stats = serve()
    assert status["gqa_prefill"] == "pallas+xla"
    assert got == want
    assert kernel_stats["attn.prefill_pairs_visited"] == (
        sliding + len(full) * 6 * TILE ** 2)
    assert kernel_stats["attn.prefill_pairs_allowed"] == stats[
        "attn.prefill_pairs_allowed"]


# sha256 heads of ``gqa_prefill_fwd``'s jaxpr text at ONE head width, taken
# on PR 61's tree (c9e78f8) before the kernel took values of another width:
# ``(R, P, H, KV, d)`` of an admission of Trinity's, SDAR's and GLM-5.2's
# cells and what else the call takes.  They trace what they traced, letter
# for letter (``tests/golden/programs.json`` is the CPU's trace and holds no
# Pallas lowering)
KERNEL_TEXT = {
    "trinity-sliding-512": ((4, 512, 32, 4, 128), dict(window=2048),
                            "0a2c07812635734c"),
    "trinity-sliding-8192": ((4, 8192, 32, 4, 128), dict(window=2048),
                             "6bf03e5c42328570"),
    "trinity-full-512": ((4, 512, 32, 4, 128), {}, "321e962f59be05a8"),
    "trinity-full-8192": ((4, 8192, 32, 4, 128), {}, "1ea2364b97369372"),
    "sdar-block-1024": ((4, 1024, 32, 4, 128), dict(block=4),
                        "61ffed2d28f9dc3b"),
    "glm52-keep-512": ((1, 512, 64, 64, 256), dict(keep=True),
                       "16bc43dcde0c5033"),
    "glm52-keep-8192": ((1, 8192, 64, 64, 256), dict(keep=True),
                        "2985c4281a3a098c"),
    "glm52-keep-16384": ((1, 16384, 64, 64, 256), dict(keep=True),
                         "fdbe6ed782e6e708"),
}


@pytest.mark.parametrize("case", list(KERNEL_TEXT))
def test_at_one_width_the_kernel_traces_the_text_it_traced(case):
    """The cells' own shapes, bfloat16, interpreter, the tiles the chip path
    takes."""
    (r, n, heads, kv, d), opts, want = KERNEL_TEXT[case]
    opts = dict(opts)
    window = opts.pop("window", None)
    sd, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    shapes = [sd((r, n, heads * d), bf), sd((r, kv, n, d), bf),
              sd((r, kv, n, d), bf), sd((r,), jnp.int32)]
    if opts.pop("keep", False):
        shapes.append(sd((r, n, n), jnp.int8))
    head = program_hash.program_head(
        lambda q, k, v, m, *keep: gqa.pallas_prefill_attention(
            q, k, v, m, 0.1, window, interpret=True, **opts,
            **({"keep": keep[0]} if keep else {})),
        tuple(shapes))
    assert head == want
