"""The attention of a family that generates by blocks (``ops/gqa.py``,
``ops/row_write.py``, ``models/kv.py``): the prefill under a mask that is
causal across blocks and open inside one — both lowerings — against a dense
mask; ``B`` queries over a cache plus themselves against the rows of a full
forward — the XLA form and the kernel ``gqa_block_decode_fwd`` under the
interpreter, and the kernel against the XLA form for both query widths,
every ``lead`` pattern, counts of 0, 1 and around a tile's edge, with NaN
past a count —; a commit that writes exactly its ``B`` rows and a denoise
forward that writes none."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops import gqa, row_write

HEADS, KV, D = 4, 2, 16


def _qkv(seed, r, n, d=D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (r, n, HEADS, d), dtype)
    k = jax.random.normal(ks[1], (r, KV, n, d), dtype)
    v = jax.random.normal(ks[2], (r, KV, n, d), dtype)
    return q, k, v


def _dense(q, k, v, scale, seen):
    """Plain masked softmax: ``seen (n, n)`` bool."""
    r, n, heads, d = q.shape
    k = jnp.repeat(k, heads // k.shape[1], axis=1)
    v = jnp.repeat(v, heads // v.shape[1], axis=1)
    logits = jnp.einsum("rqhd,rhtd->rhqt", q, k) * scale
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("rhqt,rhtd->rqhd", probs, v).reshape(r, n, heads * d)


def _block_mask(n, b):
    at = np.arange(n) // b
    return at[None, :] <= at[:, None]


@pytest.mark.parametrize("n,block", [
    (4, 4), (8, 4), (8, 8), (40, 4), (40, 8), (256, 4), (260, 4), (264, 8),
    (516, 4), (1096, 8), (1100, 4)])
def test_blocked_form_under_the_block_mask_equals_a_dense_mask(n, block):
    """Lengths below, at and across the mask's blocks and the form's query
    blocks (256) and groups of them (1024)."""
    q, k, v = _qkv(n, 2, n)
    # one compiled program a form and length (eagerly the blocks are
    # dispatched op by op)
    prefill = jax.jit(gqa.prefill_attention, static_argnums=(3, 4, 5, 6))
    got = prefill(q, k, v, 0.25, None, None, block)
    want = jax.jit(_dense, static_argnums=3)(q, k, v, 0.25,
                                             _block_mask(n, block))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the mask matters: the causal form differs inside every block
    causal = prefill(q, k, v, 0.25, None, None, 1)
    assert float(jnp.abs(causal - want).max()) > 1e-2
    # its last position of each block sees what the block mask shows all
    last = np.arange(block - 1, n, block)
    np.testing.assert_allclose(causal[:, last], want[:, last], atol=2e-5)


@pytest.mark.parametrize("lengths", [(64, 32), (12, 0), (40, 64)])
@pytest.mark.parametrize("tiles", [(16, 16), (32, 16), (16, 32)])
def test_kernel_under_the_block_mask_equals_a_dense_mask(lengths, tiles):
    """The flash kernel in interpret mode, rows of whole blocks below, at
    and across its tiles; real positions only (``ops/gqa.py``'s contract)."""
    n, block = 64, 4
    q, k, v = _qkv(7, 2, n)
    got = gqa.pallas_prefill_attention(
        q.reshape(2, n, HEADS * D), k, v, jnp.asarray(lengths), 0.25,
        block=block, block_q=tiles[0], block_k=tiles[1], interpret=True)
    want = _dense(q, k, v, 0.25, _block_mask(n, block))
    for i, length in enumerate(lengths):
        np.testing.assert_allclose(got[i, :length], want[i, :length],
                                   atol=2e-5)
    blocked = gqa.blocked_prefill_attention(q, k, v, 0.25, None, block)
    np.testing.assert_allclose(got[0, :lengths[0]], blocked[0, :lengths[0]],
                               atol=2e-5)


def test_the_block_mask_refuses_what_it_cannot_keep():
    q, k, v = _qkv(0, 1, 64)
    with pytest.raises(ValueError, match="block mask"):
        gqa.blocked_prefill_attention(q, k, v, 1.0, 8, 4)
    with pytest.raises(ValueError, match="block mask"):
        gqa.blocked_prefill_attention(q, k, v, 1.0, None, 3)
    with pytest.raises(ValueError, match="block mask"):    # 60 = 7.5 x 8
        gqa.blocked_prefill_attention(q[:, :60], k[:, :, :60], v[:, :, :60],
                                      1.0, None, 8)
    with pytest.raises(ValueError, match="block mask"):
        gqa.pallas_prefill_attention(
            q.reshape(1, 64, -1), k, v, jnp.asarray([64]), 1.0, block=3,
            block_q=16, block_k=16, interpret=True)


@pytest.mark.parametrize("shape,block,want", [
    ((1024, 128), 4, "pallas"), ((1024, 128), 1, "pallas"),
    ((1024, 128), 3, "xla"), ((1024, 64), 4, "xla"), ((640, 128), 4, "xla")])
def test_on_tpu_the_block_mask_keeps_the_kernels_rule(monkeypatch, shape,
                                                      block, want):
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    n, d = shape
    assert gqa.prefill_lowering(n, d, jnp.bfloat16, None, block) == want
    assert gqa.prefill_lowering(n, d, jnp.bfloat16, 2048, block) == (
        want if block == 1 else "xla")


@pytest.mark.parametrize("block", [4, 8])
def test_pairs_allowed_under_the_block_mask_is_a_count_of_the_mask(block):
    lengths = jnp.asarray([0, block, 5 * block, 64])
    want = sum(int(_block_mask(int(n), block).sum()) for n in lengths)
    assert float(gqa.pairs_allowed(lengths, None, block)) == want


def _kernel(*args, **kw):
    """The kernel lowering under the interpreter, four key tiles a cache of
    32 rows."""
    return gqa.pallas_block_decode_attention(*args, block_k=8,
                                             interpret=True, **kw)


FORMS = {"xla": gqa.block_decode_attention, "kernel": _kernel}


@pytest.mark.parametrize("form", list(FORMS))
def test_b_queries_over_a_cache_and_themselves_are_a_full_forwards_rows(form):
    """Slots at different cursors (one with nothing committed), rows of the
    cache past the cursor holding anything."""
    b, t, slots = 4, 32, 3
    q, k, v = _qkv(3, slots, t)
    counts = jnp.asarray([0, 8, 28])
    want = _dense(q, k, v, 0.25, _block_mask(t, b))
    junk = jax.random.normal(jax.random.key(9), k.shape)
    at = jnp.arange(t)[None, None, :, None]
    past = at < counts[:, None, None, None]
    got = FORMS[form](
        jnp.stack([q[j, int(counts[j]):int(counts[j]) + b]
                   for j in range(slots)]),
        jnp.where(past, k, junk), jnp.where(past, v, junk),
        jnp.stack([k[j, :, int(counts[j]):int(counts[j]) + b]
                   for j in range(slots)]),
        jnp.stack([v[j, :, int(counts[j]):int(counts[j]) + b]
                   for j in range(slots)]), counts, 0.25)
    for i in range(slots):
        lo = int(counts[i])
        np.testing.assert_allclose(got[i], want[i, lo:lo + b], atol=2e-5)


LEADS = [(True, True, True), (False, True, False), (False, False, False)]


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("lead", LEADS)
def test_two_blocks_over_a_cache_are_a_full_forwards_rows(lead, form):
    """A pending block in front of the block in progress: where ``lead``
    both blocks' rows are the full forward's (the second sees the front
    block's keys in the forward's own tile); where not, the front block is
    filler the second block does not see — it finds that block among the
    cache's rows, and its rows are the full forward's all the same."""
    b, t, slots = 4, 32, 3
    q, k, v = _qkv(5, slots, t)
    cursor = np.asarray([4, 12, 28])        # where the second block starts
    lead = np.asarray(lead)
    want = _dense(q, k, v, 0.25, _block_mask(t, b))
    counts = jnp.asarray(cursor - b * lead)
    junk = jax.random.normal(jax.random.key(9), k.shape)
    at = jnp.arange(t)[None, None, :, None]
    past = at < counts[:, None, None, None]
    fq, fk, fv = _qkv(11, slots, b)         # a filler front block
    rows = [np.arange(c - b, c + b) for c in cursor]
    q_new = jnp.stack([q[j, r] for j, r in enumerate(rows)])
    k_new = jnp.stack([k[j][:, r] for j, r in enumerate(rows)])
    v_new = jnp.stack([v[j][:, r] for j, r in enumerate(rows)])
    filler = ~lead[:, None, None, None]
    q_new = q_new.at[:, :b].set(jnp.where(filler, fq, q_new[:, :b]))
    k_new = k_new.at[:, :, :b].set(jnp.where(filler, fk, k_new[:, :, :b]))
    v_new = v_new.at[:, :, :b].set(jnp.where(filler, fv, v_new[:, :, :b]))
    attend = FORMS[form]
    got = attend(
        q_new, jnp.where(past, k, junk), jnp.where(past, v, junk), k_new,
        v_new, counts, 0.25, lead=jnp.asarray(lead))
    assert got.shape == (slots, 2 * b, HEADS * D)
    for i, c in enumerate(cursor):
        np.testing.assert_allclose(got[i, b:], want[i, c:c + b], atol=2e-5)
        if lead[i]:
            np.testing.assert_allclose(got[i, :b], want[i, c - b:c],
                                       atol=2e-5)
    # the second block's rows do not depend on what the filler holds
    other = attend(
        jnp.where(filler, 2 * q_new, q_new).at[:, b:].set(q_new[:, b:]),
        jnp.where(past, k, junk), jnp.where(past, v, junk),
        k_new.at[:, :, :b].set(jnp.where(filler, -k_new[:, :, :b],
                                         k_new[:, :, :b])),
        v_new.at[:, :, :b].set(jnp.where(filler, 3 * v_new[:, :, :b],
                                         v_new[:, :, :b])),
        counts, 0.25, lead=jnp.asarray(lead))
    np.testing.assert_array_equal(np.asarray(other[:, b:]),
                                  np.asarray(got[:, b:]))


# the three slots' committed rows by what they do in a tiling of 32 rows by
# 8: nothing (no tile visited), one row, one under a tile's edge, on it, one
# past it, the full cache; the LAST slot matters by itself (its idle steps
# stay on its own last tile, or on none)
KERNEL_COUNTS = {"0-under-past": (0, 7, 9), "1-on-full": (1, 8, 32),
                 "full-0-0": (32, 0, 0)}
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("dtype", list(TOL))
@pytest.mark.parametrize("counts", list(KERNEL_COUNTS))
@pytest.mark.parametrize("m,n,lead", [
    *((m, 8, lead) for m in (4, 8) for lead in LEADS), (4, 4, None)],
    ids=lambda v: "".join("FT"[x] for x in v) if isinstance(v, tuple)
    else str(v))
def test_kernel_equals_the_xla_form(m, n, lead, counts, dtype):
    """``gqa_block_decode_fwd`` under the interpreter against
    ``xla_block_decode_attention``: both blocks' queries (``m`` = 2B) and
    the last layer's (``m`` = B) over two blocks' keys, and the B-wide call
    of a first block or the direct check; rows past a count inside the tile
    it crosses hold junk and the tiles wholly past it NaN — neither seen nor
    read."""
    slots, t, bk = 3, 32, 8
    dtype = jnp.dtype(dtype)
    q = _qkv(m, slots, m, dtype=dtype)[0]
    _, k, v = _qkv(n + 1, slots, t, dtype=dtype)
    _, k_new, v_new = _qkv(n + 2, slots, n, dtype=dtype)
    counts = jnp.asarray(KERNEL_COUNTS[counts])
    lead = None if lead is None else jnp.asarray(lead)
    want = gqa.xla_block_decode_attention(q, k, v, k_new, v_new, counts, 0.25,
                                          lead)
    at = jnp.arange(t)[None, None, :, None]
    edge = counts[:, None, None, None]

    def spoiled(a):
        junk = jnp.where(at >= edge, jnp.asarray(37.5, a.dtype), a)
        return jnp.where(at >= -(-edge // bk) * bk, jnp.nan, junk)

    got = _kernel(q, spoiled(k), spoiled(v), k_new, v_new, counts, 0.25,
                  lead=lead)
    assert got.shape == (slots, m, HEADS * D) and got.dtype == dtype
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.isfinite(got).all() and float(np.abs(want).max()) > 0.3
    assert float(np.abs(got - want).max()) < TOL[dtype.name]


def test_the_kernel_refuses_a_tile_that_does_not_divide_the_cache():
    q, k, v = _qkv(0, 1, 32)
    with pytest.raises(ValueError, match="does not divide"):
        gqa.pallas_block_decode_attention(
            q[:, :4], k, v, k[:, :, :4], v[:, :, :4], jnp.asarray([4]), 1.0,
            block_k=12, interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", [False, True])
def test_a_commit_writes_exactly_its_rows_and_a_denoise_forward_none(
        dtype, kernel):
    slots, rows, b = 4, 32, 4
    ks = jax.random.split(jax.random.key(0), 4)
    caches = tuple(jax.random.normal(k, (slots, KV, rows, D)).astype(dtype)
                   for k in ks[:2])
    updates = tuple(jax.random.normal(k, (slots, KV, b, D)).astype(dtype)
                    for k in ks[2:])
    start = jnp.asarray([0, 12, 28, 16])
    write = jnp.asarray([True, False, True, False])
    if kernel:
        got = row_write.pallas_write_row_blocks(caches, updates, start,
                                                write, interpret=True)
    else:
        got = row_write.write_row_blocks(caches, updates, start, write)
    for old, new, upd in zip(caches, got, updates):
        old, new, upd = (np.asarray(a, np.float32) for a in (old, new, upd))
        for i in range(slots):
            lo = int(start[i])
            changed = (old[i] != new[i]).any(axis=(0, 2))
            if write[i]:
                assert changed.tolist() == [lo <= r < lo + b
                                            for r in range(rows)]
                np.testing.assert_array_equal(new[i, :, lo:lo + b], upd[i])
            else:
                assert not changed.any()


def test_on_tpu_the_block_write_is_the_kernel_where_the_tile_takes_it(
        monkeypatch):
    from progen_tpu.ops.lowering import record_lowerings

    monkeypatch.setattr(row_write, "_on_tpu", lambda: True)
    took = {}

    def fake(caches, updates, start, write):
        took["kernel"] = True
        return caches

    monkeypatch.setattr(row_write, "pallas_write_row_blocks", fake)
    cache = (jnp.zeros((2, KV, 32, D), jnp.bfloat16),)
    with record_lowerings() as chosen:
        row_write.write_row_blocks(
            cache, (jnp.zeros((2, KV, 4, D), jnp.bfloat16),),
            jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool))
        # a block of 3 does not divide the tile of 16: the slice update
        row_write.write_row_blocks(
            cache, (jnp.zeros((2, KV, 3, D), jnp.bfloat16),),
            jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool))
    assert took and chosen["row_write"] == {"pallas", "scatter"}
