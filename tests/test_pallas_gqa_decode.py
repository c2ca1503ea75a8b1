"""``ops/gqa.py:decode_attention``: one query a slot over the first
``counts`` rows of a slot's keys and values, two lowerings, one contract.
The Pallas kernel (``gqa_decode_fwd``) runs under the interpreter here, at
the head width Trinity and LFM2 state (128) and small tiles: against the
XLA form and a dense float64 softmax for groups of 8, 2 and 1, counts of
1, on a tile's edge, one either side of it and at ``T``, mixed over slots;
a ring in wrapped order equal to the same rows in order; bit-equal
whatever the cache holds past a count, tiles past it never read; the rows
each lowering reads; the choice of lowering from backend, mesh, dtype and
shape; ``KVBlock.decode`` through the kernel for a ring past its wrap and
a grown cache; and ``status()["gqa_decode"]``.  The block form's kernel
(``gqa_block_decode_fwd``; its arithmetic is held in
``tests/test_block_attention.py``) follows the same rule:
``block_decode_lowering``, ``KVBlock.decode_block`` through it, and the
counter that follows it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models import kv as kv_blocks
from progen_tpu.models import sdar
from progen_tpu.models import trinity as tr
from progen_tpu.ops import gqa
from progen_tpu.ops.lowering import record_lowerings
from tests import sdar_tiny
from tests.trinity_tiny import TINY, make

D, SLOTS = 128, 4
SCALE = D ** -0.5
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(heads, kv, t, dtype, seed=0, slots=SLOTS):
    """``q (S, H, d)``, ``k, v (S, KV, T, d)`` with O(1) logits and a
    spread that makes the softmax matter."""
    ks = jax.random.split(jax.random.key(seed), 3)

    def normal(k, shape, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * gain).astype(dtype)

    return (normal(ks[0], (slots, heads, D)),
            normal(ks[1], (slots, kv, t, D), 3.0),
            normal(ks[2], (slots, kv, t, D)))


def _kernel(q, k, v, counts, **kw):
    with jax.default_matmul_precision("highest"):
        return gqa.pallas_decode_attention(
            q, k, v, jnp.asarray(counts, jnp.int32), SCALE, interpret=True,
            **kw)


def _xla(q, k, v, counts):
    with jax.default_matmul_precision("highest"):
        return gqa.xla_decode_attention(
            q, k, v, jnp.asarray(counts, jnp.int32), SCALE)


def _dense(q, k, v, counts):
    """The softmax over each slot's first ``counts`` rows in float64."""
    q, k, v = (np.asarray(a.astype(jnp.float32), np.float64)
               for a in (q, k, v))
    s, heads, d = q.shape
    group = heads // k.shape[1]
    out = np.zeros((s, heads, d))
    for si, n in enumerate(counts):
        for h in range(heads):
            logits = k[si, h // group, :n] @ q[si, h] * SCALE
            p = np.exp(logits - logits.max())
            out[si, h] = (p / p.sum()) @ v[si, h // group, :n]
    return out.reshape(s, heads * d)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


# counts of the four slots by what they do in a tiling of ``T`` by ``bk``;
# the LAST slot matters by itself (its idle steps stay on its own last
# tile, every other slot's point at the next slot's first)
COUNTS = {
    "all-1": lambda t, bk: [1, 1, 1, 1],
    "a-tiles-multiple": lambda t, bk: [bk, 1, t - bk, 2 * bk],
    "a-multiple-plus-1": lambda t, bk: [bk + 1, 1, t - bk + 1, 2],
    "a-multiple-minus-1": lambda t, bk: [bk - 1, t - 1, 1, 2 * bk - 1],
    "at-T": lambda t, bk: [t, 1, t, t],
    "mixed": lambda t, bk: [t, bk + 3, 1, t - 1],
}


@pytest.mark.parametrize("case", list(COUNTS))
@pytest.mark.parametrize("heads,kv,t,bk,dtype", [
    (32, 4, 512, 128, "float32"), (32, 4, 1024, 256, "bfloat16"),
    (8, 4, 512, 256, "bfloat16"), (4, 4, 1024, 512, "float32"),
    (4, 4, 1024, None, "bfloat16")], ids=lambda v: str(v))
def test_kernel_equals_the_xla_form_and_a_dense_softmax(heads, kv, t, bk,
                                                        dtype, case):
    q, k, v = _operands(heads, kv, t, jnp.dtype(dtype))
    tile = bk or gqa.fitted_decode_tile(t)
    # (one tile covering T folds some cases onto 1 and T)
    counts = [min(max(n, 1), t) for n in COUNTS[case](t, tile)]
    got = _kernel(q, k, v, counts, block_k=bk)
    assert got.shape == (SLOTS, heads * D) and got.dtype == jnp.dtype(dtype)
    got = _f32(got)
    assert np.isfinite(got).all()
    want = _dense(q, k, v, counts)
    assert float(np.abs(want).max()) > 0.5      # not a vacuous bound
    assert float(np.abs(got - want).max()) < TOL[dtype]
    assert float(np.abs(got - _f32(_xla(q, k, v, counts))).max()) < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_ring_in_wrapped_order_is_the_same_rows_in_order(dtype):
    """A ring holds the last ``T`` tokens from wherever its write stands:
    the softmax does not care, under the kernel either."""
    t, bk = 512, 128
    q, k, v = _operands(32, 4, t, jnp.dtype(dtype), seed=3)
    full = [t] * SLOTS
    in_order = _f32(_kernel(q, k, v, full, block_k=bk))
    wrapped = _f32(_kernel(q, jnp.roll(k, 200, axis=2),
                           jnp.roll(v, 200, axis=2), full, block_k=bk))
    assert float(np.abs(in_order).max()) > 0.5
    assert float(np.abs(wrapped - in_order).max()) < TOL[dtype]
    assert float(np.abs(in_order - _dense(q, k, v, full)).max()) < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_past_a_count_are_neither_seen_nor_read(dtype):
    """Junk past a slot's count inside the tile its count crosses changes
    no bit; a tile wholly past it is not visited at all (NaN there would
    show in the running maximum)."""
    t, bk, counts = 512, 128, [1, 130, 256, 511]
    q, k, v = _operands(32, 4, t, jnp.dtype(dtype))
    got = _kernel(q, k, v, counts, block_k=bk)
    at = jnp.arange(t)[None, None, :, None]
    n = jnp.asarray(counts)[:, None, None, None]

    def spoiled(a):
        junk = jnp.where(at >= n, jnp.asarray(37.5, a.dtype), a)
        return jnp.where(at >= -(-n // bk) * bk, jnp.nan, junk)

    again = _kernel(q, spoiled(k), spoiled(v), counts, block_k=bk)
    np.testing.assert_array_equal(_f32(got), _f32(again))
    assert np.isfinite(_f32(again)).all()


def test_rows_visited_counts_whole_tiles_under_the_kernel(monkeypatch):
    k = jax.ShapeDtypeStruct((4, 4, 4096, D), jnp.bfloat16)
    counts = jnp.array([1, 1024, 1025, 4096])
    assert gqa.decode_lowering(jnp.bfloat16, k, k) == "xla"     # the CPU
    assert float(gqa.rows_visited(k, counts, "xla")) == 4 * 4096
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    assert gqa.decode_lowering(jnp.bfloat16, k, k) == "pallas"
    assert gqa.fitted_decode_tile(4096) == gqa.DECODE_TILE == 1024
    assert float(gqa.rows_visited(k, counts, "pallas")) == 1024 * (
        1 + 1 + 2 + 4)
    # long caches the large tile (Trinity's grown keys), caches under four
    # of them the small one (its rings, LFM2's cell); 4608 halves once
    assert gqa.fitted_decode_tile(9216) == 1024
    assert gqa.fitted_decode_tile(2048) == gqa.fitted_decode_tile(3072) == 512
    assert gqa.fitted_decode_tile(4608) == 512
    ring = jax.ShapeDtypeStruct((4, 4, 2048, D), jnp.bfloat16)
    assert float(gqa.rows_visited(ring, jnp.array([1, 512, 513, 2048]),
                                  "pallas")) == 512 * (1 + 1 + 2 + 4)


# ---- which lowering, and where it is stated --------------------------------


def _lowering(shape, dtype=jnp.bfloat16, cache_dtype=None, monkeypatch=None,
              on_tpu=False):
    s, heads, kv, t, d = shape
    if monkeypatch is not None:
        monkeypatch.setattr(gqa, "_on_tpu", lambda: on_tpu)
    cache = jax.ShapeDtypeStruct((s, kv, t, d), cache_dtype or dtype)
    args = (jax.ShapeDtypeStruct((s, heads, d), dtype), cache, cache,
            jax.ShapeDtypeStruct((s,), jnp.int32))
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda q, k, v, n: gqa.decode_attention(
            q, k, v, n, 0.1))(*args))
    assert chosen["gqa_decode"] == {gqa.decode_lowering(dtype, cache, cache)}
    return chosen["gqa_decode"], jaxpr


def test_cpu_default_is_the_xla_form():
    paths, jaxpr = _lowering((64, 32, 4, 2048, 128))
    assert paths == {"xla"} and "pallas_call" not in jaxpr


@pytest.mark.parametrize("shape,dtypes,want", [
    ((64, 32, 4, 2048, 128), (jnp.bfloat16, None), "pallas"),
    ((64, 32, 4, 9216, 128), (jnp.bfloat16, None), "pallas"),
    ((128, 32, 4, 3072, 128), (jnp.bfloat16, None), "pallas"),
    ((2, 8, 8, 512, 256), (jnp.float32, None), "pallas"),
    ((32, 32, 8, 2560, 64), (jnp.bfloat16, None), "xla"),    # Granite's d
    ((2, 8, 2, 384, 128), (jnp.bfloat16, None), "xla"),      # T off the tile
    ((2, 8, 2, 512, 128), (jnp.bfloat16, jnp.float32), "xla"),
    ((2, 8, 2, 512, 128), (jnp.float16, jnp.bfloat16), "xla"),
    ((3, 4, 2, 12, 8), (jnp.float32, None), "xla"),          # the tests' TINY
], ids=["trinity-ring", "trinity-grown", "lfm2", "f32-d256", "granite-d64",
        "T-384", "cache-f32", "two-halves", "tiny"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, dtypes, want):
    paths, jaxpr = _lowering(shape, dtypes[0], dtypes[1], monkeypatch,
                             on_tpu=True)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")
    # the kernel writes no (S, KV, G, T) score tensor
    s, heads, kv, t, _ = shape
    assert (f"f32[{s},{kv},{heads // kv},{t}]" in jaxpr) == (want == "xla")


def _block_lowering(shape, dtype=jnp.bfloat16, cache_dtype=None):
    """What a traced ``block_decode_attention`` of 2B = 8 tokens a slot
    notes, and its jaxpr."""
    s, heads, kv, t, d = shape
    cache = jax.ShapeDtypeStruct((s, kv, t, d), cache_dtype or dtype)
    own = jax.ShapeDtypeStruct((s, kv, 8, d), cache_dtype or dtype)
    args = (jax.ShapeDtypeStruct((s, 8, heads, d), dtype), cache, cache, own,
            own, jax.ShapeDtypeStruct((s,), jnp.int32),
            jax.ShapeDtypeStruct((s,), jnp.bool_))
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda q, k, v, kn, vn, n, lead: gqa.block_decode_attention(
                q, k, v, kn, vn, n, 0.1, lead))(*args))
    assert chosen["gqa_block_decode"] == {
        gqa.block_decode_lowering(dtype, cache, cache)}
    return chosen["gqa_block_decode"], jaxpr


@pytest.mark.parametrize("shape,dtypes,want", [
    ((64, 32, 4, 2560, 128), (jnp.bfloat16, None), "pallas"),
    ((2, 8, 8, 512, 256), (jnp.float32, None), "pallas"),
    ((64, 32, 4, 2560, 64), (jnp.bfloat16, None), "xla"),
    ((2, 8, 2, 2304, 128), (jnp.bfloat16, None), "xla"),     # 4.5 tiles
    ((2, 8, 2, 512, 128), (jnp.bfloat16, jnp.float32), "xla"),
    ((3, 4, 2, 48, 16), (jnp.float32, None), "xla"),         # the tests' TINY
], ids=["sdar-cell", "f32-d256", "d64", "T-2304", "cache-f32", "tiny"])
def test_on_tpu_the_shape_decides_for_the_block_form_too(monkeypatch, shape,
                                                         dtypes, want):
    """The one-query core's rule: a TPU backend, one float type, ``d`` on
    the lane tile, ``T`` a multiple of ``MIN_TILE``.  The kernel writes no
    ``(S, KV, G * B, T)`` score tensor, and both blocks' queries go in ONE
    call where the XLA form splits them."""
    assert _block_lowering(shape, *dtypes)[0] == {"xla"}        # the CPU
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    paths, jaxpr = _block_lowering(shape, *dtypes)
    assert paths == {want}
    assert jaxpr.count("pallas_call") == (want == "pallas")
    s, heads, kv, t, _ = shape
    assert (f"f32[{s},{kv},{heads // kv * 4},{t}]" in jaxpr) == (want == "xla")


def test_a_mesh_in_scope_keeps_the_block_forms_xla_too(monkeypatch, devices8):
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _block_lowering((64, 32, 4, 2560, 128))
    assert paths == {"xla"} and "pallas_call" not in jaxpr


def test_a_mesh_in_scope_keeps_the_xla_form(monkeypatch, devices8):
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering((64, 32, 4, 2048, 128),
                                 monkeypatch=monkeypatch, on_tpu=True)
    assert paths == {"xla"} and "pallas_call" not in jaxpr


# Trinity's tiny model at the published head width, a window of two (test)
# tiles under grown caches of four
TILE, WINDOW, MAX_LEN = 128, 256, 512
WIDE = dataclasses.replace(TINY, head_dim=D, sliding_window=WINDOW,
                           max_position_embeddings=1024)


def _force_kernel(monkeypatch):
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    monkeypatch.setattr(gqa, "DECODE_TILE", TILE)
    monkeypatch.setattr(gqa, "MIN_TILE", TILE)
    for name in ("pallas_decode_attention", "pallas_block_decode_attention"):
        monkeypatch.setattr(
            gqa, name, lambda *a, _f=getattr(gqa, name), **kw: _f(
                *a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("name,pos", [
    ("l0", [0, 127, 255, 256, 1000]),       # a ring: at, and past, its wrap
    ("l3", [0, 127, 128, 300, 511]),        # grown keys, to the last row
], ids=["ring", "grown"])
def test_kv_block_decode_through_the_kernel(monkeypatch, name, pos):
    """``KVBlock.decode`` at the published head width with the kernel
    forced (interpreter): one kernel call and no score tensor in the
    trace, the output that of the XLA form, the cache written before it is
    read (a slot at position 0 attends to the row this step wrote)."""
    params, _ = make(WIDE)
    block = tr.blocks_of(WIDE)[name]
    p = params["layers"][int(name[1])]["attn"]
    slots, rows = len(pos), block.rows(MAX_LEN)
    assert rows == (WINDOW if name == "l0" else MAX_LEN)
    x = jax.random.normal(jax.random.key(1), (slots, WIDE.hidden_size))
    shape = (slots, WIDE.num_key_value_heads, rows, D)
    cache = {"k": jax.random.normal(jax.random.key(2), shape),
             "v": jax.random.normal(jax.random.key(3), shape)}
    pos = jnp.array(pos)

    def run():
        # a fresh function per lowering: ``jax.jit`` would keep the trace
        with jax.default_matmul_precision("highest"):
            return block.decode(x, pos, cache, p)

    want, want_cache = run()
    _force_kernel(monkeypatch)
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda x, c: block.decode(x, pos, c, p))(x, cache))
    assert chosen["gqa_decode"] == {"pallas"}
    assert jaxpr.count("pallas_call") == 1      # row_write stays a scatter
    group = WIDE.num_attention_heads // WIDE.num_key_value_heads
    assert f"f32[{slots},{WIDE.num_key_value_heads},{group},{rows}]" not in jaxpr
    got, got_cache = run()
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[leaf]),
                                      np.asarray(want_cache[leaf]))
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < 2e-5


# SDAR's tiny model at the published head width: blocks of 4 over grown
# caches of four (test) tiles
SDAR_WIDE = dataclasses.replace(sdar_tiny.TINY, head_dim=D,
                                max_position_embeddings=MAX_LEN)


@pytest.mark.parametrize("tokens,queries", [(8, None), (8, 4), (4, None)],
                         ids=["two-blocks", "last-layer", "one-block"])
def test_kv_block_decode_block_through_the_kernel(monkeypatch, tokens,
                                                  queries):
    """``KVBlock.decode_block`` at the published head width with the kernel
    forced (interpreter): ONE kernel call for the core (the XLA form makes
    a pass a query block) and no score tensor in the trace, the output that
    of the XLA form, the same rows written — a pending block's where one
    rides, none where not."""
    b = sdar_tiny.BLOCK
    params, _ = sdar_tiny.make(SDAR_WIDE)
    block = sdar.blocks_of(SDAR_WIDE)["l0"]
    p = params["layers"][0]["attn"]
    # cursors: a first block, under / on / past a tile's edge, the last block
    pos0 = jnp.array([tokens - b, 124, 128, 132, MAX_LEN - b])
    commit = jnp.array([True, False, True, True, False])
    slots = len(pos0)
    x = jax.random.normal(jax.random.key(1),
                          (slots, tokens, SDAR_WIDE.hidden_size))
    shape = (slots, SDAR_WIDE.num_key_value_heads, MAX_LEN, D)
    cache = {"k": jax.random.normal(jax.random.key(2), shape),
             "v": jax.random.normal(jax.random.key(3), shape)}

    def run():
        with jax.default_matmul_precision("highest"):
            return block.decode_block(x, pos0, cache, p, commit, queries)

    want, want_cache = run()
    assert want.shape == (slots, queries or tokens, SDAR_WIDE.hidden_size)
    c = SDAR_WIDE
    rows = c.num_attention_heads // c.num_key_value_heads * b
    scores = f"f32[{slots},{c.num_key_value_heads},{rows},{MAX_LEN}]"

    def trace():
        with record_lowerings() as chosen:
            jaxpr = str(jax.make_jaxpr(lambda x, c: block.decode_block(
                x, pos0, c, p, commit, queries))(x, cache))
        return chosen["gqa_block_decode"], jaxpr

    paths, jaxpr = trace()
    assert paths == {"xla"} and scores in jaxpr
    _force_kernel(monkeypatch)
    paths, jaxpr = trace()
    assert paths == {"pallas"} and scores not in jaxpr
    assert jaxpr.count("pallas_call") == 1      # the write stays a scatter
    got, got_cache = run()
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[leaf]),
                                      np.asarray(want_cache[leaf]))
        wrote = (np.asarray(got_cache[leaf]) != np.asarray(cache[leaf])).any(
            axis=(1, 3))
        first = np.asarray(pos0) - (tokens - b)
        for i in range(slots):
            assert wrote[i].tolist() == [
                bool(commit[i]) and first[i] <= r < first[i] + b
                for r in range(MAX_LEN)]
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("form", ["one-query", "block"])
def test_decode_stats_follow_the_lowering(monkeypatch, form):
    """``attn.*_rows_read``: every row of every slot under the XLA form;
    under the kernel whole tiles up to each slot's count — a ring's count
    stops at its rows.  A step of B queries a slot follows ITS core's
    lowering: whole tiles up to the rows committed before the forward, less
    a pending block that rides it; a slot with nothing committed reads no
    tile; with no live row the counter is 0."""
    blocks = tr.blocks_of(WIDE)
    slots = 3
    caches = {n: b.init_cache(slots, MAX_LEN, jnp.float32)
              for n, b in blocks.items()}
    full = {n: b for n, b in blocks.items() if b.window is None}
    live, dead = jnp.array([True, True, True]), jnp.zeros(3, bool)
    if form == "one-query":
        pos = jnp.array([0, 127, 300])

        def read(live=live):
            return kv_blocks.decode_stats(blocks, caches, pos, live)

        want = (1 + 1 + 3) * TILE
    else:
        # committed rows 0, 128 (a tile, whole) and 300 + 4 less the
        # pending block that rides the forward
        pos0 = jnp.array([0, 128, 304])
        riding = jnp.array([False, False, True])

        def read(live=live):
            return kv_blocks.block_decode_stats(full, caches, pos0, live, 4,
                                                riding)

        want = (0 + 1 + 3) * TILE
    stats = read()
    assert float(stats["attn.full_rows_read"]) == slots * MAX_LEN
    if form == "one-query":
        assert float(stats["attn.window_rows_read"]) == slots * WINDOW
    _force_kernel(monkeypatch)
    stats = read()
    assert float(stats["attn.full_rows_read"]) == want
    if form == "one-query":
        assert float(stats["attn.window_rows_read"]) == (1 + 1 + 2) * TILE
    else:
        # B query rows a live slot and B more where a pending block rides
        assert float(stats["attn.decode_rows"]) == 4 * (3 + 1)
        assert float(stats["attn.context_tokens"]) == 0 + 128 + 300
        assert float(kv_blocks.block_decode_stats(
            full, caches, pos0, live, 4)["attn.full_rows_read"]) == (
                0 + 1 + 3) * TILE
    assert float(read(dead)["attn.full_rows_read"]) == 0


def test_cpu_notes_xla_and_the_engine_states_it():
    """``status()["gqa_decode"]`` is ``None`` before the chunk program is
    traced, then what the trace chose — the XLA form on the CPU, under
    which a step reads every row of every slot."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW

    params, policy = make()
    slots, max_len = SLOTS_PER_ADMIT_ROW, 32
    eng = ServingEngine(TINY, params, policy=policy, num_slots=slots,
                        chunk_size=4, max_len=max_len)
    assert eng.status()["gqa_decode"] is None
    eng.submit(Request(uid=0, tokens=[3, 4, 5], max_new_tokens=3,
                       temperature=0.0, seed=1))
    (done,) = eng.run_until_idle(max_chunks=10)
    assert done.uid == 0
    status = eng.status()
    assert status["gqa_decode"] == "xla"
    assert status["gqa_block_decode"] is None and status["mla_decode"] is None
    stats = eng.model_stats
    steps = stats["attn.decode_rows"]       # one live row a step
    assert steps > 0
    assert stats["attn.full_rows_read"] == steps * slots * max_len
    assert stats["attn.window_rows_read"] == steps * slots * min(
        TINY.sliding_window, max_len)
