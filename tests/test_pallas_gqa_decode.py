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
counter that follows it.  Since PR 55 the one-query kernel takes keys of
another width than the values (MiMo's full layers: 192 beside 128): the
same cases at two widths, and the two kernels' jaxpr text at ONE width held
to what it was before the kernel took two."""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models import kv as kv_blocks
from progen_tpu.models import mimo_v2 as mm
from progen_tpu.models import sdar
from progen_tpu.models import trinity as tr
from progen_tpu.ops import gqa
from progen_tpu.ops.lowering import record_lowerings
from tests import mimo_v2_tiny, sdar_tiny
from tests.trinity_tiny import TINY, make

# ``program_head``: the sha256 head of a traced body's jaxpr text
_TOOL = Path(__file__).resolve().parents[1] / "tools" / "program_hash.py"
_spec = importlib.util.spec_from_file_location("program_hash", _TOOL)
program_hash = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(program_hash)

D, SLOTS = 128, 4
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(heads, kv, t, dtype, seed=0, slots=SLOTS, d=D, dv=None):
    """``q (S, H, d)``, ``k (S, KV, T, d)``, ``v (S, KV, T, dv)`` (``dv``
    defaults to ``d``) with O(1) logits and a spread that makes the
    softmax matter."""
    ks = jax.random.split(jax.random.key(seed), 3)

    def normal(k, shape, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * gain).astype(dtype)

    return (normal(ks[0], (slots, heads, d)),
            normal(ks[1], (slots, kv, t, d), 3.0),
            normal(ks[2], (slots, kv, t, dv or d)))


def _kernel(q, k, v, counts, **kw):
    with jax.default_matmul_precision("highest"):
        return gqa.pallas_decode_attention(
            q, k, v, jnp.asarray(counts, jnp.int32), q.shape[-1] ** -0.5,
            interpret=True, **kw)


def _xla(q, k, v, counts):
    with jax.default_matmul_precision("highest"):
        return gqa.xla_decode_attention(
            q, k, v, jnp.asarray(counts, jnp.int32), q.shape[-1] ** -0.5)


def _dense(q, k, v, counts):
    """The softmax over each slot's first ``counts`` rows in float64."""
    q, k, v = (np.asarray(a.astype(jnp.float32), np.float64)
               for a in (q, k, v))
    s, heads, d = q.shape
    group, dv = heads // k.shape[1], v.shape[-1]
    out = np.zeros((s, heads, dv))
    for si, n in enumerate(counts):
        for h in range(heads):
            logits = k[si, h // group, :n] @ q[si, h] * d ** -0.5
            p = np.exp(logits - logits.max())
            out[si, h] = (p / p.sum()) @ v[si, h // group, :n]
    return out.reshape(s, heads * dv)


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
@pytest.mark.parametrize("heads,kv,t,bk,dtype,widths", [
    (32, 4, 512, 128, "float32", (D, D)),
    (32, 4, 1024, 256, "bfloat16", (D, D)),
    (8, 4, 512, 256, "bfloat16", (D, D)),
    (4, 4, 1024, 512, "float32", (D, D)),
    (4, 4, 1024, None, "bfloat16", (D, D)),
    # keys of another width than the values: MiMo's full layers (64 query
    # heads over 4 key/value heads, 192 beside 128), and one more pair
    (64, 4, 512, 128, "bfloat16", (192, 128)),
    (8, 4, 512, 256, "float32", (192, 128)),
    (8, 2, 512, 128, "bfloat16", (256, 128)),
    (4, 4, 512, 256, "float32", (256, 128))], ids=lambda v: str(v))
def test_kernel_equals_the_xla_form_and_a_dense_softmax(heads, kv, t, bk,
                                                        dtype, widths, case):
    d, dv = widths
    q, k, v = _operands(heads, kv, t, jnp.dtype(dtype), d=d, dv=dv)
    tile = bk or gqa.fitted_decode_tile(t)
    # (one tile covering T folds some cases onto 1 and T)
    counts = [min(max(n, 1), t) for n in COUNTS[case](t, tile)]
    got = _kernel(q, k, v, counts, block_k=bk)
    assert got.shape == (SLOTS, heads * dv) and got.dtype == jnp.dtype(dtype)
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


@pytest.mark.parametrize("widths", [(D, D), (192, 128)], ids=str)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_past_a_count_are_neither_seen_nor_read(dtype, widths):
    """Junk past a slot's count inside the tile its count crosses changes
    no bit; a tile wholly past it is not visited at all (NaN there would
    show in the running maximum)."""
    t, bk, counts = 512, 128, [1, 130, 256, 511]
    q, k, v = _operands(32, 4, t, jnp.dtype(dtype), d=widths[0],
                        dv=widths[1])
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
              on_tpu=False, dv=None, sink=False):
    s, heads, kv, t, d = shape
    if monkeypatch is not None:
        monkeypatch.setattr(gqa, "_on_tpu", lambda: on_tpu)
    sd = jax.ShapeDtypeStruct
    keys = sd((s, kv, t, d), cache_dtype or dtype)
    values = sd((s, kv, t, dv or d), cache_dtype or dtype)
    args = (sd((s, heads, d), dtype), keys, values, sd((s,), jnp.int32),
            sd((heads,), jnp.float32))
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda q, k, v, n, sk: gqa.decode_attention(
                q, k, v, n, 0.1, sk if sink else None))(*args))
    assert chosen["gqa_decode"] == {
        gqa.decode_lowering(dtype, keys, values, sink)}
    return chosen["gqa_decode"], jaxpr


def test_cpu_default_is_the_xla_form():
    paths, jaxpr = _lowering((64, 32, 4, 2048, 128))
    assert paths == {"xla"} and "pallas_call" not in jaxpr


@pytest.mark.parametrize("shape,dtypes,want,more", [
    ((64, 32, 4, 2048, 128), (jnp.bfloat16, None), "pallas", {}),
    ((64, 32, 4, 9216, 128), (jnp.bfloat16, None), "pallas", {}),
    ((128, 32, 4, 3072, 128), (jnp.bfloat16, None), "pallas", {}),
    ((2, 8, 8, 512, 256), (jnp.float32, None), "pallas", {}),
    ((32, 32, 8, 2560, 64), (jnp.bfloat16, None), "xla", {}),  # Granite's d
    ((2, 8, 2, 384, 128), (jnp.bfloat16, None), "xla", {}),    # T off the tile
    ((2, 8, 2, 512, 128), (jnp.bfloat16, jnp.float32), "xla", {}),
    ((2, 8, 2, 512, 128), (jnp.float16, jnp.bfloat16), "xla", {}),
    ((3, 4, 2, 12, 8), (jnp.float32, None), "xla", {}),    # the tests' TINY
    # two widths: MiMo's full layers take the kernel; its sliding layers'
    # sink and their 128-row ring, each alone, keep the XLA form, and so do
    # widths that are no whole half lane tiles or keys under a tile
    ((16, 64, 4, 17408, 192), (jnp.bfloat16, None), "pallas", {"dv": 128}),
    ((2, 8, 2, 512, 256), (jnp.float32, None), "pallas", {"dv": 128}),
    ((16, 64, 8, 512, 192), (jnp.bfloat16, None), "xla",
     {"dv": 128, "sink": True}),
    ((16, 64, 8, 128, 192), (jnp.bfloat16, None), "xla", {"dv": 128}),
    ((2, 8, 2, 512, 192), (jnp.bfloat16, jnp.float32), "xla", {"dv": 128}),
    ((2, 8, 2, 512, 192), (jnp.bfloat16, None), "xla", {"dv": 96}),
    ((2, 8, 2, 512, 64), (jnp.bfloat16, None), "xla", {"dv": 128}),
    ((2, 8, 2, 512, 192), (jnp.bfloat16, None), "xla", {}),    # 1.5 tiles
], ids=["trinity-ring", "trinity-grown", "lfm2", "f32-d256", "granite-d64",
        "T-384", "cache-f32", "two-halves", "tiny", "mimo-full-192-128",
        "f32-256-128", "192-128-sink", "192-128-T-128", "192-128-cache-f32",
        "192-96", "64-128", "192-192"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, dtypes, want, more):
    paths, jaxpr = _lowering(shape, dtypes[0], dtypes[1], monkeypatch,
                             on_tpu=True, **more)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")
    # the kernel writes no (S, KV, G, T) score tensor (a sink's column makes
    # the XLA form's one wider)
    s, heads, kv, t, _ = shape
    scores = f"f32[{s},{kv},{heads // kv},{t + bool(more.get('sink'))}]"
    assert (scores in jaxpr) == (want == "xla")


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


@pytest.mark.parametrize("shape,more", [
    ((64, 32, 4, 2048, 128), {}), ((16, 64, 4, 17408, 192), {"dv": 128})],
    ids=["one-width", "two-widths"])
def test_a_mesh_in_scope_keeps_the_xla_form(monkeypatch, devices8, shape,
                                            more):
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering(shape, monkeypatch=monkeypatch, on_tpu=True,
                                 **more)
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


# MiMo's tiny model with its FULL layers at the published head widths (keys
# 192 beside values of 128; two key/value heads): the sliding layers keep
# their tiny widths, their sink and a window of 4
MIMO_WIDE = dataclasses.replace(mimo_v2_tiny.TINY, head_dim=192,
                                v_head_dim=128, num_key_value_heads=2,
                                max_position_embeddings=1024)
FAMILIES = {"trinity": (WIDE, make, tr), "mimo": (MIMO_WIDE,
                                                  mimo_v2_tiny.make, mm)}


@pytest.mark.parametrize("family,name,pos", [
    ("trinity", "l0", [0, 127, 255, 256, 1000]),    # a ring: at, past its wrap
    ("trinity", "l3", [0, 127, 128, 300, 511]),     # grown keys, to the end
    ("mimo", "l5", [0, 127, 128, 300, 511]),        # grown keys of two widths
], ids=["ring", "grown", "mimo-full"])
def test_kv_block_decode_through_the_kernel(monkeypatch, family, name, pos):
    """``KVBlock.decode`` at the published head width with the kernel
    forced (interpreter): one kernel call and no score tensor in the
    trace, the output that of the XLA form, the cache written before it is
    read (a slot at position 0 attends to the row this step wrote)."""
    config, make_params, module = FAMILIES[family]
    params, _ = make_params(config)
    block = module.blocks_of(config)[name]
    p = params["layers"][int(name[1])]["attn"]
    slots, rows = len(pos), block.rows(MAX_LEN)
    assert rows == (WINDOW if name == "l0" else MAX_LEN)
    assert (block.head_dim, block.v_head_dim) == (
        (D, D) if family == "trinity" else (192, 128))
    x = jax.random.normal(jax.random.key(1), (slots, config.hidden_size))
    shape = (slots, block.kv_heads, rows)
    cache = {"k": jax.random.normal(jax.random.key(2),
                                    shape + (block.head_dim,)),
             "v": jax.random.normal(jax.random.key(3),
                                    shape + (block.v_head_dim,))}
    pos = jnp.array(pos)

    def run():
        # one program a call, from a fresh function per lowering (``jax.jit``
        # would keep the trace of a function it has seen): op by op the
        # interpreter takes seconds a kernel
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda: block.decode(x, pos, cache, p))()

    want, want_cache = run()
    _force_kernel(monkeypatch)
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda x, c: block.decode(x, pos, c, p))(x, cache))
    assert chosen["gqa_decode"] == {"pallas"}
    assert jaxpr.count("pallas_call") == 1      # row_write stays a scatter
    group = config.num_attention_heads // block.kv_heads
    assert f"f32[{slots},{block.kv_heads},{group},{rows}]" not in jaxpr
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

    def run():      # one program a call, a fresh function per lowering
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda: block.decode_block(
                x, pos0, cache, p, commit, queries))()

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


def test_decode_stats_follow_the_lowering_at_two_widths(monkeypatch):
    """MiMo's two kinds in one slot: under the forced kernel the FULL
    layers' counter (keys 192 beside values of 128, no sink) reads whole
    tiles up to each slot's count, while the sliding layers' — a sink, a
    ring under a tile — keeps the XLA form's every row, and the byte gauge
    prices each kind's rows at its own two widths."""
    blocks = mm.blocks_of(MIMO_WIDE)
    slots = 3
    caches = {n: b.init_cache(slots, MAX_LEN, jnp.float32)
              for n, b in blocks.items()}
    pos, live = jnp.array([0, 127, 300]), jnp.ones(3, bool)
    ring = mimo_v2_tiny.WINDOW

    def read():
        return {k: float(v) for k, v in kv_blocks.decode_stats(
            blocks, caches, pos, live).items()}

    stats = read()
    assert stats["attn.full_rows_read"] == slots * MAX_LEN
    assert stats["attn.window_rows_read"] == slots * ring
    _force_kernel(monkeypatch)
    stats = read()
    assert stats["attn.full_rows_read"] == (1 + 1 + 3) * TILE
    assert stats["attn.window_rows_read"] == slots * ring
    full, sliding = blocks["l0"], blocks["l1"]
    assert full.row_bytes(jnp.bfloat16) == 2 * (192 + 128) * 2
    gauges = kv_blocks.byte_gauges(blocks, stats, jnp.bfloat16)
    assert gauges["attn.full_bytes_read"] == (
        5 * TILE * 2 * full.row_bytes(jnp.bfloat16))
    assert gauges["attn.window_bytes_read"] == (
        slots * ring * 5 * sliding.row_bytes(jnp.bfloat16))


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


# sha256 heads of the two kernel lowerings' jaxpr text at ONE head width,
# taken on PR 54's tree (6552985) before ``gqa_decode_fwd`` took two widths:
# the four families whose keys and values are one width trace what they
# traced, letter for letter (``tests/golden/programs.json`` is the CPU's
# trace and holds no Pallas lowering)
KERNEL_TEXT = {
    "trinity-ring": ((64, 32, 4, 2048), None, "1158f06fb9caf702"),
    "trinity-grown": ((64, 32, 4, 9216), None, "26d60c9fb0c56cca"),
    "lfm2": ((128, 32, 4, 3072), None, "0a552ce617667544"),
    "sdar-two-blocks": ((64, 32, 4, 2560), (8, 8), "bb479028c49a9045"),
    "sdar-last-layer": ((64, 32, 4, 2560), (4, 8), "fea1aa10e8f105a1"),
    "sdar-one-block": ((64, 32, 4, 2560), (4, 4), "3dfbd1edd830bd8d"),
}


@pytest.mark.parametrize("case", list(KERNEL_TEXT))
def test_at_one_width_the_kernels_trace_the_text_they_traced(case):
    """The cells' own shapes, bfloat16, heads of 128, interpreter; a block
    call by its ``(queries, tokens)`` a slot, two blocks where 8 tokens."""
    (s, heads, kv, t), block, want = KERNEL_TEXT[case]
    sd = jax.ShapeDtypeStruct
    bf, counts = jnp.bfloat16, sd((s,), jnp.int32)
    cache = sd((s, kv, t, D), bf)
    if block is None:
        head = program_hash.program_head(
            lambda q, k, v, n: gqa.pallas_decode_attention(
                q, k, v, n, 0.1, interpret=True),
            (sd((s, heads, D), bf), cache, cache, counts))
    else:
        m, n = block
        own = sd((s, kv, n, D), bf)
        lead = (sd((s,), jnp.bool_),) if n == 8 else ()
        head = program_hash.program_head(
            lambda q, k, v, kn, vn, c, *lead:
            gqa.pallas_block_decode_attention(
                q, k, v, kn, vn, c, 0.1, *lead, interpret=True),
            (sd((s, m, heads, D), bf), cache, cache, own, own, counts,
             *lead))
    assert head == want


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


def test_mimo_engine_states_both_lowerings_and_serves_the_same_tokens(
        monkeypatch):
    """MiMo's engine with the kernel forced (interpreter) at the published
    FULL head widths: the chunk program's full layers take the kernel and
    its rings the XLA form (``status()["gqa_decode"]`` names both), the
    greedy tokens are the XLA engine's, and the full layers' counter stops
    at whole tiles where the XLA engine's reads every row of every slot."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW

    params, policy = mimo_v2_tiny.make(MIMO_WIDE)
    slots, max_len, prime = SLOTS_PER_ADMIT_ROW, 2 * TILE, list(range(3, 9))

    def serve():
        eng = ServingEngine(MIMO_WIDE, params, policy=policy,
                            num_slots=slots, chunk_size=4, max_len=max_len)
        eng.submit(Request(uid=0, tokens=prime, max_new_tokens=5,
                           temperature=0.0, seed=1))
        (done,) = eng.run_until_idle(max_chunks=10)
        return list(done.tokens), eng.status(), eng.model_stats

    want, status, stats = serve()
    assert status["gqa_decode"] == "xla"
    steps = stats["attn.decode_rows"]       # one live row a step
    assert steps > 0
    assert stats["attn.full_rows_read"] == steps * slots * max_len
    _force_kernel(monkeypatch)
    got, status, stats = serve()
    assert status["gqa_decode"] == "pallas+xla"
    assert status["gqa_prefill"] == "xla"
    assert got == want
    # every slot's count is under a tile: the live row's context, and the
    # idle slots' one row
    assert stats["attn.full_rows_read"] == stats["attn.decode_rows"] * (
        slots * TILE)
    assert stats["attn.window_rows_read"] == stats["attn.decode_rows"] * (
        slots * mimo_v2_tiny.WINDOW)
