"""``ops/mla_decode.py``: the attention core of an absorbed MLA decode
step, two lowerings, one contract.  The Pallas kernel (``mla_decode_fwd``)
runs under the interpreter here, at the latent widths both families
publish (512 + 64) and their head counts (64, 128): against the XLA form
for lengths of 1, on a tile's edge, one either side of it and at ``T``;
bit-equal whatever the cache holds past a length; tiles past a length
never read; the rows each lowering reads; and the choice of lowering from
backend, mesh and shape, as ``status()`` shows it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models import latent
from progen_tpu.ops import mla_decode as md
from progen_tpu.ops.lowering import record_lowerings
from tests.longcat_tiny import TINY, make

RANK, ROPE = 512, 64
LATENT = RANK + ROPE
SCALE = 192 ** -0.5
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(slots, heads, max_len, dtype, seed=0):
    """``q_cat`` and ``cache`` as ``decode_attention`` takes them, O(1)
    logits with a spread that makes the softmax matter."""
    kq, kc = jax.random.split(jax.random.key(seed))
    q = jax.random.normal(kq, (slots, heads, LATENT), jnp.float32) * 0.5
    c = jax.random.normal(kc, (slots, max_len, LATENT), jnp.float32)
    return q.astype(dtype), c.astype(dtype)


def _kernel(q, c, lengths, **kw):
    with jax.default_matmul_precision("highest"):
        return md.pallas_decode_attention(
            q, c, jnp.asarray(lengths, jnp.int32), RANK, SCALE,
            interpret=True, **kw)


def _xla(q, c, lengths):
    with jax.default_matmul_precision("highest"):
        return md.xla_decode_attention(
            q, c, jnp.asarray(lengths, jnp.int32), RANK, SCALE)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


# lengths of the four slots by what they do in a tiling of ``T`` by ``bk``;
# every case keeps a slot of length 1 beside longer ones
LENGTHS = {
    "all-1": lambda t, bk: [1, 1, 1, 1],
    "a-tiles-multiple": lambda t, bk: [bk, 1, t - bk, 2 * bk],
    "a-multiple-plus-1": lambda t, bk: [bk + 1, 1, t - bk + 1, 2],
    "a-multiple-minus-1": lambda t, bk: [bk - 1, t - 1, 1, 2 * bk - 1],
    "at-T": lambda t, bk: [t, 1, t, t],
}


@pytest.mark.parametrize("case", list(LENGTHS))
@pytest.mark.parametrize("heads,max_len,bk,dtype", [
    (64, 512, 128, "float32"), (64, 1024, 256, "bfloat16"),
    (128, 512, 256, "bfloat16"), (128, 1024, None, "float32"),
    (128, 1024, 1024, "bfloat16")], ids=lambda v: str(v))
def test_kernel_equals_the_xla_form(heads, max_len, bk, dtype, case):
    q, c = _operands(4, heads, max_len, jnp.dtype(dtype))
    tile = bk or md.fitted_tile(max_len)
    # (one tile covering T folds some cases onto 1 and T)
    lengths = [min(max(n, 1), max_len) for n in LENGTHS[case](max_len, tile)]
    got = _f32(_kernel(q, c, lengths, block_k=bk))
    want = _f32(_xla(q, c, lengths))
    assert got.shape == (4, heads, RANK) and got.dtype == np.float32
    assert np.isfinite(got).all()
    assert float(np.abs(want).max()) > 1.0      # not a vacuous bound
    assert float(np.abs(got - want).max()) < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_past_a_length_are_neither_seen_nor_read(dtype):
    """Junk past a slot's length inside the tile its length crosses changes
    no bit; a tile wholly past it is not visited at all (NaN there would
    show in the running max)."""
    max_len, bk, lengths = 512, 128, [1, 130, 256, 511]
    q, c = _operands(4, 64, max_len, jnp.dtype(dtype))
    got = _kernel(q, c, lengths, block_k=bk)
    t = jnp.arange(max_len)[None, :, None]
    n = jnp.asarray(lengths)[:, None, None]
    junk = jnp.where(t >= n, jnp.asarray(37.5, c.dtype), c)
    junk = jnp.where(t >= -(-n // bk) * bk, jnp.nan, junk)
    again = _kernel(q, junk, lengths, block_k=bk)
    np.testing.assert_array_equal(_f32(got), _f32(again))
    assert np.isfinite(_f32(again)).all()


def test_rows_visited_counts_whole_tiles_under_the_kernel(monkeypatch):
    q, c = jnp.bfloat16, jax.ShapeDtypeStruct((4, 2048, LATENT), jnp.bfloat16)
    lengths = jnp.array([1, 512, 513, 2048])
    assert float(md.rows_visited(q, c, lengths, RANK)) == 4 * 2048
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    assert md.fitted_tile(2048) == md.TILE == 512
    assert float(md.rows_visited(q, c, lengths, RANK)) == 512 * (1 + 1 + 2 + 4)


# ---- which lowering, and where it is stated --------------------------------


def _lowering(slots, heads, max_len, latent_width, rank, dtype=jnp.bfloat16,
              cache_dtype=None, monkeypatch=None, on_tpu=False):
    if monkeypatch is not None:
        monkeypatch.setattr(md, "_on_tpu", lambda: on_tpu)
    args = (jax.ShapeDtypeStruct((slots, heads, latent_width), dtype),
            jax.ShapeDtypeStruct((slots, max_len, latent_width),
                                 cache_dtype or dtype),
            jax.ShapeDtypeStruct((slots,), jnp.int32))
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda q, c, n: md.decode_attention(q, c, n, rank, SCALE))(*args))
    return chosen["mla_decode"], jaxpr


@pytest.mark.parametrize("shape,dtypes,want", [
    ((64, 128, 3072, 576, 512), (jnp.bfloat16, None), "pallas"),
    ((32, 64, 4096, 576, 512), (jnp.bfloat16, None), "pallas"),
    ((2, 8, 384, 320, 256), (jnp.float32, None), "pallas"),
    ((2, 8, 200, 576, 512), (jnp.bfloat16, None), "xla"),   # T off the tile
    ((2, 8, 512, 160, 96), (jnp.bfloat16, None), "xla"),    # rank
    ((2, 8, 512, 576, 512), (jnp.bfloat16, jnp.float32), "xla"),
    ((2, 4, 32, 24, 16), (jnp.float32, None), "xla"),       # the tests' TINY
], ids=["dsv2", "longcat", "f32-T384", "T-200", "rank-96", "cache-f32",
        "tiny"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, dtypes, want):
    paths, jaxpr = _lowering(*shape, dtypes[0], dtypes[1], monkeypatch,
                             on_tpu=True)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")
    # the kernel writes no (S, H, T) score tensor
    s, h, t = shape[:3]
    assert (f"f32[{s},{h},{t}]" in jaxpr) == (want == "xla")


def test_a_mesh_in_scope_keeps_the_xla_form(monkeypatch, devices8):
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering(32, 64, 4096, 576, 512,
                                 monkeypatch=monkeypatch, on_tpu=True)
    assert paths == {"xla"} and "pallas_call" not in jaxpr


def test_mla_decode_through_the_kernel(monkeypatch):
    """``latent.mla_decode`` at the published latent widths with the kernel
    forced (interpreter): one kernel call and no score tensor in the trace,
    the output that of the XLA form, the cache written before it is read
    (a slot at position 0 attends to the row this step wrote)."""
    import dataclasses

    wide = dataclasses.replace(
        TINY, kv_lora_rank=128, qk_rope_head_dim=64, qk_nope_head_dim=128,
        v_head_dim=128, num_attention_heads=8)
    params, _ = make(wide)
    p = params["layers"][0]["attn"][0]
    slots, max_len = 3, 256
    x = jax.random.normal(jax.random.key(1), (slots, wide.hidden_size))
    cache = jax.random.normal(jax.random.key(2),
                              (slots, max_len, wide.latent_width))
    pos = jnp.array([0, 128, 200])

    def run():
        # a fresh function per lowering: ``jax.jit`` would keep the trace
        with jax.default_matmul_precision("highest"):
            return latent.mla_decode(x, pos, cache, p, wide)

    want, want_cache = run()
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        md, "pallas_decode_attention",
        lambda *a, _f=md.pallas_decode_attention, **kw: _f(
            *a, **{**kw, "interpret": True}))
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda x, c: latent.mla_decode(x, pos, c, p, wide))(x, cache))
    assert chosen["mla_decode"] == {"pallas"}
    assert jaxpr.count("pallas_call") == 1      # row_write stays a scatter
    assert f"f32[{slots},{wide.num_attention_heads},{max_len}]" not in jaxpr
    got, got_cache = run()
    np.testing.assert_array_equal(np.asarray(got_cache),
                                  np.asarray(want_cache))
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_cpu_notes_xla_and_the_engine_states_it():
    """On the CPU ``latent.mla_decode`` takes the XLA form and says so;
    ``status()["mla_decode"]`` is ``None`` before the chunk program is
    traced, then what the trace chose; the counter of cache rows read is
    published with the others: the whole cache a step under this form."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.observe.metrics import get_registry

    params, policy = make()
    p = params["layers"][0]["attn"][0]
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda x, c: latent.mla_decode(
            x, jnp.zeros((2,), jnp.int32), c, p, TINY))(
                jnp.zeros((2, TINY.hidden_size)),
                jnp.zeros((2, 32, TINY.latent_width))))
    assert chosen["mla_decode"] == {"xla"} and "pallas_call" not in jaxpr

    slots, max_len = SLOTS_PER_ADMIT_ROW, 32
    eng = ServingEngine(TINY, params, policy=policy, num_slots=slots,
                        chunk_size=4, max_len=max_len)
    assert eng.status()["mla_decode"] is None
    eng.submit(Request(uid=0, tokens=[3, 4, 5], max_new_tokens=3,
                       temperature=0.0, seed=1))
    (done,) = eng.run_until_idle(max_chunks=10)
    assert done.uid == 0
    assert eng.status()["mla_decode"] == "xla"
    snap = get_registry().snapshot()
    rows, steps = (snap[k]["value"] for k in ("mla.cache_rows_read",
                                              "mla.decode_rows"))
    assert steps > 0 and rows == steps * slots * max_len
