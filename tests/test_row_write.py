"""``ops/row_write.py``: one new row per slot, three lowerings, one result.

The kernel runs under the Pallas interpreter here (the chip's compiler
takes it at real widths in ``test_chip_compile.py``); every case compares
it bit for bit with the scatter it replaces.  The default CPU path must
stay the scatter: tier-1 does not run an interpreted kernel per cache write.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops import row_write
from progen_tpu.ops.row_write import (
    pallas_write_rows,
    sublane_tile,
    write_rows,
)

# (name, cache shape, axis of the per-slot view)
LAYOUTS = [
    ("ring", (8, 2, 64, 128), 1),      # k / v rings: (B, h, ring, d)
    ("gate", (8, 48, 2048), 0),        # SGU gate cache: (B, n, hidden/2)
    ("latent", (8, 48, 576), 0),       # LongCat latent cache, 576 lanes
]
DTYPES = [jnp.bfloat16, jnp.float32, jnp.int32]


def _fill(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-1000, 1000, shape), dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _update_shape(shape, axis):
    return shape[:axis + 1] + shape[axis + 2:]


def _indices(rows, tile, batch):
    """0, T-1, T, the last row, and two slots at one index."""
    idx = [0, tile - 1, tile, rows - 1, 5, 5, rows // 2, tile + 1]
    return jnp.asarray((idx * batch)[:batch], jnp.int32)


def _bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("name,shape,axis", LAYOUTS,
                         ids=[c[0] for c in LAYOUTS])
def test_kernel_equals_scatter_bit_for_bit(name, shape, axis, dtype):
    cache = _fill(shape, dtype, 0)
    update = _fill(_update_shape(shape, axis), dtype, 1)
    idx = _indices(shape[axis + 1], sublane_tile(dtype), shape[0])
    want = row_write._scatter_rows(cache, update, idx, axis)
    (got,) = pallas_write_rows((cache,), (update,), idx, interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("name,shape,axis", LAYOUTS,
                         ids=[c[0] for c in LAYOUTS])
def test_kernel_leaves_other_rows_and_slots_alone(name, shape, axis):
    dtype = jnp.bfloat16
    cache = _fill(shape, dtype, 2)
    update = _fill(_update_shape(shape, axis), dtype, 3)
    idx = _indices(shape[axis + 1], sublane_tile(dtype), shape[0])
    (got,) = pallas_write_rows((cache,), (update,), idx, interpret=True)
    got, cache = np.asarray(got, np.float32), np.asarray(cache, np.float32)
    update = np.asarray(update, np.float32)
    for b, i in enumerate(np.asarray(idx)):
        np.testing.assert_array_equal(
            np.take(got[b], i, axis=axis), update[b])
        np.testing.assert_array_equal(
            np.delete(got[b], i, axis=axis), np.delete(cache[b], i, axis=axis))


def test_kernel_writes_k_and_v_in_one_call():
    shape, dtype = (4, 2, 32, 128), jnp.bfloat16
    k, v = _fill(shape, dtype, 4), _fill(shape, dtype, 5)
    uk, uv = (_fill(_update_shape(shape, 1), dtype, s) for s in (6, 7))
    idx = jnp.asarray([0, 31, 16, 15], jnp.int32)
    got_k, got_v = pallas_write_rows((k, v), (uk, uv), idx, interpret=True)
    np.testing.assert_array_equal(
        _bits(got_k), _bits(row_write._scatter_rows(k, uk, idx, 1)))
    np.testing.assert_array_equal(
        _bits(got_v), _bits(row_write._scatter_rows(v, uv, idx, 1)))
    jaxpr = str(jax.make_jaxpr(
        lambda *a: pallas_write_rows(a[:2], a[2:4], a[4], interpret=True)
    )(k, v, uk, uv, idx))
    assert jaxpr.count("pallas_call") == 1


@pytest.mark.parametrize("idx", [[-1, -64, 64, 1000], [-65, 63, 0, -2]],
                         ids=["wraps-and-clips", "below-range"])
def test_out_of_range_indices_follow_the_scatter(idx):
    """A negative index counts from the end and the result clips: the
    kernel must never be handed a tile outside the cache."""
    shape, dtype = (4, 2, 64, 128), jnp.float32
    cache = _fill(shape, dtype, 8)
    update = _fill(_update_shape(shape, 1), dtype, 9)
    idx = jnp.asarray(idx, jnp.int32)
    (got,) = pallas_write_rows((cache,), (update,), idx, interpret=True)
    np.testing.assert_array_equal(
        _bits(got), _bits(row_write._scatter_rows(cache, update, idx, 1)))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
def test_token_buffer_select_equals_scatter(dtype):
    """The engine's ``(S, max_len)`` token buffer: an iota select on every
    backend, no scatter and no kernel in its jaxpr."""
    buf = _fill((6, 40), dtype, 10)
    val = _fill((6,), dtype, 11)
    idx = jnp.asarray([0, 39, 7, 7, -1, 100], jnp.int32)
    want = row_write._scatter_rows(buf, val, idx, 0)
    np.testing.assert_array_equal(
        _bits(write_rows(buf, val, idx, axis=0)), _bits(want))
    jaxpr = str(jax.make_jaxpr(lambda *a: write_rows(*a, axis=0))(
        buf, val, idx))
    assert "scatter" not in jaxpr and "pallas_call" not in jaxpr


def _lowering(cache, axis, monkeypatch=None, on_tpu=False):
    if monkeypatch is not None:
        monkeypatch.setattr(row_write, "_on_tpu", lambda: on_tpu)
    update = jnp.zeros(_update_shape(cache.shape, axis), cache.dtype)
    idx = jnp.zeros((cache.shape[0],), jnp.int32)
    with row_write.record_paths() as paths:
        jaxpr = str(jax.make_jaxpr(lambda *a: write_rows(*a, axis=axis))(
            cache, update, idx))
    return paths, jaxpr


def test_cpu_default_is_the_scatter():
    paths, jaxpr = _lowering(jnp.zeros((4, 2, 32, 128), jnp.bfloat16), 1)
    assert paths == {"scatter"}
    assert "scatter" in jaxpr and "pallas_call" not in jaxpr


@pytest.mark.parametrize("shape,axis,dtype,want", [
    ((4, 2, 32, 128), 1, jnp.bfloat16, "pallas"),
    ((4, 48, 576), 0, jnp.bfloat16, "pallas"),
    ((4, 40, 128), 0, jnp.float32, "pallas"),    # 40 = 5 tiles of 8
    ((4, 40, 128), 0, jnp.bfloat16, "scatter"),  # 40 is no multiple of 16
    ((4, 2, 23, 128), 1, jnp.float32, "scatter"),  # an odd decode_len
    ((4, 32, 2, 128), 0, jnp.bfloat16, "scatter"),  # axis not second to last
], ids=["ring", "latent", "f32-tile-8", "bf16-off-tile", "odd-rows",
        "other-axis"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, axis, dtype, want):
    paths, jaxpr = _lowering(jnp.zeros(shape, dtype), axis, monkeypatch,
                             on_tpu=True)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")
    assert ("scatter" in jaxpr) == (want == "scatter")


def test_a_mesh_in_scope_keeps_the_scatter(monkeypatch, devices8):
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering(jnp.zeros((4, 2, 32, 128), jnp.bfloat16),
                                 1, monkeypatch, on_tpu=True)
    assert paths == {"scatter"} and "pallas_call" not in jaxpr


def test_fallback_result_equals_kernel_result_off_the_tile():
    """Rows that are no multiple of the tile take the scatter and still
    write the contract's bytes."""
    cache = _fill((3, 23, 128), jnp.float32, 12)
    update = _fill((3, 128), jnp.float32, 13)
    idx = jnp.asarray([0, 22, 11], jnp.int32)
    got = np.asarray(write_rows(cache, update, idx, axis=0))
    want = np.asarray(cache).copy()
    want[np.arange(3), np.asarray(idx)] = np.asarray(update)
    np.testing.assert_array_equal(got, want)


# ---- the decode step built on it ------------------------------------------


def _tiny_step():
    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode import ProGenDecodeStep, init_caches
    from progen_tpu.models import ProGenConfig
    from progen_tpu.parallel import unbox

    cfg = ProGenConfig(num_tokens=32, dim=16, seq_len=32, depth=2,
                       window_size=8, global_mlp_depth=1, heads=2,
                       dim_head=8, ff_mult=2)
    policy = make_policy(False)
    step = ProGenDecodeStep(config=cfg, policy=policy)
    caches = init_caches(cfg, 3, policy)
    tok = jnp.asarray([1, 2, 3], jnp.int32)
    pos = jnp.asarray([0, 9, 31], jnp.int32)
    params = unbox(step.init(jax.random.key(0), tok, pos, caches))
    return step, params, tok, pos, caches


def test_decode_step_with_the_kernel_holds_no_scatter(monkeypatch):
    """With the kernel path forced (interpreter), one ``ProGenDecodeStep``
    traces to Pallas calls — k and v of a layer in one — and no scatter;
    its logits and caches equal the default CPU path's bit for bit."""
    step, params, tok, pos, caches = _tiny_step()
    want_logits, want_caches = step.apply(params, tok, pos, caches)
    default = str(jax.make_jaxpr(step.apply)(params, tok, pos, caches))
    assert "scatter" in default and "pallas_call" not in default

    monkeypatch.setattr(row_write, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        row_write, "pallas_write_rows",
        lambda *a, _f=pallas_write_rows: _f(*a, interpret=True))
    with row_write.record_paths() as paths:
        forced = str(jax.make_jaxpr(step.apply)(params, tok, pos, caches))
    assert paths == {"pallas"}
    assert "scatter" not in forced
    # depth 2: one call for each layer's k and v, one for the gMLP layer
    assert forced.count("pallas_call") == 3
    logits, new = step.apply(params, tok, pos, caches)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    for got, want in zip(jax.tree.leaves(new), jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_states_the_lowering_its_chunk_was_built_with():
    """``status()["row_write"]``: ``None`` before the chunk program is
    traced, then what the trace chose — the scatter on the CPU."""
    from progen_tpu.decode import Request, ServingEngine

    step, params, *_ = _tiny_step()
    eng = ServingEngine(step.config, params, policy=step.policy,
                        num_slots=2, chunk_size=3)
    assert eng.status()["row_write"] is None
    eng.submit(Request(uid=0, tokens=[3, 4, 5], max_new_tokens=4, top_k=4,
                       temperature=1.0, seed=1))
    (done,) = eng.run_until_idle(max_chunks=20)
    assert done.uid == 0
    assert eng.status()["row_write"] == "scatter"
