"""Blocked-causal Pallas SGU kernel vs the XLA path (interpreter on CPU).

The kernel under test (``ops/pallas_sgu.py``) fuses ``res * (tril(W) @
gate + b)`` and skips strictly-upper-triangle weight blocks; its custom
VJP must match ``jax.grad`` of the reference composition to rtol 1e-5 in
f32, with EXACT zeros above the diagonal of the weight grad.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops.pallas_sgu import (
    DEFAULT_BLOCK,
    pallas_spatial_gate,
    sgu_block_flops,
)
from progen_tpu.ops.sgu import spatial_gate


def _inputs(rng, n, d, b=2, dtype=jnp.float32):
    res = jnp.asarray(rng.normal(size=(b, n, d)), dtype)
    gate = jnp.asarray(rng.normal(size=(b, n, d)), dtype)
    w = jnp.asarray(rng.normal(size=(n, n)) * 0.05, dtype)
    bias = jnp.asarray(rng.normal(size=(n, 1)), dtype)
    return res, gate, w, bias


def _reference(res, gate, w, bias):
    return res * spatial_gate(gate, w, bias)


# n=100/130 exercise the pad-to-block path; n=64/128 divide exactly;
# block 24 forces a non-power-of-two tile against n it does not divide
@pytest.mark.parametrize("n,d,block", [
    (64, 16, None), (128, 32, 64), (100, 8, None), (130, 8, 64), (96, 16, 24),
])
def test_pallas_sgu_matches_xla_forward(n, d, block):
    rng = np.random.default_rng(0)
    res, gate, w, bias = _inputs(rng, n, d)
    want = _reference(res, gate, w, bias)
    got = pallas_spatial_gate(res, gate, w, bias, block_size=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,d", [(64, 16), (100, 8)])
def test_pallas_sgu_gradients_match_xla(n, d):
    rng = np.random.default_rng(1)
    res, gate, w, bias = _inputs(rng, n, d)
    # a non-uniform cotangent so every backward kernel is exercised off
    # the all-ones easy case
    cot = jnp.asarray(rng.normal(size=res.shape), jnp.float32)
    f_p = lambda *a: jnp.sum(pallas_spatial_gate(*a) * cot)
    f_x = lambda *a: jnp.sum(_reference(*a) * cot)
    gp = jax.grad(f_p, argnums=(0, 1, 2, 3))(res, gate, w, bias)
    gx = jax.grad(f_x, argnums=(0, 1, 2, 3))(res, gate, w, bias)
    for got, want in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_pallas_sgu_upper_triangle_grads_exact_zero():
    """The masked parameterization's dead region: d_W above the diagonal
    must be EXACTLY zero (not merely small), matching the reference where
    tril'd-away weights never see a gradient."""
    rng = np.random.default_rng(2)
    n, d = 100, 8
    res, gate, w, bias = _inputs(rng, n, d)
    dw = jax.grad(
        lambda ww: jnp.sum(pallas_spatial_gate(res, gate, ww, bias) ** 2)
    )(w)
    upper = np.asarray(dw)[np.triu_indices(n, k=1)]
    assert np.all(upper == 0.0)
    # and the kept region is live
    assert np.any(np.asarray(dw)[np.tril_indices(n)] != 0.0)


def test_pallas_sgu_upper_triangle_weights_dead():
    rng = np.random.default_rng(3)
    n, d = 64, 8
    res, gate, w, bias = _inputs(rng, n, d)
    w2 = w + jnp.triu(jnp.ones((n, n)), k=1) * 100.0
    got1 = pallas_spatial_gate(res, gate, w, bias)
    got2 = pallas_spatial_gate(res, gate, w2, bias)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(got2),
                               rtol=0, atol=0)


def test_pallas_sgu_bf16_close_to_f32():
    """bf16 inputs, f32 accumulation: must stay near the f32 reference —
    the learned weights live at ~1e-6 scale, so a bf16 accumulator would
    blow far past this tolerance."""
    rng = np.random.default_rng(4)
    n, d = 128, 16
    res, gate, w, bias = _inputs(rng, n, d)
    want = _reference(res, gate, w, bias)
    got = pallas_spatial_gate(res.astype(jnp.bfloat16),
                              gate.astype(jnp.bfloat16),
                              w.astype(jnp.bfloat16),
                              bias.astype(jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_pallas_sgu_rejects_bad_shapes():
    z = jnp.zeros
    with pytest.raises(ValueError):
        pallas_spatial_gate(z((2, 8, 4)), z((2, 8, 4)), z((8, 6)), z((8, 1)))
    with pytest.raises(ValueError):
        pallas_spatial_gate(z((2, 6, 4)), z((2, 6, 4)), z((8, 8)), z((8, 1)))
    with pytest.raises(ValueError):
        pallas_spatial_gate(z((2, 8, 4)), z((2, 8, 4)), z((8, 8)), z((8, 2)))


def test_block_skip_flop_count_beats_dense():
    """Acceptance gate, restated for the tiles the chip takes.  The TPU
    compiler refuses weight blocks whose last two dimensions are not
    multiples of (8, 128) (tests/test_chip_compile.py), so the default
    tile is 128 wide and the block-granular causal hull is exactly
    (R+1)/2R of the dense einsum: 0.5625x at n=1024 (R=8) — the old
    <= 0.55 bound held only for the refused 64-wide tiles (0.531x) — and
    <= 0.55 from n=2048 (ProGen-base, R=16: 0.531x) upwards."""
    info = sgu_block_flops(1024, 2048)
    assert info["block"] == DEFAULT_BLOCK == 128
    assert info["ratio"] == 36 / 64
    # exact triangle count for the padded-to-even grid
    nbr = 1024 // info["block"]
    assert info["blocks_executed"] == nbr * (nbr + 1) // 2
    assert info["blocks_dense"] == nbr * nbr
    assert sgu_block_flops(2048, 3072)["ratio"] == 17 / 32 <= 0.55


def test_sharded_pallas_sgu_matches_single_device(devices8):
    """Full-manual shard_map wrapper (batch x tensor mesh, weights
    replicated) must agree with the single-device kernel, gradients
    included — the replicated weights' cotangent psum is shard_map's."""
    from progen_tpu.core.mesh import MeshConfig, make_mesh
    from progen_tpu.parallel.context import sharded_pallas_spatial_gate

    rng = np.random.default_rng(5)
    n, d = 64, 16
    res, gate, w, bias = _inputs(rng, n, d, b=4)
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2, seq=1))

    want = pallas_spatial_gate(res, gate, w, bias)
    got = sharded_pallas_spatial_gate(res, gate, w, bias, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    f_s = lambda ww, bb: jnp.sum(
        sharded_pallas_spatial_gate(res, gate, ww, bb, mesh=mesh) ** 2)
    f_1 = lambda ww, bb: jnp.sum(pallas_spatial_gate(res, gate, ww, bb) ** 2)
    gs = jax.grad(f_s, argnums=(0, 1))(w, bias)
    g1 = jax.grad(f_1, argnums=(0, 1))(w, bias)
    for got_g, want_g in zip(gs, g1):
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                                   rtol=1e-4, atol=1e-4)


def test_sharded_pallas_sgu_rejects_seq_parallel(devices8):
    """No silent mis-sharding: a seq>1 mesh must raise (cp_spatial_gate
    owns the op under sequence parallelism)."""
    from progen_tpu.core.mesh import MeshConfig, make_mesh
    from progen_tpu.parallel.context import sharded_pallas_spatial_gate

    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=1, seq=2))
    z = jnp.zeros
    with pytest.raises(ValueError, match="sequence parallelism"):
        sharded_pallas_spatial_gate(
            z((4, 16, 8)), z((4, 16, 8)), z((16, 16)), z((16, 1)), mesh=mesh)
