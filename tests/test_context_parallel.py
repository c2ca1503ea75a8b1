"""Context-parallel equivalence: the shard_map halo-exchange attention and
the sequence-sharded SGU must agree with the single-device ops exactly
(SURVEY.md §7 hard part #3: halo correctness at shard edges)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core import MeshConfig, make_mesh
from progen_tpu.ops import local_attention, spatial_gate
from progen_tpu.parallel.context import cp_local_attention, cp_spatial_gate


@pytest.fixture(scope="module")
def seq_mesh(devices8):
    return make_mesh(MeshConfig(data=1, fsdp=1, tensor=1, seq=4),
                     devices=devices8[:4])


@pytest.mark.parametrize("n,wsz", [(32, 8), (64, 8), (32, 4)])
def test_cp_attention_matches_single_device(seq_mesh, n, wsz):
    rng = np.random.default_rng(0)
    b, h, d = 2, 3, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
               for _ in range(3))
    want = local_attention(q, k, v, window_size=wsz)
    got = cp_local_attention(q, k, v, mesh=seq_mesh, window_size=wsz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_cp_attention_shard_boundaries_are_window_boundaries(seq_mesh):
    """L=32 over 4 shards -> 8 per shard; with window 8 each shard holds
    exactly one window, so EVERY previous-window lookup crosses a shard
    edge — the pure-halo regime."""
    rng = np.random.default_rng(1)
    b, h, n, d, wsz = 1, 2, 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
               for _ in range(3))
    want = local_attention(q, k, v, window_size=wsz)
    got = cp_local_attention(q, k, v, mesh=seq_mesh, window_size=wsz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_cp_attention_rejects_partial_windows(seq_mesh):
    q = jnp.zeros((1, 1, 24, 4))  # 24/4 shards = 6 per shard, window 4: 6%4!=0
    with pytest.raises(ValueError, match="divisible by window"):
        cp_local_attention(q, q, q, mesh=seq_mesh, window_size=4)


@pytest.mark.parametrize("n", [16, 32])
def test_cp_spatial_gate_matches_single_device(seq_mesh, n):
    rng = np.random.default_rng(2)
    b, d = 2, 6
    gate = jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n, 1)), jnp.float32)
    want = spatial_gate(gate, w, bias)
    got = cp_spatial_gate(gate, w, bias, mesh=seq_mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_full_model_sp_train_step_matches_single_device(devices8):
    """sp must be wired into the PRODUCT, not just the ops.
    A train step on a (data=2, seq=4) mesh with the model routing through
    cp_local_attention/cp_spatial_gate must match the unsharded step."""
    import numpy as np
    from progen_tpu.core import MeshConfig, make_mesh
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.train import make_optimizer, make_train_functions

    cfg = ProGenConfig(
        num_tokens=64, dim=16, seq_len=32, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, tensor=1, seq=4),
                     devices=devices8)
    policy = make_policy(False)  # f32: exact agreement expected
    optimizer = make_optimizer(1e-3)
    sample = jnp.zeros((4, cfg.seq_len), jnp.int32)

    model_sp = ProGen(config=cfg, policy=policy, mesh=mesh)
    fns_sp = make_train_functions(model_sp, optimizer, sample, mesh=mesh,
                                  strategies=("dp", "sp"))
    model_ref = ProGen(config=cfg, policy=policy)
    fns_ref = make_train_functions(model_ref, optimizer, sample)

    key = jax.random.key(0)
    state_sp = fns_sp.init_state(key)
    state_ref = fns_ref.init_state(key)
    for a, b in zip(jax.tree.leaves(state_sp.params),
                    jax.tree.leaves(state_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    batch = jnp.concatenate(
        [jnp.zeros((4, 1), jnp.int32),
         jax.random.randint(jax.random.key(1), (4, cfg.seq_len), 1, 60)],
        axis=1,
    )
    state_sp, m_sp = fns_sp.train_step(state_sp, batch)
    state_ref, m_ref = fns_ref.train_step(state_ref, batch)
    np.testing.assert_allclose(float(m_sp["loss"]), float(m_ref["loss"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m_sp["grad_norm"]),
                               float(m_ref["grad_norm"]),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(state_sp.params),
                    jax.tree.leaves(state_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_full_model_sp_with_fsdp_tp(devices8):
    """The cp path must compose with fsdp+tp on the same mesh (partial-manual
    shard_map: seq manual, other axes GSPMD)."""
    import numpy as np
    from progen_tpu.core import MeshConfig, make_mesh
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.train import make_optimizer, make_train_functions

    cfg = ProGenConfig(
        num_tokens=64, dim=16, seq_len=32, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
    )
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=2, seq=2),
                     devices=devices8)
    policy = make_policy(False)
    optimizer = make_optimizer(1e-3)
    sample = jnp.zeros((4, cfg.seq_len), jnp.int32)

    model_sp = ProGen(config=cfg, policy=policy, mesh=mesh)
    fns_sp = make_train_functions(model_sp, optimizer, sample, mesh=mesh,
                                  strategies=("dp", "fsdp", "tp", "sp"))
    model_ref = ProGen(config=cfg, policy=policy)
    fns_ref = make_train_functions(model_ref, optimizer, sample)

    key = jax.random.key(0)
    state_sp = fns_sp.init_state(key)
    state_ref = fns_ref.init_state(key)
    batch = jnp.concatenate(
        [jnp.zeros((4, 1), jnp.int32),
         jax.random.randint(jax.random.key(2), (4, cfg.seq_len), 1, 60)],
        axis=1,
    )
    state_sp, m_sp = fns_sp.train_step(state_sp, batch)
    state_ref, m_ref = fns_ref.train_step(state_ref, batch)
    np.testing.assert_allclose(float(m_sp["loss"]), float(m_ref["loss"]),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(state_sp.params),
                    jax.tree.leaves(state_ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_cp_gradients_flow(seq_mesh):
    """Backward through the shard_map path must work and match."""
    rng = np.random.default_rng(3)
    b, h, n, d, wsz = 1, 2, 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, n, d)), jnp.float32)
               for _ in range(3))

    f_plain = lambda q, k, v: local_attention(q, k, v, window_size=wsz).sum()
    f_cp = lambda q, k, v: cp_local_attention(
        q, k, v, mesh=seq_mesh, window_size=wsz).sum()
    g_plain = jax.grad(f_plain, argnums=(0, 1, 2))(q, k, v)
    g_cp = jax.grad(f_cp, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_plain, g_cp):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-5, atol=1e-5)
