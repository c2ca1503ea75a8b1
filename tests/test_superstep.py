"""Superstep fusion tests: parity of the fused K-step scan against the
sequential per-step loop (``tests/parity.py`` says how close), superbatch stager behavior
(stacking, partial spans, prefetch depth, donation-fresh buffers), the
hook-boundary span computation, and the memory/meter accounting."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.data.prefetch import SuperbatchStager
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.train import make_optimizer, make_train_functions
from progen_tpu.train.schedule import make_lr_schedule
from progen_tpu.train.trainer import superstep_span
from tests.parity import assert_same_steps

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=16, depth=2, window_size=8,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)
BATCH = 2


def _fns(accum):
    # warmup schedule: the lr moves every optimizer step, so the fused
    # per-step "lr" output is checked against real schedule reads
    schedule = make_lr_schedule("constant", 1e-3, warmup_steps=32)
    model = ProGen(config=CFG, policy=make_policy(False))
    optimizer = make_optimizer(learning_rate=schedule,
                               grad_accum_every=accum)
    sample = jnp.zeros((BATCH, CFG.seq_len), jnp.int32)
    return make_train_functions(
        model, optimizer, sample,
        grad_accum_every=accum, lr_schedule=schedule,
    )


def _micros(n, seed=3):
    """n micro-batches shaped like the data pipeline output: (B, L+1)
    int tokens, BOS column, pad tails."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n, BATCH, CFG.seq_len + 1), np.int32)
    for i in range(n):
        for r in range(BATCH):
            ln = int(rng.integers(CFG.seq_len // 2, CFG.seq_len + 1))
            out[i, r, 1:1 + ln] = rng.integers(1, 25, ln)
    return out


# -- parity (the tentpole's correctness contract) ----------------------------


@pytest.mark.parametrize("accum,k", [(1, 1), (1, 8), (4, 1), (4, 8)])
def test_fused_superstep_bit_exact(accum, k):
    """train_multi_step(K) == K*accum sequential train_step calls: step
    counters equal, and params, opt_state, per-micro-step losses and
    per-step lr within ``tests/parity.py``'s bound (two XLA programs do not
    round alike; the test keeps its name for the ledger's sake).  Two
    fused dispatches, fed through a real SuperbatchStager, so stager
    stacking and superbatch-buffer donation ride the same assertion."""
    fns = _fns(accum)
    dispatches = 2
    micros = _micros(dispatches * k * accum)

    state_seq = fns.init_state(jax.random.key(0))
    seq_losses, seq_lrs = [], []
    for i in range(dispatches * k * accum):
        state_seq, m = fns.train_step(state_seq, jnp.asarray(micros[i]))
        seq_losses.append(np.asarray(m["loss"]))
        seq_lrs.append(np.asarray(m["lr"]))

    state_fused = fns.init_state(jax.random.key(0))
    stager = SuperbatchStager(iter(list(micros)), jnp.asarray,
                              accum=accum, k_max=k)
    try:
        fused_losses, fused_lrs = [], []
        for _ in range(dispatches):
            state_fused, m = fns.train_multi_step(state_fused,
                                                  stager.get(k))
            assert m["loss"].shape == (k, accum)
            assert m["lr"].shape == (k,)
            fused_losses.append(np.asarray(m["loss"]).ravel())
            fused_lrs.append(np.asarray(m["lr"]))
    finally:
        stager.close()

    assert_same_steps(np.concatenate(fused_losses), np.asarray(seq_losses))
    # one lr per OPTIMIZER step = the sequential emit micro-steps' lr
    assert_same_steps(np.concatenate(fused_lrs),
                      np.asarray(seq_lrs).reshape(-1, accum)[:, -1])
    assert int(state_fused.step) == int(state_seq.step)
    assert_same_steps(state_fused.params, state_seq.params)
    assert_same_steps(state_fused.opt_state, state_seq.opt_state)


def test_multi_step_requires_multisteps_optimizer_under_accum():
    import optax

    model = ProGen(config=CFG, policy=make_policy(False))
    sample = jnp.zeros((BATCH, CFG.seq_len), jnp.int32)
    with pytest.raises(ValueError, match="MultiSteps"):
        make_train_functions(model, optax.adam(1e-3), sample,
                             grad_accum_every=4)


# -- superbatch stager -------------------------------------------------------


def test_stager_stacks_in_stream_order_with_partial_final_span():
    micros = [np.full((2, 5), i, np.int32) for i in range(12)]
    stager = SuperbatchStager(iter(micros), jnp.asarray, accum=2, k_max=3)
    try:
        sb = stager.get(3)
        assert sb.shape == (3, 2, 2, 5)
        np.testing.assert_array_equal(np.asarray(sb)[0, 0], micros[0])
        np.testing.assert_array_equal(np.asarray(sb)[2, 1], micros[5])
        # shrunken span near a hook boundary continues the stream exactly
        partial = stager.get(2)
        assert partial.shape == (2, 2, 2, 5)
        np.testing.assert_array_equal(np.asarray(partial)[0, 0], micros[6])
        np.testing.assert_array_equal(np.asarray(partial)[1, 1], micros[9])
    finally:
        stager.close()


def test_stager_validates_construction_and_k():
    with pytest.raises(ValueError):
        SuperbatchStager(iter([]), jnp.asarray, accum=0, k_max=1)
    with pytest.raises(ValueError):
        SuperbatchStager(iter([]), jnp.asarray, accum=1, k_max=0)
    stager = SuperbatchStager(iter([np.zeros((1, 2), np.int32)] * 4),
                              jnp.asarray, accum=1, k_max=2)
    try:
        with pytest.raises(ValueError):
            stager.get(3)
        with pytest.raises(ValueError):
            stager.get(0)
    finally:
        stager.close()


def test_stager_exhaustion_raises_stopiteration():
    micros = [np.zeros((1, 2), np.int32)] * 3
    stager = SuperbatchStager(iter(micros), jnp.asarray, accum=2, k_max=2)
    try:
        stager.get(1)
        with pytest.raises(StopIteration):
            stager.get(1)  # one micro left, a full step needs accum=2
    finally:
        stager.close()


def test_stager_prefetch_depth_buffers_ahead_boundedly():
    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield np.full((1, 2), i, np.int32)

    stager = SuperbatchStager(gen(), lambda b: b, accum=1, k_max=2, depth=2)
    try:
        stager.get(2)
        deadline = time.time() + 5.0
        # depth * k_max * accum = 4 buffered ahead (+1 in worker flight)
        while len(produced) < 6 and time.time() < deadline:
            time.sleep(0.01)
        assert len(produced) >= 6
        time.sleep(0.1)
        assert len(produced) <= 2 + 4 + 1
    finally:
        stager.close()


def test_stager_returns_fresh_buffers_each_get():
    """Each get() stacks into a NEW array, so the trainer can donate the
    superbatch to train_multi_step without invalidating later gets."""
    micros = [np.full((1, 2), i, np.int32) for i in range(8)]
    stager = SuperbatchStager(iter(micros), lambda b: b, accum=1, k_max=2)
    try:
        a = stager.get(2)
        b = stager.get(2)
        assert a is not b
        assert not np.shares_memory(a, b)
    finally:
        stager.close()


# -- hook-boundary span computation ------------------------------------------


def test_superstep_span_never_skips_or_doubles_hooks():
    """Walking 200 steps by spans fires exactly the hooks the per-step
    loop fires, in order, each exactly once."""
    cadences = (3, 7, 10, 25)
    gs, fired = 0, []
    while gs < 200:
        span = superstep_span(gs, 8, cadences, 200 - gs)
        assert 1 <= span <= 8
        for every in cadences:
            next_boundary = (gs // every + 1) * every
            assert gs + span <= next_boundary, "span crossed a boundary"
        gs += span
        for every in cadences:
            if gs % every == 0:
                fired.append((gs, every))
    assert gs == 200
    expected = [(s, e) for s in range(1, 201) for e in cadences
                if s % e == 0]
    assert fired == expected


def test_superstep_span_caps_and_edges():
    assert superstep_span(0, 8, (100,), 50) == 8    # open road: full K
    assert superstep_span(97, 8, (100,), 50) == 3   # lands ON the boundary
    assert superstep_span(100, 8, (100,), 50) == 8  # fresh span after it
    assert superstep_span(0, 8, (100,), 3) == 3     # epoch/max_steps budget
    assert superstep_span(0, 8, (1,), 50) == 1      # log_every=1: per-step
    assert superstep_span(0, 8, (0, 100), 50) == 8  # zero cadence ignored
    assert superstep_span(0, 8, (100,), 0) == 1     # always >= 1


# -- accounting --------------------------------------------------------------


def test_memory_plan_accounts_staged_superbatches():
    from progen_tpu.train.memory import plan

    base = plan(CFG, batch_size=8, grad_accum_every=2)
    fused = plan(CFG, batch_size=8, grad_accum_every=2, superstep_k=8)
    assert base.superbatch_bytes == 0
    # 2 buffers x K x accum x B x (L+1) x 4 bytes, unsharded mesh
    assert fused.superbatch_bytes == 2 * 8 * 2 * 8 * (CFG.seq_len + 1) * 4
    assert fused.total_bytes == base.total_bytes + fused.superbatch_bytes
    assert "staged superbatches" in fused.report()
    assert fused.detail["superstep_k"] == 8

    sharded = plan(CFG, batch_size=8, grad_accum_every=2, superstep_k=8,
                   mesh_shape={"data": 2, "fsdp": 2}, strategies=("dp",))
    assert sharded.superbatch_bytes == fused.superbatch_bytes // 4


def test_meter_rates_steps_when_ticked_with_them():
    from progen_tpu.observe.meter import ThroughputMeter

    m = ThroughputMeter()
    m.tick(0)
    time.sleep(0.01)
    m.tick(1000, steps=10)
    assert m.tokens_per_sec is not None and m.tokens_per_sec > 0
    assert m.steps_per_sec is not None and m.steps_per_sec > 0

    legacy = ThroughputMeter()
    legacy.tick(0)
    time.sleep(0.01)
    legacy.tick(1000)
    assert legacy.tokens_per_sec is not None
    assert legacy.steps_per_sec is None  # no step counts ever ticked
