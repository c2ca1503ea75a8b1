"""Fault-tolerant serving: chaos, shedding, and crash-safe replay.

The contract under test (docs/RESILIENCE.md, docs/SERVING.md): with a
seeded fault plan hitting the serving injection points, the engine
finishes every non-shed request TOKEN-IDENTICAL to a fault-free run —
transient faults are retried in place (engine dispatches are functional,
``self.state`` only advances on success), non-transient faults become
typed ``failed_fault`` completions, and a crash anywhere is recoverable
by ``snapshot() -> restore()`` replay because each request's trajectory
depends only on (params, prime, seed, knobs), never on wall-clock or
batching accidents.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import (
    FAILED_FAULT,
    SHED_DEADLINE,
    SHED_QUEUE_FULL,
    Request,
    ServingEngine,
    prime_buckets,
    run_with_restarts,
)
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.parallel import unbox
from progen_tpu.resilience import RetryError, Watchdog, faults

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=3, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)

# four serving points, one transient fault each — the acceptance plan
CHAOS_PLAN = ("serve.admit:io_error:at=2;serve.prefill:unavailable:at=2;"
              "serve.decode_chunk:io_error:at=3;serve.harvest:io_error:at=2")


@pytest.fixture(scope="module")
def trained():
    policy = make_policy(False)  # f32 end to end: parity mode
    model = ProGen(config=CFG, policy=policy)
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    params = unbox(model.init(jax.random.key(7), tokens))
    return model, params, policy


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.configure("")  # never leak a plan into the next test


def _mk_requests(n, *, seed=0, max_new=8):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(1, 9))
        reqs.append(Request(
            uid=i, tokens=rng.integers(1, CFG.num_tokens, p).tolist(),
            max_new_tokens=max_new, top_k=None, temperature=0.0,
            seed=100 + i,
        ))
    return reqs


def _run_engine(params, policy, reqs, **kw):
    eng = ServingEngine(CFG, params, policy=policy, **kw)
    for r in reqs:
        eng.submit(r)
    comps = eng.run_until_idle(max_chunks=300)
    return eng, {c.uid: (c.tokens.tolist(), c.status) for c in comps}


@pytest.fixture(scope="module")
def clean(trained):
    """Fault-free greedy baseline every chaos run is compared against."""
    _, params, policy = trained
    _, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                         chunk_size=4, max_len=20)
    return out


# ------------------------------------------------------------ containment


def test_chaos_plan_token_identity(trained, clean):
    """The acceptance criterion: transient faults at four serving points,
    all requests finish, all token-identical to the fault-free run."""
    _, params, policy = trained
    faults.configure(CHAOS_PLAN, seed=1)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20)
    assert out == clean
    assert eng.robust.faults_contained >= 4
    assert eng.robust.failed_faults == 0


def test_chaos_paged_token_identity(trained, clean):
    """Same contract in paged mode, including a page_alloc fault (the
    engine defers the round and retries) and a prefill fault (planned
    pages freed, deferred prefix registrations rolled back)."""
    _, params, policy = trained
    faults.configure("serve.page_alloc:io_error:at=2;"
                     "serve.prefill:unavailable:at=1;"
                     "serve.decode_chunk:io_error:at=2", seed=3)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, paged=True,
                           page_size=4)
    assert out == clean
    assert eng.robust.faults_contained >= 3
    # no leaked pages after the chaos run drains
    assert eng._pool.free_pages + eng._pool.cached_pages == \
        eng._pool.capacity


def test_chaos_fork_page_alloc_rollback(trained):
    """submit_fork under a page_alloc fault: the leader's admission
    defers and retries, the held followers still land as cache hits (or
    unshared after a shed — either way token-identical to the fault-free
    fork run), and the pool closes its books — a rolled-back alloc must
    not strand a fork group or leak a page reference."""
    _, params, policy = trained
    base = Request(uid=0, tokens=[3, 1, 4, 1, 5, 9, 2, 6],
                   max_new_tokens=6, top_k=8, temperature=0.9, seed=100)

    def run(plan):
        faults.configure(plan, seed=5)
        eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                            chunk_size=4, max_len=20, paged=True,
                            page_size=4)
        eng.submit_fork(base, 3)
        comps = eng.run_until_idle(max_chunks=300)
        return eng, {c.uid: (c.tokens.tolist(), c.status) for c in comps}

    _, clean_forks = run("")
    eng, out = run("serve.page_alloc:io_error:at=2")
    assert out == clean_forks
    assert eng.robust.faults_contained >= 1
    assert eng.robust.failed_faults == 0
    assert eng._pool.shared_pages == 0
    assert eng._pool.free_pages + eng._pool.cached_pages == \
        eng._pool.capacity


def test_fatal_fault_sheds_typed_completion(trained, clean):
    """A non-transient fault never raises out of the engine: the affected
    requests become ``failed_fault`` completions, everyone else finishes
    untouched."""
    _, params, policy = trained
    faults.configure("serve.prefill:fatal:at=1", seed=0)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20)
    shed = {u for u, (_, s) in out.items() if s == FAILED_FAULT}
    assert shed  # the first admitted batch was on the faulted path
    assert eng.robust.failed_faults == len(shed)
    for u in set(out) - shed:
        assert out[u] == clean[u]


def test_submit_fault_sheds_not_raises(trained):
    _, params, policy = trained
    faults.configure("serve.submit:fatal:at=1", seed=0)
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20)
    reqs = _mk_requests(3)
    for r in reqs:
        eng.submit(r)  # first one faults; must NOT raise
    out = {c.uid: c.status for c in eng.run_until_idle(max_chunks=300)}
    assert out[0] == FAILED_FAULT
    assert out[1] == "ok" and out[2] == "ok"


# --------------------------------------------------- deadlines / shedding


def test_queue_full_reject_and_shed_oldest(trained):
    _, params, policy = trained
    reqs = _mk_requests(4)

    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, max_queue=2)
    for r in reqs:
        eng.submit(r)  # 2 queued, then 2 rejected
    out = {c.uid: c.status for c in eng.run_until_idle(max_chunks=300)}
    assert [out[u] for u in range(4)] == \
        ["ok", "ok", SHED_QUEUE_FULL, SHED_QUEUE_FULL]
    assert eng.robust.sheds_queue_full == 2

    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, max_queue=2,
                        shed_policy="shed-oldest")
    for r in _mk_requests(4):
        eng.submit(r)  # oldest are pushed out, newest kept
    out = {c.uid: c.status for c in eng.run_until_idle(max_chunks=300)}
    assert [out[u] for u in range(4)] == \
        [SHED_QUEUE_FULL, SHED_QUEUE_FULL, "ok", "ok"]


def test_deadline_sheds_queued_request(trained):
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20)
    r = _mk_requests(1)[0]
    r.deadline = time.perf_counter() - 1.0  # already expired
    eng.submit(r)
    out = eng.run_until_idle(max_chunks=10)
    assert len(out) == 1 and out[0].status == SHED_DEADLINE
    assert eng.robust.sheds_deadline == 1
    assert not eng.has_work


def test_deadline_cancels_inflight_with_partial_tokens(trained, clean):
    """An in-flight request whose deadline passes is cancelled between
    chunks: its completion carries the tokens decoded so far (a PREFIX of
    the fault-free output) and its slot/pages are reclaimed."""
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=2, max_len=24, paged=True, page_size=4)
    r = _mk_requests(1, max_new=12)[0]
    eng.submit(r)
    eng.step()  # admit + first chunk; a few tokens exist now
    r.deadline = time.perf_counter() - 1.0  # expire it mid-flight
    out = eng.run_until_idle(max_chunks=10)
    assert len(out) == 1 and out[0].status == SHED_DEADLINE
    got = out[0].tokens.tolist()
    assert 0 < len(got) < 12
    assert got == clean[0][0][:len(got)]  # deterministic prefix
    assert eng.num_active == 0
    assert eng._pool.free_pages + eng._pool.cached_pages == \
        eng._pool.capacity


# ------------------------------------------------- drain / snapshot / replay


def test_drain_finishes_inflight_keeps_queue(trained):
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20)
    for r in _mk_requests(5):
        eng.submit(r)
    eng.step()  # admit up to 2
    assert eng.num_active > 0 and eng.pending > 0
    done = eng.drain(max_chunks=50)
    assert eng.num_active == 0
    assert eng.pending > 0  # queued requests survive a drain untouched
    assert all(c.ok for c in done)
    assert eng.has_work  # the queue still wants service


def test_snapshot_restore_midrun_parity(trained, clean, tmp_path):
    """snapshot -> kill -> restore -> replay is token-identical: finished
    completions plus the replayed remainder equal the straight run."""
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20)
    for r in _mk_requests(5):
        eng.submit(r)
    for _ in range(2):
        eng.step()  # some finished, some mid-decode, some queued
    path = str(tmp_path / "snap.json")
    eng.snapshot(path)
    pre = {c.uid: (c.tokens.tolist(), c.status) for c in eng.completions}

    fresh = ServingEngine(CFG, params, policy=policy, num_slots=2,
                          chunk_size=4, max_len=20)
    n = fresh.restore(path)
    assert n == 5 - len(pre)
    post = {c.uid: (c.tokens.tolist(), c.status)
            for c in fresh.run_until_idle(max_chunks=300)}
    assert {**pre, **post} == clean


def test_crash_consistent_after_retry_exhaustion(trained, clean):
    """When a 'transient' fault persists past the retry budget the engine
    raises RetryError — but stays CONSISTENT: the in-flight work is still
    snapshottable and replays token-identically on a fresh engine."""
    _, params, policy = trained
    faults.configure("serve.decode_chunk:unavailable:at=2", seed=0)
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20, fault_retries=0)
    for r in _mk_requests(5):
        eng.submit(r)
    with pytest.raises(RetryError):
        eng.run_until_idle(max_chunks=300)
    faults.configure("")

    pre = {c.uid: (c.tokens.tolist(), c.status) for c in eng.completions}
    snap = eng.snapshot()
    fresh = ServingEngine(CFG, params, policy=policy, num_slots=2,
                          chunk_size=4, max_len=20)
    fresh.restore(snap)
    post = {c.uid: (c.tokens.tolist(), c.status)
            for c in fresh.run_until_idle(max_chunks=300)}
    assert {**pre, **post} == clean


def test_run_with_restarts_replays_token_identical(trained, clean):
    """The restart-and-replay loop sample.py --serve uses: a crash mid-
    stream rebuilds the engine from the snapshot and the merged output is
    token-identical to a run that never crashed."""
    _, params, policy = trained
    restarts = []

    def factory():
        restarts.append(1)
        return ServingEngine(CFG, params, policy=policy, num_slots=2,
                             chunk_size=4, max_len=20, fault_retries=0)

    faults.configure("serve.decode_chunk:unavailable:at=2", seed=0)
    comps = run_with_restarts(factory, _mk_requests(5), attempts=3,
                              max_chunks=300)
    out = {c.uid: (c.tokens.tolist(), c.status) for c in comps}
    assert out == clean
    assert len(restarts) == 2  # initial engine + one rebuild


# ----------------------------------------------------- kernel degradation


def test_pallas_failure_degrades_to_xla_fallback(trained):
    """A failing Pallas paged kernel is swapped for the bit-identical XLA
    fallback mid-run: counted, logged, and token-identical to an engine
    that ran XLA from the start."""
    _, params, policy = trained
    _, want = _run_engine(params, policy, _mk_requests(4), num_slots=2,
                          chunk_size=4, max_len=20, paged=True,
                          page_size=4)
    faults.configure("serve.decode_chunk:fatal:at=1", seed=0)
    eng, got = _run_engine(params, policy, _mk_requests(4), num_slots=2,
                           chunk_size=4, max_len=20, paged=True,
                           page_size=4, paged_impl="pallas")
    assert eng.robust.fallback_activations == 1
    assert eng.paged_impl == "xla"
    assert got == want
    assert all(s == "ok" for _, s in got.values())


def test_compile_refusal_of_paged_kernel_reaches_caller(trained,
                                                        monkeypatch):
    """An error raised while the paged chunk program compiles (its first
    dispatch) is the caller's to see: not contained, not answered with
    the XLA fallback — on the chip that fallback would hide a kernel the
    compiler refuses behind one ``fallback_activations`` count."""
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20, paged=True, page_size=4,
                        paged_impl="pallas")

    def refuse(*a, **k):
        raise ValueError("Mosaic failed to compile TPU kernel: block "
                         "shape (1, 16) not divisible by (8, 128)")

    monkeypatch.setattr(eng, "_decode_chunk", refuse)
    for r in _mk_requests(2):
        eng.submit(r)
    with pytest.raises(ValueError, match="Mosaic failed to compile"):
        eng.run_until_idle(max_chunks=50)
    assert eng.robust.fallback_activations == 0
    assert eng.paged_impl == "pallas"


def test_runtime_failure_of_paged_kernel_after_first_run_degrades(
        trained, monkeypatch):
    """The other side of that rule: once the chunk program has run, a
    fatal fault of it that nobody injected still degrades to the XLA
    fallback, and the tokens do not change."""
    _, params, policy = trained
    kw = dict(num_slots=2, chunk_size=4, max_len=20, paged=True,
              page_size=4)
    _, want = _run_engine(params, policy, _mk_requests(2), **kw)
    eng = ServingEngine(CFG, params, policy=policy, paged_impl="pallas",
                        **kw)
    for r in _mk_requests(2):
        eng.submit(r)
    done = eng.step()  # the chunk program compiles and runs once
    assert ("chunk",) in eng._compiled_keys

    def halted(*a, **k):
        raise ValueError("device halted")

    monkeypatch.setattr(eng, "_decode_chunk", halted)
    done += eng.run_until_idle(max_chunks=50)
    assert eng.robust.fallback_activations == 1
    assert eng.paged_impl == "xla"
    assert {c.uid: (c.tokens.tolist(), c.status) for c in done} == want


# ------------------------------------------------------- warmup / watchdog


def test_aot_warmup_covers_grid_and_changes_nothing(trained, clean):
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                        chunk_size=4, max_len=20)
    stats = eng.aot_warmup()
    buckets = prime_buckets(CFG.window_size, CFG.seq_len, eng.max_len - 1)
    assert stats["programs"] == len(buckets) + 1  # admits + the chunk
    for r in _mk_requests(5):
        eng.submit(r)
    out = {c.uid: (c.tokens.tolist(), c.status)
           for c in eng.run_until_idle(max_chunks=300)}
    assert out == clean


def test_watchdog_beats_through_serve_steps(trained, tmp_path):
    """The engine beats the watchdog each step and pauses it across
    compiles, so a healthy chaos run never trips it."""
    _, params, policy = trained
    exits = []
    wd = Watchdog(timeout=30.0, out_dir=str(tmp_path),
                  exit_fn=exits.append, poll_interval=0.05)
    wd.start()
    try:
        faults.configure("serve.decode_chunk:io_error:at=1", seed=0)
        eng = ServingEngine(CFG, params, policy=policy, num_slots=2,
                            chunk_size=4, max_len=20, watchdog=wd)
        for r in _mk_requests(3):
            eng.submit(r)
        comps = eng.run_until_idle(max_chunks=300)
    finally:
        wd.stop()
    assert len(comps) == 3 and not wd.tripped and not exits
