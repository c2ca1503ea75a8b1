"""Disaggregated serving: bit-exact contracts.

The contract under test (docs/SERVING.md §6): disaggregation moves prefill
into a separate worker program whose cache handles cross a bounded handoff
queue and are DONATED into decode slots; admission order changes, tokens
must not.  It composes with the fault plan / snapshot / replay machinery
from the resilience work.
"""

import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import Handle, HandoffQueue, Request, ServingEngine
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.parallel import unbox
from progen_tpu.resilience import faults

pytestmark = [pytest.mark.serving, pytest.mark.disagg]

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=3, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)


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


def _mk_requests(n, *, seed=0, max_new=8, mixed=True):
    """Mixed greedy and sampled requests — sampled rows prove the per-
    request key chain survives disaggregation bit-for-bit."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(1, 9))
        sampled = mixed and i % 2 == 1
        reqs.append(Request(
            uid=i, tokens=rng.integers(1, CFG.num_tokens, p).tolist(),
            max_new_tokens=max_new,
            top_k=5 if sampled else None,
            temperature=0.8 if sampled else 0.0,
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
    """The inline baseline every variant is compared against."""
    _, params, policy = trained
    _, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                         chunk_size=4, max_len=20)
    return out


# ------------------------------------------------- token identity: disagg


def test_disagg_token_identity(trained, clean):
    """Prefill through the worker + handoff queue + donated merge changes
    WHEN requests are admitted, never WHAT they decode."""
    _, params, policy = trained
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, disagg=True,
                           handoff_depth=2)
    assert out == clean
    stats = eng.robustness_counters()["handoff"]
    assert stats["puts"] == stats["gets"] > 0
    assert stats["rejects"] == 0


def test_disagg_paged_no_donation_warning(trained, clean):
    """Paged disagg must not fall back to copies: the merge donates the
    handle (gate slabs split out host-side because they scatter into the
    pool).  jax warns when a donated buffer could not be used — treat
    that as failure."""
    _, params, policy = trained
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        _, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                             chunk_size=4, max_len=20, disagg=True,
                             paged=True, page_size=4)
    assert out == clean


# ------------------------------------------------------ handoff semantics


def _dummy_handle(n_req=1):
    return Handle(requests=[object()] * n_req, state={}, p_pad=8)


def test_handoff_queue_bounded_fifo():
    q = HandoffQueue(depth=2)
    assert not q and len(q) == 0 and not q.full()
    a, b, c = _dummy_handle(), _dummy_handle(2), _dummy_handle()
    assert q.put(a) and q.put(b)
    assert q.full()
    assert not q.put(c)  # at depth: rejected, counted
    assert q.stats()["rejects"] == 1
    assert q.num_requests() == 3
    assert q.peek() is a
    assert q.get() is a and q.get() is b  # FIFO
    assert q.stats() == {"depth": 2, "queued": 0, "puts": 2, "gets": 2,
                         "rejects": 1}


def test_handoff_requeue_front_unbounded():
    """requeue puts a transiently-failed merge back at the FRONT and is
    exempt from the bound — the crash-replay loop must not deadlock
    against its own backpressure."""
    q = HandoffQueue(depth=1)
    a, b = _dummy_handle(), _dummy_handle()
    assert q.put(a)
    q.requeue(b)  # full, but requeue is allowed
    assert len(q) == 2
    assert q.get() is b  # front, replayed before newer work


def test_handoff_depth_validation():
    with pytest.raises(ValueError):
        HandoffQueue(depth=0)


# ---------------------------------------------- snapshot / restore / replay


def test_disagg_snapshot_captures_handoff(trained, clean):
    """A snapshot taken while handles sit in the handoff queue must not
    lose those requests — they replay on the fresh engine."""
    _, params, policy = trained
    kw = dict(num_slots=2, chunk_size=4, max_len=20, disagg=True)
    eng = ServingEngine(CFG, params, policy=policy, **kw)
    for r in _mk_requests(5):
        eng.submit(r)
    for _ in range(2):  # step 2 prefills a batch the busy pool can't admit
        eng.step()
    assert eng.robustness_counters()["handoff"]["queued"] > 0
    pre = {c.uid: (c.tokens.tolist(), c.status) for c in eng.completions}
    snap = eng.snapshot()
    uids = set(range(5)) - set(pre)
    assert {r["uid"] for r in snap["requests"]} == uids  # nothing lost

    fresh = ServingEngine(CFG, params, policy=policy, **kw)
    fresh.restore(snap)
    post = {c.uid: (c.tokens.tolist(), c.status)
            for c in fresh.run_until_idle(max_chunks=300)}
    assert {**pre, **post} == clean


# ------------------------------------------------------------------ chaos


def test_chaos_handoff_merge_fault_token_identity(trained, clean):
    """A transient fault at the donated merge: the handle requeues at the
    queue front (donation safety: the fault fires before dispatch, so
    the buffers were never consumed) and replays exactly once."""
    _, params, policy = trained
    faults.configure("serve.handoff:io_error:at=1", seed=2)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, disagg=True)
    assert out == clean
    assert eng.robust.faults_contained >= 1


def test_chaos_prefill_worker_fault_sheds_batch(trained, clean):
    """Disagg under the standard chaos plan points that exist in this
    pipeline: prefill-worker and decode-chunk faults, all contained."""
    _, params, policy = trained
    faults.configure("serve.prefill:unavailable:at=1;"
                     "serve.decode_chunk:io_error:at=2", seed=3)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, disagg=True)
    assert out == clean
    assert eng.robust.faults_contained >= 2


# --------------------------------------------------------- bench contracts


def test_bench_ladder_survives_backend_crash(monkeypatch, capsys):
    """Turned round (PR 21): a backend that dies at first in-process use
    makes ``bench.main()`` RAISE — the caller sees the traceback and a
    non-zero exit, and no record is printed under rc 0."""
    import bench

    def boom():
        raise RuntimeError("backend init failed: device busy")

    # the persistent cache stays off inside the 8-virtual-device pytest
    # process (.claude/skills/verify/SKILL.md)
    monkeypatch.setattr(bench, "enable_compilation_cache", lambda: None)
    monkeypatch.setattr(bench.jax, "devices", boom)
    monkeypatch.setenv("PROGEN_BENCH_CONFIGS", "small,base")
    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(RuntimeError, match="backend init failed"):
        bench.main()
    assert not capsys.readouterr().out.strip()


def test_bench_records_carry_git_sha():
    """Every serving-bench record must carry the repo sha so a number in
    a jsonl is attributable to a commit."""
    from progen_tpu.observe import git_sha

    sha = git_sha()
    assert sha and all(c in "0123456789abcdef" for c in sha)
    # stamping goes through the one door (observe.platform.stamp_record,
    # which setdefaults git_sha); tests/test_observe.py sweeps EVERY
    # bench source for compliance — here just pin the serving benches
    root = pathlib.Path(__file__).resolve().parents[1]
    for script in ("benchmarks/bench_coldstart.py",
                   "benchmarks/bench_serving.py"):
        src = (root / script).read_text()
        assert "stamp_record" in src, script
