"""Speculative decoding + disaggregated serving: bit-exact contracts.

The contract under test (docs/SERVING.md §6): speculative decoding emits
every token from the TARGET model's own logits with the slot's own key
chain, so output is token-identical to non-speculative decode — greedy
and sampled alike, for ANY draft (the draft only buys throughput).
Disaggregation moves prefill into a separate worker program whose cache
handles cross a bounded handoff queue and are DONATED into decode slots;
admission order changes, tokens must not.  Both compose with the fault
plan / snapshot / replay machinery from the resilience work.
"""

import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import (
    Handle,
    HandoffQueue,
    Request,
    ServingEngine,
    check_draft_config,
    spec_acceptance,
)
from progen_tpu.models import ProGen, ProGenConfig, draft_config_for
from progen_tpu.parallel import unbox
from progen_tpu.resilience import faults

pytestmark = [pytest.mark.serving, pytest.mark.spec]

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


@pytest.fixture(scope="module")
def tiny_draft(trained):
    """A genuinely different draft model (quarter-width, 2 layers) with
    its own random params — the adversarial case for bit-exactness: its
    proposals rarely match, so nearly every round rejects early."""
    _, _, policy = trained
    dcfg = draft_config_for(CFG)
    dmodel = ProGen(config=dcfg, policy=policy)
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    dparams = unbox(dmodel.init(jax.random.key(99), tokens))
    return dcfg, dparams


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.configure("")  # never leak a plan into the next test


def _mk_requests(n, *, seed=0, max_new=8, mixed=True):
    """Mixed greedy and sampled requests — sampled rows prove the per-
    request key chain survives speculation/disaggregation bit-for-bit."""
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
    """Non-spec, non-disagg baseline every variant is compared against."""
    _, params, policy = trained
    _, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                         chunk_size=4, max_len=20)
    return out


# ------------------------------------------------------- acceptance rule


def test_acceptance_full_accept_gets_bonus():
    """All k proposals match and nothing stops: k+1 tokens emitted — the
    final verify step is the bonus token."""
    sampled = [[5, 6, 7]]
    proposed = [[5, 6]]  # proposed[j] is the guess for sampled[j]
    done = [[False, False, False]]
    live, emitted = spec_acceptance(sampled, proposed, done)
    np.testing.assert_array_equal(live, [[True, True, True]])
    np.testing.assert_array_equal(emitted, [3])


def test_acceptance_first_mismatch_emits_one():
    """Step 0 is always emitted (it is the target's own sample); a
    mismatched first proposal kills every later step."""
    live, emitted = spec_acceptance([[5, 6, 7]], [[4, 6]],
                                    [[False, False, False]])
    np.testing.assert_array_equal(live, [[True, False, False]])
    np.testing.assert_array_equal(emitted, [1])


def test_acceptance_mid_mismatch():
    live, emitted = spec_acceptance([[5, 6, 7, 8]], [[5, 9, 7]],
                                    [[False] * 4])
    np.testing.assert_array_equal(live, [[True, True, False, False]])
    np.testing.assert_array_equal(emitted, [2])


def test_acceptance_done_cuts_round_even_on_match():
    """EOS/length at step j ends the round even when the proposal
    matched — decode must not run past a finished sequence."""
    live, emitted = spec_acceptance([[5, 6, 7]], [[5, 6]],
                                    [[True, False, False]])
    np.testing.assert_array_equal(live, [[True, False, False]])
    np.testing.assert_array_equal(emitted, [1])
    live, emitted = spec_acceptance([[5, 6, 7]], [[5, 6]],
                                    [[False, True, False]])
    np.testing.assert_array_equal(emitted, [2])


def test_acceptance_batched_rows_independent():
    sampled = [[5, 6, 7], [1, 2, 3]]
    proposed = [[5, 6], [9, 2]]
    done = [[False] * 3, [False] * 3]
    _, emitted = spec_acceptance(sampled, proposed, done)
    np.testing.assert_array_equal(emitted, [3, 1])


def test_acceptance_shape_validation():
    with pytest.raises(ValueError):
        spec_acceptance([[1, 2]], [[1, 2]], [[False, False]])


def test_check_draft_config_contract():
    check_draft_config(CFG, draft_config_for(CFG))
    import dataclasses
    bad = dataclasses.replace(draft_config_for(CFG), num_tokens=64)
    with pytest.raises(ValueError, match="num_tokens"):
        check_draft_config(CFG, bad)
    bad = dataclasses.replace(draft_config_for(CFG), window_size=8)
    with pytest.raises(ValueError, match="window_size"):
        check_draft_config(CFG, bad)


# --------------------------------------------------- token identity: spec


def test_spec_identity_draft_token_identity(trained, clean):
    """The acceptance criterion: greedy AND sampled spec output equals
    non-spec token-for-token.  Identity draft (draft == target) means
    every proposal matches, so accepted-tokens/round must exceed 1."""
    _, params, policy = trained
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, spec=True, spec_k=3)
    assert out == clean
    ctr = eng.spec_counters()
    assert ctr["spec_verify_rounds"] > 0
    assert ctr["accepted_tokens_per_round"] > 1.0


def test_spec_tiny_draft_token_identity(trained, tiny_draft, clean):
    """A random quarter-width draft disagrees with the target almost
    always — output must STILL be token-identical (the draft can only
    cost throughput, never correctness)."""
    _, params, policy = trained
    dcfg, dparams = tiny_draft
    eng, out = _run_engine(
        params, policy, _mk_requests(5), num_slots=2, chunk_size=4,
        max_len=20, spec=True, spec_k=3, draft_config=dcfg,
        draft_params=dparams)
    assert out == clean
    assert eng.spec_counters()["spec_verify_rounds"] > 0


def test_spec_paged_token_identity(trained, clean):
    """Spec over the paged gate cache: pool writes are live-masked inside
    the step, ring keys merge-rolled-back — same tokens either way."""
    _, params, policy = trained
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, spec=True, spec_k=2,
                           paged=True, page_size=4)
    assert out == clean
    assert eng.spec_counters()["accepted_tokens_per_round"] > 1.0


def test_spec_tp2_sharded_smoke(trained, devices8):
    """Spec decode runs SPMD over a tensor-parallel mesh and matches the
    NON-spec engine on the same mesh token-for-token.  (Sharded and
    unsharded runs differ — tp changes reduction order — so the spec
    contract is compared within the sharded regime, mirroring
    test_engine_tp2_sharded_smoke.)"""
    from progen_tpu.core import MeshConfig, make_mesh
    from progen_tpu.parallel.sharding import param_shardings

    model, params, policy = trained
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2), devices=devices8)
    strategies = ("fsdp", "tp")
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    shardings = param_shardings(model, tokens, mesh, strategies)["params"]
    kw = dict(num_slots=2, chunk_size=4, max_len=20, mesh=mesh,
              strategies=strategies, params_shardings=shardings)
    _, base = _run_engine(params, policy, _mk_requests(5), **kw)
    _, out = _run_engine(params, policy, _mk_requests(5), spec=True,
                         spec_k=2, **kw)
    assert out == base


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


def test_spec_plus_disagg_token_identity(trained, clean):
    """The full stack: draft prefill rides the handoff handle, spec
    decode admits from the queue — still bit-exact."""
    _, params, policy = trained
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, spec=True, spec_k=2,
                           disagg=True)
    assert out == clean
    assert eng.spec_counters()["accepted_tokens_per_round"] > 1.0


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


def test_spec_snapshot_restore_parity(trained, clean, tmp_path):
    """snapshot -> kill -> restore -> replay with spec ON is token-
    identical: per-request seed determinism survives speculation."""
    _, params, policy = trained
    kw = dict(num_slots=2, chunk_size=4, max_len=20, spec=True, spec_k=2)
    eng = ServingEngine(CFG, params, policy=policy, **kw)
    for r in _mk_requests(5):
        eng.submit(r)
    for _ in range(2):
        eng.step()  # some finished, some mid-decode, some queued
    path = str(tmp_path / "snap.json")
    eng.snapshot(path)
    pre = {c.uid: (c.tokens.tolist(), c.status) for c in eng.completions}

    fresh = ServingEngine(CFG, params, policy=policy, **kw)
    n = fresh.restore(path)
    assert n == 5 - len(pre)
    post = {c.uid: (c.tokens.tolist(), c.status)
            for c in fresh.run_until_idle(max_chunks=300)}
    assert {**pre, **post} == clean


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


def test_chaos_verify_fault_token_identity(trained, clean):
    """A transient fault inside the fused verify program (the spec
    engine's serve.decode_chunk equivalent) is retried in place: state
    only advances on success, output stays token-identical."""
    _, params, policy = trained
    faults.configure("serve.verify:io_error:at=2", seed=1)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, spec=True, spec_k=2)
    assert out == clean
    assert eng.robust.faults_contained >= 1
    assert eng.robust.failed_faults == 0


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
    """Spec + disagg under the standard chaos plan points that exist in
    this pipeline: prefill-worker and verify faults, all contained."""
    _, params, policy = trained
    faults.configure("serve.prefill:unavailable:at=1;"
                     "serve.verify:io_error:at=2", seed=3)
    eng, out = _run_engine(params, policy, _mk_requests(5), num_slots=2,
                           chunk_size=4, max_len=20, spec=True, spec_k=2,
                           disagg=True)
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
