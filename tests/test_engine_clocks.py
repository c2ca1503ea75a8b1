"""The engine's and the trainer's clocks read when the work is done.

A dispatch returns before the device has run, so a stage's time, a
request's first-token instant and the idle-gap names all hang on ONE host
fetch: the slot flags in ``_harvest_done``.  These tests hold the engine to
that (stage times and first tokens end at the fetch, no fetch is added, the
tokens do not change with the ring on) and the tracer to its one span call
that feeds the profiler's annotation and the ring alike.
"""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode import engine as engine_mod
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.observe import trace as trace_mod
from progen_tpu.observe.trace import Tracer, configure_tracing, get_tracer
from progen_tpu.parallel import unbox

pytestmark = pytest.mark.serving

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=3, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)
MODES = {
    "dense": dict(num_slots=2, chunk_size=4, max_len=20),
    "paged": dict(num_slots=2, chunk_size=4, max_len=20, paged=True,
                  page_size=4),
}
SLEEP = 0.05


@pytest.fixture(scope="module")
def served():
    policy = make_policy(False)
    model = ProGen(config=CFG, policy=policy)
    params = unbox(model.init(jax.random.key(7),
                              jnp.zeros((2, CFG.seq_len), jnp.int32)))
    return params, policy


@pytest.fixture
def ring():
    """The process tracer's ring on for one test, off and empty after."""
    tracer = configure_tracing(enabled=True)
    tracer.clear()
    yield tracer
    tracer.clear()
    configure_tracing(enabled=False)


def _requests(n, *, max_new=6, seed=0):
    """Requests that cannot end early: end of sequence is masked out."""
    rng = np.random.default_rng(seed)
    mask = np.ones((max_new, CFG.num_tokens), bool)
    mask[:, 0] = False
    return [Request(uid=i, max_new_tokens=max_new, temperature=0.0, seed=i,
                    tokens=rng.integers(1, CFG.num_tokens,
                                        int(rng.integers(2, 7))).tolist(),
                    logit_mask=mask)
            for i in range(n)]


def _engine(served, mode, **kw):
    params, policy = served
    return ServingEngine(CFG, params, policy=policy, **{**MODES[mode], **kw})


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


# ------------------------------------------------- (a) stages end at the fetch


@pytest.mark.parametrize("mode", sorted(MODES))
def test_stage_times_end_at_the_flags_fetch(served, mode, monkeypatch):
    """With the host fetch slowed by 50 ms, an admitting step books it to
    ``prefill_s`` and a chunk step to ``decode_chunk_s``: a time taken at
    dispatch return would not see it."""
    real = engine_mod._host_fetch

    def slow_fetch(tree):
        time.sleep(SLEEP)
        return real(tree)

    eng = _engine(served, mode)
    eng.aot_warmup()
    before = {k: h.sum for k, h in eng._stage_hist.items()}
    monkeypatch.setattr(engine_mod, "_host_fetch", slow_fetch)
    eng.submit(_requests(1)[0])
    eng.step()          # admission, its fetch, a chunk, its fetch
    assert eng.stage_seconds["prefill_s"] >= SLEEP
    assert eng.stage_seconds["decode_chunk_s"] >= SLEEP
    chunk_only = eng.stage_seconds["decode_chunk_s"]
    prefill = eng.stage_seconds["prefill_s"]
    eng.step()          # nothing queued: a chunk step alone
    assert eng.stage_seconds["decode_chunk_s"] >= chunk_only + SLEEP
    assert eng.stage_seconds["prefill_s"] == prefill
    for stage in ("prefill_s", "decode_chunk_s"):
        assert (eng._stage_hist[stage].sum - before[stage]
                == pytest.approx(eng.stage_seconds[stage], abs=1e-9))
    assert not eng._open_stages


# ------------------------------------------------- (b) the request's timeline


def _timed_run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    steps, done = [], []
    while eng.has_work:
        t0 = time.perf_counter()
        done.extend(eng.step())
        steps.append((t0, time.perf_counter()))
        assert len(steps) < 300
    return steps, {c.uid: c for c in done}


def _check_timeline(comps, steps, spans):
    waits = [s for s in spans if s["name"] == "serve.device_wait"]
    for c in comps.values():
        assert c.ok
        assert (c.submit_time <= c.admit_time <= c.first_token_time
                <= c.finish_time)
        assert c.queue_wait == c.admit_time - c.submit_time
        assert c.ttft == c.first_token_time - c.submit_time
        # the first token is known at the return of the fetch that
        # followed the request's FIRST admission dispatch, not before
        admits = [s for s in spans if s["name"] == "serve.admit_prefill"
                  and c.uid in s["args"]["uids"]]
        wait = next(w for w in waits if w["ts"] >= admits[0]["ts"])
        assert wait["args"]["after"] == "admit"
        assert c.first_token_time >= wait["ts"] + wait["dur"]
        assert wait["args"]["step"] == admits[0]["args"]["step"]
        # it waited at least as long as the steps it sat out in the queue
        sat_out = sum(e - s for s, e in steps if e <= c.admit_time)
        assert c.queue_wait >= sat_out
        builds = [s for s in spans if s["name"] == "serve.admit_build"
                  and c.uid in s["args"].get("uids", ())]
        assert builds[0]["ts"] <= c.admit_time <= (builds[0]["ts"]
                                                   + builds[0]["dur"])


@pytest.mark.parametrize("mode", sorted(MODES))
def test_request_timeline_is_ordered_and_ends_at_fetches(served, mode, ring):
    eng = _engine(served, mode)
    q0, t0 = eng._queue_wait_hist.count, eng._ttft_hist.count
    steps, comps = _timed_run(eng, _requests(5))
    assert sorted(comps) == [0, 1, 2, 3, 4]
    _check_timeline(comps, steps, ring.ring())
    # two slots: the third request on sat out at least one whole step
    assert all(comps[u].queue_wait >= steps[0][1] - steps[0][0]
               for u in (2, 3, 4))
    # observed once per request
    assert eng._queue_wait_hist.count - q0 == 5
    assert eng._ttft_hist.count - t0 == 5
    assert not eng._ttft and not eng._admitted


def test_timeline_keeps_the_earliest_stamps_across_evict_and_replay(
        served, ring):
    """A starved page pool evicts the youngest slot back to the queue; its
    replay is admitted again, and the completion keeps the FIRST admission
    and first-token instants."""
    eng = _engine(served, "paged", num_slots=3, num_pages=2 + 5,
                  prefix_cache=False)
    q0 = eng._queue_wait_hist.count
    steps, comps = _timed_run(eng, _requests(4, max_new=10, seed=3))
    assert eng.evictions > 0
    spans = ring.ring()
    _check_timeline(comps, steps, spans)
    replayed = [u for u in comps if sum(
        1 for s in spans if s["name"] == "serve.admit_prefill"
        and u in s["args"]["uids"]) > 1]
    assert replayed
    for u in replayed:
        second = [s for s in spans if s["name"] == "serve.admit_build"
                  and u in s["args"].get("uids", ())][1]
        assert comps[u].admit_time < second["ts"]
        assert comps[u].first_token_time < second["ts"]
    assert eng._queue_wait_hist.count - q0 == 4


# ------------------------------------------------- (c) no fetch is added


# host fetches per step() of the parent commit for the script below (two
# slots, three requests of six tokens, chunks of four): the flags after
# admission, the flags after the chunk, and the sequence buffer whenever a
# slot finished; the paged engine also reads ``pos`` before each chunk
PARENT_FETCHES = {
    "dense": [2, 3, 2, 3],
    "paged": [3, 4, 3, 4],
}


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("ring_on", [False, True], ids=["ring-off", "ring-on"])
def test_host_fetches_per_step_are_the_parents(served, mode, ring_on,
                                               monkeypatch):
    calls = {"fetch": 0, "device_get": 0}
    real_fetch, real_get = engine_mod._host_fetch, jax.device_get

    def fetch(tree):
        calls["fetch"] += 1
        return real_fetch(tree)

    def device_get(x):
        calls["device_get"] += 1
        return real_get(x)

    configure_tracing(enabled=ring_on)
    try:
        eng = _engine(served, mode)
        monkeypatch.setattr(engine_mod, "_host_fetch", fetch)
        monkeypatch.setattr(jax, "device_get", device_get)
        for r in _requests(3):
            eng.submit(r)
        per_step = []
        while eng.has_work:
            before = dict(calls)
            eng.step()
            per_step.append((calls["fetch"] - before["fetch"],
                             calls["device_get"] - before["device_get"]))
    finally:
        configure_tracing(enabled=False)
        get_tracer().clear()
    # every device_get of a step is one of the engine's batched fetches
    assert [f for f, _ in per_step] == PARENT_FETCHES[mode]
    assert [g for _, g in per_step] == PARENT_FETCHES[mode]


# ------------------------------------------------- (d) one call, two sinks


def test_span_feeds_annotation_and_ring_and_annotates_with_ring_off():
    _Annotation.log = []
    t = Tracer(enabled=True, annotation=_Annotation)
    with t.span("outer", trace=3, kind="x") as sp:
        with t.span("inner"):
            pass
        sp.note(found=2)
    t.add("back-dated", 0.0, 1.0)        # the ring alone
    assert _Annotation.log == [("enter", "outer"), ("enter", "inner"),
                               ("exit", "inner"), ("exit", "outer")]
    ring = {s["name"]: s for s in t.ring()}
    assert set(ring) == {"outer", "inner", "back-dated"}
    assert ring["outer"]["args"] == {"kind": "x", "found": 2}
    assert ring["outer"]["trace"] == 3
    assert (ring["outer"]["ts"] <= ring["inner"]["ts"]
            and ring["inner"]["ts"] + ring["inner"]["dur"]
            <= ring["outer"]["ts"] + ring["outer"]["dur"])
    # ring off: the annotation is still opened, nothing is recorded
    _Annotation.log = []
    off = Tracer(annotation=_Annotation)
    with off.span("quiet", uids=[1]) as sp:
        sp.note(more=1)
    assert _Annotation.log == [("enter", "quiet"), ("exit", "quiet")]
    assert off.ring() == []
    # the process tracer annotates through the profiler
    assert get_tracer().annotation is trace_mod._jax_annotation


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_engine_span_has_its_annotation(served, mode, ring,
                                              monkeypatch):
    _Annotation.log = []
    monkeypatch.setattr(ring, "annotation", _Annotation)
    eng = _engine(served, mode)
    _timed_run(eng, _requests(3))
    spans = ring.ring()
    names = {s["name"] for s in spans}
    assert {"serve.admit_build", "serve.admit_prefill", "serve.device_wait",
            "serve.decode_chunk", "serve.harvest", "serve.admit_work",
            "serve.chunk_work"} <= names
    entered = [n for kind, n in _Annotation.log if kind == "enter"]
    exited = [n for kind, n in _Annotation.log if kind == "exit"]
    # the back-dated work spans and the instant events are the ring's alone
    ring_only = {"serve.admit_work", "serve.chunk_work"}
    timed = [s["name"] for s in spans
             if s["name"] not in ring_only and s["dur"] > 0.0]
    assert sorted(entered) == sorted(exited) == sorted(timed)
    # children carry the step they belong to; the work spans end at a fetch
    waits = [s for s in spans if s["name"] == "serve.device_wait"]
    for s in spans:
        if s["name"].startswith("serve.") and s["dur"] > 0.0:
            assert s["args"]["step"] >= 1
        if s["name"] in ring_only:
            end = s["ts"] + s["dur"]
            assert any(w["ts"] + w["dur"] <= end <= w["ts"] + w["dur"] + 1e-3
                       for w in waits)
    assert {w["args"]["after"] for w in waits} <= {"admit", "chunk", "idle"}


def test_trace_module_imports_without_jax():
    """The watchdog dumps the ring from a stdlib-only path."""
    path = os.path.abspath(trace_mod.__file__)
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('t', {path!r})\n"
        "t = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(t)\n"
        "tr = t.Tracer(enabled=True)\n"
        "with tr.span('a'):\n"
        "    pass\n"
        "t.get_tracer().add('b', 0.0, 1.0)\n"
        "assert len(tr.ring()) == 1 and t.get_tracer().ring() == []\n"
        "assert 'jax' not in sys.modules, 'trace.py pulled in jax'\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True)
    assert out.returncode == 0, out.stderr


# ------------------------------------------------- (f) tokens do not change


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tokens_identical_with_ring_on_and_off(served, mode):
    def run(enabled):
        configure_tracing(enabled=enabled)
        try:
            eng = _engine(served, mode)
            reqs = [Request(uid=i, tokens=[3 + i, 5, 7], max_new_tokens=8,
                            top_k=8, temperature=0.9, seed=40 + i)
                    for i in range(5)]
            _, comps = _timed_run(eng, reqs)
            spans = get_tracer().ring()
        finally:
            configure_tracing(enabled=False)
            get_tracer().clear()
        return {u: c.tokens.tolist() for u, c in comps.items()}, spans

    off, no_spans = run(False)
    on, spans = run(True)
    assert on == off and len(on) == 5
    assert no_spans == [] and spans


# ------------------------------------------------- (e) the feed's wait


@pytest.mark.parametrize("superstep", [1, 2], ids=["per-step", "superstep"])
def test_feed_wait_lies_inside_step_dispatch(superstep, tmp_path, ring,
                                             monkeypatch):
    """``train.feed_wait`` is a child of ``train.step_dispatch`` in both
    loops: the dispatch span keeps its extent, the child says how much of
    it waited for data.  Both open the profiler's annotation too."""
    from progen_tpu.data import shard_filename, write_tfrecord
    from progen_tpu.observe import Tracker
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    cfg = ProGenConfig(
        num_tokens=128, dim=16, seq_len=16, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
    )
    rng = np.random.default_rng(0)
    records = [bytes(rng.integers(65, 90, rng.integers(6, 14)))
               for _ in range(32)]
    data = tmp_path / "data"
    data.mkdir()
    write_tfrecord(data / shard_filename(0, 32, "train"), records)
    write_tfrecord(data / shard_filename(0, 8, "valid"), records[:8])
    _Annotation.log = []
    monkeypatch.setattr(ring, "annotation", _Annotation)
    trainer = Trainer(
        model_config=cfg,
        cfg=TrainerConfig(
            batch_size=2, grad_accum_every=2, epochs=50, learning_rate=1e-3,
            validate_every=1000, sample_every=1000, checkpoint_every=1000,
            mixed_precision=False, log_every=2, max_steps=4,
            superstep=superstep, warm_sampler=False),
        data_path=str(data), checkpoint_path=str(tmp_path / "ckpt"),
        tracker=Tracker(out_dir=str(tmp_path / "runs")), use_mesh=False)
    assert trainer.run()["step"] == 4
    trainer.store.close()
    spans = ring.ring()
    dispatches = [s for s in spans if s["name"] == "train.step_dispatch"]
    feeds = [s for s in spans if s["name"] == "train.feed_wait"]
    # per step: one dispatch of two micro-batches; fused: one per span
    assert len(dispatches) == 4 // superstep
    assert len(feeds) == (8 if superstep == 1 else len(dispatches))
    for f in feeds:
        parent = [d for d in dispatches
                  if d["ts"] <= f["ts"]
                  and f["ts"] + f["dur"] <= d["ts"] + d["dur"]]
        assert len(parent) == 1
        assert parent[0]["args"]["step"] == f["args"]["step"]
    entered = [n for kind, n in _Annotation.log if kind == "enter"]
    assert entered.count("train.feed_wait") == len(feeds)
    assert entered.count("train.step_dispatch") == len(dispatches)
    assert entered.count("train.log") == 2
    # the flight recorder still gets every phase, whatever the ring does
    kinds = [e["kind"] for e in trainer._recorder.snapshot()]
    assert kinds.count("train.step_dispatch") == len(dispatches)
    assert kinds.count("train.log") == 2 and "train.feed_wait" not in kinds
