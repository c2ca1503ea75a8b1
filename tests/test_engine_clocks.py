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
from progen_tpu.observe import compiles
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
    # the back-dated work spans, the step records, the instant events and
    # the three kinds of incident (this engine's programs compile as it
    # runs) are the ring's alone.  Spans are told apart BY NAME and counted,
    # never by a duration: on a loaded machine nothing about a clock's
    # reading is certain but its order
    ring_only = {"serve.admit_work", "serve.chunk_work", "serve.step"}
    incidents = {"xla.compile", "host.gc", "serve.slow_step"}
    instants = {"serve.submit", "serve.submit_embed", "serve.submit_fork",
                "serve.shed", "serve.preempt"}
    timed = [s["name"] for s in spans
             if s["name"] not in ring_only | incidents | instants]
    assert sorted(entered) == sorted(exited) == sorted(timed)
    assert all(s["dur"] == 0.0 for s in spans if s["name"] in instants)
    # children carry the step they belong to; a work span ends no earlier
    # than a fetch of its own step returned
    waits = [s for s in spans if s["name"] == "serve.device_wait"]
    for s in spans:
        if s["name"].startswith("serve.") and s["name"] not in instants:
            assert s["args"]["step"] >= 1
        if s["name"] in ("serve.admit_work", "serve.chunk_work"):
            end = s["ts"] + s["dur"]
            assert any(w["args"]["step"] == s["args"]["step"]
                       and w["ts"] + w["dur"] <= end for w in waits)
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


# ------------------------------------------------- (g) what no span owns


@pytest.fixture
def watched(observers):
    """``observers`` with the collector held still, so that a full pass
    over the test process's heap (its own test: ``test_incidents.py``) is
    not this engine's incident."""
    import gc

    gc.collect()
    gc.disable()
    yield observers
    gc.enable()


def _value(registry, name):
    return registry.snapshot()[name]["value"]


def _warm_engine(served, **kw):
    """Two slots, the 4-token bucket and every other program compiled, and
    enough steps behind it for the slow-step rule's means to judge: ten
    that admit two requests and ten that admit none."""
    eng = _engine(served, "dense", **kw)
    eng.aot_warmup(max_prime=4)
    for r in _requests(20, seed=3):
        r.tokens = r.tokens[:3]
        eng.submit(r)
    eng.run_until_idle()
    eng.completions.clear()
    return eng


def test_a_warmed_engine_files_nothing_then_a_cold_bucket_files_its_compile(
        served, watched):

    registry, tracer = watched
    eng = _warm_engine(served)
    assert compiles.installed()     # the constructor's doing
    tracer.clear()
    assert _value(registry, "engine.compiles_in_step") == 0
    steps_before = _value(registry, "engine.steps")
    uid = 100
    for _ in range(50):
        while eng.pending < 2:
            r, = _requests(1, seed=uid)
            r.uid, r.tokens = uid, r.tokens[:3]
            eng.submit(r)
            uid += 1
        eng.step()
    assert _value(registry, "engine.steps") == steps_before + 50
    assert tracer.incidents() == []
    assert _value(registry, "engine.compiles_in_step") == 0
    assert eng.status()["compiles_in_step"] == 0
    # no step in progress between steps
    assert getattr(compiles._thread, "step", None) is None
    eng.run_until_idle()
    # a prime of 6 tokens: the 8-token bucket was not warmed
    cold, = _requests(1, seed=1)
    cold.uid, cold.tokens = 999, [3, 4, 5, 6, 7, 8]
    eng.submit(cold)
    eng.step()
    filed = [i for i in tracer.incidents() if i["name"] == "xla.compile"]
    # the process's number of that step: the counter a reader counts from
    step = _value(registry, "engine.steps")
    assert filed and all(i["args"]["step"] == step for i in filed)
    assert any("_admit" in i["args"]["program"] for i in filed)
    assert _value(registry, "engine.compiles_in_step") == len(filed)
    assert eng.status()["compiles_in_step"] == len(filed)
    # the step that compiled stood still, and says so itself
    slow = [i for i in tracer.incidents() if i["name"] == "serve.slow_step"]
    for i in slow:
        assert i["args"]["compiles"] == len(filed)
        assert i["args"]["step"] == step


def test_a_slow_callback_is_one_host_incident_with_its_excess(
        served, watched):
    registry, tracer = watched
    eng = _warm_engine(served)
    tracer.clear()
    r, = _requests(1, seed=5)
    r.tokens = r.tokens[:3]
    r.on_complete = lambda comp: time.sleep(0.2)
    eng.submit(r)
    eng.run_until_idle()
    incident, = tracer.incidents()
    args = incident["args"]
    assert incident["name"] == "serve.slow_step" and args["which"] == "host"
    assert args["excess"] == pytest.approx(0.2, rel=0.2)
    assert args["host"] >= 0.2 and args["wall"] >= args["host"]
    assert args["wall"] == pytest.approx(
        args["host"] + args["device_wait"], abs=1e-6)
    assert args["compiles"] == 0 and args["gc_s"] == 0.0
    assert 1 <= args["step"] <= _value(registry, "engine.steps")
    # the slow step stayed out of the mean it was judged by
    assert all(mean.mean < 0.05 for mean in eng._mean_host.values())


def test_a_pause_between_steps_is_a_gap_and_an_idle_loop_is_none(
        served, watched):
    registry, tracer = watched
    eng = _warm_engine(served)
    tracer.clear()
    for r in _requests(2, seed=6):
        r.tokens = r.tokens[:3]
        eng.submit(r)
    eng.step()
    assert eng.has_work
    time.sleep(0.2)     # the caller holds the engine up with work waiting
    eng.step()
    incident, = tracer.incidents()
    args = incident["args"]
    assert incident["name"] == "serve.slow_step" and args["which"] == "gap"
    assert args["gap"] == pytest.approx(0.2, rel=0.2)
    assert args["excess"] == pytest.approx(0.2, rel=0.2)
    eng.run_until_idle()
    tracer.clear()
    # an open loop with nothing to do sleeps: not a stall
    assert not eng.has_work
    time.sleep(0.2)
    r, = _requests(1, seed=7)
    r.uid, r.tokens = 50, r.tokens[:3]
    eng.submit(r)
    eng.run_until_idle()
    assert tracer.incidents() == []


def test_a_slow_device_is_a_device_incident_of_its_program(
        served, watched, monkeypatch):
    registry, tracer = watched
    eng = _warm_engine(served)
    tracer.clear()
    real = engine_mod._host_fetch
    slow = {"in": 0}

    def fetch(tree):
        slow["in"] -= 1
        if slow["in"] == 0:     # the device is not done yet
            time.sleep(0.2)
        return real(tree)

    monkeypatch.setattr(engine_mod, "_host_fetch", fetch)
    for r in _requests(2, seed=8):
        r.tokens = r.tokens[:3]
        eng.submit(r)
    eng.step()
    # a chunk step alone: a flags fetch with nothing in flight, the chunk's
    # dispatch, then the flags fetch that waits for it — held up
    slow["in"] = 2
    eng.step()
    eng.run_until_idle()
    incident, = tracer.incidents()
    args = incident["args"]
    assert args["which"] == "device" and args["device_wait"] >= 0.2
    assert args["excess"] == pytest.approx(0.2, rel=0.2)
    assert args["host"] < 0.05
    assert [name for name, _ in args["stages"]] == ["chunk"]


def test_prefill_rounds_outside_a_step_keep_no_stage(served, watched):
    """A prefill worker runs rounds for the life of its process and never
    steps: nothing of a round may be kept for a step that never comes."""
    registry, tracer = watched
    eng = _engine(served, "dense", disagg=True)
    steps_before = _value(registry, "engine.steps")
    for r in _requests(6, seed=11):
        eng.submit(r)
    handles = 0
    while eng.pending:
        handles += eng.run_prefill_round() is not None
    assert handles >= 3 and eng._step_stages is None
    assert eng.stage_seconds["prefill_s"] > 0      # the stage's clock ran
    assert eng._mean_stage == {} and eng._admit_pads == []
    assert _value(registry, "engine.steps") == steps_before
    assert [i for i in tracer.incidents()
            if i["name"] == "serve.slow_step"] == []
    # the same engine stepping judges its prefill rounds by their bucket
    for r in _requests(4, seed=12):
        r.uid += 100
        eng.submit(r)
    eng.run_until_idle()
    assert eng._step_stages is None
    assert {("chunk", 2)} < set(eng._mean_stage) <= {
        ("chunk", 1), ("chunk", 2), ("prefill", 4), ("prefill", 8)}


def test_a_step_that_raises_leaves_nothing_to_the_next(served, watched,
                                                       monkeypatch):
    registry, tracer = watched
    eng = _warm_engine(served)
    tracer.clear()
    for r in _requests(2, seed=13):
        r.tokens = r.tokens[:3]
        eng.submit(r)
    eng.step()
    assert eng.has_work and eng._last_return is not None
    real = eng._dispatch_chunk

    def broken():
        real()                      # its stages close, then it fails
        raise RuntimeError("lost the device")

    monkeypatch.setattr(eng, "_dispatch_chunk", broken)
    with pytest.raises(RuntimeError):
        eng.step()
    assert eng._step_stages is None
    assert getattr(compiles._thread, "step", None) is None
    # the failed step's seconds are not the next step's gap
    assert eng._last_return is None
    monkeypatch.setattr(eng, "_dispatch_chunk", real)
    eng.run_until_idle()
    assert tracer.incidents() == []


def test_two_engines_number_their_steps_in_one_sequence(served, watched):
    """An incident's ``step`` is the PROCESS's count of ``step()`` calls
    (the counter ``engine.steps``), whichever engine made them: a reader
    that takes the last N of that counter takes the right incidents."""
    registry, tracer = watched
    first, second = _warm_engine(served), _warm_engine(served)
    tracer.clear()
    r, = _requests(1, seed=5)
    r.tokens = r.tokens[:3]
    r.on_complete = lambda comp: time.sleep(0.2)
    second.submit(r)
    second.run_until_idle()
    incident, = tracer.incidents()
    assert incident["args"]["which"] == "host"
    total = _value(registry, "engine.steps")
    assert total == first._step_no + second._step_no
    assert second._step_no < incident["args"]["step"] <= total


def test_admission_and_chunk_counts_against_hand_counts(served, watched,
                                                        monkeypatch):
    registry, tracer = watched
    monkeypatch.setattr(engine_mod, "SLOTS_PER_ADMIT_ROW", 2)
    eng = _engine(served, "dense", num_slots=4)
    assert eng.admit_rows == 2
    reqs = _requests(3, max_new=6, seed=2)
    for r, n in zip(reqs, (2, 5, 6)):     # buckets 4, 8, 8
        r.tokens = list(range(3, 3 + n))
        eng.submit(r)
    eng.step()
    # two runs of two rows: (2, 5) padded to 8 and (6,) padded to 8
    assert eng._admit_rows_hist.count == 2
    assert _value(registry, "engine.prefill_tokens_real") == 2 + 5 + 6
    assert _value(registry, "engine.prefill_token_slots") == 2 * 8 + 2 * 8
    rows = registry.snapshot()["engine.chunk_rows"]
    assert (rows["count"], rows["sum"]) == (1, 3)
    eng.run_until_idle()
    rows = registry.snapshot()["engine.chunk_rows"]
    assert rows["count"] == eng.chunks_run and rows["max"] == 3
    assert _value(registry, "engine.prefill_tokens_real") == 13
    # the stage's regime is the admission group by its padded lengths, the
    # chunk program by the power of two at or above its rows in flight
    assert {("admit", 8, 8), ("chunk", 4)} <= set(eng._mean_stage)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tokens_identical_with_and_without_the_listeners(served, mode):

    def run(listening):
        eng = _engine(served, mode)     # installs them
        if not listening:
            compiles.uninstall()
        reqs = [Request(uid=i, tokens=[3 + i, 5, 7], max_new_tokens=8,
                        top_k=8, temperature=0.9, seed=40 + i)
                for i in range(5)]
        _, comps = _timed_run(eng, reqs)
        return {u: c.tokens.tolist() for u, c in comps.items()}

    was = compiles.installed()
    try:
        off, on = run(False), run(True)
    finally:
        compiles.uninstall()
        if was:
            compiles.install()
    assert on == off and len(on) == 5


def test_engines_share_one_set_of_listeners(served):
    import gc

    from jax._src import monitoring


    was = compiles.installed()
    try:
        for _ in range(3):
            _engine(served, "dense")
        assert monitoring.get_event_duration_listeners().count(
            compiles._on_duration) == 1
        assert monitoring.get_event_listeners().count(
            compiles._on_event) == 1
        assert gc.callbacks.count(compiles._on_gc) == 1
    finally:
        compiles.uninstall()
        if was:
            compiles.install()
    assert compiles._on_gc not in gc.callbacks or was


def test_trainer_counts_no_recompile_and_leaves_no_step_behind(
        tmp_path, watched):
    from progen_tpu.data import shard_filename, write_tfrecord
    from progen_tpu.observe import Tracker
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    registry, tracer = watched
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
    trainer = Trainer(
        model_config=cfg,
        cfg=TrainerConfig(
            batch_size=2, epochs=50, learning_rate=1e-3,
            validate_every=1000, sample_every=1000, checkpoint_every=1000,
            mixed_precision=False, log_every=2, max_steps=5,
            warm_sampler=False),
        data_path=str(data), checkpoint_path=str(tmp_path / "ckpt"),
        tracker=Tracker(out_dir=str(tmp_path / "runs")), use_mesh=False)
    assert trainer.run()["step"] == 5
    trainer.store.close()
    assert _value(registry, "train.recompiles") == 0
    assert _value(registry, "xla.compiles") > 0
    assert getattr(compiles._thread, "step", None) is None
    # the step program compiled in the first iteration, and says which
    steps = {i["args"].get("step") for i in tracer.incidents()
             if i["name"] == "xla.compile"
             and "train_step" in i["args"]["program"]}
    assert steps == {1}


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
