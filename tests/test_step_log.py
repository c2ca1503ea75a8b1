"""Every ``step()`` leaves a record (``Tracer.step_record``): the tracer's
third store and what the engine puts in it.

The store is kept with the ring off, bounded, cleared with the ring and
carried by the dump and ``/tracez``.  The engine files ONE record a step
that did not raise, from host numbers it already held (no fetch added: that
is ``test_engine_clocks.py::test_host_fetches_per_step_are_the_parents``),
and a slow step's incident is that record plus ``which`` and ``excess``.
The slow-step rule judges a stage against its own regime: a chunk by its
rows' bucket, and a mean that refused ``SLOW_MIN_SAMPLES`` in a row takes
them as what is ordinary now.
"""

import importlib.util
import json
import os
import time
import types
import urllib.request

import pytest

from progen_tpu.decode import Request
from progen_tpu.decode import engine as engine_mod
from progen_tpu.observe.trace import STEP_CAPACITY, Tracer, get_tracer
from tests.test_engine_clocks import (  # noqa: F401  (fixtures)
    MODES,
    _engine,
    _requests,
    _timed_run,
    _value,
    _warm_engine,
    served,
    watched,
)

pytestmark = pytest.mark.serving

FIELDS = {"step", "t0", "wall", "host", "device_wait", "gap", "gc_s",
          "compiles", "stages", "t_done", "chunk_rows", "admitted",
          "admit_runs", "prefill_tokens_real", "prefill_token_slots",
          "finished"}


def _rec(step, **fields):
    return {"step": step, "t0": float(step), "wall": 0.5, **fields}


# ----------------------------------------------------------- (a) the store


@pytest.mark.parametrize("ring_on", [False, True], ids=["ring-off", "ring-on"])
def test_a_record_is_kept_whatever_the_ring_does(ring_on):
    tracer = Tracer(enabled=ring_on)
    rec = _rec(3, host=0.1, stages=[["chunk", 0.4]])
    tracer.step_record(rec)
    assert tracer.steps() == [rec]
    # the ring holds it too when it is on, as a span of the step's extent
    assert tracer.ring() == ([{"name": "serve.step", "ts": 3.0, "dur": 0.5,
                               "args": rec}] if ring_on else [])
    assert tracer.incidents() == []


def test_the_step_log_is_bounded_and_cleared_with_the_ring():
    tracer = Tracer(enabled=True)
    for i in range(STEP_CAPACITY + 44):
        tracer.step_record(_rec(i))
    kept = tracer.steps()
    assert STEP_CAPACITY >= 4096 and len(kept) == STEP_CAPACITY
    assert (kept[0]["step"], kept[-1]["step"]) == (44, STEP_CAPACITY + 43)
    tracer.clear()
    assert tracer.steps() == [] and tracer.ring() == []


def test_the_step_log_is_in_the_dump_and_on_tracez(tmp_path):
    from progen_tpu.observe.statusz import StatuszServer

    tracer = Tracer(process="unit")
    for i in range(3):
        tracer.step_record(_rec(i, gap=None, stages=[["chunk", 0.25]]))
    assert tracer.dump_obj()["steps"] == tracer.steps()
    with open(tracer.dump(str(tmp_path / "dump.json"))) as fh:
        dumped = json.load(fh)
    assert dumped["spans"] == [] and dumped["steps"] == tracer.steps()
    server = StatuszServer(role="unit", port=0, providers={"tracer": tracer})
    port = server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/tracez", timeout=10) as resp:
            body = json.loads(resp.read())
    finally:
        server.stop()
    assert body["spans"] == [] and body["steps"] == dumped["steps"]


# ------------------------------------------------ (b) one record a step


@pytest.mark.parametrize("ring_on", [False, True], ids=["ring-off", "ring-on"])
def test_one_record_a_step_with_the_same_fields_ring_on_or_off(
        served, watched, ring_on):
    registry, tracer = watched
    tracer.enabled = ring_on
    eng = _engine(served, "dense")
    for r in _requests(3):
        eng.submit(r)
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
    records = tracer.steps()
    assert len(records) == steps == _value(registry, "engine.steps")
    assert [r["step"] for r in records] == list(range(1, steps + 1))
    assert all(set(r) == FIELDS for r in records)
    spans = [s for s in tracer.ring() if s["name"] == "serve.step"]
    assert [s["args"] for s in spans] == (records if ring_on else [])
    for before, rec in zip(records, records[1:]):
        # a step begins where the one before returned, but for the caller
        assert rec["gap"] == pytest.approx(
            rec["t0"] - before["t0"] - before["wall"], abs=1e-9)
    assert records[0]["gap"] is None
    for rec in records:
        assert rec["wall"] == pytest.approx(
            rec["host"] + rec["device_wait"], abs=1e-9)
        # the last flags fetch returned inside the step, after its stages
        assert rec["t0"] < rec["t_done"] <= rec["t0"] + rec["wall"]
        assert sum(dt for _, dt in rec["stages"]) <= rec["wall"]
        assert [name for name, _ in rec["stages"]][-1:] == (
            ["chunk"] if rec["chunk_rows"] else [])
    assert sum(r["finished"] for r in records) == 3
    json.dumps(records)     # host floats, ints and strings alone


def test_record_and_incident_of_a_slow_step_agree_field_for_field(
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
    record, = [rec for rec in tracer.steps()
               if rec["step"] == incident["args"]["step"]]
    extra = {"which": "host", "excess": incident["args"]["excess"]}
    assert incident["args"] == {**record, **extra}
    assert (incident["ts"], incident["dur"]) == (record["t0"], record["wall"])
    assert record["finished"] == 1 and record["host"] >= 0.2
    # the steps that did not stand still left a record and no incident
    assert len(tracer.steps()) == 2


def test_a_step_that_raises_leaves_no_record(served, watched, monkeypatch):
    registry, tracer = watched
    eng = _warm_engine(served)
    tracer.clear()
    for r in _requests(2, seed=13):
        r.tokens = r.tokens[:3]
        eng.submit(r)
    eng.step()
    real = eng._dispatch_chunk

    def broken():
        real()
        raise RuntimeError("lost the device")

    monkeypatch.setattr(eng, "_dispatch_chunk", broken)
    with pytest.raises(RuntimeError):
        eng.step()
    monkeypatch.setattr(eng, "_dispatch_chunk", real)
    eng.run_until_idle()
    numbers = [rec["step"] for rec in tracer.steps()]
    first = numbers[0]
    # the failed step took its number with it: a hole a reader can see
    assert numbers[:2] == [first, first + 2]
    assert numbers[1:] == list(range(first + 2, first + 1 + len(numbers)))
    # what it had counted is not the next step's
    after = tracer.steps()[1]
    assert after["gap"] is None and after["admitted"] == 0


def test_a_prefill_worker_that_never_steps_keeps_no_record(served, watched):
    registry, tracer = watched
    eng = _engine(served, "dense", disagg=True)
    for r in _requests(4, seed=11):
        eng.submit(r)
    while eng.pending:
        eng.run_prefill_round()
    assert tracer.steps() == [] and eng._step_admits == []


def test_two_engines_number_their_records_in_one_sequence(served, watched):
    registry, tracer = watched
    first, second = _warm_engine(served), _warm_engine(served)
    tracer.clear()
    for eng, seed in ((first, 1), (second, 2), (first, 3)):
        r, = _requests(1, seed=seed)
        r.uid, r.tokens = seed, r.tokens[:3]
        eng.submit(r)
        eng.run_until_idle()
    numbers = [rec["step"] for rec in tracer.steps()]
    total = _value(registry, "engine.steps")
    assert total == first._step_no + second._step_no
    assert numbers == list(range(total - len(numbers) + 1, total + 1))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tokens_identical_with_the_step_log_and_without(served, mode,
                                                        monkeypatch):
    def run():
        eng = _engine(served, mode)
        reqs = [Request(uid=i, tokens=[3 + i, 5, 7], max_new_tokens=8,
                        top_k=8, temperature=0.9, seed=40 + i)
                for i in range(5)]
        _, comps = _timed_run(eng, reqs)
        return {u: c.tokens.tolist() for u, c in comps.items()}

    get_tracer().clear()
    kept = run()
    assert get_tracer().steps()
    get_tracer().clear()
    monkeypatch.setattr(Tracer, "step_record", lambda self, rec: None)
    assert run() == kept and len(kept) == 5
    assert get_tracer().steps() == []


# ------------------------------------- (c) what a record counts, by hand


def test_admitted_rows_and_token_slots_against_hand_counts(served, watched,
                                                           monkeypatch):
    registry, tracer = watched
    monkeypatch.setattr(engine_mod, "SLOTS_PER_ADMIT_ROW", 2)
    eng = _engine(served, "dense", num_slots=4)
    assert eng.admit_rows == 2
    reqs = _requests(5, max_new=6, seed=2)
    for r, n in zip(reqs, (2, 5, 6, 3, 2)):     # buckets 4, 8, 8, 4, 4
        r.tokens = list(range(3, 3 + n))
    for r in reqs[:3]:
        eng.submit(r)
    eng.step()
    first, = tracer.steps()
    # two runs of two rows: (2, 5) padded to 8 and (6,) padded to 8
    assert (first["admitted"], first["admit_runs"]) == (3, 2)
    assert first["prefill_tokens_real"] == 2 + 5 + 6
    assert first["prefill_token_slots"] == 2 * 8 + 2 * 8
    assert (first["chunk_rows"], first["finished"]) == (3, 0)
    assert [name for name, _ in first["stages"]] == [
        "('admit', 8, 8)", "chunk"]
    # one slot is free: the next step admits ONE of the two that wait, in a
    # run of two rows padded to 4, beside the three rows it carries
    for r in reqs[3:]:
        eng.submit(r)
    eng.step()
    second = tracer.steps()[-1]
    assert (second["admitted"], second["admit_runs"]) == (1, 1)
    assert second["prefill_tokens_real"] == 3
    assert second["prefill_token_slots"] == 2 * 4
    assert second["chunk_rows"] == 4
    eng.run_until_idle()
    records = tracer.steps()
    assert sum(r["admitted"] for r in records) == 5
    assert sum(r["finished"] for r in records) == 5
    assert sum(r["prefill_tokens_real"] for r in records) \
        == _value(registry, "engine.prefill_tokens_real") == 18
    assert sum(r["prefill_token_slots"] for r in records) \
        == _value(registry, "engine.prefill_token_slots")
    rows = registry.snapshot()["engine.chunk_rows"]
    assert sum(r["chunk_rows"] for r in records) == rows["sum"]
    assert sum(1 for r in records if r["chunk_rows"]) == rows["count"]


def test_status_shows_the_newest_eight_records(served, watched):
    registry, tracer = watched
    eng = _warm_engine(served)
    shown = eng.status()["last_steps"]
    assert engine_mod.LAST_STEPS == 8 and len(shown) == 8
    assert shown == tracer.steps()[-8:]
    assert shown[-1]["step"] == _value(registry, "engine.steps")
    json.dumps(shown)


def test_traceview_lists_the_steps_with_their_stages(tmp_path, capsys):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "traceview_tool", os.path.join(root, "tools", "traceview.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)
    full = dict(host=0.002, device_wait=0.448, gc_s=0.0, compiles=0,
                t_done=0.0, admit_runs=0, prefill_tokens_real=0,
                prefill_token_slots=0)
    # ring off: the dump's own store; ring on: the same steps as spans too
    for ring_on in (False, True):
        tracer = Tracer(enabled=ring_on, process="engine")
        tracer.step_record(_rec(
            7, gap=None, chunk_rows=3, admitted=3, finished=0, **full,
            stages=[["('admit', 8, 8)", 0.3], ["chunk", 0.15]]))
        tracer.step_record(_rec(8, gap=0.0004, chunk_rows=3, admitted=0,
                                finished=2, **full,
                                stages=[["chunk", 0.151]]))
        tracer.dump(str(tmp_path / "trace_engine.json"))
        assert tv.main(["--summarize", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "steps (newest 2 of 2; ms):" in out
        lines = out[out.index("steps (newest"):].splitlines()[2:]
        assert [line.split()[0] for line in lines] == ["7", "8"]
        assert lines[0].endswith("('admit', 8, 8) 300.0; chunk 150.0")
        assert lines[1].split()[:8] == [
            "8", "500.0", "2.0", "448.0", "0.4", "3", "0", "2"]


# ----------------------------------- (d) a stage is judged in its regime


def test_a_mean_takes_eight_refusals_in_a_row_as_its_regime():
    mean = engine_mod._RegimeMean()
    assert [mean.observe(0.1) for _ in range(20)] == [0.0] * 20
    # one stall, then ordinary again: refused, and the run is over
    assert mean.observe(0.4) == pytest.approx(0.3)
    assert mean.observe(0.1) == 0.0 and mean.refused == 0
    assert mean.mean == pytest.approx(0.1) and mean.n == 21
    # 2.3 times as long from here on: eight are filed, then it is the mean
    took = [mean.observe(0.23) for _ in range(60)]
    assert took[:8] == [pytest.approx(0.13)] * 8 and took[8:] == [0.0] * 52
    assert mean.mean == pytest.approx(0.23)
    # and a stall is still a stall in the new regime
    assert mean.observe(0.53) == pytest.approx(0.30)
    # under the floor, or under the factor, nothing is slow
    assert mean.observe(0.23 + 0.9 * engine_mod.SLOW_FLOOR_S) == 0.0
    low = engine_mod._RegimeMean()
    for _ in range(8):
        low.observe(0.001)
    assert low.observe(0.04) == 0.0         # forty times, under the floor
    fresh = engine_mod._RegimeMean()
    assert [fresh.observe(v) for v in (0.1,) * 7 + (5.0,)] == [0.0] * 8


class _Clock:
    """Stands in for the engine's ``time``: ten microseconds a reading,
    and whatever the test adds for the device."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        self.now += 1e-5
        return self.now


@pytest.mark.parametrize("seeded_rows", [2, 16],
                         ids=["unseen-bucket", "same-bucket"])
def test_a_regime_change_files_no_more_than_its_first_samples(
        served, watched, monkeypatch, seeded_rows):
    """Means seeded on short chunks (2 rows in flight, or 16 at a short
    context), then sixty chunks of 16 rows 2.3 times as long: the parent's
    one mean for the chunk program refused every one of them.  A bucket no
    chunk has run in judges nothing before it has its samples; a bucket
    whose time moved files ``SLOW_MIN_SAMPLES`` and takes them as its mean.
    A stall among the sixty is still one incident with its excess."""
    registry, tracer = watched
    monkeypatch.setattr(engine_mod, "SLOTS_PER_ADMIT_ROW", 4)
    clock = _Clock()
    monkeypatch.setattr(engine_mod, "time",
                        types.SimpleNamespace(perf_counter=clock.perf_counter))
    eng = _engine(served, "dense", num_slots=16)
    eng.aot_warmup(max_prime=4)
    real = engine_mod._host_fetch
    chunk_s = {"now": 0.100}
    seen = {"full": 0}

    def fetch(tree):
        # the flags fetch that waits for a chunk: the device's seconds
        if eng._open_stages and eng._open_stages[-1][1] == "chunk":
            clock.now += chunk_s["now"]
            if len(eng._inflight) == 16 and chunk_s["now"] > 0.2:
                seen["full"] += 1
                if seen["full"] == 40:
                    clock.now += 0.3        # the device stood still
        return real(tree)

    monkeypatch.setattr(engine_mod, "_host_fetch", fetch)
    uid = 0

    def serve(waves, rows):
        """``waves`` times ``rows`` requests of three chunks each."""
        nonlocal uid
        for _ in range(waves):
            for r in _requests(rows, max_new=12, seed=uid):
                r.uid, r.tokens, uid = uid, r.tokens[:3], uid + 1
                eng.submit(r)
            eng.run_until_idle()

    serve(4, seeded_rows)
    assert eng._mean_stage[("chunk", seeded_rows)].n == 12
    # the engine's programs compiled as it was built: not a step's doing
    assert {i["name"] for i in tracer.incidents()} <= {"xla.compile",
                                                        "host.gc"}
    tracer.clear()
    chunk_s["now"] = 0.230
    serve(20, 16)
    full = [rec for rec in tracer.steps() if rec["chunk_rows"] == 16]
    assert len(full) == 60 and seen["full"] == 60
    assert {i["name"] for i in tracer.incidents()} == {"serve.slow_step"}
    filed = [i["args"] for i in tracer.incidents()]
    stall = [a for a in filed if a["excess"] > 0.25]
    regime = [a for a in filed if a["excess"] <= 0.25]
    assert len(stall) == 1 and stall[0]["which"] in ("device", "host")
    assert stall[0]["excess"] == pytest.approx(0.3, rel=0.1)
    assert stall[0]["chunk_rows"] == 16
    if seeded_rows == 2:
        assert regime == []
    else:
        assert len(regime) == engine_mod.SLOW_MIN_SAMPLES
        assert all(a["which"] == "device" for a in regime)
        assert [a["step"] for a in regime] == [
            rec["step"] for rec in full[:engine_mod.SLOW_MIN_SAMPLES]]
    assert eng._mean_stage[("chunk", 16)].mean == pytest.approx(
        0.23, rel=0.05)


def test_a_step_of_sixteen_admission_runs_files_no_host_incident(
        served, watched, monkeypatch):
    """A window's first step fills every slot, one admission run a row,
    and each run costs the host its arrays and its mask: sixteen times the
    host time of the steps of one run before it, which the parent's one
    mean of the host's self time filed as a stall.  The host is judged in
    the regime of the step's admission runs, where a stall is still one."""
    registry, tracer = watched
    clock = _Clock()
    monkeypatch.setattr(engine_mod, "time",
                        types.SimpleNamespace(perf_counter=clock.perf_counter))
    eng = _engine(served, "dense", num_slots=16)
    assert eng.admit_rows == 1
    eng.aot_warmup(max_prime=4)
    take = eng._take_requests
    build_s = {"now": 0.005}

    def slow_take():
        clock.now += build_s["now"]     # the host builds a run's arrays
        return take()

    monkeypatch.setattr(eng, "_take_requests", slow_take)
    uid = 0

    def serve(rows):
        nonlocal uid
        for r in _requests(rows, max_new=8, seed=uid):
            r.uid, r.tokens, uid = uid, r.tokens[:3], uid + 1
            eng.submit(r)
        eng.run_until_idle()

    for _ in range(10):
        serve(1)
    assert eng._mean_host[1].n == 10
    assert eng._mean_host[1].mean == pytest.approx(0.005, rel=0.1)
    tracer.clear()
    serve(16)
    first = tracer.steps()[0]
    assert (first["admit_runs"], first["admitted"]) == (16, 16)
    # over the factor and over the floor of the one-run steps' mean
    assert first["host"] > 0.05 + engine_mod.SLOW_FACTOR * 0.005
    assert tracer.incidents() == []
    assert set(eng._mean_host) == {0, 1, 16}
    build_s["now"] = 0.205
    serve(1)
    incident, = tracer.incidents()
    assert incident["args"]["which"] == "host"
    assert incident["args"]["admit_runs"] == 1
    assert incident["args"]["excess"] == pytest.approx(0.2, rel=0.05)
