"""``perf/readers/step_log.py`` over a hand-made store: the window is the
last N records of the process, its two shares and what is in no stage make
up the span, the window-only
chunk mean leaves the probes out, a delivery gap counts once a carried row,
and a store that is short, stale, ahead of the runner's clock or absent (the
parent) reads ``None``."""

import pytest

from perf.lib import harness
from perf.tests.test_rehearsal import (  # noqa: F401  (checkout: fixture)
    LENGTHS,
    _add_cell,
    checkout,
)

NAMES = ("window.admit_share.backlog", "window.chunk_share.backlog",
         "window.chunk_step_ms.backlog", "window.chunk_step_ms.steady",
         "window.delivery_gap_p50_ms.backlog",
         "window.delivery_gap_p95_ms.backlog",
         "window.delivery_gap_p95_ms.steady")
T0 = 1000.0     # perf_counter at the window's opening


def _record(step, t0, wall, *, chunk=0.0, admit=0.0, rows=0, admitted=0):
    """A step of ``wall`` seconds that ran an admission group and a chunk
    back to back and fetched the flags 1 ms before it returned."""
    stages = []
    if admit:
        stages.append(["('admit', 512, 512)", admit])
    if chunk:
        stages.append(["chunk", chunk])
    return {"step": step, "t0": t0, "wall": wall, "host": 0.002,
            "device_wait": wall - 0.002, "gap": 0.0, "gc_s": 0.0,
            "compiles": 0, "stages": stages, "t_done": t0 + wall - 0.001,
            "chunk_rows": rows, "admitted": admitted,
            "admit_runs": 1 if admit else 0,
            "prefill_tokens_real": 700 * admitted,
            "prefill_token_slots": 1024 if admit else 0, "finished": 0}


@pytest.fixture
def program(monkeypatch):
    """A registry and a tracer of the test's own, as the program's."""
    from progen_tpu.observe import metrics, trace

    registry, tracer = metrics.MetricsRegistry(), trace.Tracer()
    monkeypatch.setattr(metrics, "_REGISTRY", registry)
    monkeypatch.setattr(trace, "_TRACER", tracer)
    return registry, tracer


def _fill(program):
    """Ten steps of the process: two probes' near-empty chunks and a ramp
    step at set-up, then a window of seven steps — 1.1 s of chunks at 10
    rows, 0.5 s of admissions, 0.1 s of the first step in no stage, and
    the profiler stopped for 2 s (off the runner's clock) before the last
    step."""
    registry, tracer = program
    registry.counter("engine.steps").inc(10)
    tracer.step_record(_record(1, 900.0, 0.05, chunk=0.04, rows=1))
    tracer.step_record(_record(2, 900.1, 0.05, chunk=0.04, rows=1))
    tracer.step_record(_record(3, 990.0, 0.9, chunk=0.2, admit=0.6,
                               rows=10, admitted=10))
    t, driven, off = T0, [], 0.0
    walls = [(0.2, 0.0, 0), (0.2, 0.0, 0), (0.45, 0.25, 2), (0.2, 0.0, 0),
             (0.45, 0.25, 4), (0.1, 0.0, 0), (0.1, 0.0, 0)]
    for i, (wall, admit, admitted) in enumerate(walls):
        if i == 6:
            t, off = t + 2.0, 2.0
        chunk = wall - admit - (0.0 if i else 0.1)
        tracer.step_record(_record(4 + i, t, wall, chunk=chunk, admit=admit,
                                   rows=10, admitted=admitted))
        t += wall
        # the runner reads its clock 20 us after the engine read its own
        driven.append((t - T0 - off + 20e-6, 0))
    return driven


def _obs(driven, cell="serve-base-backlog"):
    return {"workload": harness.load_workload(cell),
            "counters": {"queued": driven}, "spans": {}}


def _read(name, obs):
    spec = harness.load_metric(name)
    assert spec["reader"] == "perf/readers/step_log.py"
    return harness.load_module(spec["reader"]).read(obs, spec)


def test_the_two_shares_and_what_is_in_no_stage_make_up_the_span(program):
    obs = _obs(_fill(program))
    admit = _read("window.admit_share.backlog", obs)
    chunk = _read("window.chunk_share.backlog", obs)
    # 1.7 s of steps on the runner's clock: the profiler's 2 s are no share
    assert admit == pytest.approx(100 * 0.5 / 1.7, abs=1e-6)
    assert chunk == pytest.approx(100 * 1.1 / 1.7, abs=1e-6)
    # the first step's 0.1 s in no stage is 100 less the two
    assert 100.0 - admit - chunk == pytest.approx(100 * 0.1 / 1.7, abs=1e-6)


def test_the_window_only_chunk_mean_leaves_the_probes_and_the_ramp_out(
        program):
    driven = _fill(program)
    size = harness.load_workload("serve-base-backlog")["engine"]["chunk_size"]
    for suffix in ("backlog", "steady"):
        assert _read(f"window.chunk_step_ms.{suffix}", _obs(driven)) \
            == pytest.approx(1e3 * 1.1 / (7 * size), abs=1e-6)
    # the whole process's mean, which ``engine.chunk_step_ms.*`` reads
    assert 1e3 * (1.1 + 0.28) / (10 * size) < 0.9 * _read(
        "window.chunk_step_ms.backlog", _obs(driven))


def test_a_delivery_gap_counts_once_a_carried_row(program):
    obs = _obs(_fill(program))
    # six gaps end to end (0.2, 0.45, 0.2, 0.45, 0.1 and 0.1 with the
    # profiler's 2 s taken off) carried by 10, 8, 10, 6, 10 and 10 rows
    p50 = _read("window.delivery_gap_p50_ms.backlog", obs)
    p95 = _read("window.delivery_gap_p95_ms.backlog", obs)
    assert p50 == pytest.approx(200.0, abs=1e-3)
    assert p95 == pytest.approx(450.0, abs=1e-3)
    assert _read("window.delivery_gap_p95_ms.steady", obs) == p95
    reader = harness.load_module("perf/readers/step_log.py")
    records, taken_off = reader.window(obs)
    gaps = reader.delivery_gaps(records, taken_off)
    assert len(gaps) == 54 and gaps.count(pytest.approx(0.45)) == 14
    assert sum(taken_off) == pytest.approx(2.0, abs=1e-4)


@pytest.mark.parametrize("name", NAMES)
def test_none_on_a_short_or_stale_store_and_on_the_parent(name, program,
                                                          monkeypatch):
    from progen_tpu.observe import trace

    registry, tracer = program
    driven = _fill(program)
    assert _read(name, _obs(driven)) is not None
    # a runner that drove more steps than the store holds
    more = [(0.01 * i, 0) for i in range(4)] + [
        (e + 0.04, q) for e, q in driven]
    assert _read(name, _obs(more)) is None
    assert _read(name, {"counters": {}}) is None
    # records that end after the runner's instants for their steps, by more
    # than the profiler's 2 s can explain: they are not those steps
    early = [(e - 2.5, q) for e, q in driven[:3]] + driven[3:]
    assert _read(name, _obs(early)) is None
    # the process stepped after the window: the last record is not the
    # counter's
    registry.counter("engine.steps").inc(1)
    assert _read(name, _obs(driven)) is None
    tracer.step_record(_record(12, T0 + 9.0, 0.1, chunk=0.1, rows=1))
    registry.counter("engine.steps").inc(1)
    # ... and a step that raised left a hole in the window's numbering
    hole = driven[1:] + [(driven[-1][0] + 5.0, 0)]
    assert _read(name, _obs(hole)) is None
    # the parent: a tracer with no step log
    monkeypatch.setattr(trace, "_TRACER", object())
    assert _read(name, _obs(driven)) is None


@pytest.mark.parametrize("name", NAMES)
def test_listed_in_the_benchmark_beside_its_end_to_end_metric(name):
    bench = harness.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = harness.load_metric(name)
    assert (entry["unit"], entry["better"], entry["moves"], entry["layer"],
            entry["source"]) == (spec["unit"], spec["better"], spec["moves"],
                                 spec["layer"], "program_span")
    moved = next(m for m in bench["end_to_end"] if m["name"] == spec["moves"])
    assert sorted(entry["workloads"]) == sorted(moved["workloads"])


def test_a_rehearsed_engine_fills_the_store_the_reader_reads(monkeypatch):
    """The program's side and the reader's side meet: a tiny engine on the
    CPU, stepped as the backlog runner steps it."""
    import time

    import jax
    import jax.numpy as jnp

    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.observe import metrics
    from progen_tpu.observe.trace import get_tracer
    from progen_tpu.parallel import unbox

    cfg = ProGenConfig(num_tokens=32, dim=16, seq_len=24, depth=2,
                       window_size=4, global_mlp_depth=1, heads=2, dim_head=8,
                       ff_mult=2)
    policy = make_policy(False)
    params = unbox(ProGen(config=cfg, policy=policy).init(
        jax.random.key(7), jnp.zeros((2, cfg.seq_len), jnp.int32)))
    eng = ServingEngine(cfg, params, policy=policy, num_slots=2, chunk_size=4,
                        max_len=20)
    for i in range(10):
        eng.submit(Request(uid=i, tokens=[3, 4, 5 + i], max_new_tokens=9,
                           temperature=0.0, seed=i))
    eng.step()                      # set-up: compiles, outside the window
    t0, driven = time.perf_counter(), []
    while eng.has_work:
        eng.step()
        driven.append((time.perf_counter() - t0, eng.pending))
    assert len(driven) >= 6
    obs = {"workload": {"engine": {"chunk_size": 4}},
           "counters": {"queued": driven}}
    values = {name: _read(name, obs) for name in NAMES}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["window.chunk_share.backlog"] > 0
    assert values["window.admit_share.backlog"] > 0
    assert (values["window.admit_share.backlog"]
            + values["window.chunk_share.backlog"]) <= 100.0
    records = get_tracer().steps()[-len(driven):]
    assert records[-1]["step"] == metrics.get_registry().snapshot()[
        "engine.steps"]["value"]


@pytest.mark.parametrize("arrivals,like,suffix", [
    ({"kind": "open", "rate": 4.0}, "serve-small-steady", "steady"),
    ({"kind": "backlog", "requests_per_second": 400.0},
     "serve-base-backlog", "backlog"),
], ids=["open-loop", "backlog"])
def test_serving_cells_report_them_in_the_traced_run(
        checkout, own_registry, arrivals, like, suffix):
    """Through the harness and the runner, profiler and all: the window's
    records are found by the runner's steps, whichever loop drove them."""
    from progen_tpu.observe.trace import get_tracer

    root, copy = checkout
    layer = tuple(n for n in NAMES if n.endswith(suffix))
    mix = harness.load_traffic(harness.load_workload(like)["traffic"])
    traffic = dict(mix, name="tiny-requests", arrivals=arrivals,
                   prime_tokens={"kind": "uniform_int", "min": 4, "max": 16},
                   generated_tokens=LENGTHS)
    if "stagger" in traffic:
        traffic["stagger"] = dict(traffic["stagger"], first=4)
    _add_cell(root, name="serve-tiny", traffic=traffic, like=like,
              engine={"num_slots": 4, "chunk_size": 4, "max_len": 128},
              correct={"probes": 2, "probe_new_tokens": 12},
              per_layer=layer)
    get_tracer().clear()
    result = copy.run_cell("serve-tiny", 2 ** 31 + 11, 1.5, True, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(layer)
    values = {k.removesuffix(f".{suffix}"): v["value"]
              for k, v in result["metrics"].items()}
    assert values["window.chunk_step_ms"] > 0
    assert values["window.delivery_gap_p95_ms"] >= 4 * 0.5 * values[
        "window.chunk_step_ms"]
    if suffix == "backlog":
        shares = values["window.admit_share"], values["window.chunk_share"]
        assert all(0 < share < 100 for share in shares)
        assert sum(shares) <= 100.0
        assert (values["window.delivery_gap_p50_ms"]
                <= values["window.delivery_gap_p95_ms"])
    get_tracer().clear()
