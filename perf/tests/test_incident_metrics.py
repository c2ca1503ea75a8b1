"""The per-layer metrics that read what no span owns (PR 36): compiles and
cache misses from the registry (``registry_value.py``), compiles and slow
steps of the window from the tracer's incidents (``incident_sum.py``), and
the engine's admission and chunk counts through readers that were there.
Each is listed in ``BENCHMARK.json`` with its cells (one entry a quantity
since PR 45, held as a SET: where it lies in the list no test says),
resolves to its reader, reads ``None`` on a program without the store and a
number in the CPU rehearsal of a serving cell."""

import pytest

from perf.lib import harness
from perf.tests.test_rehearsal import (  # noqa: F401  (checkout: fixture)
    LENGTHS,
    _add_cell,
    checkout,
)

TRAIN, STEADY = ("train-small-uniref",), ("serve-small-steady",)
BACKLOG = ("serve-base-backlog", "serve-longcat-backlog",
           "serve-dsv2-decode-backlog", "serve-trinity-mixedlen-backlog",
           "serve-granite-chat-backlog", "serve-sdar-blockdiff-backlog")
CELLS = {
    "xla.compile_s": TRAIN + STEADY + BACKLOG,
    "xla.cache_misses": TRAIN + STEADY + BACKLOG,
    "train.recompiles.train": TRAIN,
    "window.compiles.steady": STEADY, "window.compiles.backlog": BACKLOG,
    "window.stall_ms.steady": STEADY, "window.stall_ms.backlog": BACKLOG,
    "engine.prefill_real_share.steady": STEADY,
    "engine.prefill_real_share.backlog": BACKLOG,
    "engine.chunk_rows.steady": STEADY, "engine.chunk_rows.backlog": BACKLOG,
    "moe.held_groups_per_token.dsv2": ("serve-dsv2-decode-backlog",),
}


def _obs(steps=0):
    return {"workload": harness.load_workload("serve-base-backlog"),
            "counters": {"queued": [(0.1 * i, 0) for i in range(steps)]},
            "spans": {}}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_listed_with_its_cells_and_silent_on_a_program_without_it(
        name, monkeypatch):
    from progen_tpu.observe import metrics, trace

    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == name)
    assert sorted(entry["workloads"]) == sorted(CELLS[name])
    assert entry["source"] in ("program_counter", "program_span")
    spec = harness.load_metric(name)
    reader = harness.load_module(spec["reader"])
    # the parent: no such counter, no incident store
    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    monkeypatch.setattr(trace, "_TRACER", object())
    assert reader.read(_obs(3), spec) is None


def test_registry_value_reads_counters_and_histogram_fields(monkeypatch):
    from progen_tpu.observe import metrics

    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "_REGISTRY", registry)
    read = harness.load_module("perf/readers/registry_value.py").read
    misses = {"args": {"name": "xla.cache_misses"}}
    seconds = {"args": {"name": "xla.compile_s", "field": "sum"}}
    assert read({}, misses) is None and read({}, seconds) is None
    registry.counter("xla.cache_misses")
    registry.histogram("xla.compile_s")
    assert read({}, misses) == 0 and read({}, seconds) == 0.0
    registry.counter("xla.cache_misses").inc(3)
    for s in (0.5, 1.25, 2.0):
        registry.histogram("xla.compile_s").observe(s)
    assert read({}, misses) == 3
    assert read({}, seconds) == pytest.approx(3.75)
    assert read({}, {"args": {"name": "xla.compile_s",
                              "field": "count"}}) == 3
    assert read({}, {"args": {"name": "xla.compile_s",
                              "field": "nothing"}}) is None


def test_incident_sum_takes_the_last_steps_of_the_process(monkeypatch):
    from progen_tpu.observe import metrics, trace

    registry = metrics.MetricsRegistry()
    tracer = trace.Tracer()
    monkeypatch.setattr(metrics, "_REGISTRY", registry)
    monkeypatch.setattr(trace, "_TRACER", tracer)
    read = harness.load_module("perf/readers/incident_sum.py").read
    compiles = {"args": {"incident": "xla.compile"}}
    stall = harness.load_metric("window.stall_ms.backlog")
    assert stall["args"]["where"] == {"which": ["host", "device"]}
    # a store but no step counter: not a program that steps
    assert read(_obs(4), compiles) is None
    registry.counter("engine.steps").inc(10)
    assert read({"counters": {}}, compiles) is None
    assert read(_obs(4), compiles) == 0 and read(_obs(4), stall) == 0.0
    tracer.incident("xla.compile", 0.0, 1.0, program="set-up")   # no step
    tracer.incident("xla.compile", 0.0, 1.0, program="ramp", step=6)
    tracer.incident("xla.compile", 0.0, 1.0, program="jit(_admit)", step=7)
    tracer.incident("serve.slow_step", 0.0, 0.3, step=6, which="host",
                    excess=0.25)
    tracer.incident("serve.slow_step", 0.0, 0.3, step=8, which="device",
                    excess=0.125)
    tracer.incident("serve.slow_step", 0.0, 2.0, step=9, which="gap",
                    excess=1.9)
    tracer.incident("serve.slow_step", 0.0, 0.3, step=10, which="host",
                    excess=0.5)
    # the window drove the last 4 of 10 steps: 7, 8, 9, 10
    assert read(_obs(4), compiles) == 1
    assert read(_obs(4), stall) == pytest.approx(625.0)
    assert read(_obs(5), compiles) == 2
    assert read(_obs(5), stall) == pytest.approx(875.0)
    every = {"args": {"incident": "serve.slow_step", "field": "excess"}}
    assert read(_obs(4), every) == pytest.approx(2.525)
    assert read(_obs(0), compiles) == 0


@pytest.mark.parametrize("arrivals,like,suffix", [
    ({"kind": "open", "rate": 4.0}, "serve-small-steady", "steady"),
    ({"kind": "backlog", "requests_per_second": 400.0},
     "serve-base-backlog", "backlog"),
], ids=["open-loop", "backlog"])
def test_serving_cells_report_them_in_the_traced_run(
        checkout, own_registry, arrivals, like, suffix):
    from progen_tpu.observe.trace import get_tracer

    root, copy = checkout
    layer = ("xla.compile_s", "xla.cache_misses") + tuple(
        f"{family}.{suffix}" for family in (
            "window.compiles", "window.stall_ms", "engine.chunk_rows",
            "engine.prefill_real_share"))
    traffic = dict(harness.load_traffic(harness.load_workload(like)["traffic"]),
                   name="tiny-requests", arrivals=arrivals,
                   prime_tokens={"kind": "uniform_int", "min": 4, "max": 16},
                   generated_tokens=LENGTHS)
    if "stagger" in traffic:
        traffic["stagger"] = dict(traffic["stagger"], first=4)
    _add_cell(root, name="serve-tiny", traffic=traffic, like=like,
              engine={"num_slots": 4, "chunk_size": 4, "max_len": 128},
              correct={"probes": 2, "probe_new_tokens": 12},
              per_layer=layer)
    get_tracer().clear()
    result = copy.run_cell("serve-tiny", 2 ** 31 + 7, 1.5, True, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == set(layer)
    values = {k.removesuffix(f".{suffix}"): v["value"]
              for k, v in result["metrics"].items()}
    # the engine compiled its programs at set-up and none in the window
    assert values["xla.compile_s"] > 0
    assert values["window.compiles"] == 0, get_tracer().incidents()
    assert 0 < values["engine.chunk_rows"] <= 4
    assert values["window.stall_ms"] >= 0 and values["xla.cache_misses"] >= 0
    # primes of 4-16 tokens in the one bucket of 32, runs of 1-4 rows of 4
    assert 100 * 4 / 32 / 4 <= values["engine.prefill_real_share"] <= 50
    get_tracer().clear()
