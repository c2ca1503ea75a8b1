"""The per-layer metrics that read the program's own clocks (its metrics
registry for the serving engine, its span ring for the trainer's feed):
each is listed with its cells, resolves to its reader, reads a number in
the CPU rehearsal of its cells and ``None`` where the program recorded
nothing.  New files and new entries only, as in ``test_rehearsal.py``,
whose temporary checkout these cases reuse."""

import pytest

from perf.lib import harness
from perf.tests.test_rehearsal import (  # noqa: F401  (checkout: fixture)
    LENGTHS,
    _add_cell,
    _check,
    checkout,
)

STEADY = ["serve-small-steady"]
BACKLOG = ["serve-base-backlog"]
CELLS = {
    "engine.queue_wait_ms": STEADY,
    "engine.ttft_ms": STEADY,
    "engine.admit_ms.steady": STEADY,
    "engine.admit_ms.backlog": BACKLOG,
    "engine.chunk_step_ms.steady": STEADY,
    "engine.chunk_step_ms.backlog": BACKLOG,
    "engine.host_ms.steady": STEADY,
    "feed.wait_ms": ["train-small-uniref"],
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_listed_with_its_cells_and_silent_on_an_empty_source(
        name, monkeypatch):
    from progen_tpu.observe import metrics, trace

    entry = next(m for m in harness.load_benchmark()["per_layer"]
                 if m["name"] == name)
    assert set(CELLS[name]) <= set(entry["workloads"])
    assert entry["source"] == "program_span" and entry["unit"] == "ms"
    spec = harness.load_metric(name)
    reader = harness.load_module(spec["reader"])
    # a program that recorded nothing: the line leaves the metric out
    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    monkeypatch.setattr(trace, "_TRACER", trace.Tracer())
    obs = {"workload": harness.load_workload(CELLS[name][0]),
           "counters": {}, "spans": {}}
    assert reader.read(obs, spec) is None
    metrics.get_registry().histogram("engine.prefill_s")  # there, but empty
    assert reader.read(obs, spec) is None


@pytest.mark.parametrize("arrivals,like,layer", [
    ({"kind": "open", "rate": 4.0}, "serve-small-steady",
     tuple(n for n in sorted(CELLS) if CELLS[n] == STEADY)),
    ({"kind": "backlog", "requests_per_second": 400.0}, "serve-base-backlog",
     tuple(n for n in sorted(CELLS) if CELLS[n] == BACKLOG)),
], ids=["open-loop", "backlog"])
def test_serving_metrics_read_the_registry(checkout, arrivals, like, layer):
    root, copy = checkout
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
    result = copy.run_cell("serve-tiny", 2 ** 31 + 5, 1.5, True, 0.0)
    _check(result, layer)
    values = {k: v["value"] for k, v in result["metrics"].items()}
    if like == "serve-small-steady":
        # a first token needs its admission program
        assert (values["engine.ttft_ms"] - values["engine.queue_wait_ms"]
                >= values["engine.admit_ms.steady"] * 0.999)


def test_feed_wait_reads_the_ring(checkout):
    root, copy = checkout
    traffic = {"name": "tiny-records", "kind": "train-records",
               "records": 32, "prefix": "# ", "residues": LENGTHS}
    layer = ("feed.wait_ms", "trainer.dispatch_ms")
    _add_cell(root, name="train-tiny", traffic=traffic,
              like="train-small-uniref", chips=4,
              trainer={"batch_size": 4, "log_every": 2}, per_layer=layer)
    result = copy.run_cell("train-tiny", 7, 1.0, True, 0.0)
    _check(result, layer)
    # the feed's wait lies inside the dispatch span
    assert (result["metrics"]["feed.wait_ms"]["value"]
            <= result["metrics"]["trainer.dispatch_ms"]["value"])
