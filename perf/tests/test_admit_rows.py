"""``engine.admit_rows.*`` (PR 26): the mean number of requests in a run of
the engine's admission program, read from the program's registry by
``perf/readers/registry_mean.py``.  Listed with its cell, resolved to that
reader, a number in the CPU rehearsal of its cell and ``None`` where the
program has no such histogram (the parent) or observed nothing in it.  New
files and new entries only; the temporary checkout is ``test_rehearsal``'s."""

import pytest

from perf.lib import harness
from perf.tests.test_rehearsal import (  # noqa: F401  (checkout: fixture)
    LENGTHS,
    _add_cell,
    _check,
    checkout,
)

CELLS = {
    "engine.admit_rows.steady": ("serve-small-steady", "norm_latency_p50"),
    "engine.admit_rows.backlog": ("serve-base-backlog", "serve_tok_s"),
}
ARRIVALS = {
    "serve-small-steady": {"kind": "open", "rate": 4.0},
    "serve-base-backlog": {"kind": "backlog", "requests_per_second": 400.0},
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_listed_with_its_cell_and_silent_on_an_empty_registry(
        name, monkeypatch):
    from progen_tpu.observe import metrics

    cell, moves = CELLS[name]
    bench = harness.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert cell in entry["workloads"] and entry["moves"] == moves
    assert entry["source"] == "program_span" and entry["unit"] == "rows"
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"].startswith("engine.admit_ms")}
    assert {entry["layer"]} == layers
    spec = harness.load_metric(name)
    assert spec["reader"] == "perf/readers/registry_mean.py"
    reader = harness.load_module(spec["reader"])
    # a program without the histogram (the parent), then one that has it
    # and observed nothing: the line leaves the metric out
    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    obs = {"workload": harness.load_workload(cell), "counters": {},
           "spans": {}}
    assert reader.read(obs, spec) is None
    metrics.get_registry().histogram("engine.admit_rows")
    assert reader.read(obs, spec) is None
    for rows in (1, 4, 4):
        metrics.get_registry().histogram("engine.admit_rows").observe(rows)
    assert reader.read(obs, spec) == 3.0     # the unit observed, not ms


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reads_rows_per_run_in_the_rehearsal_of_its_cell(checkout, name):
    root, copy = checkout
    like = CELLS[name][0]
    traffic = dict(harness.load_traffic(harness.load_workload(like)["traffic"]),
                   name="tiny-requests", arrivals=ARRIVALS[like],
                   prime_tokens={"kind": "uniform_int", "min": 4, "max": 16},
                   generated_tokens=LENGTHS)
    if "stagger" in traffic:
        traffic["stagger"] = dict(traffic["stagger"], first=4)
    _add_cell(root, name="serve-tiny", traffic=traffic, like=like,
              engine={"num_slots": 32, "chunk_size": 4, "max_len": 128},
              correct={"probes": 2, "probe_new_tokens": 12},
              per_layer=(name,))
    result = copy.run_cell("serve-tiny", 2 ** 31 + 7, 1.5, True, 0.0)
    _check(result, (name,))
    assert result["metrics"][name]["unit"] == "rows"
    # 32 slots: two rows per run; the four probes alone fill two runs
    assert 1.0 < result["metrics"][name]["value"] <= 2.0
