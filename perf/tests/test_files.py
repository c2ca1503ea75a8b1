"""BENCHMARK.json and the files it names agree with each other, with the
contract's limits, and with the program's configurations."""

import dataclasses
import json
import os
import re

import pytest

from perf.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_benchmark()


def test_names_units_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in names
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        mine = harness.cell_metrics(BENCH, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layer = harness.cell_metrics(BENCH, w["name"], "per_layer")
        assert layer
        for m in layer:  # what a layer metric moves is reported beside it
            assert m["moves"] in e2e
            assert m["moves"] in {x["name"] for x in mine}


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_file_agrees_and_names_a_reader(entry):
    spec = harness.load_metric(entry["name"])
    for key in ("name", "unit", "better", "layer", "moves", "source"):
        assert spec[key] == entry[key], key
    # a metric's cells are listed in ONE place, BENCHMARK.json's entry: a
    # cell joins a quantity by an entry's list, never by a file of a new name
    assert "workloads" not in spec
    assert set(entry["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    assert callable(harness.load_module(spec["reader"]).read)


def _quantity(entry, spec=None):
    """What a per-layer entry IS, whatever it is called: the reader, its
    arguments (its metric file's, or ``spec``'s), and what the number is
    held to."""
    spec = spec or harness.load_metric(entry["name"])
    return (spec["reader"], json.dumps(spec["args"], sort_keys=True),
            entry["unit"], entry["better"], entry["source"], entry["moves"])


def test_one_entry_a_quantity_and_room_left():
    """No two entries are one quantity under two names (a cell that reports
    a quantity the benchmark has joins its ``workloads`` list), every file
    under ``metrics/`` has its entry, and the list is inside the contract's
    128."""
    layer = BENCH["per_layer"]
    assert len(layer) <= 128, (
        f"per_layer holds {len(layer)} entries, {len(layer) - 128} over the "
        f"contract's 128")
    seen = {}
    for entry in layer:
        other = seen.setdefault(_quantity(entry), entry["name"])
        assert other == entry["name"], (
            f"{entry['name']} is {other} under another name: append its "
            f"cells to {other}'s workloads ({len(layer)} of 128 entries "
            f"used, {128 - len(layer)} left)")
    files = {f.removesuffix(".json")
             for f in os.listdir(os.path.join(harness.PERF, "metrics"))}
    assert files == {m["name"] for m in layer}


def test_every_reading_of_pr44_has_one_entry_that_reads_it_in_that_cell():
    """``data/per_layer_pr44.json`` is the list as it stood before the
    per-cell copies were merged (PR 45), each entry with its reader and
    arguments: every (entry, cell) of it is read today by exactly one entry,
    with that reader and those arguments, whose list holds the cell."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "per_layer_pr44.json")) as f:
        before = json.load(f)
    assert len(before) == 128
    today = {}
    for entry in BENCH["per_layer"]:
        today.setdefault(_quantity(entry), []).append(entry)
    read_before = set()
    for old in before:
        same = today.get(_quantity(old, spec=old), ())
        for cell in old["workloads"]:
            now = [m for m in same if cell in m["workloads"]]
            assert len(now) == 1, (old["name"], cell)
            read_before.add((now[0]["name"], cell))
    # what is read today and was not: SDAR's seven (ISSUE 41 named them and
    # the list was full) and ProGen-base's padding share
    read_today = {(m["name"], cell) for m in BENCH["per_layer"]
                  for cell in m["workloads"]}
    sdar = "serve-sdar-blockdiff-backlog"
    assert read_today - read_before == {
        ("engine.admit_rows.backlog", sdar),
        ("engine.chunk_rows.backlog", sdar),
        ("window.compiles.backlog", sdar), ("xla.compile_s", sdar),
        ("xla.cache_misses", sdar), ("moe.expert_passes_per_touched", sdar),
        ("moe.held_assignments_per_token", sdar),
        ("engine.prefill_real_share.backlog", "serve-base-backlog")}


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_files_exist_and_name_a_runner(entry):
    workload = harness.load_workload(entry["name"])
    assert workload["config"] == entry["config"]
    assert workload["traffic"] == entry["traffic"]
    assert workload["chips"] == entry["chips"]
    assert harness.load_traffic(entry["traffic"])["name"] == entry["traffic"]
    assert callable(harness.load_module(workload["runner"]).run)


@pytest.mark.parametrize("name,program", [("progen-small", "small"),
                                          ("progen-base", "base"),
                                          ("progen-large", "large")])
def test_config_file_equals_the_programs_configuration(name, program):
    from progen_tpu.models.configs import CONFIGS

    config = harness.load_config(name)
    want = dataclasses.asdict(CONFIGS[program])
    assert {k: config[k] for k in want} == want
    assert config["reduced"] == []
    listed = next((c for c in BENCH["configs"] if c["name"] == name), None)
    if listed is not None:
        assert listed["file"] == f"perf/configs/{name}.json"
        assert listed["source"] == config["source"]
        assert os.path.exists(os.path.join(harness.ROOT, listed["file"]))
