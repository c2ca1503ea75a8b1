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
    assert set(entry["workloads"]) <= set(spec["workloads"])
    assert callable(harness.load_module(spec["reader"]).read)


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
