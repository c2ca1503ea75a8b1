"""``BENCHMARK.json`` and the files it names, as tier-1 sees them: the checks
of ``perf/tests/test_files.py`` that need no JAX (names, units, limits;
every cell reports ``setup_s``, another end-to-end metric and a layer
metric; every entry's file agrees and names a reader; one entry a quantity;
at most 128 entries and 24 cells), as plain loops over the entries.  Nothing
under ``perf/`` is imported: a benchmark file that a PR breaks fails here,
in the run the driver holds every PR to."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = _json(ROOT, "BENCHMARK.json")


def _cell_metrics(cell, group):
    return [m for m in BENCH[group]
            if "workloads" not in m or cell in m["workloads"]]


def test_names_units_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perf"] and 1 <= BENCH["run_seconds"] <= 51
    assert all(LINE.match(word) for word in BENCH["command"])
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["per_layer"]) <= 128
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher"), m["name"]
        assert m["source"] in SOURCES, m["name"]
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}, m["name"]
        assert m["source"] in ("host_clock", "device_trace"), m["name"]
        assert 0.01 <= m["bound"] <= 0.1, m["name"]
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m["name"]
        assert LINE.match(m["layer"]), m["name"]
    assert "setup_s" in names
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(cells) == len(set(cells))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and LINE.match(w["why"]), w["name"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(cells) // 4)
    configs = [c["name"] for c in BENCH["configs"]]
    assert len(configs) == len(set(configs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"]), c["name"]
        assert LINE.match(c["why"]) and len(c["reduced"]) <= 16, c["name"]
        assert all(NAME.match(k) for k in c["reduced"]), c["name"]
        assert c["file"].startswith("perf/"), c["name"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) <= 64 * 1024


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    known = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= known, m["name"]
    for w in BENCH["workloads"]:
        mine = {m["name"] for m in _cell_metrics(w["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        layer = _cell_metrics(w["name"], "per_layer")
        assert layer, w["name"]
        for m in layer:  # what a layer metric moves is reported beside it
            assert m["moves"] in e2e and m["moves"] in mine, (
                w["name"], m["name"])


def test_every_entrys_file_agrees_and_names_a_reader():
    for entry in BENCH["per_layer"]:
        spec = _json(PERF, "metrics", f"{entry['name']}.json")
        for key in ("name", "unit", "better", "layer", "moves", "source"):
            assert spec[key] == entry[key], (entry["name"], key)
        # a metric's cells are listed in ONE place, BENCHMARK.json's entry
        assert "workloads" not in spec, entry["name"]
        assert isinstance(spec["args"], dict), entry["name"]
        reader = os.path.join(ROOT, spec["reader"])
        assert spec["reader"].startswith("perf/readers/"), entry["name"]
        with open(reader) as f:
            assert "\ndef read(obs, metric)" in f.read(), spec["reader"]


def test_one_entry_a_quantity_and_every_metric_file_has_its_entry():
    seen = {}
    for entry in BENCH["per_layer"]:
        spec = _json(PERF, "metrics", f"{entry['name']}.json")
        quantity = (spec["reader"], json.dumps(spec["args"], sort_keys=True),
                    entry["unit"], entry["better"], entry["source"],
                    entry["moves"])
        other = seen.setdefault(quantity, entry["name"])
        assert other == entry["name"], (
            f"{entry['name']} is {other} under another name: append its "
            f"cells to {other}'s workloads")
    files = {f.removesuffix(".json")
             for f in os.listdir(os.path.join(PERF, "metrics"))}
    assert files == {m["name"] for m in BENCH["per_layer"]}


def test_every_cells_and_configurations_files_are_there_and_agree():
    for entry in BENCH["workloads"]:
        workload = _json(PERF, "workloads", f"{entry['name']}.json")
        for key in ("name", "config", "traffic", "chips"):
            assert workload[key] == entry[key], (entry["name"], key)
        traffic = _json(PERF, "traffic", f"{entry['traffic']}.json")
        assert traffic["name"] == entry["traffic"]
        assert workload["runner"].startswith("perf/runners/")
        with open(os.path.join(ROOT, workload["runner"])) as f:
            assert "\ndef run(" in f.read(), workload["runner"]
    for entry in BENCH["configs"]:
        assert entry["file"] == f"perf/configs/{entry['name']}.json"
        config = _json(ROOT, entry["file"])
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"], entry["name"]
        assert config["reduced"] == entry["reduced"], entry["name"]
