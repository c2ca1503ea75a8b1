"""Both runners end to end at a tiny size on the CPU, through the harness
(on-chip-measurement guide, section 2, rehearsals 1 and 2).  Each case
copies BENCHMARK.json and perf/ into a temporary directory and ADDS a tiny
configuration, a traffic file, a workload file and their entries: adding a
cell needs new files and new entries and no edit of a file that is there.
The only thing steered is the harness's demand for a TPU; no number these
runs print is a measurement."""

import json
import os
import shutil

import pytest

from perf.lib import harness

TINY = dict(name="tiny", source="perf/tests", reduced=[], num_tokens=256,
            ff_mult=4, ff_glu=True, shift_tokens=True, global_mlp_depth=1,
            dim_head=32, dim=64, depth=3, heads=2, window_size=32,
            seq_len=128)
LENGTHS = {"kind": "lognormal", "median": 30, "sigma": 0.6, "min": 8,
           "max": 100}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture()
def checkout(tmp_path, monkeypatch):
    """A temporary copy of the benchmark, its harness loaded from there."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    _dump(root / "perf/configs/tiny.json", TINY)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_harness", root / "perf/lib/harness.py")
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    assert copy.ROOT == str(root)

    def any_devices(chips):
        import jax

        assert len(jax.devices()) >= chips
        return jax.devices()

    monkeypatch.setattr(copy, "require_tpu", any_devices)
    return root, copy


def _add_cell(root, *, name, traffic, like, chips=1, per_layer=(),
              end_to_end=(), **changes):
    """New files and new entries only."""
    workload = harness.load_workload(like)
    workload.update(name=name, config="tiny", traffic=traffic["name"],
                    chips=chips)
    for group, fields in changes.items():
        workload[group] = {**workload.get(group, {}), **fields}
    _dump(root / f"perf/workloads/{name}.json", workload)
    _dump(root / f"perf/traffic/{traffic['name']}.json", traffic)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if not any(c["name"] == "tiny" for c in bench["configs"]):
        bench["configs"].append({"name": "tiny", "source": "perf/tests",
                                 "file": "perf/configs/tiny.json",
                                 "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": traffic["name"], "chips": chips,
                               "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in end_to_end + per_layer and "workloads" in m:
            m["workloads"].append(name)
    _dump(root / "BENCHMARK.json", bench)


def _check(result, names):
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == set(names)
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("chips,trainer", [
    (4, {"batch_size": 4, "log_every": 2}),
    (4, {"batch_size": 4, "log_every": 2, "mesh": "1,4,1,1",
         "strategies": ["fsdp"], "remat": True}),
], ids=["dp-four-devices", "fsdp-four-devices"])
def test_train_runner(checkout, chips, trainer):
    root, copy = checkout
    traffic = {"name": "tiny-records", "kind": "train-records",
               "records": 32, "prefix": "# ", "residues": LENGTHS}
    _add_cell(root, name="train-tiny", traffic=traffic,
              like="train-small-uniref", chips=chips, trainer=trainer,
              end_to_end=("train_tok_s",),
              per_layer=("feed.pad_share", "train.step_ms",
                         "trainer.dispatch_ms"))
    _check(copy.run_cell("train-tiny", 2 ** 31 + 11, 1.0, False, 0.0),
           ["setup_s", "train_tok_s"])
    if trainer.get("strategies") is None:
        # the traced run: readers that find nothing (no TPU plane, no peak
        # table for a CPU) are left out of the line, the others report
        result = copy.run_cell("train-tiny", 5, 1.0, True, 0.0)
        _check(result, ["feed.pad_share", "train.step_ms",
                        "trainer.dispatch_ms"])
        assert result["device"]["busy_s"] == 0.0
    assert not [p for p in os.listdir(root) if p not in
                ("perf", "BENCHMARK.json", ".jax_cache")]


@pytest.mark.parametrize("arrivals,end_to_end,layer", [
    ({"kind": "open", "rate": 4.0},
     ("norm_latency_p50", "norm_latency_p95"),
     ("loadgen.late_p95", "engine.step_ms.steady")),
    ({"kind": "backlog", "requests_per_second": 400.0},
     ("serve_tok_s",), ("engine.step_ms.backlog", "engine.occupancy")),
], ids=["open-loop", "backlog"])
def test_serve_runner(checkout, arrivals, end_to_end, layer):
    root, copy = checkout
    like = ("serve-small-steady" if arrivals["kind"] == "open"
            else "serve-base-backlog")
    traffic = dict(harness.load_traffic(harness.load_workload(like)["traffic"]),
                   name="tiny-requests", arrivals=arrivals,
                   prime_tokens={"kind": "uniform_int", "min": 4, "max": 16},
                   generated_tokens=LENGTHS)
    if "stagger" in traffic:
        traffic["stagger"] = dict(traffic["stagger"], first=4)
    _add_cell(root, name="serve-tiny", traffic=traffic, like=like,
              engine={"num_slots": 4, "chunk_size": 4, "max_len": 128},
              correct={"probes": 2, "probe_new_tokens": 12},
              end_to_end=end_to_end, per_layer=layer)
    _check(copy.run_cell("serve-tiny", 2 ** 31 + 3, 1.5, False, 0.0),
           ("setup_s",) + end_to_end)
    _check(copy.run_cell("serve-tiny", 9, 1.5, True, 0.0), layer)
