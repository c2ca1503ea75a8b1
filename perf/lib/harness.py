"""Finding a cell's files by name, running it, and printing the one line.

``BENCHMARK.json`` names cells, configurations and metrics; each has a file
of its own under ``perf/`` that this module finds by that name, so a later
PR adds a cell, a configuration, a metric, a reader or a runner as new
files and new entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)


def read_json(*parts: str) -> dict:
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_workload(name: str) -> dict:
    return read_json("workloads", f"{name}.json")


def load_config(name: str) -> dict:
    return read_json("configs", f"{name}.json")


def load_traffic(name: str) -> dict:
    return read_json("traffic", f"{name}.json")


def load_metric(name: str) -> dict:
    return read_json("metrics", f"{name}.json")


def load_module(rel_path: str):
    """Import ``perf/runners/x.py`` or ``perf/readers/x.py`` by its path
    relative to the checkout."""
    path = os.path.join(ROOT, rel_path)
    name = "perf_" + rel_path.removesuffix(".py").replace("/", "_").replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def cell_metrics(bench: dict, cell: str, group: str) -> list[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def require_tpu(chips: int):
    """The devices to run on, or SystemExit: a device number never comes
    from a CPU and a cell never runs on fewer chips than it names."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"perf/run.py measures on a TPU; JAX found platform "
            f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(
            f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices


def device_record(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             process_start: float) -> dict:
    """Run one cell once and return the result object of the contract."""
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {cell!r}")
    workload = load_workload(cell)
    workload["traffic"] = load_traffic(entry["traffic"])
    config = load_config(entry["config"])
    devices = require_tpu(entry["chips"])
    runner = load_module(workload["runner"])
    run = runner.run(workload=workload, config=config, seed=seed,
                     seconds=seconds, trace=trace, chips=entry["chips"])
    device = device_record(devices, entry["chips"])
    obs = run["observations"]
    obs.update(workload=workload, config=config, chips=entry["chips"],
               device_kind=device["kind"],
               memory_peak_bytes=device["memory_peak_bytes"])
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    metrics: dict[str, dict] = {}
    if not trace:
        values = dict(run["end_to_end"])
        values["setup_s"] = run["window_open"] - process_start
        for m in cell_metrics(bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        reduced = obs.get("trace") or {}
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        for m in cell_metrics(bench, cell, "per_layer"):
            spec = load_metric(m["name"])
            value = load_module(spec["reader"]).read(obs, spec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if reduced.get("device_ops") is not None:
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    return result


class Phases:
    """Where set-up time goes: ``mark(name)`` closes a phase; ``report()``
    prints one line, seconds per phase in order."""

    def __init__(self):
        self._last = time.perf_counter()
        self.seconds: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now

    def report(self, label: str) -> None:
        parts = ", ".join(f"{k} {v:.1f}" for k, v in self.seconds.items())
        print(f"{label} phases (s): {parts}", flush=True)


class TraceStretch:
    """Profile a short stretch of the window: ``start()`` and ``stop()`` are
    called by the runner from inside its loop; ``reduce()`` afterwards."""

    def __init__(self, directory: str):
        self.directory = directory
        self.active = False
        self.done = False
        self._span = None
        self.started_at = None

    def start(self) -> None:
        import jax

        # the Python tracer records every call of the engine's host code:
        # off, or stopping the trace takes a minute; the annotations
        # (TraceMe) stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        from perf.lib.xplane import WINDOW_SPAN

        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.active = True
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def reduce(self, program_spans=()) -> dict | None:
        """``program_spans``: ``(name, start, end)`` on ``perf_counter``."""
        from perf.lib import xplane

        path = xplane.find_xplane(self.directory)
        if not path:
            return None
        return xplane.reduce_trace(path, program_spans=program_spans,
                                   anchor=self.started_at)
