"""Run one serving cell, once per seed, and print what the PROGRAM
recorded of the seconds no span owns: its incidents (compiles, collector
pauses, steps that stood still — ``progen_tpu.observe.trace``'s incident
store, kept with the ring off).

    python3 perf/tools/stall_hunt.py --workload serve-base-backlog \
        --seed 11 12 13 [--seconds 35] [--trace 1] \
        [--out chiprun_out/stall_hunt]

Each seed is a process of its own, as each run of ``perf/run.py`` is (this
one never touches JAX, so the chip is free for its children); a child runs
the cell's runner as ``perf/run.py`` does (untraced, or with ``--trace 1``
as the traced run that prints ``window.stall_ms.*``) and prints ONE line
``stall_hunt {...}``: the run's end-to-end numbers, the steps the window
drove and its length on the benchmark's clock, the incidents of the window
with their fields (those of its steps, and those that fell between two of
them), those before it counted by name, and the compile, cache
and collector totals of the process.  A run stood still where its rate lies off its siblings' (the
schedule is the same for every seed): the window lost ``window_s × (1 −
rate / the siblings' median)`` seconds, and its incidents say whose they
were.  Not part of a benchmark run.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def hunt(cell: str, seed: int, seconds: float, trace: bool) -> dict:
    from perf.lib import harness
    from progen_tpu.observe.metrics import get_registry
    from progen_tpu.observe.trace import get_tracer

    bench = harness.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    workload = harness.load_workload(cell)
    workload["traffic"] = harness.load_traffic(entry["traffic"])
    config = harness.load_config(entry["config"])
    harness.require_tpu(entry["chips"])
    runner = harness.load_module(workload["runner"])
    run = runner.run(workload=workload, config=config, seed=seed,
                     seconds=seconds, trace=trace, chips=entry["chips"])
    counters = run["observations"]["counters"]
    snap = get_registry().snapshot()
    last = snap.get("engine.steps", {}).get("value", 0)
    first = last - len(counters["queued"]) + 1
    window, between, before = [], [], {}
    for incident in get_tracer().incidents():
        step = incident["args"].get("step")
        if step is not None and step >= first:
            window.append(incident)
        elif step is None and incident["ts"] >= run["window_open"]:
            # in no step and after the window opened: the runner's own
            # seconds between two steps (a traced run's profiler)
            between.append(incident)
        else:
            before[incident["name"]] = before.get(incident["name"], 0) + 1

    def total(name, field="value"):
        return snap.get(name, {}).get(field)

    return {
        "workload": cell, "seed": seed, "traced": trace,
        "correct": bool(run["correct"]), "failed": int(run["failed"]),
        "metrics": dict(run["end_to_end"],
                        setup_s=run["window_open"] - PROCESS_START),
        "window_steps": len(counters["queued"]), "last_step": last,
        "window_s": counters["window_s"],
        "chunk_steps": len(counters["chunk_step_ms"]),
        "incidents_in_window": window, "incidents_between_steps": between,
        "incidents_before": before,
        "totals": {
            "engine.compiles_in_step": total("engine.compiles_in_step"),
            "engine.prefill_tokens_real": total("engine.prefill_tokens_real"),
            "engine.prefill_token_slots": total("engine.prefill_token_slots"),
            "engine.chunk_rows": total("engine.chunk_rows", "sum"),
            "engine.chunks": total("engine.chunk_rows", "count"),
            "xla.compiles": total("xla.compiles"),
            "xla.compile_s": total("xla.compile_s", "sum"),
            "xla.cache_hits": total("xla.cache_hits"),
            "xla.cache_misses": total("xla.cache_misses"),
            "host.gc_pauses": total("host.gc_pause_s", "count"),
            "host.gc_pause_s": total("host.gc_pause_s", "sum"),
            "host.gc_pause_max_s": total("host.gc_pause_s", "max"),
        },
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=35.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", default=None,
                        help="directory for one <cell>.<seed>.json a run")
    args = parser.parse_args(argv)

    if len(args.seed) > 1:
        worst = 0
        for seed in args.seed:
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(args.seconds),
                   "--trace", str(args.trace)]
            if args.out:
                cmd += ["--out", args.out]
            worst = max(worst, subprocess.call(cmd))
        return worst

    found = hunt(args.workload, args.seed[0], args.seconds,
                 bool(args.trace))
    print("stall_hunt " + json.dumps(found), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        path = os.path.join(args.out, f"{args.workload}.{args.seed[0]}.json")
        with open(path, "w") as f:
            json.dump(found, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
