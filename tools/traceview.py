#!/usr/bin/env python
"""traceview CLI: merge, summarize, and rank the serving trace dumps.

Usage:

    python tools/traceview.py trace_out/              # merge -> trace.json
    python tools/traceview.py --summarize trace_out/trace.json
    python tools/traceview.py --summarize --top 5 trace_out/
    python tools/traceview.py --out merged.json dump_a.json dump_b.json

Inputs may be raw per-process dumps (written by ``Tracer.dump`` /
``ServeCluster.dump_trace``), a directory containing ``trace_*.json``
dumps, or an already-merged Chrome ``trace.json`` (detected by its
``traceEvents`` key).  Raw dumps are offset-corrected onto the driver's
clock via the offsets the driver recorded from worker clock echoes.

``--summarize`` prints per-span-name count/total/p50/p95 (through the
same ``Histogram`` the benches use — one percentile code path).
It then lists the engine's step records — the ``serve.step`` spans of the
ring and each raw dump's ``steps`` store, which is kept with the ring off
— the newest ``STEP_ROWS`` of them, one line a step with its stages.
``--top N`` prints the N slowest requests by first-span..last-span wall
time, grouped by trace id (request uid).

Exit codes: 0 success, 1 no spans found (merge mode only — the read-only
``--summarize`` / ``--top`` views degrade to a message and exit 0 on an
empty or driver-only dump directory), 2 usage error.

Pure stdlib + ``progen_tpu.observe`` (itself stdlib-only for these two
modules); the heavy package ``__init__`` is bypassed with a namespace
stub so this tool never imports jax.
"""

from __future__ import annotations

import argparse
import os
import sys
import types
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
# step records listed by --summarize: two screens, the newest
STEP_ROWS = 64


def _import_observe():
    if str(REPO_ROOT) not in sys.path:
        sys.path.insert(0, str(REPO_ROOT))
    if "progen_tpu" not in sys.modules:
        stub = types.ModuleType("progen_tpu")
        stub.__path__ = [str(REPO_ROOT / "progen_tpu")]
        sys.modules["progen_tpu"] = stub
    from progen_tpu.observe import metrics, trace
    return trace, metrics


def _spans_from_chrome(obj) -> list[dict]:
    """Back-convert a merged ``traceEvents`` file to the flat span form
    (seconds; ph "X" complete events only)."""
    spans = []
    for ev in obj.get("traceEvents", ()):
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args", ()))
        s = {"name": ev["name"], "ts": ev["ts"] / 1e6,
             "dur": ev.get("dur", 0) / 1e6, "pid": ev.get("pid", 0),
             "process": str(ev.get("pid", 0))}
        if "trace" in args:
            s["trace"] = args.pop("trace")
        if args:
            s["args"] = args
        spans.append(s)
    return spans


def _collect(paths, trace_mod) -> tuple[list[dict], list[dict]]:
    """Load every input into one offset-corrected, time-sorted span list."""
    dumps = []
    spans = []
    for p in paths:
        if os.path.isdir(p):
            names = sorted(f for f in os.listdir(p)
                           if f.startswith("trace_") and f.endswith(".json"))
            for f in names:
                dumps.append(trace_mod.load_dump(os.path.join(p, f)))
            continue
        obj = trace_mod.load_dump(p)
        if "traceEvents" in obj:
            spans.extend(_spans_from_chrome(obj))
        else:
            dumps.append(obj)
    spans.extend(trace_mod.merge_dumps(dumps))
    spans.sort(key=lambda s: s["ts"])
    return spans, dumps


def summarize(spans, metrics_mod) -> list[dict]:
    """Per-span-name stats rows (count, total seconds, p50/p95 ms)."""
    by_name: dict[str, object] = {}
    for s in spans:
        h = by_name.get(s["name"])
        if h is None:
            h = by_name[s["name"]] = metrics_mod.Histogram(s["name"])
        h.observe(float(s.get("dur", 0.0)))
    rows = []
    for name in sorted(by_name, key=lambda n: -by_name[n].sum):
        h = by_name[name]
        rows.append({"name": name, "count": h.count,
                     "total_s": round(h.sum, 6),
                     "p50_ms": round(h.percentile(50.0) * 1e3, 3),
                     "p95_ms": round(h.percentile(95.0) * 1e3, 3)})
    return rows


def step_records(spans, dumps) -> list[dict]:
    """The engine's step records, oldest first and once each: the args of
    the ring's ``serve.step`` spans and the raw dumps' ``steps`` stores
    (``Tracer.step_record``), by process and step number."""
    found = {}
    for s in spans:
        if s["name"] == "serve.step":
            found[s.get("process", "?"), s["args"]["step"]] = s["args"]
    for d in dumps:
        for rec in d.get("steps", ()):
            found.setdefault((d.get("process", "main"), rec["step"]), rec)
    return [found[key] for key in sorted(found)]


def step_line(rec) -> str:
    """One step on one line: its clocks in ms, its counts, its stages."""
    gap = "-" if rec["gap"] is None else f"{rec['gap'] * 1e3:.1f}"
    stages = "; ".join(f"{program} {dt * 1e3:.1f}"
                       for program, dt in rec["stages"])
    return (f"{rec['step']:>7}  {rec['wall'] * 1e3:>9.1f}  "
            f"{rec['host'] * 1e3:>8.1f}  {rec['device_wait'] * 1e3:>8.1f}  "
            f"{gap:>7}  {rec['chunk_rows']:>4}  {rec['admitted']:>5}  "
            f"{rec['finished']:>4}  {stages}")


def top_requests(spans, n: int) -> list[dict]:
    """The n slowest requests: wall time from a request's first span start
    to its last span end, across every process it touched."""
    reqs: dict = {}
    for s in spans:
        uids = [s["trace"]] if "trace" in s else list(
            s.get("args", {}).get("uids", ()))
        for uid in uids:
            t0, t1, cnt, procs = reqs.get(
                uid, (s["ts"], s["ts"], 0, set()))
            reqs[uid] = (min(t0, s["ts"]),
                         max(t1, s["ts"] + float(s.get("dur", 0.0))),
                         cnt + 1, procs | {s.get("process", "?")})
    ranked = sorted(reqs.items(), key=lambda kv: kv[1][0] - kv[1][1])
    out = []
    for uid, (t0, t1, cnt, procs) in ranked[:n]:
        out.append({"uid": uid, "wall_ms": round((t1 - t0) * 1e3, 3),
                    "spans": cnt, "processes": sorted(procs)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="merge / summarize serving trace dumps")
    ap.add_argument("paths", nargs="+",
                    help="raw dump file(s), dump directory, or trace.json")
    ap.add_argument("--out", default=None,
                    help="write a merged Perfetto trace.json here")
    ap.add_argument("--summarize", action="store_true",
                    help="print per-span-name count/total/p50/p95")
    ap.add_argument("--top", type=int, default=0, metavar="N",
                    help="print the N slowest requests by wall time")
    args = ap.parse_args(argv)

    trace_mod, metrics_mod = _import_observe()
    spans, dumps = _collect(args.paths, trace_mod)
    steps = step_records(spans, dumps)
    if not spans and not (steps and args.summarize):
        # a driver-only or pre-traffic dump directory is a normal state
        # for the read-only views — report it and exit clean so scripted
        # `traceview --summarize` probes don't fail the pipeline
        if args.summarize or args.top:
            print("traceview: no spans found (nothing to summarize)",
                  file=sys.stderr)
            return 0
        print("traceview: no spans found", file=sys.stderr)
        return 1

    if args.out:
        if not dumps:
            print("traceview: --out needs raw dumps (got a merged trace)",
                  file=sys.stderr)
            return 2
        path = trace_mod.write_chrome_trace(args.out, dumps)
        print(f"wrote {path} ({len(spans)} spans)")
    elif not args.summarize and not args.top and dumps:
        # bare invocation on raw dumps: merge next to the inputs
        first = args.paths[0]
        out_dir = first if os.path.isdir(first) else os.path.dirname(first)
        path = trace_mod.write_chrome_trace(
            os.path.join(out_dir or ".", "trace.json"), dumps)
        print(f"wrote {path} ({len(spans)} spans)")

    if args.summarize:
        rows = summarize(spans, metrics_mod)
        width = max((len(r["name"]) for r in rows), default=4)
        print(f"{'span':<{width}}  {'count':>6}  {'total_s':>10}  "
              f"{'p50_ms':>9}  {'p95_ms':>9}")
        for r in rows:
            print(f"{r['name']:<{width}}  {r['count']:>6}  "
                  f"{r['total_s']:>10.4f}  {r['p50_ms']:>9.3f}  "
                  f"{r['p95_ms']:>9.3f}")
        if steps:
            print(f"\nsteps (newest {min(len(steps), STEP_ROWS)} of "
                  f"{len(steps)}; ms):")
            print(f"{'step':>7}  {'wall':>9}  {'host':>8}  {'wait':>8}  "
                  f"{'gap':>7}  {'rows':>4}  {'admit':>5}  {'done':>4}  "
                  f"stages")
            for rec in steps[-STEP_ROWS:]:
                print(step_line(rec))

    if args.top:
        print(f"\ntop {args.top} slowest requests:")
        for r in top_requests(spans, args.top):
            print(f"  uid {r['uid']}: {r['wall_ms']:.3f} ms over "
                  f"{r['spans']} spans in {','.join(r['processes'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
