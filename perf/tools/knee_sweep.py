"""Find the knee of an open-loop serving cell once, on the chip.

    python3 perf/tools/knee_sweep.py --workload serve-small-steady \
        --rates 4,6,8,10,12 --seconds 20 --seed 1

One process and one set-up; each rate is a short window on the drained
engine.  A rate is sustained when the queue is no longer at the window's
end than at its middle.  The knee is the highest sustained rate; the cell's
fixed rate is four fifths of it and goes into the traffic file by hand,
with this tool's table.  Not part of a benchmark run.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)

    from perf.lib import harness, loadgen, stats, traffic

    bench = harness.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(entry["traffic"])
    config = harness.load_config(entry["config"])
    devices = harness.require_tpu(entry["chips"])
    serve = harness.load_module(workload["runner"])
    engine, _, model_config = serve.build_engine(workload, config, args.seed)
    make = serve.request_factory(workload, model_config.num_tokens)
    # one throw-away request runs both programs before the first window
    warm = traffic.serve_requests(
        dict(workload["traffic"], arrivals={"kind": "backlog",
                                            "requests_per_second": 1}),
        args.seed, 1.0, model_config.num_tokens)
    engine.submit(make(dict(warm[0], uid=-1, max_new=40), 0.0))
    engine.run_until_idle()
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(workload["traffic"],
                    arrivals={"kind": "open", "rate": rate})
        requests = traffic.serve_requests(spec, args.seed, args.seconds,
                                          model_config.num_tokens)
        rec = loadgen.drive_open_loop(engine, requests, make,
                                      seconds=args.seconds,
                                      drain_seconds=args.seconds)
        engine.run_until_idle()
        engine.completions.clear()

        def queue_at(t):
            past = [q for _, end, _, _, q in rec.steps if end <= t]
            return past[-1] if past else 0

        norm = [1e3 * (rec.completed[r["uid"]][0] - r["due"])
                / rec.completed[r["uid"]][1]
                for r in requests if r["uid"] in rec.completed]
        chunk = workload["engine"]["chunk_size"]
        steps = [1e3 * (e - s) / chunk for s, e, c, _, _ in rec.steps if c]
        row = {
            "rate": rate, "requests": len(requests),
            "answered": len(norm),
            "queue_mid": queue_at(args.seconds / 2),
            "queue_end": queue_at(args.seconds),
            "drained_at_s": rec.steps[-1][1] if rec.steps else 0.0,
            "norm_latency_p50": stats.percentile(norm, 50) if norm else None,
            "norm_latency_p90": stats.percentile(norm, 90) if norm else None,
            "step_ms_median": stats.median(steps) if steps else None,
            "occupancy_mean": (sum(a for _, _, c, a, _ in rec.steps if c)
                               / max(1, len(steps))
                               / workload["engine"]["num_slots"]),
        }
        row["sustained"] = row["queue_end"] <= row["queue_mid"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    sustained = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"device": harness.device_record(devices, 1),
                      "knee": max(sustained) if sustained else None,
                      "seconds": args.seconds, "seed": args.seed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
