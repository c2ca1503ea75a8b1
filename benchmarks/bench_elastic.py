#!/usr/bin/env python
"""Elastic serving bench: SLO-burn autoscaling and zero-downtime weight
swaps under a bursty arrival schedule -> ``benchmarks/elastic.jsonl``.

One arrival schedule — a quiet trickle, then a burst of long-prefill
requests landing at once, then a quiet tail — is driven through the
multi-process cluster three ways:

- ``fixed_small``: the minimum fleet, pinned (the burst overloads it);
- ``fixed_big``:   the maximum fleet, pinned (over-provisioned burn);
- ``autoscale``:   starts at the minimum with the elastic control plane
  (``serve/control.py``) ticking between polls — the burst's queue
  depth / SLO burn scales the fleet up within the policy cooldown, and
  the quiet tail scales it back down.

Each mode records p95 latency, shed rate, and the sampled
``fleet_size_timeline``.  A fourth phase drives a steady stream through
a small cluster and hot-swaps the weights to a LoRA adapter bank
mid-run (``ControlPlane.swap_weights``): the record proves the swap
window dropped zero requests and that every completion carries the
generation that primed it (in-flight finish on the old generation,
post-swap on the new).

With ``--verify``, every non-shed completion in every mode must be
token-identical to the max-size fixed fleet's (placement, fleet size,
and mid-run scaling are invisible in the tokens), and the swap phase's
completions must be token-identical across the generation boundary
(tenant-0 requests: the adapter bank cannot perturb the base path).

CPU-proof by design (the same tiny-config fixture as bench_serving);
numbers are for trend-gating via tools/benchdiff.py, not headlines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402

from progen_tpu.observe.platform import stamp_record  # noqa: E402
from progen_tpu.observe import slo as _slo  # noqa: E402


def latency_percentiles(lat):
    if not lat:
        return 0.0, 0.0
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 95)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="default")
    ap.add_argument("--requests", type=int, default=18,
                    help="total requests per mode (trickle+burst+tail)")
    ap.add_argument("--burst-frac", type=float, default=0.5,
                    help="fraction of requests landing in the one-instant "
                         "long-prefill burst")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="trickle arrival rate (req/s) outside the burst")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prime-min", type=int, default=8)
    ap.add_argument("--prime-max", type=int, default=96,
                    help="burst requests prime at this length")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttl", type=float, default=None,
                    help="per-request deadline (s); unset = no sheds, "
                         "shed_rate still recorded (as 0)")
    ap.add_argument("--min-prefill", type=int, default=1)
    ap.add_argument("--max-prefill", type=int, default=2)
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=2)
    ap.add_argument("--cooldown", type=float, default=1.0,
                    help="autoscale policy cooldown (s)")
    ap.add_argument("--swap-at", type=int, default=4,
                    help="swap phase: completions served before the "
                         "rolling LoRA swap starts")
    ap.add_argument("--swap-requests", type=int, default=12,
                    help="swap phase request count")
    ap.add_argument("--lora-tenants", type=int, default=3)
    ap.add_argument("--lora-rank", type=int, default=4)
    ap.add_argument("--skip-modes", default="",
                    help="comma list of modes to skip "
                         "(fixed_small,fixed_big,autoscale,swap)")
    ap.add_argument("--verify", action="store_true",
                    help="assert token identity of every non-shed "
                         "completion against the max-size fixed fleet, "
                         "and across the swap's generation boundary")
    ap.add_argument("--out", metavar="FILE", default=None)
    args = ap.parse_args()

    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()

    import jax

    from progen_tpu.decode import Request
    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.serve import (
        BurnRatePolicy,
        ControlPlane,
        ServeCluster,
        make_spec,
    )

    cfg = CONFIGS[args.config]
    pmax = min(args.prime_max, cfg.seq_len - args.max_new - 1)
    pmin = min(args.prime_min, pmax)
    skip = {m.strip() for m in args.skip_modes.split(",") if m.strip()}

    # ---- the one bursty schedule every mode replays ------------------
    n = args.requests
    n_burst = max(1, int(n * args.burst_frac))
    n_pre = max(1, (n - n_burst) // 2)
    n_tail = n - n_burst - n_pre
    rng = np.random.default_rng(args.seed)
    arrivals: list[float] = []
    t = 0.0
    for _ in range(n_pre):
        t += rng.exponential(1.0 / args.rate)
        arrivals.append(t)
    t_burst = t + 0.2
    arrivals.extend([t_burst] * n_burst)   # the burst: one instant
    t = t_burst
    for _ in range(n_tail):
        t += rng.exponential(1.0 / args.rate)
        arrivals.append(t)
    # burst requests prime long (the expensive prefill wall); the
    # trickle stays short — specs fixed up front for token identity
    specs = []
    for i in range(n):
        if n_pre <= i < n_pre + n_burst:
            plen = pmax
        else:
            plen = int(rng.integers(pmin, max(pmin, pmax // 4) + 1))
        specs.append(rng.integers(1, cfg.num_tokens, plen).tolist())

    engine_kw = dict(num_slots=args.slots, chunk_size=args.chunk,
                     max_len=min(cfg.seq_len, pmax + args.max_new + 1),
                     prefill_batch=2, handoff_depth=2)
    wspec = make_spec(cfg, mixed_precision=True, init_seed=0,
                      engine=engine_kw, statusz=True)

    def make_request(uid: int, submit_time: float, tenant: int = 0,
                     toks=None) -> Request:
        return Request(uid=uid, tokens=(specs[uid] if toks is None
                                        else toks),
                       max_new_tokens=args.max_new, top_k=25,
                       temperature=1.0, seed=args.seed + uid,
                       submit_time=submit_time, ttl=args.ttl,
                       tenant=tenant)

    def run_mode(name: str, prefill: int, replicas: int, *,
                 autoscale: bool = False) -> dict:
        cluster = ServeCluster(wspec, prefill_procs=prefill,
                               replicas=replicas)
        control = None
        if autoscale:
            control = ControlPlane(cluster, BurnRatePolicy(
                min_prefill=args.min_prefill,
                max_prefill=args.max_prefill,
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas,
                up_burn=1.5, down_burn=0.5,
                up_queue_per_worker=2.0, down_queue_per_worker=0.5,
                cooldown_s=args.cooldown))
        try:
            # warm the starting fleet off the clock (scaled-up workers
            # warm themselves: add_worker forces aot_warmup pre-ready)
            wrng = np.random.default_rng(args.seed + 999)
            for i in range(max(2, prefill, replicas)):
                cluster.submit(Request(
                    uid=10_000_000 + i,
                    tokens=wrng.integers(1, cfg.num_tokens, pmax).tolist(),
                    max_new_tokens=args.max_new, top_k=25, temperature=1.0,
                    seed=args.seed, submit_time=time.perf_counter()))
            cluster.drain(timeout=600.0)
            cluster.poll(0.0)

            t0 = time.perf_counter()
            served: list = []
            nxt = 0
            timeline = [[0.0, cluster.prefill_procs, cluster.replicas]]
            last_tick = -1e9
            while len(served) < n:
                now = time.perf_counter() - t0
                while nxt < n and arrivals[nxt] <= now:
                    cluster.submit(make_request(nxt, t0 + arrivals[nxt]))
                    nxt += 1
                served.extend(cluster.poll(0.02))
                now = time.perf_counter() - t0
                if control is not None and now - last_tick >= 0.25:
                    last_tick = now
                    control.tick()
                if timeline[-1][1:] != [cluster.prefill_procs,
                                        cluster.replicas]:
                    timeline.append([round(now, 3),
                                     cluster.prefill_procs,
                                     cluster.replicas])
            wall = time.perf_counter() - t0
            timeline.append([round(wall, 3), cluster.prefill_procs,
                             cluster.replicas])
        finally:
            cluster.shutdown()
        ok = [c for c in served if c.ok]
        shed = [c for c in served if not c.ok]
        p50, p95 = latency_percentiles(sorted(c.latency for c in ok))
        out = {
            "mode": name,
            "prefill_procs": prefill,
            "replicas": replicas,
            "wall_s": round(wall, 3),
            "ok_requests": len(ok),
            "shed_requests": len(shed),
            "shed_rate": round(len(shed) / max(1, n), 4),
            "p50_latency_s": round(p50, 3),
            "p95_latency_s": round(p95, 3),
            "within_slo_frac": round(_slo.frac_within_values(
                (c.latency for c in ok), 10.0) if ok else 0.0, 3),
            "fleet_size_timeline": timeline,
            "max_prefill_seen": max(p for _, p, _r in timeline),
            "max_replicas_seen": max(r for _, _p, r in timeline),
        }
        if control is not None:
            events = [e["event"] for e in control.journal]
            out["control"] = {
                "scale_ups": events.count("scale_up"),
                "scale_downs": events.count("scale_down"),
                "journal": control.journal[-32:],
            }
        out["tokens"] = {c.uid: [int(x) for x in c.tokens] for c in ok}
        print(f"elastic[{name}]: p95={out['p95_latency_s']}s "
              f"shed={out['shed_rate']:.0%} "
              f"fleet_max={out['max_prefill_seen']}p/"
              f"{out['max_replicas_seen']}r wall={out['wall_s']}s",
              file=sys.stderr)
        return out

    def run_swap() -> dict:
        """Steady stream; rolling LoRA swap after --swap-at
        completions.  Zero drops, generation-tagged completions."""
        ns = args.swap_requests
        cluster = ServeCluster(wspec, prefill_procs=1, replicas=1)
        control = ControlPlane(cluster)
        try:
            wrng = np.random.default_rng(args.seed + 999)
            cluster.submit(Request(
                uid=10_000_000,
                tokens=wrng.integers(1, cfg.num_tokens, pmax).tolist(),
                max_new_tokens=args.max_new, top_k=25, temperature=1.0,
                seed=args.seed, submit_time=time.perf_counter()))
            cluster.drain(timeout=600.0)
            cluster.poll(0.0)

            srng = np.random.default_rng(args.seed + 7)
            stoks = [srng.integers(
                1, cfg.num_tokens,
                int(srng.integers(pmin, pmax + 1))).tolist()
                for _ in range(ns)]
            t0 = time.perf_counter()
            served: list = []
            nxt = 0
            swap_gen = None
            swap_wall = None
            while len(served) < ns:
                now = time.perf_counter() - t0
                # steady trickle; arrivals due while the blocking swap
                # rolled the fleet submit the moment it returns, so the
                # swap window always has live traffic on both sides
                while nxt < ns and nxt * (1.0 / args.rate) <= now:
                    cluster.submit(make_request(
                        nxt, t0 + nxt / args.rate, toks=stoks[nxt]))
                    nxt += 1
                served.extend(cluster.poll(0.02))
                if swap_gen is None and len(served) >= args.swap_at:
                    ts = time.perf_counter()
                    swap_gen = control.swap_weights(lora={
                        "tenants": args.lora_tenants,
                        "rank": args.lora_rank, "seed": 0})
                    swap_wall = round(time.perf_counter() - ts, 3)
            wall = time.perf_counter() - t0
        finally:
            cluster.shutdown()
        ok = [c for c in served if c.ok]
        gens = {c.uid: int(getattr(c, "generation", 0)) for c in served}
        old = sum(1 for g in gens.values() if g < (swap_gen or 1))
        new = sum(1 for g in gens.values() if g >= (swap_gen or 1))
        p50, p95 = latency_percentiles(sorted(c.latency for c in ok))
        out = {
            "mode": "swap",
            "requests": ns,
            "swap_at": args.swap_at,
            "swap_generation": swap_gen,
            "swap_window_s": swap_wall,
            "wall_s": round(wall, 3),
            "ok_requests": len(ok),
            "swap_dropped": ns - len(served),
            "served_old_gen": old,
            "served_new_gen": new,
            "p50_latency_s": round(p50, 3),
            "p95_latency_s": round(p95, 3),
            "tokens": {c.uid: [int(x) for x in c.tokens] for c in ok},
            "generations": gens,
        }
        print(f"elastic[swap]: gen={swap_gen} window={swap_wall}s "
              f"dropped={out['swap_dropped']} old/new="
              f"{old}/{new}", file=sys.stderr)
        return out

    modes: dict = {}
    if "fixed_big" not in skip:
        modes["fixed_big"] = run_mode(
            "fixed_big", args.max_prefill, args.max_replicas)
    if "fixed_small" not in skip:
        modes["fixed_small"] = run_mode(
            "fixed_small", args.min_prefill, args.min_replicas)
    if "autoscale" not in skip:
        modes["autoscale"] = run_mode(
            "autoscale", args.min_prefill, args.min_replicas,
            autoscale=True)
    swap = run_swap() if "swap" not in skip else None

    if args.verify:
        # fleet size / mid-run scaling must be invisible in the tokens:
        # every ok completion matches the max-size fixed fleet's
        ref = modes.get("fixed_big", {}).get("tokens", {})
        for name, m in modes.items():
            if name == "fixed_big" or not ref:
                continue
            bad = [u for u, tk in m["tokens"].items()
                   if u in ref and tk != ref[u]]
            assert not bad, f"{name} diverged from fixed_big: uids {bad}"
        if swap is not None:
            assert swap["swap_dropped"] == 0, \
                f"swap window dropped {swap['swap_dropped']} requests"
            assert swap["served_old_gen"] > 0, \
                "no completion finished on the priming generation"
            assert swap["served_new_gen"] > 0, \
                "no completion served on the new generation"
        print("verify: elastic token identity + zero-drop swap OK",
              file=sys.stderr)

    # tokens are for --verify, too bulky for the committed record
    for m in modes.values():
        m.pop("tokens", None)
    if swap is not None:
        swap.pop("tokens", None)

    auto = modes.get("autoscale", {})
    record = stamp_record({
        "metric": "serving_elastic",
        "config": args.config,
        "requests": n,
        "burst_requests": n_burst,
        "rate_per_sec": args.rate,
        "max_new_tokens": args.max_new,
        "ttl_s": args.ttl,
        "bounds": {"prefill": [args.min_prefill, args.max_prefill],
                   "replicas": [args.min_replicas, args.max_replicas]},
        # top-level gates (benchdiff WATCHED): the autoscale mode's
        # latency + sheds, and the swap window's drop count
        "p50_latency_s": auto.get("p50_latency_s"),
        "p95_latency_s": auto.get("p95_latency_s"),
        "shed_rate": auto.get("shed_rate"),
        "within_slo_frac": auto.get("within_slo_frac"),
        **({"swap_dropped": swap["swap_dropped"],
            "swap_window_s": swap["swap_window_s"]}
           if swap is not None else {}),
        "modes": modes,
        **({"swap": swap} if swap is not None else {}),
        "verified": bool(args.verify),
        "platform": jax.devices()[0].platform,
    })
    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
