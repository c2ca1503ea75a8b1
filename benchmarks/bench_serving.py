"""Serving throughput/latency under a synthetic Poisson request stream.

Drives :class:`progen_tpu.decode.ServingEngine` the way a server would
be driven: requests arrive at Exp(rate) inter-arrival times with ragged
prime lengths, are admitted into slots between decode chunks, and report
completion latency from their ARRIVAL time (so queueing under load is
measured, not hidden).  Prints ONE JSON line::

    {"metric": "serving", "tokens_per_sec": ..., "p50_latency_s": ...,
     "p95_latency_s": ..., "requests": N, "slots": S, "chunk": C, ...}

Usage::

    JAX_PLATFORMS=cpu python benchmarks/bench_serving.py --config small \
        --requests 16 --rate 4 --slots 4 --chunk 16 --max-new 32

A warmup pass (engine compile: admission + decode chunk programs) runs
before the clock starts.

``--paged`` switches the engine to the paged SGU gate cache (page pool +
per-request page tables, ``decode/paging.py``); ``--budget-slots N``
sizes the pool to the same modeled gate-row HBM as a fixed-slot engine
with N slots, for equal-budget concurrency comparisons — the record's
``max_in_flight`` and ``gate_hbm_bytes`` fields carry the comparison
(see benchmarks/paged.md).

``--disagg`` splits serving into the prefill-worker/handoff-queue/
decode-pool stages (``decode/handoff.py``);
the record then ALSO replays the identical arrival schedule on an inline
engine and carries ``p95_latency_s_inline`` etc. for the side-by-side.
``--long-frac`` mixes that fraction of near-``max_len`` primes into the
Poisson stream (the long-prefill interference scenario disaggregation
exists for).

``--serve-procs`` drives the SAME arrival schedule through a real
multi-process cluster (``progen_tpu/serve/``): ``--prefill-procs``
prefill worker subprocesses ship CRC-framed handle frames to
``--replicas`` decode replica subprocesses behind the router.  The
``serving_multiproc`` record carries per-stage ``stage_seconds`` (the
decode process's ``prefill_s`` is 0 — prefill wall left the process),
the cluster's transport counters, and side-by-side ``inline`` /
``sp_disagg`` (single-process disaggregated) reruns of the identical
schedule; ``--verify`` asserts the cluster's completions are
token-identical to the in-process engine AND that a second fresh
cluster replays them exactly (``benchmarks/multiproc.md``).

``--chaos`` arms the fault injector with ``--faults`` (a
``PROGEN_FAULTS``-syntax plan hitting the serving points) and records a
``serving_chaos`` line instead: goodput (tokens/sec over OK completions
only), latency percentiles over OK completions, the fraction finishing
within ``--slo`` seconds, and the engine's robustness counters (sheds,
contained faults, kernel fallbacks).  ``--verify`` additionally re-runs
the same request set fault-free and asserts every non-shed chaos
completion is token-identical (per-request seed determinism), then
exercises snapshot -> restore -> replay and asserts the SAME parity —
the replay-correctness smoke ``tools/check.sh`` gates on.  ``--out``
appends the record to a JSONL file (``benchmarks/chaos.jsonl`` by
convention) in addition to stdout.

``--trace-file`` replays a recorded heavy-traffic trace
(``benchmarks/traces/*.jsonl``) instead of drawing a Poisson stream, and
records a ``serving_qos`` line.  The replay runs on VIRTUAL time — the
trace header fixes ``step_dt`` (virtual seconds per engine step), every
arrival with ``at <= vnow`` is submitted before each step, and latencies
are virtual — so the whole schedule (admissions, preemptions, sheds,
completions) is bit-deterministic across machines and the benchdiff
bands on the QoS fields can be tight.  The record carries per-priority-
class and per-tenant virtual p50/p95, Jain's fairness index over
weight-normalized tenant service, preemption and shed counts, and the
high-class p95 margin over a FIFO rerun of the same trace (priorities
zeroed, no tenant weights).  ``--verify`` additionally asserts every
non-shed completion is token-identical to an uncontended rerun, that the
high class beat FIFO, and that no nonzero-weight tenant starved
(docs/SERVING.md §10).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.observe import slo as _slo
from progen_tpu.observe.meter import profile_trace
from progen_tpu.observe.metrics import latency_percentiles
from progen_tpu.observe.platform import stamp_record
from progen_tpu.observe.trace import (
    configure_tracing,
    get_tracer,
    merge_trace_dir,
    trace_dump_path,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="small")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean request arrivals per second (Poisson)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prime-min", type=int, default=8)
    ap.add_argument("--prime-max", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-len", type=int, default=None,
                    help="engine max_len (the serving contract: longest "
                         "request the deployment admits); default sizes "
                         "to this run's worst case prime+max_new+1")
    ap.add_argument("--paged", action="store_true",
                    help="paged SGU gate cache (global page pool) instead "
                         "of per-slot fixed max_len slabs")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size; default covers num_slots full "
                         "rows (no sharing pressure)")
    ap.add_argument("--paged-impl", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--budget-slots", type=int, default=None,
                    help="with --paged and no --num-pages: size the pool "
                         "to the SAME modeled gate-cache HBM as a "
                         "fixed-slot engine with this many slots "
                         "(equal-budget comparison)")
    ap.add_argument("--quantize", choices=("weights", "weights+pages"),
                    default=None,
                    help="opt-in int8 serving: 'weights' re-types dense "
                         "kernels and SGU spatial weights to int8 (f32 "
                         "per-channel scales); 'weights+pages' also stores "
                         "the paged gate cache as 8-bit pages (needs "
                         "--paged).  Emits a serving_quant record PLUS a "
                         "serving_quant_full full-precision record driven "
                         "on the identical schedule (same schedule_hash), "
                         "so benchdiff compares like with like")
    ap.add_argument("--match-gate", type=float, default=0.98,
                    help="with --quantize --verify: minimum greedy "
                         "token-match rate vs the full-precision engine "
                         "(the accuracy-verify tier, docs/SERVING.md §12)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode: prefill worker + "
                         "bounded handoff queue + donating merge; the "
                         "record also replays the same arrivals inline "
                         "for the p95 comparison")
    ap.add_argument("--prefill-batch", type=int, default=None,
                    help="max requests per prefill-worker dispatch "
                         "(default: num_slots)")
    ap.add_argument("--handoff-depth", type=int, default=2,
                    help="handoff queue bound (handles, not requests)")
    ap.add_argument("--serve-procs", action="store_true",
                    help="multi-process serving: spawn real prefill-worker "
                         "and decode-replica subprocesses behind the "
                         "router (progen_tpu/serve) and drive the same "
                         "arrival schedule through the cluster; records a "
                         "serving_multiproc line with per-stage timing, "
                         "transport counters, and in-process inline + "
                         "single-process-disagg comparison reruns")
    ap.add_argument("--prefill-procs", type=int, default=1,
                    help="prefill worker processes (with --serve-procs)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="decode replica processes (with --serve-procs)")
    ap.add_argument("--autoscale", action="store_true",
                    help="with --serve-procs: run the elastic control "
                         "plane (serve/control.py) between poll rounds — "
                         "scale the fleet on SLO burn rate and queue "
                         "depth within the min/max bounds; the record "
                         "gains the control journal summary")
    ap.add_argument("--min-prefill", type=int, default=None,
                    help="autoscale floor for prefill workers "
                         "(default: --prefill-procs)")
    ap.add_argument("--max-prefill", type=int, default=None,
                    help="autoscale ceiling for prefill workers "
                         "(default: --prefill-procs + 2)")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscale floor for decode replicas "
                         "(default: --replicas)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscale ceiling for decode replicas "
                         "(default: --replicas + 2)")
    ap.add_argument("--swap-at", type=int, default=None,
                    help="with --serve-procs: after N served completions, "
                         "hot-swap weights via a rolling worker upgrade "
                         "(new generation, zero dropped requests); the "
                         "record gains the swap outcome")
    ap.add_argument("--zipf", type=float, default=None, metavar="ALPHA",
                    help="popular-prompt mix: draw every prime from a "
                         "pool of --zipf-pool distinct prompts with "
                         "Zipf(ALPHA) weights instead of fresh random "
                         "primes — the repeated-prefix workload the "
                         "prefix cache dedups; with --serve-procs "
                         "--paged this records a serving_fleetcache "
                         "line comparing cache-aware vs cache-blind "
                         "routing on the same schedule")
    ap.add_argument("--zipf-pool", type=int, default=8,
                    help="distinct prompts in the --zipf pool")
    ap.add_argument("--long-frac", type=float, default=0.0,
                    help="fraction of requests with near-max_len primes "
                         "(mixed long-prefill load); the rest draw short "
                         "primes from [prime-min, prime-max/4]")
    ap.add_argument("--scenario-mix", default=None,
                    help="weighted workload mix, e.g. 'generate=0.5,"
                         "infill=0.2,embed=0.2,lora=0.1': ONE Poisson "
                         "stream mixing all four first-class workloads "
                         "through one engine; the record carries "
                         "per-workload p50/p95 latency.  Not combinable "
                         "with --disagg/--serve-procs/--chaos")
    ap.add_argument("--lora-tenants", type=int, default=4,
                    help="adapter bank size T for the lora workload "
                         "(tenant 0 is the zero-adapter base; lora "
                         "requests cycle tenants 1..T-1)")
    ap.add_argument("--lora-rank", type=int, default=8)
    ap.add_argument("--chaos", action="store_true",
                    help="arm the fault injector with --faults and record "
                         "a serving_chaos line (goodput, within-SLO "
                         "fraction, robustness counters)")
    ap.add_argument("--faults",
                    default="serve.admit:io_error:at=2;"
                            "serve.prefill:unavailable:at=2;"
                            "serve.decode_chunk:io_error:at=3;"
                            "serve.harvest:io_error:at=2",
                    help="fault plan (PROGEN_FAULTS syntax) for --chaos; "
                         "the default hits four serving points once each "
                         "with transient faults")
    ap.add_argument("--faults-seed", type=int, default=0)
    ap.add_argument("--ttl", type=float, default=None,
                    help="per-request time-to-live in seconds; expired "
                         "requests are shed as typed completions")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded submit queue; overflow is shed per "
                         "--shed-policy")
    ap.add_argument("--shed-policy", choices=("reject", "shed-oldest"),
                    default="reject")
    ap.add_argument("--slo", type=float, default=10.0,
                    help="latency SLO in seconds for the within_slo_frac "
                         "metric (over OK completions) — evaluated by "
                         "observe/slo.py, the same code path the live "
                         "fleet's burn rates use")
    ap.add_argument("--slo-target", type=float, default=0.95,
                    help="objective fraction of requests within --slo; "
                         "the record's slo_burn_rate is the error-budget "
                         "burn against this target")
    ap.add_argument("--statusz", action="store_true",
                    help="with --serve-procs: start the live introspection "
                         "plane in every process and self-check /healthz "
                         "+ /metricsz from driver and workers mid-run "
                         "(the check.sh statusz smoke)")
    ap.add_argument("--aot-warmup", action="store_true",
                    help="warm up via AOT lower().compile() over the "
                         "(prefill bucket, chunk) grid instead of two "
                         "sacrificial requests")
    ap.add_argument("--trace-file", metavar="FILE", default=None,
                    help="replay a recorded QoS trace (header line + one "
                         "arrival per line) on virtual time instead of a "
                         "Poisson stream; records a serving_qos line "
                         "with per-class/per-tenant latency, fairness "
                         "index and the FIFO-rerun comparison "
                         "(docs/SERVING.md §10)")
    ap.add_argument("--verify", action="store_true",
                    help="after the measured run: fault-free rerun + "
                         "token-identity assert on non-shed completions, "
                         "then snapshot/restore replay-parity assert")
    ap.add_argument("--out", metavar="FILE", default=None,
                    help="also append the record to this JSONL file")
    ap.add_argument("--trace", action="store_true",
                    help="record request spans in every process and merge "
                         "them into one Perfetto trace.json under "
                         "--trace-out (see docs/OBSERVABILITY.md)")
    ap.add_argument("--trace-out", metavar="DIR", default="trace_out",
                    help="directory for per-process trace dumps and the "
                         "merged trace.json (with --trace)")
    ap.add_argument("--xprof-dir", metavar="DIR", default=None,
                    help="record an xprof/TensorBoard profile of the "
                         "measured drive into this directory")
    args = ap.parse_args()

    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()

    if args.trace:
        os.makedirs(args.trace_out, exist_ok=True)
        configure_tracing(enabled=True, process="driver")

    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.models import ProGen
    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.parallel import unbox
    from progen_tpu.resilience import faults

    cfg = CONFIGS[args.config]
    policy = make_policy(True)
    model = ProGen(config=cfg, policy=policy)
    toks = jnp.zeros((1, cfg.seq_len), jnp.int32)
    params = unbox(jax.jit(model.init)(jax.random.key(0), toks))

    if args.quantize:
        if (args.serve_procs or args.chaos or args.scenario_mix
                or args.trace_file):
            raise SystemExit("--quantize drives one in-process engine "
                             "pair; drop --serve-procs/--chaos/"
                             "--scenario-mix/--trace-file")
        if args.quantize == "weights+pages" and not args.paged:
            raise SystemExit("--quantize weights+pages requires --paged")

    if args.trace_file:
        if (args.disagg or args.serve_procs or args.chaos
                or args.scenario_mix):
            raise SystemExit("--trace-file drives one in-process engine; "
                             "drop --disagg/--serve-procs/--chaos/"
                             "--scenario-mix")
        _run_trace(args, cfg, params, policy)
        return

    mix = _parse_mix(args.scenario_mix) if args.scenario_mix else None
    if mix and (args.disagg or args.serve_procs or args.chaos):
        raise SystemExit("--scenario-mix drives one in-process engine; "
                         "drop --disagg/--serve-procs/--chaos")

    rng = np.random.default_rng(args.seed)
    pmax = min(args.prime_max, cfg.seq_len - args.max_new - 1)
    pmin = min(args.prime_min, pmax)

    # request specs are FIXED up front so a --verify fault-free rerun
    # replays the exact same (tokens, seed) set — per-request seed
    # determinism then makes token identity a hard assert, not a hope
    if args.zipf is not None:
        # Zipf popular-prompt mix: K distinct primes, request i draws
        # prime rank r with p(r) ~ 1/r^alpha — repeated primes are what
        # the (fleet) prefix cache dedups.  Pool and assignment come
        # from the SAME fixed rng stream as the plain specs, so --verify
        # reruns replay the identical mix.
        pool_n = max(1, args.zipf_pool)
        pool = [rng.integers(1, cfg.num_tokens,
                             int(rng.integers(pmin, pmax + 1))).tolist()
                for _ in range(pool_n)]
        pmf = 1.0 / np.arange(1, pool_n + 1) ** float(args.zipf)
        pmf /= pmf.sum()
        specs = [list(pool[int(i)])
                 for i in rng.choice(pool_n, size=args.requests, p=pmf)]
    elif args.long_frac > 0:
        short_hi = max(pmin, pmax // 4)
        specs = [rng.integers(
            1, cfg.num_tokens,
            pmax if rng.random() < args.long_frac
            else int(rng.integers(pmin, short_hi + 1))).tolist()
            for _ in range(args.requests)]
    else:
        specs = [rng.integers(1, cfg.num_tokens,
                              int(rng.integers(pmin, pmax + 1))).tolist()
                 for _ in range(args.requests)]

    # per-request workload assignment (and infill scaffolds) are fixed up
    # front too, same reason: --verify reruns replay the identical mix
    workloads = ["generate"] * args.requests
    scaffolds: dict = {}
    if mix:
        from progen_tpu.workloads import ScaffoldSpec

        live = sorted(w for w in mix if mix[w] > 0)
        workloads = list(rng.choice(live, size=args.requests,
                                    p=[mix[w] for w in live]))
        # guarantee every requested workload appears at least once
        for i, w in enumerate(live[:args.requests]):
            workloads[i] = w
        for uid, w in enumerate(workloads):
            if w != "infill":
                continue
            srng = np.random.default_rng(args.seed + 31 * uid)
            tmpl: list = list(specs[uid])
            for g in range(args.max_new):
                r = srng.random()
                if g > 0 and r < 0.25:
                    # interior frozen scaffold position (one-hot row)
                    tmpl.append(int(srng.integers(1, cfg.num_tokens)))
                elif r < 0.625:
                    k = min(8, cfg.num_tokens - 1)
                    allowed = srng.choice(np.arange(1, cfg.num_tokens),
                                          size=k, replace=False)
                    tmpl.append(tuple(int(a) for a in allowed))
                else:
                    tmpl.append(None)
            scaffolds[uid] = ScaffoldSpec(template=tmpl,
                                          vocab=cfg.num_tokens)

    # fingerprint of everything that determines the token streams being
    # compared: quant records carry it so benchdiff never diffs
    # token_match_rate (or throughput) across DIFFERENT schedules
    sched_hash = hashlib.blake2b(json.dumps({
        "config": args.config, "requests": args.requests,
        "seed": args.seed, "rate": args.rate, "max_new": args.max_new,
        "specs": specs, "workloads": workloads,
    }, sort_keys=True).encode(), digest_size=8).hexdigest()

    def make_request(uid: int, submit_time: float,
                     ttl: float | None = None) -> Request:
        common = dict(uid=uid, top_k=25, temperature=1.0,
                      seed=args.seed + uid, submit_time=submit_time,
                      ttl=ttl)
        w = workloads[uid]
        if w == "infill":
            return Request(workload="infill",
                           **scaffolds[uid].request_kwargs(), **common)
        if w == "embed":
            return Request(tokens=specs[uid], max_new_tokens=args.max_new,
                           workload="embed", **common)
        tenant = 0
        if w == "lora":
            tenant = 1 + uid % max(1, args.lora_tenants - 1)
        return Request(tokens=specs[uid], max_new_tokens=args.max_new,
                       tenant=tenant, workload=w, **common)

    max_len = args.max_len or min(cfg.seq_len, pmax + args.max_new + 1)
    num_pages = args.num_pages
    num_pages_fp = args.num_pages
    if args.paged and num_pages is None and args.budget_slots is not None:
        from progen_tpu.train.memory import equal_budget_pages

        # the SAME byte budget buys ~2x the pages at int8 — that is the
        # equal-HBM capacity the serving_quant record reports
        gd = "int8" if args.quantize == "weights+pages" else "bf16"
        num_pages = equal_budget_pages(cfg, dense_slots=args.budget_slots,
                                       max_len=max_len,
                                       page_size=args.page_size,
                                       gate_dtype=gd)
        num_pages_fp = equal_budget_pages(
            cfg, dense_slots=args.budget_slots, max_len=max_len,
            page_size=args.page_size, gate_dtype="bf16")
    paged_kwargs = dict(
        paged=True, page_size=args.page_size, num_pages=num_pages,
        paged_impl=args.paged_impl, prefix_cache=not args.no_prefix_cache,
    ) if args.paged else {}

    # unconditional: mk_engine applies it only when use_disagg resolves
    # True (and --serve-procs builds sp-disagg comparison engines even
    # without --disagg)
    disagg_kwargs = dict(
        disagg=True, prefill_batch=args.prefill_batch,
        handoff_depth=args.handoff_depth,
    )

    lora_kwargs: dict = {}
    if mix and mix.get("lora", 0) > 0:
        from progen_tpu.workloads.lora import random_lora_bank

        lora_kwargs = dict(lora_bank=random_lora_bank(
            cfg, args.lora_tenants, args.lora_rank, seed=args.seed + 7))

    def mk_engine(*, robust: bool, use_disagg: bool | None = None,
                  use_lora: bool = True,
                  use_quant: bool = True) -> ServingEngine:
        kw = dict(paged_kwargs)
        if args.quantize and use_quant:
            kw["quantize"] = args.quantize
        elif args.paged:
            # the full-precision reference holds the SAME byte budget,
            # which at bf16 rows means fewer pages
            kw["num_pages"] = num_pages_fp
        if use_disagg if use_disagg is not None else args.disagg:
            kw.update(disagg_kwargs)
        if use_lora:
            kw.update(lora_kwargs)
        if robust:
            kw.update(max_queue=args.max_queue,
                      shed_policy=args.shed_policy)
        return ServingEngine(cfg, params, policy=policy,
                             num_slots=args.slots, chunk_size=args.chunk,
                             max_len=max_len, **kw)

    # warmup: compile the admission + chunk programs off the clock — AOT
    # over the whole (bucket, chunk) grid, or two sacrificial requests
    # (drawn from a SEPARATE rng so the measured specs stay fixed)
    warm_embed = bool(mix and mix.get("embed", 0) > 0)

    def warm(eng: ServingEngine) -> None:
        if args.aot_warmup:
            stats = eng.aot_warmup(max_prime=pmax, embed=warm_embed)
            print(f"aot warmup: {stats['programs']} programs in "
                  f"{stats['seconds']:.1f}s", file=sys.stderr)
            return
        wrng = np.random.default_rng(args.seed + 999)
        for i in range(min(2, args.slots)):
            eng.submit(Request(
                uid=10_000_000 + i,
                tokens=wrng.integers(1, cfg.num_tokens, pmax).tolist(),
                max_new_tokens=args.max_new, top_k=25, temperature=1.0,
                seed=args.seed, submit_time=time.perf_counter()))
        if warm_embed:
            eng.submit_embed(Request(
                uid=10_000_100, tokens=wrng.integers(
                    1, cfg.num_tokens, pmax).tolist(),
                submit_time=time.perf_counter()))
        eng.run_until_idle()
        eng.completions.clear()

    arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                         size=args.requests))

    def drive(eng: ServingEngine):
        """Serve the fixed request set on the fixed arrival schedule."""
        t0 = time.perf_counter()
        served: list = []
        nxt = 0
        mif = 0
        while len(served) < args.requests:
            now = time.perf_counter() - t0
            while nxt < args.requests and arrivals[nxt] <= now:
                req = make_request(nxt, t0 + arrivals[nxt], ttl=args.ttl)
                if getattr(req, "workload", "generate") == "embed":
                    eng.submit_embed(req)
                else:
                    eng.submit(req)
                nxt += 1
            if not eng.has_work:
                if nxt >= args.requests:
                    break  # nothing queued, nothing arriving: accounted
                # idle before the next arrival: sleep the gap (real
                # servers block on the queue here)
                time.sleep(max(0.0,
                               arrivals[nxt] - (time.perf_counter() - t0)))
                continue
            done_now = eng.step()
            served.extend(done_now)
            # slots live DURING this chunk: survivors + completions
            mif = max(mif, eng.num_active + len(done_now))
        return served, time.perf_counter() - t0, mif

    if args.serve_procs:
        if args.zipf is not None and args.paged:
            _run_fleetcache(args, cfg, params, max_len, paged_kwargs,
                            mk_engine, make_request, arrivals, pmax)
        else:
            _run_multiproc(args, cfg, max_len, paged_kwargs, mk_engine,
                           warm, drive, make_request, arrivals, pmax)
        return

    engine = mk_engine(robust=True)
    warm(engine)

    if args.chaos:
        faults.configure(args.faults, seed=args.faults_seed)
    with profile_trace(args.xprof_dir):
        done, wall, max_in_flight = drive(engine)
    counters = engine.robustness_counters()  # before the injector disarms
    if args.chaos:
        faults.configure("")

    ok = [c for c in done if c.ok]
    latencies = sorted(c.latency for c in ok) or [0.0]
    # p50/p95 through the shared registry histogram — the same quantile
    # code path cluster.stats() and traceview --summarize use
    p50, p95 = latency_percentiles(latencies)
    gen_tokens = int(sum(len(c.tokens) for c in ok))
    from progen_tpu.train.memory import serving_plan

    plan = serving_plan(cfg, num_slots=args.slots, max_len=max_len,
                        paged=args.paged, page_size=args.page_size,
                        num_pages=num_pages,
                        lora_tenants=(args.lora_tenants if lora_kwargs
                                      else 0),
                        lora_rank=args.lora_rank,
                        gate_dtype=("int8"
                                    if args.quantize == "weights+pages"
                                    else "bf16"))
    record = stamp_record({
        "metric": "serving_chaos" if args.chaos else "serving",
        "config": args.config,
        "requests": args.requests,
        "rate_per_sec": args.rate,
        "slots": args.slots,
        "chunk": args.chunk,
        "max_new_tokens": args.max_new,
        "max_len": max_len,
        "paged": args.paged,
        "max_in_flight": max_in_flight,
        # the budgeted resource: gate-row HBM (pool for paged, slots x
        # max_len slabs for fixed) — rings/carries are per-slot in BOTH
        # modes and excluded from the equal-budget comparison
        "gate_hbm_bytes": plan.pageable_bytes,
        "wall_s": round(wall, 3),
        "generated_tokens": gen_tokens,
        "tokens_per_sec": round(gen_tokens / wall, 1),
        "p50_latency_s": round(p50, 3),
        "p95_latency_s": round(p95, 3),
        "chunks_run": engine.chunks_run,
        "platform": jax.devices()[0].platform,
    })
    if args.long_frac > 0:
        record["long_frac"] = args.long_frac
    if mix:
        # per-workload latency through the SAME shared percentile helper
        # (and registry histograms bench.<workload>_latency_s)
        by_workload = {}
        for w in sorted(w for w in mix if mix[w] > 0):
            wc = [c for c in ok if workloads[c.uid] == w]
            lat_w = sorted(c.latency for c in wc) or [0.0]
            w50, w95 = latency_percentiles(
                lat_w, name=f"bench.{w}_latency_s")
            by_workload[w] = {
                "requests": len(wc),
                "generated_tokens": int(sum(len(c.tokens) for c in wc)),
                "p50_latency_s": round(w50, 3),
                "p95_latency_s": round(w95, 3),
            }
        record["metric"] = "serving_mix"
        record["scenario_mix"] = {k: round(v, 3) for k, v in mix.items()}
        record["workloads"] = by_workload
        record["lmask_hbm_bytes"] = (plan.lmask_bytes_per_slot
                                     * args.slots)
        if lora_kwargs:
            record["lora_tenants"] = args.lora_tenants
            record["lora_rank"] = args.lora_rank
            record["adapter_hbm_bytes"] = plan.adapter_bytes
    if args.disagg:
        # replay the IDENTICAL specs + arrival schedule inline so the
        # record carries the interference comparison disaggregation
        # exists for (fault-free: the injector is already disarmed)
        inline_eng = mk_engine(robust=True, use_disagg=False)
        warm(inline_eng)
        inline_done, inline_wall, _ = drive(inline_eng)
        inline_ok = [c for c in inline_done if c.ok]
        inline_lat = sorted(c.latency for c in inline_ok) or [0.0]
        inline_tok = int(sum(len(c.tokens) for c in inline_ok))
        i50, i95 = latency_percentiles(inline_lat,
                                       name="bench.inline_latency_s")
        record.update({
            "disagg": True,
            "prefill_batch": engine.prefill_batch,
            "handoff_depth": args.handoff_depth,
            "handoff": engine._handoff.stats(),
            "tokens_per_sec_inline": round(inline_tok / inline_wall, 1),
            "p50_latency_s_inline": round(i50, 3),
            "p95_latency_s_inline": round(i95, 3),
        })
    if args.paged:
        record.update({
            "page_size": args.page_size,
            "num_pages": engine._pool.num_pages,
            "prefix_cache": not args.no_prefix_cache,
            "prefix_hits": engine.prefix_hits,
            "evictions": engine.evictions,
            "pause_events": engine.pause_events,
        })
    if args.chaos:
        # one SLO code path: the same bucket math the live fleet's
        # /statusz burn rates run (observe/slo.py)
        frac = (_slo.frac_within_values((c.latency for c in ok), args.slo)
                if ok else 0.0)
        burn = _slo.burn_rate(frac, args.slo_target)
        record.update({
            "faults_plan": args.faults,
            "faults_seed": args.faults_seed,
            "slo_s": args.slo,
            "slo_target": args.slo_target,
            "ok_requests": len(ok),
            "goodput_tokens_per_sec": record.pop("tokens_per_sec"),
            "within_slo_frac": round(frac, 3),
            "slo_burn_rate": round(burn, 4),
            "robustness": counters,
        })

    extra_records: list = []
    if args.quantize:
        from progen_tpu.decode.paging import RESERVED_PAGES

        qtag = "w8" if args.quantize == "weights" else "w8p8"
        record["metric"] = f"serving_quant_{qtag}"
        record["quantize"] = args.quantize
        record["schedule_hash"] = sched_hash
        record["quant_decode_tok_s"] = record["tokens_per_sec"]
        record["weight_hbm_bytes_full"] = plan.weight_bytes_full
        record["weight_hbm_bytes_int8"] = plan.weight_bytes_int8
        ppr = -(-max_len // args.page_size)
        if args.paged:
            record["gate_dtype"] = engine.gate_dtype
            # concurrent max_len requests the pool can hold at this byte
            # budget — the equal-HBM capacity int8 pages are bought for
            record["equal_hbm_inflight"] = (
                (engine._pool.num_pages - RESERVED_PAGES) // ppr)
        # full-precision reference driven on the IDENTICAL schedule (and
        # when budgeted, the SAME byte budget -> fewer bf16 pages)
        fp_eng = mk_engine(robust=True, use_quant=False)
        warm(fp_eng)
        fp_done, fp_wall, fp_mif = drive(fp_eng)
        fp_ok = [c for c in fp_done if c.ok]
        fp_tok = int(sum(len(c.tokens) for c in fp_ok))
        fp_lat = sorted(c.latency for c in fp_ok) or [0.0]
        f50, f95 = latency_percentiles(fp_lat, name="bench.fp_latency_s")
        fp_plan = serving_plan(cfg, num_slots=args.slots, max_len=max_len,
                               paged=args.paged, page_size=args.page_size,
                               num_pages=num_pages_fp)
        fp_record = stamp_record({
            "metric": f"serving_quant_{qtag}_full",
            "config": args.config,
            "requests": args.requests,
            "schedule_hash": sched_hash,
            "slots": args.slots,
            "chunk": args.chunk,
            "max_new_tokens": args.max_new,
            "max_len": max_len,
            "paged": args.paged,
            "max_in_flight": fp_mif,
            "gate_hbm_bytes": fp_plan.pageable_bytes,
            "wall_s": round(fp_wall, 3),
            "generated_tokens": fp_tok,
            "tokens_per_sec": round(fp_tok / fp_wall, 1),
            "p50_latency_s": round(f50, 3),
            "p95_latency_s": round(f95, 3),
            "platform": jax.devices()[0].platform,
        })
        if args.paged:
            fp_record["gate_dtype"] = fp_eng.gate_dtype
            fp_record["num_pages"] = fp_eng._pool.num_pages
            fp_record["equal_hbm_inflight"] = (
                (fp_eng._pool.num_pages - RESERVED_PAGES) // ppr)
        extra_records.append(fp_record)

    if args.verify:
        if mix:
            _verify_mix(mk_engine, make_request, done, workloads,
                        scaffolds, args)
        else:
            _verify(mk_engine, make_request, done, args)
        if args.quantize:
            record.update(_verify_quant(mk_engine, specs, args, cfg,
                                        params, policy))
        record["verified"] = True

    if args.trace:
        get_tracer().dump(trace_dump_path(args.trace_out, "driver"))
        merged = merge_trace_dir(args.trace_out)
        if merged:
            record["trace"] = merged

    for rec in [record, *extra_records]:
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


def _load_qos_trace(path: str):
    """Parse a recorded QoS trace: one header line (``kind: qos_trace``)
    followed by one arrival per line, sorted here by ``(at, uid)`` so
    on-disk ordering is cosmetic.  Primes are NOT stored — each entry
    carries ``(prime_seed, prime_len)`` and the replayer regenerates the
    tokens, so the trace is vocabulary-agnostic and tiny."""
    header = None
    entries = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if d.get("kind") == "qos_trace":
                if header is not None:
                    raise SystemExit(f"{path}:{i + 1}: duplicate header")
                header = d
                continue
            entries.append(d)
    if header is None or not entries:
        raise SystemExit(f"{path}: need a qos_trace header line and at "
                         f"least one arrival")
    entries.sort(key=lambda e: (float(e["at"]), int(e["uid"])))
    return header, entries


def _jain_fairness(shares: list) -> float:
    """Jain's index over per-tenant weight-normalized service: 1.0 is
    perfectly weighted-fair, 1/n is one tenant taking everything."""
    if not shares:
        return 1.0
    s, s2 = sum(shares), sum(x * x for x in shares)
    if s2 <= 0.0:
        return 0.0
    return (s * s) / (len(shares) * s2)


def _run_trace(args, cfg, params, policy) -> None:
    """Replay a recorded heavy-traffic trace on VIRTUAL time and emit the
    ``serving_qos`` record (module docstring has the contract)."""
    from progen_tpu.decode import Request, ServingEngine

    header, entries = _load_qos_trace(args.trace_file)
    step_dt = float(header.get("step_dt", 1.0))
    weights = {int(k): float(v)
               for k, v in (header.get("weights") or {}).items()}
    default_max_new = int(header.get("max_new", args.max_new))

    primes = {int(e["uid"]): np.random.default_rng(
        int(e["prime_seed"])).integers(
        1, cfg.num_tokens, int(e["prime_len"])).tolist() for e in entries}
    at = {int(e["uid"]): float(e["at"]) for e in entries}
    pri = {int(e["uid"]): int(e.get("priority", 0)) for e in entries}
    ten = {int(e["uid"]): int(e.get("tenant", 0)) for e in entries}
    pmax = max(len(p) for p in primes.values())
    mx = max(int(e.get("max_new", default_max_new)) for e in entries)
    max_len = args.max_len or min(cfg.seq_len, pmax + mx + 1)

    lora_kwargs: dict = {}
    tenants = max(ten.values()) + 1
    if tenants > 1:
        from progen_tpu.workloads.lora import random_lora_bank

        lora_kwargs = dict(lora_bank=random_lora_bank(
            cfg, tenants, args.lora_rank, seed=args.seed + 7))
    paged_kwargs = dict(
        paged=True, page_size=args.page_size, num_pages=args.num_pages,
        paged_impl=args.paged_impl, prefix_cache=not args.no_prefix_cache,
    ) if args.paged else {}

    def mk(*, contended: bool = True, fifo: bool = False,
           slots: int | None = None) -> ServingEngine:
        kw = dict(paged_kwargs)
        kw.update(lora_kwargs)
        if contended:
            mq = header.get("max_queue")
            kw.update(max_queue=int(mq) if mq is not None else None,
                      shed_policy=header.get("shed_policy", "shed-oldest"))
        if not fifo:
            kw.update(qos_weights=weights or None)
        return ServingEngine(cfg, params, policy=policy,
                             num_slots=slots or args.slots,
                             chunk_size=args.chunk, max_len=max_len, **kw)

    def make_req(e: dict, *, fifo: bool = False) -> Request:
        uid = int(e["uid"])
        ttl = e.get("ttl")
        return Request(
            uid=uid, tokens=primes[uid],
            max_new_tokens=int(e.get("max_new", default_max_new)),
            top_k=25, temperature=1.0,
            seed=int(e.get("seed", args.seed + uid)),
            # virtual clock: ttl'd arrivals are measured against the
            # wall clock inside the engine, so a trace ttl of 0.0 on a
            # small virtual submit_time is ALREADY expired -> the shed
            # is deterministic, never a timing race
            submit_time=float(e["at"]),
            ttl=float(ttl) if ttl is not None else None,
            tenant=ten[uid], priority=0 if fifo else pri[uid])

    def warm(eng: ServingEngine) -> None:
        wrng = np.random.default_rng(args.seed + 999)
        for i in range(min(2, args.slots)):
            eng.submit(Request(
                uid=10_000_000 + i,
                tokens=wrng.integers(1, cfg.num_tokens, pmax).tolist(),
                max_new_tokens=mx, top_k=25, temperature=1.0,
                seed=args.seed, submit_time=time.perf_counter()))
        eng.run_until_idle()
        eng.completions.clear()

    def vdrive(eng: ServingEngine, *, fifo: bool = False):
        """Virtual-time replay: submit every arrival with ``at <= vnow``
        before each step, advance ``vnow`` by ``step_dt`` per step, and
        measure latency in virtual seconds — the whole schedule is then
        a pure function of the trace + engine config."""
        vnow = 0.0
        nxt = 0
        vlat: dict = {}
        done: list = []
        while True:
            while nxt < len(entries) and float(
                    entries[nxt]["at"]) <= vnow + 1e-9:
                eng.submit(make_req(entries[nxt], fifo=fifo))
                nxt += 1
            if not eng.has_work:
                if nxt >= len(entries):
                    break
                vnow = float(entries[nxt]["at"])  # idle gap: jump ahead
                continue
            comps = eng.step()
            vnow += step_dt
            for c in comps:
                vlat[c.uid] = vnow - at[c.uid]
                done.append(c)
        return done, vlat

    # --- measured QoS run (priorities + weights live)
    qos_eng = mk()
    warm(qos_eng)
    t0 = time.perf_counter()
    done, vlat = vdrive(qos_eng)
    wall = time.perf_counter() - t0
    counters = qos_eng.robustness_counters()

    # --- FIFO comparison: SAME trace, priorities zeroed, no weights —
    # the margin the record (and the benchdiff gate) carries
    fifo_eng = mk(fifo=True)
    warm(fifo_eng)
    fifo_done, fifo_vlat = vdrive(fifo_eng, fifo=True)

    ok = [c for c in done if c.ok]
    fifo_ok = [c for c in fifo_done if c.ok]
    gen_tokens = int(sum(len(c.tokens) for c in ok))

    hi_cls = max(pri.values())
    hi_lat = sorted(vlat[c.uid] for c in ok if pri[c.uid] == hi_cls)
    fifo_hi_lat = sorted(fifo_vlat[c.uid] for c in fifo_ok
                         if pri[c.uid] == hi_cls)
    _, hi_p95 = latency_percentiles(hi_lat or [0.0],
                                    name="bench.qos_hi_latency_v")
    _, fifo_hi_p95 = latency_percentiles(fifo_hi_lat or [0.0],
                                         name="bench.fifo_hi_latency_v")

    by_class: dict = {}
    for cls in sorted(set(pri.values())):
        lat = sorted(vlat[c.uid] for c in ok if pri[c.uid] == cls)
        p50, p95 = latency_percentiles(lat or [0.0])
        by_class[str(cls)] = {
            "requests": sum(1 for p in pri.values() if p == cls),
            "ok": len(lat),
            "p50_latency_v": round(p50, 3),
            "p95_latency_v": round(p95, 3),
        }
    by_tenant: dict = {}
    shares = []
    for t in sorted(set(ten.values())):
        tc = [c for c in ok if ten[c.uid] == t]
        lat = sorted(vlat[c.uid] for c in tc)
        p50, p95 = latency_percentiles(lat or [0.0])
        service = int(sum(len(c.tokens) for c in tc))
        w = weights.get(t, 0.0)
        by_tenant[str(t)] = {
            "requests": sum(1 for x in ten.values() if x == t),
            "ok": len(tc),
            "generated_tokens": service,
            "weight": w,
            "p50_latency_v": round(p50, 3),
            "p95_latency_v": round(p95, 3),
        }
        if w > 0.0:
            shares.append(service / w)
    fairness = _jain_fairness(shares)

    record = stamp_record({
        "metric": "serving_qos",
        "config": args.config,
        "trace": header.get("name",
                            os.path.basename(args.trace_file)),
        "requests": len(entries),
        "slots": args.slots,
        "chunk": args.chunk,
        "max_len": max_len,
        "step_dt": step_dt,
        "paged": args.paged,
        "weights": {str(k): v for k, v in sorted(weights.items())},
        "wall_s": round(wall, 3),
        "ok_requests": len(ok),
        "generated_tokens": gen_tokens,
        "preemptions": int(counters.get("preemptions", 0)),
        "fifo_preemptions": int(
            fifo_eng.robustness_counters().get("preemptions", 0)),
        "sheds": {
            "queue_full": int(counters.get("sheds_queue_full", 0)),
            "deadline": int(counters.get("sheds_deadline", 0)),
        },
        "by_class": by_class,
        "by_tenant": by_tenant,
        "qos_fairness_index": round(fairness, 4),
        "hi_class": hi_cls,
        "hi_p95_latency_v": round(hi_p95, 3),
        "hi_p95_latency_v_fifo": round(fifo_hi_p95, 3),
        "hi_p95_margin_v": round(fifo_hi_p95 - hi_p95, 3),
        "platform": jax.devices()[0].platform,
    })

    if args.verify:
        _verify_trace(mk, make_req, entries, pri, ten, weights,
                      done, fifo_done, hi_p95, fifo_hi_p95, hi_cls)
        record["verified"] = True

    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


def _verify_trace(mk, make_req, entries, pri, ten, weights,
                  done, fifo_done, hi_p95, fifo_hi_p95, hi_cls) -> None:
    """The QoS acceptance asserts: (1) every non-shed completion of BOTH
    contended runs is token-identical to an uncontended rerun (one slot
    per request — no queue, no preemption, no shed), (2) the high class's
    p95 beat the FIFO rerun's, (3) no tenant with a nonzero weight that
    submitted work starved."""
    un_eng = mk(contended=False, slots=len(entries))
    for e in entries:
        if e.get("ttl") is not None:
            continue  # ttl'd arrivals shed everywhere; nothing to pin
        un_eng.submit(make_req(e))
    clean = {c.uid: c.tokens.tolist() for c in un_eng.run_until_idle()
             if c.ok}

    for tag, comps in (("qos", done), ("fifo", fifo_done)):
        mismatched = [c.uid for c in comps
                      if c.ok and c.tokens.tolist() != clean.get(c.uid)]
        assert not mismatched, (
            f"{tag} trace replay diverged from the uncontended rerun "
            f"for uids {mismatched} — preemption broke bit-exactness")

    assert hi_p95 < fifo_hi_p95, (
        f"priority scheduling did not beat FIFO for class {hi_cls}: "
        f"p95 {hi_p95:.3f} vs FIFO {fifo_hi_p95:.3f} (virtual s)")

    ok_uids = {c.uid for c in done if c.ok}
    starved = [t for t, w in sorted(weights.items())
               if w > 0.0
               and any(ten[u] == t for u in ten)
               and not any(ten[u] == t for u in ok_uids)]
    assert not starved, (
        f"nonzero-weight tenants starved under overload: {starved}")
    print("verify: trace-replay token identity, high-class p95 margin "
          "and starvation-freedom OK", file=sys.stderr)


_PROM_LINE = None  # compiled lazily in _assert_prometheus


def _assert_prometheus(text: str) -> int:
    """Strict line-format check of a /metricsz body: every line is a
    ``# TYPE``/comment line or ``name{labels} value``.  Returns the
    sample count (must be > 0)."""
    import re

    global _PROM_LINE
    if _PROM_LINE is None:
        _PROM_LINE = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
            r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
            r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')
    samples = 0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        samples += 1
    assert samples > 0, "empty /metricsz exposition"
    return samples


def _check_statusz(cluster) -> dict:
    """Fetch /healthz + /metricsz from the DRIVER and EVERY worker while
    the cluster is live; assert 200 and parseable bodies.  This is the
    in-process half of the check.sh statusz smoke."""
    import urllib.request

    ports = cluster.stats().get("statusz_ports", {})
    assert "driver" in ports, f"no driver statusz port in {ports}"
    want = 1 + cluster.prefill_procs + cluster.replicas
    assert len(ports) == want, f"expected {want} statusz ports, got {ports}"
    out = {}
    for who, port in sorted(ports.items()):
        for ep in ("/healthz", "/metricsz"):
            body = status = None
            for attempt in range(5):  # a racy host-dict read 503s; retry
                try:
                    resp = urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{ep}", timeout=10)
                    status = resp.status
                    body = resp.read().decode()
                    if status == 200:
                        break
                except OSError:
                    pass
                time.sleep(0.2)
            assert status == 200, f"{who}{ep} -> {status}"
            if ep == "/healthz":
                health = json.loads(body)
                assert health.get("status") == "ok", f"{who}: {health}"
            else:
                out[who] = _assert_prometheus(body)
        print(f"statusz[{who}] OK on :{port} "
              f"({out[who]} samples)", file=sys.stderr)
    return out


def _run_multiproc(args, cfg, max_len, paged_kwargs, mk_engine, warm,
                   drive, make_request, arrivals, pmax) -> None:
    """--serve-procs: measure the real multi-process cluster on the same
    arrival schedule, then rerun it in-process (inline AND single-process
    disagg) so one record carries the whole comparison.  The per-stage
    timing fields prove the prefill wall left the decode process
    (``decode:*`` replicas report ``prefill_s == 0``)."""
    if args.chaos:
        raise SystemExit("--chaos drives the in-process fault injector; "
                         "multi-process fault coverage lives in "
                         "tests/test_serve_multiproc.py")
    from progen_tpu.decode import Request
    from progen_tpu.serve.cluster import ServeCluster
    from progen_tpu.serve.worker import make_spec

    engine_kw = dict(num_slots=args.slots, chunk_size=args.chunk,
                     max_len=max_len,
                     prefill_batch=args.prefill_batch,
                     handoff_depth=args.handoff_depth, **paged_kwargs)
    # init_seed=0 + mixed_precision=True is EXACTLY this script's param
    # recipe, so the workers' params are bit-identical to the in-process
    # comparison engines' — token identity is assertable
    wspec = make_spec(cfg, mixed_precision=True, init_seed=0,
                      engine=engine_kw,
                      statusz=args.statusz,
                      trace=({"dir": os.path.abspath(args.trace_out)}
                             if args.trace else None))

    def drive_cluster():
        cluster = ServeCluster(wspec, prefill_procs=args.prefill_procs,
                               replicas=args.replicas)
        control = None
        if args.autoscale or args.swap_at is not None:
            from progen_tpu.serve import BurnRatePolicy, ControlPlane

            control = ControlPlane(cluster, BurnRatePolicy(
                min_prefill=args.min_prefill or args.prefill_procs,
                max_prefill=args.max_prefill or args.prefill_procs + 2,
                min_replicas=args.min_replicas or args.replicas,
                max_replicas=args.max_replicas or args.replicas + 2,
                cooldown_s=2.0))
        try:
            # warm the fleet off the clock: sacrificial requests compile
            # prefill + merge + chunk programs in the workers
            wrng = np.random.default_rng(args.seed + 999)
            for i in range(max(2, args.prefill_procs, args.replicas)):
                cluster.submit(Request(
                    uid=10_000_000 + i,
                    tokens=wrng.integers(1, cfg.num_tokens, pmax).tolist(),
                    max_new_tokens=args.max_new, top_k=25, temperature=1.0,
                    seed=args.seed, submit_time=time.perf_counter()))
            cluster.drain(timeout=600.0)
            cluster.poll(0.0)  # discard the warm completions
            if args.statusz:
                # live-endpoint smoke while every process is up and warm:
                # the measured drive below then proves zero perturbation
                _check_statusz(cluster)

            t0 = time.perf_counter()
            served: list = []
            nxt = 0
            # fleet size over time: [t_rel_s, prefill_workers, replicas]
            # — flat without --autoscale, the scaling story with it
            timeline = [[0.0, cluster.prefill_procs, cluster.replicas]]
            last_sample = 0.0
            last_tick = -1e9
            swapped_gen = None
            while len(served) < args.requests:
                now = time.perf_counter() - t0
                while nxt < args.requests and arrivals[nxt] <= now:
                    cluster.submit(make_request(nxt, t0 + arrivals[nxt],
                                                ttl=args.ttl))
                    nxt += 1
                served.extend(cluster.poll(0.02))
                if (control is not None and args.swap_at is not None
                        and swapped_gen is None
                        and len(served) >= args.swap_at):
                    swapped_gen = control.swap_weights()
                now = time.perf_counter() - t0
                if (control is not None and args.autoscale
                        and now - last_tick >= 0.25):
                    last_tick = now
                    control.tick()
                    now = time.perf_counter() - t0
                if (now - last_sample >= 0.25
                        or timeline[-1][1:] != [cluster.prefill_procs,
                                                cluster.replicas]):
                    last_sample = now
                    timeline.append([round(now, 3),
                                     cluster.prefill_procs,
                                     cluster.replicas])
            wall = time.perf_counter() - t0
            timeline.append([round(wall, 3), cluster.prefill_procs,
                             cluster.replicas])
            extras = {"fleet_size_timeline": timeline}
            if control is not None:
                events = [e["event"] for e in control.journal]
                extras["control"] = {
                    "scale_ups": events.count("scale_up"),
                    "scale_downs": events.count("scale_down"),
                    "swaps": control.swaps,
                    "generation": cluster.generation,
                    "journal": control.journal[-64:],
                }
            if swapped_gen is not None:
                gens = {c.uid: c.generation for c in served}
                extras["swap"] = {
                    "at_completions": args.swap_at,
                    "generation": swapped_gen,
                    "served_old_gen": sum(
                        1 for g in gens.values() if g < swapped_gen),
                    "served_new_gen": sum(
                        1 for g in gens.values() if g >= swapped_gen),
                    "dropped": args.requests - len(gens),
                }
        finally:
            stats = cluster.shutdown()
        return served, wall, stats, extras

    with profile_trace(args.xprof_dir):
        done, wall, stats, extras = drive_cluster()
    ok = [c for c in done if c.ok]
    lat = sorted(c.latency for c in ok) or [0.0]
    c50, c95 = latency_percentiles(lat, name="bench.cluster_latency_s")
    gen = int(sum(len(c.tokens) for c in ok))

    def rerun(use_disagg: bool):
        eng = mk_engine(robust=True, use_disagg=use_disagg)
        warm(eng)
        r_done, r_wall, _ = drive(eng)
        r_ok = [c for c in r_done if c.ok]
        r_lat = sorted(c.latency for c in r_ok) or [0.0]
        r_tok = int(sum(len(c.tokens) for c in r_ok))
        r50, r95 = latency_percentiles(r_lat, name="bench.rerun_latency_s")
        return {
            "tokens_per_sec": round(r_tok / r_wall, 1),
            "p50_latency_s": round(r50, 3),
            "p95_latency_s": round(r95, 3),
        }

    sp_disagg = rerun(use_disagg=True)   # single-process disagg
    inline = rerun(use_disagg=False)

    record = stamp_record({
        "metric": "serving_multiproc",
        "config": args.config,
        "requests": args.requests,
        "rate_per_sec": args.rate,
        "slots": args.slots,
        "chunk": args.chunk,
        "max_new_tokens": args.max_new,
        "max_len": max_len,
        "paged": args.paged,
        "prefill_procs": args.prefill_procs,
        "replicas": args.replicas,
        "prefill_batch": engine_kw["prefill_batch"],
        "handoff_depth": args.handoff_depth,
        "wall_s": round(wall, 3),
        "generated_tokens": gen,
        "ok_requests": len(ok),
        "tokens_per_sec": round(gen / wall, 1),
        "p50_latency_s": round(c50, 3),
        "p95_latency_s": round(c95, 3),
        "slo_s": args.slo,
        "within_slo_frac": round(
            _slo.frac_within_values((c.latency for c in ok), args.slo)
            if ok else 0.0, 3),
        # per-stage wall time per worker: decode replicas must report
        # prefill_s == 0.0 — the prefill wall left the process entirely
        "stage_seconds": {w: st.get("stage_seconds")
                          for w, st in stats["workers"].items()},
        # frames / bytes / serialize+deserialize seconds, summed over
        # the router and every worker
        "transport": stats["transport_total"],
        # per-replica load counters (prefill_load / outstanding_tokens
        # per instance, maxima over the run)
        "router": stats["router"],
        "supervision": stats["supervision"],
        "sp_disagg": sp_disagg,
        "inline": inline,
        "platform": jax.devices()[0].platform,
        "autoscale": args.autoscale,
        **extras,
    })

    if args.verify:
        # token identity: every cluster completion must match the plain
        # single-process engine on the same (tokens, seed) set
        plain = mk_engine(robust=False, use_disagg=False)
        for uid in range(args.requests):
            plain.submit(make_request(uid, time.perf_counter()))
        clean = {c.uid: c.tokens.tolist() for c in plain.run_until_idle()}
        mismatched = [c.uid for c in ok
                      if [int(t) for t in c.tokens] != clean[c.uid]]
        assert not mismatched, (
            f"multi-process serving diverged from the single-process "
            f"engine for uids {mismatched}")
        # replay parity: a SECOND fresh cluster (new processes, new
        # placement — and its own scaling/swap timing) must serve
        # bit-identical tokens
        done2, _, _, _ = drive_cluster()
        first = {c.uid: [int(t) for t in c.tokens] for c in done if c.ok}
        second = {c.uid: [int(t) for t in c.tokens] for c in done2 if c.ok}
        assert first == second, "cluster replay diverged between runs"
        record["verified"] = True
        print("verify: multiproc token-identity and cluster replay "
              "parity OK", file=sys.stderr)

    if args.trace:
        # every process dumped its span ring (workers at exit, the driver
        # in cluster.shutdown with its clock-offset meta) — merge them
        # into one Perfetto-loadable timeline
        merged = merge_trace_dir(args.trace_out)
        if merged:
            record["trace"] = merged

    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


def _run_fleetcache(args, cfg, params, max_len, paged_kwargs,
                    mk_engine, make_request, arrivals, pmax) -> None:
    """--zipf + --serve-procs + --paged: measure the SAME Zipf popular-
    prompt schedule on two fresh clusters — cache-aware routing (each
    request goes to the replica whose advertised prefix digest covers
    the longest prime prefix) vs cache-blind (load-only) — and emit one
    ``serving_fleetcache`` record carrying the side-by-side
    (docs/SERVING.md §11).

    TTFT is driver-observed: handle arrival minus submit, both on the
    driver clock, so the two runs are compared on one clock with no
    cross-process correction.  ``prefill_flops_saved`` is MODELED from
    page-level hits (``hits x page_size rows x 2 x n_params``): a
    prefix hit dedups pool pages (pressure relief — fewer deferrals,
    evictions and admission pauses under a tight ``--num-pages``), it
    does not skip the batched prefill math.
    """
    if args.chaos:
        raise SystemExit("--chaos drives the in-process fault injector; "
                         "drop it for the --zipf fleetcache comparison")
    from progen_tpu.decode import Request
    from progen_tpu.serve.cluster import ServeCluster
    from progen_tpu.serve.worker import make_spec

    engine_kw = dict(num_slots=args.slots, chunk_size=args.chunk,
                     max_len=max_len,
                     prefill_batch=args.prefill_batch,
                     handoff_depth=args.handoff_depth, **paged_kwargs)
    wspec = make_spec(cfg, mixed_precision=True, init_seed=0,
                      engine=engine_kw, statusz=args.statusz)

    def drive_cluster(route_by_cache: bool):
        cluster = ServeCluster(wspec, prefill_procs=args.prefill_procs,
                               replicas=args.replicas,
                               route_by_cache=route_by_cache)
        try:
            # warm off the clock: sacrificial requests compile prefill +
            # merge + chunk programs in every worker (distinct primes —
            # their cached pages are cold and evict first under load)
            wrng = np.random.default_rng(args.seed + 999)
            for i in range(max(2, args.prefill_procs, args.replicas)):
                cluster.submit(Request(
                    uid=10_000_000 + i,
                    tokens=wrng.integers(1, cfg.num_tokens, pmax).tolist(),
                    max_new_tokens=args.max_new, top_k=25,
                    temperature=1.0, seed=args.seed,
                    submit_time=time.perf_counter()))
            cluster.drain(timeout=600.0)
            cluster.poll(0.0)  # discard the warm completions

            t0 = time.perf_counter()
            served: list = []
            nxt = 0
            while len(served) < args.requests:
                now = time.perf_counter() - t0
                while nxt < args.requests and arrivals[nxt] <= now:
                    cluster.submit(make_request(nxt, t0 + arrivals[nxt],
                                                ttl=args.ttl))
                    nxt += 1
                served.extend(cluster.poll(0.02))
            wall = time.perf_counter() - t0
        finally:
            stats = cluster.shutdown()
        return served, wall, stats

    def summarize(done, wall, stats):
        ok = [c for c in done if c.ok]
        lat = sorted(c.latency for c in ok) or [0.0]
        p50, p95 = latency_percentiles(lat, name="bench.cluster_latency_s")
        ttfts = sorted(c.ttft for c in ok if c.ttft is not None) or [0.0]
        t50, t95 = latency_percentiles(ttfts, name="bench.cluster_ttft_s")
        gen = int(sum(len(c.tokens) for c in ok))
        hits = lookups = 0
        for w, st in stats["workers"].items():
            if not w.startswith("decode:"):
                continue
            rb = st.get("robust") or {}
            if os.environ.get("FLEETCACHE_DEBUG"):
                print(f"debug {w}: hits={rb.get('prefix_hits')} "
                      f"lookups={rb.get('prefix_lookups')} "
                      f"evictions={rb.get('evictions')}", file=sys.stderr)
            hits += int(rb.get("prefix_hits", 0))
            lookups += int(rb.get("prefix_lookups", 0))
        rt = stats.get("router", {})
        return {
            "ok_requests": len(ok),
            "generated_tokens": gen,
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(gen / wall, 1) if wall else 0.0,
            "p50_latency_s": round(p50, 3),
            "p95_latency_s": round(p95, 3),
            "ttft_p50": round(t50, 4),
            "ttft_p95": round(t95, 4),
            "fleet_prefix_hits": hits,
            "fleet_prefix_lookups": lookups,
            "fleet_prefix_hit_rate": (round(hits / lookups, 4)
                                      if lookups else 0.0),
            "cache_routed": int(rt.get("cache_routed", 0)),
            "cache_fallback": int(rt.get("cache_fallback", 0)),
        }, ok

    with profile_trace(args.xprof_dir):
        aware_sum, aware_ok = summarize(*drive_cluster(True))
    blind_sum, blind_ok = summarize(*drive_cluster(False))

    n_params = int(sum(x.size for x in jax.tree_util.tree_leaves(params)))
    page_size = int(paged_kwargs.get("page_size") or 16)
    rows = aware_sum["fleet_prefix_hits"] * page_size
    record = stamp_record({
        "metric": "serving_fleetcache",
        "config": args.config,
        "requests": args.requests,
        "rate_per_sec": args.rate,
        "zipf_alpha": args.zipf,
        "zipf_pool": args.zipf_pool,
        "slots": args.slots,
        "chunk": args.chunk,
        "max_new_tokens": args.max_new,
        "max_len": max_len,
        "page_size": page_size,
        "num_pages": paged_kwargs.get("num_pages"),
        "prefill_procs": args.prefill_procs,
        "replicas": args.replicas,
        **aware_sum,
        # modeled dedup value: gate rows NOT freshly written because a
        # cached page covered them (2 flops/row/param convention)
        "prefill_rows_deduped": rows,
        "prefill_flops_saved": rows * 2 * n_params,
        "cache_blind": blind_sum,
        "ttft_p95_blind": blind_sum["ttft_p95"],
        "ttft_p95_speedup": (round(
            blind_sum["ttft_p95"] / aware_sum["ttft_p95"], 3)
            if aware_sum["ttft_p95"] > 0 else 0.0),
        "platform": jax.devices()[0].platform,
    })

    if args.verify:
        # placement is a performance hint, never a correctness input:
        # both clusters must be token-identical to the plain
        # single-process engine on the same (tokens, seed) set
        plain = mk_engine(robust=False, use_disagg=False)
        for uid in range(args.requests):
            plain.submit(make_request(uid, time.perf_counter()))
        clean = {c.uid: [int(t) for t in c.tokens]
                 for c in plain.run_until_idle()}
        for tag, comps in (("cache-aware", aware_ok),
                           ("cache-blind", blind_ok)):
            mism = [c.uid for c in comps
                    if [int(t) for t in c.tokens] != clean[c.uid]]
            assert not mism, (
                f"{tag} cluster diverged from the single-process engine "
                f"for uids {mism} — placement changed tokens")
        aw = {c.uid: [int(t) for t in c.tokens] for c in aware_ok}
        bl = {c.uid: [int(t) for t in c.tokens] for c in blind_ok}
        assert aw == bl, (
            "cache-aware and cache-blind completions differ — routing "
            "policy leaked into the token stream")
        record["verified"] = True
        print("verify: fleetcache token identity (cache-aware == "
              "cache-blind == single-process) OK", file=sys.stderr)

    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


def _parse_mix(s: str) -> dict[str, float]:
    """``'generate=0.5,infill=0.2,...'`` -> normalized weight dict."""
    from progen_tpu.workloads import WORKLOADS

    mix: dict[str, float] = {}
    for part in s.split(","):
        name, eq, w = part.partition("=")
        name = name.strip()
        if name not in WORKLOADS or not eq:
            raise SystemExit(
                f"bad --scenario-mix entry {part!r}; entries are "
                f"<workload>=<weight> with workload in {WORKLOADS}")
        mix[name] = float(w)
    if any(v < 0 for v in mix.values()) or sum(mix.values()) <= 0:
        raise SystemExit("--scenario-mix weights must be >= 0 and sum > 0")
    total = sum(mix.values())
    return {k: v / total for k, v in mix.items()}


def _verify_mix(mk_engine, make_request, done, workloads, scaffolds,
                args) -> None:
    """Scenario-mix correctness gate, asserted on the measured run:

    * rerun identity — a fresh engine serving the same request set
      reproduces every completion (tokens for generate/infill/lora,
      bit-equal vectors for embed);
    * constraint enforcement — every infill completion's generated tokens
      satisfy the scaffold's per-position allowed sets;
    * zero-adapter identity — the mix's tenant-0 requests (generate +
      infill + embed) are bit-identical on an engine built WITHOUT the
      adapter bank (serving LoRA tenants cannot perturb the base path);
    * snapshot replay — snapshot mid-run on a third engine, restore on a
      fresh one, and the merged completions match the rerun.
    """
    import time

    def submit_all(eng) -> None:
        for uid in range(args.requests):
            req = make_request(uid, time.perf_counter())
            if getattr(req, "workload", "generate") == "embed":
                eng.submit_embed(req)
            else:
                eng.submit(req)

    def payload(c):
        if c.embedding is not None:
            return ("embed", c.embedding.tobytes())
        return ("tokens", tuple(int(t) for t in c.tokens))

    clean_eng = mk_engine(robust=False)
    submit_all(clean_eng)
    clean = {c.uid: payload(c) for c in clean_eng.run_until_idle()}

    measured = {c.uid: payload(c) for c in done if c.ok}
    mismatched = [u for u, p in measured.items() if clean[u] != p]
    assert not mismatched, (
        f"scenario-mix rerun diverged for uids {mismatched}")

    for uid, spec in scaffolds.items():
        if uid not in measured or measured[uid][0] != "tokens":
            continue
        gen = measured[uid][1]
        mask = spec.logit_mask()
        bad = [g for g, t in enumerate(gen[:mask.shape[0]])
               if not mask[g, t]]
        assert not bad, (
            f"infill uid {uid} emitted masked tokens at positions {bad}")

    base_uids = [u for u in range(args.requests)
                 if workloads[u] != "lora"]
    if base_uids:
        plain = mk_engine(robust=False, use_lora=False)
        for uid in base_uids:
            req = make_request(uid, time.perf_counter())
            if getattr(req, "workload", "generate") == "embed":
                plain.submit_embed(req)
            else:
                plain.submit(req)
        base = {c.uid: payload(c) for c in plain.run_until_idle()}
        drifted = [u for u in base_uids
                   if u in measured and base[u] != measured[u]]
        assert not drifted, (
            f"tenant-0 requests diverged between the adapter-bank engine "
            f"and the bankless engine for uids {drifted}")

    snap_eng = mk_engine(robust=False)
    submit_all(snap_eng)
    for _ in range(2):
        snap_eng.step()
    snap = snap_eng.snapshot()
    pre = {c.uid: payload(c) for c in snap_eng.completions}
    replay_eng = mk_engine(robust=False)
    replay_eng.restore(snap)
    post = {c.uid: payload(c) for c in replay_eng.run_until_idle()}
    assert {**pre, **post} == clean, (
        "scenario-mix snapshot -> restore -> replay diverged")
    print("verify: scenario-mix rerun identity, constraint enforcement, "
          "tenant-0 identity and snapshot replay OK", file=sys.stderr)


def _verify_quant(mk_engine, specs, args, cfg, params, policy) -> dict:
    """The accuracy tier behind ``--quantize`` (docs/SERVING.md §12):
    greedy (temperature 0) decode of the fixed schedule on the quantized
    engine vs the full-precision engine, scored as the fraction of
    full-precision tokens the quantized stream reproduces before its
    first divergence (longest-common-prefix, summed over requests).
    Greedy decode is the right probe — it removes sampling noise, so
    every mismatch is a real argmax flip.  When a divergence exists the
    report includes the max logit rtol at the first diverging position
    (teacher-forced, both precisions on the identical prefix): the
    honest "how close was the call" number.  Fails the run below
    ``--match-gate``."""
    from progen_tpu.decode import Request as Rq
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import ProGen

    def greedy(eng):
        for uid, toks in enumerate(specs):
            eng.submit(Rq(uid=uid, tokens=list(toks),
                          max_new_tokens=args.max_new, top_k=None,
                          temperature=0.0, seed=args.seed + uid,
                          submit_time=time.perf_counter()))
        return {c.uid: c.tokens.tolist() for c in eng.run_until_idle()}

    full = greedy(mk_engine(robust=False, use_quant=False))
    quant = greedy(mk_engine(robust=False))
    matched = total = 0
    first_div = None
    for uid in sorted(full):
        f, q = full[uid], quant.get(uid, [])
        lcp = 0
        for a, b in zip(f, q):
            if a != b:
                break
            lcp += 1
        matched += lcp
        total += len(f)
        if first_div is None and lcp < min(len(f), len(q)):
            first_div = (uid, lcp)
    rate = matched / max(1, total)
    out = {"token_match_rate": round(rate, 4),
           "match_gate": args.match_gate,
           "greedy_tokens_compared": total}
    if first_div is not None:
        uid, lcp = first_div
        prefix = list(specs[uid]) + full[uid][:lcp]
        toks = jnp.zeros((1, cfg.seq_len), jnp.int32)
        toks = toks.at[0, :len(prefix)].set(jnp.asarray(prefix))
        fp_logits = ProGen(config=cfg, policy=policy).apply(
            params, toks)[0, len(prefix) - 1].astype(jnp.float32)
        qvars = ServingEngine._quantize_variables(params)
        q_logits = ProGen(config=cfg, policy=policy,
                          weights="int8").apply(
            qvars, toks)[0, len(prefix) - 1].astype(jnp.float32)
        rtol = jnp.max(jnp.abs(q_logits - fp_logits)
                       / (jnp.abs(fp_logits) + 1e-6))
        out["first_divergence_uid"] = uid
        out["max_logit_rtol_at_divergence"] = round(float(rtol), 5)
    if rate < args.match_gate:
        raise SystemExit(
            f"quant verify: token_match_rate {rate:.4f} < gate "
            f"{args.match_gate} — quantized serving rejected")
    print(f"verify: quant greedy token match {rate:.4f} over {total} "
          f"tokens (gate {args.match_gate}) OK", file=sys.stderr)
    return out


def _verify(mk_engine, make_request, done, args) -> None:
    """Fault-free rerun + snapshot/restore replay, both asserted
    token-identical to the measured run's non-shed completions.  With
    ``--disagg`` the fault-free rerun is ALSO compared against a plain
    inline engine, so both serving modes are pinned to one token
    stream."""
    import time

    clean_eng = mk_engine(robust=False)
    for uid in range(args.requests):
        clean_eng.submit(make_request(uid, time.perf_counter()))
    clean = {c.uid: c.tokens.tolist() for c in clean_eng.run_until_idle()}

    mismatched = [c.uid for c in done
                  if c.ok and c.tokens.tolist() != clean[c.uid]]
    assert not mismatched, (
        f"chaos run diverged from fault-free run for uids {mismatched}")

    if args.disagg:
        plain_eng = mk_engine(robust=False, use_disagg=False)
        for uid in range(args.requests):
            plain_eng.submit(make_request(uid, time.perf_counter()))
        plain = {c.uid: c.tokens.tolist()
                 for c in plain_eng.run_until_idle()}
        assert clean == plain, (
            "disagg serving diverged from the plain engine — "
            "bit-exactness contract broken")
    # snapshot mid-run, replay on a FRESH engine, assert token identity
    snap_eng = mk_engine(robust=False)
    for uid in range(args.requests):
        snap_eng.submit(make_request(uid, time.perf_counter()))
    for _ in range(2):
        snap_eng.step()
    snap = snap_eng.snapshot()
    pre = {c.uid: c.tokens.tolist() for c in snap_eng.completions}

    replay_eng = mk_engine(robust=False)
    replay_eng.restore(snap)
    post = {c.uid: c.tokens.tolist() for c in replay_eng.run_until_idle()}
    merged = {**pre, **post}
    assert merged == clean, (
        "snapshot -> restore -> replay diverged from the straight run")
    print("verify: chaos token-identity and snapshot replay parity OK",
          file=sys.stderr)


if __name__ == "__main__":
    main()
