"""Serving cells: the in-process ``ServingEngine`` under an open-loop
schedule (``arrivals.kind == "open"``) or a standing backlog
(``"backlog"``).

Every field of the workload's ``engine`` group is passed to the engine's
constructor, so ``paged``, ``quantize`` and the like are one new workload
file.  Set-up, all outside the window: weights made on the device from the
seed in one jitted call, the cell's one prefill bucket and the chunk
program compiled (``aot_warmup``), probe requests served and compared with
the reference, and for a backlog the queue filled and the ramp run.

No number here comes from the engine's ``stage_seconds``, its histograms
or ``Completion.first_token_time``: those time un-synchronised dispatches.
The clock is the benchmark's, read after ``step()`` has returned from the
harvest's host fetch.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
from functools import partial

import numpy as np

from perf.lib import loadgen, reference, stats, traffic
from perf.lib.harness import Phases, TraceStretch

PROBE_UID = 10 ** 9  # probe uids start here, clear of the traffic's


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax
    import jax.numpy as jnp

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.parallel import unbox

    enable_compilation_cache()
    phases.mark("imports")
    model_config = ProGenConfig(**{
        f.name: config[f.name] for f in dataclasses.fields(ProGenConfig)})
    policy = make_policy(True)
    model = ProGen(config=model_config, policy=policy)
    params = unbox(jax.jit(model.init)(
        jax.random.key(int(seed) & 0xFFFFFFFF),
        jnp.zeros((1, model_config.seq_len), jnp.int32)))
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config


def request_factory(workload: dict, num_tokens: int):
    """``make(r, submit_time, **overrides)`` -> an engine ``Request`` for one
    generated request ``r``.  End of sequence (token 0) is masked out of
    every generated position, so a request's length is the traffic file's
    and not a coin the random weights toss."""
    from progen_tpu.decode.engine import Request

    sampling = workload["traffic"]["sampling"]

    def make(r, submit_time, **overrides):
        mask = np.ones((r["max_new"], num_tokens), bool)
        mask[:, 0] = False
        fields = dict(uid=r["uid"], tokens=r["prime"],
                      max_new_tokens=r["max_new"], seed=r["seed"],
                      top_k=sampling["top_k"],
                      temperature=sampling["temperature"],
                      logit_mask=mask, submit_time=submit_time)
        fields.update(overrides)
        return Request(**fields)

    return make


def probe_check(engine, params, config: dict, workload: dict, make,
                seed: int) -> dict:
    """Serve ``probes`` greedy requests beside as many sampled ones through
    the engine, then run the reference's full forward over prime +
    generated.  At every generated position the reference's logit of the
    token served must be within ``tolerance`` of the reference's best
    allowed logit (greedy rows) or of its ``top_k``-th best (sampled rows)."""
    import jax
    import time

    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    rng = traffic.rng_for(seed, "probe")
    prime_max = workload["traffic"]["prime_tokens"]["max"]
    reqs = [{"uid": PROBE_UID + i,
             "prime": rng.integers(1, config["num_tokens"],
                                   int(rng.integers(8, prime_max + 1))).tolist(),
             "max_new": new, "seed": int(rng.integers(0, 2 ** 31 - 1))}
            for i in range(2 * n)]
    for i, r in enumerate(reqs):
        extra = {"temperature": 0.0} if i < n else {}
        engine.submit(make(r, time.perf_counter(), **extra))
    served = {c.uid: c for c in engine.run_until_idle()}
    engine.completions.clear()
    width = config["seq_len"]
    rows = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        c = served[r["uid"]]
        if not c.ok or len(c.tokens) != new:
            return {"ok": False, "why": f"probe {i} came back "
                    f"{c.finish_reason} with {len(c.tokens)} tokens"}
        seq = list(r["prime"]) + [int(t) for t in c.tokens]
        rows[i, :len(seq)] = seq
    fwd = jax.jit(partial(reference.forward, cfg=config))
    chunk = check["reference_rows"]
    with jax.default_matmul_precision("highest"):
        logits = np.concatenate([
            np.asarray(fwd(params["params"], rows[i:i + chunk]))
            for i in range(0, len(rows), chunk)])
    top_k = workload["traffic"]["sampling"]["top_k"]
    worst = {"greedy": 0.0, "sampled": 0.0}
    for i, r in enumerate(reqs):
        p = len(r["prime"])
        at = logits[i, p - 1:p - 1 + new, 1:]   # token 0 is masked out
        tok = rows[i, p:p + new] - 1
        served_logit = at[np.arange(new), tok]
        if i < n:
            gap = at.max(-1) - served_logit
            worst["greedy"] = max(worst["greedy"], float(gap.max()))
        else:
            kth = np.sort(at, axis=-1)[:, -top_k]
            gap = kth - served_logit
            worst["sampled"] = max(worst["sampled"], float(gap.max()))
    ok = max(worst.values()) <= check["tolerance"]
    return {"ok": ok, "worst": worst, "positions": 2 * n * new}


def slot_progress(engine) -> int:
    """Tokens generated so far by the requests now in their slots (one
    small host fetch of the engine's per-slot counters)."""
    import jax

    pos, start, active = jax.device_get(
        (engine.state["pos"], engine.state["start"], engine.state["active"]))
    return int(((pos - start + 1) * active).sum())


def run(*, workload, config, seed, seconds, trace, chips):
    import time

    phases = Phases()
    engine, params, model_config = build_engine(workload, config, seed, phases)
    make = request_factory(workload, model_config.num_tokens)
    probe = probe_check(engine, params, config, workload, make, seed)
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    requests = traffic.serve_requests(
        workload["traffic"], seed, seconds, model_config.num_tokens)
    tmp = tempfile.mkdtemp(prefix="perf-serve-")
    stretch = TraceStretch(os.path.join(tmp, "trace")) if trace else None
    win = workload["window"]

    def on_tick(now):
        """Profile the ``trace_seconds`` that end at ``trace_end_at`` (a
        share of the window; 1.0 ends it with the arrivals, so that
        stopping delays no submission).  Returns the seconds that starting
        or stopping took, which are not the engine's."""
        if stretch is None or stretch.done:
            return 0.0
        begin = max(0.0, win["trace_end_at"] * seconds - win["trace_seconds"])
        t = time.perf_counter()
        if not stretch.active and now >= begin:
            stretch.start()
        elif stretch.active and now >= begin + win["trace_seconds"]:
            stretch.stop()
        return time.perf_counter() - t

    try:
        if arrivals["kind"] == "backlog":
            now = time.perf_counter()
            for r in requests:
                engine.submit(make(r, now))
            while engine.chunks_run < win["ramp_chunks"]:
                engine.step()
            engine.completions.clear()
            before = slot_progress(engine)
            phases.mark("backlog and ramp")
            window_open = time.perf_counter()
            rec = loadgen.drive_backlog(engine, seconds=seconds,
                                        on_tick=on_tick)
            after = slot_progress(engine)
            wall = rec.elapsed
        else:
            window_open = time.perf_counter()
            rec = loadgen.drive_open_loop(
                engine, requests, make, seconds=seconds,
                drain_seconds=win["drain_share"] * seconds, on_tick=on_tick)
            wall = seconds
        if stretch is not None and stretch.active:
            stretch.stop()
        reduced = stretch.reduce() if stretch is not None else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    phases.mark("window, drain and trace")
    phases.report("serve")
    chunk = workload["engine"]["chunk_size"]
    slots = workload["engine"]["num_slots"]
    chunk_steps = [(e - s) for s, e, chunks, _, _ in rec.steps if chunks]
    counters = {
        "window_s": wall,
        "chunk_step_ms": [1e3 * d / chunk for d in chunk_steps],
        "occupancy": [a / slots for _, _, chunks, a, _ in rec.steps if chunks],
        "queued": [(e, q) for _, e, _, _, q in rec.steps],
    }
    end_to_end = {}
    if arrivals["kind"] == "backlog":
        finished = sum(n for _, n, ok in rec.completed.values() if ok)
        generated = finished + after - before
        attempted = len(rec.completed)
        failed = sum(1 for _, _, ok in rec.completed.values() if not ok)
        end_to_end["serve_tok_s"] = generated / wall
        counters.update(generated=generated, completed_tokens=finished)
        print(f"serve: backlog of {len(requests)}, {attempted} requests "
              f"finished and {generated} tokens generated in {wall:.3f} s "
              f"({len(chunk_steps)} chunks)", flush=True)
    else:
        attempted = len(requests)
        # an unanswered request misses every latency: it ranks last, at the
        # whole run's length per token
        worst = 1e3 * (1 + win["drain_share"]) * seconds
        norm, late, failed = [], [], 0
        for r in requests:
            done = rec.completed.get(r["uid"])
            if done is None or not done[2] or done[1] == 0:
                failed += 1
                norm.append(worst)
            else:
                norm.append(1e3 * (done[0] - r["due"]) / done[1])
            if r["uid"] in rec.submitted:
                late.append(1e3 * (rec.submitted[r["uid"]] - r["due"]))
        end_to_end["norm_latency_p50"] = stats.percentile(norm, 50)
        end_to_end["norm_latency_p95"] = stats.percentile(norm, 95)
        counters.update(late_ms=late, norm_latency=norm)
        print(f"serve: {attempted} requests at {arrivals['rate']} /s, "
              f"{failed} failed; highest percentile with ten samples beyond "
              f"it: p{stats.highest_percentile(attempted):g}; "
              f"{len(chunk_steps)} chunks", flush=True)
    return {
        "correct": bool(probe["ok"]),
        "attempted": attempted,
        "failed": failed,
        "window_open": window_open,
        "end_to_end": end_to_end,
        "observations": {"counters": counters, "spans": {}, "trace": reduced},
    }
