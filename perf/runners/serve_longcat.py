"""Serving cells of the LongCat-Flash family: the in-process
``ServingEngine`` under a standing backlog, as ``runners/serve.py`` drives
ProGen's (same window, same clock, same counters; what needs no ProGen is
imported from there).

Set-up, all outside the window: 5.17 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket and the chunk
program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_longcat.py`` (float32 ``highest``, no cache, the
non-absorbed attention, a dense loop over the held experts):

* **direct** — the family's own prefill at the timed admission shape and
  one absorbed decode step of all slots through the latent cache: every
  logit at ``positions`` prefill positions and at the decode position
  within ``tolerance``, AND the share of (token, layer) routings whose
  chosen set differs from the reference's within ``routings_limit`` (the
  one measure that tells float32 routing from bfloat16 routing on this
  chip: logits do not);
* **probes** — greedy and sampled requests through the engine, the
  reference's logit of each served token against its best / ``top_k``-th
  best allowed logit (the sibling cells' rule).

The control readings of all three limits: ``perf/tools/longcat_lowp.py``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from functools import partial

import numpy as np

from perf.lib import loadgen, reference_longcat, traffic
from perf.lib.harness import Phases, TraceStretch, load_module


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import longcat

    enable_compilation_cache()
    phases.mark("imports")
    model_config = longcat.LongCatConfig.from_dict(config)
    policy = longcat.bf16_policy()
    params = longcat.init_params(
        model_config, jax.random.key(int(seed) & 0xFFFFFFFF), policy)
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config, policy


def request_factory(workload: dict, vocab: int):
    """``make(r, submit_time, **overrides)`` -> an engine ``Request``.  End
    of sequence (token 0) is masked out of every generated position by ONE
    ``(V,)`` row that all requests share."""
    from progen_tpu.decode.engine import Request

    sampling = workload["traffic"]["sampling"]
    never_zero = np.ones((vocab,), bool)
    never_zero[0] = False

    def make(r, submit_time, **overrides):
        fields = dict(uid=r["uid"], tokens=r["prime"],
                      max_new_tokens=r["max_new"], seed=r["seed"],
                      top_k=sampling["top_k"],
                      temperature=sampling["temperature"],
                      logit_mask=never_zero, submit_time=submit_time)
        fields.update(overrides)
        return Request(**fields)

    return make


def _reference(config: dict):
    import jax

    def fwd(params, tokens, positions):
        return reference_longcat.forward_row(
            params, tokens, config, logit_positions=positions)

    return jax.jit(fwd)


def direct_row(check: dict, seed: int, vocab: int):
    """The direct check's seeded row: ``(n, tokens (hi + 1,) of which n + 1
    are real, positions (K + 1,))`` — ``n`` prime tokens, the token the
    decode step takes, and the positions compared (K over the prime, then
    the decode position).  One shape whatever the seed drew, so the compile
    cache holds the reference; causality keeps the padding out."""
    rng = traffic.rng_for(seed, "direct")
    lo, hi = check["prime_tokens"]
    n = int(rng.integers(lo, hi + 1))
    row = np.zeros((hi + 1,), np.int32)
    row[:n + 1] = rng.integers(1, vocab, n + 1)
    at = np.linspace(0, n - 1, check["positions"]).astype(np.int32)
    return n, row, np.append(at, n).astype(np.int32)


def direct_check(engine, params, model_config, policy, config: dict,
                 workload: dict, seed: int) -> dict:
    """Prefill ``admit_rows`` rows at the 4096 bucket (one real row) and run
    one decode step of every slot through its cache; compare the logits at
    ``positions`` prefill positions and at the decode position, and every
    layer's chosen experts for every token, with the reference's full
    forward over the same tokens."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import longcat

    check = workload["correct"]["direct"]
    n, row, at = direct_row(check, seed, model_config.vocab_size)
    rows, slots = engine.admit_rows, engine.num_slots
    p_pad = engine.family.bucket(n, engine.max_len)
    tokens = np.zeros((rows, p_pad), np.int32)
    tokens[0, :n] = row[:n]
    lengths = np.full((rows,), engine.family.idle_length, np.int32)
    lengths[0] = n
    positions = np.zeros((rows, len(at) - 1), np.int32)
    positions[0] = at[:-1]

    prefill = jax.jit(partial(longcat.prefill, config=model_config,
                              policy=policy, with_choices=True))
    logits, latent, _, chosen = prefill(params, tokens, lengths,
                                        logit_positions=positions)
    # the real row's cache in slot 0 of a full batch; the others idle
    caches = {name: jnp.zeros((slots, engine.max_len, v.shape[-1]), v.dtype)
              .at[0, :p_pad].set(v[0]) for name, v in latent.items()}
    tok = np.zeros((slots,), np.int32)
    tok[0] = row[n]
    pos = np.zeros((slots,), np.int32)
    pos[0] = n
    live = np.arange(slots) == 0
    step = jax.jit(partial(longcat.decode_step, config=model_config,
                           policy=policy, with_choices=True))
    step_logits, _, _, step_chosen = step(params, tok, pos, caches, live)
    got = np.concatenate([np.asarray(logits[0]),
                          np.asarray(step_logits[:1])])
    got_sets = np.concatenate([np.asarray(chosen[:, 0, :n]),
                               np.asarray(step_chosen[:, :1])], axis=1)
    del caches, latent

    with jax.default_matmul_precision("highest"):
        want, want_sets = _reference(config)(params, row, at)
    want = np.asarray(want)
    differ = np.any(np.sort(got_sets, -1) != np.sort(
        np.asarray(want_sets)[:, :n + 1], -1), axis=-1)
    diff = np.abs(got - want)
    worst, share = float(diff.max()), float(differ.mean())
    return {"ok": (worst <= check["tolerance"]
                   and share <= check["routings_limit"]),
            "worst": worst, "rms": float(np.sqrt((diff ** 2).mean())),
            "logit_std": float(want.std()), "prime": n,
            "positions": len(at), "routings_differ_share": share}


def probe_check(engine, params, config: dict, workload: dict, make,
                seed: int, probe_uid: int) -> dict:
    """``probes`` greedy requests beside as many sampled ones through the
    engine, primes one from each quarter of the cell's range; then the
    reference's full forward over prime + generated, every row padded to
    the longest the cell allows (causality keeps the padding out of what is
    read)."""
    import jax

    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    rng = traffic.rng_for(seed, "probe")
    primes = workload["traffic"]["prime_tokens"]
    edges = np.linspace(primes["min"], primes["max"] + 1, 2 * n + 1)
    reqs = [{"uid": probe_uid + i,
             "prime": rng.integers(
                 1, config["vocab_size"],
                 int(rng.integers(int(edges[i]), int(edges[i + 1])))).tolist(),
             "max_new": new, "seed": int(rng.integers(0, 2 ** 31 - 1))}
            for i in range(2 * n)]
    greedy = set(rng.permutation(2 * n)[:n].tolist())
    for i, r in enumerate(reqs):
        extra = {"temperature": 0.0} if i in greedy else {}
        engine.submit(make(r, time.perf_counter(), **extra))
    served = {c.uid: c for c in engine.run_until_idle()}
    engine.completions.clear()
    width = primes["max"] + new     # one shape whatever the seed drew
    rows = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        c = served[r["uid"]]
        if not c.ok or len(c.tokens) != new:
            return {"ok": False, "why": f"probe {i} came back "
                    f"{c.finish_reason} with {len(c.tokens)} tokens"}
        seq = list(r["prime"]) + [int(t) for t in c.tokens]
        rows[i, :len(seq)] = seq
    reference = _reference(config)
    top_k = workload["traffic"]["sampling"]["top_k"]
    worst = {"greedy": 0.0, "sampled": 0.0}
    for i, r in enumerate(reqs):
        p = len(r["prime"])
        with jax.default_matmul_precision("highest"):
            logits, _ = reference(params, rows[i], np.arange(p - 1, p - 1 + new))
        at = np.asarray(logits)[:, 1:]          # token 0 is masked out
        tok = rows[i, p:p + new] - 1
        served_logit = at[np.arange(new), tok]
        if i in greedy:
            gap = at.max(-1) - served_logit
            worst["greedy"] = max(worst["greedy"], float(gap.max()))
        else:
            kth = np.sort(at, axis=-1)[:, -top_k]
            gap = kth - served_logit
            worst["sampled"] = max(worst["sampled"], float(gap.max()))
    ok = max(worst.values()) <= check["tolerance"]
    return {"ok": ok, "worst": worst, "positions": 2 * n * new,
            "primes": [len(r["prime"]) for r in reqs]}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.longcat  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    make = request_factory(workload, model_config.vocab_size)
    direct = direct_check(engine, params, model_config, policy, config,
                          workload, seed)
    phases.mark("direct check and reference")
    print(f"serve: family vs reference {direct}", flush=True)
    probe = probe_check(engine, params, config, workload, make, seed,
                        serve.PROBE_UID)
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    if arrivals["kind"] != "backlog":
        raise ValueError("runners/serve_longcat.py drives backlogs only")
    requests = traffic.serve_requests(
        workload["traffic"], seed, seconds, model_config.vocab_size)
    tmp = tempfile.mkdtemp(prefix="perf-serve-")
    stretch = TraceStretch(os.path.join(tmp, "trace")) if trace else None
    win = workload["window"]

    def on_tick(now):
        """As ``runners/serve.py``: profile ``trace_seconds`` ending at
        ``trace_end_at`` of the window; the seconds it took are not the
        engine's."""
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
        now = time.perf_counter()
        for r in requests:
            engine.submit(make(r, now))
        while engine.chunks_run < win["ramp_chunks"]:
            engine.step()
        engine.completions.clear()
        before = serve.slot_progress(engine)
        phases.mark("backlog and ramp")
        window_open = time.perf_counter()
        rec = loadgen.drive_backlog(engine, seconds=seconds, on_tick=on_tick)
        after = serve.slot_progress(engine)
        wall = rec.elapsed
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
    finished = sum(n for _, n, ok in rec.completed.values() if ok)
    generated = finished + after - before
    attempted = len(rec.completed)
    failed = sum(1 for _, _, ok in rec.completed.values() if not ok)
    # the queue is first in, first out: what is no longer pending was
    # admitted (beside the probes, which all were)
    admitted = requests[:len(requests) - engine.pending]
    counters = {
        "window_s": wall,
        "chunk_step_ms": [1e3 * d / chunk for d in chunk_steps],
        "occupancy": [a / slots for _, _, chunks, a, _ in rec.steps if chunks],
        "queued": [(e, q) for _, e, _, _, q in rec.steps],
        "generated": generated, "completed_tokens": finished,
        "admitted_primes": probe.get("primes", []) + [
            len(r["prime"]) for r in admitted],
    }
    print(f"serve: backlog of {len(requests)}, {len(admitted)} admitted, "
          f"{attempted} requests finished and {generated} tokens generated "
          f"in {wall:.3f} s ({len(chunk_steps)} chunks); counters "
          f"{ {k: np.asarray(v).round(1).tolist() for k, v in engine.model_stats.items()} }",
          flush=True)
    return {
        "correct": bool(direct["ok"] and probe["ok"]),
        "attempted": attempted,
        "failed": failed,
        "window_open": window_open,
        "end_to_end": {"serve_tok_s": generated / wall},
        "observations": {"counters": counters, "spans": {}, "trace": reduced},
    }
