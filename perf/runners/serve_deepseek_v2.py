"""Serving cells of the DeepSeek-V2 family: the in-process ``ServingEngine``
under a standing backlog, as ``runners/serve_longcat.py`` drives LongCat's
(same window, same clock, same counters; what needs no family is imported
from there and from ``runners/serve.py``).

Set-up, all outside the window: 5.16 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket and the chunk
program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_deepseek_v2.py`` (float32 ``highest``, no cache, the
non-absorbed attention, routing by reshape / max / top-k, a dense loop over
the held experts):

* **direct** — the family's own prefill at the timed admission shape
  (``admit_rows`` real rows at the largest bucket) and one absorbed decode
  step of ALL slots through the latent cache (the admitted rows live): every
  logit at ``positions`` prefill positions and at each row's decode position
  within ``tolerance`` wherever the token's routing agreed with the
  reference's, AND the share of (token, expert layer) routings whose chosen
  set of 6 differs from the reference's within ``routings_limit``
  (``compare_row`` says why the two go together);
* **probes** — greedy and sampled requests through the engine, the
  reference's logit of each served token against its best / ``top_k``-th
  best allowed logit (the sibling cells' rule and tolerance), held as the
  share of generated positions over the tolerance within
  ``over_share_limit``.

The control readings of all three limits: ``perf/tools/deepseek_v2_lowp.py``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from functools import partial

import numpy as np

from perf.lib import loadgen, reference_deepseek_v2, traffic
from perf.lib.harness import Phases, TraceStretch, load_module


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import deepseek_v2

    enable_compilation_cache()
    phases.mark("imports")
    model_config = deepseek_v2.DeepSeekV2Config.from_dict(config)
    policy = deepseek_v2.bf16_policy()
    params = deepseek_v2.init_params(
        model_config, jax.random.key(int(seed) & 0xFFFFFFFF), policy)
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config, policy


def reference_for(config: dict):
    """The reference's full forward of one row, jitted: ``(params, tokens
    (T,), positions (K,)) -> (logits (K, V), choices)``."""
    import jax

    def fwd(params, tokens, positions):
        return reference_deepseek_v2.forward_row(
            params, tokens, config, logit_positions=positions)

    return jax.jit(fwd)


def direct_rows(check: dict, seed: int, vocab: int, rows: int):
    """The direct check's seeded rows: ``(lengths (rows,), tokens (rows, hi
    + 1) of which lengths + 1 are real, positions (rows, K + 1))`` — per
    row ``n`` prime tokens, the token the decode step takes, and the
    positions compared (K over the prime, then the decode position).  One
    shape whatever the seed drew, so the compile cache holds the reference;
    causality keeps the padding out."""
    rng = traffic.rng_for(seed, "direct")
    lo, hi = check["prime_tokens"]
    k = check["positions"] // rows
    lengths = rng.integers(lo, hi + 1, rows)
    tokens = np.zeros((rows, hi + 1), np.int32)
    at = np.zeros((rows, k + 1), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n + 1] = rng.integers(1, vocab, n + 1)
        at[i] = np.append(np.linspace(0, n - 1, k), n)
    return lengths.astype(np.int32), tokens, at


def direct_check(engine, params, model_config, policy, config: dict,
                 workload: dict, seed: int) -> dict:
    """Prefill ``admit_rows`` real rows at the largest bucket and run one
    decode step of every slot through the cache; compare the logits at the
    prefill positions and at each row's decode position, and every expert
    layer's chosen experts for every token, with the reference's full
    forward over the same tokens."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import deepseek_v2

    check = workload["correct"]["direct"]
    rows, slots = engine.admit_rows, engine.num_slots
    lengths, tokens, at = direct_rows(check, seed, model_config.vocab_size,
                                      rows)
    p_pad = engine.family.bucket(int(lengths.max()), engine.max_len)
    padded = np.zeros((rows, p_pad), np.int32)
    for i, n in enumerate(lengths):
        padded[i, :n] = tokens[i, :n]

    prefill = jax.jit(partial(deepseek_v2.prefill, config=model_config,
                              policy=policy, with_choices=True))
    logits, latent, _, chosen = prefill(params, padded, lengths,
                                        logit_positions=at[:, :-1])
    # the real rows' caches in the first slots of a full batch; the rest idle
    caches = {name: jnp.zeros((slots, engine.max_len, v.shape[-1]), v.dtype)
              .at[:rows, :p_pad].set(v) for name, v in latent.items()}
    tok = np.zeros((slots,), np.int32)
    pos = np.zeros((slots,), np.int32)
    tok[:rows] = tokens[np.arange(rows), lengths]
    pos[:rows] = lengths
    live = np.arange(slots) < rows
    # the caches are donated: this step's copy is the third on the chip
    # (the engine's state is there too), and only its logits are read
    step = jax.jit(partial(deepseek_v2.decode_step, config=model_config,
                           policy=policy, with_choices=True),
                   donate_argnums=(3,))
    step_logits, _, _, step_chosen = step(params, tok, pos, caches, live)
    logits, step_logits = np.asarray(logits), np.asarray(step_logits)
    chosen, step_chosen = np.asarray(chosen), np.asarray(step_chosen)
    del latent

    reference = reference_for(config)
    worst = {"agreed": 0.0, "all": 0.0}
    square, count, differ, routings, agreed_at, spread = 0.0, 0, 0, 0, 0, []
    for i, n in enumerate(lengths):
        with jax.default_matmul_precision("highest"):
            want, want_sets = reference(params, tokens[i], at[i])
        want = np.asarray(want)
        got = np.concatenate([logits[i], step_logits[i:i + 1]])
        got_sets = np.concatenate([chosen[:, i, :n], step_chosen[:, i:i + 1]],
                                  axis=1)
        reading = compare_row(got, got_sets, want,
                              np.asarray(want_sets)[:, :n + 1], at[i])
        for k in worst:
            worst[k] = max(worst[k], reading["worst"][k])
        square += reading["square"]
        count += got.size
        differ += reading["differ"]
        routings += reading["routings"]
        agreed_at += reading["agreed_positions"]
        spread.append(float(want.std()))
    share = differ / routings
    return {"ok": (worst["agreed"] <= check["tolerance"]
                   and share <= check["routings_limit"]
                   and 2 * agreed_at >= at.size),
            "worst": worst, "rms": float(np.sqrt(square / count)),
            "logit_std": float(np.mean(spread)),
            "primes": lengths.tolist(), "positions": int(at.size),
            "agreed_positions": agreed_at,
            "routings": routings, "routings_differ_share": share}


def compare_row(got, got_sets, want, want_sets, at) -> dict:
    """One row's readings: ``got`` / ``want (K + 1, V)`` logits at the
    positions ``at``, ``*_sets (expert layers, n + 1, k)`` the routers'
    choices at every token.  A routing DIFFERS where the chosen set is not
    the reference's; a compared position is AGREED where no expert layer's
    routing of that token differs.  With float32 routing over bfloat16
    activations a few routings in a hundred differ (near-ties of the 6th
    and 7th probability, or of the 3rd and 4th group), and where one does
    the token's logits move by up to 2 at a spread of 1 (an expert's term
    is ``16 p`` = 0.2-0.7 of its output): that is counted by the share, and
    the logits are held to the tolerance where the routing agreed."""
    wrong = np.any(np.sort(got_sets, -1) != np.sort(want_sets, -1), axis=-1)
    agreed = ~wrong.any(axis=0)[at]
    diff = np.abs(got - want)
    return {"worst": {"agreed": float(diff[agreed].max()) if agreed.any()
                      else 0.0, "all": float(diff.max())},
            "square": float((diff ** 2).sum()),
            "differ": int(wrong.sum()), "routings": int(wrong.size),
            "agreed_positions": int(agreed.sum())}


def probe_requests(workload: dict, seed: int, vocab: int, first_uid: int):
    """``2 * probes`` requests, primes one from each equal part of the
    cell's range, and which of them are greedy."""
    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    rng = traffic.rng_for(seed, "probe")
    primes = workload["traffic"]["prime_tokens"]
    edges = np.linspace(primes["min"], primes["max"] + 1, 2 * n + 1)
    reqs = [{"uid": first_uid + i,
             "prime": rng.integers(
                 1, vocab,
                 int(rng.integers(int(edges[i]), int(edges[i + 1])))).tolist(),
             "max_new": new, "seed": int(rng.integers(0, 2 ** 31 - 1))}
            for i in range(2 * n)]
    return reqs, set(rng.permutation(2 * n)[:n].tolist())


def probe_check(engine, params, config: dict, workload: dict, make,
                seed: int, probe_uid: int) -> dict:
    """``probes`` greedy requests beside as many sampled ones through the
    engine; then the reference's full forward over prime + generated, every
    row padded to the longest the probes allow (causality keeps the padding
    out of what is read)."""
    import jax

    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    reqs, greedy = probe_requests(workload, seed, config["vocab_size"],
                                  probe_uid)
    for i, r in enumerate(reqs):
        extra = {"temperature": 0.0} if i in greedy else {}
        engine.submit(make(r, time.perf_counter(), **extra))
    served = {c.uid: c for c in engine.run_until_idle()}
    engine.completions.clear()
    width = workload["traffic"]["prime_tokens"]["max"] + new
    rows = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        c = served[r["uid"]]
        if not c.ok or len(c.tokens) != new:
            return {"ok": False, "why": f"probe {i} came back "
                    f"{c.finish_reason} with {len(c.tokens)} tokens"}
        seq = list(r["prime"]) + [int(t) for t in c.tokens]
        rows[i, :len(seq)] = seq
    reference = reference_for(config)
    top_k = workload["traffic"]["sampling"]["top_k"]
    gaps = {"greedy": [], "sampled": []}
    for i, r in enumerate(reqs):
        p = len(r["prime"])
        with jax.default_matmul_precision("highest"):
            logits, _ = reference(params, rows[i], np.arange(p - 1, p - 1 + new))
        at = np.asarray(logits)[:, 1:]          # token 0 is masked out
        tok = rows[i, p:p + new] - 1
        kind = "greedy" if i in greedy else "sampled"
        gaps[kind].append(probe_gaps(at, tok, None if i in greedy else top_k))
    reading = {k: gap_reading(np.concatenate(v), check["tolerance"])
               for k, v in gaps.items()}
    ok = all(r["over_share"] <= check["over_share_limit"]
             for r in reading.values())
    return {"ok": ok, **reading, "positions": 2 * n * new,
            "primes": [len(r["prime"]) for r in reqs]}


def probe_gaps(at, tok, top_k):
    """Per generated position, the reference's best (``top_k`` None) or
    ``top_k``-th best allowed logit less its logit of the served token,
    not below 0: ``at (new, V - 1)``, ``tok (new,)``."""
    served = at[np.arange(len(tok)), tok]
    bar = at.max(-1) if top_k is None else np.sort(at, axis=-1)[:, -top_k]
    return np.maximum(bar - served, 0.0)


def gap_reading(gaps, tolerance) -> dict:
    """What the probe rule reads from the gaps of one kind of probe: the
    share of positions whose gap is over ``tolerance`` (limited: a routing
    that differs moves a token's logits by more than any tolerance, as
    ``compare_row`` says), and for the record the worst and the mean gap."""
    return {"over_share": float((gaps > tolerance).mean()),
            "worst": float(gaps.max()), "mean": float(gaps.mean())}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.deepseek_v2  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    longcat = load_module("perf/runners/serve_longcat.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    make = longcat.request_factory(workload, model_config.vocab_size)
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
        raise ValueError("runners/serve_deepseek_v2.py drives backlogs only")
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
    counters = {
        "window_s": wall,
        "chunk_step_ms": [1e3 * d / chunk for d in chunk_steps],
        "occupancy": [a / slots for _, _, chunks, a, _ in rec.steps if chunks],
        "queued": [(e, q) for _, e, _, _, q in rec.steps],
        "generated": generated, "completed_tokens": finished,
    }
    print(f"serve: backlog of {len(requests)}, "
          f"{len(requests) - engine.pending} admitted, "
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
