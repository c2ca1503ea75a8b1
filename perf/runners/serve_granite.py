"""Serving cells of the Granite 4.0-H family: the in-process
``ServingEngine`` under a standing backlog, as ``runners/serve_trinity.py``
drives Trinity's (same window, same clock, the same two rules of comparison
without routing: the family has no experts; what needs no family is imported
from ``runners/serve_deepseek_v2.py``, ``runners/serve_longcat.py`` and
``runners/serve.py``).

Set-up, all outside the window: 3.19 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket and the chunk
program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_granite.py`` (float32 ``highest``, no cache, no chunks:
the recurrence token by token):

* **direct** — the family's own prefill at the timed admission shape
  (``admit_rows`` real rows at the largest bucket: one longer than half the
  bucket, one of exactly one chunk of the scan + 1 tokens, so that a chunk
  boundary falls one token before its end; no length a multiple of the
  smallest bucket), the state it hands over laid out as ALL slots' state,
  then ``decode_steps`` steps of all slots (the admitted rows live) that
  fold one token each into the carry and the tail and grow the keys.
  Every logit at ``positions`` prefill positions and at each row's decode
  positions within ``tolerance`` of the reference's full forward, and their
  root mean square within ``rms_limit``;
* **probes** — greedy and sampled requests through the engine, the
  reference's logit of each served token against its best / ``top_k``-th
  best allowed logit (the sibling cells' rule and tolerance), held as the
  share of generated positions over the tolerance within
  ``over_share_limit``.

The control readings of the limits: ``perf/tools/granite_lowp.py``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from functools import partial

import numpy as np

from perf.lib import loadgen, reference_granite, traffic
from perf.lib.harness import Phases, TraceStretch, load_module

# query rows per score block of the reference's attention: (32 heads, 256,
# 1152 keys) float32 is 38 MB
QUERY_BLOCK = 256


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import granite_hybrid

    enable_compilation_cache()
    phases.mark("imports")
    model_config = granite_hybrid.GraniteHybridConfig.from_dict(config)
    policy = granite_hybrid.bf16_policy()
    params = granite_hybrid.init_params(
        model_config, jax.random.key(int(seed) & 0xFFFFFFFF), policy)
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config, policy


def reference_positions(check: dict, rows: int) -> int:
    """Positions a call of the reference reads: the probes' new tokens, or
    what a row of the direct check compares if that were more."""
    direct = check["direct"]
    return max(check["probe_new_tokens"],
               direct["positions"] // rows + direct["decode_steps"])


def reference_for(config: dict, workload: dict, rows: int):
    """The reference's full forward of one row: ``(params, tokens (<= T,),
    positions (<= K,)) -> logits (K, V)``.  ONE program for the direct
    check's rows and the probes': every row is padded to ``T`` = the
    longest prime + ``probe_new_tokens`` (or the direct check's steps, were
    they more) and every list of positions to
    ``K`` = :func:`reference_positions` (causality keeps the padding out of
    what is read), as the sibling runners do it: the machine's compile cache
    is shared by every cell."""
    import jax

    check = workload["correct"]
    width = (workload["traffic"]["prime_tokens"]["max"]
             + max(check["probe_new_tokens"], check["direct"]["decode_steps"]))
    count = reference_positions(check, rows)

    @jax.jit
    def fwd(params, tokens, positions):
        return reference_granite.forward_row(
            params, tokens, config, q_block=QUERY_BLOCK,
            logit_positions=positions)

    def padded(params, tokens, positions):
        k = len(positions)
        logits = fwd(params, np.pad(tokens, (0, width - len(tokens))),
                     np.pad(positions, (0, count - k), mode="edge"))
        return logits[:k]

    return padded


def direct_rows(check: dict, seed: int, vocab: int, rows: int, chunk: int,
                bucket: int):
    """The direct check's seeded rows: ``(lengths (rows,), tokens (rows, hi
    + steps) of which lengths + steps are real, positions (rows, K +
    steps))`` — per row ``n`` prime tokens, the ``steps`` tokens the decode
    steps take, and the positions compared (K over the prime, then the
    decode positions).  Row 0 is longer than half the largest prime, row 1
    is one chunk of the scan + 1 tokens long (its last token is the first
    of a chunk), the others lie anywhere in the range; a length that is a
    multiple of the smallest bucket moves one up.  One shape whatever the
    seed drew, so the compile cache holds the reference; causality keeps
    the padding out."""
    rng = traffic.rng_for(seed, "direct")
    lo, hi = check["prime_tokens"]
    steps = check["decode_steps"]
    k = check["positions"] // rows
    lengths = rng.integers(lo, hi, rows)
    lengths[0] = rng.integers(hi // 2 + 1, hi)
    lengths += lengths % bucket == 0
    if rows > 1:
        lengths[1] = chunk + 1
    tokens = np.zeros((rows, hi + steps), np.int32)
    at = np.zeros((rows, k + steps), np.int32)
    for i, n in enumerate(lengths):
        tokens[i, :n + steps] = rng.integers(1, vocab, n + steps)
        at[i] = np.append(np.linspace(0, n - 1, k), n + np.arange(steps))
    return lengths.astype(np.int32), tokens, at


def served_logits(engine, params, model_config, policy, lengths, tokens, at,
                  steps: int):
    """The family's own prefill of ``lengths.size`` rows at their bucket,
    laid out as every slot's state, then ``steps`` decode steps of all
    slots: the logits ``(rows, K + steps, V)`` at ``at``."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import granite_hybrid

    rows, slots = len(lengths), engine.num_slots
    p_pad = engine.family.bucket(int(lengths.max()), engine.max_len)
    padded = np.zeros((rows, p_pad), np.int32)
    for i, n in enumerate(lengths):
        padded[i, :n] = tokens[i, :n]

    def prefill(params, padded, lengths, positions):
        logits, handed, _ = granite_hybrid.prefill(
            params, padded, lengths, model_config, policy,
            logit_positions=positions)
        # the real rows' state in the first slots of a full batch; the
        # rest idle
        mine = granite_hybrid.caches_from(handed, lengths, model_config,
                                          engine.max_len)
        caches = jax.tree.map(
            lambda a: jnp.zeros((slots,) + a.shape[1:], a.dtype)
            .at[:rows].set(a), mine)
        return logits, caches

    logits, caches = jax.jit(prefill)(params, padded, lengths,
                                      at[:, :-steps])
    live = np.arange(slots) < rows
    # the caches are donated: this copy is the third on the chip (the
    # engine's state is there too), and only the steps' logits are read
    step = jax.jit(partial(granite_hybrid.decode_step, config=model_config,
                           policy=policy), donate_argnums=(3,))
    out = [np.asarray(logits)]
    for j in range(steps):
        tok = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        tok[:rows] = tokens[np.arange(rows), lengths + j]
        pos[:rows] = lengths + j
        step_logits, caches, _ = step(params, tok, pos, caches, live)
        out.append(np.asarray(step_logits)[:rows, None])
    del caches
    return np.concatenate(out, axis=1)


def direct_check(engine, params, model_config, policy, config: dict,
                 workload: dict, seed: int) -> dict:
    """Prefill ``admit_rows`` real rows at the largest bucket, lay the
    state they hand over out as the slots', and run ``decode_steps`` steps
    of every slot through it; compare the logits at the prefill positions
    and at each row's decode positions with the reference's full forward
    over the same tokens."""
    import jax

    check = workload["correct"]["direct"]
    rows, steps = engine.admit_rows, check["decode_steps"]
    lengths, tokens, at = direct_rows(
        check, seed, model_config.vocab_size, rows,
        model_config.mamba_chunk_size, model_config.prefill_bucket)
    got = served_logits(engine, params, model_config, policy, lengths,
                        tokens, at, steps)
    reference = reference_for(config, workload, rows)
    worst = {"prefill": 0.0, "decode": 0.0}
    square, spread = 0.0, []
    for i in range(rows):
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(params, tokens[i], at[i]))
        diff = np.abs(got[i] - want)
        worst["prefill"] = max(worst["prefill"], float(diff[:-steps].max()))
        worst["decode"] = max(worst["decode"], float(diff[-steps:].max()))
        square += float((diff ** 2).sum())
        spread.append(float(want.std()))
    rms = float(np.sqrt(square / got.size))
    return {"ok": (max(worst.values()) <= check["tolerance"]
                   and rms <= check["rms_limit"]),
            "worst": worst, "rms": rms, "logit_std": float(np.mean(spread)),
            "primes": lengths.tolist(), "positions": int(at.size)}


def probe_check(engine, params, config: dict, workload: dict, make,
                seed: int, probe_uid: int) -> dict:
    """``probes`` greedy requests beside as many sampled ones through the
    engine; then the reference's full forward over prime + generated
    (``reference_for`` pads every row to one length).
    ``serve_deepseek_v2``'s rule, with this family's reference."""
    import jax

    sibling = load_module("perf/runners/serve_deepseek_v2.py")
    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    reqs, greedy = sibling.probe_requests(workload, seed,
                                          config["vocab_size"], probe_uid)
    for i, r in enumerate(reqs):
        extra = {"temperature": 0.0} if i in greedy else {}
        engine.submit(make(r, time.perf_counter(), **extra))
    served = {c.uid: c for c in engine.run_until_idle()}
    engine.completions.clear()
    rows = []
    for i, r in enumerate(reqs):
        c = served[r["uid"]]
        if not c.ok or len(c.tokens) != new:
            return {"ok": False, "why": f"probe {i} came back "
                    f"{c.finish_reason} with {len(c.tokens)} tokens"}
        rows.append(np.asarray(list(r["prime"]) + [int(t) for t in c.tokens],
                               np.int32))
    reference = reference_for(config, workload, engine.admit_rows)
    top_k = workload["traffic"]["sampling"]["top_k"]
    gaps = {"greedy": [], "sampled": []}
    for i, r in enumerate(reqs):
        p = len(r["prime"])
        with jax.default_matmul_precision("highest"):
            logits = reference(params, rows[i], np.arange(p - 1, p - 1 + new))
        at = np.asarray(logits)[:, 1:]          # token 0 is masked out
        tok = rows[i][p:p + new] - 1
        kind = "greedy" if i in greedy else "sampled"
        gaps[kind].append(sibling.probe_gaps(
            at, tok, None if i in greedy else top_k))
    reading = {k: sibling.gap_reading(np.concatenate(v), check["tolerance"])
               for k, v in gaps.items()}
    ok = all(r["over_share"] <= check["over_share_limit"]
             for r in reading.values())
    return {"ok": ok, **reading, "positions": 2 * n * new,
            "primes": [len(r["prime"]) for r in reqs]}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.granite_hybrid  # noqa: F401

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
        raise ValueError("runners/serve_granite.py drives backlogs only")
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
          f"in {wall:.3f} s ({len(chunk_steps)} chunks); lowerings "
          f"{engine.lowerings}; counters "
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
