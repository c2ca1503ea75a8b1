"""Serving cells of the Nemotron-H family: the in-process ``ServingEngine``
under a standing backlog, as ``runners/serve_lfm2.py`` drives LFM2's (same
window, same clock, same counters, the same two rules of comparison; what
needs no family is imported from there, from ``runners/serve_longcat.py``
and from ``runners/serve.py``).

Set-up, all outside the window: 4.65 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket and the chunk
program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_nemotron3.py`` (float32 ``highest``, no cache, the
recurrence token by token, a dense loop over the held experts):

* **direct** — the engine's own compiled programs over its own state:
  ``admit_rows`` long requests (the timed admission shape, every row real)
  into the first slots, a chunk, the slots released; then EVERY slot
  admitted, ``admit_rows`` a run, the first run SHORTER rows (1, 2 and 3
  tokens among them: shorter than the convolution's four taps) INTO THE
  SLOTS THE LONG ONES LEFT; the family's decode step of all slots over the
  state that leaves — each slot at its first step after admission —;
  ``chunks`` runs of the chunk program; the step again.  The logits of
  ``compared_slots`` slots at both steps, EACH ROW's RMS difference from the
  reference's within ``row_rms_limit`` WHATEVER ITS ROUTING (a carry, a tail
  or a key that an admission misplaces or leaves behind reads as far from
  the reference as an unrelated row), the RMS over ALL of them within
  ``rms_limit`` (a lower precision fails it), AND the share of the steps'
  (token, expert layer, chosen expert) assignments whose expert is not
  among the reference's 22 within ``assignments_limit`` — top-22 of 512
  sigmoids lie close, so a chosen SET seldom agrees whole and the share of
  sets that differ would read near 1 for any server;
* **probes** — greedy and sampled requests through the engine, in two
  waves: the two longest primes first, then the two shortest INTO THE SLOTS
  THE FIRST WAVE LEFT; the reference's logit of each served token against
  its best / ``top_k``-th best allowed logit (the sibling cells' rule and
  tolerance), held as the share of generated positions over the tolerance
  within ``over_share_limit``.

The traced stretch starts and stops between two ``engine.step()`` calls,
each of which ends on the engine's one sync point with the device counters
fetched: the counters' difference over the stretch (``stretch_counters``)
is exactly what the device ops inside it did, which is what
``moe_decode_roofline.nemotron3`` divides by the kernel's device seconds.

The control readings of the limits: ``perf/tools/nemotron3_lowp.py``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from perf.lib import loadgen, reference_nemotron3, traffic
from perf.lib.harness import Phases, TraceStretch, load_module

# query rows per score block of the reference's attention: (32 heads, 256,
# 1152 keys) float32 is 38 MB
QUERY_BLOCK = 256
SHORTEST = (1, 2, 3)        # readmitted primes shorter than the four taps


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import nemotron_h

    enable_compilation_cache()
    phases.mark("imports")
    model_config = nemotron_h.NemotronHConfig.from_dict(config)
    policy = nemotron_h.bf16_policy()
    params = nemotron_h.init_params(
        model_config, jax.random.key(int(seed) & 0xFFFFFFFF), policy)
    jax.block_until_ready(params)
    counted = sum(a.size for a in jax.tree.leaves(params))
    print(f"serve: {counted:,} parameters, "
          f"{sum(a.nbytes for a in jax.tree.leaves(params)):,} bytes",
          flush=True)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config, policy


def reference_for(config: dict, workload: dict, lfm2_runner):
    """The reference's full forward of one row: ``(params, tokens (<= T,),
    positions (<= K,)) -> (logits (K, V), choices (expert layers, T, k))``.
    ONE program for the direct check's rows and the probes', padded as
    ``runners/serve_lfm2.py:reference_for`` pads them."""
    import jax

    check = workload["correct"]
    count = check["probe_new_tokens"]
    width = max(workload["traffic"]["prime_tokens"]["max"] + count,
                lfm2_runner.direct_width(workload))

    @jax.jit
    def fwd(params, tokens, positions):
        return reference_nemotron3.forward_row(
            params, tokens, config, q_block=QUERY_BLOCK,
            logit_positions=positions)

    def padded(params, tokens, positions):
        k = len(positions)
        logits, chosen = fwd(
            params, np.pad(tokens, (0, width - len(tokens))),
            np.pad(positions, (0, count - k), mode="edge"))
        return logits[:k], chosen

    return padded


def direct_primes(lfm2_runner, check: dict, seed: int, vocab: int, rows: int,
                  slots: int):
    """``runners/serve_lfm2.py:direct_primes`` (the long rows, then one
    prime a slot: the first ``rows`` shorter, the first of the rest a prime
    number long), with the first shorter ones 1, 2 and 3 tokens: all under
    the convolution's four taps, so part of the tail they leave is
    zeros."""
    first, second = lfm2_runner.direct_primes(check, seed, vocab, rows, slots)
    rng = traffic.rng_for(seed, "direct-shortest")
    second = list(second)
    for i, n in enumerate(SHORTEST[:rows]):
        second[i] = rng.integers(1, vocab, n).astype(np.int32)
    return list(first), second


def direct_reading(got, want, got_sets, want_sets, groups: dict,
                   check: dict) -> dict:
    """``got`` / ``want (N, V)``: the logits of N compared decode steps and
    the reference's; ``*_sets (N, expert layers, k)`` the routers' choices
    for those tokens; ``groups`` names lists of rows.  A row's distance is
    the RMS of its V logit differences, WHATEVER ITS ROUTING.  EVERY ROW is
    held to ``row_rms_limit`` by itself, so that one slot with a stale carry
    or tail fails; ALL ROWS TOGETHER are held to ``rms_limit``, which a
    lower precision fails; and the share of (token, expert layer, chosen
    expert) assignments whose expert is not among the reference's k for
    that token and layer to ``assignments_limit``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    row_rms = np.sqrt(np.mean((got - want) ** 2, axis=-1))
    strangers = ~np.any(
        got_sets[..., :, None] == want_sets[..., None, :], axis=-1)
    share = float(strangers.mean())
    sets_differ = float(np.any(strangers, axis=-1).mean())
    rms = float(np.sqrt(np.mean(row_rms ** 2)))
    return {"ok": bool(row_rms.max() <= check["row_rms_limit"]
                       and rms <= check["rms_limit"]
                       and share <= check["assignments_limit"]),
            "row_rms_max": {k: float(row_rms[v].max())
                            for k, v in groups.items()},
            "row_rms_median": float(np.median(row_rms)),
            "rms": rms,
            "logit_std": float(want.std(axis=-1).mean()),
            "rows": len(row_rms), "assignments": int(strangers.size),
            "assignments_differ_share": share,
            "sets_differ_share": sets_differ}


def direct_check(engine, params, model_config, policy, workload: dict,
                 seed: int, make, reference, lfm2_runner) -> dict:
    """THE ENGINE'S OWN PROGRAMS — the admission program of each bucket and
    the chunk program, the compiled ones the window times, over the
    engine's own state and with the arguments its host code builds
    (``_prefill_args``) — then the family's decode step over the state
    they left, for its logits; ``runners/serve_lfm2.py:direct_check`` has
    the procedure step by step.  The engine's host side (its queue, its
    bookings, its histograms) sees nothing of it, and its state is made
    anew afterwards."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import nemotron_h

    check = workload["correct"]["direct"]
    rows, slots = engine.admit_rows, engine.num_slots
    # nobody finishes before the last compared step
    new = (check["chunks"] + 1) * engine.chunk_size + 2
    first, second = direct_primes(lfm2_runner, check, seed,
                                  model_config.vocab_size, rows, slots)

    def settle():
        """Two states live at a time, as in the window (the programs do
        not donate theirs): what was dispatched is done before the next
        program is."""
        jax.block_until_ready(engine.state["pos"])

    def admit(primes, into):
        """One run of the admission program: ``primes`` into slots
        ``into``, row by row."""
        p_pad = engine.family.bucket(max(map(len, primes)), engine.max_len)
        src = np.zeros((slots,), np.int32)
        mask = np.zeros((slots,), bool)
        src[into], mask[into] = np.arange(len(into)), True
        requests = [make({"uid": -1, "prime": p, "max_new": new,
                          "seed": seed + int(s)}, 0.0)
                    for p, s in zip(primes, into)]
        settle()
        engine.state = engine._admit_call(
            p_pad, src, mask, *engine._prefill_args(rows, requests, p_pad))

    @jax.jit
    def peek(params, state):
        """The logits and choices of the step the chunk program would
        take next, of every slot; nothing is written."""
        pos = state["pos"]
        tok = jnp.take_along_axis(state["seq"], pos[:, None], axis=1)[:, 0]
        live = state["active"] & ~state["done"]
        logits, _, _, chosen = nemotron_h.decode_step(
            params, tok, pos, state["caches"], live, model_config, policy,
            with_choices=True)
        return logits, chosen, state["seq"], pos, live

    at = lfm2_runner.compared_slots(check, rows, slots)
    seen = []
    try:
        admit(first, np.arange(rows))
        settle()
        engine.state = engine._chunk_call()
        engine._deactivate(range(rows))         # as a harvest frees them
        for run in range(0, slots, rows):
            admit(second[run:run + rows], np.arange(run, run + rows))
        for chunks in (0, check["chunks"]):
            for _ in range(chunks):
                settle()
                engine.state = engine._chunk_call()
            settle()
            logits, chosen, seq, pos, live = peek(params, engine.state)
            seen.append((np.asarray(logits[at]),
                         np.asarray(chosen[:, at]).swapaxes(0, 1),
                         np.asarray(seq)[at], np.asarray(pos)[at]))
            if not np.asarray(live).all():
                return {"ok": False, "why": "a slot was not live at a "
                        f"compared step: {np.flatnonzero(~np.asarray(live))}"}
    finally:
        engine.state = None
        engine.state = engine._init_state()

    # the later step's row begins with the earlier one's: one call a slot
    (_, _, _, pos0), (_, _, seq, pos1) = seen
    want, want_sets = [], []
    for i in range(len(at)):
        where = np.asarray([pos0[i], pos1[i]])
        with jax.default_matmul_precision("highest"):
            logits, sets = reference(params, seq[i, :pos1[i] + 1], where)
        want.append(np.asarray(logits))
        want_sets.append(np.asarray(sets)[:, where].swapaxes(0, 1))
    # (slots, 2, ..) -> the earlier step's rows, then the later one's
    want = np.stack(want).swapaxes(0, 1).reshape(2 * len(at), -1)
    want_sets = np.stack(want_sets).swapaxes(0, 1).reshape(
        (2 * len(at),) + want_sets[0].shape[1:])
    reading = direct_reading(
        np.concatenate([s[0] for s in seen]), want,
        np.concatenate([s[1] for s in seen]), want_sets,
        lfm2_runner.direct_groups(rows, len(at)), check)
    return {**reading, "slots": at.tolist(),
            "primes": pos0.tolist(),
            "readmitted_after": [len(p) for p in first]}


def scalar_counters(engine) -> dict:
    """The family's scalar device counters as last fetched (every
    ``engine.step()`` ends on the fetch)."""
    return {k: float(v) for k, v in engine.model_stats.items()
            if np.ndim(v) == 0}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.nemotron_h  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    longcat = load_module("perf/runners/serve_longcat.py")
    lfm2_runner = load_module("perf/runners/serve_lfm2.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    make = longcat.request_factory(workload, model_config.vocab_size)
    # one program for both checks' rows
    reference = reference_for(config, workload, lfm2_runner)
    direct = direct_check(engine, params, model_config, policy, workload,
                          seed, make, reference, lfm2_runner)
    phases.mark("direct check and reference")
    print(f"serve: family vs reference {direct}", flush=True)
    probe = lfm2_runner.probe_check(engine, params, config, workload, make,
                                    seed, serve.PROBE_UID, reference)
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    if arrivals["kind"] != "backlog":
        raise ValueError("runners/serve_nemotron3.py drives backlogs only")
    requests = traffic.serve_requests(
        workload["traffic"], seed, seconds, model_config.vocab_size)
    tmp = tempfile.mkdtemp(prefix="perf-serve-")
    stretch = TraceStretch(os.path.join(tmp, "trace")) if trace else None
    win = workload["window"]
    stretch_ends = []       # the device counters at the stretch's two ends

    def on_tick(now):
        """As ``runners/serve.py``: profile ``trace_seconds`` ending at
        ``trace_end_at`` of the window; the seconds it took are not the
        engine's.  Called between two steps, the device idle and the
        counters current: both ends are exact."""
        if stretch is None or stretch.done:
            return 0.0
        begin = max(0.0, win["trace_end_at"] * seconds - win["trace_seconds"])
        t = time.perf_counter()
        if not stretch.active and now >= begin:
            stretch_ends.append(scalar_counters(engine))
            stretch.start()
        elif stretch.active and now >= begin + win["trace_seconds"]:
            stretch.stop()
            stretch_ends.append(scalar_counters(engine))
        return time.perf_counter() - t

    try:
        now = time.perf_counter()
        for r in requests:
            engine.submit(make(r, now))
        # the ramp counts from the backlog's submission (the engine's count
        # of chunks is its lifetime's: the probes' chunks are in it)
        ramp_to = engine.chunks_run + win["ramp_chunks"]
        while engine.chunks_run < ramp_to:
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
            stretch_ends.append(scalar_counters(engine))
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
    if len(stretch_ends) == 2:
        counters["stretch_counters"] = {
            k: stretch_ends[1][k] - stretch_ends[0].get(k, 0.0)
            for k in stretch_ends[1]}
    print(f"serve: backlog of {len(requests)}, {len(admitted)} admitted, "
          f"{attempted} requests finished and {generated} tokens generated "
          f"in {wall:.3f} s ({len(chunk_steps)} chunks); lowerings "
          f"{engine.lowerings}; counters "
          f"{ {k: np.asarray(v).round(1).tolist() for k, v in engine.model_stats.items()} }"
          f"; stretch {counters.get('stretch_counters')}",
          flush=True)
    return {
        "correct": bool(direct["ok"] and probe["ok"]),
        "attempted": attempted,
        "failed": failed,
        "window_open": window_open,
        "end_to_end": {"serve_tok_s": generated / wall},
        "observations": {"counters": counters, "spans": {}, "trace": reduced},
    }
