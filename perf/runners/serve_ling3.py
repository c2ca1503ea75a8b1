"""Serving cells of the Ling-3.0-flash family (``bailing_hybrid``): the
in-process ``ServingEngine`` under a standing backlog, as
``runners/serve_qwen3next.py`` drives Qwen3-Next's (same traffic, same
window, same clock, the same two rules of comparison; what needs no family is
imported from ``runners/serve_mimo.py``, ``runners/serve_nemotron3.py``,
``runners/serve_lfm2.py``, ``runners/serve_longcat.py`` and
``runners/serve.py``).

Set-up, all outside the window: 4.35 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket (512 .. 16,384,
four rows a run) and the chunk program compiled (``aot_warmup``), then two
checks against ``perf/lib/reference_ling3.py`` (float32 ``highest``, no
cache, the channel-decay delta rule TOKEN BY TOKEN, latent attention
unabsorbed under a dense mask, the router's groups a plain loop, a dense loop
over the held experts):

* **direct** — the engine's own compiled programs over its own state,
  ``admit_rows`` prompts an admission as in the window: ``long_rows``
  requests of 8,000 and more tokens into the first slots, a chunk, the slots
  released; then EVERY slot admitted — primes of ``readmit_prime_tokens`` (1,
  15, 16, 17, 63, 64, 65: a block's and a chunk's edges; and a prime number,
  a multiple of no block, chunk, tile or bucket) INTO THE SLOTS THE LONG
  ONES LEFT, whose carries, tails and latent rows still hold the long
  requests'; one row past 16,000 tokens (its run is the timed 4 x 16,384
  admission); the rest over the cell's range —; the family's decode step of
  all slots over the state that leaves, each slot at its first step after
  admission; ``chunks`` runs of the chunk program; the step again.  The
  logits of the ``compared_slots`` at both steps against the reference's
  full forward over prime + generated, ROW BY ROW, by
  ``runners/serve_nemotron3.py:direct_reading``'s three measures: every
  row's RMS difference within ``row_rms_limit`` WHATEVER ITS ROUTING (a
  stale or misplaced carry, tail or latent row reads as far from the
  reference as an unrelated row), the RMS over all rows within
  ``rms_limit``, and the share of the steps' (token, layer, chosen expert)
  assignments whose expert is not among the reference's eight within
  ``assignments_limit``;
* **probes** — a greedy and a sampled request through the engine, the longer
  first and the shorter into the slot it left; the reference's logit of each
  served token against its best / ``top_k``-th best allowed logit (the
  sibling cells' rule and tolerance), held as the share of generated
  positions over the tolerance within ``over_share_limit``.

The reference is TWO programs, by the row's length (``reference_for``): the
short rows' and one as wide as the longest prompt with its continuation.

The control readings of the limits: ``perf/tools/ling3_lowp.py``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from perf.lib import loadgen, reference_ling3, traffic
from perf.lib.harness import Phases, TraceStretch, load_module

# query rows per score block of the reference's attention: (32 heads, 128,
# 16,512 keys) float32 is 0.27 GB
QUERY_BLOCK = 128
SHORT_WIDTH = 1152      # the short reference program's positions


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import bailing_hybrid

    enable_compilation_cache()
    phases.mark("imports")
    model_config = bailing_hybrid.BailingHybridConfig.from_dict(config)
    policy = bailing_hybrid.bf16_policy()
    params = bailing_hybrid.init_params(
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
    print(f"serve: lowerings {engine.lowerings}; by program "
          f"{engine.program_lowerings}", flush=True)
    return engine, params, model_config, policy


def reference_for(config: dict, workload: dict, forward_row=None):
    """The reference's full forward of one row: ``(params, tokens (<= T,),
    positions (<= K,)) -> (logits (K, V), choices (layers, T, k))``.  TWO
    programs, chosen by the row's length, padded as
    ``runners/serve_mimo.py:reference_for`` pads them.  ``forward_row``: the
    reference's, or a variant of it (``perf/tools/ling3_lowp.py``)."""
    import jax

    forward_row = forward_row or reference_ling3.forward_row
    count = workload["correct"]["probe_new_tokens"]
    widths = (SHORT_WIDTH, workload["traffic"]["prime_tokens"]["max"] + count)

    @jax.jit
    def fwd(params, tokens, positions):
        return forward_row(params, tokens, config, q_block=QUERY_BLOCK,
                           logit_positions=positions)

    def padded(params, tokens, positions):
        k = len(positions)
        width = next(w for w in widths if len(tokens) <= w)
        logits, chosen = fwd(
            params, np.pad(tokens, (0, width - len(tokens))),
            np.pad(positions, (0, count - k), mode="edge"))
        return logits[:k], chosen

    return padded


def direct_check(engine, params, model_config, policy, workload: dict,
                 seed: int, make, reference, mimo_runner, reading) -> dict:
    """THE ENGINE'S OWN PROGRAMS — the admission program of each bucket and
    the chunk program, the compiled ones the window times, over the
    engine's own state and with the arguments its host code builds
    (``_prefill_args``) — then the family's decode step over the state
    they left, for its logits; the module docstring has the procedure.  The
    engine's host side (its queue, its bookings, its histograms) sees
    nothing of it, and its state is made anew afterwards."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import bailing_hybrid

    check = workload["correct"]["direct"]
    rows, slots = engine.admit_rows, engine.num_slots
    # nobody finishes before the last compared step
    new = (check["chunks"] + 1) * engine.chunk_size + 2
    first, second = mimo_runner.direct_primes(
        check, workload, seed, model_config.vocab_size, slots)

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

    def admit_all(primes):
        for run in range(0, len(primes), rows):
            into = np.arange(run, min(run + rows, len(primes)))
            admit(primes[run:run + rows], into)

    @jax.jit
    def peek(params, state):
        """The logits and choices of the step the chunk program would
        take next, of every slot; nothing is written."""
        pos = state["pos"]
        tok = jnp.take_along_axis(state["seq"], pos[:, None], axis=1)[:, 0]
        live = state["active"] & ~state["done"]
        logits, _, _, chosen = bailing_hybrid.decode_step(
            params, tok, pos, state["caches"], live, model_config, policy,
            with_choices=True)
        return logits, chosen, state["seq"], pos, live

    at = mimo_runner.compared_slots(check, slots)
    seen = []
    try:
        admit_all(first)
        settle()
        engine.state = engine._chunk_call()
        engine._deactivate(range(len(first)))   # as a harvest frees them
        admit_all(second)
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
    out = reading(
        np.concatenate([s[0] for s in seen]), want,
        np.concatenate([s[1] for s in seen]), want_sets,
        mimo_runner.direct_groups(check, len(at)), check)
    return {**out, "slots": at.tolist(), "primes": pos0.tolist(),
            "readmitted_after": [len(p) for p in first]}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.bailing_hybrid  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    longcat = load_module("perf/runners/serve_longcat.py")
    lfm2_runner = load_module("perf/runners/serve_lfm2.py")
    mimo_runner = load_module("perf/runners/serve_mimo.py")
    nemotron3 = load_module("perf/runners/serve_nemotron3.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    make = longcat.request_factory(workload, model_config.vocab_size)
    reference = reference_for(config, workload)
    direct = direct_check(engine, params, model_config, policy, workload,
                          seed, make, reference, mimo_runner,
                          nemotron3.direct_reading)
    phases.mark("direct check and reference")
    print(f"serve: family vs reference {direct}", flush=True)
    probe = lfm2_runner.probe_check(engine, params, config, workload, make,
                                    seed, serve.PROBE_UID, reference)
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    if arrivals["kind"] != "backlog":
        raise ValueError("runners/serve_ling3.py drives backlogs only")
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
          f"{ {k: np.asarray(v).round(1).tolist() for k, v in engine.model_gauges.items()} }",
          flush=True)
    return {
        "correct": bool(direct["ok"] and probe["ok"]),
        "attempted": attempted,
        "failed": failed,
        "window_open": window_open,
        "end_to_end": {"serve_tok_s": generated / wall},
        "observations": {"counters": counters, "spans": {}, "trace": reduced},
    }
