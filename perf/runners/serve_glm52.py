"""Serving cells of the GLM-5.2 family (``glm_moe_dsa``): the in-process
``ServingEngine`` under a standing backlog, as ``runners/serve_dots3.py``
drives dots3's (same window, same clock, same counters, the same rules of
comparison; the direct check's seeded lengths, its compared slots and its
reading of the logits are ``runners/serve_mimo.py``'s own functions, the
reading of the selected sets ``runners/serve_dots3.py``'s, the probes'
``runners/serve_lfm2.py``'s).

Set-up, all outside the window: 3.88 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket (512 .. 16,384)
and the chunk program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_glm52.py`` (float32 ``highest``, no cache, no absorbed
form, the indexers' selections as dense masks, a shared layer under its full
layer's):

* **direct** — the engine's own compiled programs over its own state, one
  prompt an admission as in the window: ``long_rows`` requests of 8,000 and
  more tokens into the first slots, a chunk, the slots released; then EVERY
  slot admitted — primes of ``readmit_prime_tokens`` (2,047, 2,048, 2,049:
  the selector's edges; and a prime number just past it, a multiple of no
  tile, segment or bucket) INTO THE SLOTS THE LONG ONES LEFT, whose latent
  rows (five leaves) and indexer rows (two) still hold the long requests'
  past the short ones' counts; one row past 16,000 tokens; the rest over the
  cell's range —; the family's decode step of all slots over the state that
  leaves, each slot at its first step after admission; ``chunks`` runs of
  the chunk program; the step again.  The logits of the ``compared_slots``
  at both steps against the reference's full forward over prime + generated,
  row by row, under the four limits of ``serve_mimo.direct_reading``; and,
  of the same steps, THE SETS THE TWO INDEXERS SELECTED (one
  ``select_rows`` a full layer a step: the shared layers have none to
  record) against the reference's own, within ``selected_keys_limit``;
* **probes** — a greedy and a sampled request through the engine, the longer
  first and the shorter into the slot it left (the sibling cells' rule).

The reference is TWO programs, by the row's length (``reference_for``): the
readmitted rows' (``SHORT_WIDTH`` positions) and one as wide as the longest
prompt with its continuation, 16,512 positions in blocks of ``QUERY_BLOCK``
query rows, ``HEAD_BLOCK`` heads and ``ROW_BLOCK`` feed-forward rows.

The control readings of the limits: ``perf/tools/glm52_lowp.py``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from perf.lib import loadgen, reference_glm52, traffic
from perf.lib.harness import Phases, TraceStretch, load_module

# query rows per score block of the reference's attention and heads per
# pass — (16 heads, 128, 16,512 keys) float32 is 0.14 GB, the indexer's (32,
# 128, 16,512) 0.27 GB — and rows per block of its feed-forward layers
QUERY_BLOCK = 128
HEAD_BLOCK = 16
ROW_BLOCK = 2048
SHORT_WIDTH = 2432      # the short reference program's positions


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import glm_dsa

    enable_compilation_cache()
    phases.mark("imports")
    model_config = glm_dsa.GLMDSAConfig.from_dict(config)
    policy = glm_dsa.bf16_policy()
    params = glm_dsa.init_params(
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


def long_width(workload: dict) -> int:
    """Positions of the long reference program: the longest prime and a
    probe's continuation (the direct check's longest row ends before it)."""
    return (workload["traffic"]["prime_tokens"]["max"]
            + workload["correct"]["probe_new_tokens"])


def reference_for(config: dict, workload: dict, forward_row=None):
    """The reference's full forward of one row: ``(params, tokens (<= T,),
    positions (<= K,)) -> (logits (K, V), choices (expert layers, T, k),
    selected (full layers, K, T) bool)``.  TWO programs, chosen by the row's
    length: rows up to ``SHORT_WIDTH`` tokens are padded to that, the others
    to :func:`long_width`; every list of positions is padded to ``K`` =
    ``probe_new_tokens`` (causality keeps the padding out of what is read),
    so that the compile cache holds two entries for it.  ``forward_row``:
    the reference's, or a variant of it (``perf/tools/glm52_lowp.py``)."""
    import jax

    forward_row = forward_row or reference_glm52.forward_row
    count = workload["correct"]["probe_new_tokens"]
    widths = (SHORT_WIDTH, long_width(workload))

    @jax.jit
    def fwd(params, tokens, positions):
        return forward_row(
            params, tokens, config, q_block=QUERY_BLOCK,
            logit_positions=positions, row_block=ROW_BLOCK,
            head_block=HEAD_BLOCK)

    def padded(params, tokens, positions):
        k, t = len(positions), len(tokens)
        width = next(w for w in widths if t <= w)
        logits, chosen, selected = fwd(
            params, np.pad(tokens, (0, width - t)),
            np.pad(positions, (0, count - k), mode="edge"))
        return logits[:k], chosen, selected[:, :k, :t]

    return padded


def direct_check(engine, params, model_config, policy, workload: dict,
                 seed: int, make, reference) -> dict:
    """THE ENGINE'S OWN PROGRAMS — the admission program of each bucket and
    the chunk program, the compiled ones the window times, over the
    engine's own state and with the arguments its host code builds
    (``_prefill_args``) — then the family's decode step over the state
    they left, for its logits, its routers' choices and its two indexers'
    selections; the module docstring has the procedure.  The engine's host
    side sees nothing of it, and its state is made anew afterwards."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import glm_dsa
    from progen_tpu.ops import dsa

    dots3 = load_module("perf/runners/serve_dots3.py")
    mimo = load_module("perf/runners/serve_mimo.py")
    check = workload["correct"]["direct"]
    rows, slots = engine.admit_rows, engine.num_slots
    if rows != 1:
        raise ValueError(f"one prompt an admission, not {rows}")
    # nobody finishes before the last compared step
    new = (check["chunks"] + 1) * engine.chunk_size + 2
    first, second = mimo.direct_primes(check, workload, seed,
                                       model_config.vocab_size, slots)

    def settle():
        """Two states live at a time, as in the window (the programs do
        not donate theirs): what was dispatched is done before the next
        program is."""
        jax.block_until_ready(engine.state["pos"])

    def admit(prime, slot):
        """One run of the admission program: ``prime`` into ``slot``."""
        p_pad = engine.family.bucket(len(prime), engine.max_len)
        src = np.zeros((slots,), np.int32)
        mask = np.zeros((slots,), bool)
        mask[slot] = True
        request = make({"uid": -1, "prime": prime, "max_new": new,
                        "seed": seed + int(slot)}, 0.0)
        settle()
        engine.state = engine._admit_call(
            p_pad, src, mask, *engine._prefill_args(rows, [request], p_pad))

    @jax.jit
    def peek(params, state):
        """The logits, choices and selections of the step the chunk program
        would take next, of every slot; nothing is written."""
        pos = state["pos"]
        tok = jnp.take_along_axis(state["seq"], pos[:, None], axis=1)[:, 0]
        live = state["active"] & ~state["done"]
        with dsa.record_selections() as picked:
            logits, _, _, chosen = glm_dsa.decode_step(
                params, tok, pos, state["caches"], live, model_config,
                policy, with_choices=True)
        return logits, chosen, picked, state["seq"], pos, live

    at = mimo.compared_slots(check, slots)
    seen = []
    try:
        for slot, prime in enumerate(first):
            admit(prime, slot)
        settle()
        engine.state = engine._chunk_call()
        engine._deactivate(range(len(first)))   # as a harvest frees them
        for slot, prime in enumerate(second):
            admit(prime, slot)
        for chunks in (0, check["chunks"]):
            for _ in range(chunks):
                settle()
                engine.state = engine._chunk_call()
            settle()
            logits, chosen, picked, seq, pos, live = peek(params,
                                                          engine.state)
            seen.append((np.asarray(logits[at]),
                         np.asarray(chosen[:, at]).swapaxes(0, 1),
                         np.asarray(seq)[at], np.asarray(pos)[at],
                         [(np.asarray(ids)[at], np.asarray(kept)[at])
                          for ids, kept in picked]))
            if not np.asarray(live).all():
                return {"ok": False, "why": "a slot was not live at a "
                        f"compared step: {np.flatnonzero(~np.asarray(live))}"}
    finally:
        engine.state = None
        engine.state = engine._init_state()

    # the later step's row begins with the earlier one's: one call a slot
    (_, _, _, pos0, picked0), (_, _, seq, pos1, picked1) = seen
    want, want_sets, got_selected, want_selected = [], [], [], []
    for i in range(len(at)):
        where = np.asarray([pos0[i], pos1[i]])
        with jax.default_matmul_precision("highest"):
            logits, sets, selected = reference(params, seq[i, :pos1[i] + 1],
                                               where)
        want.append(np.asarray(logits))
        want_sets.append(np.asarray(sets)[:, where].swapaxes(0, 1))
        selected = np.asarray(selected)
        for step, picked in enumerate((picked0, picked1)):
            for layer, (ids, kept) in enumerate(picked):
                got_selected.append((set(ids[i, :kept[i]].tolist()),
                                     int(where[step]) + 1))
                want_selected.append(set(np.flatnonzero(
                    selected[layer, step]).tolist()))
    # (slots, 2, ..) -> the earlier step's rows, then the later one's
    want = np.stack(want).swapaxes(0, 1).reshape(2 * len(at), -1)
    want_sets = np.stack(want_sets).swapaxes(0, 1).reshape(
        (2 * len(at),) + want_sets[0].shape[1:])
    reading = mimo.direct_reading(
        np.concatenate([s[0] for s in seen]), want,
        np.concatenate([s[1] for s in seen]), want_sets,
        mimo.direct_groups(check, len(at)), check)
    selection = dots3.selection_reading(got_selected, want_selected,
                                        check["selected_keys_limit"])
    return {**reading, **selection,
            "ok": bool(reading["ok"] and selection["ok"]),
            "slots": at.tolist(), "primes": pos0.tolist(),
            "readmitted_after": [len(p) for p in first]}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.glm_dsa  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    longcat = load_module("perf/runners/serve_longcat.py")
    lfm2_runner = load_module("perf/runners/serve_lfm2.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    make = longcat.request_factory(workload, model_config.vocab_size)
    reference = reference_for(config, workload)
    direct = direct_check(engine, params, model_config, policy, workload,
                          seed, make, reference)
    phases.mark("direct check and reference")
    print(f"serve: family vs reference {direct}", flush=True)
    probe = lfm2_runner.probe_check(
        engine, params, config, workload, make, seed, serve.PROBE_UID,
        lambda *a: reference(*a)[:2])
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    if arrivals["kind"] != "backlog":
        raise ValueError("runners/serve_glm52.py drives backlogs only")
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
