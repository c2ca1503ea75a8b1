"""Serving cells of the LFM2 family: the in-process ``ServingEngine`` under a
standing backlog, as ``runners/serve_deepseek_v2.py`` drives DeepSeek-V2's
(same window, same clock, same counters, the same two rules of comparison;
what needs no family is imported from there, from
``runners/serve_longcat.py`` and from ``runners/serve.py``).

Set-up, all outside the window: 3.93 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket and the chunk
program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_lfm2.py`` (float32 ``highest``, no cache, the
convolution as shifted copies of the row, a dense loop over the experts):

* **direct** — the engine's own compiled programs over its own state:
  ``admit_rows`` long requests (the timed admission shape, every row real)
  into the first slots, a chunk, the slots released; then EVERY slot
  admitted, ``admit_rows`` a run, the first run SHORTER rows (two of them
  shorter than the taps) INTO THE SLOTS THE LONG ONES LEFT; the family's
  decode step of all slots over the state that leaves — each slot at its
  first step after admission, every expert touched: a deployment's full
  load —; ``chunks`` runs of the chunk program; the step again.  The logits
  of ``compared_slots`` slots at both steps, EACH ROW's RMS difference from
  the reference's within ``row_rms_limit`` whatever its routing (a tail or
  a key that an admission misplaces or leaves behind reads as far from the
  reference as an unrelated row), the RMS over ALL of them within
  ``rms_limit`` (a lower precision fails it), AND the share of (token,
  expert layer) routings of those steps whose chosen set of 4 differs from
  the reference's within ``routings_limit``;
* **probes** — greedy and sampled requests through the engine, in two
  waves: the two longest primes first, then the two shortest INTO THE SLOTS
  THE FIRST WAVE LEFT (the engine books the lowest free slot); the
  reference's logit of each served token against its best / ``top_k``-th
  best allowed logit (the sibling cells' rule and tolerance), held as the
  share of generated positions over the tolerance within
  ``over_share_limit``.

The control readings of the limits: ``perf/tools/lfm2_lowp.py`` (a lower
precision) and ``perf/tools/lfm2_faults.py`` (a planted fault of the tail).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from perf.lib import loadgen, reference_lfm2, traffic
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
    from progen_tpu.models import lfm2

    enable_compilation_cache()
    phases.mark("imports")
    model_config = lfm2.LFM2Config.from_dict(config)
    policy = lfm2.bf16_policy()
    params = lfm2.init_params(
        model_config, jax.random.key(int(seed) & 0xFFFFFFFF), policy)
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config, policy


def direct_width(workload: dict) -> int:
    """Tokens of the direct check's longest row: its longest prime, the
    token the admission drew and ``chunks`` chunks of steps."""
    check = workload["correct"]["direct"]
    return (check["prime_tokens"][1] + 1
            + check["chunks"] * workload["engine"]["chunk_size"])


def reference_for(config: dict, workload: dict):
    """The reference's full forward of one row: ``(params, tokens (<= T,),
    positions (<= K,)) -> (logits (K, V), choices (expert layers, T, k))``.
    ONE program for the direct check's rows and the probes': every row is
    padded to ``T`` = the longer of the longest probe (prime +
    ``probe_new_tokens``) and :func:`direct_width`, and every list of
    positions to ``K`` = ``probe_new_tokens`` (causality keeps the padding
    out of what is read), so that the compile cache holds one entry for
    it."""
    import jax

    check = workload["correct"]
    count = check["probe_new_tokens"]
    width = max(workload["traffic"]["prime_tokens"]["max"] + count,
                direct_width(workload))

    @jax.jit
    def fwd(params, tokens, positions):
        return reference_lfm2.forward_row(
            params, tokens, config, q_block=QUERY_BLOCK,
            logit_positions=positions)

    def padded(params, tokens, positions):
        k = len(positions)
        logits, chosen = fwd(
            params, np.pad(tokens, (0, width - len(tokens))),
            np.pad(positions, (0, count - k), mode="edge"))
        return logits[:k], chosen

    return padded


def _prime_number(rng, lo: int, hi: int) -> int:
    """A prime number in ``[lo, hi]``, seeded."""
    sieve = np.ones(hi + 1, bool)
    sieve[:2] = False
    for i in range(2, int(hi ** 0.5) + 1):
        if sieve[i]:
            sieve[i * i::i] = False
    return int(rng.choice(np.flatnonzero(sieve[lo:]) + lo))


def direct_primes(check: dict, seed: int, vocab: int, rows: int, slots: int):
    """The direct check's seeded primes, as two lists of token rows.  The
    first ``rows`` (``prime_tokens``: the timed admission shape) go into
    the first slots and leave them.  Then one prime a slot: the first
    ``rows`` are the SHORTER ones that take those slots
    (``readmit_prime_tokens``; the first two 1 and 2 tokens long: shorter
    than the taps, so that part of the tail they leave is zeros), the rest
    ``prime_tokens`` again, the first of them a prime number long (a
    multiple of no tile, chunk or bucket)."""
    rng = traffic.rng_for(seed, "direct")
    lo, hi = check["prime_tokens"]
    first = rng.integers(lo, hi + 1, rows)
    short = rng.integers(check["readmit_prime_tokens"][0],
                         check["readmit_prime_tokens"][1] + 1, rows)
    short[:2] = (1, 2)
    rest = rng.integers(lo, hi + 1, slots - rows)
    rest[0] = _prime_number(rng, lo, hi)
    return tuple([rng.integers(1, vocab, int(n)).astype(np.int32)
                  for n in lengths]
                 for lengths in (first, (*short, *rest)))


def compared_slots(check: dict, rows: int, slots: int) -> np.ndarray:
    """The slots whose logits are compared: every readmitted one, and the
    rest of ``compared_slots`` evenly over the others (the first, whose
    prime is a prime number long, and the last among them)."""
    others = np.linspace(rows, slots - 1,
                         check["compared_slots"] - rows).astype(int)
    return np.concatenate([np.arange(rows), others])


def direct_reading(got, want, got_sets, want_sets, groups: dict,
                   check: dict) -> dict:
    """``got`` / ``want (N, V)``: the logits of N compared decode steps and
    the reference's; ``*_sets (N, expert layers, k)`` the routers' choices
    for those tokens; ``groups`` names lists of rows.  A row's distance is
    the RMS of its V logit differences, whatever its routing.  EVERY ROW is
    held to ``row_rms_limit`` by itself, so that one slot with a wrong
    tail fails (a wrong tail moves two thirds of every short convolution's
    input: the row reads as far from the reference as an unrelated one,
    over 1 at a logit spread of 1; a routing that differs moves a row by a
    few tenths).  ALL ROWS TOGETHER are held to ``rms_limit``, which a
    lower precision fails, and the share of (token, expert layer) routings
    whose chosen set is not the reference's to ``routings_limit``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    row_rms = np.sqrt(np.mean((got - want) ** 2, axis=-1))
    differ = np.any(np.sort(got_sets, -1) != np.sort(want_sets, -1), axis=-1)
    share = float(differ.mean())
    rms = float(np.sqrt(np.mean(row_rms ** 2)))
    return {"ok": bool(row_rms.max() <= check["row_rms_limit"]
                       and rms <= check["rms_limit"]
                       and share <= check["routings_limit"]),
            "row_rms_max": {k: float(row_rms[v].max())
                            for k, v in groups.items()},
            "row_rms_median": float(np.median(row_rms)),
            "rms": rms,
            "logit_std": float(want.std(axis=-1).mean()),
            "rows": len(row_rms), "routings": int(differ.size),
            "routings_differ_share": share}


def direct_groups(rows: int, compared: int) -> dict:
    """Rows of the two compared steps, both over ``compared`` slots of
    which the first ``rows`` are the readmitted ones."""
    return {"admitted": list(range(rows, compared)),
            "readmitted": list(range(rows)),
            "after_chunks": list(range(compared, 2 * compared))}


def direct_check(engine, params, model_config, policy, workload: dict,
                 seed: int, make, reference) -> dict:
    """THE ENGINE'S OWN PROGRAMS — the admission program of each bucket and
    the chunk program, the compiled ones the window times, over the
    engine's own state and with the arguments its host code builds
    (``_prefill_args``) — then the family's decode step over the state
    they left, for its logits: ``admit_rows`` long requests into the first
    slots, a chunk, the slots released; then EVERY slot admitted,
    ``admit_rows`` a run as in the window, the first run the shorter rows
    INTO THE SLOTS THE LONG ONES LEFT; one step of all slots, each at its
    first step after admission (the tail and the keys are the admission's
    alone) and at a deployment's full load; ``chunks`` chunks; one more
    step.  ``compared_slots`` of the slots are held against ``reference``'s
    full forward over prime + generated, both steps from one call.  The
    engine's host side (its queue, its bookings, its histograms) sees
    nothing of it, and its state is made anew afterwards."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import lfm2

    check = workload["correct"]["direct"]
    rows, slots = engine.admit_rows, engine.num_slots
    # nobody finishes before the last compared step
    new = (check["chunks"] + 1) * engine.chunk_size + 2
    first, second = direct_primes(check, seed, model_config.vocab_size,
                                  rows, slots)

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
        logits, _, _, chosen = lfm2.decode_step(
            params, tok, pos, state["caches"], live, model_config, policy,
            with_choices=True)
        return logits, chosen, state["seq"], pos, live

    at = compared_slots(check, rows, slots)
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
        direct_groups(rows, len(at)), check)
    return {**reading, "slots": at.tolist(),
            "primes": pos0.tolist(),
            "readmitted_after": [len(p) for p in first]}


def probe_check(engine, params, config: dict, workload: dict, make,
                seed: int, probe_uid: int, reference) -> dict:
    """``probes`` greedy requests beside as many sampled ones through the
    engine, the longer half first and the shorter half into the slots it
    left; then ``reference``'s full forward over prime + generated
    (``reference_for`` pads every row to one length).
    ``serve_deepseek_v2``'s rule, with this family's reference."""
    import jax

    sibling = load_module("perf/runners/serve_deepseek_v2.py")
    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    reqs, greedy = sibling.probe_requests(workload, seed,
                                          config["vocab_size"], probe_uid)
    served = {}
    # primes rise with the index: the upper half, then the lower half
    for wave in (range(n, 2 * n), range(n)):
        for i in wave:
            extra = {"temperature": 0.0} if i in greedy else {}
            engine.submit(make(reqs[i], time.perf_counter(), **extra))
        served.update({c.uid: c for c in engine.run_until_idle()})
        engine.completions.clear()
    rows = []
    for i, r in enumerate(reqs):
        c = served[r["uid"]]
        if not c.ok or len(c.tokens) != new:
            return {"ok": False, "why": f"probe {i} came back "
                    f"{c.finish_reason} with {len(c.tokens)} tokens"}
        rows.append(np.asarray(list(r["prime"]) + [int(t) for t in c.tokens],
                               np.int32))
    top_k = workload["traffic"]["sampling"]["top_k"]
    gaps = {"greedy": [], "sampled": []}
    for i, r in enumerate(reqs):
        p = len(r["prime"])
        with jax.default_matmul_precision("highest"):
            logits, _ = reference(params, rows[i],
                                  np.arange(p - 1, p - 1 + new))
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
    import progen_tpu.models.lfm2  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    longcat = load_module("perf/runners/serve_longcat.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    make = longcat.request_factory(workload, model_config.vocab_size)
    # one program for both checks' rows
    reference = reference_for(config, workload)
    direct = direct_check(engine, params, model_config, policy, workload,
                          seed, make, reference)
    phases.mark("direct check and reference")
    print(f"serve: family vs reference {direct}", flush=True)
    probe = probe_check(engine, params, config, workload, make, seed,
                        serve.PROBE_UID, reference)
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    if arrivals["kind"] != "backlog":
        raise ValueError("runners/serve_lfm2.py drives backlogs only")
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
