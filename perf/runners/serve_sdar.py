"""Serving cells of the SDAR family: the in-process ``ServingEngine`` under a
standing backlog, generating by DIFFUSION OVER BLOCKS, as
``runners/serve_trinity.py`` drives Trinity's (same window, same clock, same
counters; what needs no family is imported from there, from
``runners/serve_deepseek_v2.py``, ``runners/serve_longcat.py`` and
``runners/serve.py``).

Set-up, all outside the window: 4.36 B bfloat16 weights made on the device
from the seed, the admission program of every prefill bucket and the chunk
program compiled (``aot_warmup``), then two checks against
``perf/lib/reference_sdar.py`` (float32 ``highest``, no cache, the mask as a
``(T, T)`` boolean, a dense loop over all 128 experts).  Both read a whole
trajectory from ONE forward of the reference (``reference_sdar.replay_row``:
the clean row followed by a noisy copy of its generated blocks for each
denoise forward, under the mask that feeds each copy what the sampler fed
that forward), and both go through one compiled program, every row padded
to one length:

* **direct** — the family's own prefill at the timed admission shape
  (``admit_rows`` real rows at the largest bucket, their lengths covering
  every value of ``P mod B``), laid out as all slots' caches, then ``blocks``
  whole blocks of ALL slots through cache and block step (``T`` denoise
  forwards and the commit forward each, the admitted rows live, a seeded
  order of filling): every logit at ``positions`` prefill positions and at
  the B positions of every denoise forward within ``tolerance`` wherever the
  token's routing agreed with the reference's in every layer — at least
  ``agreed_floor`` of the compared positions —, the share of (token, layer)
  routings whose chosen set differs within ``routings_limit``
  (``serve_deepseek_v2.compare_row`` says why they go together), and the
  keys and values the commits wrote within ``keys_tolerance`` of the
  reference's at those positions;
* **probes** — greedy and sampled requests through the engine, which
  reports the denoise forward that kept each token: the reference's logit of
  each kept token AT THE FORWARD THAT KEPT IT against its best / ``top_k``-th
  best allowed logit there, held as the share of positions over the
  tolerance within ``over_share_limit``; and for the greedy probes, that the
  positions a forward kept are those of highest reference confidence
  wherever the reference's confidences at the cut lie more than
  ``order_margin`` (a share of the higher one) apart (``order_wrong_limit``
  of such forwards may differ).

The control readings of the limits: ``perf/tools/sdar_lowp.py``.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import time

import numpy as np

from perf.lib import loadgen, reference_sdar, traffic
from perf.lib.harness import Phases, TraceStretch, load_module

# query rows per score block of the reference's attention
QUERY_BLOCK = 512
# keys a call of the reference returns: the commits of the direct check
KEY_POSITIONS = 8
# the order rule read at other margins too, for the record (the cell's file
# sets the one that is held)
MARGINS = (0.0, 0.05, 0.1, 0.2)


def model_config_of(config: dict, workload: dict):
    """The configuration as the cell runs it: the file's sizes, and how a
    block is generated as the traffic's ``sampling`` says."""
    from progen_tpu.models import sdar

    sampling = workload["traffic"]["sampling"]
    how = {k: sampling[k] for k in ("block_length", "denoising_steps",
                                    "remasking", "confidence_threshold")
           if k in sampling}
    return dataclasses.replace(sdar.SDARConfig.from_dict(config), **how)


def reference_config(config: dict, model_config) -> dict:
    """What the reference reads: the file's keys under the traffic's."""
    return {**config, **{k: getattr(model_config, k) for k in (
        "block_length", "mask_token_id", "denoising_steps", "remasking",
        "confidence_threshold")}}


def build_engine(workload: dict, config: dict, seed: int,
                 phases: Phases | None = None):
    """The engine as the cell runs it, warmed for the cell's shapes only."""
    phases = phases or Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import sdar

    enable_compilation_cache()
    phases.mark("imports")
    model_config = model_config_of(config, workload)
    policy = sdar.bf16_policy()
    params = sdar.init_params(
        model_config, jax.random.key(int(seed) & 0xFFFFFFFF), policy)
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(model_config, params, policy=policy,
                           **workload["engine"])
    phases.mark("engine state")
    engine.aot_warmup(max_prime=workload["traffic"]["prime_tokens"]["max"])
    phases.mark("programs (compile or cache)")
    return engine, params, model_config, policy


def reference_width(workload: dict, model_config) -> tuple:
    """``(T, K)`` of the one compiled reference: the longest replay row
    (the longest prime and the probes' new tokens, and a noisy copy of the
    new blocks a denoise forward) and the most logit positions a call
    reads (every masked position of every forward of a probe, or a row of
    the direct check's)."""
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW

    check = workload["correct"]
    direct = check["direct"]
    b, steps = model_config.block_length, model_config.denoising_steps
    new = check["probe_new_tokens"]
    width = (workload["traffic"]["prime_tokens"]["max"] + new
             + steps * (new + b))
    rows = max(1, workload["engine"]["num_slots"] // SLOTS_PER_ADMIT_ROW)
    return -(-width // 128) * 128, max(
        steps * new,
        direct["positions"] // rows + direct["blocks"] * steps * b)


def reference_for(ref_config: dict, workload: dict, model_config):
    """The reference's forward of one replay row: ``(params, replay row,
    logit positions (<= K,), key positions (<= J,)) -> (logits, choices
    (layers, T, k), keys (layers, 2, J, KV, d))``, ONE program for the
    direct check's rows and the probes' (``reference_width``)."""
    import jax

    width, count = reference_width(workload, model_config)

    @jax.jit
    def fwd(params, tokens, positions, allowed, at, keys_at):
        return reference_sdar.forward_row(
            params, tokens, ref_config, q_block=QUERY_BLOCK,
            logit_positions=at, positions=positions, allowed=allowed,
            key_positions=keys_at)

    def padded(params, row, at, keys_at=()):
        tokens, positions, allowed = row
        k = len(at)
        keys_at = np.asarray(list(keys_at) + [0] * (
            KEY_POSITIONS - len(keys_at)), np.int32)
        with jax.default_matmul_precision("highest"):
            logits, chosen, keys = fwd(
                params, tokens, positions, allowed,
                np.pad(np.asarray(at, np.int32), (0, count - k),
                       mode="edge"), keys_at)
        return logits[:k], chosen, keys

    padded.width = width
    return padded


def direct_rows(check: dict, seed: int, model_config, rows: int):
    """The direct check's seeded rows: per row the prime's length (every
    value of ``P mod B`` among them, none a multiple of 128, the first over
    half the bucket so that the admission is the largest one), the tokens
    of the prime and of ``blocks`` whole blocks after its whole blocks, and
    for each of those the denoise forward that fills it (-1 a prime token):
    a seeded order, the static rule's counts a forward."""
    rng = traffic.rng_for(seed, "direct")
    b, steps = model_config.block_length, model_config.denoising_steps
    lo, hi = check["prime_tokens"]
    counts = reference_sdar.transfer_counts(b, steps)
    out = []
    for i in range(rows):
        low = hi // 2 + 1 if i == 0 else lo
        n = int(rng.integers(low // b, hi // b)) * b + i % b
        if n % 128 == 0:
            n += b
        whole = n // b * b
        end = whole + check["blocks"] * b
        tokens = rng.integers(1, model_config.mask_token_id, end)
        fills = np.full(end, -1)
        for p0 in range(whole, end, b):
            masked = [p for p in range(p0, p0 + b) if p >= n]
            order = rng.permutation(masked)
            step = 0
            while len(order):
                fills[order[:counts[step]]] = step
                order, step = order[counts[step]:], step + 1
        out.append((n, tokens.astype(np.int32), fills))
    return out


def row_reading(compare_row, got, got_sets, got_keys, want, want_sets,
                want_keys, index, keys_at) -> dict:
    """``compare_row``'s readings of one replay row (logits at the row's
    indices ``index``, choices at every index) and ``keys``: the largest
    difference of a key or value ``(layers, 2, J, KV, d)`` at those of the
    indices ``keys_at (J,)`` whose token's routing agreed in every layer (a
    routing that differs moves the layers above it, keys among them)."""
    reading = compare_row(got, got_sets, want, want_sets, index)
    wrong = np.any(np.sort(got_sets, -1) != np.sort(want_sets, -1), axis=-1)
    agreed = ~wrong.any(axis=0)[keys_at]
    diff = np.abs(np.asarray(got_keys, np.float32)
                  - np.asarray(want_keys, np.float32))[:, :, agreed]
    reading["keys"] = float(diff.max()) if agreed.any() else 0.0
    return reading


def direct_check(engine, params, model_config, policy, ref_config: dict,
                 workload: dict, seed: int, reference=None) -> dict:
    """Prefill ``admit_rows`` real rows at the largest bucket, lay their
    keys out as the slots' caches, and run ``blocks`` whole blocks of every
    slot through the block step (each denoise forward, then the commit);
    compare logits, routings and the committed keys with the reference's
    one forward of each row's replay."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.models import sdar

    check = workload["correct"]["direct"]
    rows, slots = engine.admit_rows, engine.num_slots
    b, steps = model_config.block_length, model_config.denoising_steps
    mask_id = model_config.mask_token_id
    picked = direct_rows(check, seed, model_config, rows)
    lengths = np.asarray([n for n, _, _ in picked], np.int32)
    whole = lengths // b * b
    p_pad = engine.family.bucket(int(lengths.max()), engine.max_len)
    padded = np.zeros((rows, p_pad), np.int32)
    for i, (n, tokens, _) in enumerate(picked):
        padded[i, :n] = tokens[:n]
    k = check["positions"] // rows
    at = np.stack([np.linspace(0, w - 1, k).astype(np.int32) for w in whole])

    def prefill(params, padded, lengths, positions):
        logits, per_token, _, chosen = sdar.prefill(
            params, padded, lengths, model_config, policy,
            logit_positions=positions, with_choices=True)
        mine = sdar.caches_from(per_token, lengths, model_config,
                                engine.max_len)
        caches = jax.tree.map(
            lambda a: jnp.zeros((slots,) + a.shape[1:], a.dtype)
            .at[:rows].set(a), mine)
        return logits, chosen, caches

    logits, chosen, caches = jax.jit(prefill)(params, padded, lengths, at)
    logits, chosen = np.asarray(logits), np.asarray(chosen)
    live = np.arange(slots) < rows

    def one(params, tok, pos0, caches, live, commit):
        out, caches, _, sets = sdar.block_step(
            params, tok, pos0, caches, live, commit, model_config, policy,
            with_choices=True)
        return out[:rows], caches, sets[:, :rows]

    step = jax.jit(one, donate_argnums=(3,))
    forwards = {}                       # (block, forward) -> logits, sets
    for j in range(check["blocks"]):
        pos0 = np.zeros((slots,), np.int32)
        pos0[:rows] = whole + j * b
        for s in range(steps + 1):      # forward ``steps`` is the commit
            tok = np.full((slots, b), mask_id, np.int32)
            for i, (_, tokens, fills) in enumerate(picked):
                p0 = pos0[i]
                tok[i] = np.where(fills[p0:p0 + b] < s, tokens[p0:p0 + b],
                                  mask_id)
            out, caches, sets = step(params, tok, pos0, caches, live,
                                     live & (s == steps))
            forwards[j, s] = np.asarray(out), np.asarray(sets)
    written = {name: (np.asarray(c["k"][:rows]), np.asarray(c["v"][:rows]))
               for name, c in caches.items()}
    del caches

    compare_row = load_module("perf/runners/serve_deepseek_v2.py").compare_row
    reference = reference or reference_for(ref_config, workload, model_config)
    worst = {"agreed": 0.0, "all": 0.0, "denoise_agreed": 0.0, "keys": 0.0}
    square, count, differ, routings, agreed_at, compared = 0.0, 0, 0, 0, 0, 0
    for i, (n, tokens, fills) in enumerate(picked):
        w, end = int(whole[i]), len(tokens)
        span = end - w
        row = reference_sdar.replay_row(tokens[:n], tokens[n:], fills[n:],
                                        ref_config, steps, reference.width)
        noisy = [end + s * span + j * b + np.arange(b)
                 for j in range(check["blocks"]) for s in range(steps)]
        index = np.concatenate([at[i]] + noisy)
        want, want_sets, want_keys = reference(
            params, row[:3], index, np.arange(w, end))
        want, want_sets = np.asarray(want), np.asarray(want_sets)
        got = np.concatenate([logits[i]] + [
            forwards[j, s][0][i] for j in range(check["blocks"])
            for s in range(steps)])
        # the program's choices laid out as the replay row is: the prefill,
        # the commit forwards (the clean blocks), then each denoise forward
        total = end + steps * span
        got_sets = np.zeros((chosen.shape[0], total, chosen.shape[-1]),
                            chosen.dtype)
        got_sets[:, :w] = chosen[:, i, :w]
        for j in range(check["blocks"]):
            got_sets[:, w + j * b: w + (j + 1) * b] = forwards[j, steps][1][
                :, i]
            for s in range(steps):
                lo = end + s * span + j * b
                got_sets[:, lo: lo + b] = forwards[j, s][1][:, i]
        # keys and values of the committed blocks, as the reference lays
        # them out: (layers, 2, J, KV, d) from the cache's (KV, rows, d)
        got_keys = np.stack([np.stack([
            cache[i, :, w:end].astype(np.float32).transpose(1, 0, 2)
            for cache in pair]) for pair in written.values()])
        reading = row_reading(
            compare_row, got, got_sets, got_keys, want,
            want_sets[:, :total], np.asarray(want_keys)[:, :, :end - w],
            index, np.arange(w, end))
        tail = compare_row(got[k:], got_sets, want[k:],
                           want_sets[:, :total], index[k:])
        worst["denoise_agreed"] = max(worst["denoise_agreed"],
                                      tail["worst"]["agreed"])
        for key in ("agreed", "all"):
            worst[key] = max(worst[key], reading["worst"][key])
        worst["keys"] = max(worst["keys"], reading["keys"])
        square += reading["square"]
        count += got.size
        differ += reading["differ"]
        routings += reading["routings"]
        agreed_at += reading["agreed_positions"]
        compared += len(index)
    share = differ / routings
    return {"ok": (worst["agreed"] <= check["tolerance"]
                   and share <= check["routings_limit"]
                   and agreed_at >= check["agreed_floor"] * compared
                   and worst["keys"] <= check["keys_tolerance"]),
            "worst": worst, "rms": float(np.sqrt(square / count)),
            "primes": lengths.tolist(), "positions": compared,
            "agreed_positions": agreed_at,
            "routings": routings, "routings_differ_share": share}


def order_reading(conf, kept, count):
    """One denoise forward's masked positions: ``conf`` the reference's
    confidences there, ``kept`` which of them the program kept, ``count``
    how many the rule takes.  ``None`` where the forward had no choice, else
    ``(the gap between the reference's count-th and next confidence, as a
    share of the count-th; whether the kept are NOT the reference's count
    most confident)``."""
    if len(conf) <= count:
        return None
    order = np.argsort(-conf, kind="stable")
    gap = (conf[order[count - 1]] - conf[order[count]]) / conf[
        order[count - 1]]
    return float(gap), set(order[:count]) != set(np.flatnonzero(kept))


def order_share(readings, margin) -> dict:
    """Of the forwards whose gap is over ``margin``: how many, and the
    share of them that kept other positions than the reference would."""
    held = [wrong for gap, wrong in readings if gap > margin]
    return {"checked": len(held), "wrong": int(sum(held)),
            "share": float(np.mean(held)) if held else 0.0}


def probe_check(engine, params, ref_config: dict, workload: dict, make,
                seed: int, probe_uid: int, model_config,
                reference=None) -> dict:
    """``probes`` greedy requests beside as many sampled ones through the
    engine, each reporting the denoise forward that kept each token; then
    the reference's ONE forward of each trajectory's replay."""
    sibling = load_module("perf/runners/serve_deepseek_v2.py")
    check = workload["correct"]
    n, new = check["probes"], check["probe_new_tokens"]
    b, steps = model_config.block_length, model_config.denoising_steps
    counts = reference_sdar.transfer_counts(b, steps)
    sampling = workload["traffic"]["sampling"]
    top_k = sampling["top_k"]
    reqs, greedy = sibling.probe_requests(workload, seed,
                                          model_config.mask_token_id,
                                          probe_uid)
    for i, r in enumerate(reqs):
        extra = {"temperature": 0.0} if i in greedy else {}
        engine.submit(make(r, time.perf_counter(), record_fill_steps=True,
                           **extra))
    served = {c.uid: c for c in engine.run_until_idle()}
    engine.completions.clear()
    reference = reference or reference_for(ref_config, workload, model_config)
    gaps = {"greedy": [], "sampled": []}
    orders = []
    for i, r in enumerate(reqs):
        c = served[r["uid"]]
        if not c.ok or len(c.tokens) != new or c.fill_steps is None:
            return {"ok": False, "why": f"probe {i} came back "
                    f"{c.finish_reason} with {len(c.tokens)} tokens"}
        p = len(r["prime"])
        fills = np.asarray(c.fill_steps, np.int64)
        tokens, positions, allowed, _ = reference_sdar.replay_row(
            r["prime"], c.tokens, fills, ref_config, steps, reference.width)
        whole = p // b * b
        end = (p + new) // b * b
        span = end - whole
        # every masked position of every forward: position q, filled at
        # forward f, is masked at forwards 0..f
        read = [(q, s) for q in range(p, end) for s in range(fills[q - p] + 1)]
        at = np.asarray([end + s * span + q - whole for q, s in read])
        logits, _, _ = reference(params, (tokens, positions, allowed), at)
        logits = np.array(logits)
        logits[:, [0, model_config.mask_token_id]] = -np.inf
        rows = {qs: j for j, qs in enumerate(read)}
        kept = np.asarray([rows[q, fills[q - p]] for q in range(p, end)])
        tok = np.asarray(c.tokens[:end - p], np.int64)
        kind = "greedy" if i in greedy else "sampled"
        served_logit = logits[kept, tok]
        bar = (logits[kept].max(-1) if i in greedy else np.partition(
            logits[kept], -top_k, axis=-1)[:, -top_k])
        gaps[kind].append(np.maximum(bar - served_logit, 0.0))
        if i not in greedy:
            continue
        # the reference's confidence at a masked position: the largest
        # probability of its top-k allowed logits at temperature 1
        cut = np.partition(logits, -top_k, axis=-1)[:, -top_k][:, None]
        e = np.where(logits >= cut, np.exp(
            logits - logits.max(-1, keepdims=True)), 0.0)
        conf = e.max(-1) / e.sum(-1)
        for p0 in range(whole, end, b):
            for s in range(steps):
                here = [q for q in range(max(p0, p), p0 + b)
                        if fills[q - p] >= s]
                got = order_reading(
                    np.asarray([conf[rows[q, s]] for q in here]),
                    np.asarray([fills[q - p] == s for q in here]), counts[s])
                if got is not None:
                    orders.append(got)
    reading = {k: sibling.gap_reading(np.concatenate(v), check["tolerance"])
               for k, v in gaps.items()}
    order = order_share(orders, check["order_margin"])
    ok = (all(r["over_share"] <= check["over_share_limit"]
              for r in reading.values())
          and order["share"] <= check["order_wrong_limit"])
    return {"ok": ok, **reading, "positions": 2 * n * new,
            "order": order,
            "order_by_margin": {str(m): order_share(orders, m) for m in MARGINS},
            "primes": [len(r["prime"]) for r in reqs]}


def run(*, workload, config, seed, seconds, trace, chips):
    # a program without this family fails here, at once and with no result
    import progen_tpu.models.sdar  # noqa: F401

    serve = load_module("perf/runners/serve.py")
    longcat = load_module("perf/runners/serve_longcat.py")
    phases = Phases()
    engine, params, model_config, policy = build_engine(
        workload, config, seed, phases)
    ref_config = reference_config(config, model_config)
    make = longcat.request_factory(workload, model_config.vocab_size)
    reference = reference_for(ref_config, workload, model_config)
    direct = direct_check(engine, params, model_config, policy, ref_config,
                          workload, seed, reference)
    phases.mark("direct check and reference")
    print(f"serve: family vs reference {direct}", flush=True)
    probe = probe_check(engine, params, ref_config, workload, make, seed,
                        serve.PROBE_UID, model_config, reference)
    phases.mark("probes and reference")
    print(f"serve: probes vs reference {probe}", flush=True)

    arrivals = workload["traffic"]["arrivals"]
    if arrivals["kind"] != "backlog":
        raise ValueError("runners/serve_sdar.py drives backlogs only")
    requests = traffic.serve_requests(
        workload["traffic"], seed, seconds, model_config.mask_token_id)
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
        # counted from the backlog's first chunk (the probes ran chunks of
        # their own): the window opens on slots already filled
        ramp_end = engine.chunks_run + win["ramp_chunks"]
        while engine.chunks_run < ramp_end:
            engine.step()
        engine.completions.clear()
        # committed tokens the requests asked for: ``pos`` stands on the
        # newest of them (never a masked position, a position past ``stop``
        # or after an end-of-sequence token, or a draw that was not kept)
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
          f"{attempted} requests finished and {generated} tokens committed "
          f"in {wall:.3f} s ({len(chunk_steps)} chunks, the last three ending "
          f"at {[round(e, 2) for _, e, _, _, _ in rec.steps[-3:]]}: the window "
          f"closes at the first step's end past {seconds:g} s); lowerings "
          f"{engine.program_lowerings}; counters "
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
