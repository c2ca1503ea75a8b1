"""Driving a serving engine from one thread on a fixed schedule.

Open loop (benchmarks/bench_serving.py's ``drive``, with latency timed from
the DUE instant, not the submit instant): submit what is due, step the
engine, sleep when it is idle before the next arrival.  Backlog: everything
is queued before the window; the loop only steps.  The engine is anything
with ``submit``, ``step`` (returning completions with ``uid``, ``tokens``,
``ok``), ``has_work``, ``pending``, ``num_active`` and ``chunks_run``.

``on_tick(now)`` runs between steps and may return seconds it spent on
the benchmark's own business (stopping the profiler): the loop takes them
off its clock, since no chunk is in flight between steps.

The loop's own phases carry profiler annotations (``perf.submit``,
``perf.step``, ``perf.sleep``) so that idle gaps of the device can be
attributed; outside a profiler session they cost nothing measurable.
"""

from __future__ import annotations

import time
from contextlib import nullcontext


def _annotate(name: str):
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except ImportError:  # the arithmetic is testable without JAX
        return nullcontext()


class Recorder:
    """What one drive observed, on the benchmark's clock (seconds from the
    window's opening)."""

    def __init__(self):
        self.submitted: dict = {}
        self.completed: dict = {}   # uid -> (instant, generated tokens, ok)
        self.steps: list = []       # (start, end, chunks run, active, queued)

    def note_step(self, start, end, chunks, active, queued, done):
        self.steps.append((start, end, chunks, active, queued))
        for c in done:
            self.completed[c.uid] = (end, int(len(c.tokens)), bool(c.ok))


def drive_open_loop(engine, requests, make_request, *, seconds: float,
                    drain_seconds: float, on_tick=None) -> Recorder:
    """Offer ``requests`` (dicts with ``uid`` and ``due``) at their due
    instants for ``seconds``, then let the engine drain for at most
    ``drain_seconds``.  ``on_tick(now)`` runs between steps."""
    rec = Recorder()
    n, nxt = len(requests), 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if on_tick is not None:
            t0 += on_tick(now) or 0.0
            now = time.perf_counter() - t0
        if nxt < n and requests[nxt]["due"] <= now:
            with _annotate("perf.submit"):
                while nxt < n and requests[nxt]["due"] <= now:
                    r = requests[nxt]
                    engine.submit(make_request(r, t0 + r["due"]))
                    rec.submitted[r["uid"]] = time.perf_counter() - t0
                    nxt += 1
        if now > seconds + drain_seconds:
            break
        if not engine.has_work:
            if nxt >= n:
                break
            with _annotate("perf.sleep"):
                time.sleep(max(0.0, requests[nxt]["due"]
                               - (time.perf_counter() - t0)))
            continue
        before = engine.chunks_run
        start = time.perf_counter() - t0
        with _annotate("perf.step"):
            done = engine.step()
        end = time.perf_counter() - t0
        rec.note_step(start, end, engine.chunks_run - before,
                      engine.num_active, engine.pending, done)
    return rec


def drive_backlog(engine, *, seconds: float, on_tick=None) -> Recorder:
    """Step an engine whose queue already holds the backlog for
    ``seconds``; the caller opened the window."""
    rec = Recorder()
    t0 = time.perf_counter()
    while engine.has_work:
        now = time.perf_counter() - t0
        if on_tick is not None:
            t0 += on_tick(now) or 0.0
            now = time.perf_counter() - t0
        if now >= seconds:
            break
        before = engine.chunks_run
        with _annotate("perf.step"):
            done = engine.step()
        end = time.perf_counter() - t0
        rec.note_step(now, end, engine.chunks_run - before,
                      engine.num_active, engine.pending, done)
    rec.elapsed = time.perf_counter() - t0  # time off the clock excluded
    return rec
