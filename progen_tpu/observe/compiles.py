"""What the compiler and the collector did to this process, from their own
events: counters and histograms in the metrics registry, and an incident
in the process tracer for each compilation and each long collector pause.

JAX reports every trace, backend compile and persistent-cache lookup
through ``jax.monitoring``; CPython reports the start and stop of every
collection through ``gc.callbacks``.  ``install()`` listens to both, once
per process however often it is called (the engine's and the trainer's
constructors call it: the loops whose steps it serves), and ``uninstall()``
takes the listeners out again (tests).  Nothing runs between events: a
warmed program pays nothing, a collection about a microsecond.

Feeds (``docs/OBSERVABILITY.md`` §3 has who reads each):

* counters ``xla.compiles`` (backend compiles, cache loads included),
  ``xla.cache_hits``, ``xla.cache_misses`` (JAX's own: a program compiled
  and WRITTEN to the persistent cache — one below the cache's thresholds
  is neither);
* histograms ``xla.compile_s`` (every backend compile or cache load: its
  ``sum`` is the seconds) and ``host.gc_pause_s`` (every collection);
* incidents ``xla.compile`` (``program``, ``seconds``, ``cache``: ``hit``,
  ``miss``, or ``off`` for a program the cache was not asked about or did
  not keep) and ``host.gc`` (``generation``, ``seconds``) for a pause of
  ``GC_INCIDENT_S`` or more.  Each carries the ``step`` of the loop
  iteration its thread was in, where the loop said so (``set_step``).
"""

from __future__ import annotations

import gc
import threading
import time

from progen_tpu.observe.metrics import get_registry
from progen_tpu.observe.trace import get_tracer

__all__ = ["install", "uninstall", "installed", "set_step"]

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

# a collection shorter than this is the interpreter's ordinary business
# (young generations take tens of microseconds); 10 ms is a third of the
# shortest engine step the cells run (25 ms) and what a full pass over a
# heap of a few hundred thousand objects starts to cost
GC_INCIDENT_S = 0.010

_installed = False
# of the thread an event arrives on: ``cache``, the cache's verdict on the
# compile in progress (JAX reports the hit or the miss INSIDE the
# backend-compile event, on the compiling thread), and ``step``, the loop
# iteration the thread is in (``set_step``).  Per thread, so engines
# stepping on threads of one process do not stamp each other's incidents
_thread = threading.local()
_gc_start = 0.0


def set_step(step: int | None) -> None:
    """The calling thread enters loop iteration ``step`` (an engine's
    ``step()``, a trainer's step), or leaves it (``None``): compiles and
    collector pauses on this thread carry the number meanwhile."""
    _thread.step = step


def _step_arg() -> dict:
    step = getattr(_thread, "step", None)
    return {} if step is None else {"step": step}


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT:
        get_registry().counter("xla.cache_hits").inc()
        _thread.cache = "hit"
    elif event == CACHE_MISS:
        get_registry().counter("xla.cache_misses").inc()
        _thread.cache = "miss"


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == BACKEND_COMPILE:
        registry = get_registry()
        registry.counter("xla.compiles").inc()
        registry.histogram("xla.compile_s").observe(duration)
        cache = getattr(_thread, "cache", "off")
        _thread.cache = "off"
        get_tracer().incident(
            "xla.compile", time.perf_counter() - duration, duration,
            program=kw.get("fun_name"), seconds=duration, cache=cache,
            **_step_arg())


def _on_gc(phase: str, info: dict) -> None:
    global _gc_start
    if phase == "start":
        _gc_start = time.perf_counter()
        return
    pause = time.perf_counter() - _gc_start
    get_registry().histogram("host.gc_pause_s").observe(pause)
    if pause >= GC_INCIDENT_S:
        get_tracer().incident("host.gc", _gc_start, pause,
                              generation=info.get("generation"),
                              seconds=pause, **_step_arg())


def installed() -> bool:
    return _installed


def install() -> None:
    """Start listening; a second call does nothing."""
    global _installed
    if _installed:
        return
    from jax import monitoring

    # there from the start, so that a reader tells "none" from "a program
    # that does not count"
    registry = get_registry()
    for name in ("xla.compiles", "xla.cache_hits", "xla.cache_misses"):
        registry.counter(name)
    registry.histogram("xla.compile_s")
    registry.histogram("host.gc_pause_s")
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)
    _installed = True


def uninstall() -> None:
    """Stop listening (tests; a serving process never does)."""
    global _installed
    if not _installed:
        return
    from jax import monitoring

    monitoring.unregister_event_listener(_on_event)
    monitoring.unregister_event_duration_listener(_on_duration)
    gc.callbacks.remove(_on_gc)
    _installed = False
