"""The seconds no span owns: the tracer's incident store and the listeners
that feed it (``observe/trace.py``, ``observe/compiles.py``).

An incident is kept with the ring off, the store is bounded, the dump, the
``/tracez`` endpoint and the watchdog's dump carry it; a compilation and a
collector pause reach the registry and the store through JAX's and
CPython's own events, once however often the listeners are installed.  The
engine's side (steps that stand still, compiles inside a step) is in
``test_engine_clocks.py``.
"""

import gc
import json
import os
import subprocess
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from progen_tpu.observe import compiles
from progen_tpu.observe import trace as trace_mod
from progen_tpu.observe.trace import INCIDENT_CAPACITY, Tracer


# ----------------------------------------------------------- (a) the store


@pytest.mark.parametrize("ring_on", [False, True], ids=["ring-off", "ring-on"])
def test_incident_is_kept_whatever_the_ring_does(ring_on):
    tracer = Tracer(enabled=ring_on)
    tracer.add("serve.span", 1.0, 0.5)
    tracer.incident("host.gc", 2.0, 0.25, generation=2, seconds=0.25)
    kept, = tracer.incidents()
    assert kept == {"name": "host.gc", "ts": 2.0, "dur": 0.25,
                    "args": {"generation": 2, "seconds": 0.25}}
    # the ring holds it too when it is on, and nothing when it is off
    assert [s["name"] for s in tracer.ring()] == (
        ["serve.span", "host.gc"] if ring_on else [])


def test_incident_store_is_bounded_and_cleared_with_the_ring():
    tracer = Tracer()
    for i in range(INCIDENT_CAPACITY + 44):
        tracer.incident("xla.compile", float(i), 0.0, program=f"p{i}")
    kept = tracer.incidents()
    assert INCIDENT_CAPACITY == 256 and len(kept) == 256
    assert kept[0]["args"]["program"] == "p44"
    assert kept[-1]["args"]["program"] == f"p{INCIDENT_CAPACITY + 43}"
    tracer.clear()
    assert tracer.incidents() == []


def test_incident_carries_the_step_its_caller_names():
    tracer = Tracer()
    tracer.incident("xla.compile", 0.0, 1.0, program="set-up")
    tracer.incident("serve.slow_step", 0.0, 1.0, step=9)
    assert [i["args"].get("step") for i in tracer.incidents()] == [None, 9]
    # no loop state lives on the tracer: the loops tell the listeners
    assert not hasattr(tracer, "step")


def test_incidents_are_in_the_dump_and_on_tracez(tmp_path):
    from progen_tpu.observe.statusz import StatuszServer

    tracer = Tracer(process="unit")
    tracer.incident("serve.slow_step", 3.0, 0.4, step=5, which="host",
                    excess=0.35)
    assert tracer.dump_obj()["incidents"] == tracer.incidents()
    path = tracer.dump(str(tmp_path / "dump.json"))
    with open(path) as fh:
        dumped = json.load(fh)
    assert dumped["spans"] == [] and dumped["incidents"][0]["args"] == {
        "step": 5, "which": "host", "excess": 0.35}
    server = StatuszServer(role="unit", port=0, providers={"tracer": tracer})
    port = server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/tracez", timeout=10) as resp:
            body = json.loads(resp.read())
    finally:
        server.stop()
    assert body["enabled"] is False and body["spans"] == []
    assert body["incidents"] == dumped["incidents"]


def test_watchdog_dump_holds_the_incidents_with_the_ring_off(
        tmp_path, monkeypatch):
    from progen_tpu.resilience.watchdog import Watchdog

    tracer = Tracer()
    tracer.incident("xla.compile", 1.0, 2.0, program="jit(_admit)", step=3)
    monkeypatch.setattr(trace_mod, "_TRACER", tracer)
    exits = []
    wd = Watchdog(timeout=0.1, out_dir=str(tmp_path), exit_fn=exits.append,
                  poll_interval=0.02)
    wd.start()
    deadline = time.monotonic() + 5.0
    while not exits and time.monotonic() < deadline:
        time.sleep(0.02)
    wd.stop()
    assert exits
    dumps = list(tmp_path.glob("watchdog_trace_*.json"))
    assert dumps and str(dumps[0]) in wd.artifacts
    with open(dumps[0]) as fh:
        held = json.load(fh)["incidents"]
    assert held[0]["name"] == "xla.compile" and held[0]["args"]["step"] == 3


# ------------------------------------------------------ (b) the compiler


def test_a_fresh_jit_is_counted_timed_and_filed_with_its_name(observers):
    registry, tracer = observers

    def incident_probe_fn(x):
        return x * 3 + 1

    x = jnp.arange(5.0)     # its own small programs compile here
    jax.block_until_ready(x)
    before = registry.snapshot()
    filed = len(tracer.incidents())
    compiles.set_step(12)
    jax.block_until_ready(jax.jit(incident_probe_fn)(x))
    compiles.set_step(None)
    after = registry.snapshot()
    assert after["xla.compiles"]["value"] \
        == before["xla.compiles"]["value"] + 1
    assert after["xla.compile_s"]["count"] \
        == before["xla.compile_s"]["count"] + 1
    assert after["xla.compile_s"]["sum"] > before["xla.compile_s"]["sum"]
    new = tracer.incidents()[filed:]
    assert [i["name"] for i in new] == ["xla.compile"]
    args = new[0]["args"]
    assert "incident_probe_fn" in args["program"] and args["step"] == 12
    assert args["seconds"] == new[0]["dur"] > 0
    assert args["cache"] == "off"   # no persistent cache in this process
    # a second call of the same program compiles nothing
    jax.block_until_ready(jax.jit(incident_probe_fn)(x))
    assert registry.snapshot()["xla.compiles"] == after["xla.compiles"]
    assert len(tracer.incidents()) == filed + 1


_CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from progen_tpu.observe import compiles
from progen_tpu.observe.metrics import get_registry
from progen_tpu.observe.trace import get_tracer
compiles.install()

def cached_probe_fn(x):
    return jnp.tanh(x) @ x.T

x = jnp.ones((8, 8))
jax.block_until_ready(jax.jit(cached_probe_fn)(x))
jax.clear_caches()
jax.block_until_ready(jax.jit(cached_probe_fn)(x))
snap = get_registry().snapshot()
# the compiles only: a collector pause filed in between (``host.gc``, under
# load) has no ``program``
mine = [i["args"]["cache"] for i in get_tracer().incidents()
        if i["name"] == "xla.compile"
        and "cached_probe_fn" in i["args"]["program"]]
print(json.dumps({"mine": mine, "hits": snap["xla.cache_hits"]["value"],
                  "misses": snap["xla.cache_misses"]["value"],
                  "compiles": snap["xla.compiles"]["value"]}))
"""


def test_a_second_compile_after_clear_caches_counts_a_cache_hit(tmp_path):
    """In a process of its own: the persistent cache is never turned on
    inside the eight-device pytest process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT,
                          str(tmp_path / "cache")], capture_output=True,
                         text=True, env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen["mine"] == ["miss", "hit"]
    assert seen["hits"] >= 1 and seen["misses"] >= 1
    assert seen["compiles"] >= 2   # a cache load is a backend compile too


# ----------------------------------------------------- (c) the collector


def test_every_collection_is_observed_and_a_long_pause_is_filed(
        observers, monkeypatch):
    registry, tracer = observers
    # the limit is the TEST'S OWN both times, so that a loaded machine's
    # pauses decide nothing: first one no pause reaches, then one every
    # pause does
    monkeypatch.setattr(compiles, "GC_INCIDENT_S", 1e9)
    gc.collect()
    seen = registry.snapshot()["host.gc_pause_s"]
    assert seen["count"] >= 1 and seen["max"] > 0
    # under the limit: observed, not filed
    assert not [i for i in tracer.incidents() if i["name"] == "host.gc"]
    monkeypatch.setattr(compiles, "GC_INCIDENT_S", 0.0)
    compiles.set_step(4)
    gc.collect()
    compiles.set_step(None)
    # the full collection this test asked for, among whatever the
    # interpreter ran by itself meanwhile
    pause = [i for i in tracer.incidents() if i["name"] == "host.gc"
             and i["args"]["generation"] == 2
             and i["args"].get("step") == 4][-1]
    assert pause["args"]["seconds"] == pause["dur"] > 0
    assert registry.snapshot()["host.gc_pause_s"]["count"] > seen["count"]


# ------------------------------------------------ installed once, removable


def test_listeners_are_installed_once_and_removed(observers):
    from jax._src import monitoring

    def mine():
        return (monitoring.get_event_listeners().count(compiles._on_event),
                monitoring.get_event_duration_listeners().count(
                    compiles._on_duration),
                gc.callbacks.count(compiles._on_gc))

    assert compiles.installed() and mine() == (1, 1, 1)
    compiles.install()
    compiles.install()
    assert mine() == (1, 1, 1)
    compiles.uninstall()
    assert not compiles.installed() and mine() == (0, 0, 0)
    compiles.uninstall()    # a second removal is as harmless
    compiles.install()
    assert mine() == (1, 1, 1)


def test_the_step_is_the_thread_s_own(observers):
    """Engines stepping on threads of one process do not stamp each
    other's compiles: the loop iteration is kept per thread."""
    import threading

    registry, tracer = observers
    x = jnp.arange(3.0)
    jax.block_until_ready(x)

    def thread_probe_fn(v):
        return v * 5 - 2

    def other_thread():
        jax.block_until_ready(jax.jit(thread_probe_fn)(x))

    compiles.set_step(21)
    try:
        worker = threading.Thread(target=other_thread)
        worker.start()
        worker.join()
        jax.block_until_ready(jax.jit(lambda v: v * 7 + 3)(x))
    finally:
        compiles.set_step(None)
    steps = {("thread_probe_fn" in i["args"]["program"]):
             i["args"].get("step")
             for i in tracer.incidents() if i["name"] == "xla.compile"}
    assert steps == {True: None, False: 21}
