"""Unit tests for the resilience layer (retry / faults / watchdog).

All pure-stdlib: none of these import jax, so they also pin the layer's
usability from data-prep workers and the graft driver."""

import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from progen_tpu.resilience import faults
from progen_tpu.resilience.retry import (
    AttemptTimeout,
    RetryError,
    RetryPolicy,
    default_classifier,
    retriable,
    retry_call,
)
from progen_tpu.resilience.watchdog import (
    WATCHDOG_EXIT_CODE,
    FlightRecorder,
    Watchdog,
)

FAST = RetryPolicy(max_attempts=4, base_delay=0.001, max_delay=0.002,
                   jitter=0.0, deadline=5.0)


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# retry


def test_backoff_schedule_is_deterministic_and_capped():
    p = RetryPolicy(max_attempts=5, base_delay=1.0, multiplier=2.0,
                    max_delay=3.0, jitter=0.25, seed=7)
    a = list(p.delays())
    b = list(p.delays())
    assert a == b  # seeded: same schedule every time
    assert len(a) == 4  # one delay per RETRY
    for k, d in enumerate(a):
        raw = min(3.0, 1.0 * 2.0 ** k)
        assert raw * 0.75 <= d <= raw * 1.25
    assert list(RetryPolicy(max_attempts=5, seed=8).delays()) != a


def test_classifier_transient_vs_fatal():
    class UnavailableError(Exception):  # tf.errors-style, matched by NAME
        pass

    for exc in (
        ConnectionResetError("boom"),
        TimeoutError("x"),
        AttemptTimeout("x"),
        OSError("disk glitch"),
        RuntimeError("RPC failed: UNAVAILABLE: socket closed"),
        RuntimeError("DEADLINE_EXCEEDED while fetching"),
        Exception("HTTP 503 backend error"),
        UnavailableError("nope"),
    ):
        assert default_classifier(exc), exc
    for exc in (
        FileNotFoundError("gone"),
        PermissionError("denied"),
        NotADirectoryError("x"),
        ValueError("bad config"),
        KeyError("missing"),
        RuntimeError("INVALID_ARGUMENT: shape mismatch"),
    ):
        assert not default_classifier(exc), exc


def test_retry_recovers_from_transient_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("transient")
        return "ok"

    retries = []
    out = retry_call(flaky, policy=FAST,
                     on_retry=lambda a, e, d: retries.append((a, d)))
    assert out == "ok"
    assert len(calls) == 3
    assert [a for a, _ in retries] == [1, 2]


def test_retry_fatal_propagates_immediately():
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("config error")

    with pytest.raises(ValueError):
        retry_call(bad, policy=FAST)
    assert len(calls) == 1  # never retried


def test_retry_exhaustion_raises_retry_error_with_cause():
    def always():
        raise ConnectionResetError("down")

    with pytest.raises(RetryError) as ei:
        retry_call(always, policy=FAST, label="unit")
    assert ei.value.attempts == FAST.max_attempts
    assert isinstance(ei.value.__cause__, ConnectionResetError)
    assert "unit" in str(ei.value)


def test_retry_deadline_cuts_the_loop_short():
    p = RetryPolicy(max_attempts=50, base_delay=0.2, multiplier=1.0,
                    jitter=0.0, deadline=0.3)
    calls = []

    def always():
        calls.append(1)
        raise ConnectionResetError("down")

    t0 = time.monotonic()
    with pytest.raises(RetryError):
        retry_call(always, policy=p)
    assert time.monotonic() - t0 < 2.0
    assert len(calls) < 5  # nowhere near the 50-attempt budget


def test_attempt_timeout_abandons_hung_attempt_and_retries():
    p = RetryPolicy(max_attempts=2, base_delay=0.001, jitter=0.0,
                    attempt_timeout=0.1, deadline=5.0)
    calls = []

    def hangs_once():
        calls.append(1)
        if len(calls) == 1:
            time.sleep(10)  # daemon thread is abandoned, not joined
        return "late but fine"

    assert retry_call(hangs_once, policy=p) == "late but fine"
    assert len(calls) == 2


def test_retriable_decorator():
    calls = []

    @retriable(policy=FAST, label="deco")
    def flaky(x):
        calls.append(x)
        if len(calls) == 1:
            raise ConnectionResetError("once")
        return x * 2

    assert flaky(21) == 42
    assert calls == [21, 21]


def test_retry_policy_from_env(monkeypatch):
    monkeypatch.setenv("T_RETRY_ATTEMPTS", "7")
    monkeypatch.setenv("T_RETRY_BASE_DELAY", "0.125")
    monkeypatch.setenv("T_RETRY_DEADLINE", "9.5")
    p = RetryPolicy.from_env("T_RETRY")
    assert (p.max_attempts, p.base_delay, p.deadline) == (7, 0.125, 9.5)
    # explicit overrides beat env
    assert RetryPolicy.from_env("T_RETRY", max_attempts=2).max_attempts == 2


# ---------------------------------------------------------------------------
# fault injection


def test_inject_is_noop_when_unarmed():
    faults.inject("ckpt.save")  # nothing armed -> no error, no state


def test_parse_plan_and_kinds():
    rules = faults.parse_plan(
        "ckpt.save:io_error:times=2;train.step:preempt:at=3;"
        "data.open:slow:delay=0.5,p=0.25")
    assert [(r.point, r.kind) for r in rules] == [
        ("ckpt.save", "io_error"), ("train.step", "preempt"),
        ("data.open", "slow")]
    assert rules[0].times == 2
    assert rules[1].at == 3
    assert (rules[2].delay, rules[2].p) == (0.5, 0.25)
    with pytest.raises(ValueError, match="unknown kind"):
        faults.parse_plan("x:explode")
    with pytest.raises(ValueError, match="unknown option"):
        faults.parse_plan("x:slow:wat=1")


def test_counted_injection_fires_exactly_n_times():
    inj = faults.FaultInjector("p:io_error:times=2")
    with pytest.raises(faults.InjectedIOError):
        inj.inject("p")
    with pytest.raises(faults.InjectedIOError):
        inj.inject("p")
    inj.inject("p")  # budget spent
    inj.inject("other")  # different point never armed
    assert inj.hits("p") == 3
    assert inj.fired("p") == 2


def test_at_injection_fires_on_kth_hit_only():
    inj = faults.FaultInjector("p:fatal:at=3")
    inj.inject("p")
    inj.inject("p")
    with pytest.raises(faults.InjectedFatal):
        inj.inject("p")
    inj.inject("p")
    assert inj.log == [("p", "fatal", 3)]


def test_unavailable_kind_classifies_transient():
    inj = faults.FaultInjector("p:unavailable")
    with pytest.raises(faults.InjectedUnavailable) as ei:
        inj.inject("p")
    assert default_classifier(ei.value)
    # and the fatal kind must NOT be retried
    with pytest.raises(faults.InjectedFatal) as ei2:
        faults.FaultInjector("q:fatal").inject("q")
    assert not default_classifier(ei2.value)


def test_slow_kind_delays():
    inj = faults.FaultInjector("p:slow:delay=0.05")
    t0 = time.monotonic()
    inj.inject("p")
    assert time.monotonic() - t0 >= 0.05


def test_probabilistic_injection_is_seed_deterministic():
    def outcomes(seed):
        inj = faults.FaultInjector("p:io_error:p=0.5,times=1000", seed=seed)
        out = []
        for _ in range(20):
            try:
                inj.inject("p")
                out.append(0)
            except faults.InjectedIOError:
                out.append(1)
        return out

    assert outcomes(3) == outcomes(3)
    assert 0 < sum(outcomes(3)) < 20  # actually probabilistic
    assert outcomes(3) != outcomes(4)


def test_preempt_kind_sends_sigterm():
    got = []
    prev = signal.signal(signal.SIGTERM, lambda *a: got.append(a))
    try:
        faults.FaultInjector("p:preempt").inject("p")
    finally:
        signal.signal(signal.SIGTERM, prev)
    assert got, "SIGTERM was not delivered"


def test_env_arming_and_reset(monkeypatch):
    monkeypatch.setenv("PROGEN_FAULTS", "p:io_error")
    faults.reset()  # force re-read of the env
    with pytest.raises(faults.InjectedIOError):
        faults.inject("p")
    faults.reset()
    monkeypatch.delenv("PROGEN_FAULTS")
    faults.inject("p")  # disarmed again


def test_configure_overrides_env(monkeypatch):
    monkeypatch.setenv("PROGEN_FAULTS", "p:io_error")
    faults.configure("q:fatal")
    faults.inject("p")  # env plan ignored once configured
    with pytest.raises(faults.InjectedFatal):
        faults.inject("q")


# ---------------------------------------------------------------------------
# watchdog + flight recorder


def test_flight_recorder_ring_bounds_and_dump(tmp_path):
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record("step", step=i)
    snap = rec.snapshot()
    assert [e["step"] for e in snap] == [2, 3, 4]
    assert all(e["kind"] == "step" and "t" in e for e in snap)
    path = rec.dump(str(tmp_path / "flight.json"))
    import json

    data = json.load(open(path))
    assert data["capacity"] == 3
    assert [e["step"] for e in data["events"]] == [2, 3, 4]


def test_watchdog_beats_keep_it_alive(tmp_path):
    exits = []
    wd = Watchdog(timeout=0.3, out_dir=str(tmp_path), exit_fn=exits.append,
                  poll_interval=0.05)
    with wd:
        for _ in range(10):
            time.sleep(0.05)
            wd.beat("still going")
    assert not wd.tripped and not exits


def test_watchdog_trips_within_deadline_and_dumps(tmp_path, monkeypatch):
    from progen_tpu.observe import trace as trace_mod

    # a process tracer with no incident and no ring: the trip writes the
    # stacks and the flight recorder and nothing else (the third artifact,
    # the tracer's dump, has its own test in test_incidents.py)
    monkeypatch.setattr(trace_mod, "_TRACER", trace_mod.Tracer())
    rec = FlightRecorder()
    rec.record("step", step=1, loss=2.5)
    exits = []
    tripped_at = []
    wd = Watchdog(timeout=0.2, out_dir=str(tmp_path), recorder=rec,
                  exit_fn=lambda code: (exits.append(code),
                                        tripped_at.append(time.monotonic())),
                  poll_interval=0.05, label="unit")
    t0 = time.monotonic()
    wd.start()
    deadline = t0 + 5.0
    while not exits and time.monotonic() < deadline:
        time.sleep(0.02)  # NO beats: stall
    wd.stop()
    assert exits == [WATCHDOG_EXIT_CODE]
    assert tripped_at[0] - t0 < 2.0  # well within the 5s test deadline
    stacks = list(tmp_path.glob("watchdog_stacks_*.txt"))
    flights = list(tmp_path.glob("watchdog_flight_*.json"))
    assert stacks and flights
    text = stacks[0].read_text()
    assert "no heartbeat" in text and "MainThread" in text
    import json

    events = json.load(open(flights[0]))["events"]
    assert any(e.get("loss") == 2.5 for e in events)
    assert wd.artifacts == [str(stacks[0]), str(flights[0])]


def test_watchdog_paused_section_does_not_trip(tmp_path):
    exits = []
    wd = Watchdog(timeout=0.15, out_dir=str(tmp_path), exit_fn=exits.append,
                  poll_interval=0.05)
    with wd:
        with wd.paused():
            time.sleep(0.4)  # far past timeout, but legitimately slow
        wd.beat()
        time.sleep(0.1)
    assert not wd.tripped and not exits


def test_watchdog_real_exit_code_in_subprocess(tmp_path):
    """The default exit_fn (os._exit) must get rc=42 out of a process whose
    main thread is wedged — the acceptance shape for a hung collective."""
    script = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))})
        from progen_tpu.resilience.watchdog import Watchdog
        wd = Watchdog(timeout=0.2, out_dir={repr(str(tmp_path))},
                      poll_interval=0.05)
        wd.start()
        time.sleep(30)  # wedged "collective"; never beats
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == WATCHDOG_EXIT_CODE, out.stderr
    assert "stalled" in out.stderr
    assert list(tmp_path.glob("watchdog_stacks_*.txt"))


def test_dump_all_stacks_sees_other_threads(tmp_path):
    release = threading.Event()
    t = threading.Thread(target=release.wait, name="stuck-worker",
                         daemon=True)
    t.start()
    try:
        import io

        buf = io.StringIO()
        from progen_tpu.resilience.watchdog import dump_all_stacks

        dump_all_stacks(buf)
        assert "stuck-worker" in buf.getvalue()
    finally:
        release.set()
