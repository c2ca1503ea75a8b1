"""dryrun_multichip hardening (ISSUE acceptance d): the parent process must
never initialize a real accelerator backend — it re-execs a CPU child with
the virtual-device flags — and the end-to-end dryrun must complete with no
TPU reachable at all."""

import os
import subprocess
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import __graft_entry__ as ge  # noqa: E402


class _Boom(RuntimeError):
    pass


def _forbid_devices(*a, **kw):
    raise _Boom("parent-side jax.devices() call: this initializes the real "
                "TPU backend, the exact outage round 5's dryrun died on")


def test_parent_never_touches_backend_and_respawns(monkeypatch):
    """The parent path is pure process plumbing: jax.devices() is forbidden
    (patched to raise) and the child env must force the virtual CPU mesh."""
    captured = {}

    def fake_run(cmd, env=None, **kw):
        captured["cmd"] = cmd
        captured["env"] = env
        return types.SimpleNamespace(returncode=0, stdout="ok\n", stderr="")

    monkeypatch.setattr(ge.subprocess, "run", fake_run)
    monkeypatch.setattr(ge.jax, "devices", _forbid_devices)
    monkeypatch.delenv("_PROGEN_TPU_DRYRUN_CHILD", raising=False)
    # simulate a TPU host: its pod-topology hint must not reach the child
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "tpu-host-0")

    ge.dryrun_multichip(8)

    env = captured["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["_PROGEN_TPU_DRYRUN_CHILD"] == "1"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "TPU_WORKER_HOSTNAMES" not in env
    assert captured["cmd"][0] == sys.executable
    assert captured["cmd"][-1] == "8"


def test_parent_surfaces_child_failure(monkeypatch):
    def fake_run(cmd, env=None, **kw):
        return types.SimpleNamespace(returncode=3, stdout="", stderr="boom\n")

    monkeypatch.setattr(ge.subprocess, "run", fake_run)
    monkeypatch.setattr(ge.jax, "devices", _forbid_devices)
    monkeypatch.delenv("_PROGEN_TPU_DRYRUN_CHILD", raising=False)
    with pytest.raises(RuntimeError, match="rc=3"):
        ge.dryrun_multichip(4)


def test_dryrun_multichip_completes_without_tpu():
    """End-to-end: a fresh parent process with NO accelerator reachable
    (JAX_PLATFORMS intentionally unset; this host has no TPU) runs one
    sharded train step on the 8-way virtual mesh. ~10s of real jit."""
    env = dict(os.environ)
    env.pop("_PROGEN_TPU_DRYRUN_CHILD", None)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(ge.__file__),
                                      "__graft_entry__.py"), "8"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip ok" in proc.stdout
    assert "mesh(" in proc.stdout
    # regression (a dryrun once died rc=124 with no output): the child prints per-phase progress so a
    # hang names its phase instead of dying as an opaque rc=124
    for phase in ("provision_devices", "build_mesh",
                  "trace_train_functions", "init_state", "train_step"):
        assert f"dryrun phase={phase} start" in proc.stdout, proc.stdout
        assert f"dryrun phase={phase} ok" in proc.stdout, proc.stdout


def test_phase_watchdog_emits_structured_error(monkeypatch):
    """A phase that outlives its budget must die with one JSON error line
    naming the phase and rc=3 — never a silent outer-timeout kill.  Run in
    a child so the watchdog's os._exit doesn't take pytest down."""
    code = (
        "import os; os.environ['%s']='0.2'\n"
        "import __graft_entry__ as ge, time\n"
        "with ge._phase('stall'):\n"
        "    time.sleep(30)\n" % ge._PHASE_TIMEOUT_ENV
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, cwd=os.path.dirname(ge.__file__),
    )
    assert proc.returncode == 3, (proc.returncode, proc.stdout, proc.stderr)
    assert "dryrun phase=stall start" in proc.stdout
    assert "dryrun phase=stall ok" not in proc.stdout
    err = [ln for ln in proc.stdout.splitlines()
           if ln.startswith('{"dryrun_error"')]
    assert err, proc.stdout
    payload = __import__("json").loads(err[0])
    assert payload == {"dryrun_error": "phase_timeout", "phase": "stall",
                       "budget_s": 0.2}
