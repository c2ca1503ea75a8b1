"""The stamp every benchmark record carries, and the TPU requirement.

* :func:`stamp_record` — the one door every benchmark JSON record leaves
  through (``git_sha`` + ``wall_time``).
* :func:`require_tpu` — for entry points whose output only means
  something on the device (``bench.py``'s rate, ``chip_smoke.py``): raise
  unless the process runs on a TPU.  JAX is initialized in
  the calling process and nowhere else — a chip belongs to one process at
  a time, so no child is started to look first — and a run that raises
  exits non-zero with its traceback; nothing is turned into a record.
"""

from __future__ import annotations

from progen_tpu.observe.gitinfo import git_sha

# last wall_time stamped by this process — records within one process are
# guaranteed strictly increasing even if the wall clock steps backwards
# (NTP slew mid-benchmark), so tools/benchdiff.py can order same-sha
# records by wall_time alone
_last_wall = 0.0


def stamp_record(record: dict | None = None, **extra) -> dict:
    """The one door every benchmark JSON record leaves through.

    Merges ``extra`` into a copy of ``record`` and guarantees the
    ``git_sha`` and ``wall_time`` stamps, so a record can always be
    traced back to the code that produced it and ordered against other
    records of the same metric (``tools/benchdiff.py`` picks the latest
    per file by ``wall_time``).  ``wall_time`` is monotonic-safe within
    a process; callers on a traced path pass ``wall_time=...`` captured
    outside the timed region rather than letting this function read the
    clock.  Callers pass their fields and never touch
    :func:`~progen_tpu.observe.gitinfo.git_sha` directly —
    ``tests/test_observe.py`` sweeps the bench sources to keep it that
    way."""
    global _last_wall
    import time

    out = dict(record or {})
    out.update(extra)
    out.setdefault("git_sha", git_sha())
    wall = out.get("wall_time")
    if not isinstance(wall, (int, float)):
        wall = time.time()
    wall = max(float(wall), _last_wall + 1e-3) if _last_wall else float(wall)
    _last_wall = wall
    out["wall_time"] = round(wall, 3)
    return out


def require_tpu():
    """The first device, which must be a TPU; raise naming the platform
    found otherwise.  Initializes JAX in THIS process."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"this entry point reports on the device and needs a TPU; JAX "
            f"found platform {device.platform!r} ({device.device_kind}, "
            f"{len(jax.devices())} device(s)). A CPU run is never "
            f"written under a device's name.")
    return device
