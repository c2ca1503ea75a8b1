"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time as the UNION of device-op intervals,
the idle share, the operations that took most device time, and the longest
idle gaps, each attributed to the host span that covered most of it.

Read with ``jax.profiler.ProfileData`` alone.  The functions below take
plain ``(name, start_ns, end_ns)`` tuples, so the arithmetic is tested
without a trace (perf/tests) and on the recorded one in perf/tests/data.

A TPU trace has one plane per chip, ``/device:TPU:<i>``; the line ``XLA
Ops`` holds one event per executed HLO operation (fusions, custom calls,
copies) and ``XLA Modules`` one per program.  Host threads are lines of
the ``/host:CPU`` plane; ``jax.profiler.TraceAnnotation`` spans appear
there under their own names.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "perf.trace_window"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_events(path: str) -> dict:
    """``{"device": {plane: [(name, start, end)]}, "host": [(name, start,
    end)]}`` in nanoseconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            events = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events.extend(
                        (e.name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events)
            device[plane.name] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns))
                    for e in line.events)
    return {"device": device, "host": host}


def parse_op(name: str) -> tuple[str, str, str]:
    """``(family, opcode, result type)`` of a device event's name.  The
    profiler names an XLA op by its HLO text, ``%attn2.5 = (bf16[..]{..},
    ..) custom-call(...)``: the family is the name without its numbering
    (``attn``), the opcode what follows the result type (``custom-call``),
    the type is kept without layouts.  Names in another form come back as
    ``(name, "", "")``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name, "", ""
    depth, end = 0, 0
    if rest.startswith("("):
        for end, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ")
    if end <= 0:
        return name, "", ""
    result = re.sub(r"\{[^{}]*\}", "", rest[:end])
    opcode = rest[end:].strip().split("(", 1)[0]
    family = re.sub(r"[.\d]+$", "", re.sub(r"\.\d+", "", head.lstrip("%")))
    return family or head, opcode, result


def op_key(name: str) -> str:
    """The label under which the breakdown sums an operation: numbering
    removed, so the twelve layers' copies of one fusion add up."""
    family, opcode, result = parse_op(name)
    if not opcode:
        return name[:120]
    return f"{family} [{opcode}] {result}"[:120]


def is_kernel_call(name: str) -> bool:
    """A Pallas (Mosaic) kernel: an HLO ``custom-call``; where the text
    carries a target it must be the TPU custom call."""
    _, opcode, _ = parse_op(name)
    if opcode != "custom-call":
        return False
    return "custom_call_target" not in name or "tpu_custom_call" in name


def clip(events, lo: float, hi: float):
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union_intervals(events) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals covered by any event."""
    merged: list[list[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events) -> float:
    return sum(e - s for s, e in union_intervals(events))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals inside ``[lo, hi]``."""
    out, cursor = [], lo
    for s, e in union_intervals(events):
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def top_ops(events, k: int = 10) -> list[list]:
    """``[[label, seconds], ...]``: durations summed under ``op_key``."""
    total: dict[str, float] = {}
    for name, s, e in events:
        key = op_key(name)
        total[key] = total.get(key, 0.0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


SHORT_GAP_NS = 10_000.0


def attribute_gaps(idle, host_spans, k: int = 10,
                   ignore=(WINDOW_SPAN,)) -> list[list]:
    """``[[span name, seconds], ...]``: idle time summed under the host span
    that says what the host was doing in each gap, the largest sums first.
    Spans nest (``perf.step`` contains the engine's ``serve.*``), so the
    answer is the SHORTEST span that covers at least half of the gap; when
    none does, the span that overlaps it most; ``"(no host span)"`` when
    none overlaps.  Gaps under 10 us (the device's own turn-round between
    operations) are summed under one label."""
    total: dict[str, float] = {}
    spans = [sp for sp in host_spans if sp[0] not in ignore]
    for gs, ge in idle:
        if ge - gs < SHORT_GAP_NS:
            key = "(gaps under 10 us)"
            total[key] = total.get(key, 0.0) + (ge - gs)
            continue
        covering, best, best_ov = None, "(no host span)", 0.0
        for name, s, e in spans:
            ov = min(e, ge) - max(s, gs)
            if ov <= 0:
                continue
            if ov > best_ov:
                best, best_ov = name, ov
            if 2 * ov >= ge - gs and (covering is None
                                      or e - s < covering[1]):
                covering = (name, e - s)
        name = covering[0] if covering else best
        total[name] = total.get(name, 0.0) + (ge - gs)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce_trace(path: str, host_span_prefixes=("perf.", "serve.", "train."),
                 program_spans=(), anchor: float | None = None) -> dict:
    """Everything the readers and the result line need from one trace.

    The window is the ``perf.trace_window`` annotation the runner wraps the
    traced stretch in; without one it is the span of the device events.
    Busy seconds are averaged over the chips.  ``program_spans`` are
    ``(name, start, end)`` in ``time.perf_counter`` seconds (the program's
    own tracer); ``anchor`` is that clock's reading when the window
    annotation began, which places them on the trace's clock."""
    ev = load_events(path)
    window = [sp for sp in ev["host"] if sp[0] == WINDOW_SPAN]
    all_dev = [e for evs in ev["device"].values() for e in evs]
    if not all_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0}
    lo = min(s for _, s, _ in all_dev)
    hi = max(e for _, _, e in all_dev)
    if window:
        wlo, whi = window[0][1], window[0][2]
        # keep the annotation only if the device events live on its clock
        if wlo < hi and whi > lo:
            lo, hi = wlo, whi
    busy, ops, idle = [], [], []
    for events in ev["device"].values():
        events = clip(events, lo, hi)
        busy.append(busy_ns(events))
        ops.extend(events)
        idle.extend(gaps(events, lo, hi))
    chips = len(busy)
    kernel_ns = sum(e - s for name, s, e in ops if is_kernel_call(name))
    host = [sp for sp in ev["host"]
            if sp[0].startswith(tuple(host_span_prefixes))]
    if window and anchor is not None:
        host += [(name, window[0][1] + (s - anchor) * 1e9,
                  window[0][1] + (e - anchor) * 1e9)
                 for name, s, e in program_spans]
    host = clip(host, lo, hi)
    return {
        "chips": chips,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / chips / 1e9,
        "kernel_s": kernel_ns / chips / 1e9,
        "device_ops": top_ops(ops),
        "idle_gaps": attribute_gaps(idle, host),
    }
