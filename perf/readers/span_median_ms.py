"""Median duration of one of the program's spans (``args.span``, recorded
by progen_tpu.observe.trace while the harness has it enabled) inside the
window."""

from perf.lib import stats


def read(obs, metric):
    durations = obs["spans"].get(metric["args"]["span"])
    return 1e3 * stats.median(durations) if durations else None
