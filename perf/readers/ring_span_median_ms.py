"""Median duration, in ms, of one of the program's spans (``args.span``)
over everything its ring holds (``progen_tpu.observe.trace``: the train
runner enables the ring in the traced run and does not clear it, so the
warm steps before the window are in it; a median does not mind).  A
program that records no such span, or a ring that is off, gives
``None``."""

from perf.lib import stats


def read(obs, metric):
    try:
        from progen_tpu.observe.trace import get_tracer
    except ImportError:
        return None
    durations = [s["dur"] for s in get_tracer().ring()
                 if s["name"] == metric["args"]["span"]]
    return 1e3 * stats.median(durations) if durations else None
