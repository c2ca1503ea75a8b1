"""Ratio of two of the program's registry gauges (``args.numerator`` over
``args.denominator`` in ``progen_tpu.observe.metrics``' process registry):
the engine publishes its model family's device counters there, cumulative
since it was built, each time the harvest fetches the slot flags.  A
program that has no such gauge, or a denominator of zero, gives ``None``."""


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    args = metric["args"]
    top, bottom = snap.get(args["numerator"]), snap.get(args["denominator"])
    if not top or not bottom or not bottom.get("value"):
        return None
    return args.get("scale", 1.0) * top["value"] / bottom["value"]
