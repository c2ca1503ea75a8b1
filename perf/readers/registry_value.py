"""One number of the program's metrics registry (``args.name`` in
``progen_tpu.observe.metrics``' process registry, which is always on): a
counter's or a gauge's value, or the ``args.field`` of a histogram's
snapshot (``sum``: the seconds a histogram of durations holds; ``count``,
``max``).  The registry holds the whole process,
set-up included — which is what a metric of set-up reads.  A counter that
is there and counted nothing reads 0; a program that has no such entry
gives ``None``."""


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    args = metric["args"]
    snap = get_registry().snapshot().get(args["name"])
    if not snap:
        return None
    return snap.get(args.get("field", "value"))
