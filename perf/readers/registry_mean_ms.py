"""Mean of one of the program's own duration histograms, in ms
(``args.histogram`` in ``progen_tpu.observe.metrics``' process registry,
which is always on): the exact ``sum / count``, not a bucketed percentile,
divided by the ``args.per`` field of the cell's ``engine`` group where one
is named (a chunk's time over its decode steps).  The registry holds the
whole process: the probes and warm-up of set-up and the drain are in the
mean, on both sides of a comparison alike.  A program that has no such
histogram, or observed nothing in it, gives ``None``."""


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    args = metric["args"]
    snap = get_registry().snapshot().get(args["histogram"])
    if not snap or snap.get("type") != "histogram" or not snap.get("count"):
        return None
    mean_ms = 1e3 * snap["sum"] / snap["count"]
    if args.get("per"):
        mean_ms /= obs["workload"]["engine"][args["per"]]
    return mean_ms
