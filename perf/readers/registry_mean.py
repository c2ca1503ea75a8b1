"""Mean of one of the program's registry histograms in the unit it was
observed in (``args.histogram`` in ``progen_tpu.observe.metrics``' process
registry, which is always on): the exact ``sum / count`` — for a histogram
of counts, such as rows per admission run, the mean count.  The registry
holds the whole process (set-up's probes and ramp, the drain), on both
sides of a comparison alike.  A program that has no such histogram, or
observed nothing in it, gives ``None``."""


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot().get(metric["args"]["histogram"])
    if not snap or snap.get("type") != "histogram" or not snap.get("count"):
        return None
    return snap["sum"] / snap["count"]
