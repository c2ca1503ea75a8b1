"""Share of the chip's bf16 peak the admissions needed: the operations the
REAL prime tokens admitted require (perf/lib/longcat_cost.py; the runner
lists their lengths, the program's ``moe.prefill_held`` counter gives the
assignments to held experts) over the whole of ``engine.prefill_s`` times
the published peak.  Padding to the bucket, unused rows of a run and the
merge into the slots are inside the time and not among the operations.  A
program without the counter gives ``None``."""

from perf.lib import longcat_cost, peaks


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    primes = obs["counters"].get("admitted_primes")
    if not primes or not snap.get("moe.prefill_held") \
            or not snap.get("engine.prefill_s"):
        return None
    seconds = snap["engine.prefill_s"]["sum"]
    if not seconds:
        return None
    flops = longcat_cost.prefill_flops(obs["config"], primes,
                                       snap["moe.prefill_held"]["value"])
    peak = peaks.peaks_for(obs["device_kind"])["bf16_flops"]
    return 100.0 * flops / (seconds * peak)
