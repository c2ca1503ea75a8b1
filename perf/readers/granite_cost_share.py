"""Granite's shares (``args["share"]``), all from perf/lib/granite_cost.py
and the program's counters, all over the whole process as the counters and
histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (every weight once a step, the LIVE rows' carry read and
    written once, their convolution tails, their keys up to each row's
    length — the ``ssm.*`` / ``attn.*`` counters) over the whole of
    ``engine.decode_chunk_s`` times the published bandwidth;
``state_share``
    the carry's share of those bytes, in per cent: how much of the step is
    the mechanism;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require (the runner lists their lengths; the
    scan's four products a chunk, causal attention as the mask allows),
    over the whole of ``engine.prefill_s`` times the published peak.
    Padding to the bucket, unused rows of a run and the merge into the
    slots are inside the time and not among the operations.

A program without the counters gives ``None``."""

from perf.lib import granite_cost, peaks


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    config = obs["config"]
    peak = peaks.peaks_for(obs["device_kind"])

    def value(name):
        return (snap.get(name) or {}).get("value")

    def seconds(name):
        return (snap.get(name) or {}).get("sum")

    share = metric["args"]["share"]
    if share in ("decode_hbm", "state_share"):
        need = [value(k) for k in ("ssm.decode_steps", "ssm.step_rows",
                                   "attn.context_tokens")]
        spent = seconds("engine.decode_chunk_s")
        if any(not v for v in need) or not spent:
            return None
        terms = granite_cost.decode_terms(config, *need)
        moved = float(sum(terms.values()))
        if share == "state_share":
            return 100.0 * terms["carry"] / moved
        return 100.0 * moved / (spent * peak["hbm_bytes_per_s"])
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        spent = seconds("engine.prefill_s")
        if not primes or not spent or not value("ssm.prefill_tokens"):
            return None
        flops = granite_cost.prefill_flops(config, primes)
        return 100.0 * flops / (spent * peak["bf16_flops"])
    raise ValueError(f"unknown share {share!r}")
