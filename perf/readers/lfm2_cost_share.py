"""LFM2's two shares of a peak (``args["share"]``), both from
perf/lib/lfm2_cost.py and the program's counters, both over the whole
process as the counters and histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (weights outside the experts, the experts TOUCHED, the
    head, grown rows up to ``length`` of the live slots, the short
    convolutions' tails — ``moe.*`` / ``attn.*`` / ``conv.tokens`` counters)
    over the whole of ``engine.decode_chunk_s`` times the published
    bandwidth;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require, causal attention counted as the
    mask allows (the runner lists their lengths, ``moe.prefill_held`` gives
    the assignments to held experts), over the whole of
    ``engine.prefill_s`` times the published peak.  Padding to the bucket,
    unused rows of a run and the merge into the slots are inside the time
    and not among the operations.

A program without the counters (one that has no ``conv.*``: every family
but this one) gives ``None``."""

from perf.lib import lfm2_cost, peaks


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

    conv_tokens = value("conv.tokens")
    if not conv_tokens:
        return None
    share = metric["args"]["share"]
    if share == "decode_hbm":
        need = [value("moe.decode_layers"), value("moe.experts_touched"),
                value("attn.context_tokens"),
                seconds("engine.decode_chunk_s")]
        if any(not v for v in need):
            return None
        layers, touched, context, spent = need
        moved = lfm2_cost.decode_bytes(
            config, layers / lfm2_cost.expert_layers(config), touched,
            context, conv_tokens)
        return 100.0 * moved / (spent * peak["hbm_bytes_per_s"])
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if not primes or not held or not spent:
            return None
        flops = lfm2_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak["bf16_flops"])
    raise ValueError(f"unknown share {share!r}")
