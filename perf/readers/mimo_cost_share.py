"""MiMo-V2's three shares (``args["share"]``), from perf/lib/mimo_cost.py and
the program's counters, all over the whole process as the counters and
histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (weights outside the experts, the experts TOUCHED, the
    head, ring rows up to ``min(length, window)`` and grown rows up to
    ``length`` of the live slots, each kind at its own row bytes — ``moe.*``
    / ``attn.*`` counters) over the whole of ``engine.decode_chunk_s`` times
    the published bandwidth;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require, windowed and causal attention
    counted as the mask allows at 192-wide scores and 128-wide values (the
    runner lists their lengths, ``moe.prefill_held`` gives the assignments
    to held experts), over the whole of ``engine.prefill_s`` times the
    published peak.  Padding to the bucket, the blocked form's masked pairs
    and the merge into the slots are inside the time and not among the
    operations;
``full_share_of_cache_bytes``
    of the cache bytes the decode cores READ, the share that the full
    layers' grown rows are: ``attn.full_bytes_read`` over its sum with
    ``attn.window_bytes_read`` (``models/kv.py:byte_gauges``: rows read
    times each kind's own row bytes times its blocks).  What the XLA core's
    whole-cache read of the grown keys costs beside the rings, and what a
    kernel that stops at a slot's count would give back.

A program without the counters gives ``None``."""

from perf.lib import mimo_cost, peaks


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    config = obs["config"]

    def value(name):
        return (snap.get(name) or {}).get("value")

    def seconds(name):
        return (snap.get(name) or {}).get("sum")

    share = metric["args"]["share"]
    if share == "full_share_of_cache_bytes":
        full, ring = (value("attn.full_bytes_read"),
                      value("attn.window_bytes_read"))
        if not full or ring is None:
            return None
        return 100.0 * full / (full + ring)
    peak = peaks.peaks_for(obs["device_kind"])
    if share == "decode_hbm":
        need = [value(k) for k in (
            "moe.decode_layers", "moe.experts_touched", "attn.window_tokens",
            "attn.context_tokens")] + [seconds("engine.decode_chunk_s")]
        if any(not v for v in need):
            return None
        layers, touched, window, context, spent = need
        moved = mimo_cost.decode_bytes(
            config, layers / mimo_cost.expert_layers(config), touched,
            window, context)
        return 100.0 * moved / (spent * peak["hbm_bytes_per_s"])
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if not primes or not held or not spent:
            return None
        flops = mimo_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak["bf16_flops"])
    raise ValueError(f"unknown share {share!r}")
