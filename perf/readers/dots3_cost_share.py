"""dots3's two shares (``args["share"]``), from perf/lib/dots3_cost.py and
the program's counters, both over the whole process as the counters and
histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (weights outside the routed experts, the experts TOUCHED,
    the head, and of the live rows the indexer keys they could see, the
    latent rows the selection kept and the ring rows they had — ``moe.*`` /
    ``dsa.*`` / ``mla.*`` counters) over the whole of
    ``engine.decode_chunk_s`` times the published bandwidth;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require — the SELECTED pairs of the full
    layers, the windowed pairs of the sliding ones, the indexer's score of
    every visible key (the runner lists the primes' lengths,
    ``moe.prefill_held`` gives the assignments to held experts) — over the
    whole of ``engine.prefill_s`` times the published peak.  Padding to the
    bucket, the masked pairs of the XLA forms and the merge into the slots
    are inside the time and not among the operations.

A program without the counters gives ``None``."""

from perf.lib import dots3_cost, peaks


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
    peak = peaks.peaks_for(obs["device_kind"])
    if share == "decode_hbm":
        need = [value(k) for k in (
            "moe.decode_layers", "moe.experts_touched", "mla.window_tokens",
            "dsa.context_tokens", "dsa.keys_selected")] + [
            seconds("engine.decode_chunk_s")]
        if any(not v for v in need):
            return None
        layers, *counts, spent = need
        moved = dots3_cost.decode_bytes(
            config, layers / dots3_cost.expert_layers(config), *counts)
        return 100.0 * moved / (spent * peak["hbm_bytes_per_s"])
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if not primes or not held or not spent:
            return None
        flops = dots3_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak["bf16_flops"])
    raise ValueError(f"unknown share {share!r}")
