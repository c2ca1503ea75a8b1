"""GLM-5.2's two shares (``args["share"]``), from perf/lib/glm52_cost.py and
the program's counters, both over the whole process as the counters and
histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (weights outside the routed experts, the experts TOUCHED,
    the head, and of the live rows the indexer keys they could see in the
    full layers and the latent rows the selection kept in every layer —
    ``moe.*`` / ``mla.*`` / ``dsa.*`` counters) over the whole of
    ``engine.decode_chunk_s`` times the published bandwidth;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require — the SELECTED pairs of every layer,
    the indexer's score of every visible key in the full layers (the runner
    lists the primes' lengths, ``moe.prefill_held`` gives the assignments to
    held experts) — over the whole of ``engine.prefill_s`` times the
    published peak.  Padding to the bucket, the unselected pairs of the
    visited tiles and the merge into the slots are inside the time and not
    among the operations.

A program without the counters gives ``None``."""

from perf.lib import glm52_cost, peaks


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
            "moe.decode_layers", "moe.experts_touched", "mla.context_tokens",
            "dsa.keys_selected", "dsa.selections_computed")] + [
            seconds("engine.decode_chunk_s")]
        if any(not v for v in need):
            return None
        layers, touched, context, selected, _, spent = need
        moved = glm52_cost.decode_bytes(
            config, layers / glm52_cost.expert_layers(config), touched,
            context, selected)
        return 100.0 * moved / (spent * peak["hbm_bytes_per_s"])
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if (not primes or not held or not spent
                or not value("dsa.selections_computed")):
            return None
        flops = glm52_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak["bf16_flops"])
    raise ValueError(f"unknown share {share!r}")
