"""SDAR's two shares of a peak (``args["share"]``), both from
perf/lib/sdar_cost.py and the program's counters, both over the whole
process as the counters and histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the block step's forwards needed:
    the bytes they must move (attention, router and head once a forward, the
    experts TOUCHED, the live rows' committed keys, the commits' writes —
    ``moe.*`` / ``attn.*`` / ``diffusion.*`` counters) over the whole of
    ``engine.decode_chunk_s`` times the published bandwidth;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    whole blocks of the REAL primes admitted require under the block mask
    (the runner lists their lengths, ``moe.prefill_held`` gives the
    assignments), over the whole of ``engine.prefill_s`` times the published
    peak.  Padding to the bucket, unused rows of a run and the merge into
    the slots are inside the time and not among the operations.

A program without the counters gives ``None``."""

from perf.lib import peaks, sdar_cost


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    snap = get_registry().snapshot()
    # the cell's block length is its traffic's (``runners/serve_sdar.py``)
    config = {**obs["config"], **{
        k: v for k, v in obs["workload"]["traffic"]["sampling"].items()
        if k == "block_length"}}
    peak = peaks.peaks_for(obs["device_kind"])

    def value(name):
        return (snap.get(name) or {}).get("value")

    def seconds(name):
        return (snap.get(name) or {}).get("sum")

    share = metric["args"]["share"]
    if share == "decode_hbm":
        need = [value(k) for k in (
            "moe.decode_layers", "moe.experts_touched",
            "attn.context_tokens", "diffusion.commit_forwards")] + [
                seconds("engine.decode_chunk_s")]
        if any(not v for v in need):
            return None
        layers, touched, context, commits, spent = need
        moved = sdar_cost.forward_bytes(
            config, layers / config["num_hidden_layers"], touched, context,
            commits)
        return 100.0 * moved / (spent * peak["hbm_bytes_per_s"])
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if not primes or not held or not spent:
            return None
        flops = sdar_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak["bf16_flops"])
    raise ValueError(f"unknown share {share!r}")
