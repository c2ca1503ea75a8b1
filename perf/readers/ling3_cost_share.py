"""Ling-3.0-flash's shares (``args["share"]``), all from
perf/lib/ling3_cost.py and the program's counters, over the whole
process as the counters and histograms are:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (every weight outside the routed experts once a step,
    the experts TOUCHED, the head, the LIVE rows' carry read and written
    once a delta layer, their convolution tails, their latent rows up to each
    row's length — the ``moe.*`` / ``kda.*`` / ``mla.*`` counters) over the
    whole of ``engine.decode_chunk_s`` times the published bandwidth;
``state_share``
    the carries' share of those bytes, a ratio: how much of the step is the
    recurrent state;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require (the runner lists their lengths,
    ``moe.prefill_held`` gives the assignments to held experts; the chunked
    channel-decay delta rule's products a chunk, causal latent attention as the
    mask allows),
    over the whole of ``engine.prefill_s`` times the published peak.
    Padding to the bucket, unused rows of a run and the merge into the
    slots are inside the time and not among the operations.

A program without the counters gives ``None``."""

from perf.lib import peaks, ling3_cost


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    config = obs["config"]
    share = metric["args"]["share"]
    snap = get_registry().snapshot()

    def peak(name):
        """The chip's published peak: asked for only by a share of one."""
        return peaks.peaks_for(obs["device_kind"])[name]

    def value(name):
        return (snap.get(name) or {}).get("value")

    def seconds(name):
        return (snap.get(name) or {}).get("sum")

    if share in ("decode_hbm", "state_share"):
        need = [value(k) for k in ("moe.decode_layers", "moe.experts_touched",
                                   "kda.state_bytes", "mla.context_tokens")]
        spent = seconds("engine.decode_chunk_s")
        if any(not v for v in need) or not spent:
            return None
        need[0] /= ling3_cost.expert_layers(config)  # layers run -> steps
        terms = ling3_cost.decode_terms(config, *need)
        moved = float(sum(terms.values()))
        if share == "state_share":
            return terms["carry"] / moved
        return 100.0 * moved / (spent * peak("hbm_bytes_per_s"))
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if not primes or not held or not spent or not value(
                "kda.real_tokens"):
            return None
        flops = ling3_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak("bf16_flops"))
    raise ValueError(f"unknown share {share!r}")
