"""Nemotron-H's shares (``args["share"]``), all from
perf/lib/nemotron3_cost.py and the program's counters:

``decode_hbm``
    share of the chip's memory bandwidth the decode steps needed: the bytes
    they must move (every weight outside the routed experts once a step,
    the experts TOUCHED, the head, the LIVE rows' carry read and written
    once, their convolution tails, their keys up to each row's length — the
    ``moe.*`` / ``ssm.*`` / ``attn.*`` counters) over the whole of
    ``engine.decode_chunk_s`` times the published bandwidth; over the whole
    process as the counters and histograms are;
``state_share``
    the carry's share of those bytes, in per cent: how much of the step is
    the recurrent state;
``prefill_mfu``
    share of the chip's bf16 peak the admissions needed: the operations the
    REAL prime tokens admitted require (the runner lists their lengths,
    ``moe.prefill_held`` gives the assignments to held experts; the scan's
    four products a chunk, causal attention as the mask allows), over the
    whole of ``engine.prefill_s`` times the published peak.  Padding to the
    bucket, unused rows of a run and the merge into the slots are inside
    the time and not among the operations;
``kernel_roofline``
    share of the chip's memory bandwidth the two-matrix decode kernel
    (``moe_decode_fwd``) ran at INSIDE THE TRACED STRETCH: the bytes its
    calls there must read — ``moe.expert_passes`` experts' two matrices and
    each call's rows in and out, from the counters' difference between the
    stretch's two ends (``stretch_counters``: the runner reads them between
    two steps, the device idle) — over the kernel's device seconds among
    the reduced trace's rows, over the published bandwidth.  ``None`` where
    the kernel is not among those rows or the stretch has no ends.

A program without the counters gives ``None``."""

from perf.lib import nemotron3_cost, peaks

KERNEL = "moe_decode_fwd"


def read(obs, metric):
    try:
        from progen_tpu.observe.metrics import get_registry
    except ImportError:
        return None
    config = obs["config"]
    share = metric["args"]["share"]

    def peak(name):
        """The chip's published peak: asked for only by a share of one."""
        return peaks.peaks_for(obs["device_kind"])[name]

    if share == "kernel_roofline":
        ends = obs["counters"].get("stretch_counters") or {}
        rows = (obs.get("trace") or {}).get("device_ops") or []
        spent = sum(s for name, s in rows if name.startswith(KERNEL))
        passes, calls = (ends.get("moe.expert_passes"),
                         ends.get("moe.decode_layers"))
        if not spent or not passes or not calls:
            return None
        moved = nemotron3_cost.kernel_bytes(
            config, passes, calls, obs["workload"]["engine"]["num_slots"])
        return 100.0 * moved / (spent * peak("hbm_bytes_per_s"))
    snap = get_registry().snapshot()

    def value(name):
        return (snap.get(name) or {}).get("value")

    def seconds(name):
        return (snap.get(name) or {}).get("sum")

    if share in ("decode_hbm", "state_share"):
        need = [value(k) for k in ("ssm.decode_steps", "moe.experts_touched",
                                   "ssm.step_rows", "attn.context_tokens")]
        spent = seconds("engine.decode_chunk_s")
        if any(not v for v in need) or not spent:
            return None
        terms = nemotron3_cost.decode_terms(config, *need)
        moved = float(sum(terms.values()))
        if share == "state_share":
            return 100.0 * terms["carry"] / moved
        return 100.0 * moved / (spent * peak("hbm_bytes_per_s"))
    if share == "prefill_mfu":
        primes = obs["counters"].get("admitted_primes")
        held, spent = value("moe.prefill_held"), seconds("engine.prefill_s")
        if not primes or not held or not spent or not value(
                "ssm.prefill_tokens"):
            return None
        flops = nemotron3_cost.prefill_flops(config, primes, held)
        return 100.0 * flops / (spent * peak("bf16_flops"))
    raise ValueError(f"unknown share {share!r}")
