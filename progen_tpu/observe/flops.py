"""Model-FLOPs accounting and MFU estimation.

BASELINE.md's headline metric is tokens/sec/chip, which is meaningless
across model scales; MFU (model FLOPs utilization) normalizes it against
the chip's peak so throughput claims stay honest (the reference publishes
no numbers at all — SURVEY.md §6).  Shared by ``bench.py`` and the
training loop's live metrics.
"""

from __future__ import annotations

import jax

# THE one table of peak dense bf16 TFLOP/s per chip, keyed by jax
# device_kind.  A TPU whose kind is not here is an error, not a default
# (peak_flops_per_chip).  Sources: Google Cloud TPU documentation, the
# "System architecture" page of each generation.
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,       # cloud.google.com/tpu/docs/v4
    "TPU v5 lite": 197.0,  # cloud.google.com/tpu/docs/v5e (kind as JAX reports a v5e)
    "TPU v5e": 197.0,      # same chip, alternative spelling
    "TPU v5p": 459.0,      # cloud.google.com/tpu/docs/v5p
    "TPU v6 lite": 918.0,  # cloud.google.com/tpu/docs/v6e (Trillium)
    "TPU v6e": 918.0,      # same chip, alternative spelling
}


def model_flops_per_token(cfg, num_params: int,
                          sgu_impl: str = "xla") -> float:
    """Training FLOPs (fwd+bwd) per token: the standard 6N for every dense
    parameter plus the windowed-attention score/value matmuls, which touch
    2*wsz keys per query: fwd 8*wsz*inner FLOPs/token/layer, x3 with the
    backward.

    The SGU spatial ``(n, n)`` weights are parameters but their matmul
    contracts over TOKENS, not features — 6N would charge 6·n² per token
    where the real cost is 6·n·(d_ff/2) per token (dense) — so they are
    pulled out of 6N and charged by the matmul actually executed:
    ``2·n²·(d_ff/2)`` per sequence forward for the dense xla einsum, half
    that for the blocked-causal pallas kernel (upper-triangle blocks are
    skipped; ``ops/pallas_sgu.py``), x3 with the backward.
    """
    inner = cfg.heads * cfg.dim_head
    attn = 24.0 * cfg.window_size * inner * cfg.depth
    n_gmlp = min(cfg.global_mlp_depth, cfg.depth)
    n = cfg.seq_len
    d_half = cfg.dim * cfg.ff_mult // 2
    spatial_params = n_gmlp * (n * n + n)  # weights + biases per gmlp layer
    causal = 0.5 if sgu_impl == "pallas" else 1.0
    sgu = 6.0 * n * d_half * causal * n_gmlp  # 3 x fwd 2·n·d_half per token
    return 6.0 * (num_params - spatial_params) + attn + sgu


def peak_flops_per_chip(device=None) -> float | None:
    """Peak bf16 FLOP/s of the local accelerator.  None off-TPU (there is
    no device rate to normalize; callers skip MFU then); a TPU whose
    ``device_kind`` is missing from :data:`PEAK_BF16_TFLOPS` raises —
    add the kind with its source rather than assume a peak."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    if device.device_kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no peak FLOP/s known for device_kind {device.device_kind!r}; "
            f"add it to progen_tpu.observe.flops.PEAK_BF16_TFLOPS with "
            f"its source (known: {sorted(PEAK_BF16_TFLOPS)})")
    return PEAK_BF16_TFLOPS[device.device_kind] * 1e12


def mfu(tokens_per_sec_per_chip: float, flops_per_token: float,
        peak: float | None) -> float | None:
    if peak is None or peak <= 0:
        return None
    return flops_per_token * tokens_per_sec_per_chip / peak
