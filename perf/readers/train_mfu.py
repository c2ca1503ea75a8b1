"""Model FLOP/s utilization: required forward + backward operations per
token SLOT (perf/lib/flops.py: padding is computed too, recomputation is
not charged) times slots per second per chip, over the chip's bf16 peak."""

from perf.lib import flops, peaks


def read(obs, metric):
    c = obs["counters"]
    if not c.get("slots"):
        return None
    peak = peaks.peaks_for(obs["device_kind"])["bf16_flops"]
    per_chip = c["slots"] / c["window_s"] / obs["chips"]
    return 100.0 * flops.train_flops_per_slot(obs["config"]) * per_chip / peak
