from progen_tpu.observe.flops import (
    PEAK_BF16_TFLOPS,
    mfu,
    model_flops_per_token,
    peak_flops_per_chip,
)
from progen_tpu.observe.gitinfo import git_sha
from progen_tpu.observe.meter import ThroughputMeter, profile_trace
from progen_tpu.observe.metrics import (
    LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    labeled,
    latency_buckets,
    latency_percentiles,
    merge_snapshots,
    split_labeled,
)
from progen_tpu.observe.platform import require_tpu, stamp_record
from progen_tpu.observe.robustness import RobustnessCounters
from progen_tpu.observe.slo import BurnRateTracker, SLOSpec
from progen_tpu.observe.statusz import StatuszServer, render_prometheus
from progen_tpu.observe.trace import (
    Tracer,
    chrome_trace,
    configure_tracing,
    get_tracer,
    merge_trace_dir,
    spans_for,
    trace_dump_path,
)
from progen_tpu.observe.tracker import Tracker

__all__ = [
    "PEAK_BF16_TFLOPS",
    "RobustnessCounters",
    "git_sha",
    "require_tpu",
    "stamp_record",
    "mfu",
    "model_flops_per_token",
    "peak_flops_per_chip",
    "ThroughputMeter",
    "profile_trace",
    "Tracker",
    # tracing (observe.trace)
    "Tracer",
    "chrome_trace",
    "configure_tracing",
    "get_tracer",
    "merge_trace_dir",
    "spans_for",
    "trace_dump_path",
    # metrics (observe.metrics)
    "LATENCY_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "labeled",
    "latency_buckets",
    "latency_percentiles",
    "merge_snapshots",
    "split_labeled",
    # live introspection plane (observe.statusz / observe.slo)
    "StatuszServer",
    "render_prometheus",
    "SLOSpec",
    "BurnRateTracker",
]
