"""Cross-process request tracing + unified metrics registry: span ring
semantics (zero-cost when disabled), trace context on the handle wire,
histogram quantiles against a numpy oracle, merge/offset correction, and
a REAL 2-process cluster whose merged trace shows one request's spans in
all three processes with causally consistent timestamps."""

import json

import numpy as np
import pytest

from progen_tpu.observe.metrics import (
    Histogram,
    MetricsRegistry,
    get_registry,
    latency_percentiles,
)
from progen_tpu.observe.trace import (
    Tracer,
    chrome_trace,
    configure_tracing,
    get_tracer,
    merge_dumps,
    merge_trace_dir,
    spans_for,
    trace_dump_path,
)

pytestmark = pytest.mark.trace


@pytest.fixture
def driver_tracing():
    """Enable the process tracer for one test, restore disabled+empty."""
    tracer = configure_tracing(enabled=True, process="driver")
    tracer.clear()
    yield tracer
    tracer.clear()
    configure_tracing(enabled=False, capacity=4096, process="main")


# ------------------------------------------------------------- tracer basics


def test_disabled_tracer_is_noop():
    t = Tracer()  # disabled by default
    assert t.span("a") is t.span("b")        # shared no-op singleton
    with t.span("a", trace=1, big=list(range(100))):
        pass
    t.add("b", 0.0, 1.0, trace=2)
    t.event("c", trace=3)
    assert t.ring() == []


def test_span_ring_records_and_bounds(driver_tracing):
    t = driver_tracing
    with t.span("outer", trace=7, kind="x"):
        t.event("inner", trace=7)
    ring = t.ring()
    assert [s["name"] for s in ring] == ["inner", "outer"]
    outer = ring[1]
    assert outer["trace"] == 7 and outer["args"] == {"kind": "x"}
    assert outer["dur"] >= 0.0
    # bounded: the ring keeps only the newest `capacity` spans
    configure_tracing(enabled=True, capacity=4)
    for i in range(10):
        t.event(f"e{i}")
    assert [s["name"] for s in t.ring()] == ["e6", "e7", "e8", "e9"]
    configure_tracing(enabled=True, capacity=4096)


def test_spans_for_matches_trace_and_batch_uids():
    spans = [
        {"name": "a", "ts": 0.0, "dur": 1.0, "trace": 5},
        {"name": "b", "ts": 1.0, "dur": 1.0, "args": {"uids": [4, 5]}},
        {"name": "c", "ts": 2.0, "dur": 1.0, "trace": "other"},
    ]
    assert [s["name"] for s in spans_for(spans, 5)] == ["a", "b"]
    assert [s["name"] for s in spans_for(spans, "other")] == ["c"]


def test_merge_dumps_applies_offsets_and_chrome_export(tmp_path):
    driver = {"process": "driver", "pid": 1,
              "meta": {"offsets": {"prefill:0": 10.0}},
              "spans": [{"name": "cluster.submit", "ts": 11.0, "dur": 0.1,
                         "trace": 0}]}
    worker = {"process": "prefill:0", "pid": 2, "meta": {},
              "spans": [{"name": "serve.prefill", "ts": 1.5, "dur": 0.2,
                         "args": {"uids": [0]}}]}
    merged = merge_dumps([driver, worker])
    # worker span moved onto the driver clock (1.5 + 10.0) and sorted
    assert [(s["name"], s["ts"]) for s in merged] == [
        ("cluster.submit", 11.0), ("serve.prefill", 11.5)]
    obj = chrome_trace([driver, worker])
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M"}
    assert names == {"driver", "prefill:0"}
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"cluster.submit", "serve.prefill"}
    assert all(e["ts"] >= 1e6 for e in xs)   # microseconds

    # dir merge: dump files -> one Perfetto-loadable trace.json
    for d in (driver, worker):
        with open(trace_dump_path(str(tmp_path), d["process"]), "w") as fh:
            json.dump(d, fh)
    out = merge_trace_dir(str(tmp_path))
    assert out is not None
    loaded = json.load(open(out))
    assert len([e for e in loaded["traceEvents"] if e["ph"] == "X"]) == 2


# ---------------------------------------------------------- metrics registry


def test_histogram_percentiles_against_numpy_oracle():
    rng = np.random.default_rng(0)
    # log-uniform latencies spanning the bucket range
    values = np.exp(rng.uniform(np.log(1e-3), np.log(10.0), size=2000))
    h = Histogram("t")
    for v in values:
        h.observe(float(v))
    for p in (50.0, 95.0, 99.0):
        est = h.percentile(p)
        exact = float(np.percentile(values, p))
        # log-spaced buckets (ratio ~1.245) bound relative error by one
        # bucket width
        assert abs(est - exact) / exact < 0.25, (p, est, exact)
    assert h.percentile(0.0) == pytest.approx(h.min)
    assert h.percentile(100.0) == pytest.approx(h.max)
    assert h.mean == pytest.approx(float(values.mean()), rel=1e-6)


def test_latency_percentiles_shared_path_resets():
    p50, p95 = latency_percentiles([0.1] * 99 + [10.0])
    assert p50 == pytest.approx(0.1, rel=0.3)
    assert p95 == pytest.approx(0.1, rel=0.3)
    # the named histogram is reset per call: no bleed between benches
    p50b, _ = latency_percentiles([5.0, 5.0, 5.0])
    assert p50b == pytest.approx(5.0, rel=0.3)
    assert get_registry().histogram("bench.latency_s").count == 3


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    assert reg.counter("reqs") is c and c.value == 1
    reg.gauge("depth").set(3)
    reg.histogram("lat").observe(0.5)
    with pytest.raises(ValueError):
        reg.counter("lat")
    snap = reg.snapshot()
    assert snap["reqs"] == {"type": "counter", "value": 1}
    assert snap["depth"]["value"] == 3
    assert snap["lat"]["count"] == 1
    assert json.dumps(snap)  # wire-safe: rides heartbeat frames as JSON


# --------------------------------------------------- trace context on wire


def _tiny_spec(variant="dense", trace_dir=None):
    from progen_tpu.models import ProGenConfig
    from progen_tpu.serve.worker import make_spec

    cfg = ProGenConfig(
        num_tokens=32, dim=16, seq_len=24, depth=2, window_size=4,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
    )
    kw = dict(num_slots=4, chunk_size=4, max_len=24, prefill_batch=2,
              handoff_depth=2)
    kw.update({
        "dense": {},
        "paged": dict(paged=True, page_size=4, num_pages=32),
    }[variant])
    return make_spec(cfg, mixed_precision=False, init_seed=7, engine=kw,
                     trace={"dir": trace_dir} if trace_dir else None)


def test_request_wire_carries_trace_context():
    pytest.importorskip("jax")
    from progen_tpu.decode.engine import Request
    from progen_tpu.decode.handoff import request_to_wire

    wire = request_to_wire(Request(uid="r1", tokens=[1, 2],
                                   max_new_tokens=3), now=42.0)
    assert wire["trace"] == {"id": "r1", "clock": 42.0}


@pytest.mark.multiproc
@pytest.mark.parametrize("variant", ["dense", "paged"])
def test_handle_frame_carries_trace_context(variant):
    """Every request row on a handle frame names its trace id (the uid)
    plus the sender's clock, and the producer's trace_ctx extra header
    survives the frame round-trip — the receiving process can attribute
    queue-wait to exact requests on a corrected timeline."""
    pytest.importorskip("jax")
    from progen_tpu.decode.engine import Request
    from progen_tpu.decode.handoff import (
        deserialize_handle,
        serialize_handle,
        unpack_frame,
    )
    from progen_tpu.serve.worker import build_engine_from_spec

    eng = build_engine_from_spec(_tiny_spec(variant))
    for i in range(2):
        eng.submit(Request(uid=10 + i, tokens=[1 + i, 2, 3],
                           max_new_tokens=4, seed=i))
    frame = serialize_handle(
        eng.run_prefill_round(),
        extra_header={"trace_ctx": {"clock": 1.5, "src_proc": "prefill:0"}})
    header, _ = unpack_frame(frame)
    assert [d["uid"] for d in header["reqs"]] == [10, 11]
    for d in header["reqs"]:
        assert d["trace"]["id"] == d["uid"]
        assert d["trace"]["clock"] > 0.0
    assert header["trace_ctx"] == {"clock": 1.5, "src_proc": "prefill:0"}
    h2 = deserialize_handle(frame)
    assert [r.uid for r in h2.requests] == [10, 11]


# ------------------------------------------------- real 2-process cluster


@pytest.mark.multiproc
def test_cluster_merged_trace_is_causally_ordered(tmp_path, driver_tracing):
    """One uid's spans appear in all three processes (driver router,
    prefill worker, decode replica) and, after the driver's clock-offset
    correction, driver-side causes precede worker-side effects: submit
    before the prefill round, relay before the decode merge."""
    pytest.importorskip("jax")
    import os

    from progen_tpu.decode.engine import Request
    from progen_tpu.observe.trace import load_dump
    from progen_tpu.serve.cluster import ServeCluster

    cluster = ServeCluster(_tiny_spec(trace_dir=str(tmp_path)))
    try:
        for i in range(2):
            cluster.submit(Request(uid=i, tokens=[1 + i, 2, 3],
                                   max_new_tokens=4, top_k=None,
                                   temperature=0.0, seed=i))
        done = cluster.drain(timeout=300.0)
    finally:
        stats = cluster.shutdown()
    assert len(done) == 2 and all(c.ok for c in done)
    # the driver learned offsets for every worker from clock echoes
    assert set(stats["clock_offsets"]) == {"prefill:0", "decode:0"}

    merged_path = merge_trace_dir(str(tmp_path))
    assert merged_path is not None
    obj = json.load(open(merged_path))
    proc_names = {e["args"]["name"] for e in obj["traceEvents"]
                  if e["ph"] == "M"}
    assert {"driver", "prefill:0", "decode:0"} <= proc_names

    dumps = [load_dump(os.path.join(str(tmp_path), f))
             for f in sorted(os.listdir(str(tmp_path)))
             if f.startswith("trace_") and f.endswith(".json")]
    spans = merge_dumps(dumps)
    mine = spans_for(spans, 0)
    by_proc: dict = {}
    for s in mine:
        by_proc.setdefault(s["process"], []).append(s)
    assert {"driver", "prefill:0", "decode:0"} <= set(by_proc)

    def first(proc, *names):
        ts = [s["ts"] for s in by_proc[proc] if s["name"] in names]
        assert ts, (proc, names)
        return min(ts)

    # offset estimates only ever overestimate (min over echoes still
    # includes one network delay), which can only push worker spans
    # LATER on the driver clock — so driver-cause <= worker-effect is
    # exactly the direction the correction preserves
    submit = first("driver", "cluster.submit")
    prefill = first("prefill:0", "serve.prefill", "serve.admit_prefill")
    assert submit <= prefill
    relay = first("driver", "cluster.relay")
    merge = first("decode:0", "serve.merge")
    assert relay <= merge
    done_ts = first("driver", "cluster.done")
    assert done_ts >= submit
