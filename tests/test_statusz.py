"""Live introspection plane: Prometheus exposition correctness (label
escaping, cumulative-bucket monotonicity, ``+Inf`` terminal bucket),
fleet snapshot merging, the SLO burn-rate evaluator against a hand
oracle, the per-process :class:`StatuszServer` endpoints, the benchdiff
regression gate, and a REAL 2-process cluster serving /healthz +
/metricsz from every process while producing token-identical output to
an introspection-disabled run (the zero-perturbation invariant)."""

import importlib.util
import json
import math
import os
import re
import time
import urllib.error
import urllib.request

import pytest

from progen_tpu.observe import slo as slo_mod
from progen_tpu.observe.metrics import (
    LATENCY_BUCKETS,
    MetricsRegistry,
    labeled,
    merge_snapshots,
    split_labeled,
)
from progen_tpu.observe.statusz import StatuszServer, render_prometheus

pytestmark = pytest.mark.trace

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fetch(port, path, timeout=10.0):
    """GET with a few retries: a racy host-dict read answers 503."""
    last = None
    for _ in range(5):
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout)
            return resp.status, resp.read().decode(), resp.headers
        except urllib.error.HTTPError as e:
            last = e
            if e.code != 503:
                return e.code, e.read().decode(), e.headers
    raise AssertionError(f"{path} kept failing: {last}")


# strict Prometheus line-format checker: every non-comment line must be
# name{label="value",...} number
_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')


def _assert_strict_exposition(text):
    samples = 0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        samples += 1
    assert samples > 0
    return samples


# -------------------------------------------------------- labeled names


def test_labeled_names_sort_and_escape():
    assert labeled("cluster.up", role="prefill", idx=0) == \
        'cluster.up{idx="0",role="prefill"}'
    # same label set, any kwarg order -> same registry key
    assert labeled("m", b=1, a=2) == labeled("m", a=2, b=1)
    nasty = labeled("m", k='a"b\\c\nd')
    assert nasty == 'm{k="a\\"b\\\\c\\nd"}'
    base, labelstr = split_labeled(nasty)
    assert base == "m" and labelstr == 'k="a\\"b\\\\c\\nd"'
    assert split_labeled("plain") == ("plain", "")


# -------------------------------------------------- prometheus rendering


def test_render_prometheus_counters_gauges_and_escaping():
    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(3)
    reg.gauge(labeled("cluster.up", role="prefill", idx=0)).set(1)
    reg.gauge(labeled("cluster.up", role="decode", idx=0)).set(0)
    reg.gauge(labeled("weird-name.g", path='a"b\\c')).set(2.5)
    text = render_prometheus(reg.snapshot())
    lines = text.splitlines()
    assert "# TYPE serve_requests counter" in lines
    assert "serve_requests 3" in lines
    # one TYPE line per family even with several label sets
    assert lines.count("# TYPE cluster_up gauge") == 1
    assert 'cluster_up{idx="0",role="prefill"} 1' in lines
    assert 'cluster_up{idx="0",role="decode"} 0' in lines
    # invalid chars sanitized in the name, escapes preserved in labels
    assert 'weird_name_g{path="a\\"b\\\\c"} 2.5' in lines
    _assert_strict_exposition(text)


def test_render_prometheus_histogram_cumulative_and_inf_terminal():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    for v in (0.001, 0.01, 0.01, 0.1, 50.0, 1000.0):  # 1000 > top bound
        h.observe(v)
    text = render_prometheus(reg.snapshot())
    _assert_strict_exposition(text)
    buckets = []
    for line in text.splitlines():
        m = re.match(r'lat_s_bucket\{le="([^"]+)"\} (\d+)$', line)
        if m:
            buckets.append((m.group(1), int(m.group(2))))
    assert len(buckets) == len(LATENCY_BUCKETS) + 1
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 6
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)          # cumulative: monotone
    assert counts[0] >= 0 and counts[-2] == 5  # overflow only in +Inf
    assert "lat_s_count 6" in text.splitlines()
    sum_line = [l for l in text.splitlines()
                if l.startswith("lat_s_sum ")][0]
    assert float(sum_line.split()[1]) == pytest.approx(1050.121)


def test_render_prometheus_rejects_mixed_type_family():
    snap = {"m": {"type": "counter", "value": 1},
            'm{a="b"}': {"type": "gauge", "value": 2}}
    with pytest.raises(ValueError, match="mixes types"):
        render_prometheus(snap)


# --------------------------------------------------------- fleet merging


def test_merge_snapshots_fleet_semantics():
    regs = [MetricsRegistry() for _ in range(3)]
    for i, reg in enumerate(regs):
        reg.counter("serve.requests").inc(i + 1)
        reg.gauge(labeled("cluster.up", role="decode", idx=i)).set(1)
        h = reg.histogram("serve.latency_s")
        h.observe(0.01 * (i + 1))
        h.observe(10.0)
    merged = merge_snapshots([r.snapshot() for r in regs])
    assert merged["serve.requests"]["value"] == 6     # counters sum
    for i in range(3):                                # labeled never collide
        assert merged[labeled("cluster.up", role="decode",
                              idx=i)]["value"] == 1
    h = merged["serve.latency_s"]
    assert h["count"] == 6
    assert h["sum"] == pytest.approx(30.06)
    assert h["min"] == pytest.approx(0.01)
    assert h["max"] == pytest.approx(10.0)
    # percentiles recomputed from merged buckets; p95 lands near 10s
    assert h["p95"] == pytest.approx(10.0, rel=0.3)
    # merged output renders and passes the strict checker
    _assert_strict_exposition(render_prometheus(merged))
    # bounds mismatch is a hard error, not silent garbage
    other = MetricsRegistry()
    other.histogram("serve.latency_s", buckets=(1.0, 2.0)).observe(0.5)
    with pytest.raises(ValueError, match="different bounds"):
        merge_snapshots([regs[0].snapshot(), other.snapshot()])


# ----------------------------------------------------------- SLO oracle


def test_frac_within_and_burn_rate_oracle():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    values = [0.1] * 6 + [5.0] * 4      # 60% within 1s by construction
    for v in values:
        h.observe(v)
    snap = h.snapshot()
    assert slo_mod.frac_within(snap, 1.0) == pytest.approx(0.6, abs=0.05)
    assert slo_mod.frac_within(snap, 100.0) == 1.0   # >= max
    assert slo_mod.frac_within(snap, 0.001) == 0.0   # < min
    assert slo_mod.frac_within({"count": 0}, 1.0) is None
    # burn rate: (1 - frac) / (1 - target)
    assert slo_mod.burn_rate(0.6, 0.9) == pytest.approx(4.0)
    assert slo_mod.burn_rate(1.0, 0.9) == 0.0
    assert slo_mod.burn_rate(None, 0.9) is None
    # zero error budget: any badness burns infinitely fast
    assert slo_mod.burn_rate(0.5, 1.0) == math.inf
    assert slo_mod.burn_rate(1.0, 1.0) == 0.0
    # offline form used by bench_serving --slo: same bucket math
    assert slo_mod.frac_within_values(values, 1.0) == pytest.approx(
        0.6, abs=0.05)


def test_slo_spec_validation_and_ratio_kind():
    with pytest.raises(ValueError):
        slo_mod.SLOSpec(name="x", target=1.5)
    with pytest.raises(ValueError):
        slo_mod.SLOSpec(name="x", target=0.9, kind="nope")
    spec = slo_mod.SLOSpec(name="goodput", target=0.99, kind="ratio")
    snap = {"cluster.completions_ok": {"type": "counter", "value": 98},
            "cluster.completions_shed": {"type": "counter", "value": 2}}
    res = slo_mod.evaluate(spec, snap)
    assert res["count"] == 100
    assert res["frac_good"] == pytest.approx(0.98)
    assert res["burn_rate"] == pytest.approx(2.0)   # 0.02 / 0.01
    # no data: burn is None, not a paging alert
    empty = slo_mod.evaluate(spec, {})
    assert empty["frac_good"] is None and empty["burn_rate"] is None


def test_burn_rate_tracker_multi_window():
    """Hand oracle: 100 fast completions early, then 100 slow ones.  The
    lifetime view is half-good, but the trailing window must see ONLY the
    slow regime and burn at the full 1/(1-target) rate."""
    reg = MetricsRegistry()
    spec = slo_mod.SLOSpec(name="lat", target=0.9, metric="lat_s",
                           threshold_s=1.0)
    tracker = slo_mod.BurnRateTracker([spec], windows=(30.0, 300.0),
                                      registry=reg)
    src = MetricsRegistry()
    h = src.histogram("lat_s")
    for _ in range(100):
        h.observe(0.01)
    tracker.sample(1000.0, src.snapshot())
    for _ in range(100):
        h.observe(50.0)
    tracker.sample(1040.0, src.snapshot())
    (res,) = tracker.evaluate(now=1040.0)
    assert res["count"] == 200
    assert res["frac_good"] == pytest.approx(0.5, abs=0.02)
    assert res["burn_rate"] == pytest.approx(5.0, rel=0.1)  # 0.5/0.1
    w30 = res["windows"]["30s"]
    # baseline = the t=1000 sample (strictly older than now-30s): the
    # window diff holds only the 100 slow observations
    assert w30["count"] == 100
    assert w30["frac_good"] == pytest.approx(0.0, abs=0.02)
    assert w30["burn_rate"] == pytest.approx(10.0, rel=0.1)
    w300 = res["windows"]["300s"]
    assert w300["count"] == 200          # no sample older than the window
    # gauges published for /metricsz
    assert reg.gauge("slo.lat.burn_30s").value == pytest.approx(
        10.0, rel=0.1)
    assert reg.gauge("slo.lat.frac_good").value == pytest.approx(
        0.5, abs=0.02)
    # no samples yet -> evaluable, burn None, windows empty
    fresh = slo_mod.BurnRateTracker([spec], registry=reg)
    (r0,) = fresh.evaluate()
    assert r0["burn_rate"] is None and r0["windows"] == {}


# ------------------------------------------------------- StatuszServer


def test_statusz_server_endpoints():
    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(7)
    boom = {"on": False}

    def status():
        if boom["on"]:
            raise RuntimeError("racy dict")
        return {"slots": {"total": 4}}

    srv = StatuszServer(role="decode", index=1, providers={
        "health": lambda: {"phase": "serving"},
        "status": status,
        "metrics": reg.snapshot,
    })
    try:
        port = srv.start()
        code, body, headers = _fetch(port, "/healthz")
        assert code == 200
        health = json.loads(body)
        assert health["status"] == "ok" and health["role"] == "decode"
        assert health["index"] == 1 and health["phase"] == "serving"
        code, body, _ = _fetch(port, "/statusz")
        assert code == 200 and json.loads(body)["slots"]["total"] == 4
        code, body, headers = _fetch(port, "/metricsz")
        assert code == 200
        assert headers["Content-Type"].startswith("text/plain")
        assert "serve_requests 7" in body.splitlines()
        _assert_strict_exposition(body)
        code, body, _ = _fetch(port, "/tracez")
        assert code == 200 and "spans" in json.loads(body)
        code, body, _ = _fetch(port, "/flightz")
        assert code == 200 and json.loads(body)["events"] == []
        # unknown path -> 404
        code, _, _ = _fetch(port, "/nope")
        assert code == 404
        # a provider racing a mutating dict -> 503 (retryable), not a crash
        boom["on"] = True
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz", timeout=10)
            assert False, f"expected 503, got {resp.status}"
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert "racy dict" in json.loads(e.read().decode())["error"]
        boom["on"] = False
        code, _, _ = _fetch(port, "/statusz")
        assert code == 200
    finally:
        srv.stop()
    # stopped: connections refused
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                               timeout=2)


# ----------------------------------------------------------- benchdiff


@pytest.fixture(scope="module")
def benchdiff():
    return _load_tool("benchdiff")


def _write_jsonl(path, records):
    with open(path, "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")


_GOOD = {"metric": "serving", "git_sha": "aaa", "wall_time": 100.0,
         "tokens_per_sec": 100.0, "p95_latency_s": 1.0, "wall_s": 10.0,
         "within_slo_frac": 0.99}


def test_benchdiff_self_and_noise_pass(benchdiff, tmp_path, capsys):
    base = tmp_path / "a.jsonl"
    cand = tmp_path / "b.jsonl"
    _write_jsonl(base, [_GOOD])
    _write_jsonl(cand, [dict(_GOOD, git_sha="bbb", wall_time=200.0,
                             tokens_per_sec=92.0,      # -8%: inside band
                             p95_latency_s=1.2)])      # +20%: inside band
    assert benchdiff.main([str(base), str(cand)]) == 0
    assert "no regressions" in capsys.readouterr().out


def test_benchdiff_fails_on_regression(benchdiff, tmp_path, capsys):
    base = tmp_path / "a.jsonl"
    cand = tmp_path / "b.jsonl"
    _write_jsonl(base, [_GOOD])
    _write_jsonl(cand, [dict(_GOOD, tokens_per_sec=50.0,   # -50%
                             p95_latency_s=3.0)])          # +200%
    assert benchdiff.main([str(base), str(cand)]) == 1
    err = capsys.readouterr().err
    assert "tokens_per_sec" in err and "p95_latency_s" in err
    # a tightened band flips a pass into a fail
    _write_jsonl(cand, [dict(_GOOD, tokens_per_sec=92.0)])
    assert benchdiff.main([str(base), str(cand)]) == 0
    assert benchdiff.main(["--band", "tokens_per_sec=0.05",
                           str(base), str(cand)]) == 1


def test_benchdiff_picks_latest_by_wall_time(benchdiff, tmp_path):
    base = tmp_path / "a.jsonl"
    cand = tmp_path / "b.jsonl"
    _write_jsonl(base, [_GOOD])
    # the regressed record is FIRST in the file but NEWEST by wall_time:
    # file order must not win
    _write_jsonl(cand, [dict(_GOOD, wall_time=300.0, tokens_per_sec=10.0),
                        dict(_GOOD, wall_time=200.0)])
    assert benchdiff.main([str(base), str(cand)]) == 1


def test_benchdiff_usage_errors(benchdiff, tmp_path):
    base = tmp_path / "a.jsonl"
    _write_jsonl(base, [_GOOD])
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert benchdiff.main([str(base), str(empty)]) == 2
    other = tmp_path / "other.jsonl"
    _write_jsonl(other, [dict(_GOOD, metric="different")])
    assert benchdiff.main([str(base), str(other)]) == 2
    assert benchdiff.main(["--band", "nonsense=0.1",
                           str(base), str(base)]) == 2
    assert benchdiff.main(["--band", "tokens_per_sec=abc",
                           str(base), str(base)]) == 2


# ------------------------------------------------- stamp_record ordering


def test_stamp_record_wall_time_monotonic():
    from progen_tpu.observe import platform as plat

    r1 = plat.stamp_record({"metric": "x"})
    r2 = plat.stamp_record({"metric": "x"})
    assert r2["wall_time"] > r1["wall_time"]
    # caller-provided wall_time (captured outside a traced region) is
    # kept, but clamped so in-process ordering never goes backwards
    r3 = plat.stamp_record({"metric": "x"}, wall_time=r2["wall_time"] - 50)
    assert r3["wall_time"] > r2["wall_time"]
    future = r3["wall_time"] + 1000.0
    r4 = plat.stamp_record({"metric": "x"}, wall_time=future)
    assert r4["wall_time"] == pytest.approx(future)


# ------------------------------------------------ traceview degradation


def test_traceview_degrades_on_empty_dump_dir(tmp_path, capsys):
    tv = _load_tool("traceview")
    # empty directory: the read-only views degrade and exit 0
    assert tv.main(["--summarize", str(tmp_path)]) == 0
    assert tv.main(["--summarize", "--top", "3", str(tmp_path)]) == 0
    assert "no spans" in capsys.readouterr().err
    # merge mode still signals the empty input
    assert tv.main([str(tmp_path)]) == 1
    # a driver-only dump with zero spans: same degradation
    dump = tmp_path / "trace_driver.json"
    dump.write_text(json.dumps({"process": "driver", "pid": 1,
                                "meta": {}, "spans": []}))
    assert tv.main(["--summarize", str(tmp_path)]) == 0


# ------------------------------------------------- real 2-process fleet


def _statusz_spec(statusz):
    from progen_tpu.models import ProGenConfig
    from progen_tpu.serve.worker import make_spec

    cfg = ProGenConfig(
        num_tokens=32, dim=16, seq_len=24, depth=2, window_size=4,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
    )
    kw = dict(num_slots=4, chunk_size=4, max_len=24, prefill_batch=2,
              handoff_depth=2)
    return make_spec(cfg, mixed_precision=False, init_seed=7, engine=kw,
                     statusz=statusz)


def _drive(statusz):
    from progen_tpu.decode.engine import Request
    from progen_tpu.serve.cluster import ServeCluster

    cluster = ServeCluster(_statusz_spec(statusz))
    probes = {}
    try:
        for i in range(3):
            cluster.submit(Request(uid=i, tokens=[1 + i, 2, 3],
                                   max_new_tokens=4, top_k=None,
                                   temperature=0.0, seed=i))
        done = cluster.drain(timeout=300.0)
        if statusz:
            # the per-worker staleness gauges need one heartbeat from each
            # worker, and workers beat once a second from their ready
            # frame: a fleet that drains three requests inside that second
            # has sent none yet
            deadline = time.monotonic() + 10.0
            while len(cluster._hb) < 2 and time.monotonic() < deadline:
                cluster._pump(0.1)  # the driver's event loop
            ports = cluster.stats()["statusz_ports"]
            assert set(ports) == {"driver", "prefill:0", "decode:0"}
            for who, port in ports.items():
                code, body, _ = _fetch(port, "/healthz")
                assert code == 200
                health = json.loads(body)
                assert health["status"] == "ok"
                code, text, _ = _fetch(port, "/metricsz")
                assert code == 200
                probes[who] = (health, text)
            # the driver /statusz carries the fleet view + SLO block
            code, body, _ = _fetch(ports["driver"], "/statusz")
            assert code == 200
            probes["driver_statusz"] = json.loads(body)
    finally:
        cluster.shutdown()
    toks = {c.uid: [int(t) for t in c.tokens] for c in done if c.ok}
    assert len(toks) == 3
    return toks, probes


@pytest.mark.multiproc
def test_cluster_statusz_live_and_zero_perturbation():
    """Every process of a real 2-process cluster (driver + prefill:0 +
    decode:0) serves live /healthz + /metricsz while the fleet runs, the
    driver /statusz aggregates worker registries and SLO burn rates —
    and the served tokens are IDENTICAL to an introspection-disabled
    run."""
    pytest.importorskip("jax")

    with_toks, probes = _drive(statusz=True)
    # worker healthz reports the serving phase; driver reports its peers
    assert probes["prefill:0"][0]["phase"] == "serving"
    assert probes["decode:0"][0]["phase"] == "serving"
    assert set(probes["driver"][0]["peers"]) == {"prefill:0", "decode:0"}
    # every process's exposition passes the strict line checker
    for who in ("driver", "prefill:0", "decode:0"):
        _assert_strict_exposition(probes[who][1])
    # the driver merged the fleet: its exposition carries the decode
    # engine's chunk counter and the per-worker up/staleness gauges
    driver_text = probes["driver"][1]
    assert re.search(r'^cluster_up\{idx="0",role="decode"\} 1$',
                     driver_text, re.M), driver_text
    assert re.search(r'^cluster_up\{idx="0",role="prefill"\} 1$',
                     driver_text, re.M)
    assert re.search(r'^cluster_worker_age_s\{idx="0",role="decode"\} ',
                     driver_text, re.M)
    status = probes["driver_statusz"]
    assert "cluster.latency_s" in status["metrics"]
    slo_block = {s["name"]: s for s in status["slo"]}
    assert set(slo_block) == {"latency_p95_2s", "goodput"}
    assert slo_block["goodput"]["count"] >= 3
    for res in slo_block.values():
        assert set(res["windows"]) == {"60s", "300s", "900s"}

    without_toks, _ = _drive(statusz=False)
    assert with_toks == without_toks, (
        "introspection plane perturbed served tokens")
