"""The yardstick's arithmetic: traffic, percentiles, trace reduction."""

import gzip
import math
import os

import numpy as np
import pytest

from perf.lib import flops, stats, traffic, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN = {"records": 256, "group": 16, "prefix": "# ",
         "residues": {"kind": "lognormal", "median": 260, "sigma": 0.7,
                      "min": 16, "max": 1021}}
SERVE = {"arrivals": {"kind": "open", "rate": 8.0},
         "prime_tokens": {"kind": "uniform_int", "min": 8, "max": 64},
         "generated_tokens": {"kind": "lognormal", "median": 300,
                              "sigma": 0.6, "min": 64, "max": 900}}


def test_train_records_repeat_for_a_seed_and_differ_for_another():
    a = traffic.train_records(TRAIN, 2 ** 31 + 5)
    assert a == traffic.train_records(TRAIN, 2 ** 31 + 5)
    b = traffic.train_records(TRAIN, 7)
    assert a != b
    # the schedule is the file's: the same sizes in the same order, other
    # residues; another schedule_seed reorders them
    assert list(map(len, a)) == list(map(len, b))
    c = traffic.train_records(dict(TRAIN, schedule_seed=1), 7)
    assert list(map(len, c)) != list(map(len, b))
    assert sorted(map(len, c)) == sorted(map(len, b))
    per_batch = traffic.record_tokens(a, 1024).reshape(-1, 16).sum(1)
    assert per_batch.std() / per_batch.mean() < 0.05
    assert all(r.startswith(b"# ") and 18 <= len(r) <= 1023 for r in a)


def test_serve_requests_repeat_for_a_seed_and_keep_the_load():
    a = traffic.serve_requests(SERVE, 3_000_000_001, 30.0, 256)
    assert a == traffic.serve_requests(SERVE, 3_000_000_001, 30.0, 256)
    b = traffic.serve_requests(SERVE, 4, 30.0, 256)
    assert a != b and len(a) == len(b) == 240
    for key in ("due", "max_new"):  # the schedule is the file's
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [len(r["prime"]) for r in a] == [len(r["prime"]) for r in b]
    assert [r["prime"] for r in a] != [r["prime"] for r in b]
    assert [r["seed"] for r in a] != [r["seed"] for r in b]
    c = traffic.serve_requests(dict(SERVE, schedule_seed=1), 4, 30.0, 256)
    assert ([r["max_new"] for r in c] != [r["max_new"] for r in b]
            and sorted(r["max_new"] for r in c)
            == sorted(r["max_new"] for r in b))
    assert 28.0 < a[-1]["due"] < 30.0
    assert all(x["due"] <= y["due"] for x, y in zip(a, a[1:]))
    assert all(0 not in r["prime"] and 8 <= len(r["prime"]) <= 64 for r in a)


def test_backlog_is_due_at_once_and_staggered():
    spec = dict(SERVE, arrivals={"kind": "backlog", "requests_per_second": 2},
                stagger={"first": 8, "min": 0.05, "max": 1.0})
    reqs = traffic.serve_requests(spec, 5, 10.0, 256)
    assert len(reqs) == 20 and all(r["due"] == 0.0 for r in reqs)
    plain = traffic.serve_requests(dict(spec, stagger=None), 5, 10.0, 256)
    assert all(a["max_new"] <= b["max_new"]
               for a, b in zip(reqs[:8], plain[:8]))
    assert [r["max_new"] for r in reqs[8:]] == [r["max_new"] for r in plain[8:]]


@pytest.mark.parametrize("n,p", [(9, 50.0), (20, 50.0), (100, 90.0),
                                 (199, 90.0), (200, 95.0), (1000, 99.0),
                                 (10_000, 99.9)])
def test_highest_percentile_leaves_ten_samples_beyond(n, p):
    assert stats.highest_percentile(n) == p


def test_percentile_matches_numpy_and_ranks_missing_last():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for p in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    assert stats.percentile(xs + [math.inf], 100) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_required_flops_and_parameters_of_the_published_sizes():
    small = dict(num_tokens=256, dim=1024, depth=12, heads=8, dim_head=128,
                 window_size=256, seq_len=1024, ff_mult=4, ff_glu=True,
                 global_mlp_depth=2)
    assert flops.param_count(small) == 199_904_512
    large = dict(small, dim=1536, depth=36, heads=12, window_size=512)
    assert flops.param_count(large) == 1_353_068_288
    # about six operations per parameter and slot, plus attention
    per_slot = flops.train_flops_per_slot(small)
    assert 6 * 190e6 < per_slot < 6 * 230e6
    assert flops.decode_state_bytes_per_row(small, 1024) == 33_603_584


def test_union_gaps_and_attribution():
    us = 1000.0  # the trace's clock is in nanoseconds
    ops = [("a", 0, 10 * us), ("b", 5 * us, 20 * us), ("a", 40 * us, 50 * us),
           ("c", 45 * us, 48 * us), ("d", 50.5 * us, 52 * us)]
    assert xplane.union_intervals(ops) == [
        (0, 20 * us), (40 * us, 50 * us), (50.5 * us, 52 * us)]
    assert xplane.busy_ns(ops) == 31.5 * us
    idle = xplane.gaps(ops, 0, 70 * us)
    assert idle == [(20 * us, 40 * us), (50 * us, 50.5 * us),
                    (52 * us, 70 * us)]
    assert xplane.top_ops(ops, 2) == [["a", 20e-6], ["b", 15e-6]]
    # perf.step covers the first gap too, but serve.harvest is the shorter
    # span covering most of it; the half-microsecond gap is the device's own
    host = [("perf.trace_window", 0, 70 * us), ("perf.step", 0, 38 * us),
            ("serve.harvest", 21 * us, 37 * us), ("perf.sleep", 51 * us, 70 * us)]
    assert xplane.attribute_gaps(idle, host) == [
        ["serve.harvest", 20e-6], ["perf.sleep", 18e-6],
        ["(gaps under 10 us)", 0.5e-6]]
    assert xplane.clip(ops[:3], 8 * us, 42 * us) == [
        ("a", 8 * us, 10 * us), ("b", 8 * us, 20 * us),
        ("a", 40 * us, 42 * us)]


@pytest.mark.parametrize("name,want", [
    ("%attn2.5 = (bf16[128,1280,128]{2,1,0:T(8,128)(2,1)}, bf16[128,1280,128]"
     "{2,1,0}) custom-call(bf16[128,1280,128]{2,1,0} %bitcast.1941)",
     ("attn", "custom-call", "(bf16[128,1280,128], bf16[128,1280,128])")),
    ("%fusion.319 = bf16[16,1024,1024]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[16]"
     "{0} %custom-call.3), kind=kOutput", ("fusion", "fusion",
                                             "bf16[16,1024,1024]")),
    ("jit__train_step_body(684281494301890381)",
     ("jit__train_step_body(684281494301890381)", "", "")),
])
def test_op_names_are_parsed_not_searched(name, want):
    assert xplane.parse_op(name) == want
    # a fusion that merely consumes a custom call is not a kernel
    assert xplane.is_kernel_call(name) == (want[1] == "custom-call")


def test_reduction_of_the_recorded_tpu_trace(tmp_path):
    """perf/tests/data/small_tpu.xplane.pb.gz: three 1024^2 matmuls on one
    v5e chip with 4 ms sleeps between them, recorded in PR 23."""
    packed = os.path.join(DATA, "small_tpu.xplane.pb.gz")
    path = tmp_path / "small_tpu.xplane.pb"
    with gzip.open(packed, "rb") as src:
        path.write_bytes(src.read())
    r = xplane.reduce_trace(str(path))
    assert r["chips"] == 1
    assert 0.012 < r["window_s"] < 0.1
    assert 0 < r["busy_s"] < 0.25 * r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    # the device idles while the host sleeps
    assert r["idle_gaps"][0][0] == "perf.sleep"
