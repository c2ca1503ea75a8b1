"""The Qwen3-Next cell rehearsed on the CPU at tiny widths through the
harness (as test_mimo.py does for MiMo's), the configuration file against the
catalog row and the program's defaults, the cost functions against hand
counts and the program's own parameter count, the cell's entries against the
set ISSUE 63 names, and the control tool's variants.  Nothing here measures
anything."""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from perf.lib import harness, qwen3next_cost, reference_qwen3next
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-qwen3next-longdoc-backlog"
CONFIG = harness.load_config("qwen3-next-80b-a3b-ep4pp6")
BENCH = harness.load_benchmark()
SHARES = {"decode.hbm_share.qwen3next", "prefill.mfu.qwen3next"}
OWN = SHARES | {"gdn.state_share_of_step_bytes.qwen3next",
                "gdn.scan_slots_per_real_token.qwen3next"}
WINDOW = {f"window.{k}.backlog" for k in (
    "admit_share", "chunk_share", "chunk_step_ms", "delivery_gap_p50_ms",
    "delivery_gap_p95_ms")}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "moe.experts_touched_share",
    "attn.full_rows_read_per_live_row"}
METRICS = SHARED | WINDOW | FROM_THE_FAMILY
REDUCED = ["num_hidden_layers", "experts_held", "vocab_size"]

TINY = dict(
    name="tiny-qwen3next", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, num_hidden_layers=8, full_attention_interval=4,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=1e4, num_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, rms_norm_eps=1e-6, max_position_embeddings=128,
    experts_held=8, first_expert=0, chunk=4, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")


def test_every_published_key_is_in_the_file_and_no_width_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 8         # two whole periods
    assert CONFIG["num_experts"] == 512 and CONFIG["experts_held"] == 128
    assert CONFIG["vocab_size"] == 37984 == row["config"]["vocab_size"] // 4
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    for key in ("left_out", "in_proj_order", "a_range", "dt_range", "chunk",
                "router_logit_std"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert CONFIG["deployment"]["chips"] == 24
    assert CONFIG["deployment"]["pipeline_stages"] == 6
    assert "3,667,251,328" in CONFIG["parameters"]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.qwen3_next import DELTA, FULL, Qwen3NextConfig

    default = Qwen3NextConfig()
    c = Qwen3NextConfig.from_dict(CONFIG)
    assert c == Qwen3NextConfig(num_hidden_layers=8, vocab_size=37984,
                                experts_held=128)
    for key, value in CONFIG["published"].items():
        if hasattr(default, key):
            got = getattr(default, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    for key in ("router_logit_std", "prefill_bucket", "chunk"):
        assert getattr(default, key) == CONFIG[key], key
    assert list(default.dt_range) == CONFIG["dt_range"]
    assert list(default.a_range) == CONFIG["a_range"]
    assert c.layer_types == (DELTA, DELTA, DELTA, FULL) * 2
    assert (c.experts_held, c.router_width, c.first_expert) == (128, 512, 0)
    assert c.moe_topk == 10 and c.rotary_dim == 64


def test_benchmark_entries_of_the_cell():
    """One configuration, the cell with the traffic MiMo's has, its four
    own entries, and the lists ISSUE 63 names — no other."""
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == {**entry, "config": "qwen3-next-80b-a3b-ep4pp6",
                     "traffic": "backlog-longdoc", "chips": 1}
    mimo = next(w for w in BENCH["workloads"]
                if w["name"] == "serve-mimo-longdoc-backlog")
    assert mimo["traffic"] == entry["traffic"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == entry["config"])
    assert config["reduced"] == REDUCED
    assert config["source"] == CONFIG["source"]
    workload = harness.load_workload(CELL)
    assert workload["engine"] == {"num_slots": 32, "chunk_size": 32,
                                  "max_len": 17408}
    assert workload["traffic"] == "backlog-longdoc"
    mine = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "per_layer")}
    assert mine == METRICS
    for name in OWN:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
        assert harness.load_metric(name)["layer"] == m["layer"]
    assert {m["name"] for m in harness.cell_metrics(
        BENCH, CELL, "end_to_end")} == {"setup_s", "serve_tok_s"}
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])


# --------------------------------------------------------------- the costs


def test_parameter_counts_by_hand():
    c = CONFIG
    assert (qwen3next_cost.delta_matrices(c)
            + qwen3next_cost.delta_small(c)) == 33_718_464
    assert qwen3next_cost.attention_matrices(c) + 2 * 256 == 27_263_488
    assert (qwen3next_cost.expert_layer_outside(c)
            + 128 * qwen3next_cost.expert_params(c)) == 406_849_536
    assert qwen3next_cost.total_params(c) == 3_667_251_328
    assert qwen3next_cost.carry_bytes_per_row(c) == 2_097_152
    assert qwen3next_cost.tail_bytes_per_row(c) == 49_152
    assert qwen3next_cost.kv_bytes_per_row(c) == 2_048
    slot = qwen3next_cost.slot_bytes(c, 17408)
    assert slot == 6 * (2_097_152 + 49_152) + 2 * 17408 * 2048 == 84_180_992
    # of a slot the six delta layers are 15 %
    assert 0.15 < 6 * (2_097_152 + 49_152) / slot < 0.16


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import qwen3_next

    c = qwen3_next.Qwen3NextConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: qwen3_next.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    assert made == qwen3next_cost.total_params(CONFIG) == 3_667_251_328
    assert shapes["head"].shape == (2048, 37984)
    assert shapes["layers"][0]["mixer"]["in_proj"].shape == (2048, 12288)
    assert shapes["layers"][3]["mixer"]["wq"].shape == (2048, 8192)
    assert shapes["layers"][0]["experts"]["wg"].shape == (128, 2048, 512)
    assert shapes["layers"][0]["shared_gate"].shape == (2048,)


def test_prefill_flops_and_decode_bytes_by_hand():
    c = CONFIG
    # one row of one chunk and one token more
    n = 65
    pairs = 64 * 65 / 2 + 1
    assert qwen3next_cost.chunk_pairs(n, 64) == pairs
    scan = (2 * 2 * 16 * 128 * pairs + 2 * 32 * 256 * pairs
            + 2 * 32 * 128 * pairs + 3 * 2 * 32 * 128 * 128 * n)
    assert qwen3next_cost.scan_flops(c, n) == scan
    outside = qwen3next_cost.params_outside_experts(c)
    assert outside == (6 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
                       + 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
                       + 8 * (2048 * 512 + 3 * 2048 * 512 + 2048))
    held = 2.5 * 8 * n
    want = (n * 2 * outside + 6 * scan + 2 * 4 * 16 * 256 * n * (n + 1) / 2
            + 2 * 3 * 2048 * 512 * held + 2 * 2048 * 37984)
    assert qwen3next_cost.prefill_flops(c, [n], held) == want
    # a step of 32 live rows at 6,000 tokens that touches 59 experts a layer
    terms = qwen3next_cost.decode_terms(
        c, 1, 8 * 59, 32 * 6 * 2 * 2_097_152, 32 * 6000)
    assert terms["carry"] == 32 * 6 * 2 * 2_097_152         # 0.81 GB
    assert terms["conv_tails"] == 32 * 6 * 2 * 49_152
    assert terms["grown_rows"] == 32 * 6000 * 2 * 2048
    assert terms["experts_touched"] == 8 * 59 * 3 * 2048 * 512 * 2
    assert terms["head"] == 2048 * 37984 * 2
    assert sum(terms.values()) == qwen3next_cost.decode_bytes(
        c, 1, 8 * 59, 32 * 6 * 2 * 2_097_152, 32 * 6000)
    assert 0.15 < terms["carry"] / sum(terms.values()) < 0.25


def test_direct_primes_put_a_chunks_edges_into_slots_that_long_rows_left():
    workload = harness.load_workload(CELL)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    check = workload["correct"]["direct"]
    mimo_runner = harness.load_module("perf/runners/serve_mimo.py")
    long, second = mimo_runner.direct_lengths(check, workload, 63, 32)
    assert len(long) == 5 and all(8001 <= n <= 8192 for n in long)
    assert list(second[:4]) == [1, 63, 64, 65]      # chunks of 64
    assert 67 <= second[4] <= 1021 and all(
        second[4] % d for d in range(2, 32))
    assert 16001 <= second[5] <= 16300 and len(second) == 32
    assert all(512 <= n <= 16384 for n in second[6:])
    at = mimo_runner.compared_slots(check, 32)
    assert list(at[:6]) == [0, 1, 2, 3, 4, 5] and len(at) == 8
    assert at[-1] == 31


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 63: the program's reading nearest each limit
    over its seeds, the limit, and the nearest reading OF THE SAME QUANTITY
    that the limit has to refuse (``perf/tools/qwen3next_lowp.py``, my chip
    runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    names = {"direct.row_rms_limit": check["direct"]["row_rms_limit"],
             "direct.rms_limit": check["direct"]["rms_limit"],
             "direct.assignments_limit": check["direct"]["assignments_limit"],
             "over_share_limit": check["over_share_limit"]}
    assert set(readings) - {"why"} == set(names)
    for name, limit in names.items():
        program, control = readings[name]
        assert program < limit < control, name
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["probes"] == 1 and check["probe_new_tokens"] == 128


def test_the_control_tool_plants_each_omission_in_the_references_own_terms():
    """``perf/tools/qwen3next_lowp.py`` at a tiny size: each variant traces
    the reference through the wrapped operations or a changed key, every
    control moves the result further than the stated precision does, the
    tool's chunked form is the recurrence, and nothing stays patched."""
    import jax

    from progen_tpu.models import qwen3_next

    tool = harness.load_module("perf/tools/qwen3next_lowp.py")
    assert set(tool.VARIANTS) == {
        "as-stated", "chunked", "one-notch-below", "carry-bf16", "no-erase",
        "no-l2norm", "gate-before-norm", "rotate-all", "no-attention-gate",
        "shared-ungated", "T-bf16"}
    c = qwen3_next.Qwen3NextConfig.from_dict(TINY)
    params = qwen3_next.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_qwen3next.forward_row(params, tokens, TINY)
        blocked, _ = reference_qwen3next.forward_row(params, tokens, TINY,
                                                     q_block=16)
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    far = {}
    for name, (operands, islands, choice) in tool.VARIANTS.items():
        cfg = tool.config_for(TINY, choice)
        with tool.lowered(operands and getattr(jax.numpy, operands), islands,
                          choice), jax.default_matmul_precision("highest"):
            got, _ = reference_qwen3next.forward_row(params, tokens, cfg)
        far[name] = float(np.abs(np.asarray(got, np.float32) - want).mean())
    assert 0 < far["as-stated"] < far["one-notch-below"] < 1
    assert abs(far["chunked"] - far["as-stated"]) < 0.2 * far["as-stated"]
    for name in ("no-erase", "no-l2norm", "gate-before-norm", "rotate-all",
                 "no-attention-gate", "shared-ungated"):
        assert far[name] > 1.4 * far["as-stated"], (name, far)
    # in float32 the tool's chunked form is the recurrence token by token
    q, k, v = (jax.random.normal(jax.random.key(i), (23, 4, 8))
               for i in range(3))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    alpha = jax.nn.sigmoid(jax.random.normal(jax.random.key(3), (23, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.key(4), (23, 4)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            tool.chunked_recurrence(jax.numpy.float32, chunk=4)(
                q, k, v, alpha, beta),
            reference_qwen3next.recurrence(q, k, v, alpha, beta), atol=2e-5)
    for name in ("product", "softmax", "rms_norm", "route", "carry",
                 "delta_token", "recurrence", "unit", "gated_norm",
                 "attention_gate", "shared_gate"):
        assert getattr(reference_qwen3next, name).__module__ == (
            "perf.lib.reference_qwen3next")


# ------------------------------------------------------------ rehearsal


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture()
def checkout(tmp_path, monkeypatch, own_registry):
    """A temporary copy of the benchmark with a tiny cell of this family
    ADDED: new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    _dump(root / "perf/configs/tiny-qwen3next.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-longdoc"), name="tiny-longdoc",
        arrivals={"kind": "backlog", "requests_per_second": 100.0},
        prime_tokens={"kind": "lognormal", "median": 10, "sigma": 0.9,
                      "min": 2, "max": 30},
        generated_tokens={"kind": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 6, "max": 22})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    traffic["sampling"] = dict(traffic["sampling"], top_k=5)
    _dump(root / "perf/traffic/tiny-longdoc.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-qwen3next", config="tiny-qwen3next",
                    traffic="tiny-longdoc",
                    engine={"num_slots": 32, "chunk_size": 6, "max_len": 64})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"],
                    long_prime_tokens=[20, 24],
                    readmit_prime_tokens=[1, 3, 4, 5],
                    prime_number_between=[7, 13],
                    longest_prime_tokens=[31, 34], compared_slots=8,
                    row_rms_limit=0.6, rms_limit=0.4, assignments_limit=0.5))
    _dump(root / "perf/workloads/serve-tiny-qwen3next.json", workload)
    bench["configs"].append({
        "name": "tiny-qwen3next", "source": "perf/tests",
        "file": "perf/configs/tiny-qwen3next.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-qwen3next", "config": "tiny-qwen3next",
        "traffic": "tiny-longdoc", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-qwen3next")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_qwen3next_harness", root / "perf/lib/harness.py")
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    assert copy.ROOT == str(root)

    def any_devices(chips):
        import jax

        return jax.devices()

    monkeypatch.setattr(copy, "require_tpu", any_devices)
    return root, copy


def test_the_cell_runs_end_to_end_at_a_tiny_size(checkout):
    root, copy = checkout
    result = copy.run_cell("serve-tiny-qwen3next", 2 ** 31 + 33, 1.5, False,
                           0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-qwen3next", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 1.0 < value["moe.held_assignments_per_token"] < 2.0  # 3 x 8 / 16
    assert value["attn.full_rows_read_per_live_row"] > 1
    assert 0 < value["moe.experts_touched_share"] <= 1     # of the 8 held
    assert value["gdn.scan_slots_per_real_token.qwen3next"] >= 1
    assert 0 < value["gdn.state_share_of_step_bytes.qwen3next"] < 1
    # the shares' reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 32}},
           "counters": {"admitted_primes": [5, 20]}, "trace": None}
    for name in SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0


def test_readers_of_the_new_metrics_find_nothing_in_a_program_without_them(
        monkeypatch):
    """On the parent the registry has no such gauge: ``None``, no raise."""
    from progen_tpu.observe import metrics

    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    obs = {"config": CONFIG, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 32}},
           "counters": {"admitted_primes": [300]}, "trace": None}
    for name in FROM_THE_FAMILY:
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read(obs, spec) is None, name
