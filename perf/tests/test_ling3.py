"""The Ling-3.0-flash cell rehearsed on the CPU at tiny widths through the
harness (as test_qwen3next.py does for Qwen3-Next's), the configuration file
against the catalog row and the program's defaults, the cost functions
against hand counts and the program's own parameter count, the cell's
entries against the set ISSUE 65 names, and the control tool's variants.
Nothing here measures anything."""

import importlib.util
import json
import os
import shutil
from unittest import mock

import numpy as np
import pytest

from perf.lib import harness, ling3_cost, reference_ling3
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-ling3-longdoc-backlog"
CONFIG = harness.load_config("ling-3.0-flash-ep4pp7")
BENCH = harness.load_benchmark()
SHARES = {"decode.hbm_share.ling3", "prefill.mfu.ling3"}
OWN = SHARES | {"kda.state_share_of_step_bytes.ling3",
                "kda.scan_slots_per_real_token.ling3"}
WINDOW = {f"window.{k}.backlog" for k in (
    "admit_share", "chunk_share", "chunk_step_ms", "delivery_gap_p50_ms",
    "delivery_gap_p95_ms")}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "moe.experts_touched_share",
    "mla.rows_read_per_live_row"}
METRICS = SHARED | WINDOW | FROM_THE_FAMILY
REDUCED = ["num_hidden_layers", "experts_held", "vocab_size"]
LAYER_IDS = [0, 37, 38, 39, 40, 41]

TINY = dict(
    name="tiny-ling3", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, intermediate_size=96, num_hidden_layers=6,
    first_k_dense_replace=2, layer_group_size=6, num_attention_heads=2,
    head_dim=8, short_conv_kernel_size=4, kda_lower_bound=-5,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    rope_theta=1e4, num_experts=16, num_experts_per_tok=3, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32,
    expert_swiglu_limit_list=[0] * 9 + [1.0, 1.0, 1.0],
    share_expert_swiglu_limit_list=[0] * 8 + [1.5, 1.5, 1.5, 2.0],
    layer_ids=[0, 7, 8, 9, 10, 11], rms_norm_eps=1e-6,
    max_position_embeddings=128, experts_held=8, first_expert=0, chunk=8,
    block=4, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash")


def test_every_published_key_is_in_the_file_and_no_width_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 6         # one whole period
    assert CONFIG["layer_ids"] == LAYER_IDS
    assert CONFIG["num_experts"] == 512 and CONFIG["experts_held"] == 128
    assert CONFIG["vocab_size"] == 39296 == row["config"]["vocab_size"] // 4
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    # the two limit lists at the held layers are the row's
    for key, want in (("expert_swiglu_limit_list", [0, 4, 4, 4, 4, 4]),
                      ("share_expert_swiglu_limit_list", [0, 5, 5, 5, 7, 7])):
        assert [CONFIG[key][i] for i in LAYER_IDS] == want
        assert [row["config"][key][i] for i in LAYER_IDS] == want
    for key in ("left_out", "full_layer_position", "kda_gate_form",
                "kda_output_gate", "qk_norm", "swiglu_limit_form", "a_range",
                "dt_bias_range", "chunk", "router_logit_std",
                "expert_in_gain"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert CONFIG["deployment"]["chips"] == 28
    assert CONFIG["deployment"]["pipeline_stages"] == 7
    assert "4,354,531,616" in CONFIG["parameters"]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.bailing_hybrid import (
        DELTA,
        LATENT,
        BailingHybridConfig,
    )

    default = BailingHybridConfig()
    c = BailingHybridConfig.from_dict(CONFIG)
    for key, value in CONFIG["published"].items():
        if hasattr(default, key) and not key.endswith("_limit_list"):
            assert getattr(default, key) == value, key
    for key in ("router_logit_std", "router_bias_std", "prefill_bucket",
                "chunk", "block", "expert_in_gain", "shared_in_gain"):
        assert getattr(default, key) == CONFIG[key], key
    assert list(default.a_range) == CONFIG["a_range"]
    assert list(default.dt_bias_range) == CONFIG["dt_bias_range"]
    assert c.layer_types == (DELTA,) * 5 + (LATENT,)
    assert [c.is_dense(i) for i in range(6)] == [True] + [False] * 5
    assert [c.limits(i) for i in range(6)] == [
        (0, 0), (4, 5), (4, 5), (4, 5), (4, 7), (4, 7)]
    assert (c.experts_held, c.router_width, c.first_expert) == (128, 512, 0)
    assert c.moe_topk == 8 and c.latent_width == 576


def test_benchmark_entries_of_the_cell():
    """One configuration, the cell with the traffic MiMo's and Qwen3-Next's
    have, its four own entries, and the lists ISSUE 65 names — no other."""
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == {**entry, "config": "ling-3.0-flash-ep4pp7",
                     "traffic": "backlog-longdoc", "chips": 1}
    for sibling in ("serve-mimo-longdoc-backlog",
                    "serve-qwen3next-longdoc-backlog"):
        assert next(w for w in BENCH["workloads"]
                    if w["name"] == sibling)["traffic"] == entry["traffic"]
    config = next(c for c in BENCH["configs"]
                  if c["name"] == entry["config"])
    assert config["reduced"] == REDUCED
    assert config["source"] == CONFIG["source"]
    workload = harness.load_workload(CELL)
    assert workload["engine"] == {"num_slots": 64, "chunk_size": 32,
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
    assert len(BENCH["workloads"]) == 15 and len(BENCH["per_layer"]) == 93


# --------------------------------------------------------------- the costs


def test_parameter_counts_by_hand():
    c = CONFIG
    assert (ling3_cost.delta_matrices(c)
            + ling3_cost.delta_small(c)) == 52_646_048
    assert ling3_cost.latent_matrices(c) + 512 == 31_965_696
    assert ling3_cost.dense_ffn(c) == 47_185_920
    assert (ling3_cost.expert_layer_outside(c) + 512
            + 128 * ling3_cost.expert_params(c)) == 762_184_192
    assert ling3_cost.total_params(c) == 4_354_531_616
    assert ling3_cost.carry_bytes_per_row(c) == 2_097_152
    assert ling3_cost.tail_bytes_per_row(c) == 73_728
    assert ling3_cost.latent_bytes_per_row(c) == 1_152
    slot = ling3_cost.slot_bytes(c, 17408)
    assert slot == 5 * (2_097_152 + 73_728) + 17408 * 1152 == 30_908_416
    # of a slot the five carries are a third
    assert 0.33 < 5 * 2_097_152 / slot < 0.36


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import bailing_hybrid

    c = bailing_hybrid.BailingHybridConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: bailing_hybrid.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    assert made == ling3_cost.total_params(CONFIG) == 4_354_531_616
    assert shapes["head"].shape == (2560, 39296)
    layers = shapes["layers"]
    assert layers[0]["mixer"]["in_proj"].shape == (2560, 12288)
    assert layers[0]["mixer"]["f_proj"].shape == (2560, 4096)
    assert layers[0]["mixer"]["dt_bias"].shape == (32, 128)
    assert layers[0]["ffn"]["wg"].shape == (2560, 6144)
    assert layers[5]["mixer"]["wq"].shape == (2560, 32 * 192)
    assert layers[5]["mixer"]["wkva"].shape == (2560, 576)
    assert layers[1]["experts"]["wg"].shape == (128, 2560, 768)
    assert layers[1]["router"]["bias"].shape == (512,)


def test_prefill_flops_and_decode_bytes_by_hand():
    c = CONFIG
    # one row of one chunk and one token more
    n = 65
    pairs = 64 * 65 / 2 + 1
    assert ling3_cost.chunk_pairs(n, 64) == pairs
    scan = (2 * 2 * 32 * 128 * pairs + 2 * 32 * 256 * pairs
            + 2 * 32 * 128 * pairs + 3 * 2 * 32 * 128 * 128 * n)
    assert ling3_cost.scan_flops(c, n) == scan
    outside = ling3_cost.params_outside_experts(c)
    assert outside == (
        5 * (2560 * 12288 + 2560 * 4096 + 2 * 2560 * 32 + 4096 * 2560)
        + (2560 * 6144 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + 4096 * 2560)
        + 3 * 2560 * 6144 + 5 * (2560 * 512 + 3 * 2560 * 768))
    held = 2.0 * 5 * n
    want = (n * 2 * outside + 5 * scan
            + 2 * 32 * (192 + 128) * n * (n + 1) / 2
            + 2 * 3 * 2560 * 768 * held + 2 * 2560 * 39296)
    assert ling3_cost.prefill_flops(c, [n], held) == want
    # a step of 64 live rows at 6,000 tokens that touches 80 experts a layer
    terms = ling3_cost.decode_terms(
        c, 1, 5 * 80, 64 * 5 * 2 * 2_097_152, 64 * 6000)
    assert terms["carry"] == 64 * 5 * 2 * 2_097_152         # 1.34 GB
    assert terms["conv_tails"] == 64 * 5 * 2 * 73_728
    assert terms["latent_rows"] == 64 * 6000 * 1152
    assert terms["experts_touched"] == 5 * 80 * 3 * 2560 * 768 * 2
    assert terms["head"] == 2560 * 39296 * 2
    assert sum(terms.values()) == ling3_cost.decode_bytes(
        c, 1, 5 * 80, 64 * 5 * 2 * 2_097_152, 64 * 6000)
    assert 0.15 < terms["carry"] / sum(terms.values()) < 0.35


def test_direct_primes_put_the_edges_into_slots_that_long_rows_left():
    workload = harness.load_workload(CELL)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    check = workload["correct"]["direct"]
    mimo_runner = harness.load_module("perf/runners/serve_mimo.py")
    long, second = mimo_runner.direct_lengths(check, workload, 65, 64)
    assert len(long) == 8 and all(8001 <= n <= 8192 for n in long)
    # blocks of 16, chunks of 64
    assert list(second[:7]) == [1, 15, 16, 17, 63, 64, 65]
    assert 67 <= second[7] <= 1021 and all(
        second[7] % d for d in range(2, 32))
    assert 16001 <= second[8] <= 16300 and len(second) == 64
    assert all(512 <= n <= 16384 for n in second[9:])
    at = mimo_runner.compared_slots(check, 64)
    assert list(at[:9]) == list(range(9)) and len(at) == 12
    assert at[-1] == 63


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 65: the program's reading nearest each limit
    over its seeds, the limit, and the nearest reading OF THE SAME QUANTITY
    that the limit has to refuse (``perf/tools/ling3_lowp.py``, my chip
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
    """``perf/tools/ling3_lowp.py`` at a tiny size: each variant traces the
    reference through the wrapped operations, every control moves the
    result further than the stated precision does, the tool's chunked form
    is the recurrence, and nothing stays patched."""
    import jax

    from progen_tpu.models import bailing_hybrid

    tool = harness.load_module("perf/tools/ling3_lowp.py")
    assert set(tool.VARIANTS) == {
        "as-stated", "chunked", "one-notch-below", "head-decay", "no-erase",
        "no-bound", "no-l2norm", "no-delta-gate", "no-latent-gate",
        "no-group-limit", "no-clip", "carry-bf16", "exponents-bf16"}
    c = bailing_hybrid.BailingHybridConfig.from_dict(TINY)
    params = bailing_hybrid.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_ling3.forward_row(params, tokens, TINY)
        blocked, _ = reference_ling3.forward_row(params, tokens, TINY,
                                                 q_block=16)
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    far = {}
    for name, (operands, islands, choice) in tool.VARIANTS.items():
        # the tool's chunked forms at the tiny chunk
        with mock.patch.multiple(tool, CHUNK=8, BLOCK=4), tool.lowered(
                operands and getattr(jax.numpy, operands), islands,
                choice), jax.default_matmul_precision("highest"):
            got, _ = reference_ling3.forward_row(
                params, tokens, tool.config_for(TINY, choice))
        far[name] = float(np.abs(np.asarray(got, np.float32) - want).mean())
    assert 0 < far["as-stated"] < far["one-notch-below"] < 1
    assert abs(far["chunked"] - far["as-stated"]) < 0.2 * far["as-stated"]
    for name in ("head-decay", "no-erase", "no-bound", "no-l2norm",
                 "no-delta-gate", "no-latent-gate", "no-group-limit",
                 "no-clip"):
        assert far[name] > 1.4 * far["as-stated"], (name, far)
    # in float32 the tool's chunked form is the recurrence token by token
    q, k, v = (jax.random.normal(jax.random.key(i), (23, 2, 8))
               for i in range(3))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    alpha = jax.numpy.exp(-5 * jax.nn.sigmoid(
        2 * jax.random.normal(jax.random.key(3), (23, 2, 8))))
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.key(4), (23, 2)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            tool.chunked_recurrence(jax.numpy.float32, 8, 4)(
                q, k, v, alpha, beta),
            reference_ling3.recurrence(q, k, v, alpha, beta), atol=2e-5)
    for name in ("product", "softmax", "rms_norm", "route", "carry",
                 "delta_token", "recurrence", "unit", "log_decay",
                 "delta_gate", "latent_gate", "kept_groups", "clipped"):
        assert getattr(reference_ling3, name).__module__ == (
            "perf.lib.reference_ling3")


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
    _dump(root / "perf/configs/tiny-ling3.json", TINY)
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
    workload.update(name="serve-tiny-ling3", config="tiny-ling3",
                    traffic="tiny-longdoc",
                    engine={"num_slots": 32, "chunk_size": 6, "max_len": 64})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"], long_rows=5,
                    long_prime_tokens=[20, 24],
                    readmit_prime_tokens=[1, 3, 4, 5],
                    prime_number_between=[7, 13],
                    longest_prime_tokens=[31, 34], compared_slots=8,
                    row_rms_limit=1.2, rms_limit=0.6, assignments_limit=0.5))
    _dump(root / "perf/workloads/serve-tiny-ling3.json", workload)
    bench["configs"].append({
        "name": "tiny-ling3", "source": "perf/tests",
        "file": "perf/configs/tiny-ling3.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-ling3", "config": "tiny-ling3",
        "traffic": "tiny-longdoc", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-ling3")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_ling3_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-ling3", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-ling3", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0.8 < value["moe.held_assignments_per_token"] < 2.2  # 3 x 8 / 16
    assert value["mla.rows_read_per_live_row"] > 1
    assert 0 < value["moe.experts_touched_share"] <= 1     # of the 8 held
    assert value["kda.scan_slots_per_real_token.ling3"] >= 1
    assert 0 < value["kda.state_share_of_step_bytes.ling3"] < 1
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
           "workload": {"engine": {"num_slots": 64}},
           "counters": {"admitted_primes": [300]}, "trace": None}
    for name in FROM_THE_FAMILY:
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read(obs, spec) is None, name
