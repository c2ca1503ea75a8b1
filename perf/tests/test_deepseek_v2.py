"""The DeepSeek-V2 cell rehearsed on the CPU at tiny widths through the
harness (as test_longcat.py does for LongCat's), the configuration file
against the catalog row and the program's defaults, and the cost functions
against hand counts.  Nothing here measures anything."""

import dataclasses
import importlib.util
import json
import os
import shutil

import pytest

from perf.lib import deepseek_v2_cost, harness
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-dsv2-decode-backlog"
CONFIG = harness.load_config("deepseek-v2-ep4")
BENCH = harness.load_benchmark()
REDUCED = ("num_hidden_layers", "experts_held", "vocab_size")
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the expert and latent families share, and this family's own
OWN = {"decode.hbm_share.dsv2", "moe.experts_touched_share.dsv2",
       "moe.held_groups_per_token.dsv2"}
METRICS = SHARED | OWN | {
    "moe.held_assignments_per_token", "moe.held_load_max_over_mean",
    "moe.expert_passes_per_touched", "mla.rows_read_per_live_row"}
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "kv_lora_rank", "q_lora_rank",
          "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
          "num_experts_per_tok", "n_routed_experts", "n_shared_experts",
          "n_group", "topk_group", "routed_scaling_factor", "rope_theta",
          "rms_norm_eps", "first_k_dense_replace", "max_position_embeddings")

TINY = dict(
    name="tiny-dsv2", source="perf/tests", reduced=[], vocab_size=64,
    hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
    v_head_dim=12, n_routed_experts=16, n_shared_experts=2, n_group=4,
    topk_group=2, num_experts_per_tok=3, routed_scaling_factor=16,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 16, "type": "yarn"},
    max_position_embeddings=64, experts_held=4, first_expert=0,
    prefill_bucket=8)


# ------------------------------------------------------- the files agree


def test_every_published_key_is_unchanged_unless_reduced():
    published = CONFIG["published"]
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in published.items():
        if key in REDUCED:
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    for key in WIDTHS:                      # no width is ever reduced
        assert key not in REDUCED and CONFIG[key] == published[key]
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"],
            CONFIG["experts_held"], CONFIG["first_expert"]) == (
                5, 25600, 40, 0)
    assert "experts_held" not in published          # the share's own key
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    assert "4 chips that share each layer" in CONFIG["deployment"]
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_catalog_row_is_what_was_copied():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "DeepSeek-V2")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.deepseek_v2 import DeepSeekV2Config

    default, published = DeepSeekV2Config(), CONFIG["published"]
    for key in WIDTHS + ("num_hidden_layers", "vocab_size"):
        assert getattr(default, key) == published[key], key
    assert dataclasses.asdict(default.rope_scaling) == {
        k: v for k, v in published["rope_scaling"].items() if k != "type"}
    assert default.experts_held == published["n_routed_experts"]
    c = DeepSeekV2Config.from_dict(CONFIG)
    for key in WIDTHS + REDUCED:
        assert getattr(c, key) == CONFIG[key], key
    assert c.router_width == 160 and c.latent_width == 576
    assert c.moe_topk == 6 and c.num_layers == 5
    assert c.seq_len == 163840 == CONFIG["max_position_embeddings"]


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "backlog-longgen"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "serve_tok_s"}
    layer = harness.cell_metrics(BENCH, CELL, "per_layer")
    assert {m["name"] for m in layer} == METRICS
    assert all(m["moves"] in e2e for m in layer)
    assert {m["name"] for m in layer if m["workloads"] == [CELL]} == OWN
    traffic = harness.load_traffic(entry["traffic"])
    assert traffic["arrivals"] == {"kind": "backlog",
                                   "requests_per_second": 10.0}
    assert traffic["schedule_seed"] == 32 and traffic["stagger"]["first"] == 64
    workload = harness.load_workload(CELL)
    assert traffic["prime_tokens"]["max"] + traffic["generated_tokens"][
        "max"] == workload["engine"]["max_len"] == 3072
    assert workload["engine"]["chunk_size"] == 32
    assert workload["engine"]["num_slots"] in (64, 48, 32)


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 32: attention 149.23 M, shared 47.19 M, router 0.82 M, an
    # expert 23.59 M, the dense FFN 188.74 M
    assert deepseek_v2_cost.attention_params(c) == (
        5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 16384 * 5120) == 149_225_472
    assert deepseek_v2_cost.shared_params(c) == 47_185_920
    assert deepseek_v2_cost.router_params(c) == 819_200
    assert deepseek_v2_cost.expert_params(c) == 23_592_960
    assert deepseek_v2_cost.dense_ffn_params(c) == 188_743_680
    assert deepseek_v2_cost.expert_layers(c) == 4
    # dense layer 337.97 M + 4 x 1,140.95 M + 262.14 M of vocabulary
    total = (5 * 149_225_472 + 188_743_680
             + 4 * (47_185_920 + 819_200 + 40 * 23_592_960)
             + 2 * 25600 * 5120)
    assert deepseek_v2_cost.total_params(c) == total == 5_163_909_120
    assert round(total / 1e6, 1) == 5163.9 and round(2 * total / 1e9, 2) == 10.33
    assert deepseek_v2_cost.latent_bytes_per_token(c) == 5 * 576 * 2


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import deepseek_v2

    c = deepseek_v2.DeepSeekV2Config.from_dict(CONFIG)
    shapes = jax.eval_shape(
        lambda k: deepseek_v2.init_params(c, k), jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    norms = 5 * (2 * 5120 + 1536 + 512) + 5120
    assert made - norms == deepseek_v2_cost.total_params(CONFIG)


def test_decode_bytes_by_hand():
    c = CONFIG
    fixed = (5 * 149_225_472 + 188_743_680 + 4 * (47_185_920 + 819_200)
             + 5120 * 25600) * 2
    assert deepseek_v2_cost.decode_bytes(c, 1, 0, 0) == fixed
    # a step of 64 live rows at 1,000 tokens that touches 36.5 of 40
    # experts in each of 4 layers: ISSUE 32's ~ 11 GB with ~ 6.9 GB of
    # experts, the largest single term
    terms = deepseek_v2_cost.decode_terms(c, 1, 4 * 36.5, 64 * 1000)
    assert terms["routed_experts_touched"] == 146 * 23_592_960 * 2
    assert terms["latent_cache"] == 64_000 * 5760
    assert terms["attention"] == 5 * 149_225_472 * 2
    assert terms["dense_layer"] == 188_743_680 * 2
    assert terms["head"] == 5120 * 25600 * 2
    assert 6.8e9 < terms["routed_experts_touched"] < 7.0e9
    assert max(terms, key=terms.get) == "routed_experts_touched"
    assert 9.5e9 < sum(terms.values()) < 11.5e9
    got = deepseek_v2_cost.decode_bytes(c, 10, 1460, 640_000)
    assert got == 10 * fixed + 1460 * 23_592_960 * 2 + 640_000 * 5760


# ------------------------------------------- the comparison's measures


def test_compare_row_holds_logits_where_the_routing_agreed():
    import numpy as np

    runner = harness.load_module("perf/runners/serve_deepseek_v2.py")
    want_sets = np.tile(np.array([0, 1, 2]), (2, 5, 1))     # 2 layers, 5 tokens
    got_sets = want_sets.copy()
    got_sets[0, 1] = [2, 1, 0]          # the same set in another order
    got_sets[1, 3] = [0, 1, 7]          # token 3 routed otherwise in layer 1
    at = np.array([0, 3, 4])
    want = np.zeros((3, 8))
    got = want.copy()
    got[0, 2], got[1, 5], got[2, 1] = 0.04, 1.5, -0.03
    r = runner.compare_row(got, got_sets, want, want_sets, at)
    assert (r["differ"], r["routings"], r["agreed_positions"]) == (1, 10, 2)
    assert r["worst"] == {"agreed": 0.04, "all": 1.5}
    assert r["square"] == pytest.approx(0.04 ** 2 + 1.5 ** 2 + 0.03 ** 2)


def test_probe_gaps_and_their_reading():
    import numpy as np

    runner = harness.load_module("perf/runners/serve_deepseek_v2.py")
    at = np.array([[3.0, 2.0, 1.0, 0.0], [0.0, 1.0, 2.0, 3.0]])
    greedy = runner.probe_gaps(at, np.array([0, 1]), None)
    np.testing.assert_allclose(greedy, [0.0, 2.0])
    sampled = runner.probe_gaps(at, np.array([1, 0]), 2)     # 2nd best: 2.0
    np.testing.assert_allclose(sampled, [0.0, 2.0])
    inside = runner.probe_gaps(at, np.array([0, 3]), 2)      # above the bar
    np.testing.assert_allclose(inside, [0.0, 0.0])
    reading = runner.gap_reading(np.array([0.0, 0.05, 0.2, 1.0]), 0.1)
    assert reading == {"over_share": 0.5, "worst": 1.0, "mean": 0.3125}


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 32: the program's largest reading over its
    seeds, the limit, the control one notch below (my chip runs)."""
    check = harness.load_workload(CELL)["correct"]
    assert 0.0563 < check["direct"]["tolerance"] < 0.416
    assert 0.0565 < check["direct"]["routings_limit"] < 0.278
    assert 0.0391 < check["over_share_limit"] < 0.152
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["direct"]["prime_tokens"] == [513, 1023]   # the 1024 bucket


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
    _dump(root / "perf/configs/tiny-dsv2.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-longgen"), name="tiny-longgen",
        arrivals={"kind": "backlog", "requests_per_second": 400.0},
        prime_tokens={"kind": "lognormal", "median": 8, "sigma": 0.6,
                      "min": 3, "max": 16},
        generated_tokens={"kind": "lognormal", "median": 16, "sigma": 0.6,
                          "min": 6, "max": 40})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    _dump(root / "perf/traffic/tiny-longgen.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-dsv2", config="tiny-dsv2",
                    traffic="tiny-longgen",
                    engine={"num_slots": 32, "chunk_size": 4, "max_len": 56})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=6, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"], prime_tokens=[9, 15],
                    positions=8, tolerance=0.5, routings_limit=1.0))
    _dump(root / "perf/workloads/serve-tiny-dsv2.json", workload)
    bench["configs"].append({"name": "tiny-dsv2", "source": "perf/tests",
                             "file": "perf/configs/tiny-dsv2.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-dsv2", "config": "tiny-dsv2",
        "traffic": "tiny-longgen", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the share of the bandwidth's peak is left out: the table of peaks
        # has no row for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] != (
                "decode.hbm_share.dsv2"):
            m["workloads"].append("serve-tiny-dsv2")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_dsv2_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-dsv2", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-dsv2", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - NOT_ON_A_CPU - {
        "decode.hbm_share.dsv2"}
    per_token = traced["metrics"]["moe.held_assignments_per_token"]["value"]
    assert 0 < per_token <= TINY["num_experts_per_tok"]
    assert traced["metrics"]["moe.held_load_max_over_mean"]["value"] >= 1
    assert not [p for p in os.listdir(root) if p not in
                ("perf", "BENCHMARK.json", ".jax_cache")]
    # the share's reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the number means nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite"}
    spec = copy.load_metric("decode.hbm_share.dsv2")
    assert copy.load_module(spec["reader"]).read(obs, spec) > 0


def test_readers_of_the_new_metrics_find_nothing_in_a_program_without_them(
        monkeypatch):
    """On the parent the registry has no such gauge: ``None``, no raise."""
    from progen_tpu.observe import metrics

    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    for name in ("decode.hbm_share.dsv2", "moe.experts_touched_share.dsv2",
                 "moe.held_assignments_per_token",
                 "moe.held_load_max_over_mean"):
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read({"config": CONFIG, "device_kind": "TPU v5 lite"},
                           spec) is None
