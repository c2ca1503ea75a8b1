"""The dots3 cell rehearsed on the CPU at tiny widths through the harness (as
test_mimo.py does for MiMo-V2's), the configuration file against the catalog
row and the program's defaults, the cost functions against hand counts, the
measure of the selections and the control tool's variants.  Nothing here
measures anything."""

import importlib.util
import json
import os
import shutil
from unittest import mock

import pytest

from perf.lib import dots3_cost, harness
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-dots3-longdoc-backlog"
CONFIG = harness.load_config("dots3-note-prev-ep8")
BENCH = harness.load_benchmark()
SHARES = {"decode.hbm_share.dots3", "prefill.mfu.dots3"}
OWN = SHARES | {"dsa.selected_share_of_context",
                "dsa.index_rows_read_per_live_row"}
WINDOW = {f"window.{k}.backlog" for k in (
    "admit_share", "chunk_share", "chunk_step_ms", "delivery_gap_p50_ms",
    "delivery_gap_p95_ms")}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "moe.experts_touched_share",
    "mla.rows_read_per_live_row"}
METRICS = SHARED | WINDOW | FROM_THE_FAMILY
REDUCED = ["num_hidden_layers", "layer_types", "experts_held", "vocab_size"]
FULL, SLIDING = "full_attention", "sliding_attention"

TINY = dict(
    name="tiny-dots3", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=5, first_k_dense_replace=1,
    layer_types=[FULL, FULL, SLIDING, SLIDING, SLIDING],
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e5,
    index_n_heads=16, index_head_dim=8, index_topk=8, index_norm_eps=1e-6,
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=100.0, sliding_window_size=5,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True, n_routed_experts=16,
    num_experts_per_tok=2, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1, scoring_func="sigmoid", rms_norm_eps=1e-5,
    max_position_embeddings=128, experts_held=8, first_expert=0,
    prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "dots3-note-prev")


def test_every_published_key_is_in_the_file_and_no_width_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 5
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:5] == [
        FULL, FULL, SLIDING, SLIDING, SLIDING]
    # after the two leading full layers every period of four has one full
    # layer and three sliding ones
    pattern = row["config"]["layer_types"]
    assert {pattern[i:i + 4].count(FULL) for i in range(2, 46, 4)} == {1}
    assert CONFIG["n_routed_experts"] == 256 and CONFIG["experts_held"] == 32
    assert CONFIG["vocab_size"] == 19008 == row["config"]["vocab_size"] // 8
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    for key in ("assumed", "deployment", "parameters"):
        assert CONFIG[key]
    for key in ("apply_mla_qkv_lora_rescale", "attention_gate",
                "sliding_window_size", "indexer", "indexer_rope",
                "indexer_storage", "rope", "softmax_scale", "router",
                "seeded_weights", "left_out"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "8 v5e chips" in CONFIG["deployment"]
    assert "layers 0-4 of 46" in CONFIG["deployment"]
    assert "4,087,154,176" in CONFIG["parameters"]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.dots3 import Dots3Config

    default = Dots3Config()
    c = Dots3Config.from_dict(CONFIG)
    assert c == Dots3Config(num_hidden_layers=5, vocab_size=19008,
                            experts_held=32,
                            layer_types=default.layer_types[:5])
    for key, value in CONFIG["published"].items():
        if hasattr(default, key):
            got = getattr(default, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    for key in ("router_logit_std", "router_bias_std", "prefill_bucket",
                "index_norm_eps"):
        assert getattr(default, key) == CONFIG[key], key
    assert c.seq_len == 524288 and c.num_layers == 5
    assert (c.experts_held, c.router_width, c.moe_topk) == (32, 256, 8)


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["traffic"] == "backlog-longdoc-2k"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"] == REDUCED
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/dots3-note-prev-ep8.json"
    assert "8.17 GB" in listed["why"]
    assert len(BENCH["per_layer"]) <= 83 and len(BENCH["workloads"]) == 12
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "serve_tok_s"}
    layer = harness.cell_metrics(BENCH, CELL, "per_layer")
    assert {m["name"] for m in layer} == METRICS
    assert {m["name"] for m in layer if m["workloads"] == [CELL]} == OWN
    assert not [m["name"] for m in layer if "roofline" in m["name"]]
    for m in layer:       # each has its file, and the file says the same
        assert m["moves"] == ("setup_s" if m["name"].startswith("xla.")
                              else "serve_tok_s")
        spec = harness.load_metric(m["name"])
        assert all(spec[k] == v for k, v in m.items() if k != "workloads")
        assert os.path.exists(os.path.join(harness.ROOT, spec["reader"]))
    for text in [entry["why"], listed["why"]]:
        assert 0 < len(text) <= 200
    workload = harness.load_workload(CELL)
    assert workload["engine"] == {"num_slots": 16, "chunk_size": 32,
                                  "max_len": 17408}
    assert workload["window"] == harness.load_workload(
        "serve-mimo-longdoc-backlog")["window"]
    assert workload["runner"] == "perf/runners/serve_dots3.py"
    mix, sibling = (harness.load_traffic(n) for n in (
        "backlog-longdoc-2k", "backlog-longdoc"))
    assert mix["arrivals"] == {"kind": "backlog", "requests_per_second": 8.0}
    assert mix["prime_tokens"] == {"kind": "lognormal", "median": 6144,
                                   "sigma": 0.6, "min": 2048, "max": 16384}
    for key in ("generated_tokens", "stagger", "sampling", "kind"):
        assert mix[key] == sibling[key], key
    assert mix["schedule_seed"] == 56


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 56's arithmetic
    assert dots3_cost.indexer_params(c) == 1024 * 8192 + 5120 * 128 + 5120 * 64
    assert dots3_cost.attention_params(c, FULL) == (
        5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 16384 * 5120 + 5120 * 128 + 9_371_648) == 144_048_128
    assert dots3_cost.attention_params(c, SLIDING) == (
        5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
        + 8192 * 5120 + 5120 * 64) == 90_832_896
    assert dots3_cost.dense_ffn_params(c) == 212_336_640
    assert dots3_cost.expert_params(c) == 23_592_960
    assert [dots3_cost.layers_of(c, k) for k in (FULL, SLIDING)] == [2, 3]
    assert (dots3_cost.dense_layers(c), dots3_cost.expert_layers(c)) == (1, 4)
    assert dots3_cost.latent_bytes_per_row(c, FULL) == 1152
    assert dots3_cost.latent_bytes_per_row(c, SLIDING) == 2176
    assert dots3_cost.index_bytes_per_row(c) == 256
    whole = dict(c, **{k: CONFIG["published"][k] for k in CONFIG["reduced"]
                       if k in CONFIG["published"]}, experts_held=256)
    # the text stack alone: the published 288 B counts the towers and the
    # draft layer too
    assert 279e9 < dots3_cost.total_params(whole) < 280e9


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import dots3

    c = dots3.Dots3Config.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: dots3.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # norm scales (two a layer and the last; the two latents' a block), the
    # indexer's LayerNorm, the routers' biases
    small = ((5 * 2 + 1) * 5120 + 2 * (1024 + 512) + 3 * (1024 + 1024)
             + 2 * 2 * 128 + 4 * 256)
    assert made - small == dots3_cost.total_params(CONFIG)
    assert made == 4_087_154_176           # the figure the files state
    assert shapes["head"].shape == (5120, 19008)
    assert shapes["layers"][1]["attn"]["wiq"].shape == (1024, 64 * 128)
    assert "wiq" not in shapes["layers"][2]["attn"]


def test_prefill_flops_and_decode_bytes_by_hand():
    c = CONFIG
    n = 6000
    held = n * 1.0 * 4
    outside = (2 * 144_048_128 + 3 * 90_832_896 + 212_336_640
               + 4 * (1_310_720 + 23_592_960))
    selected = 2048 * 2049 / 2 + (n - 2048) * 2048
    windowed = 513 * 514 / 2 + (n - 513) * 513
    scored = n * (n + 1) / 2
    want = (n * 2 * outside
            + 2 * (192 + 128) * 128 * 2 * selected
            + 2 * (256 + 128) * 64 * 3 * windowed
            + 2 * 64 * 128 * 2 * scored
            + 2 * 23_592_960 * held + 2 * 5120 * 19008)
    assert dots3_cost.prefill_flops(c, [n], held) == want
    # a step of 16 live rows at 6,000 tokens, 12 of 32 experts a layer touched
    terms = dots3_cost.decode_terms(c, 1, 48, 16 * 513, 16 * 6000, 16 * 2048)
    assert terms["experts_touched"] == 48 * 23_592_960 * 2
    assert terms["attention"] == (2 * 144_048_128 + 3 * 90_832_896) * 2
    assert terms["index_rows"] == 16 * 6000 * 2 * 256
    assert terms["selected_rows"] == 16 * 2048 * 2 * 1152
    assert terms["ring_rows"] == 16 * 513 * 3 * 2176
    moved = sum(terms.values())
    assert moved == dots3_cost.decode_bytes(c, 1, 48, 8208, 96000, 32768)
    assert 4.0e9 < moved < 5.0e9
    # what the XLA score reads of the indexer rows whatever the contexts
    assert 16 * 2 * 17408 * 256 == 142_606_336


# ------------------------------------------- the comparison's measures


def test_direct_primes_put_both_edges_into_slots_that_long_rows_left():
    runner = harness.load_module("perf/runners/serve_mimo.py")
    workload = harness.load_workload(CELL)
    workload["traffic"] = harness.load_traffic("backlog-longdoc-2k")
    check = workload["correct"]["direct"]
    long, second = runner.direct_lengths(check, workload, 2 ** 31 + 5, 16)
    assert len(long) == 7 and all(8001 <= n <= 8192 for n in long)
    assert second[:6].tolist() == [512, 513, 514, 2047, 2048, 2049]
    assert 2053 <= second[6] <= 2297 and all(
        second[6] % d for d in range(2, 48))
    assert 16001 <= second[7] <= 16300
    assert all(2048 <= n <= 16384 for n in second[8:]) and len(second) == 16
    at = runner.compared_slots(check, 16)
    assert at.tolist() == [0, 1, 2, 3, 4, 5, 6, 7, 15]
    mine = harness.load_module("perf/runners/serve_dots3.py")
    # every readmitted row with its two chunks fits the short reference
    assert second[6] + 3 * 32 + 2 <= mine.SHORT_WIDTH
    assert mine.long_width(workload) == 16384 + 128


def test_a_selection_is_held_by_the_share_of_keys_in_one_set_alone():
    mine = harness.load_module("perf/runners/serve_dots3.py")
    full = set(range(8))
    got = [(full, 8),                       # nothing to drop: left out
           ({0, 1, 2, 3}, 20), ({0, 1, 2, 9}, 20), ({4, 5, 6, 7}, 30),
           ({0, 1}, 20), (set(range(20)), 20)]     # half kept; none dropped
    want = [full] + [{0, 1, 2, 3}] * 5
    reading = mine.selection_reading(got, want, 0.5)
    assert reading["selections"] == 5 and reading["ok"]
    assert reading["selections_differ_share"] == pytest.approx(4 / 5)
    assert reading["selected_keys_apart_share"] == pytest.approx(
        (0 + 2 / 8 + 1.0 + 2 / 6 + 16 / 24) / 5)
    assert reading["selected_keys_wrong_most"] == 16
    assert not mine.selection_reading(got, want, 0.4)["ok"]
    assert mine.selection_reading(got[:1], want[:1], 0.0) == {
        "ok": True, "selections": 0, "selections_differ_share": 0.0,
        "selected_keys_apart_share": 0.0, "selected_keys_wrong_most": 0}


def test_the_cells_limits_lie_between_their_two_readings():
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    for name, pair in readings.items():
        if name == "why":
            continue
        program, control = pair
        section = check
        for part in name.split(".")[:-1]:
            section = section[part]
        limit = section[name.split(".")[-1]]
        lo, hi = sorted((program, control))
        assert lo < limit < hi, name
    assert {n for n in readings if n != "why"} == {
        "direct.routings_limit", "direct.tolerance", "direct.agreed_floor",
        "direct.row_rms_limit", "direct.selected_keys_limit",
        "over_share_limit"}


def test_the_control_tool_plants_each_omission_in_the_references_own_terms():
    tool = harness.load_module("perf/tools/dots3_lowp.py")
    assert set(tool.VARIANTS) == {
        "as-stated", "islands-bf16", "fp8-operands", "no-selection",
        "top-1024", "no-relu", "unweighted-heads", "keys-unrotated",
        "no-gate", "no-rescale", "window-514", "sliding-at-full-base"}
    changed = {name: v[2] for name, v in tool.VARIANTS.items()}
    assert changed["top-1024"] == {"index_topk": 1024}
    assert changed["no-selection"]["index_topk"] >= 2 ** 20
    assert changed["window-514"] == {"sliding_window_size": 514}
    assert changed["sliding-at-full-base"] == {
        "swa_rope_theta": CONFIG["rope_theta"]}
    assert changed["no-gate"] == {"attention_gate_type": None,
                                  "swa_attention_gate_type": None}
    # at a tiny size: each variant traces the reference through the wrapped
    # operations or a changed key, every one moves the result, and a lower
    # precision reads further from the float32 reference
    import jax
    import numpy as np

    from perf.lib import reference_dots3
    from progen_tpu.models import dots3

    c = dots3.Dots3Config.from_dict(TINY)
    params = dots3.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = reference_dots3.forward_row(params, tokens, TINY)[0]
        blocked = reference_dots3.forward_row(
            params, tokens, TINY, q_block=16, row_block=16, head_block=2)[0]
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    far = {}
    # the tiny top-k is 8 and the tiny window 5: planted as half and + 1
    tiny_variants = dict(tool.VARIANTS, **{
        "top-1024": (None, (), {"index_topk": 4}),
        "window-514": (None, (), {"sliding_window_size": 6}),
        "sliding-at-full-base": (None, (), {"swa_rope_theta": 1e5})})
    with mock.patch.object(tool, "VARIANTS", tiny_variants):
        for name in tool.VARIANTS:
            forward_row, ctx = tool.variant_forward(name, TINY)
            with ctx():
                got = forward_row(params, tokens, TINY)[0]
            far[name] = float(np.abs(np.asarray(got, np.float32)
                                     - want).mean())
    assert 0 < far["as-stated"] < far["fp8-operands"] < 1
    # (a window of 5 turns the slower of the two tiny frequencies by 0.4
    # radians at most: the base moves the result, not by twice the rounding)
    assert far["sliding-at-full-base"] != far["as-stated"]
    for name in set(far) - {"as-stated", "islands-bf16", "fp8-operands",
                            "sliding-at-full-base"}:
        assert far[name] > 2 * far["as-stated"], (name, far)
    # nothing stays patched
    for name in ("product", "softmax", "sigmoid", "rms_norm", "route"):
        assert getattr(reference_dots3, name).__module__ == (
            "perf.lib.reference_dots3")


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
    _dump(root / "perf/configs/tiny-dots3.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-longdoc-2k"), name="tiny-longdoc-2k",
        arrivals={"kind": "backlog", "requests_per_second": 100.0},
        prime_tokens={"kind": "lognormal", "median": 14, "sigma": 0.6,
                      "min": 8, "max": 30},
        generated_tokens={"kind": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 6, "max": 22})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    traffic["sampling"] = dict(traffic["sampling"], top_k=5)
    _dump(root / "perf/traffic/tiny-longdoc-2k.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-dots3", config="tiny-dots3",
                    traffic="tiny-longdoc-2k",
                    engine={"num_slots": 16, "chunk_size": 6, "max_len": 64})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"],
                    long_prime_tokens=[20, 24],
                    readmit_prime_tokens=[4, 5, 6, 7, 8, 9],
                    prime_number_between=[11, 13],
                    longest_prime_tokens=[31, 34], compared_slots=9,
                    row_rms_limit=0.6, tolerance=0.6, agreed_floor=0.1,
                    routings_limit=0.5, selected_keys_limit=0.3))
    _dump(root / "perf/workloads/serve-tiny-dots3.json", workload)
    bench["configs"].append({
        "name": "tiny-dots3", "source": "perf/tests",
        "file": "perf/configs/tiny-dots3.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-dots3", "config": "tiny-dots3",
        "traffic": "tiny-longdoc-2k", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-dots3")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_dots3_harness", root / "perf/lib/harness.py")
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    assert copy.ROOT == str(root)

    def any_devices(chips):
        import jax

        return jax.devices()

    monkeypatch.setattr(copy, "require_tpu", any_devices)
    runner = copy.load_module("perf/runners/serve_dots3.py")
    monkeypatch.setattr(runner, "SHORT_WIDTH", 64)     # the engine's max_len
    monkeypatch.setattr(runner, "QUERY_BLOCK", 8)
    monkeypatch.setattr(runner, "HEAD_BLOCK", 2)
    return root, copy


def test_the_cell_runs_end_to_end_at_a_tiny_size(checkout):
    root, copy = checkout
    result = copy.run_cell("serve-tiny-dots3", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-dots3", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0.7 < value["moe.held_assignments_per_token"] < 1.3   # 2 x 8 / 16
    assert 0 < value["moe.experts_touched_share"] <= 1     # of the 8 held
    # contexts of 8-52 under a top-k of 8: most keys are dropped
    assert 0.1 < value["dsa.selected_share_of_context"] < 0.9
    # the XLA score reads max_len rows of EVERY slot, whatever the contexts
    assert value["dsa.index_rows_read_per_live_row"] > 1
    # the sparse core reads top-k rows a slot
    assert value["mla.rows_read_per_live_row"] < value[
        "dsa.index_rows_read_per_live_row"]
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 16}},
           "counters": {"admitted_primes": [9, 20]}, "trace": None}
    for name in SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0


def test_readers_of_the_new_metrics_find_nothing_in_a_program_without_them(
        monkeypatch):
    """On the parent the registry has no such gauge: ``None``, no raise."""
    from progen_tpu.observe import metrics

    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    obs = {"config": CONFIG, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 16}},
           "counters": {"admitted_primes": [3000]}, "trace": None}
    for name in FROM_THE_FAMILY:
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read(obs, spec) is None, name
