"""The GLM-5.2 cell rehearsed on the CPU at tiny widths through the harness
(as test_dots3.py does for dots3's), the configuration file against the
catalog row and the program's defaults, the cost functions against hand
counts and ``jax.eval_shape``, and the control tool's variants.  Nothing here
measures anything."""

import importlib.util
import json
import os
import shutil
from unittest import mock

import pytest

from perf.lib import glm52_cost, harness
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-glm52-longdoc-backlog"
CONFIG = harness.load_config("glm-5.2-ep16")
BENCH = harness.load_benchmark()
SHARES = {"decode.hbm_share.glm52", "prefill.mfu.glm52"}
OWN = SHARES | {"dsa.layers_per_selection.glm52"}
WINDOW = {f"window.{k}.backlog" for k in (
    "admit_share", "chunk_share", "chunk_step_ms", "delivery_gap_p50_ms",
    "delivery_gap_p95_ms")}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "moe.experts_touched_share",
    "mla.rows_read_per_live_row", "dsa.selected_share_of_context",
    "dsa.index_rows_read_per_live_row"}
METRICS = SHARED | WINDOW | FROM_THE_FAMILY
REDUCED = ["num_hidden_layers", "indexer_types", "mlp_layer_types",
           "layer_types", "experts_held", "vocab_size"]
FULL, SHARED_LAYER = "full", "shared"

TINY = dict(
    name="tiny-glm52", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=5,
    indexer_types=[FULL, SHARED_LAYER, SHARED_LAYER, SHARED_LAYER, FULL],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    rope_parameters={"rope_theta": 1e4, "rope_type": "default"},
    rope_interleave=True, indexer_rope_interleave=True,
    index_n_heads=16, index_head_dim=8, index_topk=8, index_norm_eps=1e-6,
    n_routed_experts=16, num_experts_per_tok=2, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    rms_norm_eps=1e-5, max_position_embeddings=128, experts_held=8,
    first_expert=0, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "GLM-5.2")


def test_every_published_key_is_in_the_file_and_no_width_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 5
    # the published layers 2-6: the last leading dense layer, then one
    # whole period of four expert layers, 3 shared : 1 full
    assert CONFIG["indexer_types"] == row["config"]["indexer_types"][2:7] == [
        FULL, SHARED_LAYER, SHARED_LAYER, SHARED_LAYER, FULL]
    assert CONFIG["mlp_layer_types"] == row["config"]["mlp_layer_types"][
        2:7] == ["dense"] + ["sparse"] * 4
    pattern = row["config"]["indexer_types"]
    assert {pattern[i:i + 4].count(FULL) for i in range(3, 75, 4)} == {1}
    assert pattern.count(FULL) == 21 and len(pattern) == 78
    assert CONFIG["n_routed_experts"] == 256 and CONFIG["experts_held"] == 16
    assert CONFIG["vocab_size"] == 19360 == row["config"]["vocab_size"] // 8
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    for key in ("assumed", "deployment", "parameters"):
        assert CONFIG[key]
    for key in ("indexer", "indexer_storage", "rope", "softmax_scale",
                "router", "shared_layers", "seeded_weights", "left_out"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "16 v5e chips" in CONFIG["deployment"]
    assert "HAND-OVER" in CONFIG["deployment"]
    assert "3,881,517,056" in CONFIG["parameters"]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.glm_dsa import GLMDSAConfig

    default = GLMDSAConfig()
    c = GLMDSAConfig.from_dict(CONFIG)
    assert c == GLMDSAConfig(
        num_hidden_layers=5, vocab_size=19360, experts_held=16,
        indexer_types=default.indexer_types[2:7],
        mlp_layer_types=default.mlp_layer_types[2:7])
    for key, value in CONFIG["published"].items():
        if hasattr(default, key):
            got = getattr(default, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    assert default.rope_theta == CONFIG["published"]["rope_parameters"][
        "rope_theta"]
    for key in ("router_logit_std", "router_bias_std", "prefill_bucket",
                "index_norm_eps"):
        assert getattr(default, key) == CONFIG[key], key
    assert c.seq_len == 1048576 and c.num_layers == 5
    assert (c.experts_held, c.router_width, c.moe_topk) == (16, 256, 8)


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["traffic"] == "backlog-longdoc-2k"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"] == REDUCED
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/glm-5.2-ep16.json"
    assert "7.76 GB" in listed["why"]
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "serve_tok_s"}
    layer = harness.cell_metrics(BENCH, CELL, "per_layer")
    assert {m["name"] for m in layer} == METRICS
    assert {m["name"] for m in layer if m["workloads"] == [CELL]} == OWN
    assert not [m["name"] for m in layer if "roofline" in m["name"]]
    # every entry dots3's cell is in beside its own two shares
    dots3 = {m["name"] for m in harness.cell_metrics(
        BENCH, "serve-dots3-longdoc-backlog", "per_layer")}
    assert METRICS - OWN == {n for n in dots3 if not n.endswith(".dots3")}
    for m in layer:       # each has its file, and the file says the same
        assert m["moves"] == ("setup_s" if m["name"].startswith("xla.")
                              else "serve_tok_s")
        spec = harness.load_metric(m["name"])
        assert all(spec[k] == v for k, v in m.items() if k != "workloads")
        assert os.path.exists(os.path.join(harness.ROOT, spec["reader"]))
    for text in [entry["why"], listed["why"]]:
        assert 0 < len(text) <= 200
    workload = harness.load_workload(CELL)
    dots3_cell = harness.load_workload("serve-dots3-longdoc-backlog")
    # dots3's engine, as ISSUE 60 names it
    assert workload["engine"] == dots3_cell["engine"] == {
        "num_slots": 16, "chunk_size": 32, "max_len": 17408}
    assert workload["window"] == dots3_cell["window"]
    assert workload["traffic"] == dots3_cell["traffic"]
    assert workload["runner"] == "perf/runners/serve_glm52.py"


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 60's arithmetic
    assert glm52_cost.indexer_params(c) == (
        2048 * 4096 + 6144 * 128 + 6144 * 32) == 9_371_648
    assert glm52_cost.attention_params(c) == (
        6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
        + 16384 * 6144) == 165_019_648
    assert glm52_cost.dense_ffn_params(c) == 226_492_416
    assert glm52_cost.expert_params(c) == 37_748_736
    assert glm52_cost.router_params(c) == 1_572_864
    assert (glm52_cost.full_layers(c), glm52_cost.dense_layers(c),
            glm52_cost.expert_layers(c)) == (2, 1, 4)
    assert glm52_cost.latent_bytes_per_row(c) == 1152
    assert glm52_cost.index_bytes_per_row(c) == 256
    whole = dict(c, **{k: CONFIG["published"][k] for k in CONFIG["reduced"]
                       if k in CONFIG["published"]}, experts_held=256)
    # ~750 B as described
    assert 740e9 < glm52_cost.total_params(whole) < 760e9


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import glm_dsa

    c = glm_dsa.GLMDSAConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: glm_dsa.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # norm scales (two a layer and the last; the two latents' a block), the
    # indexers' LayerNorm, the routers' biases
    small = ((5 * 2 + 1) * 6144 + 5 * (2048 + 512) + 2 * 2 * 128 + 4 * 256)
    assert made - small == glm52_cost.total_params(CONFIG)
    assert made == 3_881_517_056           # the figure the files state
    assert shapes["head"].shape == (6144, 19360)
    assert shapes["layers"][4]["attn"]["wiq"].shape == (2048, 32 * 128)
    for shared in (1, 2, 3):
        assert not [k for k in shapes["layers"][shared]["attn"]
                    if k.startswith(("wi", "ik_"))]


def test_prefill_flops_and_decode_bytes_by_hand():
    c = CONFIG
    n = 6000
    held = n * 0.5 * 4
    outside = (5 * 165_019_648 + 2 * 9_371_648 + 226_492_416
               + 4 * (1_572_864 + 37_748_736))
    selected = 2048 * 2049 / 2 + (n - 2048) * 2048
    scored = n * (n + 1) / 2
    want = (n * 2 * outside
            + 2 * (256 + 256) * 64 * 5 * selected
            + 2 * 32 * 128 * 2 * scored
            + 2 * 37_748_736 * held + 2 * 6144 * 19360)
    assert glm52_cost.prefill_flops(c, [n], held) == want
    # a step of 16 live rows at 6,000 tokens, 6 of 16 experts a layer
    # touched; the program sums the kept keys over the five layers
    terms = glm52_cost.decode_terms(c, 1, 24, 16 * 6000, 5 * 16 * 2048)
    assert terms["experts_touched"] == 24 * 37_748_736 * 2
    assert terms["attention"] == (5 * 165_019_648 + 2 * 9_371_648) * 2
    assert terms["index_rows"] == 16 * 6000 * 2 * 256
    assert terms["selected_rows"] == 16 * 2048 * 5 * 1152
    moved = sum(terms.values())
    assert moved == glm52_cost.decode_bytes(c, 1, 24, 96000, 163840)
    assert 4.0e9 < moved < 5.0e9
    # what the XLA score reads of the indexer rows whatever the contexts
    assert 16 * 2 * 17408 * 256 == 142_606_336


# ------------------------------------------- the comparison's measures


def test_direct_primes_put_the_selectors_edges_into_slots_that_long_rows_left():
    runner = harness.load_module("perf/runners/serve_mimo.py")
    workload = harness.load_workload(CELL)
    workload["traffic"] = harness.load_traffic("backlog-longdoc-2k")
    check = workload["correct"]["direct"]
    long, second = runner.direct_lengths(check, workload, 2 ** 31 + 5, 12)
    assert len(long) == 4 and all(8001 <= n <= 8192 for n in long)
    assert second[:3].tolist() == [2047, 2048, 2049]
    assert 2053 <= second[3] <= 2297 and all(
        second[3] % d for d in range(2, 48))
    assert 16001 <= second[4] <= 16300
    assert all(2048 <= n <= 16384 for n in second[5:]) and len(second) == 12
    at = runner.compared_slots(check, 12)
    assert at.tolist() == [0, 1, 2, 3, 4, 6, 8, 9, 11]
    mine = harness.load_module("perf/runners/serve_glm52.py")
    # every readmitted row with its two chunks fits the short reference
    assert second[3] + 3 * 32 + 2 <= mine.SHORT_WIDTH
    assert mine.long_width(workload) == 16384 + 128


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
    tool = harness.load_module("perf/tools/glm52_lowp.py")
    assert set(tool.VARIANTS) == {
        "as-stated", "islands-bf16", "fp8-operands", "no-selection",
        "top-1024", "no-relu", "unweighted-heads", "shared-attends-all",
        "shared-selects-itself", "full-borrows", "half-split-rope",
        "half-split-indexer-rope"}
    changed = {name: v[2] for name, v in tool.VARIANTS.items()}
    assert changed["top-1024"] == {"index_topk": 1024}
    assert changed["no-selection"]["index_topk"] >= 2 ** 20
    assert changed["shared-attends-all"] == {"shared_selection": "none"}
    assert changed["shared-selects-itself"] == {"shared_selection": "own"}
    assert changed["full-borrows"] == {"full_selection": "borrow"}
    assert changed["half-split-rope"] == {"rope_interleave": False}
    # at a tiny size: each variant traces the reference through the wrapped
    # operations or a changed key, every one moves the result, and a lower
    # precision reads further from the float32 reference
    import jax
    import numpy as np

    from perf.lib import reference_glm52
    from progen_tpu.models import glm_dsa

    c = glm_dsa.GLMDSAConfig.from_dict(TINY)
    params = glm_dsa.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = reference_glm52.forward_row(params, tokens, TINY)[0]
        blocked = reference_glm52.forward_row(
            params, tokens, TINY, q_block=16, row_block=16, head_block=2)[0]
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    far = {}
    # the tiny top-k is 8: planted as half
    tiny_variants = dict(tool.VARIANTS, **{
        "top-1024": (None, (), {"index_topk": 4})})
    with mock.patch.object(tool, "VARIANTS", tiny_variants):
        for name in tool.VARIANTS:
            forward_row, ctx = tool.variant_forward(name, TINY)
            with ctx():
                got = forward_row(params, tokens, TINY)[0]
            far[name] = float(np.abs(np.asarray(got, np.float32)
                                     - want).mean())
    assert 0 < far["as-stated"] < far["fp8-operands"] < 1
    for name in set(far) - {"as-stated", "islands-bf16", "fp8-operands"}:
        assert far[name] > 2 * far["as-stated"], (name, far)
    # nothing stays patched
    for name in ("product", "softmax", "sigmoid", "rms_norm", "route"):
        assert getattr(reference_glm52, name).__module__ == (
            "perf.lib.reference_glm52")


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
    _dump(root / "perf/configs/tiny-glm52.json", TINY)
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
    workload.update(name="serve-tiny-glm52", config="tiny-glm52",
                    traffic="tiny-longdoc-2k",
                    engine={"num_slots": 16, "chunk_size": 6, "max_len": 64})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.3,
        direct=dict(workload["correct"]["direct"],
                    long_prime_tokens=[20, 24],
                    readmit_prime_tokens=[7, 8, 9],
                    prime_number_between=[11, 13],
                    longest_prime_tokens=[31, 34], compared_slots=9,
                    row_rms_limit=1.2, tolerance=0.6, agreed_floor=0.1,
                    routings_limit=0.5, selected_keys_limit=0.3))
    _dump(root / "perf/workloads/serve-tiny-glm52.json", workload)
    bench["configs"].append({
        "name": "tiny-glm52", "source": "perf/tests",
        "file": "perf/configs/tiny-glm52.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-glm52", "config": "tiny-glm52",
        "traffic": "tiny-longdoc-2k", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-glm52")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_glm52_harness", root / "perf/lib/harness.py")
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    assert copy.ROOT == str(root)

    def any_devices(chips):
        import jax

        return jax.devices()

    monkeypatch.setattr(copy, "require_tpu", any_devices)
    runner = copy.load_module("perf/runners/serve_glm52.py")
    monkeypatch.setattr(runner, "SHORT_WIDTH", 64)     # the engine's max_len
    monkeypatch.setattr(runner, "QUERY_BLOCK", 8)
    monkeypatch.setattr(runner, "HEAD_BLOCK", 2)
    return root, copy


def test_the_cell_runs_end_to_end_at_a_tiny_size(checkout):
    root, copy = checkout
    result = copy.run_cell("serve-tiny-glm52", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-glm52", 7, 1.5, True, 0.0)
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
    assert value["mla.rows_read_per_live_row"] < 5 * value[
        "dsa.index_rows_read_per_live_row"]
    # two layers compute a selection and three borrow it
    assert value["dsa.layers_per_selection.glm52"] == 2.5
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 16}},
           "counters": {"admitted_primes": [9, 20]}, "trace": None}
    for name in SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0


def test_a_shared_layer_that_selects_for_itself_is_not_correct(checkout):
    """The runner's ``correct`` at the tiny size, held against a reference
    in which ONE thing is changed — the shared layers run their full layer's
    indexer weights on their own input (``perf/tools/glm52_lowp.py``'s
    ``shared-selects-itself``) —: the engine, which borrows, is refused."""
    root, copy = checkout
    runner = copy.load_module("perf/runners/serve_glm52.py")
    plain = runner.reference_glm52.forward_row

    def control(params, tokens, cfg, **kwargs):
        return plain(params, tokens, {**cfg, "shared_selection": "own"},
                     **kwargs)

    with mock.patch.object(runner.reference_glm52, "forward_row", control):
        result = copy.run_cell("serve-tiny-glm52", 11, 0.5, False, 0.0)
    assert result["correct"] is False and result["failed"] == 0


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
