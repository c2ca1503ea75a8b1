"""The MiMo-V2 cell rehearsed on the CPU at tiny widths through the harness
(as test_nemotron3.py does for Nemotron-3's), the configuration file against
the catalog row and the program's defaults, the cost functions against hand
counts, the measures of the comparison and the control tool's variants.
Nothing here measures anything."""

import importlib.util
import json
import os
import shutil
from unittest import mock

import numpy as np
import pytest

from perf.lib import harness, mimo_cost, reference_mimo
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-mimo-longdoc-backlog"
CONFIG = harness.load_config("mimo-v2.5-ep16")
BENCH = harness.load_benchmark()
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the families share, and this family's own
SHARES = {"decode.hbm_share.mimo", "prefill.mfu.mimo"}
OWN = SHARES | {"attn.full_share_of_cache_bytes.mimo"}
WINDOW = {f"window.{k}.backlog" for k in (
    "admit_share", "chunk_share", "chunk_step_ms", "delivery_gap_p50_ms",
    "delivery_gap_p95_ms")}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "moe.experts_touched_share",
    "attn.full_rows_read_per_live_row",
    # Trinity's three are ratios of gauges that ``models/kv.py`` and the
    # family publish under the same names: the cell joins their lists
    "attn.window_rows_read_per_live_row.trinity",
    "attn.window_share_of_context.trinity",
    "attn.prefill_pairs_visited_per_allowed.trinity"}
METRICS = SHARED | WINDOW | FROM_THE_FAMILY
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "experts_held", "vocab_size"]

TINY = dict(
    name="tiny-mimo", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=7, hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], num_attention_heads=4,
    num_key_value_heads=1, head_dim=24, v_head_dim=16,
    swa_num_attention_heads=4, swa_num_key_value_heads=2, swa_head_dim=24,
    swa_v_head_dim=16, sliding_window=4, partial_rotary_factor=0.334,
    rope_theta=1e5, swa_rope_theta=100.0, attention_value_scale=0.707,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    n_routed_experts=16, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=None, scoring_func="sigmoid", n_group=1,
    topk_group=1, n_shared_experts=None, layernorm_epsilon=1e-5,
    max_position_embeddings=128, experts_held=8, first_expert=0,
    prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "MiMo-V2.5")


def test_every_published_key_is_in_the_file_and_no_width_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 7
    assert CONFIG["hybrid_layer_pattern"] == row["config"][
        "hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert CONFIG["moe_layer_freq"] == row["config"]["moe_layer_freq"][:7]
    # after the leading dense layer every period of six has five sliding
    # layers and one full
    pattern = row["config"]["hybrid_layer_pattern"]
    assert {sum(pattern[i:i + 6]) for i in range(1, 43, 6)} == {5}
    assert CONFIG["n_routed_experts"] == 256 and CONFIG["experts_held"] == 16
    assert CONFIG["vocab_size"] == 19072 == row["config"]["vocab_size"] // 8
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    for key in ("assumed", "deployment", "parameters"):
        assert CONFIG[key]
    for key in ("hybrid_layer_pattern_values", "attention_value_scale",
                "sliding_window", "partial_rotary_factor",
                "attention_chunk_size", "attention_projection_layout",
                "router_eps", "routed_scaling_factor", "embedding_multiplier",
                "seeded_scales", "prefill_bucket", "left_out"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "16 v5e chips" in CONFIG["deployment"]
    assert "layers 0-6 of 48" in CONFIG["deployment"]
    assert "3,429,955,392" in CONFIG["parameters"]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.mimo_v2 import MiMoV2Config

    default = MiMoV2Config()
    c = MiMoV2Config.from_dict(CONFIG)
    assert c == MiMoV2Config(
        num_hidden_layers=7, vocab_size=19072, experts_held=16,
        hybrid_layer_pattern=default.hybrid_layer_pattern[:7],
        moe_layer_freq=default.moe_layer_freq[:7])
    for key, value in CONFIG["published"].items():
        if hasattr(default, key):
            got = getattr(default, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    for key in ("router_logit_std", "router_bias_std", "sink_mean",
                "sink_std", "prefill_bucket"):
        assert getattr(default, key) == CONFIG[key], key
    assert c.seq_len == 1048576 and c.num_layers == 7
    assert (c.experts_held, c.router_width, c.first_expert) == (16, 256, 0)
    assert c.moe_topk == 8 and c.rotary_dim(0) == c.rotary_dim(1) == 64


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["traffic"] == "backlog-longdoc"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/mimo-v2.5-ep16.json"
    assert "6.86 GB" in listed["why"]
    assert len(BENCH["per_layer"]) <= 80
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
    assert "xla" in entry["why"].lower()    # the cores this family runs
    workload = harness.load_workload(CELL)
    assert workload["engine"] == {"num_slots": 16, "chunk_size": 32,
                                  "max_len": 17408}
    assert workload["window"] == harness.load_workload(
        "serve-dsv2-decode-backlog")["window"]
    assert workload["runner"] == "perf/runners/serve_mimo.py"
    mix = harness.load_traffic("backlog-longdoc")
    assert mix["arrivals"] == {"kind": "backlog", "requests_per_second": 8.0}
    assert mix["prime_tokens"] == {"kind": "lognormal", "median": 4096,
                                   "sigma": 0.9, "min": 512, "max": 16384}
    assert mix["generated_tokens"] == {"kind": "lognormal", "median": 384,
                                       "sigma": 0.5, "min": 128, "max": 1024}
    assert mix["stagger"] == dict(harness.load_traffic(
        "backlog-heavytail")["stagger"], first=16)
    assert mix["schedule_seed"] == 54 and mix["sampling"]["top_k"] == 25


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 54's arithmetic
    assert mimo_cost.attention_params(c, mimo_cost.FULL) == 89_128_960
    assert mimo_cost.attention_params(c, mimo_cost.SLIDING) == 94_371_840
    assert mimo_cost.dense_ffn_params(c) == 201_326_592
    assert mimo_cost.router_params(c) == 1_048_576
    assert mimo_cost.expert_params(c) == 25_165_824
    assert [mimo_cost.layers_of(c, k) for k in (0, 1)] == [2, 5]
    assert (mimo_cost.dense_layers(c), mimo_cost.expert_layers(c)) == (1, 6)
    assert mimo_cost.kv_bytes_per_row(c, mimo_cost.FULL) == 2560
    assert mimo_cost.kv_bytes_per_row(c, mimo_cost.SLIDING) == 5120
    whole = dict(c, **{k: CONFIG["published"][k] for k in CONFIG["reduced"]
                       if k in CONFIG["published"]}, experts_held=256)
    assert 308.7e9 < mimo_cost.total_params(whole) < 308.9e9


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import mimo_v2

    c = mimo_v2.MiMoV2Config.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: mimo_v2.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # norm scales (two a layer and the last), the sinks, the routers' biases
    small = (7 * 2 + 1) * 4096 + 5 * 64 + 6 * 256
    assert made - small == mimo_cost.total_params(CONFIG)
    assert made == 3_429_955_392           # the figure the files state
    assert shapes["head"].shape == (4096, 19072)
    assert shapes["layers"][1]["attn"]["sink"].shape == (64,)
    assert "sink" not in shapes["layers"][5]["attn"]


def test_prefill_flops_and_decode_bytes_by_hand():
    c = CONFIG
    held = 600 * 0.5 * 6
    outside = (2 * 89_128_960 + 5 * 94_371_840 + 201_326_592
               + 6 * 1_048_576)
    full = 600 * 601 / 2
    sliding = 128 * 129 / 2 + (600 - 128) * 128
    want = (600 * 2 * outside + 2 * (192 + 128) * 64 * (2 * full
                                                        + 5 * sliding)
            + 2 * 25_165_824 * held + 2 * 4096 * 19072)
    assert mimo_cost.prefill_flops(c, [600], held) == want
    # ISSUE 54's step: 16 live rows, 6.4 of 16 experts a layer touched (38
    # in the six layers)
    terms = mimo_cost.decode_terms(c, 1, 38, 16 * 128, 16 * 4500)
    assert terms["experts_touched"] == 38 * 25_165_824 * 2
    assert terms["attention"] == (2 * 89_128_960 + 5 * 94_371_840) * 2
    assert terms["head"] == 4096 * 19072 * 2
    assert terms["ring_rows"] == 16 * 128 * 5 * 5120       # 52 MB of rings
    assert terms["grown_rows"] == 16 * 4500 * 2 * 2560
    moved = sum(terms.values())
    assert moved == mimo_cost.decode_bytes(c, 1, 38, 2048, 72000)
    assert 4.0e9 < moved < 4.5e9
    # what the XLA core reads of the grown keys whatever the contexts
    assert 16 * 2 * 17408 * 2560 == 1_426_063_360


# ------------------------------------------- the comparison's measures


def test_direct_primes_put_the_rings_edges_into_slots_that_long_rows_left():
    runner = harness.load_module("perf/runners/serve_mimo.py")
    workload = harness.load_workload(CELL)
    workload["traffic"] = harness.load_traffic("backlog-longdoc")
    check = workload["correct"]["direct"]
    for seed in (0, 2 ** 31 + 9):
        first, second = runner.direct_primes(check, workload, seed, 19072, 16)
        assert len(first) == 5 and len(second) == 16
        assert all(len(p) > 8000 for p in first)
        assert [len(p) for p in second[:4]] == [1, 127, 128, 129]
        n = len(second[4])                  # a prime number of tokens
        assert 130 < n < 1100 and all(
            n % d for d in range(2, int(n ** 0.5) + 1))
        assert 16000 < len(second[5]) <= 16300
        assert all(512 <= len(p) <= 16384 for p in second[6:])
        assert all((p > 0).all() and (p < 19072).all()
                   and p.dtype == np.int32 for p in first + second)
        # every row ends inside the engine with the steps it takes
        new = (check["chunks"] + 1) * 32 + 2
        assert max(len(p) for p in second) + new <= 17408
    at = runner.compared_slots(check, 16)
    assert at.tolist()[:6] == [0, 1, 2, 3, 4, 5] and at[-1] == 15
    assert len(at) == check["compared_slots"] == len(set(at.tolist()))
    groups = runner.direct_groups(check, len(at))
    assert groups["readmitted"] == [0, 1, 2, 3, 4]
    assert groups["after_chunks"] == list(range(len(at), 2 * len(at)))


def test_every_row_is_held_by_itself_and_agreed_rows_logit_by_logit():
    runner = harness.load_module("perf/runners/serve_mimo.py")
    check = {"long_rows": 5, "row_rms_limit": 0.5, "tolerance": 0.4,
             "agreed_floor": 0.3, "routings_limit": 0.2}
    rng = np.random.default_rng(0)
    want = rng.normal(size=(16, 512))
    sets = np.tile(np.arange(8), (16, 6, 1))
    groups = runner.direct_groups(check, 8)
    near = want + 0.05 * rng.normal(size=want.shape)
    good = runner.direct_reading(near, want, sets[..., ::-1], sets, groups,
                                 check)
    assert good["ok"] and good["routings_differ_share"] == 0
    assert good["agreed_share"] == 1 and good["rows"] == 16
    assert good["routings"] == 16 * 6
    far = near.copy()
    far[1] = rng.normal(size=512)           # the slot with the 127-token prime
    other = sets.copy()
    other[1, 0, 0] = 99                     # ... even where its routing differs
    bad = runner.direct_reading(far, want, other, sets, groups, check)
    assert not bad["ok"] and bad["row_rms_max"]["readmitted"] > 1.2
    assert bad["row_rms_max"]["admitted"] < 0.06
    assert bad["worst_agreed"] < 0.4 and bad["row_rms_max_differing"] > 1.2
    one = near.copy()
    one[9, 7] += 0.5                        # one logit of an agreed row
    assert not runner.direct_reading(one, want, sets, sets, groups,
                                     check)["ok"]
    other = sets.copy()
    other[:12, 0, 0] = 99                   # 12 of 16 rows differ in a layer
    few = runner.direct_reading(near, want, other, sets, groups, check)
    assert not few["ok"] and few["agreed_share"] == 0.25
    assert few["routings_differ_share"] == 12 / 96


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 54: the program's reading nearest each limit
    over its seeds, the limit, and the nearest reading OF THE SAME QUANTITY
    that the limit has to refuse (``perf/tools/mimo_lowp.py``, my chip
    runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    names = {"direct.row_rms_limit": check["direct"]["row_rms_limit"],
             "direct.tolerance": check["direct"]["tolerance"],
             "direct.routings_limit": check["direct"]["routings_limit"],
             "over_share_limit": check["over_share_limit"]}
    assert set(readings) - {"why", "direct.agreed_floor"} <= set(names)
    for name in set(readings) - {"why", "direct.agreed_floor"}:
        program, control = readings[name]
        assert program < names[name] < control, name
    if "direct.agreed_floor" in readings:   # a floor: the other way round
        program, control = readings["direct.agreed_floor"]
        assert control < check["direct"]["agreed_floor"] < program
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["probes"] == 1 and check["probe_new_tokens"] == 128
    for text in (check["why"], check["direct"]["why"]):
        assert "float8" in text and "bfloat16" in text


def test_the_control_tool_plants_each_omission_in_the_references_own_terms():
    """``perf/tools/mimo_lowp.py`` at a tiny size: each variant traces the
    reference through the wrapped operations or a changed key, every one
    moves the result, and a lower precision reads further from the float32
    reference."""
    import jax

    from progen_tpu.models import mimo_v2

    tool = harness.load_module("perf/tools/mimo_lowp.py")
    assert set(tool.VARIANTS) == {
        "as-stated", "islands-bf16", "fp8-operands", "no-sink",
        "sink-on-full", "no-value-scale", "window-129",
        "sliding-at-full-base", "one-notch-below"}
    c = mimo_v2.MiMoV2Config.from_dict(TINY)
    params = mimo_v2.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_mimo.forward_row(params, tokens, TINY)
        blocked, _ = reference_mimo.forward_row(params, tokens, TINY,
                                                q_block=16, row_block=16)
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    far = {}
    # the tiny window is 4: "window-129" is planted as window + 1
    tiny_variants = dict(tool.VARIANTS, **{
        "window-129": (None, (), {"sliding_window": 5}, False)})
    with mock.patch.object(tool, "VARIANTS", tiny_variants):
        for name in tool.VARIANTS:
            forward_row, ctx, weights = tool.variant_forward(name, TINY)
            with ctx():
                got, _ = forward_row(weights(params), tokens, TINY)
            far[name] = float(np.abs(np.asarray(got, np.float32)
                                     - want).mean())
    assert 0 < far["as-stated"] < far["fp8-operands"] < 1
    for name in ("no-sink", "sink-on-full", "no-value-scale", "window-129",
                 "sliding-at-full-base"):
        assert far[name] > 2 * far["as-stated"], (name, far)
    assert far["one-notch-below"] > far["as-stated"]
    sunk = tool.with_full_sinks(params)
    assert all("sink" in layer["attn"] for layer in sunk["layers"])
    assert "sink" not in params["layers"][0]["attn"]
    # nothing stays patched
    for name in ("product", "softmax", "rms_norm", "route"):
        assert getattr(reference_mimo, name).__module__ == (
            "perf.lib.reference_mimo")


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
    _dump(root / "perf/configs/tiny-mimo.json", TINY)
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
    workload.update(name="serve-tiny-mimo", config="tiny-mimo",
                    traffic="tiny-longdoc",
                    engine={"num_slots": 16, "chunk_size": 6, "max_len": 64})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"],
                    long_prime_tokens=[20, 24],
                    readmit_prime_tokens=[1, 3, 4, 5],
                    prime_number_between=[7, 13],
                    longest_prime_tokens=[31, 34], compared_slots=8,
                    row_rms_limit=0.6, tolerance=0.6, agreed_floor=0.1,
                    routings_limit=0.5))
    _dump(root / "perf/workloads/serve-tiny-mimo.json", workload)
    bench["configs"].append({
        "name": "tiny-mimo", "source": "perf/tests",
        "file": "perf/configs/tiny-mimo.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-mimo", "config": "tiny-mimo",
        "traffic": "tiny-longdoc", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-mimo")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_mimo_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-mimo", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-mimo", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0.7 < value["moe.held_assignments_per_token"] < 1.3   # 2 x 8 / 16
    assert value["attn.full_rows_read_per_live_row"] > 1
    assert value["attn.window_rows_read_per_live_row.trinity"] >= 1
    assert 0 < value["attn.window_share_of_context.trinity"] < 1
    assert value["attn.prefill_pairs_visited_per_allowed.trinity"] > 1
    assert 0 < value["moe.experts_touched_share"] <= 1     # of the 8 held
    # 2 full blocks of 64 rows x 1 head against 5 rings of 4 rows x 2 heads
    assert value["attn.full_share_of_cache_bytes.mimo"] == pytest.approx(
        100 * 2 * 64 / (2 * 64 + 5 * 4 * 2))
    # the shares' reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 16}},
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
           "workload": {"engine": {"num_slots": 16}},
           "counters": {"admitted_primes": [300]}, "trace": None}
    for name in FROM_THE_FAMILY:
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read(obs, spec) is None, name
