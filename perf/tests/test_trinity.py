"""The Trinity cell rehearsed on the CPU at tiny widths through the harness
(as test_deepseek_v2.py does for DeepSeek-V2's), the configuration file
against the catalog row and the program's defaults, and the cost functions
against hand counts.  Nothing here measures anything."""

import importlib.util
import json
import os
import shutil

import pytest

from perf.lib import harness, trinity_cost
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-trinity-mixedlen-backlog"
CONFIG = harness.load_config("trinity-mini-ep8")
BENCH = harness.load_benchmark()
REDUCED = ("num_hidden_layers", "num_dense_layers", "layer_types",
           "experts_held", "vocab_size")
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "num_experts_per_tok", "num_experts", "num_shared_experts",
          "n_group", "topk_group", "route_scale", "route_norm", "score_func",
          "rope_theta", "rms_norm_eps", "sliding_window", "mup_enabled",
          "global_attn_every_n_layers", "max_position_embeddings")
SLIDING, FULL = "sliding_attention", "full_attention"
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the families share (experts, grown keys), this family's own
SHARES = {"decode.hbm_share.trinity", "prefill.mfu.trinity"}
OWN = SHARES | {
    "moe.experts_touched_share.trinity",
    "attn.window_rows_read_per_live_row.trinity",
    "attn.window_share_of_context.trinity",
    "attn.prefill_pairs_visited_per_allowed.trinity"}
FROM_THE_FAMILY = OWN | {
    "moe.held_assignments_per_token", "moe.held_load_max_over_mean",
    "moe.expert_passes_per_touched", "attn.full_rows_read_per_live_row"}
METRICS = SHARED | FROM_THE_FAMILY

TINY = dict(
    name="tiny-trinity", source="perf/tests", reduced=[], vocab_size=64,
    hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
    num_hidden_layers=5, num_dense_layers=1, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, sliding_window=8,
    layer_types=[SLIDING] * 3 + [FULL, SLIDING], num_experts=8,
    num_experts_per_tok=3, num_shared_experts=1, n_group=1, topk_group=1,
    route_norm=True, route_scale=2.826, score_func="sigmoid",
    mup_enabled=True, rms_norm_eps=1e-5, rope_theta=10000,
    max_position_embeddings=64, experts_held=2, first_expert=0,
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
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["vocab_size"], CONFIG["experts_held"],
            CONFIG["first_expert"]) == (9, 1, 25024, 16, 0)
    # the published list's first nine: two whole periods after the dense
    # layer, 6 sliding and 2 full among the expert layers
    assert CONFIG["layer_types"] == published["layer_types"][:9] == (
        [SLIDING] * 3 + [FULL]) * 2 + [SLIDING]
    assert "experts_held" not in published          # the share's own key
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    # what the config has no key for is listed, one line each
    for key in ("attention_gate", "qk_norm", "rope", "norms", "mup",
                "router_bias", "seeded_weights", "router_logit_std",
                "router_bias_std", "prefill_bucket"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "8 chips that share each layer" in CONFIG["deployment"]
    assert "1,243,428,096 parameters" in CONFIG["deployment"]
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_catalog_row_is_what_was_copied():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Trinity-Mini")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]
    # every key of the row is in the file under the same name
    assert not [k for k in row["config"] if k not in CONFIG]


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.trinity import TrinityConfig

    default, published = TrinityConfig(), CONFIG["published"]
    for key in WIDTHS + ("num_hidden_layers", "num_dense_layers",
                         "vocab_size"):
        assert getattr(default, key) == published[key], key
    assert list(default.layer_types) == published["layer_types"]
    assert default.experts_held == published["num_experts"]
    c = TrinityConfig.from_dict(CONFIG)
    for key in WIDTHS + tuple(k for k in REDUCED if k != "layer_types"):
        assert getattr(c, key) == CONFIG[key], key
    assert list(c.layer_types) == CONFIG["layer_types"]
    assert c.router_width == 128 and c.moe_topk == 8 and c.num_layers == 9
    assert c.seq_len == 131072 == CONFIG["max_position_embeddings"]
    assert (c.router_logit_std, c.router_bias_std, c.prefill_bucket) == (
        CONFIG["router_logit_std"], CONFIG["router_bias_std"],
        CONFIG["prefill_bucket"])


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "backlog-heavytail"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/trinity-mini-ep8.json"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "serve_tok_s"}
    layer = harness.cell_metrics(BENCH, CELL, "per_layer")
    assert {m["name"] for m in layer} == METRICS
    assert all(m["moves"] in e2e for m in layer)
    assert {m["name"] for m in layer if m["workloads"] == [CELL]} == OWN
    for m in layer:       # each has its file, and the file says the same
        spec = harness.load_metric(m["name"])
        assert all(spec[k] == v for k, v in m.items() if k != "workloads")
        assert os.path.exists(os.path.join(harness.ROOT, spec["reader"]))
    for text in [entry["why"], listed["why"]]:
        assert 0 < len(text) <= 200
    traffic = harness.load_traffic(entry["traffic"])
    assert traffic["arrivals"] == {"kind": "backlog",
                                   "requests_per_second": 18.0}
    assert traffic["prime_tokens"] == {
        "kind": "lognormal", "median": 1536, "sigma": 1.0, "min": 256,
        "max": 8192}
    assert traffic["generated_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.5, "min": 128,
        "max": 1024}
    assert traffic["stagger"] == {"first": 64, "min": 0.05, "max": 1.0}
    assert traffic["sampling"]["top_k"] == 25
    assert traffic["schedule_seed"] == 34
    workload = harness.load_workload(CELL)
    assert traffic["prime_tokens"]["max"] + traffic["generated_tokens"][
        "max"] == workload["engine"]["max_len"] == 9216
    assert workload["engine"]["chunk_size"] == 32
    assert workload["engine"]["num_slots"] in (64, 48)
    assert workload["runner"] == "perf/runners/serve_trinity.py"


def test_the_traffic_is_past_the_window_for_two_primes_in_five():
    """ISSUE 34's arithmetic on the mix: 39 % of primes longer than the
    2048 window, 5 % at the clip, a mean of about 2.3 k."""
    import numpy as np

    from perf.lib import traffic as gen

    mix = harness.load_traffic("backlog-heavytail")
    reqs = gen.serve_requests(mix, 2 ** 31 + 7, 35, CONFIG["vocab_size"])
    assert len(reqs) == 630
    primes = np.array([len(r["prime"]) for r in reqs])
    assert 0.37 < (primes > 2048).mean() < 0.41
    assert 0.04 < (primes == 8192).mean() < 0.06
    assert 2200 < primes.mean() < 2400 and primes.min() == 256
    assert max(max(r["prime"]) for r in reqs) < CONFIG["vocab_size"]
    assert min(min(r["prime"]) for r in reqs) >= 1


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 34: attention 27.26 M (q 8.389 + k 1.049 + v 1.049 + o 8.389 +
    # gate 8.389), shared 6.29 M, router 0.26 M, an expert 6.29 M, the
    # dense FFN 37.75 M
    assert trinity_cost.attention_params(c) == (
        3 * 2048 * 4096 + 2 * 2048 * 512) == 27_262_976
    assert trinity_cost.shared_params(c) == 6_291_456
    assert trinity_cost.router_params(c) == 262_144
    assert trinity_cost.expert_params(c) == 6_291_456
    assert trinity_cost.dense_ffn_params(c) == 37_748_736
    assert trinity_cost.expert_layers(c) == 8
    assert trinity_cost.layers_of(c, SLIDING) == 7
    assert trinity_cost.layers_of(c, FULL) == 2
    # dense layer 65.01 M + 8 x 134.48 M + 102.50 M of vocabulary
    total = (9 * 27_262_976 + 37_748_736
             + 8 * (6_291_456 + 262_144 + 16 * 6_291_456)
             + 2 * 25024 * 2048)
    assert trinity_cost.total_params(c) == total == 1_243_348_992
    assert round(total / 1e6, 1) == 1243.3
    assert trinity_cost.kv_bytes_per_row(c) == 2 * 4 * 128 * 2


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import trinity

    c = trinity.TrinityConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(
        lambda k: trinity.init_params(c, k), jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # four norms a layer, the q and k norms, the final norm, the routers'
    # biases
    small = 9 * (4 * 2048 + 2 * 128) + 2048 + 8 * 128
    assert made - small == trinity_cost.total_params(CONFIG)
    assert made == 1_243_428_096           # the figure the files state


def test_attention_pairs_follow_the_mask():
    assert trinity_cost.attention_pairs(5, None) == 15
    assert trinity_cost.attention_pairs(5, 8) == 15          # inside it
    assert trinity_cost.attention_pairs(8, 8) == 36
    # past it: 8 keys a token
    assert trinity_cost.attention_pairs(10, 8) == 36 + 2 * 8
    brute = sum(min(i + 1, 2048) for i in range(8192))
    assert trinity_cost.attention_pairs(8192, 2048) == brute


def test_prefill_flops_by_hand():
    c = CONFIG
    # ISSUE 34: about 1.0 GFLOP a real token, 0.77 of it linear
    linear = 2 * (9 * 27_262_976 + 37_748_736 + 8 * (6_291_456 + 262_144))
    assert round(linear / 1e9, 2) == 0.67
    one = trinity_cost.prefill_flops(c, [1000], 0)
    pair = 2 * 2 * 32 * 128
    assert one == (1000 * linear + pair * 9 * 500_500
                   + 2 * 2048 * 25024)
    # past the window a sliding layer costs 2048 pairs a token, a full one
    # all of them
    long = trinity_cost.prefill_flops(c, [8192], 8 * 8192)
    pairs = (2 * 8192 * 8193 / 2
             + 7 * (2048 * 2049 / 2 + (8192 - 2048) * 2048))
    assert long == (8192 * linear + pair * pairs
                    + 2 * 6_291_456 * 8 * 8192 + 2 * 2048 * 25024)
    # with 1.0 held assignment a token and layer: 0.77 GFLOP linear
    assert round((linear + 8 * 2 * 6_291_456) / 1e9, 2) == 0.77


def test_decode_bytes_by_hand():
    c = CONFIG
    fixed = (9 * 27_262_976 + 37_748_736 + 8 * (6_291_456 + 262_144)
             + 2048 * 25024) * 2
    assert trinity_cost.decode_bytes(c, 1, 0, 0, 0) == fixed
    # ISSUE 34's step: 64 live rows of mean context 2.5 k (1.8 k of it in
    # a window), all 16 held experts of each of 8 layers touched
    terms = trinity_cost.decode_terms(c, 1, 8 * 16, 64 * 1800, 64 * 2500)
    assert terms["routed_experts_touched"] == 128 * 6_291_456 * 2
    assert terms["ring_rows"] == 64 * 1800 * 7 * 2048
    assert terms["grown_rows"] == 64 * 2500 * 2 * 2048
    assert terms["head"] == 2048 * 25024 * 2
    assert 2.3e9 < fixed + terms["routed_experts_touched"] < 2.5e9
    assert 1.4e9 < terms["ring_rows"] < 1.7e9
    assert 0.6e9 < terms["grown_rows"] < 0.8e9
    got = trinity_cost.decode_bytes(c, 10, 1280, 1_152_000, 1_600_000)
    assert got == (10 * fixed + 1280 * 6_291_456 * 2
                   + 1_152_000 * 7 * 2048 + 1_600_000 * 2 * 2048)


# ------------------------------------------- the comparison's measures


def test_direct_rows_stand_on_both_sides_of_the_window():
    import numpy as np

    runner = harness.load_module("perf/runners/serve_trinity.py")
    check = harness.load_workload(CELL)["correct"]["direct"]
    for seed in (0, 5, 2 ** 31 + 9):
        lengths, tokens, at = runner.direct_rows(check, seed, 25024, 4, 2048)
        assert lengths[0] > 4096 and lengths[1] == 2046
        assert lengths.min() >= 256 and lengths.max() <= 8192
        assert tokens.shape == (4, 8192 + check["decode_steps"])
        assert at.shape == (4, 16 + check["decode_steps"])
        for i, n in enumerate(lengths):
            steps = check["decode_steps"]
            assert at[i, -steps:].tolist() == list(range(n, n + steps))
            assert (tokens[i, :n + steps] > 0).all()
            assert (tokens[i, n + steps:] == 0).all()
            assert at[i, 0] == 0 and at[i, -steps - 1] == n - 1
        # the short row's third step is the first past the window
        assert at[1, -check["decode_steps"] + 2] == 2048
        again = runner.direct_rows(check, seed, 25024, 4, 2048)
        np.testing.assert_array_equal(tokens, again[1])


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 34: the program's largest reading over its
    seeds, the limit, the control one notch below (my chip runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    assert set(readings) - {"why"} == {
        "direct.tolerance", "direct.routings_limit", "direct.agreed_floor",
        "over_share_limit"}
    for name, limit in (("direct.tolerance", check["direct"]["tolerance"]),
                        ("direct.routings_limit",
                         check["direct"]["routings_limit"]),
                        ("over_share_limit", check["over_share_limit"])):
        program, control = readings[name]     # the program's largest
        assert program < limit < control, name
    program, control = readings["direct.agreed_floor"]    # its smallest
    assert control < check["direct"]["agreed_floor"] < program
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["direct"]["prime_tokens"] == [256, 8192]
    assert check["direct"]["decode_steps"] >= 3    # the short row wraps


def test_the_control_tool_lowers_the_references_own_operations():
    """``perf/tools/trinity_lowp.py`` at a tiny size: each variant traces
    the reference through the wrapped operations, and a lower precision
    reads further from the float32 reference."""
    import inspect

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf.lib import reference_trinity
    from progen_tpu.models import trinity

    tool = harness.load_module("perf/tools/trinity_lowp.py")
    source = inspect.getsource(reference_trinity)
    assert f'"{tool.HEAD}"' in source and f'{tool.SCORES}"' in source
    config = {**TINY, "experts_held": 8}
    c = trinity.TrinityConfig.from_dict(config)
    params = trinity.init_params(c, jax.random.key(3), trinity.bf16_policy())
    tokens = jax.random.randint(jax.random.key(4), (40,), 1, 64)

    def forward():
        return jax.jit(lambda p, t: reference_trinity.forward_row(
            p, t, config))(params, tokens)

    with jax.default_matmul_precision("highest"):
        want, want_sets = forward()
        rms = {}
        for name, (narrower, islands) in tool.VARIANTS.items():
            with tool.lowered(narrower and getattr(jnp, narrower), islands):
                got, sets = forward()
            assert got.shape == want.shape and sets.shape == want_sets.shape
            rounded = got.astype(jnp.bfloat16).astype(jnp.float32)
            # bfloat16 logits exactly where the island is lowered
            assert bool((rounded == got).all()) == ("logits" in islands)
            rms[name] = float(np.sqrt(np.mean(
                (np.asarray(got, np.float32) - np.asarray(want)) ** 2)))
        again, _ = forward()            # the patches are gone
    np.testing.assert_array_equal(again, want)
    assert 0 < rms["as-stated"] < rms["one-notch-below"]
    assert rms["one-notch-below"] > 2 * rms["as-stated"]


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
    _dump(root / "perf/configs/tiny-trinity.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-heavytail"), name="tiny-heavytail",
        arrivals={"kind": "backlog", "requests_per_second": 400.0},
        prime_tokens={"kind": "lognormal", "median": 8, "sigma": 1.0,
                      "min": 3, "max": 30},
        generated_tokens={"kind": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 6, "max": 22})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    _dump(root / "perf/traffic/tiny-heavytail.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-trinity", config="tiny-trinity",
                    traffic="tiny-heavytail",
                    engine={"num_slots": 32, "chunk_size": 4, "max_len": 56})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=6, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"], prime_tokens=[9, 30],
                    positions=8, tolerance=0.5, routings_limit=1.0,
                    agreed_floor=0.0))
    _dump(root / "perf/workloads/serve-tiny-trinity.json", workload)
    bench["configs"].append({"name": "tiny-trinity", "source": "perf/tests",
                             "file": "perf/configs/tiny-trinity.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-trinity", "config": "tiny-trinity",
        "traffic": "tiny-heavytail", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-trinity")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_trinity_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-trinity", 2 ** 31 + 33, 1.5, False,
                           0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-trinity", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k.removesuffix(".trinity"): v["value"]
             for k, v in traced["metrics"].items()}
    # 3 of 8 a token, 2 of 8 held: 0.75 assignments a token on average
    assert 0 < value["moe.held_assignments_per_token"] < 3
    assert value["moe.held_load_max_over_mean"] >= 1
    # the XLA decode core reads every row of every slot: far more than the
    # live rows hold; the traffic is past the window of 8
    assert value["attn.full_rows_read_per_live_row"] > 1
    assert value["attn.window_rows_read_per_live_row"] > 1
    assert 0 < value["attn.window_share_of_context"] < 1
    assert not [p for p in os.listdir(root) if p not in
                ("perf", "BENCHMARK.json", ".jax_cache")]
    # the shares' reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "counters": {"admitted_primes": [5, 20]}}
    for name in SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0


def test_readers_of_the_new_metrics_find_nothing_in_a_program_without_them(
        monkeypatch):
    """On the parent the registry has no such gauge: ``None``, no raise."""
    from progen_tpu.observe import metrics

    monkeypatch.setattr(metrics, "_REGISTRY", metrics.MetricsRegistry())
    obs = {"config": CONFIG, "device_kind": "TPU v5 lite",
           "counters": {"admitted_primes": [300]}}
    for name in FROM_THE_FAMILY:
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read(obs, spec) is None, name
