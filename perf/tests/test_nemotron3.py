"""The Nemotron-H cell rehearsed on the CPU at tiny widths through the
harness (as test_lfm2.py does for LFM2's), the configuration file against
the catalog row and the program's defaults, the cost functions against hand
counts, and the measures of the comparison.  Nothing here measures
anything."""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from perf.lib import harness, nemotron3_cost, reference_nemotron3
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-nemotron3-longgen-backlog"
CONFIG = harness.load_config("nemotron-3-super-120b-a12b-ep4pp8")
BENCH = harness.load_benchmark()
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the families share, and this family's own
SHARES = {"decode.hbm_share.nemotron3", "prefill.mfu.nemotron3",
          "moe_decode_roofline.nemotron3"}
OWN = SHARES | {"ssm.state_share_of_step_bytes.nemotron3"}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "moe.experts_touched_share",
    "attn.full_rows_read_per_live_row",
    "ssm.scan_slots_per_real_token.granite"}
METRICS = SHARED | FROM_THE_FAMILY
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "experts_held",
           "vocab_size", "num_nextn_predict_layers"]

TINY = dict(
    name="tiny-nemotron3", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, num_hidden_layers=5, hybrid_override_pattern="MEM*E",
    mamba_num_heads=8, mamba_head_dim=16, n_groups=4, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, expand=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=5,
    norm_topk_prob=True, layer_norm_epsilon=1e-5,
    max_position_embeddings=128, experts_held=8, first_expert=0,
    num_nextn_predict_layers=0, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")


def test_every_published_key_is_in_the_file_and_no_width_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 11
    assert CONFIG["hybrid_override_pattern"] == row["config"][
        "hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    # every 11-layer stage of the published pattern has 5 M, 5 E, 1 *
    pattern = row["config"]["hybrid_override_pattern"]
    assert {tuple(pattern[i:i + 11].count(k) for k in "ME*")
            for i in range(0, 88, 11)} == {(5, 5, 1)}
    assert CONFIG["n_routed_experts"] == 512 and CONFIG["experts_held"] == 128
    assert CONFIG["vocab_size"] == 32768 == row["config"]["vocab_size"] // 4
    assert CONFIG["num_nextn_predict_layers"] == 0
    assert set(CONFIG["reduced_from"]) == set(REDUCED)
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    for key in ("no_rotation", "route_norm_eps", "in_proj_order",
                "gated_group_norm", "latent_placement", "dt_clamp",
                "tie_word_embeddings", "seeded_scales", "prefill_bucket"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "32 v5e chips" in CONFIG["deployment"]
    assert "4,648.2 M parameters" in CONFIG["deployment"]
    assert CONFIG["cut_counts"]["kv_bytes_per_token"] == 1024
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.nemotron_h import NemotronHConfig

    default = NemotronHConfig()
    c = NemotronHConfig.from_dict(CONFIG)
    assert c == NemotronHConfig(
        num_hidden_layers=11, vocab_size=32768, experts_held=128,
        hybrid_override_pattern=default.hybrid_override_pattern[:11])
    for key, value in CONFIG["published"].items():
        if hasattr(default, key) and key != "num_nextn_predict_layers":
            assert getattr(default, key) == value, key
    for key in ("router_logit_std", "router_bias_std", "prefill_bucket"):
        assert getattr(default, key) == CONFIG[key], key
    assert c.seq_len == 262144 and c.num_layers == 11
    assert (c.experts_held, c.router_width, c.first_expert) == (128, 512, 0)
    assert c.moe_topk == 22 and c.mamba_inner == 8192


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["traffic"] == "backlog-longgen"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == ("perf/configs/"
                              "nemotron-3-super-120b-a12b-ep4pp8.json")
    assert "9.30 GB" in listed["why"]
    assert len(BENCH["per_layer"]) <= 128
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "serve_tok_s"}
    layer = harness.cell_metrics(BENCH, CELL, "per_layer")
    assert {m["name"] for m in layer} == METRICS
    assert {m["name"] for m in layer if m["workloads"] == [CELL]} == OWN
    for m in layer:       # each has its file, and the file says the same
        assert m["moves"] == ("setup_s" if m["name"].startswith("xla.")
                              else "serve_tok_s")
        spec = harness.load_metric(m["name"])
        assert all(spec[k] == v for k, v in m.items() if k != "workloads")
        assert os.path.exists(os.path.join(harness.ROOT, spec["reader"]))
    for text in [entry["why"], listed["why"]]:
        assert 0 < len(text) <= 200
    workload = harness.load_workload(CELL)
    assert workload["engine"] == {"num_slots": 64, "chunk_size": 32,
                                  "max_len": 3072}
    # the siblings' window; the runner counts the ramp from the backlog's
    # submission (the cell's why says what that does to where a window ends)
    assert workload["window"] == harness.load_workload(
        "serve-dsv2-decode-backlog")["window"]
    assert workload["runner"] == "perf/runners/serve_nemotron3.py"


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 49's arithmetic
    assert nemotron3_cost.mamba_params(c) == (
        4096 * 18560 + 8192 * 4096) == 109_576_192
    assert nemotron3_cost.attention_params(c) == 35_651_584
    assert nemotron3_cost.expert_params(c) == 2 * 1024 * 2688 == 5_505_024
    assert nemotron3_cost.expert_layer_params_outside(c) == (
        4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376) == 54_525_952
    assert [nemotron3_cost.layers_of(c, k) for k in "ME*"] == [5, 5, 1]
    assert nemotron3_cost.carry_bytes_per_row(c) == 4_194_304
    assert nemotron3_cost.tail_bytes_per_row(c) == 3 * 10240 * 2
    assert nemotron3_cost.kv_bytes_per_row(c) == 1024
    whole = dict(c, **{k: CONFIG["published"][k] for k in CONFIG["reduced"]
                       if k in CONFIG["published"]})
    del whole["experts_held"]
    assert 120.6e9 < nemotron3_cost.total_params(whole) < 120.7e9


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import nemotron_h

    c = nemotron_h.NemotronHConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: nemotron_h.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # norm scales, the mixers' conv weights and biases, dt_bias, A, D and
    # gated norms, the routers' biases
    small = (12 * 4096 + 5 * (10240 * 4 + 10240 + 3 * 128 + 8192) + 5 * 512)
    assert made - small == nemotron3_cost.total_params(CONFIG)
    assert made == 4_648_163_712           # the figure the files state
    assert made * 2 - CONFIG["cut_counts"]["parameter_bytes"] == -(
        5 * (3 * 128 * 2 + 512 * 2))       # float32 among the small ones
    assert shapes["head"].shape == (4096, 32768)


def test_prefill_flops_and_decode_bytes_by_hand():
    c = CONFIG
    held = 600 * 5.5 * 5
    outside = 5 * 109_576_192 + 35_651_584 + 5 * 54_525_952
    pairs = 4 * 128 * 129 / 2 + 88 * 89 / 2            # chunks of 128
    scan = 2 * 8 * 128 * pairs + 2 * 8192 * pairs + 4 * 8192 * 128 * 600
    want = (600 * 2 * outside + 5 * scan + 2 * 2 * 32 * 128 * 600 * 601 / 2
            + 2 * 5_505_024 * held + 2 * 4096 * 32768)
    assert nemotron3_cost.prefill_flops(c, [600], held) == want
    # ISSUE 49's step: 60 live rows, 118 of 128 experts a layer touched
    terms = nemotron3_cost.decode_terms(c, 1, 5 * 118, 5 * 60, 60 * 1200)
    assert terms["experts_touched"] == 590 * 5_505_024 * 2
    assert terms["carry"] == 300 * 2 * 4_194_304
    assert terms["mamba_projections"] == 5 * 109_576_192 * 2
    assert terms["head"] == 4096 * 32768 * 2
    assert terms["grown_rows"] == 60 * 1200 * 1024
    moved = sum(terms.values())
    assert moved == nemotron3_cost.decode_bytes(c, 1, 590, 300, 72000)
    assert 0.55 < terms["experts_touched"] / moved < 0.65
    assert 0.2 < terms["carry"] / moved < 0.26
    assert 12.5e-3 < moved / 819e9 < 14e-3             # the floor of a step
    assert nemotron3_cost.kernel_bytes(c, 118, 1, 64) == (
        118 * 11_010_048 + 64 * 1024 * 6)


# ------------------------------------------- the comparison's measures


def test_direct_primes_put_three_rows_under_the_taps_into_used_slots():
    runner = harness.load_module("perf/runners/serve_nemotron3.py")
    lfm2_runner = harness.load_module("perf/runners/serve_lfm2.py")
    check = harness.load_workload(CELL)["correct"]["direct"]
    assert check["prime_tokens"] == [513, 1023]
    for seed in (0, 2 ** 31 + 9):
        first, second = runner.direct_primes(lfm2_runner, check, seed, 32768,
                                             4, 64)
        assert len(first) == 4 and len(second) == 64
        assert [len(p) for p in second[:3]] == [1, 2, 3]
        assert all(len(p) <= 512 < len(q) for p, q in zip(second, first))
        n = len(second[4])                  # a prime number of tokens
        assert all(n % d for d in range(2, int(n ** 0.5) + 1))
        assert all((p > 0).all() and (p < 32768).all()
                   and p.dtype == np.int32 for p in first + second)


def test_every_compared_row_is_held_by_itself_and_assignments_are_counted():
    runner = harness.load_module("perf/runners/serve_nemotron3.py")
    lfm2_runner = harness.load_module("perf/runners/serve_lfm2.py")
    check = {"row_rms_limit": 0.5, "rms_limit": 0.3,
             "assignments_limit": 0.2}
    rng = np.random.default_rng(0)
    want = rng.normal(size=(32, 512))
    sets = np.tile(np.arange(22), (32, 5, 1))
    groups = lfm2_runner.direct_groups(4, 16)
    near = want + 0.1 * rng.normal(size=want.shape)
    good = runner.direct_reading(near, want, sets[..., ::-1], sets, groups,
                                 check)
    assert good["ok"] and good["assignments_differ_share"] == 0
    assert good["assignments"] == 32 * 5 * 22 and good["rows"] == 32
    far = near.copy()
    far[0] = rng.normal(size=512)           # the slot with the 1-token prime
    bad = runner.direct_reading(far, want, sets, sets, groups, check)
    assert not bad["ok"] and bad["row_rms_max"]["readmitted"] > 1.2
    assert bad["row_rms_max"]["admitted"] < 0.12 and bad["rms"] < 0.3
    loose = runner.direct_reading(want + 0.4 * rng.normal(size=want.shape),
                                  want, sets, sets, groups, check)
    assert not loose["ok"] and max(loose["row_rms_max"].values()) < 0.5
    other = sets.copy()
    other[:, :, :2] = 100 + np.arange(2)    # two strangers of 22 everywhere
    one = runner.direct_reading(near, want, other, sets, groups, check)
    assert one["ok"] and one["assignments_differ_share"] == 2 / 22
    assert one["sets_differ_share"] == 1.0  # every SET differs: no measure
    other[:, :, :6] = 100 + np.arange(6)
    assert not runner.direct_reading(near, want, other, sets, groups,
                                     check)["ok"]


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 49: the program's reading nearest each limit
    over its seeds, the limit, and the nearest reading OF THE SAME QUANTITY
    that the limit has to refuse (``perf/tools/nemotron3_lowp.py``, my chip
    runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    names = {"direct.row_rms_limit": check["direct"]["row_rms_limit"],
             "direct.rms_limit": check["direct"]["rms_limit"],
             "direct.assignments_limit": check["direct"][
                 "assignments_limit"],
             "over_share_limit": check["over_share_limit"]}
    assert set(readings) - {"why"} <= set(names)
    for name in set(readings) - {"why"}:
        program, control = readings[name]
        assert program < names[name] < control, name
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["probes"] == 2 and check["probe_new_tokens"] == 128
    for text in (check["why"], check["direct"]["why"]):
        assert "float8" in text and "bfloat16" in text


def test_the_control_tool_lowers_the_references_own_operations():
    """``perf/tools/nemotron3_lowp.py`` at a tiny size: each variant traces
    the reference through the wrapped operations, a lower precision reads
    further from the float32 reference, and a bfloat16 carry alone moves
    the result."""
    import jax

    from progen_tpu.models import nemotron_h

    tool = harness.load_module("perf/tools/nemotron3_lowp.py")
    assert set(tool.VARIANTS) == {"as-stated", "carry-bf16", "islands-bf16",
                                  "one-notch-below"}
    c = nemotron_h.NemotronHConfig.from_dict(TINY)
    params = nemotron_h.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_nemotron3.forward_row(params, tokens, TINY)
    far = {}
    for name, (narrower, islands) in tool.VARIANTS.items():
        with tool.lowered(narrower and getattr(jax.numpy, narrower),
                          islands):
            got, _ = reference_nemotron3.forward_row(params, tokens, TINY)
        far[name] = float(np.abs(np.asarray(got, np.float32) - want).mean())
    assert 0 < far["as-stated"] < far["one-notch-below"]
    assert far["carry-bf16"] != far["as-stated"]
    # nothing stays patched
    for name in ("product", "carry", "island"):
        assert getattr(reference_nemotron3, name).__module__ == (
            "perf.lib.reference_nemotron3")


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
    _dump(root / "perf/configs/tiny-nemotron3.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-longgen"), name="tiny-longgen",
        arrivals={"kind": "backlog", "requests_per_second": 400.0},
        prime_tokens={"kind": "lognormal", "median": 8, "sigma": 1.0,
                      "min": 1, "max": 30},
        generated_tokens={"kind": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 6, "max": 22})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    traffic["sampling"] = dict(traffic["sampling"], top_k=5)
    _dump(root / "perf/traffic/tiny-longgen.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-nemotron3", config="tiny-nemotron3",
                    traffic="tiny-longgen",
                    engine={"num_slots": 32, "chunk_size": 6, "max_len": 56})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"], prime_tokens=[17, 30],
                    readmit_prime_tokens=[1, 16], compared_slots=8,
                    row_rms_limit=0.6, rms_limit=0.3,
                    assignments_limit=0.2))
    _dump(root / "perf/workloads/serve-tiny-nemotron3.json", workload)
    bench["configs"].append({
        "name": "tiny-nemotron3", "source": "perf/tests",
        "file": "perf/configs/tiny-nemotron3.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-nemotron3", "config": "tiny-nemotron3",
        "traffic": "tiny-longgen", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-nemotron3")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_nemotron3_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-nemotron3", 2 ** 31 + 33, 1.5, False,
                           0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-nemotron3", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 1.5 < value["moe.held_assignments_per_token"] < 2.5  # 4 x 8 / 16
    assert value["attn.full_rows_read_per_live_row"] > 1
    assert 0 < value["moe.experts_touched_share"] <= 1     # of the 8 held
    assert value["ssm.scan_slots_per_real_token.granite"] >= 1
    assert 0 < value["ssm.state_share_of_step_bytes.nemotron3"] < 100
    # the shares' reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "workload": {"engine": {"num_slots": 32}},
           "counters": {"admitted_primes": [5, 20],
                        "stretch_counters": {"moe.expert_passes": 40.0,
                                             "moe.decode_layers": 10.0}},
           "trace": {"device_ops": [["moe_decode_fwd [custom-call] f32[32,"
                                     "32]", 1e-3], ["fusion", 2e-3]]}}
    for name in SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0
    obs["trace"]["device_ops"].pop(0)       # the kernel not among the rows
    spec = copy.load_metric("moe_decode_roofline.nemotron3")
    assert copy.load_module(spec["reader"]).read(obs, spec) is None


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
