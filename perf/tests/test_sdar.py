"""The SDAR cell rehearsed on the CPU at tiny widths through the harness (as
test_granite.py does for Granite's), the configuration file against the
catalog row and the program's defaults, the cost functions against hand
counts, and the measures of the comparison.  Nothing here measures
anything."""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from perf.lib import harness, reference_sdar, sdar_cost
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-sdar-blockdiff-backlog"
CONFIG = harness.load_config("sdar-30b-a3b-chat-pp8")
BENCH = harness.load_benchmark()
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the families share, and this family's own (where an entry
# lies in BENCHMARK.json's list is a later PR's to change)
SHARES = {"decode.hbm_share.sdar", "prefill.mfu.sdar"}
OWN = SHARES | {
    "diffusion.tokens_per_forward.sdar",
    "diffusion.commit_share_of_forwards.sdar",
    "moe.rows_per_touched_expert.sdar", "moe.experts_touched_share.sdar"}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "attn.full_rows_read_per_live_row"}
METRICS = SHARED | FROM_THE_FAMILY

TINY = dict(
    name="tiny-sdar", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1000000, max_position_embeddings=128,
    block_length=4, mask_token_id=95, denoising_steps=4,
    remasking="low_confidence_dynamic", confidence_threshold=0.9,
    prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")


def test_every_published_key_is_in_the_file_and_only_the_depth_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 6
    assert CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["num_experts"] == 128 and CONFIG["vocab_size"] == 151936
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    for key in ("block_length", "mask_token_id", "logit_shift", "remasking",
                "confidence_threshold", "qk_norm", "rope", "router",
                "noise_schedule", "seeded_weights", "router_logit_std",
                "prefill_bucket"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "8 stages" in CONFIG["deployment"] or "8-stage" in CONFIG[
        "deployment"]
    assert "4,361,055,744 parameters" in CONFIG["deployment"]
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.sdar import SDARConfig

    default = SDARConfig()
    c = SDARConfig.from_dict(CONFIG)
    assert c == SDARConfig(num_hidden_layers=6)
    for key, value in CONFIG["published"].items():
        if hasattr(default, key) and key != "mlp_only_layers":
            assert getattr(default, key) == value, key
    assert default.mlp_only_layers == ()
    for key in ("block_length", "mask_token_id", "denoising_steps",
                "remasking", "confidence_threshold", "router_logit_std",
                "prefill_bucket"):
        assert getattr(default, key) == CONFIG[key], key
    assert c.seq_len == 32768 and c.num_layers == 6
    assert c.experts_held == c.router_width == 128


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["traffic"] == "backlog-chat-blockdiff"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/sdar-30b-a3b-chat-pp8.json"
    assert "8.72 GB" in listed["why"]
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
    assert workload["engine"] == {"num_slots": 64, "chunk_size": 30,
                                  "max_len": 2560}
    assert workload["runner"] == "perf/runners/serve_sdar.py"


def test_the_traffic_is_chat_decoded_by_blocks():
    t = harness.load_traffic("backlog-chat-blockdiff")
    assert t["arrivals"] == {"kind": "backlog", "requests_per_second": 24.0}
    assert t["prime_tokens"] == {"kind": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    assert t["generated_tokens"] == {"kind": "lognormal", "median": 512,
                                     "sigma": 0.6, "min": 128, "max": 1536}
    assert t["stagger"] == {"first": 64, "min": 0.05, "max": 1.0}
    s = t["sampling"]
    assert (s["top_k"], s["temperature"], s["block_length"],
            s["denoising_steps"], s["remasking"]) == (
                25, 1.0, 4, 2, "low_confidence_static")
    assert t["schedule_seed"] == 41
    from perf.lib import traffic

    reqs = traffic.serve_requests(t, 3, 35.0, 151669)
    assert len(reqs) == 840
    assert max(len(r["prime"]) + r["max_new"] for r in reqs) <= 2560
    runner = harness.load_module("perf/runners/serve_sdar.py")
    workload = dict(harness.load_workload(CELL), traffic=t)
    c = runner.model_config_of(CONFIG, workload)
    assert (c.denoising_steps, c.remasking) == (2, "low_confidence_static")
    assert runner.reference_config(CONFIG, c)["denoising_steps"] == 2
    assert runner.reference_width(workload, c) == (1536, 256)


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 41's table
    assert sdar_cost.attention_params(c) == 18_874_368
    assert sdar_cost.router_params(c) == 262_144
    assert sdar_cost.expert_params(c) == 4_718_592
    assert sdar_cost.head_params(c) == 311_164_928
    layer = 18_874_368 + 262_144 + 128 * 4_718_592
    assert sdar_cost.total_params(c) == 6 * layer + 2 * 311_164_928
    assert sdar_cost.kv_bytes_per_row(c) == 2048
    assert 6 * sdar_cost.kv_bytes_per_row(c) == 12_288   # a token of a slot


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import sdar

    c = sdar.SDARConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: sdar.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    small = 6 * (2 * 2048 + 2 * 128) + 2048      # norm scales
    assert made - small == sdar_cost.total_params(CONFIG)
    assert made == 4_361_055_744           # the figure the files state
    assert "head" in shapes


def test_prefill_flops_by_hand():
    c = dict(CONFIG, block_length=4)
    assert sdar_cost.attention_pairs(8, 4) == 4 * 4 + 4 * 8
    # one prime of 602: its 600 tokens in whole blocks, 8 assignments a
    # token in each of 6 layers counted, 5 layers' needed
    held = 600 * 8 * 6
    one = sdar_cost.prefill_flops(c, [602], held)
    pair = 2 * 2 * 32 * 128
    want = (5 * (600 * 2 * (18_874_368 + 262_144) + pair * 600 * 604 / 2)
            + 600 * 2 * 2 * 2048 * 512
            + 2 * 4_718_592 * 600 * 8 * 5)
    assert one == want
    # the experts are two thirds of it, attention's pairs a fiftieth
    assert 0.6 < 2 * 4_718_592 * 600 * 8 * 5 / one < 0.7
    assert sdar_cost.prefill_flops(c, [3], 0) == 0


def test_forward_bytes_by_hand():
    c = dict(CONFIG, block_length=4)
    # a forward of 64 live rows of mean context 400 that touches every
    # expert, a third of the rows committing
    terms = sdar_cost.forward_terms(c, 1, 6 * 128, 64 * 400, 21)
    assert terms["experts_touched"] == 6 * 128 * 4_718_592 * 2
    assert terms["attention"] == 6 * 18_874_368 * 2
    assert terms["head"] == 311_164_928 * 2
    assert terms["committed_rows"] == 64 * 400 * 12_288
    assert terms["commit_writes"] == 21 * 4 * 12_288
    moved = sum(terms.values())
    assert moved == sdar_cost.forward_bytes(c, 1, 6 * 128, 64 * 400, 21)
    assert 0.83 < terms["experts_touched"] / moved < 0.87
    # ISSUE 41: the floor of a forward at the published bandwidth
    assert 9.9e-3 < moved / 819e9 < 11e-3


# ------------------------------------------- the comparison's measures


def test_direct_rows_cover_every_p_mod_4_at_the_largest_bucket():
    runner = harness.load_module("perf/runners/serve_sdar.py")
    workload = dict(harness.load_workload(CELL),
                    traffic=harness.load_traffic("backlog-chat-blockdiff"))
    check = workload["correct"]["direct"]
    c = runner.model_config_of(CONFIG, workload)
    assert check["blocks"] == 2 and check["positions"] == 64
    for seed in (0, 5, 2 ** 31 + 9):
        rows = runner.direct_rows(check, seed, c, 4)
        lengths = [n for n, _, _ in rows]
        assert sorted(n % 4 for n in lengths) == [0, 1, 2, 3]
        assert all(n % 128 and 256 <= n <= 1024 for n in lengths)
        assert 512 < lengths[0]            # the 1024 bucket
        for n, tokens, fills in rows:
            whole = n // 4 * 4
            assert len(tokens) == len(fills) == whole + 8
            assert (tokens > 0).all() and (tokens < 151669).all()
            assert (fills[:n] == -1).all() and (fills[n:] >= 0).all()
            for p0 in (whole, whole + 4):
                block = fills[max(p0, n):p0 + 4]
                assert (block == 0).sum() == min(2, len(block))
        again = runner.direct_rows(check, seed, c, 4)
        np.testing.assert_array_equal(rows[1][1], again[1][1])


def test_the_order_rule_reads_a_forwards_choice():
    runner = harness.load_module("perf/runners/serve_sdar.py")
    conf = np.asarray([0.10, 0.08, 0.04, 0.05])
    assert runner.order_reading(conf[:2], [True, True], 2) is None
    gap, wrong = runner.order_reading(conf, [True, True, False, False], 2)
    assert abs(gap - 0.03 / 0.08) < 1e-9 and not wrong
    assert runner.order_reading(conf, [True, False, False, True], 2)[1]
    readings = [(0.3, False), (0.01, True), (0.2, True)]
    assert runner.order_share(readings, 0.05) == {
        "checked": 2, "wrong": 1, "share": 0.5}
    assert runner.order_share(readings, 0.5)["share"] == 0.0


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 41: the program's reading nearest each limit
    over its seeds, the limit, the control nearest it (my chip runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    names = {"direct.tolerance": check["direct"]["tolerance"],
             "direct.routings_limit": check["direct"]["routings_limit"],
             "direct.keys_tolerance": check["direct"]["keys_tolerance"],
             "over_share_limit": check["over_share_limit"],
             "order_wrong_limit": check["order_wrong_limit"]}
    assert set(readings) - {"why"} == set(names) | {"direct.agreed_floor"}
    for name, limit in names.items():
        program, control = readings[name]
        assert program < limit < control, name
    program, control = readings["direct.agreed_floor"]
    assert control < check["direct"]["agreed_floor"] < program
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["probes"] == 2 and check["probe_new_tokens"] == 128
    for text in (check["why"], check["direct"]["why"]):
        assert "causal" in text and "bfloat16" in text


def test_the_control_tool_lowers_the_references_own_operations():
    """``perf/tools/sdar_lowp.py`` at a tiny size: each variant traces the
    reference through the wrapped operations, a lower precision reads
    further from the float32 reference, and a causal mask inside the block
    further still."""
    import jax

    from progen_tpu.models import sdar

    tool = harness.load_module("perf/tools/sdar_lowp.py")
    assert set(tool.VARIANTS) == {"as-stated", "islands-bf16",
                                  "one-notch-below", "causal-in-block"}
    c = sdar.SDARConfig.from_dict(TINY)
    params = sdar.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_sdar.forward_row(params, tokens, TINY)
    far = {}
    for name in ("as-stated", "islands-bf16", "one-notch-below"):
        narrower, islands = tool.VARIANTS[name]
        with tool.lowered(narrower and getattr(jax.numpy, narrower),
                          islands):
            got, _ = reference_sdar.forward_row(params, tokens, TINY)
        far[name] = float(np.abs(np.asarray(got, np.float32) - want).mean())
    assert 0 < far["as-stated"] <= far["islands-bf16"] < far[
        "one-notch-below"]
    with jax.default_matmul_precision("highest"):
        causal, _ = reference_sdar.forward_row(
            params, tokens, TINY, allowed=np.tril(np.ones((40, 40), bool)))
    assert float(np.abs(causal - want).mean()) > far["islands-bf16"]
    # nothing stays patched
    assert reference_sdar.product.__module__ == "perf.lib.reference_sdar"


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
    _dump(root / "perf/configs/tiny-sdar.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-chat-blockdiff"), name="tiny-blocks",
        arrivals={"kind": "backlog", "requests_per_second": 400.0},
        prime_tokens={"kind": "lognormal", "median": 8, "sigma": 1.0,
                      "min": 3, "max": 30},
        generated_tokens={"kind": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 6, "max": 22})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    traffic["sampling"] = dict(traffic["sampling"], top_k=5)
    _dump(root / "perf/traffic/tiny-blocks.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-sdar", config="tiny-sdar",
                    traffic="tiny-blocks",
                    engine={"num_slots": 32, "chunk_size": 6, "max_len": 56})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0, order_margin=0.05, order_wrong_limit=0.0,
        direct=dict(workload["correct"]["direct"], prime_tokens=[8, 30],
                    positions=8, tolerance=0.5, routings_limit=0.2,
                    agreed_floor=0.3, keys_tolerance=0.5))
    _dump(root / "perf/workloads/serve-tiny-sdar.json", workload)
    bench["configs"].append({"name": "tiny-sdar", "source": "perf/tests",
                             "file": "perf/configs/tiny-sdar.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-sdar", "config": "tiny-sdar",
        "traffic": "tiny-blocks", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-sdar")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_sdar_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-sdar", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-sdar", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k.removesuffix(".sdar"): v["value"]
             for k, v in traced["metrics"].items()}
    # 4 tokens a block of 3 forwards, less what last blocks drop
    assert 0.8 < value["diffusion.tokens_per_forward"] <= 4 / 3
    assert 30 < value["diffusion.commit_share_of_forwards"] < 45
    assert value["attn.full_rows_read_per_live_row"] > 1
    assert 0 < value["moe.experts_touched_share"] * 128 / 8 <= 1
    assert value["moe.held_load_max_over_mean"] >= 1
    assert not [p for p in os.listdir(root) if p not in
                ("perf", "BENCHMARK.json", ".jax_cache")]
    # the shares' reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "workload": {"traffic": {"sampling": {"block_length": 4}}},
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
           "workload": {"traffic": {"sampling": {"block_length": 4}}},
           "counters": {"admitted_primes": [300]}}
    for name in FROM_THE_FAMILY:
        spec = harness.load_metric(name)
        reader = harness.load_module(spec["reader"])
        assert reader.read(obs, spec) is None, name
