"""The Granite cell rehearsed on the CPU at tiny widths through the harness
(as test_trinity.py does for Trinity's), the configuration file against the
catalog row and the program's defaults, and the cost functions against hand
counts.  Nothing here measures anything."""

import importlib.util
import json
import os
import shutil

import pytest

from perf.lib import granite_cost, harness
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-granite-chat-backlog"
CONFIG = harness.load_config("granite-4.0-h-micro")
BENCH = harness.load_benchmark()
MAMBA, ATTENTION = "mamba", "attention"
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the families share, and this family's own (where an entry
# lies in BENCHMARK.json's list is a later PR's to change)
SHARES = {"decode.hbm_share.granite", "prefill.mfu.granite",
          "ssm.state_share_of_step_bytes.granite"}
OWN = SHARES | {"ssm.scan_slots_per_real_token.granite"}
FROM_THE_FAMILY = OWN | {"attn.full_rows_read_per_live_row"}
METRICS = SHARED | FROM_THE_FAMILY

TINY = dict(
    name="tiny-granite", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, shared_intermediate_size=96, num_hidden_layers=6,
    layer_types=[MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA, MAMBA],
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.1,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=8, rms_norm_eps=1e-5,
    max_position_embeddings=128, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


def test_every_published_key_is_in_the_file_and_nothing_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == []
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 40 == len(CONFIG["layer_types"])
    assert [i for i, k in enumerate(CONFIG["layer_types"])
            if k == ATTENTION] == [5, 15, 25, 35]
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    # what the config has no key for is listed, one line each
    for key in ("ssm_state_dtype", "A_log", "dt_bias", "D", "conv",
                "time_step_limit", "mlp", "seeded_scales", "prefill_bucket"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "float32" in CONFIG["assumed"]["ssm_state_dtype"]
    assert "3,191,396,096 parameters" in CONFIG["deployment"]
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.granite_hybrid import GraniteHybridConfig

    default = GraniteHybridConfig()
    c = GraniteHybridConfig.from_dict(CONFIG)
    assert c == default
    for key in ("vocab_size", "hidden_size", "shared_intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier",
                "logits_scaling", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_n_groups", "mamba_chunk_size", "rms_norm_eps",
                "max_position_embeddings", "tie_word_embeddings",
                "position_embedding_type", "num_local_experts",
                "prefill_bucket"):
        assert getattr(default, key) == CONFIG[key], key
    assert list(default.layer_types) == CONFIG["layer_types"]
    assert list(c.dt_range) == CONFIG["dt_range"] == [0.001, 0.1]
    assert list(c.a_range) == CONFIG["a_range"] == [1.0, 16.0]
    assert c.seq_len == 131072 and c.num_layers == 40


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "backlog-chat"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"] == []
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/granite-4.0-h-micro.json"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0
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
    traffic = harness.load_traffic(entry["traffic"])
    assert traffic["arrivals"] == {"kind": "backlog",
                                   "requests_per_second": 12.0}
    assert traffic["prime_tokens"] == {
        "kind": "lognormal", "median": 256, "sigma": 0.7, "min": 32,
        "max": 1024}
    assert traffic["generated_tokens"] == {
        "kind": "lognormal", "median": 384, "sigma": 0.7, "min": 64,
        "max": 1536}
    assert traffic["stagger"] == {"first": 32, "min": 0.05, "max": 1.0}
    assert traffic["sampling"]["top_k"] == 25
    assert traffic["sampling"]["temperature"] == 1.0
    assert traffic["schedule_seed"] == 38
    workload = harness.load_workload(CELL)
    assert traffic["prime_tokens"]["max"] + traffic["generated_tokens"][
        "max"] == workload["engine"]["max_len"] == 2560
    assert workload["engine"]["chunk_size"] == 32
    assert workload["engine"]["num_slots"] in (32, 24)
    assert workload["runner"] == "perf/runners/serve_granite.py"


def test_the_traffic_is_short_chat():
    """ISSUE 38's mix: primes of a few hundred tokens, answers of a few
    hundred, three requests in four under one 512 bucket."""
    import numpy as np

    from perf.lib import traffic as gen

    mix = harness.load_traffic("backlog-chat")
    reqs = gen.serve_requests(mix, 2 ** 31 + 7, 35, CONFIG["vocab_size"])
    assert len(reqs) == 420
    primes = np.array([len(r["prime"]) for r in reqs])
    new = np.array([r["max_new"] for r in reqs])
    assert 230 < np.median(primes) < 290 and primes.min() >= 32
    assert primes.max() <= 1024 and (primes <= 512).mean() > 0.8
    assert 64 <= new[32:].min() and new.max() <= 1536
    assert 340 < np.median(new[32:]) < 430
    assert max(max(r["prime"]) for r in reqs) < CONFIG["vocab_size"]
    assert min(min(r["prime"]) for r in reqs) >= 1


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 38: in_proj 2048 x 8512 + out_proj 4096 x 2048
    assert granite_cost.mamba_inner(c) == 4096
    assert granite_cost.conv_channels(c) == 4352
    assert granite_cost.mamba_params(c) == (
        2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048) == 25_821_184
    assert granite_cost.mlp_params(c) == 50_331_648
    assert granite_cost.attention_params(c) == 10_485_760
    assert granite_cost.layers_of(c, MAMBA) == 36
    assert granite_cost.layers_of(c, ATTENTION) == 4
    total = (36 * 25_821_184 + 4 * 10_485_760 + 40 * 50_331_648
             + 100_352 * 2048)
    assert granite_cost.total_params(c) == total == 3_190_292_480
    # a slot's state: 2.1 MB of carry and 26 kB of tail a state layer,
    # 2,048 B of keys and values a token and attention layer
    assert granite_cost.carry_bytes_per_row(c) == 64 * 64 * 128 * 4
    assert 36 * granite_cost.carry_bytes_per_row(c) == 75_497_472
    assert granite_cost.tail_bytes_per_row(c) == 3 * 4352 * 2
    assert 4 * granite_cost.kv_bytes_per_row(c) == 8192


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import granite_hybrid

    c = granite_hybrid.GraniteHybridConfig.from_dict(CONFIG)
    shapes = jax.eval_shape(
        lambda k: granite_hybrid.init_params(c, k), jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # two norms a layer and the final one; a state layer's convolution
    # (weights and bias), A_log, dt_bias, D and the gated norm's scale
    small = 40 * 2 * 2048 + 2048 + 36 * (4352 * 5 + 3 * 64 + 4096)
    assert made - small == granite_cost.total_params(CONFIG)
    assert made == 3_191_396_096           # the figure the files state
    assert "head" not in shapes


def test_prefill_flops_by_hand():
    c = CONFIG
    linear = 2 * (36 * 25_821_184 + 4 * 10_485_760 + 40 * 50_331_648)
    assert round(linear / 1e9, 2) == 5.97           # a real token
    assert granite_cost.chunk_pairs(5, 256) == 15
    assert granite_cost.chunk_pairs(256, 256) == 256 * 257 / 2
    assert granite_cost.chunk_pairs(257, 256) == 256 * 257 / 2 + 1
    assert granite_cost.chunk_pairs(600, 256) == 2 * 32896 + 88 * 89 / 2
    # a state layer over one row: C B^T and the in-chunk hand-over over the
    # causal pairs, the carry's two products a token
    pairs = granite_cost.chunk_pairs(600, 256)
    scan = 2 * 128 * pairs + 2 * 4096 * pairs + 2 * 2 * 4096 * 128 * 600
    assert granite_cost.scan_flops(c, 600) == scan
    one = granite_cost.prefill_flops(c, [600])
    pair = 2 * 2 * 32 * 64
    assert one == (600 * linear + 36 * scan + 4 * pair * 600 * 601 / 2
                   + 2 * 2048 * 100_352)
    # the recurrence is a fiftieth of the matrices' operations: the prefill
    # is the projections' and the MLPs'
    assert 0.015 < 36 * scan / (600 * linear) < 0.025
    two = granite_cost.prefill_flops(c, [600, 257])
    assert two == one + granite_cost.prefill_flops(c, [257])


def test_decode_bytes_by_hand():
    c = CONFIG
    fixed = granite_cost.total_params(c) * 2
    assert granite_cost.decode_bytes(c, 1, 0, 0) == fixed == 6_380_584_960
    # ISSUE 38's step: 32 live rows of mean context 500
    terms = granite_cost.decode_terms(c, 1, 32 * 36, 32 * 500)
    assert terms["carry"] == 32 * 36 * 2 * 2_097_152 == 4_831_838_208
    assert terms["conv_tails"] == 32 * 36 * 2 * 26_112
    assert terms["grown_rows"] == 32 * 500 * 8192
    assert terms["head"] == 2048 * 100_352 * 2
    moved = sum(terms.values())
    assert 0.41 < terms["carry"] / moved < 0.44
    assert 0.34 < terms["mlps"] / moved < 0.37
    # the floor of a step at the published bandwidth: about 14 ms
    assert 13.5e-3 < moved / 819e9 < 14.5e-3
    got = granite_cost.decode_bytes(c, 10, 10 * 32 * 36, 160_000)
    assert got == 10 * fixed + 10 * (terms["carry"] + terms[
        "conv_tails"]) + 160_000 * 8192


# ------------------------------------------- the comparison's measures


def test_direct_rows_put_a_chunk_boundary_one_token_before_a_rows_end():
    import numpy as np

    runner = harness.load_module("perf/runners/serve_granite.py")
    check = harness.load_workload(CELL)["correct"]["direct"]
    steps = check["decode_steps"]
    assert steps >= 8 and check["positions"] == 64
    for seed in (0, 5, 2 ** 31 + 9):
        lengths, tokens, at = runner.direct_rows(check, seed, 100_352, 2,
                                                 256, 128)
        assert 512 < lengths[0] < 1024 and lengths[1] == 257
        assert all(n % 128 for n in lengths)
        assert tokens.shape == (2, 1024 + steps)
        assert at.shape == (2, 32 + steps)
        for i, n in enumerate(lengths):
            assert at[i, -steps:].tolist() == list(range(n, n + steps))
            assert (tokens[i, :n + steps] > 0).all()
            assert (tokens[i, n + steps:] == 0).all()
            assert at[i, 0] == 0 and at[i, -steps - 1] == n - 1
        again = runner.direct_rows(check, seed, 100_352, 2, 256, 128)
        np.testing.assert_array_equal(tokens, again[1])
    assert runner.reference_positions(
        harness.load_workload(CELL)["correct"], 2) == 128


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 38: the program's largest reading over its
    seeds, the limit, the control one notch below (my chip runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    assert set(readings) - {"why"} == {
        "direct.tolerance", "direct.rms_limit", "over_share_limit"}
    for name, limit in (("direct.tolerance", check["direct"]["tolerance"]),
                        ("direct.rms_limit", check["direct"]["rms_limit"]),
                        ("over_share_limit", check["over_share_limit"])):
        program, control = readings[name]     # the program's largest
        assert program < limit < control, name
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["over_share_limit"] == 0.08
    assert check["probes"] == 2 and check["probe_new_tokens"] == 128
    assert check["direct"]["prime_tokens"] == [32, 1024]
    for text in (check["why"], check["direct"]["why"]):
        assert "carry" in text and "bfloat16" in text


def test_the_control_tool_lowers_the_references_own_operations():
    """``perf/tools/granite_lowp.py`` at a tiny size: each variant traces
    the reference through the wrapped operations, and a lower precision
    reads further from the float32 reference."""
    import inspect

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf.lib import reference_granite
    from progen_tpu.models import granite_hybrid

    tool = harness.load_module("perf/tools/granite_lowp.py")
    source = inspect.getsource(reference_granite)
    assert f'"{tool.HEAD}"' in source and f'{tool.SCORES}"' in source
    c = granite_hybrid.GraniteHybridConfig.from_dict(TINY)
    params = granite_hybrid.init_params(c, jax.random.key(3),
                                        granite_hybrid.bf16_policy())
    tokens = jax.random.randint(jax.random.key(4), (40,), 1, 96)

    def forward():
        return jax.jit(lambda p, t: reference_granite.forward_row(
            p, t, TINY))(params, tokens)

    with jax.default_matmul_precision("highest"):
        want = forward()
        rms = {}
        for name, (narrower, islands, state) in tool.VARIANTS.items():
            with tool.lowered(narrower and getattr(jnp, narrower), islands,
                              getattr(jnp, state)):
                got = forward()
            assert got.shape == want.shape and got.dtype == want.dtype
            rms[name] = float(np.sqrt(np.mean(
                (np.asarray(got, np.float32) - np.asarray(want)) ** 2)))
        again = forward()            # the patches are gone
    np.testing.assert_array_equal(again, want)
    # at 40 tokens a bfloat16 carry is lost in the bfloat16 products' own
    # noise (it is another reading, not a larger one); the islands and the
    # float8 products are not
    assert 0 < rms["as-stated"] != rms["carry-bf16"]
    assert rms["carry-bf16"] < 1.5 * rms["as-stated"] < rms["islands-bf16"]
    assert rms["one-notch-below"] > 2 * rms["as-stated"], rms


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
    _dump(root / "perf/configs/tiny-granite.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-chat"), name="tiny-chat",
        arrivals={"kind": "backlog", "requests_per_second": 400.0},
        prime_tokens={"kind": "lognormal", "median": 8, "sigma": 1.0,
                      "min": 3, "max": 30},
        generated_tokens={"kind": "lognormal", "median": 10, "sigma": 0.5,
                          "min": 6, "max": 22})
    traffic["stagger"] = dict(traffic["stagger"], first=8)
    _dump(root / "perf/traffic/tiny-chat.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-granite", config="tiny-granite",
                    traffic="tiny-chat",
                    engine={"num_slots": 32, "chunk_size": 4, "max_len": 56})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=6, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"], prime_tokens=[3, 30],
                    positions=8, tolerance=0.5, rms_limit=0.2))
    _dump(root / "perf/workloads/serve-tiny-granite.json", workload)
    bench["configs"].append({"name": "tiny-granite", "source": "perf/tests",
                             "file": "perf/configs/tiny-granite.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-granite", "config": "tiny-granite",
        "traffic": "tiny-chat", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-granite")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_granite_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-granite", 2 ** 31 + 33, 1.5, False,
                           0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-granite", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k.removesuffix(".granite"): v["value"]
             for k, v in traced["metrics"].items()}
    # the XLA decode core reads every row of every slot: far more than the
    # live rows hold
    assert value["attn.full_rows_read_per_live_row"] > 1
    # whole chunks of 8 and whole buckets for primes of 3-30 tokens
    assert 1 < value["ssm.scan_slots_per_real_token"] < 16
    assert not [p for p in os.listdir(root) if p not in
                ("perf", "BENCHMARK.json", ".jax_cache")]
    # the shares' reader on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "counters": {"admitted_primes": [5, 20]}}
    for name in SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0
    spec = copy.load_metric("ssm.state_share_of_step_bytes.granite")
    assert copy.load_module(spec["reader"]).read(obs, spec) < 100


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
