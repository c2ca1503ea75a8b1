"""The LFM2 cell rehearsed on the CPU at tiny widths through the harness (as
test_sdar.py does for SDAR's), the configuration file against the catalog
row and the program's defaults, the cost functions against hand counts, and
the measures of the comparison.  Nothing here measures anything."""

import importlib.util
import json
import os
import shutil

import numpy as np
import pytest

from perf.lib import harness, lfm2_cost, reference_lfm2
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-lfm2-longgen-backlog"
CONFIG = harness.load_config("lfm2-8b-a1b-pp2")
BENCH = harness.load_benchmark()
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports, what the families share, and this family's own (where an entry
# lies in BENCHMARK.json's list is a later PR's to change)
SHARES = {"decode.hbm_share.lfm2", "prefill.mfu.lfm2"}
OWN = SHARES | {"moe.experts_touched_share"}
FROM_THE_FAMILY = OWN | {
    "moe.held_load_max_over_mean", "moe.held_assignments_per_token",
    "moe.expert_passes_per_touched", "attn.full_rows_read_per_live_row"}
METRICS = SHARED | FROM_THE_FAMILY

TINY = dict(
    name="tiny-lfm2", source="perf/tests", reduced=[], vocab_size=96,
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=4, num_dense_layers=1,
    layer_types=["conv", "conv", "full_attention", "conv"],
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    conv_bias=False, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    norm_eps=1e-5, rope_theta=1000000, max_position_embeddings=128,
    experts_held=8, first_expert=0, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "LFM2-8B-A1B")


def test_every_published_key_is_in_the_file_and_only_the_depth_is_reduced():
    row = _catalog_row()
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in row["config"].items():
        assert CONFIG["published"][key] == value, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 12
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:12]
    assert CONFIG["layer_types"] == ["conv", "conv", "full_attention",
                                     "conv"] * 3           # whole periods
    assert CONFIG["num_experts"] == CONFIG["experts_held"] == 32
    assert CONFIG["num_experts_per_tok"] == 4
    assert CONFIG["vocab_size"] == 65536
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    for key in ("tie_word_embeddings", "route_norm_eps", "rope_pairing",
                "in_proj_order", "head_dim", "seeded_scales",
                "prefill_bucket", "kv_cache_row"):
        assert CONFIG["assumed"][key] and "\n" not in CONFIG["assumed"][key]
    assert "two-stage pipeline" in CONFIG["deployment"]
    assert "3,928.7 M parameters" in CONFIG["deployment"]
    assert CONFIG["published_counts"]["kv_bytes_per_token"] == 12288
    assert CONFIG["cut_counts"]["kv_bytes_per_token"] == 6144
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_programs_defaults_are_the_published_widths():
    from progen_tpu.models.lfm2 import LFM2Config

    default = LFM2Config()
    c = LFM2Config.from_dict(CONFIG)
    assert c == LFM2Config(num_hidden_layers=12,
                           layer_types=default.layer_types[:12])
    for key, value in CONFIG["published"].items():
        if hasattr(default, key) and key != "layer_types":
            assert getattr(default, key) == value, key
    assert list(default.layer_types) == CONFIG["published"]["layer_types"]
    for key in ("embed_rms", "logit_std", "router_logit_std",
                "router_bias_std", "prefill_bucket", "tie_word_embeddings"):
        assert getattr(default, key) == CONFIG[key], key
    assert c.seq_len == 128000 and c.num_layers == 12 and c.head_dim == 64
    assert c.experts_held == c.router_width == 32 and c.first_expert == 0


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["traffic"] == "backlog-longgen"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    assert listed["file"] == "perf/configs/lfm2-8b-a1b-pp2.json"
    assert "7.86 GB" in listed["why"]
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
    assert workload["engine"] == {"num_slots": 128, "chunk_size": 32,
                                  "max_len": 3072}
    assert workload["window"] == harness.load_workload(
        "serve-dsv2-decode-backlog")["window"]
    assert workload["runner"] == "perf/runners/serve_lfm2.py"
    # the touched share's divisor is the configuration's, not a file's
    touched = harness.load_metric("moe.experts_touched_share")
    assert touched["args"] == {} and CONFIG["experts_held"] == 32


def test_the_traffic_is_the_siblings_file():
    """``backlog-longgen`` as DeepSeek-V2's cell has it: the longest
    request fits the engine's ``max_len``."""
    t = harness.load_traffic("backlog-longgen")
    assert t["prime_tokens"]["max"] + t["generated_tokens"]["max"] == 3072
    assert t["schedule_seed"] == 32
    from perf.lib import traffic

    reqs = traffic.serve_requests(t, 3, 35.0, 65536)
    assert max(len(r["prime"]) + r["max_new"] for r in reqs) <= 3072
    assert min(len(r["prime"]) for r in reqs) >= 128


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    c = CONFIG
    # ISSUE 46's arithmetic
    assert lfm2_cost.conv_params(c) == 4 * 2048 * 2048 == 16_777_216
    assert lfm2_cost.attention_params(c) == 2 * 2048 ** 2 + 2 * 2048 * 512
    assert lfm2_cost.dense_ffn_params(c) == 44_040_192
    assert lfm2_cost.expert_params(c) == 11_010_048
    assert lfm2_cost.router_params(c) == 65_536
    assert lfm2_cost.expert_layers(c) == 10
    assert (lfm2_cost.layers_of(c, "conv"),
            lfm2_cost.layers_of(c, "full_attention")) == (9, 3)
    assert lfm2_cost.total_params(c) == (
        9 * 16_777_216 + 3 * 10_485_760 + 2 * 44_040_192
        + 10 * (65_536 + 32 * 11_010_048) + 65536 * 2048)
    assert lfm2_cost.kv_bytes_per_row(c) == 2048
    assert 3 * lfm2_cost.kv_bytes_per_row(c) == 6144     # a token of a slot
    assert lfm2_cost.tail_bytes_per_row(c) == 4096
    # the uncut model: 12,288 B a token, 8.34 B parameters
    whole = dict(c, **{k: CONFIG["published"][k] for k in CONFIG["reduced"]})
    assert 6 * lfm2_cost.kv_bytes_per_row(whole) == 12288
    assert 8.33e9 < lfm2_cost.total_params(whole) < 8.35e9


def test_the_program_makes_as_many_parameters_as_the_cost_file_counts():
    import jax

    from progen_tpu.models import lfm2

    c = lfm2.LFM2Config.from_dict(CONFIG)
    shapes = jax.eval_shape(lambda k: lfm2.init_params(c, k),
                            jax.random.key(0))
    made = sum(x.size for x in jax.tree.leaves(shapes))
    # norm scales, q/k norms, taps, the routers' biases
    small = 12 * 2 * 2048 + 2048 + 3 * 2 * 64 + 9 * 3 * 2048 + 10 * 32
    assert made - small == lfm2_cost.total_params(CONFIG)
    assert made == 3_928_728_256           # the figure the files state
    assert "head" not in shapes


def test_prefill_flops_by_hand():
    c = CONFIG
    held = 600 * 4 * 10
    one = lfm2_cost.prefill_flops(c, [600], held)
    outside = (9 * 16_777_216 + 3 * 10_485_760 + 2 * 44_040_192
               + 10 * 65_536)
    pair = 2 * 2 * 32 * 64
    want = (600 * 2 * outside + pair * 3 * 600 * 601 / 2
            + 2 * 11_010_048 * held + 2 * 2048 * 65536)
    assert one == want
    # the experts are three fifths of it, attention's pairs a fortieth
    assert 0.55 < 2 * 11_010_048 * held / one < 0.65
    assert pair * 3 * 600 * 601 / 2 / one < 0.03


def test_decode_bytes_by_hand():
    c = CONFIG
    # a step of 128 live rows of mean context 1,500 that touches every
    # expert
    terms = lfm2_cost.decode_terms(c, 1, 320, 128 * 1500, 128 * 9)
    assert terms["experts_touched"] == 320 * 11_010_048 * 2
    assert terms["short_convolutions"] == 9 * 16_777_216 * 2
    assert terms["head"] == 65536 * 2048 * 2
    assert terms["grown_rows"] == 128 * 1500 * 6144
    assert terms["tails"] == 128 * 9 * 3 * 4096
    moved = sum(terms.values())
    assert moved == lfm2_cost.decode_bytes(c, 1, 320, 128 * 1500, 128 * 9)
    assert 0.75 < terms["experts_touched"] / moved < 0.8
    assert terms["tails"] / moved < 0.002
    # ISSUE 46: the floor of a step at the published bandwidth
    assert 10.5e-3 < moved / 819e9 < 11.5e-3


# ------------------------------------------- the comparison's measures


def test_direct_primes_are_the_admission_shape_then_one_a_slot():
    runner = harness.load_module("perf/runners/serve_lfm2.py")
    check = harness.load_workload(CELL)["correct"]["direct"]
    assert check["prime_tokens"] == [513, 1023]
    assert check["readmit_prime_tokens"] == [1, 512]
    for seed in (0, 5, 2 ** 31 + 9):
        first, second = runner.direct_primes(check, seed, 65536, 8, 128)
        assert len(first) == 8 and len(second) == 128
        assert all(513 <= len(p) <= 1023 for p in first + second[8:])
        # every readmitted row is shorter than the row its slot held, two
        # of them shorter than the taps
        assert [len(p) for p in second[:2]] == [1, 2]
        assert all(len(p) <= 512 < len(q) for p, q in zip(second, first))
        n = len(second[8])                  # a prime number of tokens
        assert all(n % d for d in range(2, int(n ** 0.5) + 1))
        assert all((p > 0).all() and p.dtype == np.int32
                   for p in first + second)
        again = runner.direct_primes(check, seed, 65536, 8, 128)
        for p, q in zip(second, again[1]):
            np.testing.assert_array_equal(p, q)
    at = runner.compared_slots(check, 8, 128)
    assert len(at) == len(set(at.tolist())) == check["compared_slots"] == 24
    assert at[:9].tolist() == list(range(9)) and at[-1] == 127
    groups = runner.direct_groups(8, 24)
    assert sorted(sum(groups.values(), [])) == list(range(48))
    assert groups["readmitted"] == list(range(8))
    # the longest row fits the engine and the reference's one program
    workload = harness.load_workload(CELL)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    assert runner.direct_width(workload) == 1023 + 1 + 2 * 32 <= 1024 + 128


def test_every_compared_row_is_held_by_itself():
    """One row far from the reference among many near it fails; so does a
    share of differing routings over the limit, and nothing else."""
    runner = harness.load_module("perf/runners/serve_lfm2.py")
    check = {"row_rms_limit": 0.5, "rms_limit": 0.3, "routings_limit": 0.3}
    rng = np.random.default_rng(0)
    want = rng.normal(size=(48, 512))
    sets = np.tile(np.arange(4), (48, 10, 1))
    groups = runner.direct_groups(8, 24)
    near = want + 0.1 * rng.normal(size=want.shape)
    good = runner.direct_reading(near, want, sets[..., ::-1], sets, groups,
                                 check)
    assert good["ok"] and good["routings_differ_share"] == 0
    assert good["routings"] == 480 and good["rows"] == 48
    assert all(0.08 < v < 0.12 for v in good["row_rms_max"].values())
    far = near.copy()
    far[0] = rng.normal(size=512)           # the slot with the 1-token prime
    bad = runner.direct_reading(far, want, sets, sets, groups, check)
    assert not bad["ok"] and bad["row_rms_max"]["readmitted"] > 1.2
    assert bad["row_rms_max"]["admitted"] < 0.12 and bad["rms"] < 0.3
    # all rows a little further than the precision allows: none by itself
    loose = runner.direct_reading(want + 0.4 * rng.normal(size=want.shape),
                                  want, sets, sets, groups, check)
    assert not loose["ok"] and max(loose["row_rms_max"].values()) < 0.5
    other = sets.copy()
    other[:, :4, 0] = 9                     # four layers in ten
    routed = runner.direct_reading(near, want, other, sets, groups, check)
    assert not routed["ok"] and routed["routings_differ_share"] == 0.4


def test_the_cells_limits_lie_between_their_two_readings():
    """PERF.md section 6, PR 46: the program's reading nearest each limit
    over its seeds, the limit, and the nearest reading OF THE SAME QUANTITY
    that the limit has to refuse: a planted fault's least faulty row for
    the limit every row is held to by itself, the control one notch below
    the stated precision for the others (my chip runs)."""
    check = harness.load_workload(CELL)["correct"]
    readings = check["readings"]
    names = {"direct.row_rms_limit": check["direct"]["row_rms_limit"],
             "direct.rms_limit": check["direct"]["rms_limit"],
             "direct.routings_limit": check["direct"]["routings_limit"],
             "over_share_limit": check["over_share_limit"]}
    assert set(readings) - {"why"} == set(names)
    for name, limit in names.items():
        program, control = readings[name]
        assert program < limit < control, name
    assert set(check["direct"]) == {
        "prime_tokens", "readmit_prime_tokens", "chunks", "compared_slots",
        "row_rms_limit", "rms_limit", "routings_limit", "why"}
    assert check["tolerance"] == 0.1                   # the sibling cells'
    assert check["probes"] == 2 and check["probe_new_tokens"] == 128
    for text in (check["why"], check["direct"]["why"]):
        assert "float8" in text and "bfloat16" in text


def test_the_planted_faults_are_faults_of_the_tail():
    """``perf/tools/lfm2_faults.py`` at a tiny size: each fault changes
    what an admission leaves in a slot's tail and nothing else."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.decode.paging import SlotCaches
    from progen_tpu.ops import ssd

    tool = harness.load_module("perf/tools/lfm2_faults.py")
    z = jnp.arange(2 * 6 * 3, dtype=jnp.float32).reshape(2, 6, 3)
    lengths = jnp.asarray([1, 4])
    with tool.FAULTS["tail_at_padded_length"]():
        np.testing.assert_array_equal(ssd.conv_tail(z, lengths, 3), z[:, 4:])
    np.testing.assert_array_equal(ssd.conv_tail(z, lengths, 3)[1], z[1, 2:4])

    old = {"l0": {"conv": jnp.full((4, 2, 3), 7.0)},
           "l1": {"k": jnp.full((4, 5), 7.0)}}
    handle = {"pos": lengths,
              "caches": {"l0": {"conv": jnp.ones((2, 2, 3))},
                         "l1": {"k": jnp.ones((2, 5))}}}
    src, mask = jnp.asarray([0, 0, 1, 0]), jnp.asarray([1, 0, 1, 0], bool)

    def take(h, o):
        m = mask.reshape((-1,) + (1,) * (o.ndim - 1))
        return jnp.where(m, jnp.take(h, src, axis=0), o)

    def merged(fault=None):
        layout = SlotCaches(None)
        if fault is None:
            return layout.merge(take, old, handle, {}, ())
        with tool.FAULTS[fault]():
            return layout.merge(take, old, handle, {}, ())

    right = merged()
    assert (right["l0"]["conv"][jnp.asarray([0, 2])] == 1).all()
    kept = merged("tail_kept")
    assert (kept["l0"]["conv"] == 7).all()
    short = merged("tail_kept_where_the_prime_is_short")["l0"]["conv"]
    # slot 0 took a prime of one token: its older tail row is the last
    # request's; slot 2 took four tokens and is whole
    assert (short[0, 0] == 7).all() and (short[0, 1] == 1).all()
    assert (short[2] == 1).all()
    for out in (kept, merged("tail_kept_where_the_prime_is_short")):
        np.testing.assert_array_equal(out["l1"]["k"], right["l1"]["k"])
    assert SlotCaches.merge.__module__ == "progen_tpu.decode.paging"
    del jax


def test_the_control_tool_lowers_the_references_own_operations():
    """``perf/tools/lfm2_lowp.py`` at a tiny size: each variant traces the
    reference through the wrapped operations, and a lower precision reads
    further from the float32 reference."""
    import jax

    from progen_tpu.models import lfm2

    tool = harness.load_module("perf/tools/lfm2_lowp.py")
    assert set(tool.VARIANTS) == {"as-stated", "islands-bf16",
                                  "one-notch-below"}
    c = lfm2.LFM2Config.from_dict(TINY)
    params = lfm2.init_params(c, jax.random.key(0))
    tokens = np.arange(1, 41, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_lfm2.forward_row(params, tokens, TINY)
    far = {}
    for name in ("as-stated", "islands-bf16", "one-notch-below"):
        narrower, islands = tool.VARIANTS[name]
        with tool.lowered(narrower and getattr(jax.numpy, narrower),
                          islands):
            got, _ = reference_lfm2.forward_row(params, tokens, TINY)
        far[name] = float(np.abs(np.asarray(got, np.float32) - want).mean())
    assert 0 < far["as-stated"] <= far["islands-bf16"] < far[
        "one-notch-below"]
    # nothing stays patched
    assert reference_lfm2.product.__module__ == "perf.lib.reference_lfm2"
    assert reference_lfm2.island.__module__ == "perf.lib.reference_lfm2"


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
    _dump(root / "perf/configs/tiny-lfm2.json", TINY)
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
    workload.update(name="serve-tiny-lfm2", config="tiny-lfm2",
                    traffic="tiny-longgen",
                    engine={"num_slots": 32, "chunk_size": 6, "max_len": 56})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=8, tolerance=0.5,
        over_share_limit=0.0,
        direct=dict(workload["correct"]["direct"], prime_tokens=[17, 30],
                    readmit_prime_tokens=[1, 16], compared_slots=8,
                    row_rms_limit=0.6, rms_limit=0.3, routings_limit=0.2))
    _dump(root / "perf/workloads/serve-tiny-lfm2.json", workload)
    bench["configs"].append({"name": "tiny-lfm2", "source": "perf/tests",
                             "file": "perf/configs/tiny-lfm2.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-lfm2", "config": "tiny-lfm2",
        "traffic": "tiny-longgen", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the shares of a peak are left out: the table of peaks has no row
        # for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in SHARES:
            m["workloads"].append("serve-tiny-lfm2")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_lfm2_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-lfm2", 2 ** 31 + 33, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-lfm2", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - SHARES - NOT_ON_A_CPU
    value = {k: v["value"] for k, v in traced["metrics"].items()}
    assert value["moe.held_assignments_per_token"] == 2    # top-2, all held
    assert value["attn.full_rows_read_per_live_row"] > 1
    assert 0 < value["moe.experts_touched_share"] <= 1     # of the 8 held
    assert value["moe.held_load_max_over_mean"] >= 1
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
