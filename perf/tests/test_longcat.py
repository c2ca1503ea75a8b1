"""The LongCat-Flash cell rehearsed on the CPU at tiny widths through the
harness (as test_rehearsal.py does for ProGen's), the configuration file
against its ``published`` block, and the cost functions against hand
counts.  Nothing here measures anything."""

import importlib.util
import json
import os
import shutil

import pytest

from perf.lib import harness, longcat_cost
from perf.tests.backlog import NOT_ON_A_CPU, SHARED

CELL = "serve-longcat-backlog"
CONFIG = harness.load_config("longcat-flash-chat-ep32")
BENCH = harness.load_benchmark()
WIDTHS = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
          "num_attention_heads", "kv_lora_rank", "q_lora_rank",
          "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "moe_topk",
          "n_routed_experts", "zero_expert_num", "routed_scaling_factor",
          "rope_theta", "rms_norm_eps")

PEAK_SHARES = ("decode.hbm_share.longcat", "prefill.mfu.longcat")
# the cell's per-layer metrics as a SET of names: what every backlog cell
# reports and what this family adds
OWN = {*PEAK_SHARES, "moe.real_experts_per_token"}
METRICS = SHARED | OWN | {
    "moe.held_load_max_over_mean", "moe.expert_passes_per_touched",
    "mla.rows_read_per_live_row"}

TINY = dict(
    name="tiny-longcat", source="perf/tests", reduced=[], vocab_size=64,
    hidden_size=32, ffn_hidden_size=64, expert_ffn_hidden_size=16,
    num_layers=2, num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
    qk_rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=12,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=8, zero_expert_num=4, moe_topk=3, rms_norm_eps=1e-5,
    rope_theta=1e7, max_position_embeddings=64, experts_held=2,
    first_expert=0, router_bias_std=0.01, prefill_bucket=8)


# ------------------------------------------------------- the files agree


def test_every_published_key_is_unchanged_unless_reduced():
    published, reduced = CONFIG["published"], set(CONFIG["reduced"])
    assert reduced == {"num_layers", "experts_held", "vocab_size"}
    for key, value in published.items():
        if key in reduced:
            assert CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    for key in WIDTHS:                      # no width is ever reduced
        assert key not in reduced and CONFIG[key] == published[key]
    assert (CONFIG["num_layers"], CONFIG["vocab_size"],
            CONFIG["experts_held"]) == (4, 16384, 16)
    for key in ("assumed", "deployment", "precision", "reference"):
        assert CONFIG[key]
    assert os.path.exists(os.path.join(harness.ROOT, CONFIG["reference"]))


def test_the_catalog_row_is_what_was_copied():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "LongCat-Flash-Chat")
    assert CONFIG["published"] == row["config"]
    assert CONFIG["source"] == row["source_url"]


def test_the_program_reads_the_file_as_it_is():
    from progen_tpu.models.longcat import LongCatConfig

    c = LongCatConfig.from_dict(CONFIG)
    for key in WIDTHS + ("num_layers", "vocab_size", "experts_held"):
        assert getattr(c, key) == CONFIG[key], key
    assert c.router_width == 768 and c.latent_width == 576


def test_benchmark_entries_of_the_cell():
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "backlog-longprompt"
    listed = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == CONFIG["reduced"]
    assert listed["source"] == CONFIG["source"]
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")}
    assert e2e == {"setup_s", "serve_tok_s"}
    layer = harness.cell_metrics(BENCH, CELL, "per_layer")
    assert {m["name"] for m in layer} == METRICS
    assert all(m["moves"] in e2e for m in layer)
    assert {m["name"] for m in layer if m["workloads"] == [CELL]} == OWN
    traffic = harness.load_traffic(entry["traffic"])
    assert traffic["arrivals"] == {"kind": "backlog",
                                   "requests_per_second": 16.0}
    assert traffic["prime_tokens"]["max"] + traffic["generated_tokens"][
        "max"] <= harness.load_workload(CELL)["engine"]["max_len"]


# ---------------------------------------------------- costs, by hand


def test_parameter_counts_by_hand():
    # ISSUE 28: 2 x MLA 90.58 M + 2 x FFN 226.49 M + router 4.72 M
    assert longcat_cost.attention_params(CONFIG) == (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 8192 * 6144) == 90_570_752
    assert longcat_cost.dense_ffn_params(CONFIG) == 226_492_416
    assert longcat_cost.expert_params(CONFIG) == 37_748_736
    assert longcat_cost.router_params(CONFIG) == 4_718_592
    assert longcat_cost.layer_params_outside_experts(CONFIG) == 638_844_928
    # 4 layers of 638.8 M + 16 x 37.7 M, 2 x 16384 x 6144 of vocabulary
    assert longcat_cost.total_params(CONFIG) == 5_172_625_408
    assert longcat_cost.latent_bytes_per_token(CONFIG) == 9216


def test_prefill_flops_by_hand():
    c = CONFIG
    one = longcat_cost.prefill_flops(c, [1], 0)
    # one token: every matrix once, one query-key pair a head, the head
    assert one == (2 * 4 * 638_844_928 + 2 * 4 * 2 * 64 * 320
                   + 2 * 6144 * 16384)
    two = longcat_cost.prefill_flops(c, [2048, 1], 100.0)
    pairs = 2048 * 2049 / 2 + 1
    assert two == pytest.approx(
        2049 * 2 * 4 * 638_844_928 + 8 * 2 * 64 * 320 * pairs
        + 2 * 37_748_736 * 100 + 2 * 2 * 6144 * 16384)
    # ISSUE 28's arithmetic: about 5.4 GFLOP a token at 2048 tokens
    per_token = longcat_cost.prefill_flops(c, [2048], 0.25 * 4 * 2048) / 2048
    assert 5.2e9 < per_token < 5.6e9


def test_decode_bytes_by_hand():
    c = CONFIG
    weights = (4 * 638_844_928 + 6144 * 16384) * 2
    assert longcat_cost.decode_bytes(c, 1, 0, 0) == weights
    assert 5.2e9 < weights < 5.4e9            # ISSUE 28: 5.3 GB a step
    got = longcat_cost.decode_bytes(c, 10, 250, 10 * 32 * 2000)
    assert got == 10 * weights + 250 * 37_748_736 * 2 + 640_000 * 9216


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
    _dump(root / "perf/configs/tiny-longcat.json", TINY)
    traffic = dict(
        harness.load_traffic("backlog-longprompt"), name="tiny-longprompt",
        arrivals={"kind": "backlog", "requests_per_second": 400.0},
        prime_tokens={"kind": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 4, "max": 40},
        generated_tokens={"kind": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 3, "max": 16})
    traffic["stagger"] = dict(traffic["stagger"], first=4)
    _dump(root / "perf/traffic/tiny-longprompt.json", traffic)
    workload = harness.load_workload(CELL)
    workload.update(name="serve-tiny-longcat", config="tiny-longcat",
                    traffic="tiny-longprompt",
                    engine={"num_slots": 32, "chunk_size": 4, "max_len": 64})
    workload["correct"] = dict(
        workload["correct"], probes=1, probe_new_tokens=6, tolerance=0.5,
        direct=dict(workload["correct"]["direct"], prime_tokens=[33, 40],
                    positions=8, tolerance=0.5, routings_limit=1.0))
    _dump(root / "perf/workloads/serve-tiny-longcat.json", workload)
    bench["configs"].append({"name": "tiny-longcat", "source": "perf/tests",
                             "file": "perf/configs/tiny-longcat.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({
        "name": "serve-tiny-longcat", "config": "tiny-longcat",
        "traffic": "tiny-longprompt", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the two shares of a peak are left out: the table of peaks has no
        # row for a CPU, and that is an error there, not a default
        if CELL in m.get("workloads", ()) and m["name"] not in PEAK_SHARES:
            m["workloads"].append("serve-tiny-longcat")
    _dump(root / "BENCHMARK.json", bench)

    spec = importlib.util.spec_from_file_location(
        "perf_rehearsal_longcat_harness", root / "perf/lib/harness.py")
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
    result = copy.run_cell("serve-tiny-longcat", 2 ** 31 + 29, 1.5, False, 0.0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "serve_tok_s"}
    traced = copy.run_cell("serve-tiny-longcat", 7, 1.5, True, 0.0)
    assert traced["correct"] is True and traced["failed"] == 0
    # no TPU plane for a CPU: the idle share's reader finds nothing and the
    # metric is left out of the line; the rest report
    assert set(traced["metrics"]) == METRICS - set(PEAK_SHARES) - NOT_ON_A_CPU
    real = traced["metrics"]["moe.real_experts_per_token"]["value"]
    assert 0 < real <= TINY["moe_topk"]
    assert traced["metrics"]["moe.held_load_max_over_mean"]["value"] >= 1
    assert not [p for p in os.listdir(root) if p not in
                ("perf", "BENCHMARK.json", ".jax_cache")]
    # the shares' readers on what the run left in the registry, against a
    # v5e's peaks: the arithmetic runs; the numbers mean nothing here
    obs = {"config": TINY, "device_kind": "TPU v5 lite",
           "counters": {"admitted_primes": [16, 30, 9]}}
    for name in PEAK_SHARES:
        spec = copy.load_metric(name)
        assert copy.load_module(spec["reader"]).read(obs, spec) > 0
    empty = dict(obs, counters={})
    spec = copy.load_metric("prefill.mfu.longcat")
    assert copy.load_module(spec["reader"]).read(empty, spec) is None
