"""The entries that read the program's scope names (PR 67's seven, PR 68's
thirteen): each names the one reader, no cell counts a group twice, every
cell reads what no scope names, and PR 68's thirteen hold the groups and the
cells they were asked to.  Nothing compiles; the counts are the file's own
and not pinned."""

import pytest

from perf.lib import harness

READER = "perf/readers/scope_share.py"
WIRING = ["engine", "head", "embed", "norm"]
STEADY, TRAIN = ["serve-small-steady"], ["train-small-uniref"]
BASE = ["serve-base-backlog"]
# entry -> (groups, cells; None: the thirteen backlog cells, end-to-end metric)
PR68 = {
    "device.scope_share.sample.backlog": (["sample"], None, "serve_tok_s"),
    "device.scope_share.wiring.backlog": (WIRING, None, "serve_tok_s"),
    "device.scope_share.sgu.backlog": (["sgu"], BASE, "serve_tok_s"),
    "device.scope_share.attention.steady": (
        ["attn"], STEADY, "norm_latency_p50"),
    "device.scope_share.dense.steady": (["ffn"], STEADY, "norm_latency_p50"),
    "device.scope_share.sgu.steady": (["sgu"], STEADY, "norm_latency_p50"),
    "device.scope_share.sample.steady": (
        ["sample"], STEADY, "norm_latency_p50"),
    "device.scope_share.wiring.steady": (WIRING, STEADY, "norm_latency_p50"),
    "device.scope_share.attention.train": (["attn"], TRAIN, "train_tok_s"),
    "device.scope_share.dense.train": (["ffn"], TRAIN, "train_tok_s"),
    "device.scope_share.sgu.train": (["sgu"], TRAIN, "train_tok_s"),
    "device.scope_share.wiring.train": (
        ["head", "embed", "norm", "loss"], TRAIN, "train_tok_s"),
    "device.scope_share.optim.train": (["optim"], TRAIN, "train_tok_s"),
}


def scope_entries(bench) -> list[tuple[dict, dict]]:
    """``(entry, its metric file)`` of the entries the reader serves."""
    pairs = [(m, harness.load_metric(m["name"])) for m in bench["per_layer"]]
    return [(m, spec) for m, spec in pairs if spec.get("reader") == READER]


def test_the_scope_entries_name_the_reader_and_split_the_busy_time():
    bench = harness.load_benchmark()
    mine = scope_entries(bench)
    assert {m["name"] for m, _ in mine} == {
        m["name"] for m in bench["per_layer"] if m["name"].startswith(
            ("device.scope_share.", "device.unscoped_share."))}
    seen: dict[str, set] = {}
    for m, spec in mine:
        assert (m["source"], m["layer"], m["unit"], m["better"]) == (
            "device_trace", "device", "%", "lower")
        assert all(spec[k] == m[k] for k in ("name", "unit", "moves"))
        for cell in m["workloads"]:
            groups = seen.setdefault(cell, set())
            assert not groups & set(spec["args"]["groups"]), (
                cell, m["name"])            # no second count
            groups |= set(spec["args"]["groups"])
    # every cell reads what the program has not named
    assert all("-" in seen[w["name"]] for w in bench["workloads"])


@pytest.mark.parametrize("name", sorted(PR68))
def test_an_entry_of_pr68_holds_its_groups_and_cells(name):
    bench = harness.load_benchmark()
    groups, cells, moves = PR68[name]
    if cells is None:
        cells = [w["name"] for w in bench["workloads"]
                 if w["name"].endswith("-backlog")]
        assert len(cells) == 13
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert harness.load_metric(name)["args"] == {"groups": groups}
    reports = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert all(cell in reports[moves] for cell in cells)
