"""``models/experts.py:held_experts`` for a decode step's handful of tokens,
for a block step's few hundred and for an admission's thousands: four
lowerings, one contract.  The Pallas kernels (``moe_decode_fwd`` — every
token through every touched expert —, ``moe_grouped_fwd`` — an expert's
own rows in padded row tiles — and ``moe_sorted_fwd`` — row tiles of the
rows as sorted, a tile that straddles experts visited once an expert —,
``ops/moe_decode.py``) run under the interpreter here, over the tiny
configurations' own expert weights and routers: against the XLA form
(windows of ``ragged_dot``) and against a dense float32 loop over the held
experts, on the cases that break grouped kernels; the choice of lowering
from backend, mesh and shape at each cell's decode, block-step and
admission shapes, as ``status()`` shows it; and the counters
``moe.expert_passes``, ``moe.rows_computed`` and
``moe.prefill_rows_computed``, and the terms' way to their tokens,
``moe_sorted_fwd``'s own row DMAs, noted under ``"moe_combine"`` and
counted by ``moe.prefill_rows_combined``."""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models import deepseek_v2 as ds
from progen_tpu.models import experts
from progen_tpu.models import longcat as lc
from progen_tpu.models import sdar
from progen_tpu.models import trinity as tr
from progen_tpu.ops import moe_decode as md
from progen_tpu.ops.lowering import record_lowerings
from tests import deepseek_v2_tiny, longcat_tiny, sdar_tiny, trinity_tiny
from tests.families import jitted

F32 = jnp.float32
# (family module, tiny config, make, index of an expert layer)
FAMILIES = {
    "longcat": (lc, longcat_tiny.TINY, longcat_tiny.make, 0),
    "dsv2": (ds, deepseek_v2_tiny.TINY, deepseek_v2_tiny.make, 1),
    "trinity": (tr, trinity_tiny.TINY, trinity_tiny.make, 1),
}
# the bfloat16 bound of the siblings' kernel tests, and float32's
TOL = {"float32": 2e-5, "bfloat16": 2e-2}
# what the grouped kernel's tests add: SDAR holds the whole layer
LAYERS = {**FAMILIES, "sdar": (sdar, sdar_tiny.TINY, sdar_tiny.make, 0)}


def _kernel_path(monkeypatch, lane=16, step_bytes=None, row_tile=None,
                 most=None, sorted_tile=None):
    """The chip's choice with the interpreter behind it, at the tiny
    widths: a lane tile of ``lane``, with ``step_bytes`` a limit small
    enough that an expert takes several steps (of all three kernels), with
    ``row_tile`` row tiles small enough that an expert takes several
    items, with ``most = (MAX_TOKENS, MAX_GROUPED_TOKENS)`` other edges of
    the rule's ranges, and with ``sorted_tile`` the row tile of
    ``moe_sorted_fwd``."""
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    monkeypatch.setattr(md, "LANE", lane)
    if step_bytes:
        monkeypatch.setattr(md, "STEP_BYTES", step_bytes)
        monkeypatch.setattr(md, "SORTED_STEP_BYTES", step_bytes)
    if row_tile:
        monkeypatch.setattr(md, "ROW_TILE", row_tile)
    if most:
        monkeypatch.setattr(md, "MAX_TOKENS", most[0])
        monkeypatch.setattr(md, "MAX_GROUPED_TOKENS", most[1])
    if sorted_tile:
        monkeypatch.setattr(md, "SORTED_ROW_TILE", sorted_tile)
    for name in ("pallas_expert_terms", "pallas_grouped_terms",
                 "pallas_sorted_add"):
        monkeypatch.setattr(
            md, name, lambda *a, _f=getattr(md, name), **kw: _f(
                *a, **{**kw, "interpret": True}))


def _layer(family, mixed=False, held=None, first=0):
    """One expert layer of the tiny configuration, holding ``held`` experts
    from ``first`` on (default: all of them)."""
    module, config, make, index = LAYERS[family]
    params, policy = make(config, mixed)
    layer = params["layers"][index]
    if held is not None:
        config = dataclasses.replace(config, experts_held=held,
                                     first_expert=first)
        layer = dict(layer, experts={k: v[first:first + held] for k, v in
                                     layer["experts"].items()})
    return module, config, layer, policy.compute_dtype


def _routed(module, config, layer, u):
    out = jitted(module.route)(u, layer["router"], config)
    return out[0], out[1]                      # ids, weights


@functools.partial(jax.jit, static_argnames="c")
def _dense(u, ids, w, live, layer, c):
    """Every held expert over every token in float32, the assignments
    picked out after: nothing grouped, nothing skipped (one program a
    shape: eagerly the loop compiles its every op anew at each)."""
    e = {k: v.astype(F32) for k, v in layer["experts"].items()}
    uf = u.astype(F32)
    y = jnp.zeros(uf.shape, F32)
    for j in range(c.experts_held):
        out = (jax.nn.silu(uf @ e["wg"][j]) * (uf @ e["wu"][j])) @ e["wd"][j]
        wj = jnp.sum(jnp.where(ids == c.first_expert + j, w, 0.0), axis=-1)
        y = y + out * (wj * live)[:, None]
    return y


def _both(monkeypatch, u, ids, w, live, layer, c, kernel="pallas",
          capacity=None, **tiles):
    """``held_experts`` under the XLA form, then under the kernel (which
    alone gets ``capacity``, its window's rows)."""
    def held(capacity=None):
        # one program a call (a fresh trace per lowering): the interpreter
        # runs a kernel's grid op by op when it is not under ``jit``
        return jax.jit(lambda *a: experts.held_experts(*a, c, capacity))(
            u, ids, w, live, layer["experts"])

    with jax.default_matmul_precision("highest"):
        want, load = held()
        _kernel_path(monkeypatch, **tiles)
        with record_lowerings() as chosen:
            got, load2 = held(capacity)
    assert chosen["moe_experts"] == {kernel}
    assert got.shape == u.shape and got.dtype == F32
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load2))
    return np.asarray(got), np.asarray(want), np.asarray(load)


# what a step's tokens and their choices look like, by what it breaks:
# ``(tokens, live, held, first, ids -> ids)``
def _all_to_one(ids, c):
    return jnp.full_like(ids, c.first_expert + 1)


def _ends_and_middle_empty(ids, c):
    """No assignment to the first, the last or a middle held expert."""
    lo, n = c.first_expert, c.experts_held
    empty = jnp.array([lo, lo + n // 2, lo + n - 1])
    free = lo + 1
    return jnp.where((ids[..., None] == empty).any(-1), free, ids)


CASES = {
    "as-routed": (24, lambda t: jnp.arange(t) % 5 != 0, None, 0, None),
    "all-to-one-expert": (16, lambda t: jnp.ones(t, bool), None, 0,
                          _all_to_one),
    "no-live-row": (16, lambda t: jnp.zeros(t, bool), None, 0, None),
    "empty-experts-at-ends-and-middle": (
        24, lambda t: jnp.arange(t) % 4 != 1, None, 0,
        _ends_and_middle_empty),
    "rows-not-live": (32, lambda t: jnp.arange(t) < 9, None, 0, None),
    # a share in the middle of the router: ids below and above it
    "ids-outside-the-held-range": (24, lambda t: jnp.arange(t) % 7 != 0, 3,
                                   2, None),
    # 13 tokens: neither the row group of 16 nor t * k a multiple of a tile
    "tokens-off-the-row-group": (13, lambda t: jnp.arange(t) != 4, None, 0,
                                 None),
    "one-token": (1, lambda t: jnp.ones(t, bool), None, 0, None),
    "the-most-tokens": (128, lambda t: jnp.arange(t) % 3 != 0, None, 0,
                        None),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_kernel_equals_the_xla_form_and_the_dense_loop(monkeypatch, family,
                                                       case):
    t, live_of, held, first, rewrite = CASES[case]
    module, c, layer, _ = _layer(family, held=held, first=first)
    u = jax.random.normal(jax.random.key(3), (t, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    if rewrite is not None:
        ids = rewrite(ids, c)
    live = live_of(t)
    # inner tiles of 8 of the 16 columns: two steps an expert
    got, want, load = _both(monkeypatch, u, ids, w, live, layer, c,
                            step_bytes=3 * 32 * 8 * 4, lane=8)
    assert md.inner_tile(c.hidden_size, 16, 4) == 8
    dense = np.asarray(_dense(u, ids, w, live, layer, c))
    assert np.isfinite(got).all()
    if case == "no-live-row":
        assert load.sum() == 0 and not got.any() and not want.any()
        return
    if case == "all-to-one-expert":          # the old overflow
        assert load[1] == t * c.moe_topk and load.sum() == load[1]
    if case == "ids-outside-the-held-range":
        local = np.asarray(ids) - c.first_expert
        assert (local < 0).any() and (local >= c.experts_held).any()
    if case == "empty-experts-at-ends-and-middle":
        n = c.experts_held
        assert load[0] == load[n // 2] == load[n - 1] == 0 < load.sum()
    assert float(np.abs(dense).max()) > 0.05    # not a vacuous bound
    assert float(np.abs(got - want).max()) < TOL["float32"]
    assert float(np.abs(got - dense).max()) < 10 * TOL["float32"]
    assert not got[~np.asarray(live)].any()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_bfloat16_operands_float32_sums(monkeypatch, family):
    """The cells' precision: bfloat16 tokens and weights, float32
    accumulation; against today's form (which rounds gate and up to
    bfloat16 before ``silu``) within the siblings' bfloat16 bound, and no
    further from the float32 dense loop than today's form is."""
    module, c, layer, dtype = _layer(family, mixed=True)
    assert dtype == jnp.bfloat16
    u = jax.random.normal(jax.random.key(5), (32, c.hidden_size)).astype(
        dtype)
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(32) % 6 != 0
    got, want, _ = _both(monkeypatch, u, ids, w, live, layer, c)
    dense = np.asarray(_dense(u, ids, w, live, layer, c))
    scale = float(np.abs(dense).max())
    assert scale > 0.05
    assert float(np.abs(got - want).max()) < TOL["bfloat16"] * max(1, scale)
    assert (float(np.abs(got - dense).max())
            <= float(np.abs(want - dense).max()) + 1e-6)


def test_junk_in_rows_that_are_not_an_experts_changes_no_bit(monkeypatch):
    """Every token passes every touched expert; what is not the expert's
    is weighted zero and selected away, so not even a NaN in a row that is
    not ``live`` reaches a live row's sum."""
    module, c, layer, _ = _layer("dsv2")
    u = jax.random.normal(jax.random.key(7), (16, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(16) % 4 != 0
    got, _, _ = _both(monkeypatch, u, ids, w, live, layer, c)
    junk = jnp.where(live[:, None], u, jnp.nan)
    again, _ = experts.held_experts(junk, ids, w, live, layer["experts"], c)
    np.testing.assert_array_equal(got, np.asarray(again))


def test_only_the_listed_experts_are_read(monkeypatch):
    """An expert with no live assignment is not on the list: NaN in its
    matrices shows nowhere.  (That its tiles are not FETCHED either is the
    index maps' doing: past the list they name the block already held.)"""
    module, c, layer, _ = _layer("trinity")
    u = jax.random.normal(jax.random.key(9), (16, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    ids = _ends_and_middle_empty(ids, c)
    live = jnp.ones(16, bool)
    got, want, load = _both(monkeypatch, u, ids, w, live, layer, c)
    idle = jnp.asarray(load == 0)
    assert bool(idle.any())
    poisoned = dict(layer, experts={
        k: jnp.where(idle[:, None, None], jnp.nan, v)
        for k, v in layer["experts"].items()})
    again, _ = experts.held_experts(u, ids, w, live, poisoned["experts"], c)
    np.testing.assert_array_equal(got, np.asarray(again))


def test_expert_terms_by_hand_over_a_short_list():
    """``ops/moe_decode.py``'s own contract: the first ``n_real`` listed
    experts, in the list's order, whatever lies past them; an empty list
    gives zeros."""
    t, h, inner, held = 24, 256, 384, 6
    ks = jax.random.split(jax.random.key(0), 6)
    u = jax.random.normal(ks[0], (t, h))
    wg = jax.random.normal(ks[1], (held, h, inner)) * h ** -0.5
    wu = jax.random.normal(ks[2], (held, h, inner)) * h ** -0.5
    wd = jax.random.normal(ks[3], (held, inner, h)) * inner ** -0.5
    wt = (jax.random.uniform(ks[4], (held, t))
          * (jax.random.uniform(ks[5], (held, t)) < 0.3))
    eid = jnp.array([4, 1, 3, 0, 5, 2])
    with jax.default_matmul_precision("highest"):
        want = md.xla_expert_terms(u, eid, 3, wt, wg, wu, wd)
        by_hand = sum(
            wt[i][:, None] * ((jax.nn.silu(u @ wg[e]) * (u @ wu[e])) @ wd[e])
            for i, e in enumerate([4, 1, 3]))
        for tile in (128, 384, None):
            got = md.pallas_expert_terms(u, eid, 3, wt, wg, wu, wd,
                                         tile=tile, interpret=True)
            assert float(jnp.abs(got - want).max()) < TOL["float32"]
        none = md.pallas_expert_terms(u, eid, 0, wt, wg, wu, wd, tile=128,
                                      interpret=True)
    assert float(jnp.abs(want - by_hand).max()) < TOL["float32"]
    assert float(jnp.abs(want).max()) > 0.1 and not np.asarray(none).any()
    with pytest.raises(ValueError, match="does not divide"):
        md.pallas_expert_terms(u, eid, 3, wt, wg, wu, wd, tile=256,
                               interpret=True)


# ---- a block step's few hundred tokens: an expert's own rows in row tiles ---

ROW_TILE = 8            # the tests' row tile: an expert takes several items


def _one_expert_on_a_tiles_edge(ids, c):
    """The second held expert gets exactly two row tiles of rows, and no
    other assignment."""
    a, b = c.first_expert + 1, c.first_expert + 2
    ids = jnp.where(ids == a, b, ids)
    return ids.at[:2 * ROW_TILE, 0].set(a)


def _second_expert_idle(ids, c):
    return jnp.where(ids == c.first_expert + 1, c.first_expert, ids)


# ``(tokens, live, ids -> ids)``; each on a layer held whole (SDAR) and on
# a share in the middle of the router (dsv2: 3 experts from the third on)
GROUPED_CASES = {
    "129-tokens": (129, lambda t: jnp.arange(t) % 5 != 0, None),
    "256-tokens": (256, lambda t: jnp.arange(t) % 5 != 0, None),
    "1024-tokens": (1024, lambda t: jnp.arange(t) % 3 != 0, None),
    "an-expert-without-rows": (256, lambda t: jnp.arange(t) % 4 != 1,
                               _second_expert_idle),
    "rows-end-on-a-tiles-edge": (256, lambda t: jnp.ones(t, bool),
                                 _one_expert_on_a_tiles_edge),
    "all-to-one-expert": (160, lambda t: jnp.arange(t) % 7 != 0,
                          _all_to_one),
    "no-live-row": (256, lambda t: jnp.zeros(t, bool), None),
}
SHARES = {"sdar": (None, 0), "dsv2": (3, 2)}
GROUPED = [("sdar", case) for case in GROUPED_CASES] + [
    ("dsv2", "256-tokens"), ("dsv2", "an-expert-without-rows"),
    ("dsv2", "rows-end-on-a-tiles-edge")]


def _listed_weights(ids, w, live, c):
    """``(eid, n_real, wt (held, T))`` of ``ops/moe_decode.py``'s contract
    for the held experts: the touched first."""
    held = jnp.arange(c.experts_held) + c.first_expert
    names = (ids[None] == held[:, None, None]) & live[None, :, None]
    wt = jnp.sum(jnp.where(names, w[None], 0.0), axis=-1)
    touched = names.any(axis=(1, 2))
    eid = jnp.argsort(~touched)
    return eid, jnp.sum(touched), wt[eid]


@pytest.mark.parametrize("family,case", GROUPED,
                         ids=[f"{f}-{c}" for f, c in GROUPED])
def test_grouped_kernel_equals_the_xla_form_the_oracle_and_the_dense_loop(
        monkeypatch, family, case):
    t, live_of, rewrite = GROUPED_CASES[case]
    held, first = SHARES[family]
    module, c, layer, _ = _layer(family, held=held, first=first)
    u = jax.random.normal(jax.random.key(13), (t, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    if rewrite is not None:
        ids = rewrite(ids, c)
    live = live_of(t)
    # two inner steps an item, row tiles of 8: most experts take several
    inner = layer["experts"]["wg"].shape[-1]
    got, want, load = _both(
        monkeypatch, u, ids, w, live, layer, c, kernel="pallas_grouped",
        lane=8, step_bytes=3 * c.hidden_size * (inner // 2) * 4,
        row_tile=ROW_TILE, most=(128, 1024))
    assert md.inner_tile(c.hidden_size, inner, 4) == inner // 2
    e = layer["experts"]
    with jax.default_matmul_precision("highest"):
        oracle = np.asarray(md.xla_expert_terms(
            u, *_listed_weights(ids, w, live, c), e["wg"], e["wu"], e["wd"]))
    dense = np.asarray(_dense(u, ids, w, live, layer, c))
    assert np.isfinite(got).all()
    if case == "no-live-row":
        assert load.sum() == 0 and not got.any() and not want.any()
        return
    if family == "dsv2":             # assignments below and above the share
        local = np.asarray(ids) - c.first_expert
        assert (local < 0).any() and (local >= c.experts_held).any()
    if case == "an-expert-without-rows":
        assert load[1] == 0 < load[0]
    if case == "rows-end-on-a-tiles-edge":
        assert load[1] == 2 * ROW_TILE
    if case == "all-to-one-expert":
        assert load[1] == int(live.sum()) * c.moe_topk == load.sum()
    assert load.max() > ROW_TILE                # more rows than one tile
    assert float(np.abs(dense).max()) > 0.05
    assert float(np.abs(got - want).max()) < TOL["float32"]
    assert float(np.abs(got - oracle).max()) < TOL["float32"]
    assert float(np.abs(got - dense).max()) < 10 * TOL["float32"]
    assert not got[~np.asarray(live)].any()


@pytest.mark.parametrize("family", list(SHARES))
def test_grouped_bfloat16_operands_float32_sums(monkeypatch, family):
    """As the all-rows kernel's: within the siblings' bfloat16 bound of the
    XLA form, and no further from the float32 dense loop than it is."""
    held, first = SHARES[family]
    module, c, layer, dtype = _layer(family, mixed=True, held=held,
                                     first=first)
    assert dtype == jnp.bfloat16
    u = jax.random.normal(jax.random.key(15), (256, c.hidden_size)).astype(
        dtype)
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(256) % 6 != 0
    got, want, _ = _both(monkeypatch, u, ids, w, live, layer, c,
                         kernel="pallas_grouped", row_tile=16)
    dense = np.asarray(_dense(u, ids, w, live, layer, c))
    scale = float(np.abs(dense).max())
    assert scale > 0.05
    assert float(np.abs(got - want).max()) < TOL["bfloat16"] * max(1, scale)
    assert (float(np.abs(got - dense).max())
            <= float(np.abs(want - dense).max()) + 1e-6)


def test_grouped_junk_outside_an_experts_rows_changes_no_bit(monkeypatch):
    """Rows that are not ``live`` reach no expert (a NaN there shows
    nowhere), and what a row tile's padding holds is weighted zero and
    selected away: the kernel over NaN padding writes the bits it writes
    over zeros."""
    module, c, layer, _ = _layer("sdar")
    u = jax.random.normal(jax.random.key(17), (160, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(160) % 4 != 0
    _kernel_path(monkeypatch, row_tile=ROW_TILE)
    with record_lowerings() as chosen:
        got, again = (experts.held_experts(
            jnp.where(live[:, None], u, fill), ids, w, live,
            layer["experts"], c)[0] for fill in (0.0, jnp.nan))
    assert chosen["moe_experts"] == {"pallas_grouped"}
    assert float(jnp.abs(got).max()) > 0.05
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
    # the kernel's own contract: 3 items of 8 rows, 5 + 8 + 2 real
    e = layer["experts"]
    xs = jax.random.normal(jax.random.key(19), (24, c.hidden_size))
    wt = jnp.where(jnp.arange(24) % 8 < jnp.repeat(jnp.array([5, 8, 2]), 8),
                   0.5, 0.0)
    eid = jnp.array([1, 4, 4])
    clean, dirty = (md.pallas_grouped_terms(
        jnp.where(wt[:, None] != 0, xs, fill), eid, 3, wt, e["wg"], e["wu"],
        e["wd"], row_tile=8, interpret=True) for fill in (0.0, jnp.nan))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    assert not np.asarray(clean)[np.asarray(wt) == 0].any()
    assert np.abs(np.asarray(clean)[np.asarray(wt) != 0]).min() > 0


def test_grouped_terms_by_hand_over_a_short_list():
    """``pallas_grouped_terms``'s own contract: item ``i`` is rows ``[8 i,
    8 i + 8)`` through expert ``eid[i]``, weighted; the first ``n_real``
    items are computed, in one or in several inner steps, and what lies
    past them is neither read nor written."""
    h, inner, held, rt = 256, 384, 6, 8
    ks = jax.random.split(jax.random.key(1), 6)
    xs = jax.random.normal(ks[0], (5 * rt, h))
    wg = jax.random.normal(ks[1], (held, h, inner)) * h ** -0.5
    wu = jax.random.normal(ks[2], (held, h, inner)) * h ** -0.5
    wd = jax.random.normal(ks[3], (held, inner, h)) * inner ** -0.5
    wt = jax.random.uniform(ks[4], (5 * rt,)) * (
        jax.random.uniform(ks[5], (5 * rt,)) < 0.7)
    eid = jnp.array([0, 3, 3, 5, 2])            # expert 3 in two items
    with jax.default_matmul_precision("highest"):
        by_hand = jnp.concatenate([
            wt[i * rt:(i + 1) * rt, None] * (
                (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
            for i, e in enumerate([0, 3, 3, 5])
            for x in [xs[i * rt:(i + 1) * rt]]])
        for tile in (128, 384, None):
            got = md.pallas_grouped_terms(xs, eid, 4, wt, wg, wu, wd,
                                          row_tile=rt, tile=tile,
                                          interpret=True)
            assert got.shape == (5 * rt, h) and got.dtype == F32
            assert float(jnp.abs(got[:4 * rt] - by_hand).max()) < TOL[
                "float32"]
        # a NaN expert past the list is not read
        poisoned = md.pallas_grouped_terms(
            xs, eid, 4, wt, wg.at[2].set(jnp.nan), wu, wd, row_tile=rt,
            interpret=True)
    np.testing.assert_array_equal(np.asarray(poisoned[:4 * rt]),
                                  np.asarray(got[:4 * rt]))
    assert float(jnp.abs(by_hand).max()) > 0.1
    with pytest.raises(ValueError, match="does not divide"):
        md.pallas_grouped_terms(xs, eid, 4, wt, wg, wu, wd, row_tile=rt,
                                tile=256, interpret=True)
    with pytest.raises(ValueError, match="tiles of"):
        md.pallas_grouped_terms(xs[:-1], eid, 4, wt[:-1], wg, wu, wd,
                                row_tile=rt, interpret=True)


# ---- an admission's thousands: row tiles of the rows as sorted --------------

def _few_rows_an_expert(t):
    """One token in four live: about five rows an expert, so a row tile of
    8 holds two experts' rows and a tile of 16 three."""
    return jnp.arange(t) % 4 == 0


# ``(tokens, live, ids -> ids, rows of a window (None: the rule's), row
# tile)``; the rule's edges are moved so that these few tokens are an
# admission (``most=(16, 64)``)
SORTED_CASES = {
    "as-routed": (160, lambda t: jnp.arange(t) % 5 != 0, None, None, 8),
    "tiles-straddle-two-experts": (96, _few_rows_an_expert, None, None, 8),
    "tiles-straddle-three-experts": (96, _few_rows_an_expert, None, None,
                                     16),
    "an-expert-without-rows": (160, lambda t: jnp.arange(t) % 4 != 1,
                               _second_expert_idle, None, 8),
    "rows-end-on-a-tiles-edge": (160, lambda t: jnp.ones(t, bool),
                                 _one_expert_on_a_tiles_edge, None, 8),
    "all-to-one-expert": (160, lambda t: jnp.arange(t) % 7 != 0,
                          _all_to_one, None, 8),
    "no-live-row": (160, lambda t: jnp.zeros(t, bool), None, None, 8),
    # three tiles a window, then one: the loop runs again and again
    "a-window-overflows-and-runs-again": (
        160, lambda t: jnp.arange(t) % 5 != 0, None, 24, 8),
    "a-window-of-one-tile": (96, lambda t: jnp.arange(t) % 3 != 0, None, 5,
                             8),
}
SORTED = [("sdar", case) for case in SORTED_CASES] + [
    ("dsv2", "as-routed"), ("dsv2", "tiles-straddle-three-experts"),
    ("dsv2", "an-expert-without-rows"),
    ("dsv2", "a-window-overflows-and-runs-again")]


def _sorted_terms(xs, wt, lo, hi, wg, wu, wd, *, row_tile, tile=None):
    """``moe_sorted_fwd``'s terms in sorted order: every row its own token
    of a ``y`` of zeros, so row ``r`` of the result is what the kernel adds
    for row ``r`` (zero where it adds nothing)."""
    n, h = xs.shape
    return md.pallas_sorted_add(
        jnp.zeros((n, h // md.LANE, md.LANE), F32), xs, jnp.arange(n), wt,
        lo, hi, wg, wu, wd, row_tile=row_tile, tile=tile,
        interpret=True).reshape(n, h)


def _once_a_token(ids, c):
    """A token names an expert once, as a router's top-k does (what
    ``moe_sorted_fwd`` is given: an item's rows are one expert's, and it
    fetches them together): where a rewritten row names one twice the
    later ones go to an expert no chip holds."""
    k = ids.shape[-1]
    again = (ids[:, :, None] == ids[:, None, :]) & (
        jnp.arange(k)[:, None] > jnp.arange(k)[None, :])
    return jnp.where(again.any(-1), c.router_width, ids)


def _experts_in_the_fullest_tile(load, rt):
    """How many experts have a row in one row tile of the sorted rows, at
    most."""
    ends = np.cumsum(load)
    owner = np.repeat(np.arange(len(load)), load)       # a row's expert
    return max(len(set(owner[i:i + rt])) for i in range(0, ends[-1], rt))


@pytest.mark.parametrize("family,case", SORTED,
                         ids=[f"{f}-{c}" for f, c in SORTED])
def test_sorted_kernel_equals_the_xla_form_the_oracle_and_the_dense_loop(
        monkeypatch, family, case):
    t, live_of, rewrite, capacity, rt = SORTED_CASES[case]
    held, first = SHARES[family]
    module, c, layer, _ = _layer(family, held=held, first=first)
    u = jax.random.normal(jax.random.key(23), (t, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    if rewrite is not None:
        ids = _once_a_token(rewrite(ids, c), c)
    live = live_of(t)
    # two inner steps an item
    inner = layer["experts"]["wg"].shape[-1]
    got, want, load = _both(
        monkeypatch, u, ids, w, live, layer, c, kernel="pallas_sorted",
        capacity=capacity, lane=8, most=(16, 64), sorted_tile=rt,
        step_bytes=3 * c.hidden_size * (inner // 2) * 4)
    assert md.fitted_tile(u, layer["experts"]) == md.Tiles(inner // 2, rt,
                                                           True)
    e = layer["experts"]
    with jax.default_matmul_precision("highest"):
        oracle = np.asarray(md.xla_expert_terms(
            u, *_listed_weights(ids, w, live, c), e["wg"], e["wu"], e["wd"]))
    dense = np.asarray(_dense(u, ids, w, live, layer, c))
    assert np.isfinite(got).all()
    if case == "no-live-row":
        assert load.sum() == 0 and not got.any() and not want.any()
        return
    if family == "dsv2":             # assignments below and above the share
        local = np.asarray(ids) - c.first_expert
        assert (local < 0).any() and (local >= c.experts_held).any()
    if case.startswith("tiles-straddle"):
        most = {"tiles-straddle-two-experts": 2,
                "tiles-straddle-three-experts": 3}[case]
        assert _experts_in_the_fullest_tile(load, rt) >= most
    if case == "an-expert-without-rows":
        assert load[1] == 0 < load[0]
    if case == "rows-end-on-a-tiles-edge":
        assert load[1] == 2 * ROW_TILE
    if case == "all-to-one-expert":
        assert load[1] == int(live.sum()) == load.sum()
    if capacity:                                 # more than two windows
        cap = experts.sorted_window(c, t, c.moe_topk, rt, capacity)
        assert cap == -(-capacity // rt) * rt and load.sum() > 2 * cap
    assert float(np.abs(dense).max()) > 0.05
    assert float(np.abs(got - want).max()) < TOL["float32"]
    assert float(np.abs(got - oracle).max()) < TOL["float32"]
    assert float(np.abs(got - dense).max()) < 10 * TOL["float32"]
    assert not got[~np.asarray(live)].any()


@pytest.mark.parametrize("family", list(SHARES))
def test_sorted_bfloat16_operands_float32_sums(monkeypatch, family):
    """As the other kernels': within the siblings' bfloat16 bound of the
    XLA form, and no further from the float32 dense loop than it is."""
    held, first = SHARES[family]
    module, c, layer, dtype = _layer(family, mixed=True, held=held,
                                     first=first)
    assert dtype == jnp.bfloat16
    u = jax.random.normal(jax.random.key(25), (256, c.hidden_size)).astype(
        dtype)
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(256) % 6 != 0
    got, want, _ = _both(monkeypatch, u, ids, w, live, layer, c,
                         kernel="pallas_sorted", most=(16, 64),
                         sorted_tile=16)
    dense = np.asarray(_dense(u, ids, w, live, layer, c))
    scale = float(np.abs(dense).max())
    assert scale > 0.05
    assert float(np.abs(got - want).max()) < TOL["bfloat16"] * max(1, scale)
    assert (float(np.abs(got - dense).max())
            <= float(np.abs(want - dense).max()) + 1e-6)


def test_sorted_junk_past_the_live_rows_changes_no_bit(monkeypatch):
    """Rows that are not ``live`` reach no expert (a NaN there shows
    nowhere); and in the kernel's own contract what lies past an expert's
    rows — the rest of the last tile, the tiles after it — is selected
    away or never visited: over NaN there the kernel writes the bits it
    writes over zeros, and zeros in the rows of a visited tile that are
    nobody's."""
    module, c, layer, _ = _layer("sdar")
    u = jax.random.normal(jax.random.key(27), (160, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(160) % 4 != 0
    _kernel_path(monkeypatch, most=(16, 64), sorted_tile=8)
    with record_lowerings() as chosen:
        got, again = (experts.held_experts(
            jnp.where(live[:, None], u, fill), ids, w, live,
            layer["experts"], c)[0] for fill in (0.0, jnp.nan))
    assert chosen["moe_experts"] == {"pallas_sorted"}
    assert float(jnp.abs(got).max()) > 0.05
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
    # the contract: 5 tiles of 8 rows; experts 1, 4 and 6 hold rows [0, 5),
    # [5, 13) and [13, 19): tiles 0 to 2 are visited, 3 and 4 are not
    e = layer["experts"]
    held = e["wu"].shape[0]
    xs = jax.random.normal(jax.random.key(29), (40, c.hidden_size))
    load = jnp.zeros(held, jnp.int32).at[jnp.array([1, 4, 6])].set(
        jnp.array([5, 8, 6]))
    hi = jnp.cumsum(load)
    wt = jnp.full(40, 0.5)
    rows = jnp.arange(40)[:, None]
    clean, dirty = (_sorted_terms(
        jnp.where(rows < 19, xs, fill), wt, hi - load, hi, e["wg"], e["wu"],
        e["wd"], row_tile=8) for fill in (0.0, jnp.nan))
    np.testing.assert_array_equal(np.asarray(clean)[:24],
                                  np.asarray(dirty)[:24])
    assert not np.asarray(clean)[19:24].any()
    assert np.abs(np.asarray(clean)[:19]).min(axis=-1).max() > 0


def test_sorted_work_list_by_hand():
    """Loads of 5, 0, 6, 9 and 3 rows in tiles of 8: the first expert in
    tile 0, the third in tiles 0 and 1, the fourth in 1 and 2, the fifth
    in 2 — six items, tiles in ascending order, and past them the last
    one again up to the bound of ``tiles + held - 1``."""
    load = jnp.array([5, 0, 6, 9, 3])
    hi = jnp.cumsum(load)
    eid, tile, n = md.sorted_work_list(hi - load, hi, 4, 8)
    assert int(n[0]) == 6 and eid.shape == tile.shape == (4 + 5 - 1,)
    assert np.asarray(eid).tolist() == [0, 2, 2, 3, 3, 4, 4, 4]
    assert np.asarray(tile).tolist() == [0, 0, 1, 1, 2, 2, 2, 2]
    # no rows at all: no item, and the list names blocks that exist
    zero = jnp.zeros(5, jnp.int32)
    eid, tile, n = md.sorted_work_list(zero, zero, 4, 8)
    assert int(n[0]) == 0
    assert 0 <= int(eid.min()) and int(eid.max()) < 5
    assert 0 <= int(tile.min()) and int(tile.max()) < 4
    # every tile full and every expert but the first starting inside one:
    # the bound is met
    load = jnp.array([3, 8, 8, 8, 5])
    hi = jnp.cumsum(load)
    assert int(md.sorted_work_list(hi - load, hi, 4, 8)[2][0]) == 4 + 5 - 1


@pytest.mark.parametrize("gated", [True, False],
                         ids=["three-matrices", "two-matrices"])
def test_sorted_terms_by_hand_over_a_short_list(gated):
    """``pallas_sorted_add``'s own contract, a row a token: row ``r`` of the
    sorted
    rows through the expert whose ``[lo, hi)`` holds it, weighted — both
    expert forms, in one or in several inner steps; an expert without rows
    is not read, and the rows of a visited tile that are nobody's are
    zero."""
    h, inner, held, rt = 256, 384, 6, 8
    ks = jax.random.split(jax.random.key(2), 6)
    xs = jax.random.normal(ks[0], (5 * rt, h))
    wg = jax.random.normal(ks[1], (held, h, inner)) * h ** -0.5
    wu = jax.random.normal(ks[2], (held, h, inner)) * h ** -0.5
    wd = jax.random.normal(ks[3], (held, inner, h)) * inner ** -0.5
    wt = jax.random.uniform(ks[4], (5 * rt,)) + 0.1
    load = jnp.array([3, 0, 12, 7, 0, 5])       # 27 rows: tiles 0 to 3
    hi = jnp.cumsum(load)
    owner = np.repeat(np.arange(held), np.asarray(load))

    def expert(x, e):
        if gated:
            return (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        return jnp.square(jax.nn.relu(x @ wu[e])) @ wd[e]

    with jax.default_matmul_precision("highest"):
        by_hand = jnp.stack([wt[r] * expert(xs[r], e)
                             for r, e in enumerate(owner)])
        for tile in (128, 384, None):
            got = _sorted_terms(
                xs, wt, hi - load, hi, wg if gated else None, wu, wd,
                row_tile=rt, tile=tile)
            assert got.shape == (5 * rt, h) and got.dtype == F32
            assert float(jnp.abs(got[:27] - by_hand).max()) < TOL["float32"]
            assert not np.asarray(got[27:32]).any()
        # a NaN expert without rows is not read
        poisoned = _sorted_terms(
            xs, wt, hi - load, hi, wg.at[1].set(jnp.nan) if gated else None,
            wu.at[4].set(jnp.nan), wd, row_tile=rt)
    np.testing.assert_array_equal(np.asarray(poisoned[:32]),
                                  np.asarray(got[:32]))
    assert float(jnp.abs(by_hand).max()) > 0.1
    with pytest.raises(ValueError, match="does not divide"):
        _sorted_terms(xs, wt, hi - load, hi, wg, wu, wd, row_tile=rt,
                      tile=256)
    with pytest.raises(ValueError, match="whole tiles of"):
        _sorted_terms(xs[:-1], wt[:-1], hi - load, hi, wg, wu, wd,
                      row_tile=rt)


# ---- the terms reach their tokens: the kernel adds them to ``y`` itself ----

def _combine_case(gated, h=256, inner=384, held=6, rt=8, tokens=24):
    """27 sorted rows in tiles of 8 over 24 tokens: experts 0, 2, 3 and 5
    hold rows [0, 3), [3, 15), [15, 22) and [22, 27) — tile 0 is two
    experts', tile 1 two, tile 2 three —, each expert's tokens distinct and
    ascending, tokens 1 and 2 held by experts 0 and 2 IN ONE TILE, token 9
    by experts 2 and 3 in tile 1 and 2; past row 27 the list names tokens
    that are really there."""
    ks = jax.random.split(jax.random.key(59), 7)
    load = jnp.array([3, 0, 12, 7, 0, 5])
    tok = jnp.concatenate([
        jnp.array([0, 1, 2]), jnp.arange(1, 13),
        jnp.array([9, *range(14, 20)]),
        jnp.arange(19, 24), jnp.array([1, 2, 9, 0, 23] + [5] * 8)])
    operands = dict(
        xs=jax.random.normal(ks[0], (5 * rt, h)),
        wt=jax.random.uniform(ks[4], (5 * rt,)) + 0.1,
        wg=(jax.random.normal(ks[1], (held, h, inner)) * h ** -0.5
            if gated else None),
        wu=jax.random.normal(ks[2], (held, h, inner)) * h ** -0.5,
        wd=jax.random.normal(ks[3], (held, inner, h)) * inner ** -0.5)
    y = jax.random.normal(ks[5], (tokens, h))
    return y, tok.astype(jnp.int32), load, operands


def _combined(y, tok, load, operands, rt=8, tile=None):
    hi = jnp.cumsum(load)
    t, h = y.shape
    return md.pallas_sorted_add(
        y.reshape(t, h // md.LANE, md.LANE), operands["xs"], tok,
        operands["wt"],
        hi - load, hi, operands["wg"], operands["wu"], operands["wd"],
        row_tile=rt, tile=tile, interpret=True).reshape(t, h)


def _scattered(y, tok, load, operands):
    """The parent's combine, by hand: every sorted row through the expert
    whose rows hold it, weighted, and XLA's scatter-add of the live ones."""
    o = operands
    owner = np.repeat(np.arange(load.shape[0]), np.asarray(load))

    def expert(x, e):
        if o["wg"] is None:
            return jnp.square(jax.nn.relu(x @ o["wu"][e])) @ o["wd"][e]
        return (jax.nn.silu(x @ o["wg"][e]) * (x @ o["wu"][e])) @ o["wd"][e]

    terms = jnp.stack([o["wt"][r] * expert(o["xs"][r], e)
                       for r, e in enumerate(owner)])
    return y.at[tok[:len(owner)]].add(terms)


@pytest.mark.parametrize("tile", [128, None], ids=["three-steps", "one-step"])
@pytest.mark.parametrize("gated", [True, False],
                         ids=["three-matrices", "two-matrices"])
def test_combine_equals_the_parents_scatter_add(gated, tile):
    """``pallas_sorted_add`` against the scatter-add it replaces, both
    expert forms, an expert's inner width in one step and in three: a
    token that two experts hold in one tile is added to twice, one that no
    live row names keeps its bits, and so does every token when no expert
    has a row."""
    y, tok, load, operands = _combine_case(gated)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_scattered(y, tok, load, operands))
        got = np.asarray(_combined(y, tok, load, operands, tile=tile))
        idle = np.asarray(_combined(y, tok, jnp.zeros_like(load), operands,
                                    tile=tile))
    assert got.shape == y.shape and got.dtype == np.float32
    assert float(np.abs(got - want).max()) < TOL["float32"]
    moved = np.abs(want - np.asarray(y)).max(axis=-1)
    assert moved[[1, 2, 9]].min() > 0.05           # held twice
    np.testing.assert_array_equal(got[13], np.asarray(y)[13])   # by nobody
    assert moved[13] == 0
    np.testing.assert_array_equal(idle, np.asarray(y))


def test_combine_reads_nothing_past_an_experts_rows():
    """What lies outside every ``[lo, hi)`` — the rest of the last visited
    tile, the tiles after it, an expert without rows — moves no bit of
    ``y``: NaN rows, NaN weights and a NaN expert there, and token numbers
    that point at real rows."""
    y, tok, load, operands = _combine_case(True)
    rows = jnp.arange(tok.shape[0])
    dirty = dict(
        operands,
        xs=jnp.where(rows[:, None] < 27, operands["xs"], jnp.nan),
        wt=jnp.where(rows < 27, operands["wt"], jnp.nan),
        wg=operands["wg"].at[1].set(jnp.nan),
        wu=operands["wu"].at[4].set(jnp.nan))
    with jax.default_matmul_precision("highest"):
        clean, again = (np.asarray(_combined(y, tok, load, o))
                        for o in (operands, dirty))
    assert np.isfinite(again).all()
    np.testing.assert_array_equal(clean, again)


def test_combine_carries_y_from_window_to_window(monkeypatch):
    """Two windows of one admission: the second call adds to what the
    first left (``y`` is the kernel's own operand, aliased), as the
    parent's loop carried its scatter-add — ``held_experts`` whole, under a
    window of one tile and under the rule's."""
    module, c, layer, _ = _layer("sdar")
    u = jax.random.normal(jax.random.key(31), (96, c.hidden_size))
    ids, w = _routed(module, c, layer, u)
    live = jnp.arange(96) % 3 != 0
    _kernel_path(monkeypatch, lane=8, most=(16, 64), sorted_tile=8)
    held = jax.jit(lambda cap: experts.held_experts(
        u, ids, w, live, layer["experts"], c, cap)[0], static_argnums=0)
    with jax.default_matmul_precision("highest"), \
            record_lowerings() as chosen:
        one, many = np.asarray(held(None)), np.asarray(held(8))
    assert chosen["moe_combine"] == {"pallas_rows"}
    assert int(live.sum()) * c.moe_topk > 4 * 8            # many windows
    assert float(np.abs(one).max()) > 0.05
    assert float(np.abs(one - many).max()) < TOL["float32"]
    assert not many[~np.asarray(live)].any()


# ---- which lowering, and where it is stated --------------------------------

# tokens of a decode call and of the smallest admission run, hidden and
# inner widths, held experts of each cell (PERF.md section 4)
CELLS = {
    "dsv2": (64, 4 * 512, 5120, 1536, 40),
    "longcat": (32, 2 * 512, 6144, 2048, 16),
    "trinity": (64, 4 * 512, 2048, 1024, 16),
}
# SDAR's block step (64 slots x 4 positions) and its admissions' buckets of
# 4 rows, at its widths (all 128 experts held, top-8)
SDAR = {"h": 2048, "inner": 768, "held": 128, "k": 8, "router": 128}


def _shapes(t, h, inner, held, dtype=jnp.bfloat16, weights=None):
    u = jax.ShapeDtypeStruct((t, h), dtype)
    wdt = weights or dtype
    e = {"wg": jax.ShapeDtypeStruct((held, h, inner), wdt),
         "wu": jax.ShapeDtypeStruct((held, h, inner), wdt),
         "wd": jax.ShapeDtypeStruct((held, inner, h), wdt)}
    return u, e


def _lowering(t, h, inner, held, k=6, router=160, **kw):
    u, e = _shapes(t, h, inner, held, **kw)
    c = types.SimpleNamespace(experts_held=held, first_expert=0, moe_topk=k,
                              router_width=router)
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(
            lambda u, ids, w, live, e: experts.held_experts(
                u, ids, w, live, e, c))(
                    u, jax.ShapeDtypeStruct((t, k), jnp.int32),
                    jax.ShapeDtypeStruct((t, k), F32),
                    jax.ShapeDtypeStruct((t,), bool), e))
    return chosen["moe_experts"], jaxpr


@pytest.mark.parametrize("tokens,want", [
    (64 * 4, "pallas_grouped"), (4 * 128, "pallas_grouped"),
    (4 * 256, "pallas_grouped"), (4 * 512, "pallas_sorted"),
    (4 * 1024, "pallas_sorted")],
    ids=["block-step", "admit-128", "admit-256", "admit-512", "admit-1024"])
def test_on_tpu_sdars_block_step_takes_the_grouped_kernel(monkeypatch,
                                                          tokens, want):
    """The cell's block step (256 tokens) and its two smallest admission
    shapes (512 and 1,024 tokens) carry few rows an expert and take
    ``moe_grouped_fwd`` — no ``ragged_dot``, no window loop, no scatter-add
    of rows; from 2,048 tokens on a call is ``moe_sorted_fwd`` inside the
    window loop, with its scatter-add and without ``ragged_dot``."""
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    paths, jaxpr = _lowering(tokens, **SDAR)
    assert paths == {want}
    assert jaxpr.count("pallas_call") == 1 and "ragged_dot" not in jaxpr
    assert md.inner_tile(SDAR["h"], SDAR["inner"], 2) == SDAR["inner"]
    if want == "pallas_sorted":
        assert "name=moe_sorted_fwd" in jaxpr and "while" in jaxpr
        return
    assert "name=moe_grouped_fwd" in jaxpr and "while" not in jaxpr
    # the one scatter-add is ``load``'s bincount over the assignments
    assert jaxpr.count("= scatter") == 1
    assert f"i32[{SDAR['held'] + 1}] = scatter-add" in jaxpr


@pytest.mark.parametrize("cell", list(CELLS))
def test_on_tpu_decode_takes_the_kernel_and_admission_by_its_tokens(
        monkeypatch, cell):
    """A sibling cell's decode step is ``moe_decode_fwd``; its smallest
    admission ``moe_sorted_fwd`` from 2,048 tokens on (DeepSeek-V2's and
    Trinity's 4 rows x 512) and ``moe_grouped_fwd`` under that (LongCat's 2
    rows x 512 = 1,024: ``MAX_GROUPED_TOKENS``); every larger bucket is
    ``moe_sorted_fwd``."""
    decode, admit, h, inner, held = CELLS[cell]
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    paths, jaxpr = _lowering(decode, h, inner, held)
    assert paths == {"pallas"}
    assert jaxpr.count("pallas_call") == 1 and "ragged_dot" not in jaxpr
    assert "sort" in jaxpr and "while" not in jaxpr
    paths, jaxpr = _lowering(admit, h, inner, held)
    if admit <= md.MAX_GROUPED_TOKENS:
        assert cell == "longcat" and paths == {"pallas_grouped"}
        assert "name=moe_grouped_fwd" in jaxpr and "ragged_dot" not in jaxpr
        paths, jaxpr = _lowering(2 * admit, h, inner, held)
    assert paths == {"pallas_sorted"}
    assert jaxpr.count("pallas_call") == 1 and "ragged_dot" not in jaxpr
    assert "name=moe_sorted_fwd" in jaxpr and "while" in jaxpr
    # and the tile the cell's widths get: whole, under the byte limit
    ik = md.inner_tile(h, inner, 2)
    assert ik == {"dsv2": 384, "longcat": 256, "trinity": 1024}[cell]
    assert inner % ik == 0 and 3 * h * ik * 2 <= md.STEP_BYTES


# an admission run of each expert cell (PERF.md section 4): ``(tokens, h,
# inner, held, k, router width, matrices)``, the inner tile the rule gives
# it and the rows of its window
ADMISSIONS = {
    "trinity-4x8192": ((32768, 2048, 1024, 16, 8, 128, 3), 1024, 16384),
    "trinity-4x512": ((2048, 2048, 1024, 16, 8, 128, 3), 1024, 1024),
    "lfm2-8x1024": ((8192, 2048, 1792, 32, 4, 32, 3), 1792, 16384),
    "lfm2-8x256": ((2048, 2048, 1792, 32, 4, 32, 3), 1792, 4096),
    "nemotron3-4x1024": ((4096, 1024, 2688, 128, 22, 512, 2), 2688, 11264),
    "dsv2-4x512": ((2048, 5120, 1536, 40, 6, 160, 3), 768, 1536),
    "sdar-4x512": ((2048, 2048, 768, 128, 8, 128, 3), 768, 8192),
    "longcat-2x2048": ((4096, 6144, 2048, 16, 12, 768, 3), 512, 512),
}


@pytest.mark.parametrize("run", list(ADMISSIONS))
def test_on_tpu_an_admissions_tiles_and_window(monkeypatch, run):
    """``moe_sorted_fwd`` at the cells' admission runs: row tiles of the
    MXU's 128 rows whatever an expert gets (one layer alone on the chip:
    128 is the fastest or tied from LongCat's 64 rows an expert to
    Trinity's 2,048), the inner tile the widest under
    ``SORTED_STEP_BYTES`` — Trinity's, LFM2's, Nemotron-3's and SDAR's
    whole expert a step, so its matrices stay while its row tiles stream
    —, and a window of half the rows the tokens would send the held
    experts if every slot were live."""
    (t, h, inner, held, k, router, matrices), ik, window = ADMISSIONS[run]
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    u, e = _shapes(t, h, inner, held)
    if matrices == 2:
        del e["wg"]
    c = types.SimpleNamespace(experts_held=held, first_expert=0, moe_topk=k,
                              router_width=router)
    tiles = md.fitted_tile(u, e)
    assert tiles == md.Tiles(ik, 128, True)
    assert tiles.lowering == "pallas_sorted"
    assert matrices * h * ik * 2 <= md.SORTED_STEP_BYTES
    assert (ik == inner) == (run.split("-")[0] in (
        "trinity", "lfm2", "nemotron3", "sdar"))
    assert experts.sorted_window(c, t, k, tiles.rows) == window
    assert window % tiles.rows == 0 and window <= t * k


def test_sorted_window_by_hand():
    """Half the expectation with every slot live, in whole row tiles: at
    least one, never more than hold the assignments; ``capacity``
    overrides the rule and is rounded up to tiles."""
    c = types.SimpleNamespace(experts_held=16, first_expert=0, moe_topk=8,
                              router_width=128)
    assert experts.sorted_window(c, 32768, 8, 128) == 16384
    assert experts.sorted_window(c, 1100, 8, 128) == 640       # 550 -> 5
    assert experts.sorted_window(c, 40, 8, 128) == 128         # 20 -> 1
    assert experts.sorted_window(c, 40, 1, 128, 5000) == 128   # 40 rows
    assert experts.sorted_window(c, 32768, 8, 128, 24) == 128
    assert experts.sorted_window(c, 32768, 8, 8, 24) == 24


@pytest.mark.parametrize("why", ["a-mesh-in-scope", "two-types"])
def test_an_admission_traces_to_the_same_text_whatever_the_backend(
        monkeypatch, devices8, why):
    """Where the rule keeps the XLA form on a chip — a mesh in scope,
    tokens and weights of two types — an admission is the CPU's code and
    nothing else: with the chip's choice forced its trace is the CPU's,
    line for line."""
    kw = {"weights": jnp.float32} if why == "two-types" else {}
    here = _lowering(2048, 256, 128, 8, **kw)
    assert here[0] == {"xla"}
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    if why == "a-mesh-in-scope":
        with jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",)):
            assert _lowering(2048, 256, 128, 8) == here
    else:
        assert _lowering(2048, 256, 128, 8, **kw) == here
    assert _lowering(2048, 256, 128, 8)[0] == {"pallas_sorted"}


@pytest.mark.parametrize("shape,kw,want", [
    ((128, 256, 128, 8), {}, "pallas"),                 # the most tokens
    ((129, 256, 128, 8), {}, "pallas_grouped"),         # one more
    ((256, 256, 128, 8), {}, "pallas_grouped"),
    ((md.MAX_GROUPED_TOKENS, 256, 128, 8), {}, "pallas_grouped"),
    ((md.MAX_GROUPED_TOKENS + 1, 256, 128, 8), {}, "pallas_sorted"),
    ((2048, 256, 128, 8), {}, "pallas_sorted"),
    ((32768, 256, 128, 8), {}, "pallas_sorted"),        # Trinity's long runs
    ((64, 256, 128, 8), {"dtype": jnp.float32}, "pallas"),
    ((256, 256, 128, 8), {"dtype": jnp.float32}, "pallas_grouped"),
    ((64, 256, 128, 8), {"weights": jnp.float32}, "xla"),   # two types
    ((256, 256, 128, 8), {"weights": jnp.float32}, "xla"),
    ((2048, 256, 128, 8), {"weights": jnp.float32}, "xla"),
    ((64, 200, 128, 8), {}, "xla"),                     # h off the lane tile
    ((256, 200, 128, 8), {}, "xla"),
    ((2048, 200, 128, 8), {}, "xla"),
    ((64, 256, 96, 8), {}, "xla"),                      # the inner width
    ((2048, 256, 96, 8), {}, "xla"),
    ((4, 32, 16, 8), {"dtype": jnp.float32}, "xla"),    # the tests' TINY
], ids=["t-128", "t-129", "t-256", "t-most-grouped", "t-one-more", "t-2048",
        "t-32768", "float32", "float32-256", "f32-weights",
        "f32-weights-256", "f32-weights-2048", "h-200", "h-200-256",
        "h-200-2048", "inner-96", "inner-96-2048", "tiny"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, kw, want):
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    assert md.MAX_TOKENS == 128 < md.MAX_GROUPED_TOKENS < 2048
    paths, jaxpr = _lowering(*shape, **kw)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want != "xla")
    if want != "xla":
        kernel = {"pallas": "moe_decode_fwd",
                  "pallas_grouped": "moe_grouped_fwd",
                  "pallas_sorted": "moe_sorted_fwd"}[want]
        assert f"name={kernel}" in jaxpr


@pytest.mark.parametrize("tokens", [64, 256, 2048])
def test_a_mesh_in_scope_keeps_todays_form(monkeypatch, devices8, tokens):
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering(tokens, 5120, 1536, 40)
    assert paths == {"xla"} and "pallas_call" not in jaxpr


# ---- the counter -----------------------------------------------------------


def test_expert_passes_by_hand(monkeypatch):
    """One work item a touched expert under the kernel (a call's tokens
    all fit one item), nothing under today's form, whose reads the program
    cannot know; a decode step sums its expert layers', a prefill counts
    none."""
    module, c, layer, _ = _layer("dsv2")
    u = jax.random.normal(jax.random.key(11), (8, c.hidden_size))
    # tokens 0..5 live: experts 1 and 2 by three tokens, expert 9 by one
    ids = jnp.array([[1, 2, 20]] * 3 + [[9, 21, 22]] + [[23, 24, 25]] * 2
                    + [[3, 4, 5]] * 2)
    c = dataclasses.replace(c, experts_held=12)
    layer = dict(layer, experts={k: v[:12] for k, v in
                                 layer["experts"].items()})
    live = jnp.arange(8) < 6
    w = jnp.ones(ids.shape, F32)
    _, load = experts.held_experts(u, ids, w, live, layer["experts"], c)
    assert np.asarray(load).tolist() == [0, 3, 3, 0, 0, 0, 0, 0, 0, 1, 0, 0]

    def counted(load, tokens=u):
        got = experts.kernel_counters(tokens, layer["experts"], load, c)
        assert sorted(got) == ["moe.expert_passes",
                               "moe.prefill_rows_combined",
                               "moe.rows_computed"]
        assert all(v.dtype == F32 and v.shape == () for v in got.values())
        # a decode step's few tokens: no combine around the kernel
        assert float(got["moe.prefill_rows_combined"]) == 0
        return float(got["moe.expert_passes"]), float(
            got["moe.rows_computed"])

    assert counted(load) == (0, 0)
    _kernel_path(monkeypatch)
    # three touched experts, the call's 8 rows padded to a sublane tile of 16
    assert md.ROW_GROUP == 16 and counted(load) == (3, 3 * 16)
    idle = jnp.zeros_like(load)
    assert counted(idle) == (0, 0)


@pytest.mark.parametrize("steps,passes", [(1, 4), (2, 2 + 1 + 1 + 3)],
                         ids=["whole-inner-width", "two-inner-steps"])
def test_grouped_counters_by_hand(monkeypatch, steps, passes):
    """One item a row tile of an expert's own rows: 9, 8, 1 and 17 rows in
    tiles of 8 are 2 + 1 + 1 + 3 items and 56 rows through the MXU for 35
    assignments; an expert's matrices are fetched once where a step holds
    the whole inner width (its items lie side by side and keep the
    blocks), once an ITEM where the inner width takes several steps."""
    module, c, layer, _ = _layer("sdar")
    inner = layer["experts"]["wg"].shape[-1]
    _kernel_path(monkeypatch, lane=8, row_tile=8, most=(4, 64),
                 step_bytes=3 * c.hidden_size * (inner // steps) * 4)
    rows = {1: 9, 2: 8, 5: 1, 6: 17}
    ids = jnp.concatenate([jnp.full((n, 1), e) for e, n in rows.items()])
    t = ids.shape[0]
    assert t == 35 and md.MAX_TOKENS < t <= md.MAX_GROUPED_TOKENS
    u = jax.random.normal(jax.random.key(21), (t, c.hidden_size))
    c = dataclasses.replace(c, num_experts_per_tok=1)
    live = jnp.ones(t, bool)
    with record_lowerings() as chosen:
        y, load = experts.held_experts(u, ids, jnp.ones((t, 1), F32), live,
                                       layer["experts"], c)
    assert chosen["moe_experts"] == {"pallas_grouped"}
    assert np.asarray(load).tolist() == [0, 9, 8, 0, 0, 1, 17, 0]
    got = experts.kernel_counters(u, layer["experts"], load, c)
    assert float(got["moe.expert_passes"]) == passes
    assert float(got["moe.rows_computed"]) == (2 + 1 + 1 + 3) * 8
    want = _dense(u, ids, jnp.ones((t, 1), F32), live, layer, c)
    assert float(jnp.abs(y - want).max()) < 10 * TOL["float32"]


@pytest.mark.parametrize("steps,passes", [(1, 5), (2, 2 + 2 + 1 + 3)],
                         ids=["whole-inner-width", "two-inner-steps"])
def test_sorted_counters_by_hand(monkeypatch, steps, passes):
    """One item a row tile an expert has a row in: 9, 8, 1 and 17 rows,
    sorted back to back at [0, 9), [9, 17), [17, 18) and [18, 35), in
    tiles of 8 and windows of 24 rows (half of 35, in whole tiles): the
    first window holds 2 + 2 + 1 + 1 items — tiles 1 and 2 are visited by
    two and by three experts —, the second the last expert's other 11
    rows in 2: 64 rows through the MXU for 35 assignments.  An expert's
    matrices are fetched once a window it has rows in where a step holds
    the whole inner width (the last expert twice), once an ITEM where it
    does not."""
    module, c, layer, _ = _layer("sdar")
    inner = layer["experts"]["wg"].shape[-1]
    _kernel_path(monkeypatch, lane=8, most=(4, 16), sorted_tile=8,
                 step_bytes=3 * c.hidden_size * (inner // steps) * 4)
    rows = {1: 9, 2: 8, 5: 1, 6: 17}
    ids = jnp.concatenate([jnp.full((n, 1), e) for e, n in rows.items()])
    t = ids.shape[0]
    assert t == 35 > md.MAX_GROUPED_TOKENS
    u = jax.random.normal(jax.random.key(31), (t, c.hidden_size))
    c = dataclasses.replace(c, num_experts_per_tok=1)
    live = jnp.ones(t, bool)
    with record_lowerings() as chosen:
        y, load = experts.held_experts(u, ids, jnp.ones((t, 1), F32), live,
                                       layer["experts"], c)
    assert chosen["moe_experts"] == {"pallas_sorted"}
    assert np.asarray(load).tolist() == [0, 9, 8, 0, 0, 1, 17, 0]
    got = experts.kernel_counters(u, layer["experts"], load, c)
    assert experts.sorted_window(c, t, 1, 8) == 24
    assert float(got["moe.expert_passes"]) == passes
    assert float(got["moe.rows_computed"]) == (2 + 2 + 1 + 1 + 2) * 8
    # the rows of ``y`` the kernel fetched and wrote back: the live
    # assignments (the scatter-add it replaced moved both windows' 48)
    assert float(got["moe.prefill_rows_combined"]) == 35
    assert chosen["moe_combine"] == {"pallas_rows"}
    want = _dense(u, ids, jnp.ones((t, 1), F32), live, layer, c)
    assert float(jnp.abs(y - want).max()) < 10 * TOL["float32"]


def test_a_prefill_counts_the_rows_its_lowering_computed(monkeypatch):
    """``moe.prefill_rows_computed`` beside ``moe.prefill_held``: the rows
    an admission's lowering passed through the experts over the rows that
    had an assignment.  Nothing under the XLA form, whose products the
    program cannot know; under ``moe_sorted_fwd`` whole row tiles, at
    least the assignments and, a layer, less than a tile more an expert
    and a straddled tile more an expert; the decode counters stay 0 in a
    prefill.  And ``moe.prefill_rows_combined`` beside them: the rows of
    ``y`` the kernel moved, one a held assignment (0 under the XLA
    form)."""
    module, config, make, _ = FAMILIES["trinity"]
    params, policy = make(config)
    assert "moe.prefill_rows_computed" in experts.STAT_KEYS
    tokens = jnp.arange(48, dtype=jnp.int32).reshape(2, 24) % 50 + 3
    lengths = jnp.array([24, 9])

    def prefill():      # one program a call: a fresh trace per lowering
        return jax.jit(lambda: module.prefill(params, tokens, lengths, config,
                                              policy)[2])()

    before = prefill()
    assert float(before["moe.prefill_held"]) > 0
    assert float(before["moe.prefill_rows_computed"]) == 0
    assert "moe.prefill_rows_combined" in experts.STAT_KEYS
    assert float(before["moe.prefill_rows_combined"]) == 0
    rt = 8
    _kernel_path(monkeypatch, lane=8, most=(4, 16), sorted_tile=rt)
    with record_lowerings() as chosen:
        after = prefill()
    assert chosen["moe_experts"] == {"pallas_sorted"}
    held, rows = (float(after[k]) for k in ("moe.prefill_held",
                                            "moe.prefill_rows_computed"))
    assert held == float(before["moe.prefill_held"])
    expert_layers = config.num_hidden_layers - config.num_dense_layers
    assert rows % rt == 0
    assert held <= rows < held + expert_layers * config.experts_held * 2 * rt
    assert float(after["moe.prefill_rows_combined"]) == held
    assert chosen["moe_combine"] == {"pallas_rows"}
    assert float(after["moe.rows_computed"]) == 0
    assert float(after["moe.expert_passes"]) == 0


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_decode_step_counts_its_passes_and_a_prefill_none(monkeypatch,
                                                            family):
    module, config, make, _ = FAMILIES[family]
    params, policy = make(config)
    for key in ("moe.expert_passes", "moe.rows_computed"):
        assert key in module.STAT_KEYS
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) + 3

    def prefill():
        # one program a call (a fresh trace per lowering): eagerly the
        # blocked cores alone take seconds
        return jax.jit(lambda: module.prefill(
            params, tokens, jnp.array([6, 4]), config, policy)[2])()

    def counted(stats):
        return (float(stats["moe.expert_passes"]),
                float(stats["moe.rows_computed"]))

    assert counted(prefill()) == (0, 0)
    fam = {"longcat": lc.LongCatFamily, "dsv2": ds.DeepSeekV2Family,
           "trinity": tr.TrinityFamily}[family](config, policy)
    caches = fam.init_caches(4, 16)
    live = jnp.array([True, True, False, True])

    def decode():
        # a fresh trace per lowering
        return jax.jit(lambda: module.decode_step(
            params, jnp.array([5, 6, 7, 8]), jnp.array([0, 1, 0, 2]), caches,
            live, config, policy)[2])()

    before = decode()
    assert counted(before) == (0, 0)                   # the CPU: the XLA form
    _kernel_path(monkeypatch, lane=8)
    with record_lowerings() as chosen:
        after = decode()
    assert chosen["moe_experts"] == {"pallas"}
    touched = float(after["moe.experts_touched"])
    # every touched expert once, the step's 4 rows padded to a sublane tile
    assert touched > 0 and counted(after) == (touched, touched * md.ROW_GROUP)
    np.testing.assert_array_equal(np.asarray(before["moe.held_load"]),
                                  np.asarray(after["moe.held_load"]))
    # and with the kernel on a tiny prefill still counts none
    assert counted(prefill()) == (0, 0)


def test_a_block_step_counts_its_passes_and_a_prefill_none(monkeypatch):
    """``models/sdar.py``: a block step of 2 slots x 4 positions sums its
    three expert layers' passes and rows under the grouped kernel (the
    rule's lower edge moved under the step's 8 tokens), nothing under the
    XLA form; a prefill counts neither whatever ran."""
    config = sdar_tiny.TINY
    params, policy = sdar_tiny.make(config)
    block, mask = sdar_tiny.BLOCK, sdar_tiny.MASK_ID
    toks = jnp.arange(2 * 32, dtype=jnp.int32).reshape(2, 32) % 90 + 1
    primes = jnp.asarray([20, 8])
    blk = jnp.full((2, block), mask, jnp.int32)

    def both():
        # a fresh trace per lowering
        @jax.jit
        def run(params):
            _, rows, before = sdar.prefill(params, toks, primes, config,
                                           policy)
            caches = sdar.caches_from(rows, primes, config, 48)
            return before, sdar.block_step(
                params, blk, primes, caches, jnp.asarray([True, True]),
                jnp.zeros(2, bool), config, policy)[2]
        return run(params)

    for stats in both():
        assert float(stats["moe.expert_passes"]) == 0
        assert float(stats["moe.rows_computed"]) == 0
    _kernel_path(monkeypatch, lane=8, row_tile=8, most=(4, 512))
    with record_lowerings() as chosen:
        before, step = both()
    # the step's 8 tokens took the kernel, the prefill's 64 too
    assert chosen["moe_experts"] == {"pallas_grouped"}
    assert float(before["moe.expert_passes"]) == 0
    assert float(before["moe.rows_computed"]) == 0
    touched = float(step["moe.experts_touched"])
    assert float(step["moe.expert_passes"]) == touched > 0
    # no expert has more than 8 of a layer's 16 assignments: a tile each
    assignments = 2 * block * config.num_experts_per_tok * 3
    assert float(jnp.sum(step["moe.held_load"])) == assignments
    assert float(step["moe.rows_computed"]) == 8 * touched > assignments


def test_cpu_notes_xla_and_the_engine_states_it_per_program():
    """On the CPU both programs take today's form and say so;
    ``status()["moe_experts"]`` is ``None`` before a program is traced,
    then ``{program: lowering}``; the counter is published with the others
    and stays 0 under this form."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.observe.metrics import get_registry

    _, config, make, _ = FAMILIES["dsv2"]
    params, policy = make(config)
    eng = ServingEngine(config, params, policy=policy,
                        num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                        max_len=32)
    assert eng.status()["moe_experts"] is None
    eng.submit(Request(uid=0, tokens=[3, 4, 5], max_new_tokens=3,
                       temperature=0.0, seed=1))
    (done,) = eng.run_until_idle(max_chunks=10)
    assert done.uid == 0
    assert eng.status()["moe_experts"] == {"chunk": "xla", "admit": "xla"}
    assert eng.status()["moe_combine"] is None      # no program sorts
    snap = get_registry().snapshot()
    assert snap["moe.prefill_rows_combined"]["value"] == 0
    assert snap["moe.experts_touched"]["value"] > 0
    assert snap["moe.expert_passes"]["value"] == 0
    assert snap["moe.rows_computed"]["value"] == 0
    # an engine has one admission program a bucket, and the rule may give
    # each another lowering: the program's entry names all its traces took
    from progen_tpu.ops.lowering import note

    def impl(x):
        note("moe_experts", "pallas_grouped" if x.shape[0] <= 1024 else "xla")
        return x + 1

    admit = eng._jit_noting(impl, "admit")
    for tokens in (2048, 512, 4096):
        admit(jnp.zeros(tokens))
    assert eng.status()["moe_experts"] == {"chunk": "xla",
                                           "admit": "pallas_grouped+xla"}


def test_the_admission_program_names_the_sorted_kernel(monkeypatch):
    """With the chip's choice forced and the rule's edges at the engine's
    slots, the chunk program (32 tokens) takes ``moe_decode_fwd`` and the
    admission program (2 rows x 32 = 64 token slots) the sorted kernel:
    ``engine.program_lowerings`` and ``status()`` say so, and the registry
    carries the admission's rows beside its assignments."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.observe.metrics import get_registry

    _, config, make, _ = FAMILIES["dsv2"]
    params, policy = make(config)
    slots = 2 * SLOTS_PER_ADMIT_ROW
    _kernel_path(monkeypatch, lane=8, most=(slots, slots),
                 sorted_tile=8)
    eng = ServingEngine(config, params, policy=policy, num_slots=slots,
                        chunk_size=4, max_len=32)
    eng.submit(Request(uid=0, tokens=list(range(3, 23)), max_new_tokens=3,
                       temperature=0.0, seed=1))
    (done,) = eng.run_until_idle(max_chunks=10)
    assert done.uid == 0
    assert eng.program_lowerings["admit"]["moe_experts"] == "pallas_sorted"
    assert eng.status()["moe_experts"] == {"chunk": "pallas",
                                           "admit": "pallas_sorted"}
    snap = get_registry().snapshot()
    held = snap["moe.prefill_held"]["value"]
    rows = snap["moe.prefill_rows_computed"]["value"]
    # 20 real tokens, 3 of 16 experts each, all held, two expert layers
    assert held == 20 * 3 * 2
    assert rows % 8 == 0 and held <= rows < held + 2 * 16 * 2 * 8
    # and how the admission's terms reached their tokens: the kernel's row
    # DMAs, a row of ``y`` a held assignment
    assert eng.status()["moe_combine"] == {"admit": "pallas_rows"}
    assert eng.lowerings["moe_combine"] == "pallas_rows"
    assert snap["moe.prefill_rows_combined"]["value"] == held
