"""``models/experts.py:held_experts`` for a decode step's handful of tokens:
two lowerings, one contract.  The Pallas kernel (``moe_decode_fwd``,
``ops/moe_decode.py``) runs under the interpreter here, over the three tiny
configurations' own expert weights and routers: against today's XLA form
(windows of ``ragged_dot``) and against a dense float32 loop over the held
experts, on the cases that break grouped kernels; the choice of lowering
from backend, mesh and shape at each cell's decode and admission shapes, as
``status()`` shows it; and the counter ``moe.expert_passes``."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models import deepseek_v2 as ds
from progen_tpu.models import experts
from progen_tpu.models import longcat as lc
from progen_tpu.models import trinity as tr
from progen_tpu.ops import moe_decode as md
from progen_tpu.ops.lowering import record_lowerings
from tests import deepseek_v2_tiny, longcat_tiny, trinity_tiny

F32 = jnp.float32
# (family module, tiny config, make, index of an expert layer)
FAMILIES = {
    "longcat": (lc, longcat_tiny.TINY, longcat_tiny.make, 0),
    "dsv2": (ds, deepseek_v2_tiny.TINY, deepseek_v2_tiny.make, 1),
    "trinity": (tr, trinity_tiny.TINY, trinity_tiny.make, 1),
}
# the bfloat16 bound of the siblings' kernel tests, and float32's
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _kernel_path(monkeypatch, lane=16, step_bytes=None):
    """The chip's choice with the interpreter behind it, at the tiny
    widths: a lane tile of ``lane`` and, with ``step_bytes``, a limit
    small enough that an expert takes several steps."""
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    monkeypatch.setattr(md, "LANE", lane)
    if step_bytes:
        monkeypatch.setattr(md, "STEP_BYTES", step_bytes)
    monkeypatch.setattr(
        md, "pallas_expert_terms",
        lambda *a, _f=md.pallas_expert_terms, **kw: _f(
            *a, **{**kw, "interpret": True}))


def _layer(family, mixed=False, held=None, first=0):
    """One expert layer of the tiny configuration, holding ``held`` experts
    from ``first`` on (default: all of them)."""
    module, config, make, index = FAMILIES[family]
    params, policy = make(config, mixed)
    layer = params["layers"][index]
    if held is not None:
        config = dataclasses.replace(config, experts_held=held,
                                     first_expert=first)
        layer = dict(layer, experts={k: v[first:first + held] for k, v in
                                     layer["experts"].items()})
    return module, config, layer, policy.compute_dtype


def _routed(module, config, layer, u):
    out = module.route(u, layer["router"], config)
    return out[0], out[1]                      # ids, weights


def _dense(u, ids, w, live, layer, c):
    """Every held expert over every token in float32, the assignments
    picked out after: nothing grouped, nothing skipped."""
    e = {k: v.astype(F32) for k, v in layer["experts"].items()}
    uf = u.astype(F32)
    y = jnp.zeros(uf.shape, F32)
    for j in range(c.experts_held):
        out = (jax.nn.silu(uf @ e["wg"][j]) * (uf @ e["wu"][j])) @ e["wd"][j]
        wj = jnp.sum(jnp.where(ids == c.first_expert + j, w, 0.0), axis=-1)
        y = y + out * (wj * live)[:, None]
    return y


def _both(monkeypatch, u, ids, w, live, layer, c, **tiles):
    """``held_experts`` under today's form, then under the kernel."""
    with jax.default_matmul_precision("highest"):
        want, load = experts.held_experts(u, ids, w, live, layer["experts"],
                                          c)
        _kernel_path(monkeypatch, **tiles)
        with record_lowerings() as chosen:
            got, load2 = experts.held_experts(u, ids, w, live,
                                              layer["experts"], c)
    assert chosen["moe_experts"] == {"pallas"}
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


# ---- which lowering, and where it is stated --------------------------------

# tokens of a decode call and of the smallest admission run, hidden and
# inner widths, held experts of each cell (PERF.md section 4)
CELLS = {
    "dsv2": (64, 4 * 512, 5120, 1536, 40),
    "longcat": (32, 2 * 512, 6144, 2048, 16),
    "trinity": (64, 4 * 512, 2048, 1024, 16),
}


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


@pytest.mark.parametrize("cell", list(CELLS))
def test_on_tpu_decode_takes_the_kernel_and_admission_todays_form(
        monkeypatch, cell):
    decode, admit, h, inner, held = CELLS[cell]
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    paths, jaxpr = _lowering(decode, h, inner, held)
    assert paths == {"pallas"}
    assert jaxpr.count("pallas_call") == 1 and "ragged_dot" not in jaxpr
    assert "sort" in jaxpr and "while" not in jaxpr
    paths, jaxpr = _lowering(admit, h, inner, held)
    assert paths == {"xla"}
    assert "pallas_call" not in jaxpr and "ragged_dot" in jaxpr
    # and the tile the cell's widths get: whole, under the byte limit
    ik = md.inner_tile(h, inner, 2)
    assert ik == {"dsv2": 384, "longcat": 256, "trinity": 1024}[cell]
    assert inner % ik == 0 and 3 * h * ik * 2 <= md.STEP_BYTES


def test_an_admission_traces_to_the_same_text_whatever_the_backend(
        monkeypatch):
    """A call too large for the kernel is today's code and nothing else:
    with the chip's choice forced its trace is the CPU's, line for line."""
    here = _lowering(2048, 256, 128, 8)[1]
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    assert _lowering(2048, 256, 128, 8)[1] == here


@pytest.mark.parametrize("shape,kw,want", [
    ((128, 256, 128, 8), {}, "pallas"),                 # the most tokens
    ((129, 256, 128, 8), {}, "xla"),
    ((64, 256, 128, 8), {"dtype": jnp.float32}, "pallas"),
    ((64, 256, 128, 8), {"weights": jnp.float32}, "xla"),   # two types
    ((64, 200, 128, 8), {}, "xla"),                     # h off the lane tile
    ((64, 256, 96, 8), {}, "xla"),                      # the inner width
    ((4, 32, 16, 8), {"dtype": jnp.float32}, "xla"),    # the tests' TINY
], ids=["t-128", "t-129", "float32", "f32-weights", "h-200", "inner-96",
        "tiny"])
def test_on_tpu_the_shape_decides(monkeypatch, shape, kw, want):
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    paths, jaxpr = _lowering(*shape, **kw)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")


def test_a_mesh_in_scope_keeps_todays_form(monkeypatch, devices8):
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering(64, 5120, 1536, 40)
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
    assert float(experts.expert_passes(u, layer["experts"], load)) == 0
    _kernel_path(monkeypatch)
    assert float(experts.expert_passes(u, layer["experts"], load)) == 3
    idle = jnp.zeros_like(load)
    assert float(experts.expert_passes(u, layer["experts"], idle)) == 0


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_decode_step_counts_its_passes_and_a_prefill_none(monkeypatch,
                                                            family):
    module, config, make, _ = FAMILIES[family]
    params, policy = make(config)
    assert "moe.expert_passes" in module.STAT_KEYS
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) + 3

    def prefill():
        return module.prefill(params, tokens, jnp.array([6, 4]), config,
                              policy)[2]

    assert float(prefill()["moe.expert_passes"]) == 0
    fam = {"longcat": lc.LongCatFamily, "dsv2": ds.DeepSeekV2Family,
           "trinity": tr.TrinityFamily}[family](config, policy)
    caches = fam.init_caches(4, 16)
    live = jnp.array([True, True, False, True])

    def decode():
        # a fresh trace per lowering
        return module.decode_step(params, jnp.array([5, 6, 7, 8]),
                                  jnp.array([0, 1, 0, 2]), caches, live,
                                  config, policy)[2]

    before = decode()
    assert float(before["moe.expert_passes"]) == 0     # the CPU: today's form
    _kernel_path(monkeypatch, lane=8)
    after = decode()
    assert (float(after["moe.expert_passes"])
            == float(after["moe.experts_touched"]) > 0)
    np.testing.assert_array_equal(np.asarray(before["moe.held_load"]),
                                  np.asarray(after["moe.held_load"]))
    # and with the kernel on a tiny prefill still counts none
    assert float(prefill()["moe.expert_passes"]) == 0


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
    snap = get_registry().snapshot()
    assert snap["moe.experts_touched"]["value"] > 0
    assert snap["moe.expert_passes"]["value"] == 0
