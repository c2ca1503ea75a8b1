"""GLM-5.2 (``progen_tpu/models/glm_dsa.py``) against the plain reference
(``perf/lib/reference_glm52.py``: float32, no cache, the indexer's selection
as a dense mask scattered from its own top-k, a shared layer reading its full
layer's mask): the forward over a stack whose every layer attends under a
selection, unequal right-padded rows prefilled and then decoded past
``index_topk`` (keys are dropped), the selected sets equal to the
reference's and a shared layer's THE SAME as its full layer's at the
selector's edges, each omission the reference can plant failing the tolerance
the program keeps, the two kinds of cache and of parameters, the counters and
byte gauges, the two rotations, and which lowering an admission's core takes
at the published widths."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_glm52 as ref
from progen_tpu.models import driver, glm_dsa as gm, latent
from progen_tpu.ops import dsa, gqa
from progen_tpu.ops.lowering import record_lowerings
from tests.families import fresh, jitted
from tests.glm_dsa_tiny import (TINY, TOP_K, WIDE, WIDE_LAYERS, WIDE_TOP_K,
                                 as_dict, force_prefill_kernel, make)

T, MAX_LEN = 32, 48
LAYERS, OWNERS = 5, 2
# float32 end to end against float32 ``highest``: what is left is the order
# of sums (the absorbed form, the one division after the value product)
TOL = 5e-5


def _published():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "perf", "configs",
                        "glm-5.2-ep16.json")
    with open(path) as f:
        return gm.GLMDSAConfig.from_dict(json.load(f))


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


@functools.partial(jax.jit, static_argnames=("policy", "everywhere", "c"))
def _prefill(params, toks, lengths, policy, everywhere=False, c=TINY):
    pos = (jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
           if everywhere else None)
    with jax.default_matmul_precision("highest"):
        return gm.prefill(params, toks, lengths, c, policy,
                          logit_positions=pos)


@functools.partial(jax.jit, static_argnames=("changed",))
def _reference(params, toks, changed=()):
    """Logits of every position, the routers' choices and the full layers'
    selections, a row; ``changed``: configuration keys planted."""
    cfg = {**as_dict(TINY), **dict(changed)}
    with jax.default_matmul_precision("highest"):   # one trace for the rows
        return jax.lax.map(
            lambda row: ref.forward_row(params, row, cfg, q_block=8), toks)


def test_the_tiny_model_has_every_kind_of_layer():
    params, _ = make()
    assert TINY.indexer_types == (gm.FULL, gm.SHARED, gm.SHARED, gm.FULL,
                                  gm.SHARED)
    assert ["ffn" in layer for layer in params["layers"]] == [
        True, False, False, False, False]
    blocks = gm.blocks_of(TINY)
    assert [(b.selection, b.indexer) for b in blocks.values()] == [
        ("own", True), ("borrow", False), ("borrow", False), ("own", True),
        ("borrow", False)]
    assert blocks["l0"] is blocks["l3"] and blocks["l1"] is blocks["l4"]
    full, shared = params["layers"][3]["attn"], params["layers"][1]["attn"]
    assert full["wqb"].shape == (24, 4 * 16) and full["wkva"].shape == (64, 20)
    assert full["wkvb"].shape == (16, 4 * (12 + 16))
    assert full["wiq"].shape == (24, 16 * 8) and full["wik"].shape == (64, 8)
    assert full["wiw"].shape == (64, 16)
    # a shared layer has NO indexer leaf and no gate
    assert set(full) - set(shared) == {"wiq", "wik", "wiw", "ik_scale",
                                       "ik_bias"}
    assert "wgate" not in full and "shared" in params["layers"][1]
    # the published layout: 21 full and 57 shared, three dense layers
    whole = gm.GLMDSAConfig()
    assert whole.indexer_types.count(gm.FULL) == 21
    assert [i for i, k in enumerate(whole.indexer_types)
            if k == gm.FULL][:6] == [0, 1, 2, 6, 10, 14]
    assert whole.indexer_types[74] == gm.FULL and whole.indexer_types[77] == (
        gm.SHARED)
    assert whole.mlp_layer_types.count(gm.DENSE) == 3
    assert whole.latent_width == 576 and whole.q_gain == whole.kv_gain == 1


def test_forward_matches_the_reference_at_every_real_position():
    params, policy = make()
    toks = _tokens()
    want, _, _ = _reference(params, toks)
    got, rows, stats = _prefill(params, toks, jnp.array([T, 21]), policy,
                                True)
    junk, _, _ = _prefill(params, toks.at[1, 21:].set(5), jnp.array([T, 21]),
                          policy, True)
    assert float(jnp.abs(got[0] - want[0]).max()) < TOL
    assert float(jnp.abs(got[1, :21] - want[1, :21]).max()) < TOL
    np.testing.assert_array_equal(got[1, :21], junk[1, :21])
    assert float(want.std()) > 0.3              # not a vacuous bound
    assert float(stats["moe.tokens"]) == 4 * (T + 21)
    assert rows["l0"]["latent"].shape == (2, T, 20)
    assert rows["l3"]["index"].shape == (2, T, 8)
    assert rows["l1"].shape == rows["l4"].shape == (2, T, 20)
    # four segments of 8 rows, the first without the indexer: scored in the
    # two full layers; every layer's core the blocked form under the mask
    scored, _ = dsa.prefill_pairs(T, TOP_K)
    assert scored == 8 * (16 + 24 + 32)
    assert float(stats["dsa.prefill_pairs_scored"]) == 2 * OWNERS * scored
    # one block of 32 query rows against 32 keys, a row, a layer
    assert float(stats["dsa.prefill_pairs_attended"]) == 2 * LAYERS * T * T
    allowed = sum(min(t + 1, TOP_K) for n in (T, 21) for t in range(n))
    assert float(stats["dsa.prefill_pairs_selected"]) == LAYERS * allowed
    assert float(stats["dsa.selections_computed"]) == 2 * OWNERS
    assert float(stats["dsa.selections_borrowed"]) == 2 * (LAYERS - OWNERS)


@pytest.mark.parametrize("changed", [
    (("index_topk", 64),), (("index_topk", TOP_K // 2),),
    (("index_relu", False),), (("index_head_weights", False),),
    (("shared_selection", "none"),), (("shared_selection", "own"),),
    (("full_selection", "borrow"),), (("rope_interleave", False),),
    (("indexer_rope_interleave", False),), (("routed_scaling_factor", 1.0),),
    (("shared_expert", False),)],
    ids=lambda c: f"{c[0][0]}={c[0][1]}")
def test_each_omission_fails_the_tolerance_the_program_keeps(changed):
    """The reference with ONE of the family's choices left out or moved
    stands far from the reference as stated, where the program stands
    within ``TOL``: no selection, half of it, no ReLU, unweighted indexer
    heads, a shared layer that attends EVERY key, a shared layer that
    SELECTS FOR ITSELF (its full layer's indexer weights on its own input),
    a full layer that reads the selection before it, half-split pairs on
    either side, unscaled router weights, no shared expert."""
    params, _ = make()
    toks = _tokens()
    want, _, _ = _reference(params, toks)
    other, _, _ = _reference(params, toks, changed)
    assert float(jnp.abs(other - want).max()) > 100 * TOL


@functools.partial(jax.jit, static_argnames=("policy",))
def _step(p, t, ps, c, policy):
    """A decode step of every row and what its indexers selected: one
    program a precision for the file."""
    with dsa.record_selections() as picked, \
            jax.default_matmul_precision("highest"):
        logits, c, stats = gm.decode_step(
            p, t, ps, c, jnp.ones(t.shape, bool), TINY, policy)
    return logits, c, picked, stats


def _served(params, policy, toks, primes, bucket):
    """Logits of every position from ``prime - 1`` on, a row — the
    prefill's last position, then a decode step a token through the caches
    — and each step's selections ``[(rows, kept)] x full layers``."""
    primes = jnp.asarray(primes)
    first, per_token, _ = _prefill(params, toks[:, :bucket], primes, policy)
    caches = jitted(gm.caches_from)(per_token, primes, TINY, MAX_LEN)
    step = functools.partial(_step, policy=policy)
    out, selections = [first[:, 0]], []
    for i in range(T - int(primes.max())):
        pos = primes + i
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        logits, caches, picked, _ = step(params, tok, pos, caches)
        out.append(logits)
        selections.append(picked)
    return jnp.stack(out, axis=1), selections


@pytest.mark.parametrize("primes,bucket,mixed,tol", [
    ((1, TOP_K - 1), 8, False, TOL), ((5, TOP_K), 8, False, TOL),
    ((13, TOP_K + 1), 16, False, TOL), ((6, TOP_K + 1), 16, True, 0.3)],
    ids=["one-token-and-under-top-k", "under-and-at-top-k",
         "past-top-k-beside-one-over", "bf16-params-and-compute"])
def test_unequal_rows_prefilled_then_decoded_match_the_reference(
        primes, bucket, mixed, tol):
    params, policy = make(mixed=mixed)
    toks = _tokens()
    start = max(primes)
    want, _, selected = _reference(params, toks)
    got, selections = _served(params, policy, toks, primes, bucket)
    assert got.dtype == jnp.float32
    steps = T - start + 1
    for row, prime in enumerate(primes):
        diff = jnp.abs(got[row] - want[row, prime - 1:prime - 1 + steps])
        assert float(jnp.sqrt(jnp.mean(diff ** 2)) if mixed
                     else diff.max()) < tol
        if mixed:
            continue
        # the SETS the two indexers selected, step by step, are the
        # reference's at float32 — ONE ``select_rows`` a full layer a step
        for i, picked in enumerate(selections):
            at = prime + i
            assert len(picked) == OWNERS
            for layer, (ids, kept) in enumerate(picked):
                mine = set(np.asarray(ids[row, :int(kept[row])]).tolist())
                theirs = set(np.flatnonzero(
                    np.asarray(selected[row, layer, at])).tolist())
                assert mine == theirs and len(mine) == min(at + 1, TOP_K)
    assert T > 2 * TOP_K            # every row passed top-k: keys dropped


def _attended(c, owner: bool, handed, cache, x, pos, p):
    """A block's decode over ``cache`` and what it hands on."""
    block = latent.LatentBlock(c, selection="own" if owner else "borrow")
    return jax.jit(block.decode)(x, pos, cache, p, handed)


@pytest.mark.parametrize("count", [TOP_K - 1, TOP_K, TOP_K + 1])
def test_a_shared_layers_selection_is_the_preceding_full_layers(count):
    """A decode step at the selector's edges (a context of ``top_k - 1``,
    ``top_k``, ``top_k + 1`` keys): the full block hands on ``(rows,
    kept)``, the shared block returns THE SAME and reads its own latent rows
    at those numbers alone — its output is the dense softmax over exactly
    that set of ITS rows, and rows outside the set, or past the count, may
    hold anything."""
    params, _ = make()
    full, shared = params["layers"][0]["attn"], params["layers"][1]["attn"]
    ks = jax.random.split(jax.random.key(count), 4)
    pos = jnp.array([count - 1, count - 1])
    x = jax.random.normal(ks[0], (2, TINY.hidden_size))
    own = {"latent": jax.random.normal(ks[1], (2, MAX_LEN, 20)),
           "index": jax.random.normal(ks[2], (2, MAX_LEN, 8))}
    mine = jax.random.normal(ks[3], (2, MAX_LEN, 20))
    with jax.default_matmul_precision("highest"):
        _, _, handed = _attended(TINY, True, None, own, x, pos, full)
        out, cache, passed = _attended(TINY, False, handed, mine, x, pos,
                                       shared)
        rows, kept = handed
        assert passed[0] is not None and kept.tolist() == [
            min(count, TOP_K)] * 2
        np.testing.assert_array_equal(passed[0], rows)
        np.testing.assert_array_equal(passed[1], kept)
        # every row NOT selected, and every row past the count, scrambled:
        # the shared block's output does not move
        chosen = np.zeros((2, MAX_LEN), bool)
        for r in range(2):
            chosen[r, np.asarray(rows[r, :int(kept[r])])] = True
        junk = jnp.where(chosen[..., None], mine, 7.0)
        again, _, _ = _attended(TINY, False, handed, junk, x, pos, shared)
    np.testing.assert_allclose(out, again, atol=1e-6)
    assert chosen.sum(-1).tolist() == [min(count, TOP_K)] * 2
    # the token's own row is written and, being the newest, selected
    assert chosen[:, count - 1].all()
    assert float(jnp.abs(cache[:, count - 1] - mine[:, count - 1]).max()) > 0
    if count > TOP_K:               # a key WAS dropped
        assert not chosen[:, :count].all()


def test_a_borrower_without_an_owner_before_it_is_refused():
    with pytest.raises(ValueError, match="no layer below"):
        dataclasses.replace(TINY, indexer_types=(gm.SHARED,) + (gm.FULL,) * 4)
    with pytest.raises(ValueError, match="owned or borrowed"):
        latent.LatentBlock(TINY, selection="lend")
    with pytest.raises(ValueError, match="borrower has none"):
        latent.LatentBlock(TINY, indexer=True, selection="borrow")


def test_a_slot_holds_two_cache_shapes_of_one_latent_shape():
    _, policy = make()
    family = gm.GLMDSAFamily(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    owner = {"latent": (3, MAX_LEN, 20), "index": (3, MAX_LEN, 8)}
    assert jax.tree.map(lambda a: a.shape, caches) == {
        "l0": owner, "l1": (3, MAX_LEN, 20), "l2": (3, MAX_LEN, 20),
        "l3": owner, "l4": (3, MAX_LEN, 20)}
    # the published shapes: a full layer's token 1,408 B, a shared one's
    # 1,152 B; a slot of the cell 5 x 17,408 x 1,152 + 2 x 17,408 x 256
    whole = gm.blocks_of(_published())
    shapes = jax.eval_shape(lambda: {
        n: b.init_cache(1, 17408, jnp.bfloat16) for n, b in whole.items()})
    assert shapes["l0"]["latent"].shape == (1, 17408, 576)
    assert shapes["l4"]["index"].shape == (1, 17408, 128)
    assert shapes["l1"].shape == shapes["l3"].shape == (1, 17408, 576)
    assert sum(a.size * 2 for a in jax.tree.leaves(shapes)) == (
        5 * 17408 * 1152 + 2 * 17408 * 256)


def test_decode_counts_contexts_selections_and_the_rows_each_core_reads():
    params, policy = make()
    family = gm.GLMDSAFamily(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live = jnp.array([True, False, True])
    pos = jnp.array([2, 30, 20])
    _, _, stats = jax.jit(functools.partial(
        gm.decode_step, config=TINY, policy=policy))(
        params, jnp.array([4, 5, 6]), pos, caches, live)
    got = {k: float(v) for k, v in stats.items() if k != "moe.held_load"}
    assert got["mla.decode_rows"] == 2
    assert got["mla.context_tokens"] == 3 + 21
    assert got["dsa.context_tokens"] == LAYERS * (3 + 21)
    assert got["dsa.keys_selected"] == LAYERS * (3 + TOP_K)
    assert got["dsa.index_rows_read"] == OWNERS * 3 * MAX_LEN  # every slot's
    assert got["mla.cache_rows_read"] == 3 * TOP_K      # ONE block's XLA core
    assert got["dsa.selections_computed"] == OWNERS * 2
    assert got["dsa.selections_borrowed"] == (LAYERS - OWNERS) * 2
    assert got["moe.decode_layers"] == 4 and got["moe.tokens"] == 4 * 2
    gauges = family.publish(stats)
    assert gauges["dsa.index_bytes_read"] == OWNERS * 3 * MAX_LEN * 8 * 4
    assert gauges["mla.cache_bytes_read"] == 3 * TOP_K * LAYERS * 20 * 4
    assert gauges["dsa.selections_read"] == LAYERS * 2
    assert "mla.window_bytes_read" not in gauges


def test_who_selected_is_counted_from_the_trace_not_from_the_labels(
        monkeypatch):
    """``dsa.selections_computed`` follows the ``select_rows`` calls the
    step traced: shared layers that SELECT FOR THEMSELVES (here: layer 0's
    indexer run again on layer 0's cache, in every borrowing core) count as
    computing, and ``dsa.layers_per_selection`` falls from 2.5 to 1."""
    params, policy = make()
    family = gm.GLMDSAFamily(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live, pos = jnp.array([True, False, True]), jnp.array([2, 30, 20])
    core, decode, borrowing = dsa.sparse_decode_attention, latent.mla_decode, []

    def marked(*args, selection=None, **kwargs):
        borrowing[:] = [selection == "borrow"]
        return decode(*args, selection=selection, **kwargs)

    def selects_for_itself(q_cat, cache, rows, kept, rank, scale):
        if borrowing[0]:
            heads = (3, TINY.index_n_heads)
            rows, kept = dsa.select_rows(
                jnp.ones(heads + (TINY.index_head_dim,)), jnp.ones(heads),
                caches["l0"]["index"], pos + 1, TINY.index_topk)
        return core(q_cat, cache, rows, kept, rank, scale)

    monkeypatch.setattr(latent, "mla_decode", marked)
    monkeypatch.setattr(dsa, "sparse_decode_attention", selects_for_itself)
    _, _, stats = jax.jit(functools.partial(
        gm.decode_step, config=TINY, policy=policy))(
        params, jnp.array([4, 5, 6]), pos, caches, live)
    assert float(stats["dsa.selections_computed"]) == LAYERS * 2
    assert float(stats["dsa.selections_borrowed"]) == 0
    gauges = family.publish(stats)
    assert gauges["dsa.selections_read"] / gauges[
        "dsa.selections_computed"] == 1


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = _published()
    assert c.num_hidden_layers == 5 and c.experts_held == 16
    assert c.indexer_types == (gm.FULL,) + (gm.SHARED,) * 3 + (gm.FULL,)
    assert c.mlp_layer_types == (gm.DENSE,) + (gm.SPARSE,) * 4
    assert (c.index_topk, c.rope_theta, c.vocab_size) == (2048, 8e6, 19360)
    assert c.rope_interleave and c.indexer_rope_interleave
    for changed in ({"scoring_func": "softmax"}, {"n_shared_experts": 2},
                    {"n_group": 8}, {"indexer_types": ("full",)},
                    {"mlp_layer_types": ("dense",) * 4},
                    {"first_expert": 250}):
        with pytest.raises(ValueError):
            dataclasses.replace(c, **changed)


# ---------------------------------------------------------------- the rotation


def test_interleaved_pairs_are_the_definition_and_not_the_half_split_ones():
    """``driver.rope_pairs`` rotates pairs ``(2i, 2i + 1)``: the product of
    two vectors rotated by it is the product of the two rotated by the
    reference's written-out definition (the columns' order is the
    program's own), and differs from the half-split rotation's; the
    model's logits move when either side's pairs are changed."""
    ks = jax.random.split(jax.random.key(2), 2)
    a = jax.random.normal(ks[0], (6, 3, 8))
    b = jax.random.normal(ks[1], (6, 3, 8))
    at = jnp.arange(6) * 5 + 1
    inv = TINY.rope_inv_freq

    def dots(rotate):
        return jnp.einsum("qhd,khd->hqk", rotate(a), rotate(b))

    want = dots(lambda x: ref.rope(x, at, TINY.rope_theta))
    got = dots(lambda x: driver.rope_pairs(x, at, inv))
    half = dots(lambda x: driver.rope(x, at, inv))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(
        half, dots(lambda x: ref.rope(x, at, TINY.rope_theta, False)),
        atol=1e-5)
    assert float(jnp.abs(got - half).max()) > 0.1
    params, policy = make()
    toks = _tokens()
    base, _, _ = _prefill(params, toks, jnp.array([T, T]), policy)
    for key in ("rope_interleave", "indexer_rope_interleave"):
        moved, _, _ = _prefill(params, toks, jnp.array([T, T]), policy,
                               c=dataclasses.replace(TINY, **{key: False}))
        assert float(jnp.abs(moved - base).max()) > 100 * TOL


# ---------------------------- which lowering an admission's core takes


@pytest.mark.parametrize("where,want", [
    ("on-a-tpu", "pallas"), ("cpu-default", "xla"),
    ("tiny-widths-on-a-tpu", "xla")])
def test_every_layer_traces_one_kernel_call_where_the_kernel_applies(
        where, want, monkeypatch):
    """A full and a shared layer's admission, abstract operands, ``P``
    4,096 of one row: at the published widths (192 + 64 joined to 256,
    beside values of 256) on a TPU each is exactly ONE ``pallas_call``,
    ``gqa_prefill_fwd``, under the byte mask — which the full layer
    computes (its thresholds counted, ``ops/kth.py``: one loop of rounds a
    segment, no ``top_k``) and the shared one takes as an operand (none);
    on the CPU and at the tiny widths the blocked XLA form under the same
    mask."""
    c, n = (TINY, 32) if where.startswith("tiny") else (_published(), 4096)
    policy = gm.bf16_policy()
    params = jax.eval_shape(lambda k: gm.init_params(c, k, policy),
                            jax.random.key(0))
    if where != "cpu-default":
        monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1, n, c.hidden_size), jnp.bfloat16)
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32)
    keep = jax.ShapeDtypeStruct((1, n, n), jnp.int8)
    with record_lowerings() as chosen:
        own = str(jax.make_jaxpr(lambda x, p, m: latent.mla_prefill(
            x, p, c, m, selection="own")[0])(
            x, params["layers"][0]["attn"], lengths))
        borrowed = str(jax.make_jaxpr(lambda x, p, m, k: latent.mla_prefill(
            x, p, c, m, selection="borrow", handed=k)[0])(
            x, params["layers"][1]["attn"], lengths, keep))
    assert chosen == {"gqa_prefill": {want}, "dsa_kth": {"xla"}}
    keys = "bitcast_convert_type[new_dtype=uint32]"
    assert own.count(keys) == n // c.index_topk - 1 and keys not in borrowed
    assert "top_k[" not in own + borrowed
    for text in (own, borrowed):
        assert text.count("pallas_call") == (want == "pallas")
        assert ("name=gqa_prefill_fwd" in text) == (want == "pallas")
    assert f"i8[1,{n},{n}]" in own
    got = gm.prefill_attention_stats(gm.blocks_of(c), ["mask"] * 2, n,
                                     jnp.array([n - n // 4 - 1]), jnp.bfloat16)
    # a row of 3,071: three live query tiles of 1,024 under the kernel
    scored = dsa.prefill_pairs(n, c.index_topk)[0]
    assert float(got["dsa.prefill_pairs_scored"]) == 2 * scored
    if want == "pallas":
        assert float(got["dsa.prefill_pairs_attended"]) == 5 * (
            (1 + 2 + 3) * 1024 ** 2)


def test_prefill_through_the_kernel_serves_the_blocks_logits(monkeypatch):
    """``glm_dsa.prefill`` with the heads at the published widths (192 + 64
    beside 256), rows of 1,024 and 600 in a bucket of 1,024, a selection of
    512: the kernel under the selection as its keep mask (interpreter, tiles
    of 256), in all three layers of ``WIDE``, gives the blocked form's logits at every
    real position, whatever the padding holds."""
    params, policy = make(WIDE)
    n, lengths = 1024, jnp.array([1024, 600])
    toks = jax.random.randint(jax.random.key(1), (2, n), 1, WIDE.vocab_size)
    at = jnp.broadcast_to(jnp.arange(0, n, 8), (2, n // 8))

    def lowered():
        prefill = fresh(gm.prefill)

        def run(tokens):
            with jax.default_matmul_precision("highest"), \
                    record_lowerings() as chosen:
                logits, _, stats = prefill(params, tokens, lengths, WIDE,
                                           policy, logit_positions=at)
            return logits, stats, chosen

        return run

    want, blocked, chosen = lowered()(toks)
    assert chosen["gqa_prefill"] == {"xla"}
    force_prefill_kernel(monkeypatch)
    run = lowered()
    got, stats, chosen = run(toks)
    assert chosen["gqa_prefill"] == {"pallas"}
    junk, _, _ = run(jnp.where(jnp.arange(n)[None] < lengths[:, None],
                               toks, 5))
    for row, length in enumerate(lengths.tolist()):
        real = np.asarray(at[row]) < length
        assert float(jnp.abs(got[row, real] - want[row, real]).max()) < 2e-4
        np.testing.assert_array_equal(np.asarray(got[row, real]),
                                      np.asarray(junk[row, real]))
    assert float(want.std()) > 0.3
    # tiles of 256: ten under the diagonal of 1,024 rows, six of 600
    assert float(stats["dsa.prefill_pairs_attended"]) == (
        WIDE_LAYERS * 16 * 256 ** 2)
    for name in ("dsa.prefill_pairs_scored", "dsa.prefill_pairs_selected"):
        assert float(stats[name]) == float(blocked[name]) > 0
    assert WIDE_TOP_K < 600
