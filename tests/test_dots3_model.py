"""dots3 (``progen_tpu/models/dots3.py``) against the plain reference
(``perf/lib/reference_dots3.py``: float32, no cache, no ring, the window and
the indexer's selection as a dense mask scattered from its own top-k): the
forward over a stack with both latent shapes, unequal right-padded rows
prefilled and then decoded past ``index_topk`` (keys are dropped) and past
the window (the ring wraps), the selected sets equal to the reference's,
each omission the reference can plant failing the tolerance the program
keeps, the two kinds of cache, the counters and byte gauges, and
``ops/dsa.py``'s cores against a dense softmax, and which lowering an
admission's core takes: the flash kernel under the selection as its keep mask
where ``ops/mla_prefill.py`` says it applies, the masked blocks elsewhere."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_dots3 as ref
from progen_tpu.models import dots3 as dm
from progen_tpu.models import latent
from progen_tpu.ops import dsa, mla_prefill
from progen_tpu.ops.lowering import record_lowerings
from tests.families import fresh, jitted
from tests.dots3_tiny import (TINY, TOP_K, WIDE, WIDE_TOP_K, WINDOW, as_dict,
                              force_prefill_kernel, make)

T, MAX_LEN = 32, 48
# float32 end to end against float32 ``highest``: what is left is the order
# of sums (the absorbed form, the one division after the value product)
TOL = 5e-5


def _published():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "perf", "configs",
                        "dots3-note-prev-ep8.json")
    with open(path) as f:
        return dm.Dots3Config.from_dict(json.load(f))


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


@functools.partial(jax.jit, static_argnames=("policy", "everywhere"))
def _prefill(params, toks, lengths, policy, everywhere=False):
    pos = (jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
           if everywhere else None)
    with jax.default_matmul_precision("highest"):
        return dm.prefill(params, toks, lengths, TINY, policy,
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
    assert TINY.layer_types == (dm.FULL, dm.FULL, dm.SLIDING, dm.SLIDING)
    assert ["ffn" in layer for layer in params["layers"]] == [
        True, False, False, False]
    blocks = dm.blocks_of(TINY)
    assert [(b.indexer, b.window) for b in blocks.values()] == [
        (True, None), (True, None), (False, WINDOW), (False, WINDOW)]
    assert blocks["l0"] is blocks["l1"] and blocks["l2"] is blocks["l3"]
    full, sliding = params["layers"][1]["attn"], params["layers"][2]["attn"]
    assert full["wqb"].shape == (24, 4 * 12) and full["wkva"].shape == (64, 20)
    assert sliding["wqb"].shape == (16, 2 * 16)
    assert sliding["wkva"].shape == (64, 28)
    assert full["wgate"].shape == (64, 4) and sliding["wgate"].shape == (64, 2)
    assert full["wiq"].shape == (24, 16 * 8) and full["wik"].shape == (64, 8)
    assert full["wiw"].shape == (64, 16) and "wiq" not in sliding
    assert "shared" in params["layers"][1]
    # the published layout: 13 full and 33 sliding, the first five F F s s s
    whole = dm.Dots3Config()
    assert whole.layer_types.count(dm.FULL) == 13
    assert whole.layer_types[:6] == (dm.FULL, dm.FULL, dm.SLIDING,
                                     dm.SLIDING, dm.SLIDING, dm.FULL)
    f, s = whole.shape_of(dm.FULL), whole.shape_of(dm.SLIDING)
    assert (f.latent_width, s.latent_width) == (576, 1088)
    assert abs(f.q_gain ** 2 - 5) < 1e-9 and abs(f.kv_gain ** 2 - 10) < 1e-9
    assert abs(s.kv_gain ** 2 - 5) < 1e-9 and s.index_topk == 0


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
    assert float(stats["moe.tokens"]) == 3 * (T + 21)
    assert rows["l0"]["latent"].shape == (2, T, 20)
    assert rows["l0"]["index"].shape == (2, T, 8)
    assert rows["l2"].shape == (2, T, 28)
    # four segments of 8 rows, the first without the indexer; two layers
    scored, attended = dsa.prefill_pairs(T, TOP_K)
    assert (scored, attended) == (8 * (16 + 24 + 32), 8 * (8 + 16 + 24 + 32))
    assert float(stats["dsa.prefill_pairs_scored"]) == 2 * 2 * scored
    assert float(stats["dsa.prefill_pairs_attended"]) == 2 * 2 * attended
    allowed = sum(min(t + 1, TOP_K) for n in (T, 21) for t in range(n))
    assert float(stats["dsa.prefill_pairs_selected"]) == 2 * allowed


@pytest.mark.parametrize("changed", [
    (("index_topk", 64),), (("index_topk", TOP_K // 2),),
    (("index_relu", False),), (("index_head_weights", False),),
    (("index_rotate_keys", False),), (("attention_gate_type", None),),
    (("swa_attention_gate_type", None),),
    (("apply_mla_qkv_lora_rescale", False),),
    (("sliding_window_size", WINDOW + 1),), (("swa_rope_theta", 1e5),),
    (("shared_expert", False),)],
    ids=lambda c: f"{c[0][0]}={c[0][1]}")
def test_each_omission_fails_the_tolerance_the_program_keeps(changed):
    """The reference with ONE of the family's choices left out or moved
    stands far from the reference as stated, where the program stands
    within ``TOL``: no selection, half of it, no ReLU, unweighted indexer
    heads, unrotated indexer keys, no gate on either kind, no rescale, a
    window one wider, the sliding layers at the full base, no shared
    expert."""
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
        logits, c, _ = dm.decode_step(p, t, ps, c, jnp.ones(t.shape, bool),
                                      TINY, policy)
    return logits, c, picked


def _served(params, policy, toks, primes, bucket):
    """Logits of every position from ``prime - 1`` on, a row — the
    prefill's last position, then a decode step a token through the caches
    — and each step's selections ``[(rows, kept)] x full layers``."""
    primes = jnp.asarray(primes)
    first, per_token, _ = _prefill(params, toks[:, :bucket], primes, policy)
    caches = jitted(dm.caches_from)(per_token, primes, TINY, MAX_LEN)
    step = functools.partial(_step, policy=policy)
    out, selections = [first[:, 0]], []
    for i in range(T - int(primes.max())):
        pos = primes + i
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        logits, caches, picked = step(params, tok, pos, caches)
        out.append(logits)
        selections.append(picked)
    return jnp.stack(out, axis=1), selections


@pytest.mark.parametrize("primes,bucket,mixed,tol", [
    ((1, WINDOW - 1), 8, False, TOL), ((WINDOW + 1, TOP_K), 8, False, TOL),
    ((13, TOP_K + 1), 16, False, TOL), ((WINDOW, TOP_K + 1), 16, True, 0.3)],
    ids=["one-token-and-under-the-window", "past-the-window-and-at-top-k",
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
        # the SETS the indexer selected, step by step and layer by layer,
        # are the reference's at float32
        for i, picked in enumerate(selections):
            at = prime + i
            for layer, (ids, kept) in enumerate(picked):
                mine = set(np.asarray(ids[row, :int(kept[row])]).tolist())
                theirs = set(np.flatnonzero(
                    np.asarray(selected[row, layer, at])).tolist())
                assert mine == theirs and len(mine) == min(at + 1, TOP_K)
    # every row passed top-k (keys were dropped) and wrapped its ring
    assert T - 1 - min(primes) > 2 * WINDOW and T > 2 * TOP_K


def test_a_slot_holds_two_latent_shapes_an_indexer_leaf_and_a_ring():
    _, policy = make()
    family = dm.Dots3Family(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    assert jax.tree.map(lambda a: a.shape, caches) == {
        "l0": {"latent": (3, MAX_LEN, 20), "index": (3, MAX_LEN, 8)},
        "l1": {"latent": (3, MAX_LEN, 20), "index": (3, MAX_LEN, 8)},
        "l2": (3, WINDOW, 28), "l3": (3, WINDOW, 28)}
    # the published shapes: a full layer's token 1,408 B, a ring 513 x 2,176 B
    whole = dm.blocks_of(dm.Dots3Config())
    shapes = jax.eval_shape(lambda: {
        n: whole[n].init_cache(1, 17408, jnp.bfloat16) for n in ("l0", "l2")})
    assert shapes["l0"]["latent"].shape == (1, 17408, 576)
    assert shapes["l0"]["index"].shape == (1, 17408, 128)
    assert shapes["l2"].shape == (1, 513, 1088)
    # a ring laid out from a prefill keeps the LAST token of each residue
    ring = family.blocks["l2"]
    rows = jnp.arange(2 * 12, dtype=jnp.float32).reshape(2, 12, 1)
    laid = ring.cache_rows(rows, jnp.array([12, 7]), MAX_LEN)[..., 0]
    assert laid[0].tolist() == [10, 11, 7, 8, 9]
    assert laid[1].tolist() == [12 + 5, 12 + 6, 12 + 2, 12 + 3, 12 + 4]


def test_decode_counts_contexts_selections_and_the_rows_each_core_reads():
    params, policy = make()
    family = dm.Dots3Family(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live = jnp.array([True, False, True])
    pos = jnp.array([2, 30, 20])
    _, _, stats = jax.jit(functools.partial(
        dm.decode_step, config=TINY, policy=policy))(
        params, jnp.array([4, 5, 6]), pos, caches, live)
    got = {k: float(v) for k, v in stats.items() if k != "moe.held_load"}
    assert got["mla.decode_rows"] == 2
    assert got["mla.context_tokens"] == got["dsa.context_tokens"] == 3 + 21
    assert got["dsa.keys_selected"] == 3 + TOP_K
    assert got["dsa.index_rows_read"] == 3 * MAX_LEN       # every slot's
    assert got["mla.cache_rows_read"] == 3 * TOP_K         # the XLA core's
    assert got["mla.window_tokens"] == 3 + WINDOW
    assert got["mla.window_rows_read"] == 3 * WINDOW
    assert got["moe.decode_layers"] == 3 and got["moe.tokens"] == 3 * 2
    gauges = family.publish(stats)
    assert gauges["dsa.index_bytes_read"] == 3 * MAX_LEN * 2 * 8 * 4
    assert gauges["mla.cache_bytes_read"] == 3 * TOP_K * 2 * 20 * 4
    assert gauges["mla.window_bytes_read"] == 3 * WINDOW * 2 * 28 * 4
    assert "attn.full_bytes_read" not in gauges


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = _published()
    assert c.num_hidden_layers == 5 and c.experts_held == 32
    assert c.layer_types == (dm.FULL, dm.FULL) + (dm.SLIDING,) * 3
    assert (c.index_topk, c.sliding_window_size) == (2048, 513)
    for changed in ({"scoring_func": "softmax"}, {"n_shared_experts": 2},
                    {"attention_gate_type": "elementwise"},
                    {"layer_types": ("full_attention",)},
                    {"first_expert": 250}):
        with pytest.raises(ValueError):
            dataclasses.replace(c, **changed)


# ------------------------------------------------------- ops/dsa.py's cores


def _dense_sparse_attention(q, k, v, scores, top_k):
    """A dense softmax under the causal mask and the selection, the set
    built from a full sort."""
    n = q.shape[1]
    causal = np.tril(np.ones((n, n), bool))
    masked = np.where(causal, scores, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")[..., :top_k]
    picked = np.zeros(masked.shape, bool)
    np.put_along_axis(picked, order, True, axis=-1)
    seen = causal & picked
    logits = np.einsum("rqhd,rhkd->rhqk", q, k) * q.shape[-1] ** -0.5
    logits = np.where(seen[:, None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("rhqk,rhkd->rqhd", p, v).reshape(q.shape[0], n, -1)


@pytest.mark.parametrize("n,top_k,block", [(24, 8, 128), (12, 5, 128),
                                           (32, 16, 8)],
                         ids=["segments", "one-segment", "blocks"])
def test_sparse_prefill_core_matches_a_dense_softmax(n, top_k, block,
                                                     monkeypatch):
    monkeypatch.setattr(dsa, "QUERY_BLOCK", block)
    assert dsa.segments(n, top_k) == {24: (8, 8), 12: (12, 12),
                                      32: (16, 8)}[n]
    ks = jax.random.split(jax.random.key(3), 8)
    r, h, nope, rot, vd, j, d = 2, 3, 6, 2, 5, 16, 4
    q_nope = jax.random.normal(ks[0], (r, n, h, nope))
    q_rope = jax.random.normal(ks[1], (r, n, h, rot))
    k_nope = jax.random.normal(ks[2], (r, h, n, nope))
    k_r = jax.random.normal(ks[3], (r, n, rot))
    v = jax.random.normal(ks[4], (r, h, n, vd))
    q_idx = jax.random.normal(ks[5], (r, n, j, d))
    w = jax.random.normal(ks[6], (r, n, j))
    k_idx = jax.random.normal(ks[7], (r, n, d))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(
            dsa.sparse_prefill_attention, top_k=top_k))(
            q_nope, q_rope, k_nope, k_r, v, q_idx, w, k_idx)
        scores = dsa.index_scores(q_idx, w, k_idx)
    want_scores = np.einsum("rnj,rnjt->rnt", w, np.maximum(np.einsum(
        "rnjd,rtd->rnjt", q_idx, k_idx), 0))
    np.testing.assert_allclose(scores, want_scores, atol=1e-5)
    k = np.concatenate([k_nope, np.broadcast_to(
        np.asarray(k_r)[:, None], (r, h, n, rot))], -1)
    want = _dense_sparse_attention(
        np.concatenate([q_nope, q_rope], -1), k, np.asarray(v),
        np.asarray(scores), top_k)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_select_rows_puts_the_kept_rows_first_and_gathers_them():
    ks = jax.random.split(jax.random.key(5), 5)
    s, t, j, d, top_k = 3, 24, 16, 4, 8
    q_idx = jax.random.normal(ks[0], (s, j, d))
    w = jax.random.normal(ks[1], (s, j))
    index = jax.random.normal(ks[2], (s, t, d))
    counts = jnp.array([3, 8, 20])
    select_rows = jax.jit(dsa.select_rows, static_argnums=4)
    rows, kept = select_rows(q_idx, w, index, counts, top_k)
    assert rows.shape == (s, top_k) and kept.tolist() == [3, 8, 8]
    scores = np.asarray(dsa.index_scores(q_idx[:, None], w[:, None],
                                         index)[:, 0])
    for i in range(s):
        n = int(counts[i])
        best = np.argsort(-scores[i, :n], kind="stable")[:top_k]
        assert rows[i, :int(kept[i])].tolist() == best.tolist()
    # the sparse core over the gathered rows is the dense core over the set
    q_cat = jax.random.normal(ks[3], (s, 2, 6))
    cache = jax.random.normal(ks[4], (s, t, 6))
    got = jax.jit(dsa.sparse_decode_attention, static_argnums=(4, 5))(
        q_cat, cache, rows, kept, 4, 0.5)
    for i in range(s):
        rows_i = np.asarray(cache[i])[np.asarray(rows[i, :int(kept[i])])]
        logits = np.asarray(q_cat[i]) @ rows_i.T * 0.5
        p = np.exp(logits - logits.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows_i[:, :4]
        np.testing.assert_allclose(got[i], want, atol=2e-5)
    # a cache shorter than top_k keeps what there is
    rows, kept = select_rows(q_idx, w, index[:, :5], counts, top_k)
    assert rows.shape == (s, 5) and kept.tolist() == [3, 5, 5]


# ------------------------------------- which lowering an admission's core takes


@pytest.mark.parametrize("where,want", [
    ("on-a-tpu", "pallas"), ("cpu-default", "xla"),
    ("tiny-widths-on-a-tpu", "xla"), ("a-mesh-on-a-tpu", "xla")])
def test_a_full_layer_traces_one_kernel_call_where_the_kernel_applies(
        where, want, monkeypatch, devices8):
    """A full layer's admission, abstract operands, ``P`` 4,096 of one
    row: at the published widths on a TPU exactly ONE ``pallas_call``,
    ``mla_prefill_fwd``, no float32 ``(1, 128, 128, keys)`` score block in
    the text and the note ``"mla_prefill": "pallas"``; on the CPU, at the
    tiny widths and under a mesh today's masked blocks and no note."""
    import contextlib

    c, n = (TINY, 32) if where.startswith("tiny") else (_published(), 4096)
    policy = dm.bf16_policy()
    params = jax.eval_shape(lambda k: dm.init_params(c, k, policy),
                            jax.random.key(0))
    shape = c.shape_of(dm.FULL)
    if where != "cpu-default":
        monkeypatch.setattr(mla_prefill, "_on_tpu", lambda: True)
    mesh = (jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
            if where.startswith("a-mesh") else contextlib.nullcontext())
    x = jax.ShapeDtypeStruct((1, n, c.hidden_size), jnp.bfloat16)
    with mesh, record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda x, p, m: latent.mla_prefill(
            x, p, shape, m, indexer=True, gate=True)[0])(
            x, params["layers"][1]["attn"],
            jax.ShapeDtypeStruct((1,), jnp.int32)))
        got = dm.prefill_attention_stats(
            dm.blocks_of(c), n, jnp.array([n - n // 4 - 1]), jnp.bfloat16)
    heads = shape.num_attention_heads
    blocks = f"f32[1,{heads},{dsa.segments(n, c.index_topk)[1]},"
    if want == "pallas":
        assert chosen == {"mla_prefill": {"pallas"}, "dsa_kth": {"xla"}}
        assert jaxpr.count("pallas_call") == 1
        assert "name=mla_prefill_fwd" in jaxpr and blocks not in jaxpr
        assert f"i8[1,{n},{n}]" in jaxpr      # the mask, a byte a pair
    else:
        assert chosen == {"dsa_kth": {"xla"}}
        assert "pallas_call" not in jaxpr and blocks in jaxpr
    # the selection is the same text either way: its thresholds counted
    # (``ops/kth.py``), one loop of rounds a segment past the first, no
    # ``top_k``
    assert jaxpr.count("bitcast_convert_type[new_dtype=uint32]") == (
        n // c.index_topk - 1)
    assert "top_k[" not in jaxpr
    # a row of 3,071: three live query tiles of 1,024 under the kernel
    scored, attended = dsa.prefill_pairs(n, c.index_topk)
    assert float(got["dsa.prefill_pairs_scored"]) == 2 * scored
    assert float(got["dsa.prefill_pairs_attended"]) == 2 * (
        (1 + 2 + 3) * 1024 ** 2 if want == "pallas" else attended)


def test_prefill_through_the_kernel_serves_the_blocks_logits(monkeypatch):
    """``dots3.prefill`` with the full layers' heads at the published
    widths, rows of 1,024 and 600 in a bucket of 1,024, a selection of 512:
    the kernel under the selection as its keep mask (interpreter, tiles of
    256) gives the masked blocks' logits at every real position, whatever
    the padding holds; ``dsa.prefill_pairs_attended`` counts the tiles
    visited where the blocks count whole segments, ``_scored`` and
    ``_selected`` do not move."""
    params, policy = make(WIDE)
    n, lengths = 1024, jnp.array([1024, 600])
    toks = jax.random.randint(jax.random.key(1), (2, n), 1, WIDE.vocab_size)
    at = jnp.broadcast_to(jnp.arange(0, n, 8), (2, n // 8))

    def lowered():
        """``dm.prefill`` as ONE program, traced under what is patched NOW
        (a fresh function per lowering: ``jax.jit`` would keep the trace;
        eagerly the interpreter runs the kernel's grid op by op).  What a
        trace notes is in the first call's ``chosen`` alone."""
        prefill = fresh(dm.prefill)

        def run(tokens):
            with jax.default_matmul_precision("highest"), \
                    record_lowerings() as chosen:
                logits, _, stats = prefill(params, tokens, lengths, WIDE,
                                           policy, logit_positions=at)
            return logits, stats, chosen

        return run

    want, blocked, chosen = lowered()(toks)
    assert "mla_prefill" not in chosen
    force_prefill_kernel(monkeypatch)
    run = lowered()
    got, stats, chosen = run(toks)
    assert chosen["mla_prefill"] == {"pallas"}
    junk, _, _ = run(jnp.where(jnp.arange(n)[None] < lengths[:, None],
                               toks, 5))
    for row, length in enumerate(lengths.tolist()):
        real = np.asarray(at[row]) < length
        assert float(jnp.abs(got[row, real] - want[row, real]).max()) < 2e-4
        np.testing.assert_array_equal(np.asarray(got[row, real]),
                                      np.asarray(junk[row, real]))
    assert float(want.std()) > 0.3
    scored, attended = dsa.prefill_pairs(n, WIDE_TOP_K)
    assert attended == 512 * (512 + 1024)
    assert float(blocked["dsa.prefill_pairs_attended"]) == 2 * 2 * attended
    # tiles of 256: ten under the diagonal of 1,024 rows, six of 600
    visited = dsa.prefill_pairs(n, WIDE_TOP_K, "pallas", lengths)[1]
    assert visited.tolist() == [10 * 256 ** 2, 6 * 256 ** 2]
    assert float(stats["dsa.prefill_pairs_attended"]) == 2 * 16 * 256 ** 2
    for name in ("dsa.prefill_pairs_scored", "dsa.prefill_pairs_selected"):
        assert float(stats[name]) == float(blocked[name]) > 0
