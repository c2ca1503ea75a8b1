"""Trinity (``progen_tpu/models/trinity.py``) against the plain reference
(``perf/lib/reference_trinity.py``: float32, no cache, the window as a mask,
a dense loop over the experts): prefill over a stack with a dense layer and
sliding and full expert layers, prefill then decode through the rings and
the grown caches past a ring's wrap, the two cache layouts, the sigmoid
router against a NumPy transcription, the counters on a hand-sized batch,
the attention cores against a plain masked softmax."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_trinity as ref
from progen_tpu.models import experts
from progen_tpu.models import trinity as tr
from progen_tpu.ops import gqa
from tests.families import jitted, reference
from tests.trinity_tiny import TINY, WINDOW, as_dict, make

T, MAX_LEN = 40, 48


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


@jax.jit
def _reference(params, toks):
    """The reference's logits of every position, ONE program for the file
    (eagerly its blocked attention alone takes seconds a call)."""
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, toks, as_dict(TINY))


@functools.partial(jax.jit, static_argnames=("policy",))
def _prefill(params, toks, lengths, policy):
    """One program a shape and precision for the file."""
    with jax.default_matmul_precision("highest"):
        return tr.prefill(params, toks, lengths, TINY, policy)


def _served_logits(params, policy, toks, primes, bucket):
    """Logits of every position from ``prime - 1`` on, a row: the
    prefill's last position, then one decode step per token through the
    caches (rows of different primes step together, each at its own
    position)."""
    live = jnp.ones((toks.shape[0],), bool)
    primes = jnp.asarray(primes)
    first, per_token, _ = _prefill(params, toks[:, :bucket], primes, policy)
    caches = jitted(tr.caches_from)(per_token, primes, TINY, MAX_LEN)
    out = [first[:, 0]]
    for i in range(T - int(primes.max())):
        pos = primes + i
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        logits, caches, _ = jitted(tr.decode_step)(
            params, tok, pos, caches, live, TINY, policy)
        out.append(logits)
    return jnp.stack(out, axis=1)


def test_the_tiny_model_has_every_kind_of_layer():
    params, _ = make()
    assert TINY.layer_types == (tr.SLIDING,) * 3 + (tr.FULL, tr.SLIDING)
    assert ["ffn" in layer for layer in params["layers"]] == [
        True, False, False, False, False]
    blocks = tr.blocks_of(TINY)
    assert [blocks[f"l{i}"].window for i in range(5)] == [
        WINDOW, WINDOW, WINDOW, None, WINDOW]
    layer = params["layers"][1]
    assert layer["norm"].shape == (4, 32)
    assert layer["attn"]["wq"].shape == (32, 4 * 8)
    assert layer["attn"]["wk"].shape == (32, 2 * 8)
    assert layer["attn"]["wgate"].shape == (32, 4 * 8)
    assert layer["attn"]["q_norm"].shape == (8,)
    assert layer["shared"]["wg"].shape == (32, 16)
    assert layer["experts"]["wg"].shape == (8, 32, 16)
    assert layer["router"]["w"].shape == (32, 8)
    assert layer["router"]["bias"].dtype == jnp.float32
    assert TINY.embed_gain == math.sqrt(32)
    # the published layout: every 4th layer full, 2 leading dense layers
    whole = tr.TrinityConfig()
    assert whole.layer_types.count(tr.FULL) == 8
    assert whole.layer_types[:4] == (tr.SLIDING,) * 3 + (tr.FULL,)


def test_prefill_logits_match_the_reference_at_every_position():
    params, policy = make()
    toks = _tokens()
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        got, rows, stats = jitted(tr.prefill)(
            params, toks, jnp.array([T, 13]), TINY, policy,
            logit_positions=pos)
        junk = toks.at[1, 13:].set(5)
        again, _, _ = jitted(tr.prefill)(
            params, junk, jnp.array([T, 13]), TINY, policy,
            logit_positions=pos)
    assert float(jnp.abs(got[0] - want[0]).max()) < 5e-5
    assert float(jnp.abs(got[1, :13] - want[1, :13]).max()) < 5e-5
    np.testing.assert_array_equal(got[1, :13], again[1, :13])
    assert float(want.std()) > 0.3              # not a vacuous bound
    # only real tokens are counted, once per EXPERT layer; one block a
    # layer, the dense one included
    assert float(stats["moe.tokens"]) == 4 * (T + 13)
    assert sorted(rows) == ["l0", "l1", "l2", "l3", "l4"]
    assert rows["l0"]["k"].shape == (2, 2, T, 8)


def test_the_window_and_the_missing_rotation_change_the_logits():
    """The reference with the window lifted, or with the full block rotated
    as a sliding one, is another model: the agreement above is not that of
    two masks that never bite."""
    params, _ = make()
    toks = _tokens()
    cfg = as_dict(TINY)
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        wide, all_sliding = (
            jax.jit(lambda p, t: ref.forward(p, t, {**cfg, **other}))(
                params, toks)
            for other in ({"sliding_window": T},
                          {"sliding_window": T,
                           "layer_types": ["sliding_attention"] * 5}))
    np.testing.assert_allclose(want[:, :WINDOW], wide[:, :WINDOW], atol=1e-5)
    assert float(jnp.abs(want - wide)[:, WINDOW:].max()) > 0.05
    assert float(jnp.abs(wide - all_sliding).max()) > 0.05


@pytest.mark.parametrize("primes,bucket", [
    ((5, 3), 8), ((10, 8), 16), ((19, 26), 32), ((33, 7), 40)],
    ids=["wrap-in-decode", "wrapped-once-in-prefill",
         "wrapped-thrice-in-prefill", "mixed"])
@pytest.mark.parametrize("mixed,tol", [(False, 5e-5), (True, 0.3)],
                         ids=["float32", "bf16-params-and-compute"])
def test_prefill_then_decode_past_a_rings_wrap_matches_the_reference(
        primes, bucket, mixed, tol):
    params, policy = make(mixed=mixed)
    toks = _tokens()
    start = max(primes)
    want = _reference(params, toks)
    with jax.default_matmul_precision("highest"):
        got = _served_logits(params, policy, toks, primes, bucket)
    assert got.dtype == jnp.float32
    for row, prime in enumerate(primes):
        # step i of a row stands on position prime + i - 1
        steps = T - start + 1
        diff = jnp.abs(got[row] - want[row, prime - 1:prime - 1 + steps])
        # float32: every logit.  bfloat16 at a width of 32 flips one routing
        # in ten (near-ties of 8 sigmoids), and a flipped expert moves a
        # token's logits by 1: there the root mean square is held
        assert float(jnp.sqrt(jnp.mean(diff ** 2)) if mixed
                     else diff.max()) < tol
    assert T - min(primes) > WINDOW         # every row's rings wrapped


def test_a_slot_holds_a_ring_for_a_sliding_block_and_grown_keys_for_a_full():
    _, policy = make()
    family = tr.TrinityFamily(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    assert {n: c["k"].shape for n, c in caches.items()} == {
        **{f"l{i}": (3, 2, WINDOW, 8) for i in (0, 1, 2, 4)},
        "l3": (3, 2, MAX_LEN, 8)}
    assert caches["l0"]["v"].shape == caches["l0"]["k"].shape
    # an engine shorter than the window holds no more than it can reach
    assert family.init_caches(3, 6)["l0"]["k"].shape == (3, 2, 6, 8)
    ring, grown = family.blocks["l0"], family.blocks["l3"]
    pos = jnp.array([0, 7, 8, 21])
    at, counts = ring.place(pos, WINDOW)
    assert at.tolist() == [0, 7, 0, 5] and counts.tolist() == [1, 8, 8, 8]
    at, counts = grown.place(pos, MAX_LEN)
    assert at.tolist() == [0, 7, 8, 21] and counts.tolist() == [1, 8, 9, 22]


@pytest.mark.parametrize("length", [0, 1, 5, 8, 9, 21, 24])
def test_prefill_rows_land_in_the_ring_where_decode_would_write_them(length):
    _, policy = make()
    ring = tr.TrinityFamily(TINY, policy).blocks["l0"]
    # the per-token rows hold their own position, so a ring row says which
    # token it took
    per_token = jnp.broadcast_to(
        jnp.arange(24, dtype=jnp.float32)[None, None, :, None], (1, 2, 24, 8))
    rows = ring.cache_rows({"k": per_token, "v": per_token},
                           jnp.array([length]), MAX_LEN)
    got = np.asarray(rows["k"][0, 0, :, 0])
    assert rows["k"].shape == (1, 2, WINDOW, 8)
    for p in range(max(0, length - WINDOW), length):
        assert got[p % WINDOW] == p
    # a grown cache keeps every token where it is, padded to max_len
    grown = tr.KVBlock(TINY, None).cache_rows(
        {"k": per_token, "v": per_token}, jnp.array([length]), MAX_LEN)
    assert grown["v"].shape == (1, 2, MAX_LEN, 8)
    np.testing.assert_array_equal(grown["v"][0, 1, :24, 3], np.arange(24))


def test_decode_counts_rows_contexts_windows_and_cache_rows_read():
    params, policy = make()
    family = tr.TrinityFamily(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live = jnp.array([True, False, True])
    pos = jnp.array([2, 30, 20])
    _, _, stats, chosen = jitted(tr.decode_step)(
        params, jnp.array([4, 5, 6]), pos, caches, live, TINY, policy,
        with_choices=True)
    assert chosen.shape == (4, 3, TINY.num_experts_per_tok)
    assert float(stats["moe.decode_layers"]) == 4      # the expert layers
    assert float(stats["attn.decode_rows"]) == 2
    assert float(stats["attn.context_tokens"]) == 3 + 21
    assert float(stats["attn.window_tokens"]) == 3 + WINDOW
    # the XLA core reads every row of every slot: one block of each kind
    assert float(stats["attn.window_rows_read"]) == 3 * WINDOW
    assert float(stats["attn.full_rows_read"]) == 3 * MAX_LEN
    assert float(stats["moe.tokens"]) == 4 * 2
    assert float(stats["moe.held_load"].sum()) == 4 * 2 * 3
    assert 0 < float(stats["moe.experts_touched"]) <= 4 * 2 * 3
    # no live row: nothing is counted
    _, _, idle = jitted(tr.decode_step)(
        params, jnp.array([4, 5, 6]), pos, caches, jnp.zeros((3,), bool),
        TINY, policy)
    assert all(float(jnp.sum(v)) == 0 for v in idle.values())
    assert set(idle) == set(tr.STAT_KEYS)
    assert not set(tr.STAT_KEYS) & {"mla.decode_rows", "mla.context_tokens",
                                    "mla.cache_rows_read"}
    assert not [k for k in experts.STAT_KEYS if not k.startswith("moe.")]


# ------------------------------------------------------------ the router


def _numpy_route(u, router, c):
    """The release's router transcribed with NumPy: ``sigmoid``, the top-k
    of ``scores + bias``, the weights gathered from ``scores``."""
    logits = u.astype(np.float64) @ np.asarray(router["w"], np.float64)
    scores = 1 / (1 + np.exp(-logits))
    picked = scores + np.asarray(router["bias"], np.float64)
    ids = np.argsort(-picked, axis=-1, kind="stable")[
        :, :c.num_experts_per_tok]
    w = np.take_along_axis(scores, ids, -1)
    if c.route_norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids, w * c.route_scale


@pytest.mark.parametrize("route_norm", [True, False])
def test_sigmoid_router_against_numpy(route_norm):
    c = dataclasses.replace(TINY, route_norm=route_norm)
    params, _ = make()
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(5), (64, c.hidden_size))
    with jax.default_matmul_precision("highest"):
        ids, w = tr.route(u, router, c)
        ref_ids, ref_w = ref.route(u, router, as_dict(c))
    want_ids, want_w = _numpy_route(np.asarray(u), router, c)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_array_equal(np.sort(ref_ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1), rtol=1e-5)
    np.testing.assert_allclose(np.sort(ref_w, -1), np.sort(want_w, -1),
                               rtol=1e-5)
    if route_norm:      # the weights of a token sum to route_scale
        np.testing.assert_allclose(w.sum(-1), c.route_scale, rtol=1e-5)


def test_the_bias_picks_and_does_not_weigh():
    params, _ = make()
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(5), (64, TINY.hidden_size))
    unbiased = {**router, "bias": jnp.zeros_like(router["bias"])}
    pushed = {**router, "bias": router["bias"].at[2].add(10.0)}
    with jax.default_matmul_precision("highest"):
        ids, w = tr.route(u, router, TINY)
        plain_ids, _ = tr.route(u, unbiased, TINY)
        pushed_ids, pushed_w = tr.route(u, pushed, TINY)
        scores = jax.nn.sigmoid(u @ router["w"])
    # the seeded bias changes some token's choice ...
    assert bool((jnp.sort(ids, -1) != jnp.sort(plain_ids, -1)).any())
    # ... a large one forces its expert on every token ...
    assert bool((pushed_ids == 2).any(-1).all())
    # ... and no weight ever holds it: they are the chosen sigmoids,
    # normalised, whatever the bias
    for i, ww in ((ids, w), (pushed_ids, pushed_w)):
        s = jnp.take_along_axis(scores, i, -1)
        np.testing.assert_allclose(
            ww, TINY.route_scale * s / s.sum(-1, keepdims=True), rtol=1e-5)


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = tr.TrinityConfig.from_dict({
        "num_hidden_layers": 4, "layer_types": [tr.SLIDING] * 3 + [tr.FULL],
        "model_type": "afmoe", "unknown": 1})
    assert c.layer_types == (tr.SLIDING,) * 3 + (tr.FULL,)
    assert hash(c) is not None
    with pytest.raises(ValueError, match="layer_types"):
        tr.TrinityConfig(num_hidden_layers=3, layer_types=(tr.FULL,))
    with pytest.raises(ValueError, match="layer_types"):
        tr.TrinityConfig(num_hidden_layers=1, layer_types=("chunked",))
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=6, experts_held=4)
    with pytest.raises(ValueError, match="key/value heads"):
        dataclasses.replace(TINY, num_key_value_heads=3)
    for other in (dict(score_func="softmax"), dict(n_group=2),
                  dict(topk_group=2)):
        with pytest.raises(ValueError, match="sigmoid top-k"):
            dataclasses.replace(TINY, **other)
    assert dataclasses.replace(TINY, mup_enabled=False).embed_gain == 1


def test_the_experts_window_at_trinitys_share():
    share = tr.TrinityConfig(experts_held=16)        # 1.0 a token
    assert experts.moe_capacity(share, 64) == 128
    assert experts.moe_capacity(share, 4096) == 2 * 4096
    assert experts.moe_capacity(share, 8) == 8 * 8


# ------------------------------------------------------ the attention cores


def _plain_attention(q, k, v, scale, window):
    """One masked softmax over ``q (P, H, d)``, ``k, v (KV, P, d)``."""
    p, heads, _ = q.shape
    group = heads // k.shape[0]
    gap = np.arange(p)[:, None] - np.arange(p)[None, :]
    seen = (gap >= 0) if window is None else (gap >= 0) & (gap < window)
    out = []
    for h in range(heads):
        s = np.where(seen, q[:, h] @ k[h // group].T * scale, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        out.append(e / e.sum(-1, keepdims=True) @ v[h // group])
    return np.stack(out, axis=1).reshape(p, -1)


@pytest.mark.parametrize("window", [None, 5, 16, 700])
def test_prefill_core_against_a_plain_masked_softmax(window, monkeypatch):
    monkeypatch.setattr(gqa, "QUERY_BLOCK", 16)     # three blocks of 40 ...
    monkeypatch.setattr(gqa, "FULL_GROUP", 2)       # ... in two groups
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (2, 40, 4, 8))
    k = jax.random.normal(ks[1], (2, 2, 40, 8))
    v = jax.random.normal(ks[2], (2, 2, 40, 8))
    with jax.default_matmul_precision("highest"):
        got = gqa.prefill_attention(q, k, v, 0.3, window)
    for r in range(2):
        want = _plain_attention(*(np.asarray(a[r], np.float64)
                                  for a in (q, k, v)), 0.3, window)
        np.testing.assert_allclose(got[r], want, atol=1e-5)


def test_decode_core_reads_a_slots_rows_in_any_order_up_to_its_count():
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (3, 4, 8))
    k = jax.random.normal(ks[1], (3, 2, 12, 8))
    v = jax.random.normal(ks[2], (3, 2, 12, 8))
    counts = jnp.array([1, 7, 12])
    with jax.default_matmul_precision("highest"):
        got = gqa.decode_attention(q, k, v, counts, 0.3)
        order = jax.random.permutation(jax.random.key(4), 12)
        shuffled = gqa.decode_attention(q[2:], k[2:, :, order],
                                        v[2:, :, order], counts[2:], 0.3)
        junk = gqa.decode_attention(q, k.at[1, :, 7:].set(1e4),
                                    v.at[1, :, 7:].set(1e4), counts, 0.3)
    for s, n in enumerate(counts.tolist()):
        # the last of n tokens attending causally over all n
        qs = np.zeros((n, 4, 8))
        qs[-1] = q[s]
        want = _plain_attention(qs, np.asarray(k[s, :, :n], np.float64),
                                np.asarray(v[s, :, :n], np.float64), 0.3,
                                None)[-1]
        np.testing.assert_allclose(got[s], want, atol=1e-5)
    np.testing.assert_allclose(shuffled[0], got[2], atol=1e-5)
    np.testing.assert_array_equal(junk, got)
    assert float(gqa.rows_visited(k, counts, "xla")) == 3 * 12
