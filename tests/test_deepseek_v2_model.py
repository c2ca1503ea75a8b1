"""DeepSeek-V2 (``progen_tpu/models/deepseek_v2.py``) against the plain
reference (``perf/lib/reference_deepseek_v2.py``: float32, no cache, the
non-absorbed attention, routing by reshape / max / top-k, a dense loop over
the experts): prefill then decode through the latent cache with a leading
dense layer and two expert layers, the group-limited router against a NumPy
transcription, the YaRN table and the softmax scale against their closed
forms, the experts' window."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_deepseek_v2 as ref
from progen_tpu.models import deepseek_v2 as ds
from progen_tpu.models import experts, latent
from progen_tpu.models.longcat import LongCatConfig
from tests.families import jitted, reference
from tests.deepseek_v2_tiny import TINY, as_dict, make

T, PRIME, MAX_LEN = 24, 10, 32


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


def _served_logits(params, policy, toks, config=TINY):
    """Logits of every position from ``PRIME - 1`` on: the prefill's last
    position, then one decode step per token through the cache."""
    rows = toks.shape[0]
    first, rows_latent, _ = jitted(ds.prefill)(
        params, toks[:, :16], jnp.full((rows,), PRIME), config, policy)
    caches = {k: jnp.pad(v, ((0, 0), (0, MAX_LEN - 16), (0, 0)))
              for k, v in rows_latent.items()}
    live = jnp.ones((rows,), bool)
    out = [first[:, 0]]
    for t in range(PRIME, T):
        logits, caches, _ = jitted(ds.decode_step)(
            params, toks[:, t], jnp.full((rows,), t), caches, live, config,
            policy)
        out.append(logits)
    return jnp.stack(out, axis=1)


def test_the_tiny_model_has_every_kind_of_layer():
    params, _ = make()
    kinds = ["ffn" in layer for layer in params["layers"]]
    assert kinds == [True, False, False]
    assert ds.cache_names(TINY) == ["l0", "l1", "l2"]
    assert params["layers"][1]["shared"]["wg"].shape == (32, 2 * 16)
    assert params["layers"][1]["experts"]["wg"].shape == (16, 32, 16)
    assert params["layers"][1]["router"]["w"].shape == (32, 16)


def test_prefill_logits_match_the_reference_at_every_position():
    params, policy = make()
    toks = _tokens()
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        got, rows, stats = jitted(ds.prefill)(
            params, toks, jnp.array([T, 13]), TINY, policy,
            logit_positions=pos)
        junk = toks.at[1, 13:].set(5)
        again, _, _ = jitted(ds.prefill)(
            params, junk, jnp.array([T, 13]), TINY, policy,
            logit_positions=pos)
    assert float(jnp.abs(got[0] - want[0]).max()) < 2e-5
    assert float(jnp.abs(got[1, :13] - want[1, :13]).max()) < 2e-5
    np.testing.assert_array_equal(got[1, :13], again[1, :13])
    assert float(want.std()) > 0.3              # not a vacuous bound
    # only real tokens are counted, once per EXPERT layer; one latent block
    # a layer, the dense one included
    assert float(stats["moe.tokens"]) == 2 * (T + 13)
    assert sorted(rows) == ["l0", "l1", "l2"]
    assert rows["l0"].shape == (2, T, TINY.latent_width)


@pytest.mark.parametrize("mixed,tol", [(False, 2e-5), (True, 0.3)],
                         ids=["float32", "bf16-params-and-compute"])
def test_prefill_then_decode_matches_the_reference(mixed, tol):
    params, policy = make(mixed=mixed)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)[:, PRIME - 1:]
        got = _served_logits(params, policy, toks)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < tol


def test_decode_counts_rows_context_layers_and_touched_experts():
    params, policy = make()
    toks = _tokens()
    caches = latent.LatentFamily.init_caches(
        ds.DeepSeekV2Family(TINY, policy), 2, MAX_LEN)
    live = jnp.array([True, False])
    _, _, stats, chosen = jitted(ds.decode_step)(
        params, toks[:, 0], jnp.array([0, 0]), caches, live, TINY, policy,
        with_choices=True)
    assert chosen.shape == (2, 2, TINY.num_experts_per_tok)
    assert float(stats["moe.decode_layers"]) == 2      # the expert layers
    assert float(stats["mla.decode_rows"]) == 1
    assert float(stats["mla.context_tokens"]) == 1
    assert float(stats["moe.tokens"]) == 2
    assert float(stats["moe.held_load"].sum()) == 2 * 3
    assert float(stats["moe.experts_touched"]) == 2 * 3
    # all 16 experts held: each of a token's topk_group groups is a held one
    assert float(stats["moe.held_groups_chosen"]) == 2 * TINY.topk_group


# ------------------------------------------------------------ the router


def _numpy_route(u, w, c):
    """The release's ``group_limited_greedy`` transcribed with NumPy."""
    logits = u.astype(np.float64) @ w.astype(np.float64)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    scores = e / e.sum(-1, keepdims=True)
    t = scores.shape[0]
    group_scores = scores.reshape(t, c.n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=-1, kind="stable")[
        :, :c.topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1, axis=-1)
    score_mask = np.repeat(group_mask, c.n_routed_experts // c.n_group, -1)
    tmp = np.where(score_mask > 0, scores, 0.0)
    ids = np.argsort(-tmp, axis=-1, kind="stable")[:, :c.num_experts_per_tok]
    weights = np.take_along_axis(scores, ids, -1) * c.routed_scaling_factor
    return ids, weights, group_idx


@pytest.mark.parametrize("topk_group", [1, 2, 4])
def test_group_limited_router_against_numpy(topk_group):
    c = dataclasses.replace(TINY, topk_group=topk_group)
    params, _ = make()
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(5), (64, c.hidden_size))
    with jax.default_matmul_precision("highest"):
        ids, w, kept = ds.route(u, router, c)
        ref_ids, ref_w = ref.route(u, router, as_dict(c))
    want_ids, want_w, want_groups = _numpy_route(
        np.asarray(u), np.asarray(router["w"]), c)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_array_equal(np.sort(ref_ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1), rtol=1e-5)
    np.testing.assert_allclose(np.sort(ref_w, -1), np.sort(want_w, -1),
                               rtol=1e-5)
    size = c.n_routed_experts // c.n_group
    groups_used = [set((row // size).tolist()) for row in np.asarray(ids)]
    assert all(len(g) <= topk_group for g in groups_used)
    assert int(kept.sum(-1).max()) == int(kept.sum(-1).min()) == topk_group
    for row, groups in zip(np.asarray(kept), want_groups):
        assert set(np.flatnonzero(row)) == set(groups.tolist())
    # weights are 16 p of the UNMASKED softmax, not renormalised
    probs = jax.nn.softmax(u @ router["w"], axis=-1)
    np.testing.assert_allclose(
        w, c.routed_scaling_factor * jnp.take_along_axis(probs, ids, -1),
        rtol=1e-5)
    if topk_group == c.n_group:          # every group kept: plain top-k
        _, plain = jax.lax.top_k(probs, c.num_experts_per_tok)
        np.testing.assert_array_equal(np.sort(ids, -1), np.sort(plain, -1))
    else:                                # the limit changes some choice
        _, plain = jax.lax.top_k(probs, c.num_experts_per_tok)
        assert bool((jnp.sort(ids, -1) != jnp.sort(plain, -1)).any())


# ----------------------------------------------- YaRN and the softmax scale


def test_yarn_table_and_softmax_scale_at_the_published_keys():
    c = ds.DeepSeekV2Config()
    assert c.yarn_bounds(64) == (10, 23)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert c.q_gain == pytest.approx(1.58963, abs=1e-5) and c.kv_gain == 1
    i = np.arange(32)
    f = 10000.0 ** (-2 * i / 64)
    r = np.clip((i - 10) / 13, 0, 1)
    want = f / 40 * r + f * (1 - r)
    got = np.asarray(c.rope_inv_freq(64))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)     # untouched
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(64, 10000.0, as_dict(c)["rope_scaling"]), want,
        rtol=1e-12)


def test_the_config_reads_the_published_rope_scaling_group():
    group = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
             "mscale_all_dim": 0.707,
             "original_max_position_embeddings": 4096, "type": "yarn"}
    c = ds.DeepSeekV2Config.from_dict({"rope_scaling": group, "unknown": 1})
    assert c.rope_scaling == ds.YarnScaling() and hash(c) is not None
    with pytest.raises(ValueError, match="rope_scaling type"):
        ds.DeepSeekV2Config.from_dict({"rope_scaling": {"type": "linear"}})
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=12, experts_held=8)


def test_the_softmax_scale_rides_on_the_query():
    """``q_gain`` is the one place ``m^2`` enters: attention with the gain
    equals attention without it on scores scaled by ``m^2`` (here: on a
    query weight scaled by it)."""
    params, _ = make()
    p = params["layers"][0]["attn"]
    x = jax.random.normal(jax.random.key(3), (2, T, TINY.hidden_size))
    plain = dataclasses.replace(
        TINY, rope_scaling=dataclasses.replace(TINY.rope_scaling, factor=1.0))
    assert plain.q_gain == 1 and TINY.q_gain > 1.5
    with jax.default_matmul_precision("highest"):
        want, _ = latent.mla_prefill(x, p, TINY)
        cache = jnp.zeros((2, MAX_LEN, TINY.latent_width))
        step = jax.jit(latent.mla_decode, static_argnums=4)
        for t in range(T):
            got, cache = step(x[:, t], jnp.full((2,), t), cache, p, TINY)
            assert float(jnp.abs(got - want[:, t]).max()) < 1e-5
        scaled = {**p, "wqb": p["wqb"] * TINY.q_gain}
        by_weight, _ = latent.mla_prefill(x, scaled, _Gainless(TINY))
    np.testing.assert_allclose(by_weight, want, atol=1e-5)


class _Gainless:
    """``TINY`` with the gain taken off (its table kept)."""

    def __init__(self, c):
        self._c = c

    q_gain = 1.0

    def __getattr__(self, name):
        return getattr(self._c, name)


# ------------------------------------------------------- the experts' window


def test_the_window_follows_the_expected_load():
    longcat = LongCatConfig(experts_held=16)          # 0.25 a token
    assert experts.moe_capacity(longcat, 8192) == 4096
    assert experts.moe_capacity(longcat, 32) == 128
    assert experts.moe_capacity(longcat, 4) == 4 * 12
    share = ds.DeepSeekV2Config(experts_held=40)       # 1.5 a token
    assert experts.moe_capacity(share, 64) == 256
    assert experts.moe_capacity(share, 4096) == 3 * 4096
    assert experts.moe_capacity(share, 8) == 8 * 6
    whole = ds.DeepSeekV2Config()                      # 6 a token: all
    assert experts.moe_capacity(whole, 4096) == 6 * 4096


@pytest.mark.parametrize("held", [2, 4])
def test_no_assignment_is_dropped_under_a_skewed_router(held):
    """Every token routed to the held experts' groups (a router whose held
    columns win by 6 logits): the default window, sized for the EXPECTED
    load, overflows and runs again; the result is the one window's that
    holds every assignment, and the reference's."""
    c = dataclasses.replace(TINY, experts_held=held)
    params, _ = make()
    layer = dict(params["layers"][1])
    u = jax.random.normal(jax.random.key(6), (600, c.hidden_size))
    u = u.at[:, 0].set(6.0)
    layer["router"] = {"w": layer["router"]["w"].at[0, :held].add(1.0)}
    layer["experts"] = {k: v[:held] for k, v in layer["experts"].items()}
    live = jnp.arange(600) % 7 != 0
    with jax.default_matmul_precision("highest"):
        ids, w, _ = ds.route(u, layer["router"], c)
        small, l1 = experts.held_experts(u, ids, w, live, layer["experts"], c)
        whole, l2 = experts.held_experts(u, ids, w, live, layer["experts"], c,
                                         capacity=600 * c.moe_topk)
        want, _ = ref.routed(u, layer["router"], layer["experts"], as_dict(c))
    cap = experts.moe_capacity(c, 600)
    assert float(l1.sum()) > cap            # more than one window
    np.testing.assert_array_equal(l1, l2)
    np.testing.assert_allclose(small[live], whole[live], atol=2e-5)
    np.testing.assert_allclose(small[live], want[live], atol=2e-4, rtol=1e-4)
    assert float(jnp.abs(small[~live]).max()) == 0
