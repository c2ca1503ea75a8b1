"""Ling-3.0-flash's expert layer as one chip's share: over all
expert-parallel ranks the routed shares add up to the uncut layer's routed
experts — the shared expert, which every chip computes alike, counted once —
under the router's GROUP LIMIT, where a rank may hold none of a token's kept
groups; the router is 512 wide (here 16) whatever is held, keeps 2 of its 4
groups and weighs its top-k to ``routed_scaling_factor``; the layer's limit
clips both expert forms."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_ling3 as ref
from progen_tpu.models import bailing_hybrid as bh
from tests.bailing_hybrid_tiny import TINY, as_dict, make
from tests.families import jitted, reference

TOKENS = 40
LAYER = 5               # published layer 11: limits 1.0 and 2.0
LIMITS = TINY.limits(LAYER)


def _layer_and_input():
    params, _ = make()
    u = jax.random.normal(jax.random.key(11), (TOKENS, TINY.hidden_size))
    return params["layers"][LAYER], u


def _share(layer, config, first, held):
    cut = dataclasses.replace(config, first_expert=first, experts_held=held)
    experts = {k: v[first:first + held] for k, v in layer["experts"].items()}
    return cut, {**layer, "experts": experts}


@pytest.mark.parametrize("ranks", [1, 4])
def test_shares_over_all_ranks_sum_to_the_uncut_layer(ranks):
    """The four shares' results (and the one rank that holds everything),
    the shared expert counted ONCE, add up to the uncut layer under the
    layer's limits; a rank is one of the router's four groups, so for half
    the tokens it holds no kept group and adds nothing."""
    layer, u = _layer_and_input()
    live = jnp.ones((TOKENS,), bool)
    held = TINY.num_experts // ranks
    assert LIMITS == (1.0, 2.0)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference(ref, TINY, "moe", limits=LIMITS)(u, layer)
        shared = jitted(bh.swiglu)(u, layer["shared"], limit=LIMITS[1])
        total, idle = shared, 0                     # once, not once a rank
        for rank in range(ranks):
            cut, part = _share(layer, TINY, rank * held, held)
            y, _, stats = jitted(bh.moe_share)(u, part, cut, live,
                                               limit=LIMITS[0])
            total = total + y
            idle += int(jnp.sum(jnp.abs(y).max(axis=-1) == 0))
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert float(jnp.abs(total + (ranks - 1) * shared - whole).max()) > (
        1e-2 if ranks > 1 else -1)
    if ranks > 1:       # 2 of 4 groups kept: two ranks or more idle a token
        assert 2 * TOKENS <= idle < 3 * TOKENS


@pytest.mark.parametrize("first,held", [(0, 16), (0, 4), (5, 4), (12, 4)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        got, ids, stats = jitted(bh.moe_share)(u, part, cut, live,
                                               limit=LIMITS[0])
        _, all_ids, _ = jitted(bh.moe_share)(u, layer, TINY, live,
                                             limit=LIMITS[0])
        want, want_ids = ref.routed(u, part, as_dict(cut), LIMITS[0])
        _, weights = jitted(bh.route)(u, layer["router"], TINY)
    np.testing.assert_array_equal(ids, all_ids)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_allclose(weights.sum(-1), TINY.routed_scaling_factor,
                               rtol=1e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=16)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])


def test_the_group_limit_keeps_two_of_four_and_changes_the_choice():
    """Every token's three experts lie in two of the four groups of four —
    the two whose two largest biased scores sum highest —, and for some
    token that is not the plain top-3 of 16."""
    layer, u = _layer_and_input()
    with jax.default_matmul_precision("highest"):
        ids, _ = jitted(bh.route)(u, layer["router"], TINY)
        plain, _ = jitted(bh.route)(u, layer["router"], dataclasses.replace(
            TINY, n_group=1, topk_group=1))
        s = ref.sigmoid(ref.product("th,he->te", u, layer["router"]["w"]))
    c = np.asarray(s + layer["router"]["bias"])
    top2 = np.sort(c.reshape(TOKENS, 4, 4), axis=-1)[..., 2:].sum(-1)
    best = np.argsort(-top2, axis=-1)[:, :2]
    groups = np.asarray(ids) // 4
    for t in range(TOKENS):
        assert set(groups[t]) <= set(best[t]), t
    assert (np.sort(ids, -1) != np.sort(plain, -1)).any()


def test_the_limit_binds_on_both_expert_forms():
    """The clip moves the routed and the shared expert's output, and with
    the limit past every product it does not."""
    layer, u = _layer_and_input()
    live = jnp.ones((TOKENS,), bool)
    a = u @ layer["experts"]["wg"][0]
    assert float(jnp.mean(a > LIMITS[0])) > 0.01        # it binds
    clipped, _, _ = jitted(bh.moe_share)(u, layer, TINY, live,
                                         limit=LIMITS[0])
    plain, _, _ = jitted(bh.moe_share)(u, layer, TINY, live)
    far, _, _ = jitted(bh.moe_share)(u, layer, TINY, live, limit=1e4)
    assert float(jnp.abs(clipped - plain).max()) > 1e-2
    np.testing.assert_allclose(far, plain, atol=1e-6)
    shared = jitted(bh.swiglu)(u, layer["shared"], limit=LIMITS[1])
    assert float(jnp.abs(shared - bh.swiglu(u, layer["shared"])).max()) > 1e-2
    np.testing.assert_allclose(shared, ref.swiglu(u, layer["shared"],
                                                  LIMITS[1]), atol=5e-5)


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=14, experts_held=4)
