"""DeepSeek-V2's expert layer as one chip's share: over all expert-parallel
ranks the routed parts, with the shared experts counted once, add up to the
uncut reference layer; the router is as wide, and its groups as many,
whatever is held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_deepseek_v2 as ref
from progen_tpu.models import deepseek_v2 as ds
from progen_tpu.models.latent import swiglu
from tests.families import jitted, reference
from tests.deepseek_v2_tiny import TINY, make

TOKENS = 40


def _layer_and_input():
    params, _ = make()
    u = jax.random.normal(jax.random.key(11), (TOKENS, TINY.hidden_size))
    return params["layers"][1], u


def _share(layer, config, first, held):
    cut = dataclasses.replace(config, first_expert=first, experts_held=held)
    experts = {k: v[first:first + held] for k, v in layer["experts"].items()}
    return cut, {**layer, "experts": experts}


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_shares_over_all_ranks_sum_to_the_uncut_layer(ranks):
    layer, u = _layer_and_input()
    live = jnp.ones((TOKENS,), bool)
    held = TINY.n_routed_experts // ranks
    with jax.default_matmul_precision("highest"):
        routed, _ = reference(ref, TINY, "routed")(u, layer["router"],
                                                   layer["experts"])
        whole = routed + ref.swiglu(u, layer["shared"])
        total = jnp.zeros_like(u)
        for rank in range(ranks):
            cut, part = _share(layer, TINY, rank * held, held)
            y, _, _ = jitted(ds.moe_share)(u, part, cut, live)
            total = total + y
        # every chip computes the shared experts alike: counted once
        shared = swiglu(u, layer["shared"], scope="moe.shared")
    np.testing.assert_allclose(total + shared, whole, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-3
    assert float(jnp.abs(routed).max()) > 1e-3


@pytest.mark.parametrize("first,held", [(0, 16), (0, 4), (4, 4), (6, 6),
                                        (12, 4)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        got, ids, stats = jitted(ds.moe_share)(u, part, cut, live)
        _, all_ids, _ = jitted(ds.moe_share)(u, layer, TINY, live)
        want, _ = reference(ref, cut, "routed")(u, part["router"],
                                                part["experts"])
    np.testing.assert_array_equal(ids, all_ids)
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=16)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])
    # held groups among each token's topk_group: a group counts as held if
    # any of its experts is
    size = TINY.n_routed_experts // TINY.n_group
    mine = set(range(first // size, (first + held - 1) // size + 1))
    _, _, kept = ds.route(u, layer["router"], TINY)
    want_groups = sum(len(mine & set(np.flatnonzero(row)))
                      for row in np.asarray(kept))
    assert float(stats["moe.held_groups_chosen"]) == want_groups
    if held == 16:
        assert want_groups == TOKENS * TINY.topk_group


def test_tokens_that_are_not_live_reach_no_expert_and_are_not_counted():
    layer, u = _layer_and_input()
    live = jnp.arange(TOKENS) < 25
    y, _, stats = jitted(ds.moe_share)(u, layer, TINY, live)
    assert float(jnp.abs(y[25:]).max()) == 0
    assert float(stats["moe.tokens"]) == 25
    assert float(stats["moe.held_load"].sum()) == 25 * 3
    assert float(stats["moe.held_groups_chosen"]) == 25 * TINY.topk_group
