"""GLM-5.2's expert layer as one chip's share: over all expert-parallel
ranks the routed shares add up to the uncut layer's routed experts — the
shared expert, which every chip computes alike, counted once —; the router is
256 wide (here 8) whatever is held, and its weights carry
``routed_scaling_factor`` 2.5."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_glm52 as ref
from progen_tpu.models import glm_dsa as gm
from tests.families import jitted, reference
from tests.glm_dsa_tiny import TINY, as_dict, make

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
        whole, _ = reference(ref, TINY, "routed")(u, layer)
        total = ref.swiglu(u, layer["shared"])      # once, not once a rank
        for rank in range(ranks):
            cut, part = _share(layer, TINY, rank * held, held)
            y, _, _ = jitted(gm.moe_share)(u, part, cut, live)
            total = total + y
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert float(jnp.abs(ref.swiglu(u, layer["shared"])).max()) > 1e-3


@pytest.mark.parametrize("first,held", [(0, 8), (0, 2), (3, 2), (6, 2)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        got, ids, stats = jitted(gm.moe_share)(u, part, cut, live)
        _, all_ids, _ = jitted(gm.moe_share)(u, layer, TINY, live)
        want, want_ids = ref.routed(u, part, {**as_dict(cut),
                                              "shared_expert": False})
        _, weights = jitted(gm.route)(u, layer["router"], TINY)
    np.testing.assert_array_equal(ids, all_ids)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the chosen weights sum to ``routed_scaling_factor``, not to one
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=8)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=6, experts_held=4)
