"""The expert layer as one chip's share: over all expert-parallel ranks the
shares, with the identity terms counted once, add up to the uncut layer;
the router is 768 wide (here 12) whatever is held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_longcat as ref
from progen_tpu.models import longcat as lc
from tests.families import jitted, reference
from tests.longcat_tiny import TINY, make

TOKENS = 40


def _layer_and_input():
    params, _ = make()
    u = jax.random.normal(jax.random.key(11), (TOKENS, TINY.hidden_size))
    return params["layers"][0], u


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
        whole, _ = reference(ref, TINY, "moe")(u, layer["router"],
                                               layer["experts"])
        total = jnp.zeros_like(u)
        for rank in range(ranks):
            cut, part = _share(layer, TINY, rank * held, held)
            y, _, _ = jitted(lc.moe_share)(u, part, cut, live)
            total = total + y
        # every share carries all identity terms: keep one copy
        none, part = _share(layer, TINY, 0, 0)
        identity, _ = reference(ref, none, "moe")(u, part["router"],
                                                  part["experts"])
    np.testing.assert_allclose(total - (ranks - 1) * identity, whole,
                               atol=2e-5)
    assert float(jnp.abs(identity).max()) > 1e-3    # there were some


@pytest.mark.parametrize("first,held", [(0, 8), (0, 2), (3, 2), (6, 2)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        _, ids, stats = jitted(lc.moe_share)(u, part, cut, live)
        _, all_ids, _ = jitted(lc.moe_share)(u, layer, TINY, live)
        want, _ = reference(ref, cut, "moe")(u, part["router"],
                                             part["experts"])
        got, _, _ = jitted(lc.moe_share)(u, part, cut, live)
    np.testing.assert_array_equal(ids, all_ids)
    assert int(ids.max()) >= TINY.n_routed_experts      # identity chosen
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=12)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])
    assert float(stats["moe.real_chosen"]) == counts[:8].sum()


def test_a_share_outside_the_real_experts_is_refused():
    with pytest.raises(ValueError, match="real experts"):
        dataclasses.replace(TINY, first_expert=6, experts_held=4)
