"""dots3's expert layer as one chip's share: over all expert-parallel ranks
the routed shares add up to the uncut layer's routed experts — the shared
expert, which every chip computes alike, counted once —; the router is 256
wide (here 8) whatever is held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_dots3 as ref
from progen_tpu.models import dots3 as dm
from tests.families import jitted, reference
from tests.dots3_tiny import TINY, as_dict, make

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
            y, _, _ = jitted(dm.moe_share)(u, part, cut, live)
            total = total + y
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(jnp.abs(ref.swiglu(u, layer["shared"])).max()) > 1e-3


@pytest.mark.parametrize("first,held", [(0, 8), (0, 2), (3, 2), (6, 2)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        got, ids, stats = jitted(dm.moe_share)(u, part, cut, live)
        _, all_ids, _ = jitted(dm.moe_share)(u, layer, TINY, live)
        want, want_ids = ref.routed(u, part, {**as_dict(cut),
                                              "shared_expert": False})
    np.testing.assert_array_equal(ids, all_ids)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=8)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=6, experts_held=4)
