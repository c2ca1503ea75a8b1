"""Qwen3-Next's expert layer as one chip's share: over all expert-parallel
ranks the routed shares add up to the uncut layer's routed experts — the
GATED shared expert, which every chip computes alike, counted once —; the
router is 512 wide (here 16) whatever is held and its top-k sum to 1."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_qwen3next as ref
from progen_tpu.models import qwen3_next as qn
from tests.families import jitted, reference
from tests.qwen3_next_tiny import TINY, as_dict, make

TOKENS = 40


def _layer_and_input():
    params, _ = make()
    u = jax.random.normal(jax.random.key(11), (TOKENS, TINY.hidden_size))
    return params["layers"][1], u


def _share(layer, config, first, held):
    cut = dataclasses.replace(config, first_expert=first, experts_held=held)
    experts = {k: v[first:first + held] for k, v in layer["experts"].items()}
    return cut, {**layer, "experts": experts}


@pytest.mark.parametrize("ranks", [1, 4])
def test_shares_over_all_ranks_sum_to_the_uncut_layer(ranks):
    """The four shares' results (and the one rank that holds everything),
    the gated shared expert counted ONCE, add up to the uncut layer; counted
    once a rank they would not."""
    layer, u = _layer_and_input()
    live = jnp.ones((TOKENS,), bool)
    held = TINY.num_experts // ranks
    with jax.default_matmul_precision("highest"):
        whole, _ = reference(ref, TINY, "moe")(u, layer)
        shared = jitted(qn.gated_shared)(u, layer)
        total = shared                              # once, not once a rank
        for rank in range(ranks):
            cut, part = _share(layer, TINY, rank * held, held)
            y, _, _ = jitted(qn.moe_share)(u, part, cut, live)
            total = total + y
    np.testing.assert_allclose(total, whole, atol=5e-5)
    np.testing.assert_allclose(shared, ref.gated_shared(u, layer), atol=5e-5)
    # the gate is a token's own and is not 1: ungated the layer is another
    ungated = qn.swiglu(u, layer["shared"])
    assert float(jnp.abs(shared - ungated).max()) > 1e-2
    assert float(jnp.abs(total + (ranks - 1) * shared - whole).max()) > (
        1e-2 if ranks > 1 else -1)


@pytest.mark.parametrize("first,held", [(0, 16), (0, 4), (5, 4), (12, 4)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        got, ids, stats = jitted(qn.moe_share)(u, part, cut, live)
        _, all_ids, _ = jitted(qn.moe_share)(u, layer, TINY, live)
        want, want_ids = ref.routed(u, part, as_dict(cut))
        _, weights = jitted(qn.route)(u, layer["router"], TINY)
    np.testing.assert_array_equal(ids, all_ids)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=16)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=14, experts_held=4)
