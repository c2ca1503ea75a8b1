"""Trinity's expert layer as one chip's share: over all expert-parallel
ranks the routed parts, with the shared expert counted once, add up to the
uncut reference layer; the router is as wide whatever is held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_trinity as ref
from progen_tpu.models import trinity as tr
from progen_tpu.models.driver import swiglu
from tests.families import jitted, reference
from tests.trinity_tiny import TINY, make

TOKENS = 40


def _layer_and_input():
    params, _ = make()
    u = jax.random.normal(jax.random.key(11), (TOKENS, TINY.hidden_size))
    return params["layers"][2], u


def _share(layer, config, first, held):
    cut = dataclasses.replace(config, first_expert=first, experts_held=held)
    experts = {k: v[first:first + held] for k, v in layer["experts"].items()}
    return cut, {**layer, "experts": experts}


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_shares_over_all_ranks_sum_to_the_uncut_layer(ranks):
    layer, u = _layer_and_input()
    live = jnp.ones((TOKENS,), bool)
    held = TINY.num_experts // ranks
    with jax.default_matmul_precision("highest"):
        routed, _ = reference(ref, TINY, "routed")(u, layer["router"],
                                                   layer["experts"])
        whole = routed + ref.swiglu(u, layer["shared"])
        total = jnp.zeros_like(u)
        for rank in range(ranks):
            cut, part = _share(layer, TINY, rank * held, held)
            y, _, _ = jitted(tr.moe_share)(u, part, cut, live)
            total = total + y
        # every chip computes the shared expert alike: counted once
        shared = swiglu(u, layer["shared"], scope="moe.shared")
    np.testing.assert_allclose(total + shared, whole, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-3
    assert float(jnp.abs(routed).max()) > 1e-3


@pytest.mark.parametrize("first,held", [(0, 8), (0, 2), (2, 2), (3, 4),
                                        (6, 2)])
def test_routing_is_over_the_whole_router_whatever_is_held(first, held):
    layer, u = _layer_and_input()
    cut, part = _share(layer, TINY, first, held)
    live = jnp.ones((TOKENS,), bool)
    with jax.default_matmul_precision("highest"):
        got, ids, stats = jitted(tr.moe_share)(u, part, cut, live)
        _, all_ids, _ = jitted(tr.moe_share)(u, layer, TINY, live)
        want, _ = reference(ref, cut, "routed")(u, part["router"],
                                                part["experts"])
    np.testing.assert_array_equal(ids, all_ids)
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = np.bincount(np.asarray(ids).ravel(), minlength=8)
    np.testing.assert_array_equal(stats["moe.held_load"],
                                  counts[first:first + held])
    assert float(stats["moe.tokens"]) == TOKENS


def test_tokens_that_are_not_live_reach_no_expert_and_are_not_counted():
    layer, u = _layer_and_input()
    live = jnp.arange(TOKENS) < 25
    y, _, stats = jitted(tr.moe_share)(u, layer, TINY, live)
    assert float(jnp.abs(y[25:]).max()) == 0
    assert float(stats["moe.tokens"]) == 25
    assert float(stats["moe.held_load"].sum()) == 25 * 3


def test_a_whole_model_of_one_share_is_the_references_of_that_share():
    """Two of the eight experts held (one of four shares): the program and
    the reference leave the same terms out, before the post-MLP norm."""
    cut = dataclasses.replace(TINY, first_expert=2, experts_held=2)
    params, policy = make(cut)
    assert params["layers"][1]["experts"]["wg"].shape == (2, 32, 16)
    toks = jax.random.randint(jax.random.key(1), (1, 24), 1, TINY.vocab_size)
    pos = jnp.arange(24)[None]
    with jax.default_matmul_precision("highest"):
        want = reference(ref, cut)(params, toks)
        got, _, stats = jitted(tr.prefill)(params, toks, jnp.array([24]), cut,
                                           policy, logit_positions=pos)
    assert float(jnp.abs(got - want).max()) < 5e-5
    # 3 of 8 a token, 2 of 8 held: 0.75 assignments a token on average
    assert 0 < float(stats["moe.prefill_held"]) < 4 * 24 * 2
