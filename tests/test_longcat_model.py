"""LongCat-Flash (``progen_tpu/models/longcat.py``) against the plain
reference (``perf/lib/reference_longcat.py``: float32, no cache, the
non-absorbed attention, a dense loop over the experts): prefill then decode
through the latent cache, absorbed against non-absorbed attention, the
identity experts and the router's bias."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_longcat as ref
from perf.tools.longcat_lowp import lowered
from progen_tpu.models import longcat as lc
from tests.families import jitted, reference
from tests.longcat_tiny import TINY, as_dict, make

T, PRIME, MAX_LEN = 24, 10, 32


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


def _served_logits(params, policy, toks, config=TINY):
    """Logits of every position from ``PRIME - 1`` on: the prefill's last
    position, then one decode step per token through the cache."""
    rows = toks.shape[0]
    first, latent, _ = jitted(lc.prefill)(
        params, toks[:, :16], jnp.full((rows,), PRIME), config, policy)
    caches = {k: jnp.pad(v, ((0, 0), (0, MAX_LEN - 16), (0, 0)))
              for k, v in latent.items()}
    live = jnp.ones((rows,), bool)
    out = [first[:, 0]]
    for t in range(PRIME, T):
        logits, caches, _ = jitted(lc.decode_step)(
            params, toks[:, t], jnp.full((rows,), t), caches, live, config,
            policy)
        out.append(logits)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("mixed,tol", [(False, 2e-5), (True, 0.25)],
                         ids=["float32", "bf16-params-and-compute"])
def test_prefill_then_decode_matches_the_reference(mixed, tol):
    params, policy = make(mixed=mixed)
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)[:, PRIME - 1:]
        got = _served_logits(params, policy, toks)
    assert got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < tol
    if not mixed:
        # not a vacuous bound: the logits spread by O(1)
        assert float(want.std()) > 0.3


def test_ragged_rows_and_padding_do_not_leak():
    """A row's logits do not depend on its padding or its neighbour."""
    params, policy = make()
    toks = _tokens()
    lengths = jnp.array([T, 13])
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        got, _, stats = jitted(lc.prefill)(params, toks, lengths, TINY,
                                           policy, logit_positions=pos)
        junk = toks.at[1, 13:].set(5)
        again, _, _ = jitted(lc.prefill)(params, junk, lengths, TINY, policy,
                                         logit_positions=pos)
    assert float(jnp.abs(got[0] - want[0]).max()) < 2e-5
    assert float(jnp.abs(got[1, :13] - want[1, :13]).max()) < 2e-5
    np.testing.assert_array_equal(got[1, :13], again[1, :13])
    # only real tokens are counted, once per layer
    assert float(stats["moe.tokens"]) == TINY.num_layers * (T + 13)


def test_absorbed_decode_equals_non_absorbed_attention():
    """One attention block alone: the absorbed step over the latent cache
    against the prefill form that expands keys and values."""
    params, _ = make()
    p = params["layers"][0]["attn"][1]
    x = jax.random.normal(jax.random.key(3), (2, T, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        want, latent = jax.jit(lc.mla_prefill, static_argnums=2)(x, p, TINY)
        cache = jnp.zeros((2, MAX_LEN, TINY.latent_width))
        step = jax.jit(lc.mla_decode, static_argnums=4)
        for t in range(T):
            got, cache = step(x[:, t], jnp.full((2,), t), cache, p, TINY)
            assert float(jnp.abs(got - want[:, t]).max()) < 1e-5
    np.testing.assert_allclose(cache[:, :T], latent, atol=1e-6)
    assert cache.shape[-1] == TINY.kv_lora_rank + TINY.qk_rope_head_dim


def test_identity_experts_are_chosen_and_weighted():
    """``y = sum_{real} 6 p_i E_i(x) + sum_{identity} 6 p_i x``: with the
    real experts' down-projections zeroed only the identity terms remain,
    weighted by the router's own probabilities (not renormalised)."""
    params, _ = make()
    layer = dict(params["layers"][0])
    layer["experts"] = {**layer["experts"],
                        "wd": jnp.zeros_like(layer["experts"]["wd"])}
    u = jax.random.normal(jax.random.key(4), (40, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        y, ids, _ = jitted(lc.moe_share)(u, layer, TINY,
                                         jnp.ones((40,), bool))
        probs = jax.nn.softmax(u @ layer["router"]["w"], axis=-1)
    identity = ids >= TINY.n_routed_experts
    assert bool(identity.any()) and bool((~identity).any())
    w = jnp.where(identity, jnp.take_along_axis(probs, ids, -1), 0).sum(-1)
    want = TINY.routed_scaling_factor * w[:, None] * u
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_router_chooses_by_p_plus_bias_and_weighs_by_p():
    """The bias moves the choice and never the weight."""
    params, _ = make()
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(5), (64, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        ids, w = jitted(lc.route)(u, router, TINY)
        free, _ = jitted(lc.route)(
            u, {**router, "bias": 0 * router["bias"]}, TINY)
        probs = jax.nn.softmax(u @ router["w"], axis=-1)
    assert bool((jnp.sort(ids, -1) != jnp.sort(free, -1)).any())
    want = TINY.routed_scaling_factor * jnp.take_along_axis(probs, ids, -1)
    np.testing.assert_allclose(w, want, rtol=1e-5)
    _, by_bias = jax.lax.top_k(probs + router["bias"], TINY.moe_topk)
    np.testing.assert_array_equal(jnp.sort(ids, -1), jnp.sort(by_bias, -1))


def test_grouped_product_drops_nothing_when_a_window_overflows():
    """Capacity 8 against ~3 assignments a token: many windows, the same
    result as one window that holds every assignment."""
    params, _ = make()
    layer = params["layers"][0]
    u = jax.random.normal(jax.random.key(6), (48, TINY.hidden_size))
    live = jnp.arange(48) % 5 != 0
    with jax.default_matmul_precision("highest"):
        ids, w = jitted(lc.route)(u, layer["router"], TINY)
        small, l1 = jitted(lc.held_experts)(
            u, ids, w, live, layer["experts"], TINY, capacity=8)
        whole, l2 = jitted(lc.held_experts)(
            u, ids, w, live, layer["experts"], TINY,
            capacity=48 * TINY.moe_topk)
    np.testing.assert_allclose(small[live], whole[live], atol=1e-5)
    np.testing.assert_array_equal(l1, l2)
    assert float(l1.sum()) > 8 * 3


def test_seeded_weights_spread_the_router_and_keep_activations_bounded():
    """What the issue asks of the seeded weights: router logits spread by
    O(1) a token, so the choice differs between tokens; activations stay
    O(1) through the stack; the default capacity rule never exceeds the
    assignments there are."""
    params, policy = make()
    toks = _tokens(seed=8)
    _, _, _, chosen = jitted(lc.prefill)(
        params, toks, jnp.full((2,), T), TINY, policy, with_choices=True)
    sets = {tuple(sorted(c.tolist())) for c in
            np.asarray(chosen[0]).reshape(-1, TINY.moe_topk)}
    assert len(sets) > 10
    logits, _, _ = jitted(lc.prefill)(params, toks, jnp.full((2,), T), TINY,
                                      policy)
    assert 0.1 < float(jnp.abs(logits).mean()) < 10
    full = lc.LongCatConfig(experts_held=16)
    assert lc.moe_capacity(full, 8192) == 4096
    assert lc.moe_capacity(full, 32) == 128
    assert lc.moe_capacity(full, 4) == 4 * 12


def test_the_reference_one_notch_below_is_further_off_than_the_program():
    """What the cell's limits rest on (perf/tools/longcat_lowp.py wraps the
    reference's named operations; the reference stays float32): with
    bfloat16 islands and float8 operands it errs more than the program in
    the configuration's own precision, and routes more tokens otherwise."""
    params, policy = make(mixed=True)
    toks = _tokens(seed=9)
    cfg = as_dict(TINY)

    def forward_row():
        """Traced anew at each call site: under ``lowered`` the reference's
        operations are other functions, and ``again`` below must be the
        program ``want`` was, compiled after the wrapping is undone."""
        return jax.jit(lambda p, row: ref.forward_row(p, row, cfg))(
            params, toks[0])

    with jax.default_matmul_precision("highest"):
        want, sets = forward_row()
        got = _served_logits(params, policy, toks)[0]
        with lowered(jnp.float8_e4m3fn):
            low, low_sets = forward_row()
        again, _ = forward_row()
    assert low.dtype == jnp.float32
    np.testing.assert_array_equal(again, want)    # the wrapping is undone
    program = float(jnp.abs(got - want[PRIME - 1:]).max())
    below = float(jnp.abs(low - want)[PRIME - 1:].max())
    assert below > 2 * program
    assert bool(jnp.any(jnp.sort(low_sets, -1) != jnp.sort(sets, -1)))
