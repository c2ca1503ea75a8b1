"""Granite 4.0-H (``progen_tpu/models/granite_hybrid.py``) against the plain
reference (``perf/lib/reference_granite.py``: float32, no cache, no chunks,
the recurrence token by token): prefill over a stack of Mamba-2 layers and
one attention layer, prefill then decode through the carry, the convolution
tail and the grown keys, the state block's cache, the four multipliers, the
tied head, the counters on a hand-sized batch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_granite as ref
from progen_tpu.models import driver
from progen_tpu.models import granite_hybrid as gh
from progen_tpu.models import kv
from progen_tpu.ops.lowering import record_lowerings
from tests.families import fresh, jitted, reference
from tests.granite_tiny import CHUNK, TINY, make

T, MAX_LEN = 40, 48


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


def _served_logits(params, policy, toks, primes, bucket, config=TINY):
    """Logits of every position from ``prime - 1`` on, a row: the
    prefill's last position, then one decode step per token through the
    caches (rows of different primes step together, each at its own
    position)."""
    live = jnp.ones((toks.shape[0],), bool)
    primes = jnp.asarray(primes)
    first, handed, _ = jitted(gh.prefill)(params, toks[:, :bucket], primes,
                                          config, policy)
    caches = jitted(gh.caches_from)(handed, primes, config, MAX_LEN)
    out = [first[:, 0]]
    for i in range(T - int(primes.max())):
        pos = primes + i
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        logits, caches, _ = jitted(gh.decode_step)(
            params, tok, pos, caches, live, config, policy)
        out.append(logits)
    return jnp.stack(out, axis=1)


def test_the_tiny_model_has_every_kind_of_layer_and_a_tied_head():
    params, _ = make()
    assert TINY.layer_types.count(gh.ATTENTION) == 1
    assert TINY.mamba_chunk_size == CHUNK < T
    assert TINY.attention_multiplier != TINY.head_dim ** -0.5
    assert "head" not in params                 # the logits read the embedding
    assert params["embed"].shape == (TINY.vocab_size, TINY.hidden_size)
    mamba, attn = params["layers"][0], params["layers"][2]
    inner, channels = TINY.mamba_inner, TINY.conv_channels
    assert (inner, channels) == (128, 128 + 2 * 16)
    assert mamba["mixer"]["in_proj"].shape == (64, inner + channels + 4)
    assert mamba["mixer"]["conv_w"].shape == (channels, 4)
    assert mamba["mixer"]["out_proj"].shape == (inner, 64)
    for name in ("a_log", "dt_bias", "d"):      # the recurrence's own: float32
        assert mamba["mixer"][name].shape == (4,)
        assert mamba["mixer"][name].dtype == jnp.float32
    # the seeded steps and decays are the Mamba-2 authors' ranges
    step = jax.nn.softplus(mamba["mixer"]["dt_bias"])
    assert 0.001 <= float(step.min()) and float(step.max()) <= 0.1
    a = jnp.exp(mamba["mixer"]["a_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert attn["mixer"]["wq"].shape == (64, 4 * 16)
    assert attn["mixer"]["wk"].shape == (64, 2 * 16)
    assert mamba["ffn"]["wg"].shape == attn["ffn"]["wg"].shape == (64, 96)
    # the published layout: 36 state layers, attention at 5, 15, 25, 35
    whole = gh.GraniteHybridConfig()
    assert [i for i, k in enumerate(whole.layer_types)
            if k == gh.ATTENTION] == [5, 15, 25, 35]
    assert gh.mamba_layers(whole) == 36 and whole.conv_channels == 4352
    assert whole.head_dim == 64 and whole.attention_multiplier == 1 / 64


def test_prefill_logits_match_the_reference_at_every_position():
    params, policy = make()
    toks = _tokens()
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    lengths = jnp.array([T, 13])
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        got, handed, stats = jitted(gh.prefill)(
            params, toks, lengths, TINY, policy, logit_positions=pos)
        junk = toks.at[1, 13:].set(5)
        again, handed_again, _ = jitted(gh.prefill)(
            params, junk, lengths, TINY, policy, logit_positions=pos)
    assert float(jnp.abs(got[0] - want[0]).max()) < 5e-5
    assert float(jnp.abs(got[1, :13] - want[1, :13]).max()) < 5e-5
    assert float(want.std()) > 0.3              # not a vacuous bound
    # what stands after a row's true length reaches neither its logits nor
    # the state it hands over
    np.testing.assert_array_equal(got[1, :13], again[1, :13])
    for name in ("l0", "l5"):
        for leaf in ("ssm", "conv"):
            np.testing.assert_array_equal(handed[name][leaf][1],
                                          handed_again[name][leaf][1])
    # a state block hands over a state, an attention block rows per token
    assert sorted(handed) == [f"l{i}" for i in range(6)]
    assert handed["l0"]["ssm"].shape == (2, 4, 32, 16)
    assert handed["l0"]["ssm"].dtype == jnp.float32
    assert handed["l0"]["conv"].shape == (2, 3, TINY.conv_channels)
    assert handed["l2"]["k"].shape == (2, 2, T, 16)
    # real tokens a state layer, and the whole chunks computed for them
    assert float(stats["ssm.prefill_tokens"]) == 5 * (T + 13)
    assert float(stats["ssm.prefill_slots"]) == 5 * 2 * T
    assert float(stats["ssm.step_rows"]) == 0
    assert set(stats) == set(gh.STAT_KEYS)
    assert not [k for k in stats if k.startswith("moe.")]


@pytest.mark.parametrize("primes,bucket", [
    ((5, 2), 8), ((8, 9), 16), ((19, 26), 32), ((33, 1), 40)],
    ids=["below-a-chunk", "at-and-past-a-chunk", "across-chunks", "mixed"])
@pytest.mark.parametrize("mixed,tol", [(False, 5e-5), (True, 0.3)],
                         ids=["float32", "bf16-params-and-compute"])
def test_prefill_then_decode_through_the_carry_matches_the_reference(
        primes, bucket, mixed, tol):
    """The carry and the tail at each row's TRUE length, whatever the
    bucket, then up to 39 steps that fold one token each into them, beside
    the attention layer's grown keys."""
    params, policy = make(mixed=mixed)
    toks = _tokens()
    start = max(primes)
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        got = _served_logits(params, policy, toks, primes, bucket)
    assert got.dtype == jnp.float32
    for row, prime in enumerate(primes):
        # step i of a row stands on position prime + i - 1
        steps = T - start + 1
        diff = jnp.abs(got[row] - want[row, prime - 1:prime - 1 + steps])
        # float32: every logit.  bfloat16 parameters and products at a
        # width of 64 move a logit of spread 1.0 by up to 0.15 (8 bits of
        # mantissa through 6 layers and a norm that rescales the sum); a
        # wrong carry, tail or key moves it by the spread itself
        assert float(diff.max()) < tol
    assert float(want.std()) > 0.3


def test_the_carry_reaches_the_logits():
    """The reference with the carry dropped between tokens (``S_{t-1} = 0``:
    ``a`` very negative) is another model, so the agreement above is not
    that of a recurrence that remembers nothing."""
    params, _ = make()
    toks = _tokens()
    forgetful = dict(params, layers=[
        {**layer, "mixer": {**layer["mixer"], "a_log": layer["mixer"][
            "a_log"] + 20.0}} if "a_log" in layer["mixer"] else layer
        for layer in params["layers"]])
    with jax.default_matmul_precision("highest"):
        want = reference(ref, TINY)(params, toks)
        other = reference(ref, TINY)(forgetful, toks)
    np.testing.assert_allclose(want[:, 0], other[:, 0], atol=1e-5)
    assert float(jnp.abs(want - other)[:, 8:].max()) > 0.1


MULTIPLIERS = {"embedding_multiplier": 6.0, "residual_multiplier": 0.5,
               "attention_multiplier": 0.25, "logits_scaling": 2.0}


@pytest.mark.parametrize("name", list(MULTIPLIERS))
def test_each_multiplier_matters_and_is_the_references(name):
    """With another value the reference is another model, and the family
    under the same value is that model."""
    params, policy = make()
    toks = _tokens()
    other = dataclasses.replace(TINY, **{name: MULTIPLIERS[name]})
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    with jax.default_matmul_precision("highest"):
        base = reference(ref, TINY)(params, toks)
        want = reference(ref, other)(params, toks)
        got, _, _ = jitted(gh.prefill)(params, toks, jnp.array([T, T]), other,
                                       policy, logit_positions=pos)
        served = _served_logits(params, policy, toks, (12, 7), 16, other)
    assert float(jnp.abs(want - base).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < 5e-5
    assert float(jnp.abs(served[0] - want[0, 11:]).max()) < 5e-5


def test_the_head_is_the_embedding_and_untied_families_keep_theirs():
    params, policy = make()
    x = jax.random.normal(jax.random.key(3), (5, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        tied = driver._logits(x, params, TINY)
        normed = driver.rms_norm(x, params["final_norm"], TINY.rms_norm_eps)
        want = normed @ params["embed"].T / TINY.logits_scaling
        head = jax.random.normal(jax.random.key(4),
                                 (TINY.hidden_size, TINY.vocab_size))
        untied = driver._logits(x, {**params, "head": head}, TINY)
    np.testing.assert_allclose(tied, want, atol=1e-5)
    assert tied.dtype == jnp.float32
    np.testing.assert_allclose(untied, normed @ head / TINY.logits_scaling,
                               atol=1e-5)
    # a row of the embedding is that token's column of the head
    moved = {**params, "embed": params["embed"].at[7].mul(2.0)}
    with jax.default_matmul_precision("highest"):
        again = driver._logits(x, moved, TINY)
    np.testing.assert_allclose(again[:, 7], 2.0 * tied[:, 7], rtol=1e-5)
    np.testing.assert_array_equal(again[:, :7], tied[:, :7])


def test_a_slots_state_does_not_depend_on_max_len_and_its_keys_do():
    _, policy = make()
    family = gh.GraniteHybridFamily(TINY, policy)
    for max_len in (MAX_LEN, 4 * MAX_LEN):
        caches = family.init_caches(3, max_len)
        assert sorted(caches) == [f"l{i}" for i in range(6)]
        for i in (0, 1, 3, 4, 5):
            assert caches[f"l{i}"]["ssm"].shape == (3, 4, 32, 16)
            assert caches[f"l{i}"]["ssm"].dtype == jnp.float32
            assert caches[f"l{i}"]["conv"].shape == (3, 3, 160)
        assert caches["l2"]["k"].shape == (3, 2, max_len, 16)
        assert caches["l2"]["v"].shape == caches["l2"]["k"].shape
    assert isinstance(family.blocks["l2"], kv.KVBlock)
    assert family.blocks["l2"].window is None
    assert family.blocks["l2"].scale == TINY.attention_multiplier
    # the published widths: 2.1 MB of carry a slot and state layer
    whole = gh.state_block(gh.GraniteHybridConfig())
    shapes = jax.eval_shape(lambda: whole.init_cache(1, 2560, jnp.bfloat16))
    assert shapes["ssm"].shape == (1, 64, 64, 128)
    assert shapes["conv"].shape == (1, 3, 4352)


def test_rows_that_idle_stay_finite_and_an_admission_overwrites_them():
    """1,000 steps of garbage in a slot that is not live: the state stays
    finite (every decay is at most 1, the input is normed), and the state a
    prefill hands over replaces all of it."""
    params, policy = make()
    family = gh.GraniteHybridFamily(TINY, policy)
    caches = family.init_caches(2, MAX_LEN)
    live = jnp.array([False, False])

    @jax.jit
    def idle(caches):
        def body(i, caches):
            tok = jnp.array([3, 90]) + i % 2
            return gh.decode_step(params, tok, jnp.array([5, 40]), caches,
                                  live, TINY, policy)[1]
        return jax.lax.fori_loop(0, 1000, body, caches)

    caches = idle(caches)
    for leaf in jax.tree.leaves(caches):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())
    assert float(jnp.abs(caches["l0"]["ssm"]).max()) > 0
    toks = _tokens()
    primes = jnp.array([9, 4])
    _, fresh, _ = jitted(family.prefill)(params, toks[:, :16], primes,
                                         MAX_LEN)
    merged = jax.tree.map(lambda old, new: old.at[:2].set(new), caches, fresh)
    for i in (0, 1, 3, 4, 5):      # nothing of the idle state is left
        for leaf in ("ssm", "conv"):
            np.testing.assert_array_equal(merged[f"l{i}"][leaf],
                                          fresh[f"l{i}"][leaf])


def test_decode_counts_state_rows_contexts_and_cache_rows_read():
    params, policy = make()
    family = gh.GraniteHybridFamily(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live = jnp.array([True, False, True])
    pos = jnp.array([2, 30, 20])
    # a program of its own each: what a TRACE notes is what is read here
    step = fresh(gh.decode_step)
    with record_lowerings() as chosen:
        _, new, stats = step(params, jnp.array([4, 5, 6]), pos, caches, live,
                             TINY, policy)
    assert chosen["ssd_step"] == {"xla"} and "ssd_prefill" not in chosen
    assert float(stats["ssm.step_rows"]) == 5 * 2
    assert float(stats["attn.decode_rows"]) == 2
    assert float(stats["attn.context_tokens"]) == 3 + 21
    # the XLA core reads every row of every slot of the one attention block
    assert float(stats["attn.full_rows_read"]) == 3 * MAX_LEN
    assert float(stats["ssm.prefill_tokens"]) == 0
    assert set(stats) == set(gh.STAT_KEYS)
    assert new["l0"]["ssm"].dtype == jnp.float32
    assert new["l0"]["conv"].dtype == caches["l0"]["conv"].dtype
    # no live row: nothing is counted
    _, _, idle = step(params, jnp.array([4, 5, 6]), pos, caches,
                      jnp.zeros((3,), bool), TINY, policy)
    assert all(float(v) == 0 for v in idle.values())
    with record_lowerings() as chosen:
        fresh(gh.prefill)(params, _tokens()[:, :16], jnp.array([16, 3]), TINY,
                          policy)
    assert chosen["ssd_prefill"] == {"xla"} and "ssd_step" not in chosen


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = gh.GraniteHybridConfig.from_dict({
        "num_hidden_layers": 3, "layer_types": ["mamba", "attention",
                                                "mamba"],
        "model_type": "granitemoehybrid", "hidden_act": "silu",
        "rope_theta": 10000, "unknown": 1})
    assert c.layer_types == (gh.MAMBA, gh.ATTENTION, gh.MAMBA)
    assert hash(c) is not None and c.num_layers == 3
    assert c.embed_gain == c.embedding_multiplier == 12
    with pytest.raises(ValueError, match="layer_types"):
        gh.GraniteHybridConfig(num_hidden_layers=3)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=("mamba",) * 5 + ("sliding",))
    with pytest.raises(ValueError, match="key/value heads"):
        dataclasses.replace(TINY, num_key_value_heads=3)
    with pytest.raises(ValueError, match="x hidden"):
        dataclasses.replace(TINY, mamba_d_head=16)
    for other in (dict(num_local_experts=4), dict(mamba_n_groups=2),
                  dict(position_embedding_type="rope"),
                  dict(tie_word_embeddings=False)):
        with pytest.raises(ValueError, match="not supported"):
            dataclasses.replace(TINY, **other)
