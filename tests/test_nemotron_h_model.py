"""Nemotron-H (``models/nemotron_h.py``) against its plain reference
(``perf/lib/reference_nemotron3.py``) at tiny widths on the CPU, seeded
weights: the forward over right-padded rows, prefill then decode through
the blocks' caches, the float32 islands (a bfloat16 one fails a tolerance),
the gated norm a group, the two-matrix expert form through all three
lowerings, the shares adding up to the uncut layer, the router's bias, what
each kind of layer states about its cache, the counters and the config's
refusals."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_nemotron3 as ref
from progen_tpu.models import driver, experts, kv, state
from progen_tpu.models import nemotron_h as nh
from progen_tpu.ops import moe_decode as md
from progen_tpu.ops import ssd
from progen_tpu.ops.lowering import record_lowerings
from tests.families import fresh, jitted, reference
from tests.nemotron_h_tiny import TINY, as_dict, make, share
from tests.test_pallas_moe_decode import _kernel_path

F32 = jnp.float32
MAX_LEN = 32
# float32 on both sides: what differs is the order of sums (the chunked scan
# against the token-by-token recurrence, ragged windows against a dense loop
# over experts, the blocked softmax), a few 1e-6 on logits of spread 1; a
# bfloat16 island moves a logit by 1e-3 and more (the islands' test below)
TOL = 2e-5
LENGTHS = (13, 1, 2, 24)        # past a chunk of 8; under the four taps


@pytest.fixture(scope="module")
def weights():
    return make()


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(1, TINY.vocab_size, (4, 24)), jnp.int32)


@pytest.fixture(scope="module")
def wanted(weights, rows):
    """The reference's logits at every position of every row."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference(ref, TINY)(weights[0], rows))


def test_forward_over_right_padded_rows_is_the_references(weights, rows,
                                                          wanted):
    params, policy = weights
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    at = jnp.broadcast_to(jnp.arange(24), (4, 24))
    logits, handed, stats = jitted(nh.prefill)(
        params, rows, lengths, TINY, policy, logit_positions=at)
    for i, n in enumerate(LENGTHS):
        assert np.abs(np.asarray(logits[i, :n]) - wanted[i, :n]).max() < TOL
    assert float(wanted.std()) > 0.5            # not a vacuous bound
    # an expert layer hands over nothing: M at 0, 2, 5 and * at 3
    assert sorted(handed) == ["l0", "l2", "l3", "l5"]
    tokens = sum(LENGTHS)
    assert stats["moe.tokens"] == 3 * tokens
    assert stats["moe.held_load"].sum() == stats["moe.prefill_held"] == (
        3 * tokens * TINY.num_experts_per_tok)
    assert stats["ssm.prefill_tokens"] == 3 * tokens
    assert stats["ssm.prefill_slots"] == 3 * 4 * 24     # whole chunks of 8


@pytest.mark.parametrize("steps", [3])
def test_prefill_then_decode_is_the_references_full_forward(weights, rows,
                                                            wanted, steps):
    """Unequal right-padded rows (1 and 2 tokens: shorter than the taps; 13:
    past a chunk) prefilled, laid out as slots, then decoded token by token:
    every step's logits are the reference's at that position of the row."""
    params, policy = weights
    lengths = jnp.asarray([13, 1, 2, 20], jnp.int32)
    _, handed, _ = jitted(nh.prefill)(params, rows, lengths, TINY, policy)
    caches = jitted(nh.caches_from)(handed, lengths, TINY, MAX_LEN)
    live = jnp.ones((4,), bool)
    for j in range(steps):
        pos = lengths + j
        tok = rows[jnp.arange(4), pos]
        logits, caches, stats = jitted(nh.decode_step)(
            params, tok, pos, caches, live, TINY, policy)
        want = wanted[np.arange(4), np.asarray(pos)]
        assert np.abs(np.asarray(logits) - want).max() < TOL, j
    assert stats["ssm.step_rows"] == 3 * 4 and stats["ssm.decode_steps"] == 1
    assert stats["moe.decode_layers"] == 3 and stats["moe.tokens"] == 12
    assert stats["attn.decode_rows"] == 4
    assert stats["attn.context_tokens"] == float(jnp.sum(pos + 1))


def _bf16_carry(monkeypatch):
    step = ssd.ssd_step

    def rounded(state, *a):
        y, new = step(state.astype(jnp.bfloat16).astype(F32), *a)
        return y, new.astype(jnp.bfloat16).astype(F32)

    monkeypatch.setattr(ssd, "ssd_step", rounded)


def _bf16_norm_statistics(monkeypatch):
    def rms_norm(x, scale, eps):
        xs = x.astype(jnp.bfloat16)
        var = jnp.mean(xs * xs, axis=-1, keepdims=True)
        return (xs * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale

    # the stack's norms are ``driver.stack_norm``'s, which calls this name
    monkeypatch.setattr(driver, "rms_norm", rms_norm)


def _bf16_router(monkeypatch):
    def low(u, router, topk, **kw):
        lo = jnp.bfloat16
        scores = jax.nn.sigmoid(jnp.dot(u.astype(lo), router["w"].astype(lo)))
        _, ids = jax.lax.top_k(scores + router["bias"].astype(lo), topk)
        w = jnp.take_along_axis(scores, ids, -1)
        w = w / (jnp.sum(w, -1, keepdims=True) + lo(kw["eps"]))
        return ids, (w * lo(kw["scale"])).astype(F32)

    monkeypatch.setattr(experts, "sigmoid_route", low)


@pytest.mark.parametrize("island", [_bf16_carry, _bf16_norm_statistics,
                                    _bf16_router],
                         ids=lambda f: f.__name__.strip("_"))
def test_bfloat16_where_float32_is_stated_fails_the_tolerance(
        monkeypatch, weights, rows, wanted, island):
    """The comparison is tight enough to tell: the carry re-rounded every
    token, the norms' statistics or the router in bfloat16 moves a logit by
    more than the tolerance the float32 program keeps."""
    params, policy = weights
    island(monkeypatch)
    lengths = jnp.asarray([13, 9, 12, 20], jnp.int32)
    # traced anew under the patch: ``jitted`` would hand back the float32
    # programs the tests above compiled
    _, handed, _ = fresh(nh.prefill)(params, rows, lengths, TINY, policy)
    caches = jitted(nh.caches_from)(handed, lengths, TINY, MAX_LEN)
    worst, step = 0.0, fresh(nh.decode_step)
    for j in range(3):
        pos = lengths + j
        logits, caches, _ = step(
            params, rows[jnp.arange(4), pos], pos, caches,
            jnp.ones((4,), bool), TINY, policy)
        want = wanted[np.arange(4), np.asarray(pos)]
        worst = max(worst, float(np.abs(np.asarray(logits) - want).max()))
    assert worst > 10 * TOL


def test_the_gated_norm_is_a_groups_own(weights):
    """``RMSNorm_w(y * silu(z))`` over each group of ``I / G`` channels by
    itself, the gate first: against a loop over the groups."""
    block = nh.state_block(TINY)
    ks = jax.random.split(jax.random.key(1), 3)
    inner, heads, d = block.inner, block.heads, block.head_dim
    y = jax.random.normal(ks[0], (5, heads, d))
    z = jax.random.normal(ks[1], (5, inner))
    p = dict(weights[0]["layers"][0]["mixer"])
    p["d"] = jnp.zeros((heads,))
    p["out_proj"] = jnp.eye(inner)
    got = block._out(y, jnp.zeros((5, heads, d)), z, p)
    gated = y.reshape(5, inner) * jax.nn.silu(z)
    width = inner // TINY.n_groups
    want = jnp.concatenate([
        driver.rms_norm(gated[:, g * width:(g + 1) * width],
                        p["norm"][g * width:(g + 1) * width],
                        TINY.layer_norm_epsilon)
        for g in range(TINY.n_groups)], axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    whole = driver.rms_norm(gated, p["norm"], TINY.layer_norm_epsilon)
    assert float(jnp.abs(got - whole).max()) > 1e-2     # not one group


# ------------------------------------------------- the two-matrix experts


def _expert_layer(mixed=False, held=None, first=0):
    params, policy = make(TINY, mixed)
    layer = params["layers"][1]
    c = TINY
    if held is not None:
        c = share(first, held)
        layer = dict(layer, experts={k: v[first:first + held]
                                     for k, v in layer["experts"].items()})
    return c, layer, policy.compute_dtype


@functools.partial(jax.jit, static_argnames="c")
def _dense(v, ids, w, live, layer, c):
    """Every held expert over every token in float32."""
    e = {k: a.astype(F32) for k, a in layer["experts"].items()}
    vf = v.astype(F32)
    y = jnp.zeros(vf.shape, F32)
    for j in range(c.experts_held):
        out = jnp.square(jax.nn.relu(vf @ e["wu"][j])) @ e["wd"][j]
        wj = jnp.sum(jnp.where(ids == c.first_expert + j, w, 0.0), axis=-1)
        y = y + out * (wj * live)[:, None]
    return y


@pytest.mark.parametrize("lowering,tokens,tiles", [
    ("xla", 24, {}),
    ("pallas", 24, dict(lane=8, step_bytes=2 * 16 * 8 * 4)),
    ("pallas", 13, dict(lane=8)),
    ("pallas_grouped", 40, dict(lane=8, most=(16, 64), row_tile=8,
                                step_bytes=2 * 16 * 8 * 4)),
    ("pallas_grouped", 40, dict(lane=8, most=(16, 64), row_tile=8)),
    ("pallas_sorted", 40, dict(lane=8, most=(8, 16), sorted_tile=8,
                               step_bytes=2 * 16 * 8 * 4)),
    ("pallas_sorted", 40, dict(lane=8, most=(8, 16), sorted_tile=8))],
    ids=["ragged-windows", "decode-kernel-three-steps",
         "decode-kernel-off-the-row-group", "grouped-kernel-three-steps",
         "grouped-kernel-one-step", "sorted-kernel-three-steps",
         "sorted-kernel-one-step"])
@pytest.mark.parametrize("held,first", [(None, 0), (4, 8)],
                         ids=["all-held", "a-share-in-the-middle"])
def test_two_matrix_experts_through_every_lowering(monkeypatch, lowering,
                                                   tokens, tiles, held,
                                                   first):
    """Experts of ``{"wu", "wd"}`` and ``relu^2``, in the latent's width:
    the ``ragged_dot`` windows (two products), ``moe_decode_fwd``,
    ``moe_grouped_fwd`` and ``moe_sorted_fwd`` under the interpreter,
    against a dense loop over the held experts.  Tokens that are not live
    and assignments outside the share add nothing."""
    c, layer, _ = _expert_layer(held=held, first=first)
    assert sorted(layer["experts"]) == ["wd", "wu"]
    u = jax.random.normal(jax.random.key(3), (tokens, c.hidden_size))
    ids, w = jitted(nh.route)(u, layer["router"], c)
    v = u @ layer["latent_in"]
    live = jnp.arange(tokens) % 5 != 0
    if tiles:
        _kernel_path(monkeypatch, **tiles)
    with jax.default_matmul_precision("highest"), \
            record_lowerings() as chosen:
        # one program, traced under the lowering patched in above: eagerly
        # the interpreter runs a kernel's grid op by op
        got, load = fresh(experts.held_experts)(v, ids, w, live,
                                                layer["experts"], c)
        counted = experts.kernel_counters(v, layer["experts"], load, c)
    assert chosen["moe_experts"] == {lowering}
    dense = np.asarray(_dense(v, ids, w, live, layer, c))
    assert float(np.abs(dense).max()) > 0.05
    assert float(np.abs(np.asarray(got) - dense).max()) < 2e-4
    assert not np.asarray(got)[~np.asarray(live)].any()
    mine = (np.asarray(ids) >= first) & (np.asarray(ids) < first
                                         + c.experts_held)
    assert load.sum() == (mine & np.asarray(live)[:, None]).sum()
    touched = float((np.asarray(load) > 0).sum())
    if lowering == "xla":
        assert counted["moe.expert_passes"] == 0
    elif lowering == "pallas":
        assert counted["moe.expert_passes"] == touched
    if tiles.get("step_bytes"):     # two tiles a step: 8 of the 24 columns
        assert md.inner_tile(16, 24, 4, matrices=2) == 8
        assert md.inner_tile(16, 24, 4) == 8


def test_inner_tile_counts_the_matrices_an_expert_has():
    """At the published widths (latent 1024, inner 2688 = 21 x 128,
    bfloat16) two tiles a step hold the whole inner width; counted as three
    the budget would end at 896."""
    assert md.inner_tile(1024, 2688, 2, matrices=2) == 2688
    assert md.inner_tile(1024, 2688, 2) == 896
    shapes = {"wu": jax.ShapeDtypeStruct((128, 1024, 2688), jnp.bfloat16),
              "wd": jax.ShapeDtypeStruct((128, 2688, 1024), jnp.bfloat16)}
    u = jax.ShapeDtypeStruct((64, 1024), jnp.bfloat16)
    assert md.fitted_tile(u, shapes) is None            # not on a TPU


# ----------------------------------------------------------- the share


def test_four_shares_add_up_to_the_uncut_layer(weights):
    """Four chips hold 4 of 16 experts each: their routed parts — summed in
    the latent, projected back by each — with the shared expert and
    everything outside the experts counted once are the uncut reference's
    layer."""
    params, _ = weights
    layer = params["layers"][1]
    u = jax.random.normal(jax.random.key(11), (24, TINY.hidden_size))
    live = jnp.ones((24,), bool)
    with jax.default_matmul_precision("highest"):
        want, want_ids = ref.latent_moe(u, layer, as_dict(TINY))
        total, loads = 0.0, []
        for s in range(4):
            c = share(4 * s)
            mine = dict(layer, experts={k: v[4 * s:4 * s + 4]
                                        for k, v in layer["experts"].items()})
            y, ids, stats = nh.moe_share(u, mine, c, live)
            np.testing.assert_array_equal(ids, want_ids)    # one router
            total = total + y
            loads.append(stats["moe.held_load"])
            # the reference given the same share gives the same part
            part, _ = ref.latent_moe(u, mine, as_dict(c))
            shared = nh.relu2(u, layer["shared"], "ffn.shared")
            assert float(jnp.abs(y + shared - part).max()) < TOL
        total = total + nh.relu2(u, layer["shared"], "ffn.shared")
    assert float(jnp.abs(total - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 0.5
    assert float(sum(x.sum() for x in loads)) == 24 * TINY.num_experts_per_tok


def test_the_bias_picks_and_does_not_weigh(weights):
    params, _ = weights
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(2), (12, TINY.hidden_size))
    ids, w = nh.route(u, router, TINY)
    lifted = dict(router, bias=router["bias"].at[7].set(10.0))
    ids2, w2 = nh.route(u, lifted, TINY)
    assert bool((ids2 == 7).any(axis=-1).all())     # picked everywhere
    assert not bool((ids == 7).any(axis=-1).all())
    scores = jax.nn.sigmoid(u @ router["w"])
    chosen = jnp.take_along_axis(scores, ids2, axis=-1)
    want = chosen / chosen.sum(-1, keepdims=True) * TINY.routed_scaling_factor
    np.testing.assert_allclose(w2, want, rtol=1e-5)      # the bias is not in
    np.testing.assert_allclose(w2.sum(-1), 5.0, rtol=1e-5)


# ------------------------------------------------------ caches, config


def test_each_kind_of_layer_states_its_own_cache_and_an_expert_layer_none():
    blocks = nh.blocks_of(TINY)
    assert list(blocks) == ["l0", "l2", "l3", "l5"]
    assert isinstance(blocks["l3"], kv.KVBlock)
    assert blocks["l3"].window is None
    assert blocks["l3"].scale == pytest.approx(TINY.head_dim ** -0.5)
    assert all(isinstance(blocks[n], state.StateBlock)
               for n in ("l0", "l2", "l5"))
    family = nh.NemotronHFamily(TINY, make()[1])
    for max_len in (16, 4096):      # the state does not depend on it
        caches = jax.eval_shape(lambda: family.init_caches(3, max_len))
        assert caches["l0"]["ssm"].shape == (3, 8, 8, 8)
        assert caches["l0"]["ssm"].dtype == jnp.float32
        assert caches["l0"]["conv"].shape == (3, 3, 64 + 2 * 4 * 8)
        assert caches["l3"]["k"].shape == (3, 2, max_len, 8)
    # the published widths: 4.19 MB of carry and 61 KB of tail a slot
    whole = nh.state_block(nh.NemotronHConfig())
    shapes = jax.eval_shape(lambda: whole.init_cache(1, 3072, jnp.bfloat16))
    assert shapes["ssm"].shape == (1, 128, 64, 128)
    assert shapes["conv"].shape == (1, 3, 10240)
    assert whole.groups == 8 and whole.chunk == 128


@pytest.mark.parametrize("change,message", [
    (dict(hybrid_override_pattern="MEM-EME"), "hybrid_override_pattern"),
    (dict(num_hidden_layers=8), "hybrid_override_pattern"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(n_group=2), "n_group"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(first_expert=14, experts_held=4), "routed experts"),
    (dict(mamba_num_heads=6), "hidden"),
    (dict(num_key_value_heads=3), "key/value")])
def test_a_config_the_served_model_does_not_have_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(TINY, **change)


def test_the_published_pattern_and_from_dict():
    whole = nh.NemotronHConfig()
    assert [whole.layers_of(k) for k in "ME*"] == [40, 40, 8]
    assert whole.mamba_inner == 8192 and whole.rms_norm_eps == 1e-5
    c = nh.NemotronHConfig.from_dict(
        dict(as_dict(TINY), model_type="nemotron_h", rope_theta=10000))
    assert c == TINY
    with pytest.raises(ValueError, match="groups"):
        state.StateBlock(6, 8, 8, 4, 4, 1e-5, 8)
