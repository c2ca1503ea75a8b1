"""LFM2 (``progen_tpu/models/lfm2.py``) against the plain reference
(``perf/lib/reference_lfm2.py``: float32, no cache, the convolution as
shifted copies of the row, a dense loop over the experts): the full forward,
prefill of right-padded rows of unequal length then decode through the tails
and the grown keys — primes of 1 and 2 tokens, shorter than the taps, among
them —, the tail a prefill hands over, the router, the shares of an expert
layer, the counters on a hand-sized batch, and Trinity's router through the
function the two families share."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_lfm2 as ref
from perf.lib import reference_trinity
from progen_tpu.models import experts, lfm2
from progen_tpu.models import trinity as tr
from progen_tpu.ops import ssd
from tests import trinity_tiny
from tests.families import jitted
from tests.lfm2_tiny import TINY, as_dict, make

T, MAX_LEN = 40, 48
CONV_LAYERS = TINY.layer_types.count(lfm2.CONV)
EXPERT_LAYERS = TINY.num_hidden_layers - TINY.num_dense_layers


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


@functools.cache
def _programs(mixed):
    """One prefill (a trace a bucket), one step and one reference forward
    per precision for every case below."""
    params, policy = make(mixed=mixed)
    prefill = jax.jit(lambda toks, primes: lfm2.prefill(
        params, toks, primes, TINY, policy)[:2])
    step = jax.jit(lambda t, ps, c: lfm2.decode_step(
        params, t, ps, c, jnp.ones((t.shape[0],), bool), TINY, policy)[:2])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda toks: ref.forward(
            params, toks, as_dict(TINY)))(_tokens())
    return prefill, step, want


def _served_logits(prefill, step, toks, primes, bucket):
    """Logits of every position from ``prime - 1`` on, a row: the
    prefill's last position, then one decode step per token through the
    caches (rows of different primes step together, each at its own
    position)."""
    primes = jnp.asarray(primes)
    first, per_token = prefill(toks[:, :bucket], primes)
    caches = jitted(lfm2.caches_from)(per_token, primes, TINY, MAX_LEN)
    out = [first[:, 0]]
    for i in range(T - int(primes.max())):
        pos = primes + i
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        logits, caches = step(tok, pos, caches)
        out.append(logits)
    return jnp.stack(out, axis=1)


def test_the_tiny_model_has_every_kind_of_layer():
    params, _ = make()
    assert ["ffn" in layer for layer in params["layers"]] == [
        True, True, False, False, False, False]
    assert ["conv_w" in layer["mixer"] for layer in params["layers"]] == [
        True, False, True, True, False, True]
    assert "head" not in params                 # tied to the embedding
    blocks = lfm2.blocks_of(TINY)
    assert [type(blocks[f"l{i}"]).__name__ for i in range(6)] == [
        "ShortConvBlock", "AttentionBlock", "ShortConvBlock",
        "ShortConvBlock", "AttentionBlock", "ShortConvBlock"]
    assert not hasattr(blocks["l0"], "decode_block")
    conv, attn = params["layers"][2]["mixer"], params["layers"][4]["mixer"]
    assert conv["in_proj"].shape == (32, 96)
    assert conv["conv_w"].shape == (32, 3)
    assert set(conv) == {"in_proj", "conv_w", "out_proj"}       # no bias
    assert attn["wq"].shape == (32, 32) and attn["wk"].shape == (32, 16)
    assert attn["q_norm"].shape == (8,)
    layer = params["layers"][2]
    assert layer["norm"].shape == (2, 32)
    assert layer["experts"]["wg"].shape == (8, 32, 16)
    assert layer["router"]["bias"].dtype == jnp.float32
    assert "shared" not in layer
    # the published layout: 18 convolutions and 6 attention layers, the
    # first twelve three whole periods of conv conv attention conv
    whole = lfm2.LFM2Config()
    assert whole.layer_types.count(lfm2.FULL) == 6
    assert whole.layer_types[:12] == (lfm2.CONV, lfm2.CONV, lfm2.FULL,
                                      lfm2.CONV) * 3
    assert whole.head_dim == 64 and whole.experts_held == 32


def test_prefill_logits_match_the_reference_at_every_position():
    params, policy = make()
    toks = _tokens()
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    want = _programs(False)[2]
    prefill = jax.jit(lambda t: lfm2.prefill(
        params, t, jnp.array([T, 13]), TINY, policy, logit_positions=pos))
    with jax.default_matmul_precision("highest"):
        got, rows, stats = prefill(toks)
        again, _, _ = prefill(toks.at[1, 13:].set(5))
    # float32 end to end: what is left is the order of the sums (the
    # experts' grouped product against a dense loop), a few units in the
    # sixth place at logits of spread 1
    assert float(jnp.abs(got[0] - want[0]).max()) < 5e-5
    assert float(jnp.abs(got[1, :13] - want[1, :13]).max()) < 5e-5
    np.testing.assert_array_equal(got[1, :13], again[1, :13])
    assert float(want.std()) > 0.3              # not a vacuous bound
    # only real tokens are counted, once per EXPERT layer
    assert float(stats["moe.tokens"]) == EXPERT_LAYERS * (T + 13)
    assert float(stats["conv.tokens"]) == 0     # a decode step's counter
    assert sorted(rows) == [f"l{i}" for i in range(6)]
    assert rows["l0"]["conv"].shape == (2, 2, 32)
    assert rows["l1"]["k"].shape == (2, 1, T, 16)     # two heads a row


def test_the_convolution_and_the_rotation_change_the_logits():
    """The reference with the taps before the token struck out, or with
    positions that do not count, is another model: the agreement above is
    not that of mechanisms that never bite."""
    params, _ = make()
    toks = _tokens()
    cfg = as_dict(TINY)
    pointwise = jax.tree.map(lambda a: a, params)
    for layer in pointwise["layers"]:
        if "conv_w" in layer["mixer"]:
            layer["mixer"] = {**layer["mixer"], "conv_w": layer["mixer"][
                "conv_w"].at[:, :-1].set(0)}
    want = _programs(False)[2]
    with jax.default_matmul_precision("highest"):
        no_taps = jax.jit(lambda p: ref.forward(p, toks, cfg))(pointwise)
        no_rotation = jax.jit(lambda p: ref.forward(
            p, toks, {**cfg, "rope_theta": 1e30}))(params)
    np.testing.assert_allclose(want[:, 0], no_taps[:, 0], atol=1e-5)
    assert float(jnp.abs(want - no_taps)[:, 1:].max()) > 0.05
    assert float(jnp.abs(want - no_rotation).max()) > 0.05


@pytest.mark.parametrize("primes,bucket", [
    ((1, 2), 8), ((10, 8), 16), ((19, 3), 32), ((33, 1), 40)],
    ids=["shorter-than-the-taps", "at-and-past-a-bucket", "long-and-short",
         "mixed"])
@pytest.mark.parametrize("mixed,tol", [(False, 5e-5), (True, 0.3)],
                         ids=["float32", "bf16-params-and-compute"])
def test_prefill_of_unequal_rows_then_decode_matches_the_reference(
        primes, bucket, mixed, tol):
    prefill, step, want = _programs(mixed)
    start = max(primes)
    with jax.default_matmul_precision("highest"):
        got = _served_logits(prefill, step, _tokens(), primes, bucket)
    assert got.dtype == jnp.float32
    for row, prime in enumerate(primes):
        # step i of a row stands on position prime + i - 1
        steps = T - start + 1
        diff = jnp.abs(got[row] - want[row, prime - 1:prime - 1 + steps])
        # float32: every logit.  bfloat16 at a width of 32 flips one routing
        # in ten (near-ties of 8 sigmoids), and a flipped expert moves a
        # token's logits by 1: there the root mean square is held
        assert float(jnp.sqrt(jnp.mean(diff ** 2)) if mixed
                     else diff.max()) < tol


@pytest.mark.parametrize("length", [0, 1, 2, 3, 13, 16])
def test_prefill_hands_over_the_tail_at_the_rows_true_length(length):
    """The tail is the last two gated inputs ``z`` of the REAL tokens —
    zero rows where the row is shorter —, whatever stands in the padding."""
    params, policy = make()
    block = lfm2.blocks_of(TINY)["l0"]
    p = params["layers"][0]["mixer"]
    u = jax.random.normal(jax.random.key(3), (1, 16, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        _, rows = block.prefill(u, p, jnp.array([length]))
        z, _ = ref.gated_input(u[0], p)
    tail = np.asarray(rows["conv"][0])
    assert tail.shape == (2, TINY.hidden_size)
    want = np.zeros_like(tail)
    for j, at in enumerate((length - 2, length - 1)):
        if at >= 0:
            want[j] = z[at]
    np.testing.assert_allclose(tail, want, atol=1e-6)
    assert block.cache_rows(rows, jnp.array([length]), MAX_LEN) is rows
    # ... and a step over it is the convolution at the next position
    if 0 < length < 16:
        with jax.default_matmul_precision("highest"):
            out, cache = block.decode(u[0, length][None], jnp.array([length]),
                                      {"conv": rows["conv"]}, p)
            full = ref.short_conv(u[0], p, as_dict(TINY))
        np.testing.assert_allclose(out[0], full[length], atol=1e-5)
        np.testing.assert_allclose(cache["conv"][0, 0], tail[1], atol=0)
        np.testing.assert_allclose(cache["conv"][0, 1], z[length], atol=1e-6)


def test_a_slots_cache_is_a_tail_for_a_convolution_and_grown_keys_for_attention():
    _, policy = make()
    family = lfm2.LFM2Family(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    assert {n: jax.tree.map(lambda a: a.shape, c)
            for n, c in caches.items()} == {
        **{f"l{i}": {"conv": (3, 2, 32)} for i in (0, 2, 3, 5)},
        # the two key/value heads of 8 side by side in one row of 16
        **{f"l{i}": {"k": (3, 1, MAX_LEN, 16), "v": (3, 1, MAX_LEN, 16)}
           for i in (1, 4)}}
    # the tail is as large whatever the engine's max_len
    assert family.init_caches(3, 6)["l0"]["conv"].shape == (3, 2, 32)


def test_a_cache_row_holds_as_many_heads_as_fill_the_lane_tile():
    assert lfm2.heads_packed(8, 64) == 2            # the published widths
    assert lfm2.heads_packed(8, 128) == 1
    assert lfm2.heads_packed(2, 8) == 2             # the tiny model's
    assert lfm2.heads_packed(3, 32) == 3 and lfm2.heads_packed(3, 64) == 1
    block = lfm2.AttentionBlock(lfm2.LFM2Config())
    assert (block.kv_heads, block.head_dim, block.scale) == (4, 128, 0.125)
    # query heads 0-3 read key/value head 0 (the first half of row 0),
    # 4-7 head 1 (its second half), 8-11 head 2 (the first half of row 1)
    assert block.half.tolist()[:12] == [0] * 4 + [1] * 4 + [0] * 4
    # a packed block's projection and finish are the plain head's: the
    # core over rows of 2 d against a plain softmax over each head's own d
    params, _ = make()
    tiny = lfm2.blocks_of(TINY)["l1"]
    p = params["layers"][1]["mixer"]
    x = jax.random.normal(jax.random.key(2), (1, 9, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        got, rows = tiny.prefill(x, p, jnp.array([9]))
        want = ref.attention(x[0], p, as_dict(TINY), 4)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    assert rows["k"].shape == (1, 1, 9, 16)


def test_decode_counts_rows_contexts_tails_and_cache_rows_read():
    params, policy = make()
    family = lfm2.LFM2Family(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live = jnp.array([True, False, True])
    pos = jnp.array([2, 30, 20])
    step = jax.jit(lambda live: lfm2.decode_step(
        params, jnp.array([4, 5, 6]), pos, caches, live, TINY, policy,
        with_choices=True))
    _, _, stats, chosen = step(live)
    assert chosen.shape == (EXPERT_LAYERS, 3, TINY.num_experts_per_tok)
    assert float(stats["moe.decode_layers"]) == EXPERT_LAYERS
    assert float(stats["attn.decode_rows"]) == 2
    assert float(stats["attn.context_tokens"]) == 3 + 21
    # the XLA core reads every row of every slot of one block
    assert float(stats["attn.full_rows_read"]) == 3 * MAX_LEN
    # live rows x convolution layers
    assert float(stats["conv.tokens"]) == 2 * CONV_LAYERS
    assert float(stats["moe.tokens"]) == EXPERT_LAYERS * 2
    assert float(stats["moe.held_load"].sum()) == EXPERT_LAYERS * 2 * 3
    # no live row: nothing is counted
    _, _, idle, _ = step(jnp.zeros((3,), bool))
    assert all(float(jnp.sum(v)) == 0 for v in idle.values())
    assert set(idle) == set(lfm2.STAT_KEYS)


# ------------------------------------------------------------ the router


def _numpy_route(u, router, c):
    """The release's router transcribed with NumPy: ``sigmoid``, the top-k
    of ``scores + bias``, the weights gathered from ``scores`` and
    renormalised over their sum plus 1e-6."""
    logits = u.astype(np.float64) @ np.asarray(router["w"], np.float64)
    scores = 1 / (1 + np.exp(-logits))
    picked = scores + np.asarray(router["bias"], np.float64)
    ids = np.argsort(-picked, axis=-1, kind="stable")[
        :, :c.num_experts_per_tok]
    w = np.take_along_axis(scores, ids, -1)
    if c.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return ids, w * c.routed_scaling_factor


@pytest.mark.parametrize("norm", [True, False])
def test_sigmoid_router_against_numpy(norm):
    c = dataclasses.replace(TINY, norm_topk_prob=norm,
                            routed_scaling_factor=1.5)
    params, _ = make()
    router = params["layers"][2]["router"]
    u = jax.random.normal(jax.random.key(5), (64, c.hidden_size))
    with jax.default_matmul_precision("highest"):
        ids, w = lfm2.route(u, router, c)
        ref_ids, ref_w = ref.route(u, router, as_dict(c))
    want_ids, want_w = _numpy_route(np.asarray(u), router, c)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    np.testing.assert_array_equal(np.sort(ref_ids, -1), np.sort(want_ids, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1), rtol=1e-5)
    np.testing.assert_allclose(np.sort(ref_w, -1), np.sort(want_w, -1),
                               rtol=1e-5)


def test_the_bias_picks_and_does_not_weigh():
    params, _ = make()
    router = params["layers"][2]["router"]
    u = jax.random.normal(jax.random.key(5), (64, TINY.hidden_size))
    unbiased = {**router, "bias": jnp.zeros_like(router["bias"])}
    pushed = {**router, "bias": router["bias"].at[2].add(10.0)}
    with jax.default_matmul_precision("highest"):
        ids, w = lfm2.route(u, router, TINY)
        plain_ids, _ = lfm2.route(u, unbiased, TINY)
        pushed_ids, pushed_w = lfm2.route(u, pushed, TINY)
        scores = jax.nn.sigmoid(u @ router["w"])
    # the seeded bias changes some token's choice ...
    assert bool((jnp.sort(ids, -1) != jnp.sort(plain_ids, -1)).any())
    # ... a large one forces its expert on every token ...
    assert bool((pushed_ids == 2).any(-1).all())
    # ... and no weight ever holds it: they are the chosen sigmoids,
    # unbiased, renormalised, whatever the bias
    for i, ww in ((ids, w), (pushed_ids, pushed_w)):
        s = jnp.take_along_axis(scores, i, -1)
        np.testing.assert_allclose(
            ww, s / (s.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)


def _trinity_route_as_it_was(u, router, c):
    """``models/trinity.py:route`` as PR 34 wrote it, before the function
    moved to ``models/experts.py``."""
    logits = jnp.dot(u.astype(jnp.float32), router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + router["bias"].astype(jnp.float32),
                           c.num_experts_per_tok)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if c.route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * c.route_scale


@pytest.mark.parametrize("route_norm", [True, False])
def test_trinitys_router_through_the_shared_function_is_bit_for_bit(
        route_norm):
    c = dataclasses.replace(trinity_tiny.TINY, route_norm=route_norm)
    params, _ = trinity_tiny.make()
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(5), (64, c.hidden_size))
    ids, w = jax.jit(lambda u: tr.route(u, router, c))(u)
    was_ids, was_w = jax.jit(
        lambda u: _trinity_route_as_it_was(u, router, c))(u)
    np.testing.assert_array_equal(ids, was_ids)
    np.testing.assert_array_equal(w, was_w)
    # one program: the same text
    text = [str(jax.make_jaxpr(lambda u, f=f: f(u, router, c))(u))
            for f in (tr.route, _trinity_route_as_it_was)]
    assert text[0] == text[1]
    # and the reference's router reads the same choices
    with jax.default_matmul_precision("highest"):
        ref_ids, _ = reference_trinity.route(u, router,
                                             trinity_tiny.as_dict(c))
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(ref_ids, -1))


# ------------------------------------------------------------- the shares

TOKENS = 40


def _layer_and_input():
    params, _ = make()
    u = jax.random.normal(jax.random.key(11), (TOKENS, TINY.hidden_size))
    return params["layers"][3], u


def _share(layer, first, held):
    cut = dataclasses.replace(TINY, first_expert=first, experts_held=held)
    part = {k: v[first:first + held] for k, v in layer["experts"].items()}
    return cut, {**layer, "experts": part}


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(chips):
    """1, 2 and 4 chips sharing a layer (all, half and a quarter of the
    experts held): the held parts summed give the uncut reference's expert
    layer."""
    layer, u = _layer_and_input()
    live = jnp.ones((TOKENS,), bool)
    held = TINY.num_experts // chips
    with jax.default_matmul_precision("highest"):
        whole, want_ids = ref.routed(u, layer["router"], layer["experts"],
                                     as_dict(TINY))
        total = jnp.zeros_like(u)
        for chip in range(chips):
            cut, part = _share(layer, chip * held, held)
            y, ids, stats = lfm2.moe_share(u, part, cut, live)
            total = total + y
            np.testing.assert_array_equal(np.sort(ids, -1),
                                          np.sort(want_ids, -1))
            counts = np.bincount(np.asarray(ids).ravel(), minlength=8)
            np.testing.assert_array_equal(
                stats["moe.held_load"], counts[chip * held:(chip + 1) * held])
            # the reference of that share leaves the same terms out
            part_want, _ = ref.routed(u, part["router"], part["experts"],
                                      as_dict(cut))
            np.testing.assert_allclose(y, part_want, atol=2e-5)
    # float32: the order of a token's k terms differs, no more
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(jnp.abs(whole).max()) > 1e-3


def test_tokens_that_are_not_live_reach_no_expert_and_are_not_counted():
    layer, u = _layer_and_input()
    live = jnp.arange(TOKENS) < 25
    y, _, stats = lfm2.moe_share(u, layer, TINY, live)
    assert float(jnp.abs(y[25:]).max()) == 0
    assert float(stats["moe.tokens"]) == 25
    assert float(stats["moe.held_load"].sum()) == 25 * 3


def test_the_experts_window_with_every_expert_held():
    whole = lfm2.LFM2Config()                       # 4.0 a token
    assert experts.moe_capacity(whole, 128) == 4 * 128
    assert experts.moe_capacity(whole, 8192) == 4 * 8192


# ------------------------------------------------- config and convolution


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = lfm2.LFM2Config.from_dict({
        "num_hidden_layers": 4, "model_type": "lfm2_moe", "unknown": 1,
        "layer_types": ["conv", "conv", "full_attention", "conv"]})
    assert c.layer_types == (lfm2.CONV, lfm2.CONV, lfm2.FULL, lfm2.CONV)
    assert hash(c) is not None
    assert c.rms_norm_eps == c.norm_eps == 1e-5 and c.embed_gain == 1
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.LFM2Config(num_hidden_layers=3, layer_types=(lfm2.FULL,))
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.LFM2Config(num_hidden_layers=1, layer_types=("mamba",))
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=6, experts_held=4)
    with pytest.raises(ValueError, match="key/value heads"):
        dataclasses.replace(TINY, num_key_value_heads=3)
    with pytest.raises(ValueError, match="two taps"):
        dataclasses.replace(TINY, conv_L_cache=1)
    for other in (dict(conv_bias=True), dict(use_expert_bias=False),
                  dict(tie_word_embeddings=False)):
        with pytest.raises(ValueError, match="not supported"):
            dataclasses.replace(TINY, **other)


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_convolutions_three_forms_without_a_bias_agree(taps):
    """``ops/ssd.py``'s helpers with ``bias`` None (LFM2's) are the ones
    with a zero bias (what Granite's would compute)."""
    ks = jax.random.split(jax.random.key(9), 2)
    u = jax.random.normal(ks[0], (2, 12, 16))
    w = jax.random.normal(ks[1], (16, taps))
    zero = jnp.zeros((16,))
    np.testing.assert_array_equal(ssd.causal_conv(u, w, None),
                                  ssd.causal_conv(u, w, zero))
    lengths = jnp.array([12, 1])
    tail = ssd.conv_tail(u[:, :-1], lengths - 1, taps)
    assert tail.shape == (2, taps - 1, 16)
    assert float(jnp.abs(tail[1]).max()) == 0       # nothing before token 0
    out, new = ssd.conv_step(tail, u[jnp.arange(2), lengths - 1], w, None)
    full = ssd.causal_conv(u, w, None)
    np.testing.assert_allclose(out[0], full[0, 11], atol=1e-5)
    np.testing.assert_allclose(out[1], full[1, 0], atol=1e-5)
    np.testing.assert_array_equal(new[:, -1], u[jnp.arange(2), lengths - 1])
