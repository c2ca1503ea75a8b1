"""MiMo-V2 (``progen_tpu/models/mimo_v2.py``) against the plain reference
(``perf/lib/reference_mimo.py``: float32, no cache, the window as a dense
mask, the sink as a literal extra column, a dense loop over the experts):
the forward over a stack with the dense layer and a whole period of expert
layers, unequal right-padded rows prefilled and then decoded across the
ring's three edges, each omission the reference can plant failing the
tolerance that the program keeps, the shares adding up to the uncut layer,
the router's bias, the two kinds of cache, the byte gauges' arithmetic and
the attention cores with a sink and two widths against a dense softmax."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_mimo as ref
from progen_tpu.models import experts, kv
from progen_tpu.models import mimo_v2 as mm
from progen_tpu.ops import gqa
from tests.families import jitted, reference
from tests.mimo_v2_tiny import TINY, WINDOW, as_dict, make

T, MAX_LEN = 24, 32
# one full layer over a dense FFN and one sliding layer over experts: all an
# omission needs, at a third of the compile time
PAIR = dataclasses.replace(TINY, num_hidden_layers=2,
                           hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1))
# float32 end to end against float32 ``highest``: what is left is the order
# of sums (the blocked softmax divides after the value product, the ring
# holds keys in another order): 2e-6 read at a logit spread of 1.0
TOL = 5e-5


def _tokens(seed=1, rows=2):
    return jax.random.randint(jax.random.key(seed), (rows, T), 1,
                              TINY.vocab_size)


@functools.partial(jax.jit, static_argnames=("policy", "everywhere"))
def _prefill(params, toks, lengths, policy, everywhere=False):
    """One program a shape for the whole file (eagerly the blocked core
    alone takes seconds)."""
    pos = (jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
           if everywhere else None)
    return mm.prefill(params, toks, lengths, TINY, policy,
                      logit_positions=pos)


def _prefill_everywhere(params, policy, toks, lengths=(T, 13)):
    with jax.default_matmul_precision("highest"):
        return _prefill(params, toks, jnp.array(lengths), policy, True)


def test_the_tiny_model_has_every_kind_of_layer():
    params, _ = make()
    assert TINY.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert TINY.moe_layer_freq == (0,) + (1,) * 6
    assert ["ffn" in layer for layer in params["layers"]] == (
        [True] + [False] * 6)
    blocks = mm.blocks_of(TINY)
    assert [blocks[f"l{i}"].window for i in range(7)] == [
        None, WINDOW, WINDOW, WINDOW, WINDOW, None, WINDOW]
    assert blocks["l1"] is blocks["l2"] and blocks["l0"] is blocks["l5"]
    sliding, full = params["layers"][1]["attn"], params["layers"][5]["attn"]
    assert sliding["wq"].shape == full["wq"].shape == (32, 4 * 12)
    assert sliding["wk"].shape == (32, 2 * 12) and full["wk"].shape == (32, 12)
    assert sliding["wv"].shape == (32, 2 * 8) and full["wv"].shape == (32, 8)
    assert sliding["wo"].shape == (4 * 8, 32)
    assert sliding["sink"].shape == (4,) and "sink" not in full
    # sinks of N(4, 1): large enough that forgetting one moves the logits
    sinks = np.concatenate([np.asarray(layer["attn"]["sink"])
                            for layer in params["layers"]
                            if "sink" in layer["attn"]])
    assert 3.0 < sinks.mean() < 5.0 and sinks.std() > 0.5
    assert params["layers"][1]["norm"].shape == (2, 32)
    assert params["layers"][1]["experts"]["wg"].shape == (8, 32, 16)
    assert params["layers"][1]["router"]["bias"].dtype == jnp.float32
    assert "shared" not in params["layers"][1]
    assert TINY.rotary_dim(mm.FULL) == 4 and TINY.embed_gain == 1
    # the published layout: 39 sliding and 9 full, one dense layer, 64 of
    # 192 columns rotated
    whole = mm.MiMoV2Config()
    assert whole.hybrid_layer_pattern.count(1) == 39
    assert whole.hybrid_layer_pattern[:7] == (0, 1, 1, 1, 1, 0, 1)
    assert sum(whole.moe_layer_freq) == 47 and whole.rotary_dim(1) == 64
    assert whole.route_scale == 1.0


def test_forward_matches_the_reference_at_every_real_position():
    params, policy = make()
    toks = _tokens()
    want = _reference(params, toks)
    got, rows, stats = _prefill_everywhere(params, policy, toks)
    junk, _, _ = _prefill_everywhere(params, policy, toks.at[1, 13:].set(5))
    assert float(jnp.abs(got[0] - want[0]).max()) < TOL
    assert float(jnp.abs(got[1, :13] - want[1, :13]).max()) < TOL
    np.testing.assert_array_equal(got[1, :13], junk[1, :13])
    assert float(want.std()) > 0.3              # not a vacuous bound
    # only real tokens are counted, once per EXPERT layer
    assert float(stats["moe.tokens"]) == 6 * (T + 13)
    assert sorted(rows) == [f"l{i}" for i in range(7)]
    assert rows["l1"]["k"].shape == (2, 2, T, 12)
    assert rows["l1"]["v"].shape == (2, 2, T, 8)
    assert rows["l0"]["k"].shape == (2, 1, T, 12)


@jax.jit
def _reference(params, toks):
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, toks, as_dict(TINY))


def _with_full_sinks(params):
    """The weights with a sink in the full layers too (the sliding layers'
    seeded values reused): what a program that adds it there would read."""
    donor = params["layers"][1]["attn"]["sink"]
    return {**params, "layers": [
        layer if "sink" in layer["attn"] else
        {**layer, "attn": {**layer["attn"], "sink": donor}}
        for layer in params["layers"]]}


def _low_islands(monkeypatch):
    """bfloat16 where the configuration states float32: every softmax, the
    norms' statistics and the router's sigmoid."""
    bf = jnp.bfloat16
    monkeypatch.setattr(ref, "softmax", lambda x: jax.nn.softmax(
        x.astype(bf), axis=-1).astype(jnp.float32))
    monkeypatch.setattr(ref, "sigmoid", lambda x: jax.nn.sigmoid(
        x.astype(bf)).astype(jnp.float32))

    def rms_norm(x, scale, eps):
        xs = x.astype(bf)
        var = jnp.mean(xs * xs, axis=-1, keepdims=True)
        return (xs * jax.lax.rsqrt(var + bf(eps)) * scale.astype(bf)).astype(
            jnp.float32)

    monkeypatch.setattr(ref, "rms_norm", rms_norm)


@pytest.mark.parametrize("omission", [
    "no-sink", "sink-on-full-layers", "no-value-scale", "window-plus-one",
    "sliding-at-the-full-base", "bf16-where-float32-is-stated"])
def test_each_omission_fails_the_tolerance_the_program_keeps(
        omission, monkeypatch):
    """The reference with one of the family's choices left out or moved is
    another model: the program stands FURTHER from it than ``TOL`` by
    orders, so the agreement above is not that of terms that never bite."""
    params, policy = make(PAIR)
    toks = _tokens()
    pos = jnp.broadcast_to(jnp.arange(T), toks.shape)
    with jax.default_matmul_precision("highest"):
        # before any patch: the cached programs are the unpatched ones
        want = reference(ref, PAIR)(params, toks)
        got, _, _ = jitted(mm.prefill)(params, toks, jnp.array([T, T]), PAIR,
                                       policy, logit_positions=pos)
    cfg = as_dict(PAIR)
    weights = params
    if omission == "no-sink":
        cfg["add_swa_attention_sink_bias"] = False
    elif omission == "sink-on-full-layers":
        cfg["add_full_attention_sink_bias"] = True
        weights = _with_full_sinks(params)
    elif omission == "no-value-scale":
        cfg["attention_value_scale"] = 1.0
    elif omission == "window-plus-one":
        cfg["sliding_window"] = WINDOW + 1
    elif omission == "sliding-at-the-full-base":
        cfg["swa_rope_theta"] = cfg["rope_theta"]
    else:
        _low_islands(monkeypatch)
    with jax.default_matmul_precision("highest"):
        other = jax.jit(lambda w: ref.forward(w, toks, cfg))(weights)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(got - other).max()) > 100 * TOL


def _served_logits(params, policy, toks, primes, bucket):
    """Logits of every position from ``prime - 1`` on, a row: the
    prefill's last position, then one decode step per token through the
    caches (rows of different primes step together, each at its own
    position)."""
    live = jnp.ones((toks.shape[0],), bool)
    primes = jnp.asarray(primes)
    first, per_token, _ = _prefill(params, toks[:, :bucket], primes, policy)
    caches = jitted(mm.caches_from)(per_token, primes, TINY, MAX_LEN)
    out = [first[:, 0]]
    for i in range(T - int(primes.max())):
        pos = primes + i
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        logits, caches, _ = jitted(mm.decode_step)(
            params, tok, pos, caches, live, TINY, policy)
        out.append(logits)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("primes,bucket,mixed,tol", [
    ((1, WINDOW - 1), 8, False, TOL), ((WINDOW, WINDOW + 1), 8, False, TOL),
    ((13, 2), 16, False, TOL), ((WINDOW, WINDOW + 1), 8, True, 0.3)],
    ids=["one-token-and-under-the-window", "at-and-past-the-window",
         "past-a-chunk-beside-two", "bf16-params-and-compute"])
def test_unequal_rows_prefilled_then_decoded_match_the_reference(
        primes, bucket, mixed, tol):
    params, policy = make(mixed=mixed)
    toks = _tokens()
    start = max(primes)
    want = _reference(params, toks)
    with jax.default_matmul_precision("highest"):
        got = _served_logits(params, policy, toks, primes, bucket)
    assert got.dtype == jnp.float32
    for row, prime in enumerate(primes):
        # step i of a row stands on position prime + i - 1
        steps = T - start + 1
        diff = jnp.abs(got[row] - want[row, prime - 1:prime - 1 + steps])
        # float32: every logit.  bfloat16 at a width of 32 flips some
        # routings (near-ties of 8 sigmoids), and a flipped expert moves a
        # token's logits by 1: there the root mean square is held
        assert float(jnp.sqrt(jnp.mean(diff ** 2)) if mixed
                     else diff.max()) < tol
    assert T - min(primes) > 2 * WINDOW     # every row's rings wrapped


def test_a_slot_holds_a_ring_of_one_head_shape_and_grown_keys_of_another():
    _, policy = make()
    family = mm.MiMoV2Family(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    assert {n: (c["k"].shape, c["v"].shape) for n, c in caches.items()} == {
        **{f"l{i}": ((3, 2, WINDOW, 12), (3, 2, WINDOW, 8))
           for i in (1, 2, 3, 4, 6)},
        **{f"l{i}": ((3, 1, MAX_LEN, 12), (3, 1, MAX_LEN, 8))
           for i in (0, 5)}}
    ring, grown = family.blocks["l1"], family.blocks["l0"]
    assert ring.sink and not grown.sink
    pos = jnp.array([0, 3, 4, 21])
    at, counts = ring.place(pos, WINDOW)
    assert at.tolist() == [0, 3, 0, 1] and counts.tolist() == [1, 4, 4, 4]
    at, counts = grown.place(pos, MAX_LEN)
    assert at.tolist() == [0, 3, 4, 21] and counts.tolist() == [1, 4, 5, 22]
    # a row of a ring and a row of grown keys cost different bytes
    assert ring.row_bytes(jnp.bfloat16) == 2 * (12 + 8) * 2
    assert grown.row_bytes(jnp.bfloat16) == 1 * (12 + 8) * 2
    # the published shapes: 5,120 and 2,560 B a token
    whole = mm.blocks_of(mm.MiMoV2Config())
    assert whole["l1"].row_bytes(jnp.bfloat16) == 8 * 320 * 2
    assert whole["l0"].row_bytes(jnp.bfloat16) == 4 * 320 * 2
    assert whole["l1"].rows(17408) == 128 and whole["l0"].rows(17408) == 17408


def test_decode_counts_rows_contexts_windows_and_the_bytes_each_kind_reads():
    params, policy = make()
    family = mm.MiMoV2Family(TINY, policy)
    caches = family.init_caches(3, MAX_LEN)
    live = jnp.array([True, False, True])
    pos = jnp.array([2, 30, 20])
    _, _, stats, chosen = jax.jit(functools.partial(
        mm.decode_step, config=TINY, policy=policy, with_choices=True))(
        params, jnp.array([4, 5, 6]), pos, caches, live)
    assert chosen.shape == (6, 3, TINY.num_experts_per_tok)
    assert float(stats["moe.decode_layers"]) == 6
    assert float(stats["attn.decode_rows"]) == 2
    assert float(stats["attn.context_tokens"]) == 3 + 21
    assert float(stats["attn.window_tokens"]) == 3 + WINDOW
    # the XLA core reads every row of every slot: one block of each kind
    assert float(stats["attn.window_rows_read"]) == 3 * WINDOW
    assert float(stats["attn.full_rows_read"]) == 3 * MAX_LEN
    assert set(stats) == set(mm.STAT_KEYS)
    assert not set(kv.BYTE_GAUGES) & set(mm.STAT_KEYS)  # none rides the scan
    # published: each kind's rows at its OWN row bytes, times its blocks
    gauges = family.publish(jax.tree.map(np.asarray, stats))
    f32 = 4
    assert gauges["attn.window_bytes_read"] == (
        3 * WINDOW * 5 * 2 * (12 + 8) * f32)
    assert gauges["attn.full_bytes_read"] == (
        3 * MAX_LEN * 2 * 1 * (12 + 8) * f32)
    assert gauges["moe.held_assignments"] == 6 * 2 * 2
    # a family without such a block publishes no byte gauge
    assert kv.byte_gauges({"l0": object()}, gauges, jnp.float32) == {}


# ------------------------------------------------------------ the experts


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips with 2 of the 8 experts each: the terms they compute,
    with everything outside the experts (the residual stream that attention
    left) counted ONCE, add up to the uncut reference's layer."""
    params, _ = make()
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.key(8), (19, TINY.hidden_size))
    live = jnp.ones((19,), bool)
    eps = TINY.layernorm_epsilon
    with jax.default_matmul_precision("highest"):
        u = ref.rms_norm(x, layer["norm"][1], eps)
        whole, ids = ref.routed(u, layer["router"], layer["experts"],
                                as_dict(TINY))
        want = x + whole
        total, held = x, 0.0
        for first in range(0, 8, 2):
            c = dataclasses.replace(TINY, experts_held=2, first_expert=first)
            share = {**layer, "experts": {
                k: w[first:first + 2] for k, w in layer["experts"].items()}}
            term, share_ids, stats = mm.moe_share(
                mm.rms_norm(x, layer["norm"][1], eps), share, c, live)
            np.testing.assert_array_equal(np.sort(share_ids, -1),
                                          np.sort(ids, -1))
            total = total + term
            held += float(stats["moe.held_load"].sum())
            # the reference, given the same share, gives the same part
            part, _ = ref.routed(u, share["router"], share["experts"],
                                 as_dict(c))
            np.testing.assert_allclose(term, part, atol=TOL)
    np.testing.assert_allclose(total, want, atol=TOL)
    assert held == 19 * TINY.num_experts_per_tok    # every assignment once
    assert float(jnp.abs(whole).mean()) > 0.05      # the experts weigh


def test_the_bias_picks_and_does_not_weigh():
    params, _ = make()
    router = params["layers"][1]["router"]
    u = jax.random.normal(jax.random.key(5), (64, TINY.hidden_size))
    unbiased = {**router, "bias": jnp.zeros_like(router["bias"])}
    pushed = {**router, "bias": router["bias"].at[2].add(10.0)}
    with jax.default_matmul_precision("highest"):
        ids, w = mm.route(u, router, TINY)
        ref_ids, ref_w = ref.route(u, router, as_dict(TINY))
        plain_ids, _ = mm.route(u, unbiased, TINY)
        pushed_ids, pushed_w = mm.route(u, pushed, TINY)
        scores = jax.nn.sigmoid(u @ router["w"])
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(ref_ids, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(ref_w, -1), rtol=1e-5)
    # the seeded bias changes some token's choice ...
    assert bool((jnp.sort(ids, -1) != jnp.sort(plain_ids, -1)).any())
    # ... a large one forces its expert on every token ...
    assert bool((pushed_ids == 2).any(-1).all())
    # ... and no weight ever holds it: they are the chosen sigmoids over
    # their sum (``routed_scaling_factor`` null: times 1), whatever the bias
    for i, ww in ((ids, w), (pushed_ids, pushed_w)):
        s = jnp.take_along_axis(scores, i, -1)
        np.testing.assert_allclose(ww, s / s.sum(-1, keepdims=True),
                                   rtol=1e-5)


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = mm.MiMoV2Config.from_dict({
        "num_hidden_layers": 3, "hybrid_layer_pattern": [0, 1, 1],
        "moe_layer_freq": [0, 1, 1], "routed_scaling_factor": None,
        "n_shared_experts": None, "model_type": "mimo_v2", "unknown": 1,
        "rope_scaling": {"rope_type": "default"}})
    assert c.hybrid_layer_pattern == (0, 1, 1) and hash(c) is not None
    assert c.heads_of(mm.SLIDING) == (64, 8, 192, 128)
    assert c.heads_of(mm.FULL) == (64, 4, 192, 128)
    assert (c.theta_of(mm.SLIDING), c.theta_of(mm.FULL)) == (1e4, 1e7)
    assert c.sink_of(mm.SLIDING) and not c.sink_of(mm.FULL)
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        mm.MiMoV2Config(num_hidden_layers=3, hybrid_layer_pattern=(0, 1))
    with pytest.raises(ValueError, match="moe_layer_freq"):
        mm.MiMoV2Config(num_hidden_layers=2, moe_layer_freq=(0, 2))
    with pytest.raises(ValueError, match="routed experts"):
        dataclasses.replace(TINY, first_expert=7, experts_held=2)
    with pytest.raises(ValueError, match="key/value heads"):
        dataclasses.replace(TINY, swa_num_key_value_heads=3)
    with pytest.raises(ValueError, match="whole number of pairs"):
        dataclasses.replace(TINY, partial_rotary_factor=0.3)
    for other in (dict(scoring_func="softmax"), dict(n_group=2),
                  dict(topk_group=2), dict(n_shared_experts=1)):
        with pytest.raises(ValueError, match="sigmoid top-k"):
            dataclasses.replace(TINY, **other)
    # a share's window of the grouped product: 0.5 assignments a token
    share = mm.MiMoV2Config(experts_held=16)
    assert experts.moe_capacity(share, 16384) == 16384


# ------------------------------------------------------ the attention cores


def _dense_attention(q, k, v, scale, window, sink):
    """One dense ``(P, P)`` softmax a head with the sink as an extra
    column: ``q (P, H, d)``, ``k (KV, P, d)``, ``v (KV, P, dv)``."""
    p, heads, _ = q.shape
    group = heads // k.shape[0]
    gap = np.arange(p)[:, None] - np.arange(p)[None, :]
    seen = (gap >= 0) if window is None else (gap >= 0) & (gap < window)
    out = []
    for h in range(heads):
        s = np.where(seen, q[:, h] @ k[h // group].T * scale, -np.inf)
        if sink is not None:
            s = np.concatenate([s, np.full((p, 1), sink[h])], axis=-1)
        e = np.exp(s - s.max(-1, keepdims=True))
        probs = (e / e.sum(-1, keepdims=True))[:, :p]
        out.append(probs @ v[h // group])
    return np.stack(out, axis=1).reshape(p, -1)


def _core_inputs(p=40):
    ks = jax.random.split(jax.random.key(2), 4)
    return (jax.random.normal(ks[0], (2, p, 4, 12)),
            jax.random.normal(ks[1], (2, 2, p, 12)),
            jax.random.normal(ks[2], (2, 2, p, 8)),
            4 + jax.random.normal(ks[3], (4,)))


@pytest.mark.parametrize("with_sink", [True, False], ids=["sink", "no-sink"])
@pytest.mark.parametrize("window", [None, 5, 16, 24, 700], ids=[
    "full", "below-the-block", "at-the-block", "above-the-block",
    "past-the-row"])
def test_blocked_prefill_core_with_a_sink_and_two_widths(window, with_sink,
                                                         monkeypatch):
    monkeypatch.setattr(gqa, "QUERY_BLOCK", 16)     # three blocks of 40 ...
    monkeypatch.setattr(gqa, "FULL_GROUP", 2)       # ... in two groups
    q, k, v, sink = _core_inputs()
    sink = sink if with_sink else None
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(
            gqa.prefill_attention, scale=0.3, window=window))(
            q, k, v, sink=sink)
    assert got.shape == (2, 40, 4 * 8)
    for r in range(2):
        want = _dense_attention(
            *(np.asarray(a[r], np.float64) for a in (q, k, v)), 0.3, window,
            None if sink is None else np.asarray(sink, np.float64))
        np.testing.assert_allclose(got[r], want, atol=1e-5)
    # a window under the block still costs a whole block and the window
    # before it: the pairs are counted as they are computed
    if window == 5:
        lengths = jnp.array([40, 40])
        assert float(gqa.pairs_visited(lengths, 40, 5, "xla")) == (
            2 * 3 * 16 * (16 + 5))
        assert float(gqa.pairs_allowed(lengths, 5)) == 2 * (15 + 35 * 5)


def test_the_sink_takes_mass_and_has_no_value():
    q, k, v, sink = _core_inputs(8)
    core = jax.jit(functools.partial(gqa.prefill_attention, scale=0.3))
    with jax.default_matmul_precision("highest"):
        plain = core(q, k, v)
        sunk = core(q, k, v, sink=sink)
        far = core(q, k, v, sink=jnp.full((4,), -1e4))
    # the outputs shrink towards zero, head by head, and never grow
    ratio = np.asarray(sunk[0, 0]) / np.asarray(plain[0, 0])
    assert (ratio > 0).all() and (ratio < 1).all()
    np.testing.assert_allclose(far, plain, atol=1e-6)


@pytest.mark.parametrize("with_sink", [True, False], ids=["sink", "no-sink"])
def test_decode_core_with_a_sink_and_two_widths(with_sink):
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (3, 4, 12))
    k = jax.random.normal(ks[1], (3, 2, 12, 12))
    v = jax.random.normal(ks[2], (3, 2, 12, 8))
    sink = 4 + jax.random.normal(ks[3], (4,)) if with_sink else None
    counts = jnp.array([1, 7, 12])
    with jax.default_matmul_precision("highest"):
        got = gqa.decode_attention(q, k, v, counts, 0.3, sink=sink)
        junk = gqa.xla_decode_attention(
            q, k.at[1, :, 7:].set(1e4), v.at[1, :, 7:].set(1e4), counts, 0.3,
            sink)
    assert got.shape == (3, 4 * 8)
    for s, n in enumerate(counts.tolist()):
        # the last of n tokens attending causally over all n
        qs = np.zeros((n, 4, 12))
        qs[-1] = q[s]
        want = _dense_attention(
            qs, np.asarray(k[s, :, :n], np.float64),
            np.asarray(v[s, :, :n], np.float64), 0.3, None,
            None if sink is None else np.asarray(sink, np.float64))[-1]
        np.testing.assert_allclose(got[s], want, atol=1e-5)
    np.testing.assert_array_equal(junk, got)


def test_the_decode_kernel_takes_two_widths_and_the_rest_is_declined(
        monkeypatch):
    """On a TPU: the one-query decode kernel takes the FULL layers' grown
    caches, keys 192 wide beside values of 128 (PR 55), and the PREFILL
    kernel the same two widths (PR 62: the keys padded to 256 on the way
    in); a sink or a ring under a tile, each alone, keeps the sliding layers
    on the XLA decode core, and the prefill kernel still declines a sink,
    values that are no whole lane tiles — 64, or 192 at one width — and a
    window of 128 (``ops/gqa.py``'s docstring)."""
    monkeypatch.setattr(gqa, "_on_tpu", lambda: True)
    bf = jnp.bfloat16
    assert gqa.prefill_lowering(1024, 128, bf, None) == "pallas"
    assert gqa.prefill_lowering(1024, 128, bf, None, dv=128) == "pallas"
    assert gqa.prefill_lowering(1024, 128, bf, None, sink=True) == "xla"
    assert gqa.prefill_lowering(1024, 128, bf, None, dv=64) == "xla"
    assert gqa.prefill_lowering(1024, 192, bf, None) == "xla"
    assert gqa.prefill_lowering(1024, 192, bf, None, dv=128) == "pallas"
    assert gqa.prefill_lowering(1024, 128, bf, 128) == "xla"
    sd = jax.ShapeDtypeStruct
    k = sd((16, 4, 1024, 128), bf)
    assert gqa.decode_lowering(bf, k, k) == "pallas"
    assert gqa.decode_lowering(bf, k, k, sink=True) == "xla"
    assert gqa.decode_lowering(bf, sd((16, 4, 1024, 256), bf), k) == "pallas"
    grown = (sd((16, 4, 17408, 192), bf), sd((16, 4, 17408, 128), bf))
    assert gqa.decode_lowering(bf, *grown) == "pallas"
    assert gqa.decode_lowering(bf, *grown, sink=True) == "xla"
    assert gqa.decode_lowering(jnp.float32, *grown) == "xla"
    ring = (sd((16, 8, 128, 192), bf), sd((16, 8, 128, 128), bf))
    assert gqa.decode_lowering(bf, *ring) == "xla"
    assert gqa.decode_lowering(bf, *ring, sink=True) == "xla"
    # the cell's engine: one kind a lowering, and the full layers' counter
    # stops at a slot's count while the rings' reads every row
    c = mm.MiMoV2Config(num_hidden_layers=7)
    blocks = mm.blocks_of(c)
    caches = {"l0": dict(zip("kv", grown)), "l1": dict(zip("kv", ring))}
    pos = jnp.array([0, 1023, 1024] + [6000] * 13)
    stats = mm.attention_stats(
        {n: blocks[n] for n in caches}, caches, pos, jnp.ones(16, bool))
    assert float(stats["attn.full_rows_read"]) == 1024 * (1 + 1 + 2 + 13 * 6)
    assert float(stats["attn.window_rows_read"]) == 16 * 128
    # and the prefill's counters follow the lowering that runs
    stats = mm.prefill_attention_stats(blocks, 512, jnp.array([512]), bf)
    sliding = 2 * 256 * (256 + 128)     # a block sees itself + the window
    full = 2 * 256 * 512                # two blocks share the keys of both
    assert float(stats["attn.prefill_pairs_visited"]) == (
        5 * sliding + 2 * full)
    assert float(stats["attn.prefill_pairs_allowed"]) == (
        5 * (128 * 129 / 2 + 384 * 128) + 2 * 512 * 513 / 2)
