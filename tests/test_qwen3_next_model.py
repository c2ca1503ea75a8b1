"""Qwen3-Next (``models/qwen3_next.py``) against its plain reference
(``perf/lib/reference_qwen3next.py``) at tiny widths on the CPU, seeded
weights: the forward over right-padded rows, prefill then decode through the
blocks' caches, a float32 island (a bfloat16 one fails the tolerance), the
chunked delta rule against the recurrence token by token at a chunk's edges,
the one-token step, which key head a value head reads, the gate after the
norm, the rotation over a quarter of a head, what each kind of layer states
about its cache and the config's refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_qwen3next as ref
from progen_tpu.models import driver, kv, state
from progen_tpu.models import qwen3_next as qn
from progen_tpu.ops import gdn
from progen_tpu.ops.lowering import record_lowerings
from tests.families import fresh, jitted, reference
from tests.qwen3_next_tiny import TINY, as_dict, make

F32 = jnp.float32
MAX_LEN = 32
# float32 on both sides: what differs is the order of sums (the chunked form
# against the token-by-token recurrence, ragged windows against a dense loop
# over experts, the blocked softmax), a few 1e-6 on logits of spread 1; a
# bfloat16 island moves a logit by 1e-3 and more (the island's test below)
TOL = 4e-5
LENGTHS = (13, 1, 2, 24)        # across chunks of 4; under the four taps


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
    with record_lowerings() as chosen:
        logits, handed, stats = fresh(qn.prefill)(
            params, rows, lengths, TINY, policy, logit_positions=at)
    for i, n in enumerate(LENGTHS):
        assert np.abs(np.asarray(logits[i, :n]) - wanted[i, :n]).max() < TOL
    assert float(wanted.std()) > 0.5            # not a vacuous bound
    assert chosen["gdn_prefill"] == {"xla"} and "gdn_step" not in chosen
    assert sorted(handed) == [f"l{i}" for i in range(8)]
    assert sorted(handed["l0"]) == ["conv", "state"]
    assert sorted(handed["l3"]) == ["k", "v"]
    tokens = sum(LENGTHS)
    assert stats["moe.tokens"] == 8 * tokens
    assert stats["moe.held_load"].sum() == stats["moe.prefill_held"] == (
        8 * tokens * TINY.num_experts_per_tok)
    assert stats["gdn.real_tokens"] == 6 * tokens
    assert stats["gdn.scan_slots"] == 6 * 4 * 24        # whole chunks of 4


def test_prefill_then_decode_is_the_references_full_forward(weights, rows,
                                                            wanted):
    """Unequal right-padded rows (1 and 2 tokens: shorter than the taps; 13:
    across three chunks) prefilled, laid out as slots, then decoded token by
    token: every step's logits are the reference's at that position."""
    params, policy = weights
    lengths = jnp.asarray([13, 1, 2, 20], jnp.int32)
    _, handed, _ = jitted(qn.prefill)(params, rows, lengths, TINY, policy)
    caches = jitted(qn.caches_from)(handed, lengths, TINY, MAX_LEN)
    live = jnp.ones((4,), bool)
    for j in range(3):
        pos = lengths + j
        tok = rows[jnp.arange(4), pos]
        logits, caches, stats = jitted(qn.decode_step)(
            params, tok, pos, caches, live, TINY, policy)
        want = wanted[np.arange(4), np.asarray(pos)]
        assert np.abs(np.asarray(logits) - want).max() < TOL, j
    assert stats["gdn.state_bytes"] == 2 * 6 * 4 * (4 * 8 * 8 * 4)
    assert stats["moe.decode_layers"] == 8 and stats["moe.tokens"] == 32
    assert stats["attn.decode_rows"] == 4
    assert stats["attn.context_tokens"] == float(jnp.sum(pos + 1))


def test_a_bfloat16_carry_fails_the_tolerance(monkeypatch, weights, rows,
                                              wanted):
    """The comparison is tight enough to tell: the carry re-rounded every
    token moves a logit by more than the tolerance the float32 program
    keeps."""
    params, policy = weights
    step = gdn.gdn_step

    def rounded(carry, *a):
        o, new = step(carry.astype(jnp.bfloat16).astype(F32), *a)
        return o, new.astype(jnp.bfloat16).astype(F32)

    monkeypatch.setattr(gdn, "gdn_step", rounded)
    lengths = jnp.asarray([13, 9, 12, 20], jnp.int32)
    _, handed, _ = jitted(qn.prefill)(params, rows, lengths, TINY, policy)
    caches = jitted(qn.caches_from)(handed, lengths, TINY, MAX_LEN)
    worst, step_fn = 0.0, fresh(qn.decode_step)
    for j in range(3):
        pos = lengths + j
        logits, caches, _ = step_fn(
            params, rows[jnp.arange(4), pos], pos, caches,
            jnp.ones((4,), bool), TINY, policy)
        want = wanted[np.arange(4), np.asarray(pos)]
        worst = max(worst, float(np.abs(np.asarray(logits) - want).max()))
    assert worst > 10 * TOL


# ----------------------------------------------------------- the delta rule


def _delta_inputs(r, p, hk=2, hv=4, dk=8, dv=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (r, p, hk, dk))
    k = jax.random.normal(ks[1], (r, p, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (r, p, hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (r, p, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, p, hv)))
    return q, k, v, g, beta


@jax.jit
def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence over one row ``(P, ...)``: every output
    and the carry after every token."""
    e = v.shape[1] // k.shape[1]
    q, k = (jnp.repeat(a, e, axis=1) for a in (q, k))

    def token(s, at):
        s, o = ref.delta_token(s, *at)
        return s, (o, s)

    zero = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, zero, (q, k, v, jnp.exp(g), beta))[1]


C = 4
_scan = jax.jit(gdn.gdn_scan, static_argnames="chunk")


@pytest.mark.parametrize("lengths,bucket", [
    ((0, 1, C - 1, C, C + 1), 8), ((13, 7, 16, 2, 0), 16)],
    ids=["a-chunks-edges", "a-padded-bucket"])
def test_the_chunked_form_is_the_recurrence_token_by_token(lengths, bucket):
    """Rows of 0, 1, C - 1, C and C + 1 tokens, and rows padded to a bucket
    past whole chunks: the outputs at real positions and the carry AT EACH
    ROW'S TRUE LENGTH (zeros for a row of length 0) are the recurrence's,
    whatever the padding holds."""
    q, k, v, g, beta = _delta_inputs(len(lengths), bucket, seed=bucket)
    with jax.default_matmul_precision("highest"):
        o, carry = _scan(q, k, v, g, beta, jnp.asarray(lengths), chunk=C)
        for i, n in enumerate(lengths):
            want_o, carries = _token_by_token(q[i], k[i], v[i], g[i],
                                              beta[i])
            want = carries[n - 1] if n else jnp.zeros_like(carries[0])
            assert float(jnp.abs(carry[i] - want).max()) < 1e-6, (i, n)
            if n:
                assert float(jnp.abs(o[i, :n] - want_o[:n]).max()) < 1e-6
    assert bool(jnp.isfinite(o).all())
    assert float(jnp.abs(carry).max()) > 0.5
    assert gdn.scanned_slots(len(lengths), bucket, C) == len(lengths) * bucket


@pytest.mark.parametrize("c", [1, 2, 3, 4, 5, 8, 64])
def test_substitution_inverts_a_unit_lower_triangle(c):
    a = jnp.tril(jax.random.normal(jax.random.key(c), (3, c, c)), -1) * 0.3
    with jax.default_matmul_precision("highest"):
        t = gdn.unit_lower_inverse(a)
        eye = jnp.eye(c)
        np.testing.assert_allclose(t @ (eye - a), jnp.broadcast_to(
            eye, a.shape), atol=1e-5)


@pytest.mark.parametrize("beta,decay", [(0.5, 1.0), (0.9, 0.97), (1.0, 1.0)])
def test_a_chunk_of_one_repeated_token_keeps_its_digits(beta, decay):
    """Equal keys all through a chunk — a run of one token — make ``A``'s
    entries ``-beta decay^(i - j)``: the inverse is bounded by 1, and the
    series of powers ``(I + A)(I + A^2)...`` would form terms up to ``C(62,
    k) beta^k`` on its way there (160 off at ``beta`` 0.5 in float32 here, 176
    on the chip).
    Forward substitution stays at a rounding of the float64 inverse."""
    i = np.arange(64)
    a = -beta * np.tril(np.ones((64, 64)), -1) * decay ** (
        i[:, None] - i[None, :])
    want = np.linalg.inv(np.eye(64) - a)
    with jax.default_matmul_precision("highest"):
        got = gdn.unit_lower_inverse(jnp.asarray(a, F32)[None])[0]
    assert np.abs(want).max() <= 1.0
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-5


def test_the_step_is_the_recurrence_and_a_value_head_reads_key_head_j_over_2():
    """One token a slot against the reference's token; and with key head 0
    zeroed the value heads 0 and 1 (``j // 2 == 0``) write and read nothing
    while heads 2 and 3 are untouched."""
    q, k, v, g, beta = (a[:, 0] for a in _delta_inputs(3, 1, seed=2))
    carry = jax.random.normal(jax.random.key(9), (3, 4, 8, 8))
    o, new = jitted(gdn.gdn_step)(carry, q, k, v, g, beta)
    for i in range(3):
        want_s, want_o = ref.delta_token(
            carry[i], jnp.repeat(q[i], 2, 0), jnp.repeat(k[i], 2, 0), v[i],
            jnp.exp(g[i]), beta[i])
        np.testing.assert_allclose(new[i], want_s, atol=1e-6)
        np.testing.assert_allclose(o[i], want_o, atol=1e-6)
    zeroed = k.at[:, 0].set(0.0)
    o0, new0 = jitted(gdn.gdn_step)(carry, q.at[:, 0].set(0.0), zeroed, v,
                                    g, beta)
    decayed = carry * jnp.exp(g)[..., None, None]
    np.testing.assert_allclose(new0[:, :2], decayed[:, :2], atol=1e-6)
    assert not np.asarray(o0[:, :2]).any()
    np.testing.assert_allclose(new0[:, 2:], new[:, 2:], atol=1e-6)
    np.testing.assert_allclose(o0[:, 2:], o[:, 2:], atol=1e-6)


def test_the_gate_comes_after_the_norm_with_one_plain_weight(weights):
    block = qn.delta_block(TINY)
    ks = jax.random.split(jax.random.key(1), 2)
    o = jax.random.normal(ks[0], (5, 4, 8))
    z = jax.random.normal(ks[1], (5, 32))
    p = dict(weights[0]["layers"][0]["mixer"], out_proj=jnp.eye(32))
    assert p["norm"].shape == (8,)
    got = block._out(o, z, p)
    want = (driver.rms_norm(o, p["norm"], TINY.rms_norm_eps).reshape(5, 32)
            * jax.nn.silu(z))
    np.testing.assert_allclose(got, want, atol=1e-6)
    before = driver.rms_norm(o * jax.nn.silu(z).reshape(o.shape), p["norm"],
                             TINY.rms_norm_eps).reshape(5, 32)
    assert float(jnp.abs(got - before).max()) > 1e-2    # not Mamba-2's


# ------------------------------------------------------------ attention


def test_a_quarter_of_a_head_is_rotated_and_the_gate_is_the_projections(
        weights):
    """Of a head's 16 columns the first 4 move with the position and 12 do
    not; at position 0 nothing moves; the gate is the second half of each
    head's 32 projected columns, an element each."""
    block = qn.AttentionBlock(TINY)
    p = weights[0]["layers"][3]["mixer"]
    x = jax.random.normal(jax.random.key(4), (1, 6, TINY.hidden_size))
    at = jnp.arange(6)[None]
    q, k, v, gate = block.project(x, p, at)
    q0, k0, _, _ = block.project(x, p, jnp.zeros_like(at))
    assert q.shape == (1, 6, 4, 16) and k.shape == v.shape == (1, 6, 2, 16)
    assert TINY.rotary_dim == 4
    np.testing.assert_array_equal(q[..., 4:], q0[..., 4:])
    np.testing.assert_array_equal(k[..., 4:], k0[..., 4:])
    np.testing.assert_array_equal(q[:, 0], q0[:, 0])
    assert float(jnp.abs(q[:, 1:, :, :4] - q0[:, 1:, :, :4]).min()) > 1e-6
    qg = (x @ p["wq"]).reshape(1, 6, 4, 32)
    np.testing.assert_allclose(gate, qg[..., 16:].reshape(1, 6, 64),
                               atol=1e-6)
    # the zero-centred norm: q before the rotation is N(q) (1 + w)
    want = driver.rms_norm(qg[..., :16], 1.0 + p["q_norm"],
                           TINY.rms_norm_eps)
    np.testing.assert_allclose(q0, want, atol=1e-6)
    o = jax.random.normal(jax.random.key(5), (1, 6, 64))
    np.testing.assert_allclose(
        block.finish(o, gate, p), (o * jax.nn.sigmoid(gate)) @ p["wo"],
        atol=1e-5)


# ------------------------------------------------------ caches, config


def test_each_kind_of_layer_states_its_own_cache():
    blocks = qn.blocks_of(TINY)
    assert list(blocks) == [f"l{i}" for i in range(8)]
    assert [isinstance(b, kv.KVBlock) for b in blocks.values()] == [
        False, False, False, True] * 2
    assert all(isinstance(blocks[f"l{i}"], state.DeltaBlock)
               for i in (0, 1, 2, 4, 5, 6))
    assert blocks["l3"].window is None
    assert blocks["l3"].scale == pytest.approx(TINY.head_dim ** -0.5)
    family = qn.Qwen3NextFamily(TINY, make()[1])
    for max_len in (16, 4096):      # the state does not depend on it
        caches = jax.eval_shape(lambda: family.init_caches(3, max_len))
        assert caches["l0"]["state"].shape == (3, 4, 8, 8)
        assert caches["l0"]["state"].dtype == jnp.float32
        assert caches["l0"]["conv"].shape == (3, 3, 64)
        assert caches["l3"]["k"].shape == (3, 2, max_len, 16)
    # the published widths: 2.10 MB of carry and 49 KB of tail a slot
    whole = qn.delta_block(qn.Qwen3NextConfig())
    shapes = jax.eval_shape(lambda: whole.init_cache(1, 3072, jnp.bfloat16))
    assert shapes["state"].shape == (1, 32, 128, 128)
    assert shapes["conv"].shape == (1, 3, 8192)
    assert whole.state_bytes() == 2097152 and whole.chunk == 64
    assert qn.Qwen3NextConfig().rotary_dim == 64


@pytest.mark.parametrize("change,message", [
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers=(0,)), "mlp_only_layers"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(first_expert=14, experts_held=4), "routed experts"),
    (dict(linear_num_value_heads=5), "value heads"),
    (dict(partial_rotary_factor=0.3), "pairs"),
    (dict(num_key_value_heads=3), "key/value")])
def test_a_config_the_served_model_does_not_have_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(TINY, **change)


def test_the_published_kinds_and_from_dict():
    whole = qn.Qwen3NextConfig()
    assert whole.layer_types.count(qn.DELTA) == 36
    assert whole.layer_types.count(qn.FULL) == 12
    assert whole.layer_types[:4] == (qn.DELTA,) * 3 + (qn.FULL,)
    c = qn.Qwen3NextConfig.from_dict(
        dict(as_dict(TINY), model_type="qwen3_next", dt_range=[0.01, 0.2]))
    assert c == TINY
    with pytest.raises(ValueError, match="key heads"):
        state.DeltaBlock(3, 4, 8, 8, 4, 1e-6, 4)
