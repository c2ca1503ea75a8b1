"""Qwen3-Next (``models/qwen3_next.py``) against its plain reference
(``perf/lib/reference_qwen3next.py``) at tiny widths on the CPU, seeded
weights: the forward over right-padded rows, prefill then decode through the
blocks' caches, a float32 island (a bfloat16 one fails the tolerance), the
chunked delta rule against the recurrence token by token at a chunk's edges,
the one-token step, which key head a value head reads, the gate after the
norm, the rotation over a quarter of a head, what each kind of layer states
about its cache and the config's refusals."""

import dataclasses
import functools

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
    """The reference's recurrence over rows ``(R, P, ...)``: every output
    and the carry after every token, a row."""
    def row(q, k, v, g, beta):
        e = v.shape[1] // k.shape[1]
        q, k = (jnp.repeat(a, e, axis=1) for a in (q, k))

        def token(s, at):
            s, o = ref.delta_token(s, *at)
            return s, (o, s)

        zero = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), F32)
        return jax.lax.scan(token, zero, (q, k, v, jnp.exp(g), beta))[1]

    return jax.vmap(row)(q, k, v, g, beta)


C = 4
WIDE = dict(dk=128, dv=128)     # the kernel's widths: whole lane tiles


@functools.lru_cache(maxsize=None)
def _form(kernel: bool, chunk: int, step=None):
    """``gdn_scan``'s XLA form, or its kernel under the interpreter, in
    chunks of ``chunk``: ONE program a ``step`` (the tokens a grid step that
    the caller has patched into ``gdn.STEP_TOKENS``, which the trace reads)
    and shape — ``lengths`` is a runtime scalar, so cases that differ in it
    alone share a compiled body."""
    form = (functools.partial(gdn.pallas_gdn_scan, interpret=True)
            if kernel else gdn.xla_gdn_scan)
    return jax.jit(lambda *a: form(*a, chunk))


# (the kernel?, chunk, tokens a grid step, lengths, bucket)
CHUNKED = {
    "a-chunks-edges": (False, C, None, (0, 1, C - 1, C, C + 1), 8),
    "a-padded-bucket": (False, C, None, (13, 7, 16, 2, 0), 16),
    "kernel-a-chunks-edges": (True, 16, None, (0, 1, 15, 16, 17), 32),
    # three grid steps of two chunks a row: a row that ends in the last, one
    # in the first and one at the second's first token
    "kernel-rows-end-in-other-steps": (True, 16, 32, (90, 20, 33), 96),
    "kernel-a-bucket-past-whole-chunks": (True, 16, None, (40, 7), 40),
    "kernel-chunks-of-64": (True, 64, None, (128, 65), 128),
}


@pytest.mark.parametrize("case", CHUNKED)
def test_the_chunked_form_is_the_recurrence_token_by_token(case, monkeypatch):
    """Rows of 0, 1, C - 1, C and C + 1 tokens, and rows padded to a bucket
    past whole chunks: the outputs at real positions and the carry AT EACH
    ROW'S TRUE LENGTH (zeros for a row of length 0) are the recurrence's,
    whatever the padding holds — by the XLA form and by the kernel, whose
    rows also end in different grid steps.  Compared on the host: a slice a
    length is no program of its own."""
    kernel, chunk, step, lengths, bucket = CHUNKED[case]
    if step:
        monkeypatch.setattr(gdn, "STEP_TOKENS", step)
    inputs = _delta_inputs(len(lengths), bucket, seed=bucket,
                           **(WIDE if kernel else {}))
    with jax.default_matmul_precision("highest"):
        o, carry = (np.asarray(a) for a in _form(kernel, chunk, step)(
            *inputs, jnp.asarray(lengths)))
        want_o, carries = (np.asarray(a) for a in _token_by_token(*inputs))
    for i, n in enumerate(lengths):
        want = carries[i, n - 1] if n else np.zeros_like(carries[i, 0])
        assert np.abs(carry[i] - want).max() < 1e-6, (i, n)
        assert np.abs(o[i, :n] - want_o[i, :n]).max(initial=0) < 1e-6, (i, n)
    assert np.isfinite(o).all()
    # not a vacuous bound (a unit key of 128 columns has smaller entries)
    assert np.abs(carry).max() > (0.3 if kernel else 0.5)
    assert gdn.scanned_slots(len(lengths), bucket, chunk) == (
        len(lengths) * -(-bucket // chunk) * chunk)
    if kernel:      # nothing is left in a chunk wholly past a row's length
        for i, n in enumerate(lengths):
            assert not o[i, -(-n // chunk) * chunk:].any()


def test_the_kernel_leaves_a_chunk_past_a_rows_length_alone(monkeypatch):
    """NaN in every chunk that lies wholly past its row's length (and in
    ``g`` and ``beta`` from the length on): ``o`` is zero there, and ``o``
    before it and the carry are, bit for bit, what clean inputs give.  The
    shape of ``kernel-rows-end-in-other-steps``: one compiled body."""
    lengths, chunk, bucket = (33, 0, 16), 16, 96
    monkeypatch.setattr(gdn, "STEP_TOKENS", 32)
    clean = _delta_inputs(3, bucket, seed=5, **WIDE)
    past = (jnp.arange(bucket)[None, :]
            >= -(-jnp.asarray(lengths) // chunk)[:, None] * chunk)
    at = jnp.arange(bucket)[None, :] >= jnp.asarray(lengths)[:, None]
    q, k, v = (jnp.where(past[..., None, None], jnp.nan, x)
               for x in clean[:3])
    g, beta = (jnp.where(at[..., None], jnp.nan, x) for x in clean[3:])
    scan = _form(True, chunk, 32)
    o, carry = scan(q, k, v, g, beta, jnp.asarray(lengths))
    want_o, want = scan(*clean, jnp.asarray(lengths))
    assert bool(jnp.isnan(q).any()) and bool(jnp.isnan(g).any())
    np.testing.assert_array_equal(carry, want)
    assert not np.asarray(carry[1]).any()
    assert not np.asarray(jnp.where(past[..., None, None], o, 0.0)).any()
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(o[i, :n], want_o[i, :n])


def test_the_scan_slots_counter_follows_the_lowering(monkeypatch):
    """``gdn.scan_slots`` counts what the traced lowering computes: every
    chunk of the bucket under the XLA form, whole chunks up to each row's
    length under the kernel — which only the published widths take."""
    wide = {f"l{i}": state.DeltaBlock(2, 4, 128, 128, 4, 1e-6, 64)
            for i in range(3)}
    lengths = jnp.asarray([65, 0, 512, 1], jnp.int32)
    stats = fresh(lambda n: state.delta_prefill_stats(wide, (4, 512), n))
    assert stats(lengths)["gdn.scan_slots"] == 3 * 4 * 512
    monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
    assert wide["l0"].scan_lowering(512) == "pallas"
    stats = fresh(lambda n: state.delta_prefill_stats(wide, (4, 512), n))
    assert stats(lengths)["gdn.scan_slots"] == 3 * (128 + 0 + 512 + 64)
    assert stats(lengths)["gdn.real_tokens"] == 3 * 578
    # a chunk that is not whole blocks of 16 rows, a width off the lane tile
    assert state.DeltaBlock(2, 4, 128, 128, 4, 1e-6, 24).scan_lowering(
        512) == "xla"
    assert qn.delta_block(TINY).scan_lowering(512) == "xla"


def test_a_delta_block_of_published_widths_prefills_through_the_kernel(
        monkeypatch):
    """``DeltaBlock.prefill`` at ``Dk = Dv = 128`` with the chip said to be
    there: the op notes ``"pallas"`` and the kernel (under the interpreter)
    hands over what the XLA form hands over — the mixer's output at real
    positions, the carry and the tail."""
    block = state.DeltaBlock(2, 4, 128, 128, 4, 1e-6, 16)
    p = block.init_weights(jax.random.key(3), 64, F32, (0.001, 0.1),
                           (1.0, 16.0))
    u = jax.random.normal(jax.random.key(4), (2, 48, 64))
    lengths = jnp.asarray([37, 16], jnp.int32)
    with jax.default_matmul_precision("highest"):
        with record_lowerings() as chosen:
            want, held = fresh(block.prefill)(u, p, lengths)
        assert chosen["gdn_prefill"] == {"xla"}
        monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
        monkeypatch.setattr(gdn, "pallas_gdn_scan", functools.partial(
            gdn.pallas_gdn_scan, interpret=True))
        with record_lowerings() as chosen:
            got, handed = fresh(block.prefill)(u, p, lengths)
    assert chosen["gdn_prefill"] == {"pallas"}
    for i, n in enumerate((37, 16)):
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=4e-6)
    np.testing.assert_allclose(handed["state"], held["state"], atol=4e-6)
    np.testing.assert_array_equal(handed["conv"], held["conv"])
    assert float(jnp.abs(held["state"]).max()) > 0.1


@jax.jit
def _inverse_in_a_kernel(a):
    """``gdn.blocked_lower_inverse`` as the kernel runs it: on one triangle
    in a kernel's memory, under the interpreter."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, t_ref):
        t_ref[...] = gdn.blocked_lower_inverse(a_ref[...])

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        a.shape, F32), interpret=True)(a)


INVERSES = {"substitution": lambda a: gdn.unit_lower_inverse(a[None])[0],
            "kernel": _inverse_in_a_kernel}


@pytest.mark.parametrize("form,c", [
    ("substitution", c) for c in (1, 2, 3, 4, 5, 8, 64)] + [
    ("kernel", c) for c in (16, 32, 48, 64)])
def test_substitution_inverts_a_unit_lower_triangle(form, c):
    """The XLA form's substitution at any size; the kernel's by blocks of 16
    rows, three of them too (a level's last block may be short)."""
    a = jnp.tril(jax.random.normal(jax.random.key(c), (3, c, c)), -1) * 0.3
    with jax.default_matmul_precision("highest"):
        t = jnp.stack([INVERSES[form](x) for x in a])
        eye = jnp.eye(c)
        np.testing.assert_allclose(t @ (eye - a), jnp.broadcast_to(
            eye, a.shape), atol=1e-5)


@pytest.mark.parametrize("form", INVERSES)
@pytest.mark.parametrize("beta,decay", [(0.5, 1.0), (0.9, 0.97), (1.0, 1.0)])
def test_a_chunk_of_one_repeated_token_keeps_its_digits(beta, decay, form):
    """Equal keys all through a chunk — a run of one token — make ``A``'s
    entries ``-beta decay^(i - j)``: the inverse is bounded by 1, and the
    series of powers ``(I + A)(I + A^2)...`` would form terms up to ``C(62,
    k) beta^k`` on its way there (160 off at ``beta`` 0.5 in float32 here, 176
    on the chip).
    Forward substitution stays at a rounding of the float64 inverse, and so
    does the kernel's: substitution inside blocks of 16 rows, ``T21 = T22
    A21 T11`` under them."""
    i = np.arange(64)
    a = -beta * np.tril(np.ones((64, 64)), -1) * decay ** (
        i[:, None] - i[None, :])
    want = np.linalg.inv(np.eye(64) - a)
    with jax.default_matmul_precision("highest"):
        got = INVERSES[form](jnp.asarray(a, F32))
    assert np.abs(want).max() <= 1.0
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-5


def test_the_step_is_the_recurrence_and_a_value_head_reads_key_head_j_over_2():
    """One token a slot against the reference's token; and with key head 0
    zeroed the value heads 0 and 1 (``j // 2 == 0``) write and read nothing
    while heads 2 and 3 are untouched."""
    q, k, v, g, beta = (a[:, 0] for a in _delta_inputs(3, 1, seed=2))
    carry = jax.random.normal(jax.random.key(9), (3, 4, 8, 8))
    o, new = jitted(gdn.gdn_step)(carry, q, k, v, g, beta)
    want_s, want_o = jax.jit(jax.vmap(ref.delta_token))(   # every slot's
        carry, jnp.repeat(q, 2, 1), jnp.repeat(k, 2, 1), v, jnp.exp(g), beta)
    np.testing.assert_allclose(new, want_s, atol=1e-6)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
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
