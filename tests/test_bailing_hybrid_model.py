"""Ling-3.0-flash (``models/bailing_hybrid.py``) against its plain reference
(``perf/lib/reference_ling3.py``) at tiny widths on the CPU, seeded weights:
the forward over right-padded rows, prefill then decode through the blocks'
caches, the chunked channel-decay delta rule against the recurrence token by
token at a block's and a chunk's edges — by the XLA form and by the kernel
``kda_prefill_fwd`` under the interpreter, which stops at a row's length —, a
chunk whose decays sit AT the gate's bound, the rule with every channel's decay equal against
``gdn_scan``, the one-token step, the gate a head after the norm, the
full-rank query under interleaved rotary pairs, what each kind of layer
states about its cache and the config's refusals."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_ling3 as ref
from progen_tpu.models import bailing_hybrid as bh
from progen_tpu.models import driver, latent, state
from progen_tpu.ops import gdn
from progen_tpu.ops.lowering import record_lowerings
from tests.bailing_hybrid_tiny import TINY, as_dict, make
from tests.families import fresh, jitted, reference

F32 = jnp.float32
MAX_LEN = 32
# float32 on both sides: what differs is the order of sums (the chunked form
# against the token-by-token recurrence, ragged windows against a dense loop
# over experts, the absorbed step against expanded keys), a few 1e-6 on
# logits of spread 1
TOL = 4e-5
LENGTHS = (19, 1, 2, 24)        # across blocks of 4 and chunks of 8; under
#                                 the four taps
C, B = TINY.chunk, TINY.block


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
        return np.asarray(reference(ref, TINY, q_block=8)(weights[0], rows))


def test_forward_over_right_padded_rows_is_the_references(weights, rows,
                                                          wanted):
    params, policy = weights
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    at = jnp.broadcast_to(jnp.arange(24), (4, 24))
    with record_lowerings() as chosen:
        logits, handed, stats = fresh(bh.prefill)(
            params, rows, lengths, TINY, policy, logit_positions=at)
    for i, n in enumerate(LENGTHS):
        assert np.abs(np.asarray(logits[i, :n]) - wanted[i, :n]).max() < TOL
    assert float(wanted.std()) > 0.5            # not a vacuous bound
    assert chosen["kda_prefill"] == {"xla"} and "kda_step" not in chosen
    assert "gdn_prefill" not in chosen
    assert sorted(handed) == [f"l{i}" for i in range(6)]
    assert sorted(handed["l0"]) == ["conv", "state"]
    assert handed["l5"].shape == (4, 24, 20)        # latent rows a token
    tokens = sum(LENGTHS)
    assert stats["moe.tokens"] == 5 * tokens        # layer 0 is dense
    assert stats["moe.held_load"].sum() == stats["moe.prefill_held"] == (
        5 * tokens * TINY.num_experts_per_tok)
    assert stats["kda.real_tokens"] == 5 * tokens
    assert stats["kda.scan_slots"] == 5 * 4 * 24    # whole chunks of 8


def test_prefill_then_decode_is_the_references_full_forward(weights, rows,
                                                            wanted):
    """Unequal right-padded rows (1 and 2 tokens: shorter than the taps; 19:
    across blocks and chunks) prefilled, laid out as slots, then decoded
    token by token: every step's logits are the reference's at that
    position."""
    params, policy = weights
    lengths = jnp.asarray([19, 1, 2, 20], jnp.int32)
    _, handed, _ = jitted(bh.prefill)(params, rows, lengths, TINY, policy)
    caches = jitted(bh.caches_from)(handed, lengths, TINY, MAX_LEN)
    live = jnp.ones((4,), bool)
    for j in range(3):
        pos = lengths + j
        tok = rows[jnp.arange(4), pos]
        logits, caches, stats = jitted(bh.decode_step)(
            params, tok, pos, caches, live, TINY, policy)
        want = wanted[np.arange(4), np.asarray(pos)]
        assert np.abs(np.asarray(logits) - want).max() < TOL, j
    assert stats["kda.state_bytes"] == 2 * 5 * 4 * (2 * 8 * 8 * 4)
    assert stats["moe.decode_layers"] == 5 and stats["moe.tokens"] == 20
    assert stats["mla.decode_rows"] == 4
    assert stats["mla.context_tokens"] == float(jnp.sum(pos + 1))


# ----------------------------------------------------------- the delta rule


def _delta_inputs(r, p, h=2, dk=8, dv=8, seed=0, spread=2.0, shift=0.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (r, p, h, dk))
    k = jax.random.normal(ks[1], (r, p, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (r, p, h, dv))
    g = -5.0 * jax.nn.sigmoid(
        spread * jax.random.normal(ks[3], (r, p, h, dk)) + shift)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, p, h)))
    return q, k, v, g, beta


@jax.jit
def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence over rows ``(R, P, ...)``: every output
    and the carry after every token, a row."""
    def row(q, k, v, g, beta):
        def token(s, at):
            s, o = ref.delta_token(s, *at)
            return s, (o, s)

        zero = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), F32)
        return jax.lax.scan(token, zero, (q, k, v, jnp.exp(g), beta))[1]

    return jax.vmap(row)(q, k, v, g, beta)


@jax.jit
def _scan(q, k, v, g, beta, lengths):
    return gdn.kda_scan(q, k, v, g, beta, lengths, C, B)


WIDE = dict(dk=128, dv=128)     # the kernel's widths: whole lane tiles
KC = 64                         # its chunk, in blocks of gdn.SOLVED rows
# The CARRY's limit at these sizes (sums of 128 terms over chunks of 64, a
# carry of 0.3-0.5), set between two readings over ``CHUNKED``'s kernel cases
# (PR 66, this file's inputs): the float32 forms lie at most 1.4e-6 (the
# kernel) and 2.6e-6 (``xla_kda_scan`` in chunks of 64, blocks of 16) from the
# recurrence, a bfloat16 path about 1e-3.  The OUTPUTS keep the XLA cases'
# 1e-6 (the kernel's largest: 7.9e-8, the XLA form's 1.0e-7).
WIDE_TOL = 4e-6


@functools.lru_cache(maxsize=None)
def _kernel(step=None):
    """The kernel under the interpreter in chunks of 64, ONE program a
    ``step`` (the tokens a grid step that the caller has patched into
    ``gdn.STEP_TOKENS``, which the trace reads) and shape: ``lengths`` is a
    runtime scalar, so cases that differ in it alone share a compiled
    body."""
    return jax.jit(lambda *a: gdn.pallas_kda_scan(*a, KC, interpret=True))


def _held_to_the_recurrence(inputs, lengths, scan=_scan, carry_tol=1e-6):
    """``scan``'s outputs at real positions (to 1e-6) and its carry at each
    row's true length (to ``carry_tol``) against the recurrence token by
    token (compared on the host: a slice a length is no program of its
    own)."""
    with jax.default_matmul_precision("highest"):
        o, carry = (np.asarray(a) for a in scan(*inputs,
                                                jnp.asarray(lengths)))
        want_o, carries = (np.asarray(a) for a in _token_by_token(*inputs))
    for i, n in enumerate(lengths):
        want = carries[i, n - 1] if n else np.zeros_like(carries[i, 0])
        assert np.abs(carry[i] - want).max() < carry_tol, (i, n)
        assert np.abs(o[i, :n] - want_o[i, :n]).max(initial=0) < 1e-6, (i, n)
    assert np.isfinite(o).all()
    return o, carry


# (tokens a grid step of the kernel | None: the XLA form, heads, lengths,
# bucket); the XLA form's first two are ONE compiled shape
CHUNKED = {
    "a-blocks-and-a-chunks-edges": (
        None, 2, (0, 1, B - 1, B, B + 1, C - 1, C, C + 1), 24),
    "a-padded-bucket": (None, 2, (19, 7, 24, 2, 0, 13, 24, 17), 24),
    "a-bucket-past-whole-chunks": (None, 2, (21, 13), 21),
    "kernel-a-blocks-and-a-chunks-edges": (
        512, 2, (0, 1, 15, 16, 17, 63, 64, 65), 128),
    # three grid steps of two chunks a row: a row that ends in the last, one
    # in the first and one at the second's second token
    "kernel-rows-end-in-other-steps": (128, 2, (300, 70, 129), 384),
    # eight heads: two grid steps of four a block of tokens
    "kernel-a-bucket-past-whole-steps": (128, 8, (200, 77), 200),
}


@pytest.mark.parametrize("case", CHUNKED)
def test_the_chunked_form_is_the_recurrence_token_by_token(case, monkeypatch):
    """Rows of 0, 1, block - 1, block, block + 1, C - 1, C and C + 1 tokens,
    and rows padded to a bucket past whole chunks: the outputs at real
    positions and the carry AT EACH ROW'S TRUE LENGTH (zeros for a row of
    length 0) are the recurrence's, whatever the padding holds — by the XLA
    form and by the kernel, whose rows also end in different grid steps and
    whose bucket need not be whole ones."""
    step, heads, lengths, bucket = CHUNKED[case]
    chunk = KC if step else C
    if step:
        monkeypatch.setattr(gdn, "STEP_TOKENS", step)
    o, carry = _held_to_the_recurrence(
        _delta_inputs(len(lengths), bucket, h=heads, seed=bucket,
                      **(WIDE if step else {})),
        lengths, *((_kernel(step), WIDE_TOL) if step else ()))
    # not a vacuous bound (a unit key of 128 columns has smaller entries)
    assert np.abs(carry).max() > (0.3 if step else 0.5)
    assert gdn.scanned_slots(len(lengths), bucket, chunk) == (
        len(lengths) * -(-bucket // chunk) * chunk)
    if step:        # nothing is left in a chunk wholly past a row's length
        for i, n in enumerate(lengths):
            assert not o[i, -(-n // chunk) * chunk:].any()


def test_the_kernel_leaves_a_chunk_past_a_rows_length_alone(monkeypatch):
    """NaN in every chunk that lies wholly past its row's length (and in
    ``g`` and ``beta`` from the length on): ``o`` is zero there, and ``o``
    before it and the carry are, bit for bit, what clean inputs give.  The
    shape of ``kernel-rows-end-in-other-steps``: one compiled body."""
    lengths, bucket = (130, 0, 64), 384
    monkeypatch.setattr(gdn, "STEP_TOKENS", 128)
    clean = _delta_inputs(3, bucket, seed=5, **WIDE)
    past = (jnp.arange(bucket)[None, :]
            >= -(-jnp.asarray(lengths) // KC)[:, None] * KC)
    at = jnp.arange(bucket)[None, :] >= jnp.asarray(lengths)[:, None]
    q, k, v = (jnp.where(past[..., None, None], jnp.nan, x)
               for x in clean[:3])
    g = jnp.where(at[..., None, None], jnp.nan, clean[3])
    beta = jnp.where(at[..., None], jnp.nan, clean[4])
    o, carry = _kernel(128)(q, k, v, g, beta, jnp.asarray(lengths))
    want_o, want = _kernel(128)(*clean, jnp.asarray(lengths))
    assert bool(jnp.isnan(q).any()) and bool(jnp.isnan(g).any())
    np.testing.assert_array_equal(carry, want)
    assert not np.asarray(carry[1]).any()
    assert not np.asarray(jnp.where(past[..., None, None], o, 0.0)).any()
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(o[i, :n], want_o[i, :n])


def test_a_chunk_whose_decays_sit_at_the_bound_stays_finite_and_equal():
    """Channels at the gate's bound of -5 a token beside channels that
    hardly decay: inside a chunk ``exp(-gam)`` alone would pass e^35 here
    (e^320 at the published chunk of 64: not a float32), and the products
    by blocks take no positive exponent at all — in the XLA form and in the
    kernel."""
    q, k, v, g, beta = _delta_inputs(8, 24, seed=3)
    at_bound = (jnp.arange(8) % 2 == 0)[None, None, None, :]
    g = jnp.broadcast_to(jnp.where(at_bound, -5.0 + 1e-4, -1e-3), g.shape)
    assert float(-jnp.sum(g[0, :C, 0, 0])) > 35
    _held_to_the_recurrence((q, k, v, g, beta), (24, 17, 8, 9, 1, 0, 16, 23))
    # the published sizes: a chunk of 64 in blocks of 16, every channel at
    # the bound, would overflow any form that takes exp(-gam)
    # (both to 1e-6: at the bound the carry is small, and both read 3e-8)
    for widths, scan in (({}, jax.jit(
            lambda *a: gdn.xla_kda_scan(*a, 64, 16))), (WIDE, _kernel())):
        wide = _delta_inputs(1, 64, h=1, seed=4, **widths)
        wide = wide[:3] + (jnp.full_like(wide[3], -5.0 + 1e-4), wide[4])
        assert float(jnp.exp(-jnp.sum(wide[3][0, :, 0, 0]))) == float("inf")
        _held_to_the_recurrence(wide, (64,), scan)


def test_with_every_channels_decay_equal_it_is_the_heads_decay_rule():
    """``g`` constant over a head's channels: ``kda_scan`` is ``gdn_scan``
    with that decay a head (its XLA form), and ``kda_step`` ``gdn_step``."""
    q, k, v, g, beta = _delta_inputs(8, 24, seed=6)     # ``CHUNKED``'s shape
    head = g[..., 0]
    lengths = (24, 11, 0, 7, 19, 8, 1, 16)
    with jax.default_matmul_precision("highest"):
        o, carry = (np.asarray(a) for a in _scan(
            q, k, v, jnp.broadcast_to(head[..., None], g.shape), beta,
            jnp.asarray(lengths)))
        want_o, want = (np.asarray(a) for a in jax.jit(
            lambda *a: gdn.xla_gdn_scan(*a, C))(q, k, v, head, beta,
                                                jnp.asarray(lengths)))
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(o[i, :n], want_o[i, :n], atol=1e-6)
    np.testing.assert_allclose(carry, want, atol=1e-6)
    s = jax.random.normal(jax.random.key(2), (8, 2, 8, 8))
    one = [a[:, 0] for a in (q, k, v, head, beta)]
    got = jitted(gdn.kda_step)(s, *one[:3], jnp.broadcast_to(
        one[3][..., None], (8, 2, 8)), one[4])
    for a, b in zip(got, jitted(gdn.gdn_step)(s, *one)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_scan_slots_counter_follows_the_lowering(monkeypatch):
    """``kda.scan_slots`` counts what the traced lowering computes: every
    chunk of the bucket under the XLA form, whole chunks up to each row's
    length under the kernel — which only the published widths in blocks of
    16 rows take."""
    def wide(chunk=64, block=16, dim=128):
        return state.ChannelDeltaBlock(2, dim, 128, 4, 1e-6, chunk, block,
                                       -5.0)

    blocks = {f"l{i}": wide() for i in range(3)}
    lengths = jnp.asarray([65, 0, 512, 1], jnp.int32)
    stats = fresh(lambda n: state.kda_prefill_stats(blocks, (4, 512), n))
    assert stats(lengths)["kda.scan_slots"] == 3 * 4 * 512
    monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
    assert blocks["l0"].scan_lowering(512) == "pallas"
    stats = fresh(lambda n: state.kda_prefill_stats(blocks, (4, 512), n))
    assert stats(lengths)["kda.scan_slots"] == 3 * (128 + 0 + 512 + 64)
    assert stats(lengths)["kda.real_tokens"] == 3 * 578
    # products in blocks of 8 rows, a chunk that is not whole blocks of 16,
    # a width off the lane tile
    assert wide(block=8).scan_lowering(512) == "xla"
    assert wide(chunk=24).scan_lowering(512) == "xla"
    assert wide(dim=64).scan_lowering(512) == "xla"
    assert bh.delta_block(TINY).scan_lowering(512) == "xla"
    assert bh.delta_block(bh.BailingHybridConfig()).scan_lowering(
        16384) == "pallas"


def test_a_delta_block_of_published_widths_prefills_through_the_kernel(
        monkeypatch):
    """``ChannelDeltaBlock.prefill`` at ``Dk = Dv = 128`` in blocks of 16
    with the chip said to be there, a row at a time: the op notes
    ``"pallas"`` and the kernel (under the interpreter) hands over what the
    XLA form hands over — the mixer's output at real positions, the carry
    and the tail."""
    block = state.ChannelDeltaBlock(2, 128, 128, 4, 1e-6, 16, 16, -5.0)
    p = block.init_weights(jax.random.key(3), 64, F32, (-6.0, 2.0),
                           (0.5, 2.0))
    u = jax.random.normal(jax.random.key(4), (2, 48, 64))
    lengths = jnp.asarray([37, 16], jnp.int32)
    with jax.default_matmul_precision("highest"):
        with record_lowerings() as chosen:
            want, held = fresh(block.prefill)(u, p, lengths)
        assert chosen["kda_prefill"] == {"xla"}
        monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
        monkeypatch.setattr(gdn, "pallas_kda_scan", functools.partial(
            gdn.pallas_kda_scan, interpret=True))
        with record_lowerings() as chosen:
            got, handed = fresh(block.prefill)(u, p, lengths)
    assert chosen["kda_prefill"] == {"pallas"}
    for i, n in enumerate((37, 16)):
        np.testing.assert_allclose(got[i, :n], want[i, :n], atol=1e-6)
    np.testing.assert_allclose(handed["state"], held["state"], atol=WIDE_TOL)
    np.testing.assert_array_equal(handed["conv"], held["conv"])
    assert float(jnp.abs(held["state"]).max()) > 0.1


def test_the_step_is_the_recurrence():
    q, k, v, g, beta = (a[:, 0] for a in _delta_inputs(3, 1, seed=2))
    carry = jax.random.normal(jax.random.key(9), (3, 2, 8, 8))
    o, new = jitted(gdn.kda_step)(carry, q, k, v, g, beta)
    want_s, want_o = jax.jit(jax.vmap(ref.delta_token))(   # every slot's
        carry, q, k, v, jnp.exp(g), beta)
    np.testing.assert_allclose(new, want_s, atol=1e-6)
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    # a channel's decay is its own: channel 0 held, the others forgotten
    only = jnp.full_like(g, -5.0).at[..., 0].set(0.0)
    _, kept = jitted(gdn.kda_step)(carry, q, jnp.zeros_like(k), v, only, beta)
    np.testing.assert_allclose(kept[:, :, 0], carry[:, :, 0], atol=1e-6)
    assert float(jnp.abs(kept[:, :, 1:]).max()) < 0.05


def test_the_gate_is_bounded_and_a_heads_gate_comes_after_the_norm(weights):
    block = bh.delta_block(TINY)
    p = dict(weights[0]["layers"][1]["mixer"], out_proj=jnp.eye(16))
    x = 3.0 * jax.random.normal(jax.random.key(1), (5, TINY.hidden_size))
    beta, g = block._gates(x, p)
    assert g.shape == (5, 2, 8) and beta.shape == (5, 2)
    assert float(g.max()) < 0 and float(g.min()) > TINY.kda_lower_bound
    np.testing.assert_allclose(g, ref.log_decay(
        (x @ p["f_proj"]).reshape(5, 2, 8), p["a_log"], p["dt_bias"],
        TINY.kda_lower_bound), atol=1e-6)
    # the seeded ranges exercise the bound and long memory alike
    assert float(g.min()) < -4 and float(g.max()) > -0.05
    o = jax.random.normal(jax.random.key(2), (5, 2, 8))
    assert p["norm"].shape == (8,) and p["g_proj"].shape == (32, 2)
    gate = jax.nn.sigmoid(x @ p["g_proj"])
    want = (driver.rms_norm(o, p["norm"], TINY.rms_norm_eps)
            * gate[..., None]).reshape(5, 16)
    np.testing.assert_allclose(block._out(o, x, p), want, atol=1e-6)
    # before the norm a head's gate would cancel
    before = driver.rms_norm(o * gate[..., None], p["norm"],
                             TINY.rms_norm_eps).reshape(5, 16)
    assert float(jnp.abs(want - before).max()) > 1e-2


# ------------------------------------------------------------ attention


def test_the_query_has_no_low_rank_and_pairs_rotate_interleaved(weights):
    """``q = u W_q`` straight (no ``wqa``, no query norm); of a head's 12
    columns the last 4 move with the position, in pairs ``(2i, 2i + 1)``;
    the cache row is ``[N(c_kv) | rope(k_r)]``."""
    p = weights[0]["layers"][5]["mixer"]
    assert "wq" in p and "wqa" not in p and "q_norm" not in p
    x = jax.random.normal(jax.random.key(4), (1, 6, TINY.hidden_size))
    at = jnp.arange(6)[None]
    q_nope, q_rope, row = latent.mla_project(x, p, TINY, at)
    q = (x @ p["wq"]).reshape(1, 6, 2, 12)
    np.testing.assert_allclose(q_nope, q[..., :8], atol=1e-6)
    want = ref.rotate_pairs(q[0, ..., 8:], at[0], TINY.rope_theta)
    # the program keeps the pairs' first members, then their second
    np.testing.assert_allclose(
        q_rope[0], jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1),
        atol=1e-5)
    kva = x @ p["wkva"]
    np.testing.assert_allclose(
        row[..., :16], driver.rms_norm(kva[..., :16], p["kv_norm"],
                                       TINY.rms_norm_eps), atol=1e-6)
    assert row.shape == (1, 6, 20)


# ------------------------------------------------------ caches, config


def test_each_kind_of_layer_states_its_own_cache():
    blocks = bh.blocks_of(TINY)
    assert list(blocks) == [f"l{i}" for i in range(6)]
    assert [type(b) for b in blocks.values()] == (
        [state.ChannelDeltaBlock] * 5 + [latent.LatentBlock])
    assert blocks["l5"].options == {"window": None, "indexer": False,
                                    "gate": True}
    assert blocks["l0"] is blocks["l4"]             # one instance a kind
    family = bh.BailingHybridFamily(TINY, make()[1])
    for max_len in (16, 4096):      # the state does not depend on it
        caches = jax.eval_shape(lambda: family.init_caches(3, max_len))
        assert caches["l0"]["state"].shape == (3, 2, 8, 8)
        assert caches["l0"]["state"].dtype == jnp.float32
        assert caches["l0"]["conv"].shape == (3, 3, 48)
        assert caches["l5"].shape == (3, max_len, 20)
    # the published widths: 2.10 MB of carry and 73.7 KB of tail a slot and
    # delta layer, 1,152 B a token in the one latent layer
    c = bh.BailingHybridConfig()
    whole = bh.delta_block(c)
    shapes = jax.eval_shape(lambda: whole.init_cache(1, 3072, jnp.bfloat16))
    assert shapes["state"].shape == (1, 32, 128, 128)
    assert shapes["conv"].shape == (1, 3, 12288)
    assert whole.state_bytes() == 2097152
    assert (whole.chunk, whole.block, whole.bound) == (64, 16, -5.0)
    assert c.latent_width == 576
    # a delta block of the head's-decay kind counts under other names
    assert state.kda_decode_stats({"a": state.DeltaBlock(
        2, 4, 8, 8, 4, 1e-6, 4)}, jnp.ones((2,), bool))[
            "kda.state_bytes"] == 0
    assert state.delta_decode_stats(blocks, jnp.ones((2,), bool))[
        "gdn.state_bytes"] == 0


@pytest.mark.parametrize("change,message", [
    (dict(kda_safe_gate=False), "kda_safe_gate"),
    (dict(use_kda_lora=True), "use_kda_lora"),
    (dict(q_lora_rank=24), "q_lora_rank"),
    (dict(rope_interleave=False), "rope_interleave"),
    (dict(num_kv_heads_for_linear_attn=1), "num_kv_heads_for_linear_attn"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(scale_router_input=True), "scale_router_input"),
    (dict(first_expert=14, experts_held=4), "routed experts"),
    (dict(n_group=3), "groups"),
    (dict(layer_ids=(0, 1)), "layer_ids"),
    (dict(layer_ids=(0, 7, 8, 9, 10, 12)), "no entry for layer 12"),
    (dict(kda_lower_bound=0.0), "bound")])
def test_a_config_the_served_model_does_not_have_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        bh.delta_block(dataclasses.replace(TINY, **change))


def test_the_published_kinds_limits_and_from_dict():
    whole = bh.BailingHybridConfig()
    assert whole.layer_ids == tuple(range(42))
    assert whole.layer_types.count(bh.DELTA) == 35
    assert whole.layer_types.count(bh.LATENT) == 7
    assert whole.layer_types[:6] == (bh.DELTA,) * 5 + (bh.LATENT,)
    assert [whole.is_dense(i) for i in range(3)] == [True, True, False]
    # the tiny share: published layers 0, 7-11, limits in the last ones
    assert TINY.layer_types == (bh.DELTA,) * 5 + (bh.LATENT,)
    assert [TINY.is_dense(i) for i in range(6)] == [True] + [False] * 5
    assert [TINY.limits(i) for i in range(6)] == [
        (0.0, 0.0), (0.0, 0.0), (0.0, 1.5), (1.0, 1.5), (1.0, 1.5),
        (1.0, 2.0)]
    c = bh.BailingHybridConfig.from_dict(
        dict(as_dict(TINY), model_type="bailing_hybrid",
             layer_ids=list(TINY.layer_ids), max_window_layers=20))
    assert c == TINY
