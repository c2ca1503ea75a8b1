"""``ops/mla_prefill.py``: the causal MLA attention core, two lowerings,
one contract.  The Pallas flash kernel (``mla_prefill_fwd``) runs under the
interpreter here, at the head widths LongCat publishes (128 + 64, v 128):
against the blocked XLA form at every real position, for lengths that end
inside a tile, on a tile edge, at ``P`` and at 0; real positions bit-equal
whatever the padding holds; skipped tiles written as zeros; and the choice
of lowering from backend, mesh and shape, as ``status()`` shows it.  Under a
KEEP MASK (dots3's full layers bring the indexer's selection as one): equal
to a dense masked softmax, rows that keep no key of their first tiles or not
themselves included; and with none the kernel's text is PR 56's."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models import longcat as lc
from progen_tpu.ops import mla_prefill as mp
from progen_tpu.ops.lowering import record_lowerings
from tests.families import fresh
from tests.longcat_tiny import TINY, make
from tools import program_hash

NOPE, ROPE, VD = 128, 64, 128
R = 2
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(p, heads, dtype, seed=0, rows=R):
    """Operands as ``prefill_attention`` takes them, O(1) logits."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def normal(k, shape, gain=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * gain).astype(dtype)

    return (normal(ks[0], (rows, p, heads, NOPE)),
            normal(ks[1], (rows, p, heads, ROPE)),
            normal(ks[2], (rows, heads, p, NOPE), 0.3),
            normal(ks[3], (rows, p, ROPE), 0.3),
            normal(ks[4], (rows, heads, p, VD)))


def _kernel(q_nope, q_rope, k_nope, k_r, v, lengths, keep=None, **tiles):
    with jax.default_matmul_precision("highest"):
        return mp.pallas_prefill_attention(
            q_nope.transpose(0, 2, 1, 3), q_rope.transpose(0, 2, 1, 3),
            k_nope, k_r, v, jnp.asarray(lengths, jnp.int32), keep,
            interpret=True, **tiles)


def _blocked(*ops):
    """One compiled program a shape (eagerly the blocks are dispatched op
    by op)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(mp.blocked_prefill_attention)(*ops)


@functools.lru_cache(maxsize=1)
def _blocked_reference(p, heads, dtype):
    """The blocked form over ``_operands(p, heads, dtype)``: the lengths and
    the tiles reach the kernel alone, so the cases of one shape, which
    follow each other, compare against the same array."""
    return _f32(_blocked(*_operands(p, heads, jnp.dtype(dtype))))


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


# lengths of the two rows, by what the first row's end does in a 128 x 256
# tiling of P = 512 (the second row varies the other way)
LENGTHS = {
    "inside-a-tile": lambda p: [p - 200, 77],
    "on-a-tile-edge": lambda p: [p // 2, p - 128],
    "at-P": lambda p: [p, p],
    "a-row-of-0": lambda p: [p - 1, 0],
}


@pytest.mark.parametrize("case", list(LENGTHS))
@pytest.mark.parametrize("tiles", [(128, 256), (256, 128), None],
                         ids=["q128-k256", "q256-k128", "chip-tiles"])
@pytest.mark.parametrize("p,heads,dtype", [
    (512, 2, "float32"), (512, 4, "bfloat16"), (1024, 3, "bfloat16"),
    (1024, 2, "float32")], ids=lambda v: str(v))
def test_kernel_equals_the_blocked_form_at_every_real_position(
        p, heads, dtype, tiles, case):
    ops = _operands(p, heads, jnp.dtype(dtype))
    lengths = LENGTHS[case](p)
    kw = dict(block_q=tiles[0], block_k=tiles[1]) if tiles else {}
    got = _f32(_kernel(*ops, lengths, **kw))
    want = _blocked_reference(p, heads, dtype)
    assert got.shape == (R, p, heads * VD) and np.isfinite(got).all()
    assert float(np.abs(want).max()) > 1.0      # not a vacuous bound
    for row, n in enumerate(lengths):
        if n:
            assert float(np.abs(got[row, :n] - want[row, :n]).max()) \
                < TOL[dtype]
        # a query tile that starts at or past the length reads as zeros
        bq = kw.get("block_q") or mp.fitted_tile(p)
        first_dead = -(-n // bq) * bq
        assert not got[row, first_dead:].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tiles", [(128, 256), (256, 256)],
                         ids=["q128-k256", "q256-k256"])
def test_real_positions_are_bit_equal_whatever_the_padding_holds(dtype,
                                                                 tiles):
    """The rule ``test_longcat_model.py`` checks for ``prefill``: junk in
    the operands at pad positions changes no bit at a real one, inside a
    partly real tile or elsewhere."""
    p, lengths = 512, [300, 129]
    q_nope, q_rope, k_nope, k_r, v = _operands(p, 2, jnp.dtype(dtype))
    pad = jnp.arange(p)[None, :] >= jnp.asarray(lengths)[:, None]  # (R, P)

    def junk(x, axis):
        shape = [1] * x.ndim
        shape[0], shape[axis] = R, p
        return jnp.where(pad.reshape(shape), jnp.asarray(37.5, x.dtype), x)

    kw = dict(block_q=tiles[0], block_k=tiles[1])
    got = _kernel(q_nope, q_rope, k_nope, k_r, v, lengths, **kw)
    again = _kernel(junk(q_nope, 1), junk(q_rope, 1), junk(k_nope, 2),
                    junk(k_r, 1), junk(v, 2), lengths, **kw)
    for row, n in enumerate(lengths):
        np.testing.assert_array_equal(_f32(got[row, :n]),
                                      _f32(again[row, :n]))
    assert np.isfinite(_f32(again)).all()


def test_rows_of_length_0_cost_nothing_and_read_as_zeros():
    """Every row empty: no tile is visited (NaN operands would show), and
    every output is written, as zeros."""
    p = 512
    ops = [jnp.full_like(x, jnp.nan) for x in _operands(p, 2, jnp.bfloat16)]
    got = _f32(_kernel(*ops, [0, 0], block_q=128, block_k=128))
    assert got.shape == (R, p, 2 * VD) and not got.any()

# ---- under a keep mask ------------------------------------------------------

T512 = dict(block_q=512, block_k=512)


def _dense_masked_softmax(q_nope, q_rope, k_nope, k_r, v, keep):
    """The whole ``(R, H, P, P)`` float32 score under ``s <= t`` and
    ``keep``, one softmax: nothing of the kernel's or the blocked form's."""
    q = jnp.concatenate([q_nope, q_rope], -1).astype(jnp.float32)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, None], k_nope.shape[:3]
                                  + k_r.shape[-1:])], -1).astype(jnp.float32)
    n = q.shape[1]
    with jax.default_matmul_precision("highest"):
        logits = jnp.einsum("rqhd,rhkd->rhqk", q, k) * q.shape[-1] ** -0.5
        seen = jnp.tril(jnp.ones((n, n), bool)) & (keep != 0)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), -1)
        out = jnp.einsum("rhqk,rhkd->rqhd", probs, v.astype(jnp.float32))
    return np.asarray(out.reshape(out.shape[0], n, -1))


def _selection(p, seed, share=0.3):
    """``keep (R, P, P)`` int8: a random ``share`` of the pairs and key 0
    of every row — but in each row of ``ODD`` what its name says."""
    keep = np.array(jax.random.bernoulli(
        jax.random.key(seed), share, (R, p, p)), np.int8)
    keep[:, :, 0] = 1
    for row, keys in ODD.items():
        keep[:, row] = 0
        keep[:, row, list(keys)] = 1
    return jnp.asarray(keep)


# query row -> the only keys it keeps (tiles of 512): nothing of its first
# key tile, nothing of its first two, not itself (and the last key before
# it), nothing but its own
ODD = {900: (600, 899), 1023: (1022,), 700: (3, 699), 513: (513,)}


@pytest.mark.parametrize("p,heads,dtype", [
    (1024, 2, "float32"), (1024, 4, "bfloat16"), (1536, 2, "bfloat16"),
    (2048, 2, "float32"), (2048, 3, "bfloat16")], ids=lambda v: str(v))
def test_masked_kernel_equals_a_dense_masked_softmax(p, heads, dtype):
    """Every position of both rows, the odd rows among them: a row whose
    kept keys all lie beyond its first key tile (its running maximum is
    still the floor when they come) and one that does not keep itself."""
    ops = _operands(p, heads, jnp.dtype(dtype))
    keep = _selection(p, seed=p + heads)
    got = _f32(_kernel(*ops, [p, p], keep, **T512))
    want = _dense_masked_softmax(*ops, keep)
    assert np.isfinite(got).all() and float(np.abs(want).max()) > 1.0
    assert float(np.abs(got - want).max()) < TOL[dtype]
    for row in ODD:
        assert float(np.abs(got[:, row] - want[:, row]).max()) < TOL[dtype]
        assert np.abs(got[:, row]).max() > 0


def test_off_the_chip_a_mask_goes_to_the_blocked_form():
    """``prefill_attention`` with a mask where the kernel does not apply:
    the blocked XLA form under the same rule."""
    p = 1024
    ops = _operands(p, 2, jnp.float32)
    keep = _selection(p, seed=11)
    with record_lowerings() as chosen, \
            jax.default_matmul_precision("highest"):
        got = _f32(mp.prefill_attention(*ops, keep=keep))
    assert chosen == {"mla_prefill": {"xla"}}
    want = _dense_masked_softmax(*ops, keep)
    assert float(np.abs(got - want).max()) < TOL["float32"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_masked_rows_of_unequal_lengths_ignore_what_the_pads_hold(dtype):
    """Junk in the operands AND in the mask at pad positions changes no bit
    at a real one; query tiles past a row's length read as zeros."""
    p, lengths = 1536, [1100, 513]
    q_nope, q_rope, k_nope, k_r, v = _operands(p, 2, jnp.dtype(dtype))
    keep = _selection(p, seed=7)
    pad = jnp.arange(p)[None, :] >= jnp.asarray(lengths)[:, None]  # (R, P)

    def junk(x, axis):
        shape = [1] * x.ndim
        shape[0], shape[axis] = R, p
        return jnp.where(pad.reshape(shape), jnp.asarray(37.5, x.dtype), x)

    got = _f32(_kernel(q_nope, q_rope, k_nope, k_r, v, lengths, keep, **T512))
    other = jnp.where(pad[:, :, None] | pad[:, None, :], 1 - keep, keep)
    again = _f32(_kernel(junk(q_nope, 1), junk(q_rope, 1), junk(k_nope, 2),
                         junk(k_r, 1), junk(v, 2), lengths, other, **T512))
    want = _dense_masked_softmax(q_nope, q_rope, k_nope, k_r, v, keep)
    assert np.isfinite(again).all()
    for row, n in enumerate(lengths):
        np.testing.assert_array_equal(got[row, :n], again[row, :n])
        assert float(np.abs(got[row, :n] - want[row, :n]).max()) < TOL[dtype]
        assert not got[row, -(-n // 512) * 512:].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_mask_that_keeps_everything_is_the_unmasked_kernel(dtype):
    """The same arithmetic on the same numbers: the floor under the maximum
    changes no value once a key is kept.  Held to
    the last place and not to the bit, because the interpreter compiles
    the two bodies apart and XLA's CPU fusion sums a row of probabilities
    in another order behind the mask's select (with the select taken out
    of the masked body the two are bit-equal)."""
    p, lengths = 1024, [1024, 700]
    ops = _operands(p, 2, jnp.dtype(dtype))
    plain = _f32(_kernel(*ops, lengths, **T512))
    masked = _f32(_kernel(*ops, lengths, jnp.ones((R, p, p), jnp.int8),
                          **T512))
    ulp = {"float32": 2.0 ** -23, "bfloat16": 2.0 ** -7}[dtype]
    assert np.abs(plain - masked).max() <= 2 * ulp * np.abs(plain).max()
    assert not masked[1, 1024:].any()


# sha256 heads of the kernel's jaxpr text WITHOUT a mask at the shapes of the
# two cells that call it so, taken on PR 56's tree (34aff0b) before the kernel
# took one: LongCat's and DeepSeek-V2's admissions trace what they traced,
# letter for letter (``tests/golden/programs.json`` is the CPU's trace and
# holds no Pallas lowering)
KERNEL_TEXT = {
    "longcat": ((2, 64, 4096), "99459dbaf36edac2"),
    "deepseek_v2": ((4, 128, 1024), "1428685ae621b80a"),
}


@pytest.mark.parametrize("case", list(KERNEL_TEXT))
def test_without_a_mask_the_kernel_traces_the_text_it_traced(case):
    (r, heads, p), want = KERNEL_TEXT[case]
    sd = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    head = program_hash.program_head(
        lambda *a: mp.pallas_prefill_attention(*a, interpret=True),
        (sd((r, heads, p, NOPE)), sd((r, heads, p, ROPE)),
         sd((r, heads, p, NOPE)), sd((r, p, ROPE)), sd((r, heads, p, VD)),
         jax.ShapeDtypeStruct((r,), jnp.int32)))
    assert head == want
    masked = program_hash.program_head(
        lambda *a: mp.pallas_prefill_attention(*a, interpret=True),
        (sd((r, heads, p, NOPE)), sd((r, heads, p, ROPE)),
         sd((r, heads, p, NOPE)), sd((r, p, ROPE)), sd((r, heads, p, VD)),
         jax.ShapeDtypeStruct((r,), jnp.int32),
         jax.ShapeDtypeStruct((r, p, p), jnp.int8)))
    assert masked != want


def test_pairs_visited_counts_the_tiles_the_grid_visits():
    """One head's pairs a row: live query tiles against the key tiles at or
    under the diagonal and under the length (tiles of 1024 at P = 4096)."""
    got = mp.pairs_visited(jnp.array([4096, 2049, 2048, 1, 0]), 4096)
    assert got.tolist() == [t * 1024 ** 2 for t in (10, 6, 3, 1, 0)]


# ---- which lowering, and where it is stated --------------------------------


def _lowering(q_shape, dtype=jnp.bfloat16, monkeypatch=None, on_tpu=False):
    r, p, heads, nope, rope, vd = q_shape
    if monkeypatch is not None:
        monkeypatch.setattr(mp, "_on_tpu", lambda: on_tpu)
    args = [jax.ShapeDtypeStruct(s, dtype) for s in (
        (r, p, heads, nope), (r, p, heads, rope), (r, heads, p, nope),
        (r, p, rope), (r, heads, p, vd))]
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda *a: mp.prefill_attention(*a))(*args))
    return chosen["mla_prefill"], jaxpr


def test_cpu_default_is_the_blocked_form():
    paths, jaxpr = _lowering((2, 512, 2, NOPE, ROPE, VD))
    assert paths == {"xla"} and "pallas_call" not in jaxpr


@pytest.mark.parametrize("q_shape,dtype,want", [
    ((2, 512, 2, 128, 64, 128), jnp.bfloat16, "pallas"),
    ((2, 1536, 2, 128, 64, 128), jnp.float32, "pallas"),
    ((1, 1024, 3, 256, 128, 128), jnp.bfloat16, "pallas"),
    ((2, 384, 2, 128, 64, 128), jnp.bfloat16, "xla"),   # P off the tile
    ((2, 512, 2, 128, 32, 128), jnp.bfloat16, "xla"),   # rope
    ((2, 512, 2, 64, 64, 128), jnp.bfloat16, "xla"),    # nope
    ((2, 512, 2, 128, 64, 96), jnp.bfloat16, "xla"),    # v
    ((2, 16, 4, 8, 8, 12), jnp.float32, "xla"),         # the tests' TINY
], ids=["published", "f32-P1536", "wider", "P-384", "rope-32", "nope-64",
        "v-96", "tiny"])
def test_on_tpu_the_shape_decides(monkeypatch, q_shape, dtype, want):
    paths, jaxpr = _lowering(q_shape, dtype, monkeypatch, on_tpu=True)
    assert paths == {want}
    assert ("pallas_call" in jaxpr) == (want == "pallas")
    # the kernel takes no concatenated keys and returns what ``wo`` reads
    assert ("concatenate" in jaxpr) == (want == "xla")


def test_a_mesh_in_scope_keeps_the_blocked_form(monkeypatch, devices8):
    mesh = jax.sharding.Mesh(np.asarray(devices8[:2]), ("data",))
    with mesh:
        paths, jaxpr = _lowering((2, 512, 2, NOPE, ROPE, VD),
                                 monkeypatch=monkeypatch, on_tpu=True)
    assert paths == {"xla"} and "pallas_call" not in jaxpr


WIDE = dataclasses.replace(
    TINY, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VD,
    num_attention_heads=2, max_position_embeddings=1024, prefill_bucket=512)


def _force_kernel(monkeypatch):
    monkeypatch.setattr(mp, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        mp, "pallas_prefill_attention",
        lambda *a, _f=mp.pallas_prefill_attention: _f(
            *a, block_q=128, block_k=256, interpret=True))


def test_prefill_through_the_kernel(monkeypatch):
    """``longcat.prefill`` at the published head widths with the kernel
    forced (interpreter): no score block, no key concatenation and no
    stitching of blocks in the trace; logits at real positions those of the
    blocked form; bit-equal whatever the padding holds; an empty row's
    attention written as zeros."""
    params, policy = make(WIDE)
    p = 512
    toks = jax.random.randint(jax.random.key(1), (R, p), 1, WIDE.vocab_size)
    lengths = jnp.array([p - 100, 140])
    at = jnp.broadcast_to(jnp.arange(0, p, 4), (R, p // 4))

    def lowered():
        """``lc.prefill`` as ONE program traced under what is patched NOW:
        a fresh function per lowering (``jax.jit`` would keep the trace;
        eagerly the interpreter runs the kernel's grid op by op)."""
        prefill = fresh(lc.prefill)

        def run(tokens, lens):
            with jax.default_matmul_precision("highest"):
                return prefill(params, tokens, lens, WIDE, policy,
                               logit_positions=at)[0]

        return run

    want = lowered()(toks, lengths)
    _force_kernel(monkeypatch)
    with record_lowerings() as chosen:
        jaxpr = str(jax.make_jaxpr(lambda t, n: lc.prefill(
            params, t, n, WIDE, policy)[0])(toks, lengths))
    # (the experts of a prefill keep today's form whatever the backend)
    assert chosen == {"mla_prefill": {"pallas"}, "moe_experts": {"xla"}}
    assert jaxpr.count("pallas_call") == 2 * WIDE.num_layers
    assert "dynamic_update_slice" not in jaxpr
    assert f"f32[{R},{WIDE.num_attention_heads},256," not in jaxpr

    run = lowered()
    got = run(toks, lengths)
    junk = jnp.where(jnp.arange(p)[None, :] < lengths[:, None], toks, 5)
    again = run(junk, lengths)
    for row, n in enumerate(np.asarray(lengths)):
        real = np.asarray(at[row]) < n
        assert float(jnp.abs(got[row, real] - want[row, real]).max()) < 2e-4
        np.testing.assert_array_equal(np.asarray(got[row, real]),
                                      np.asarray(again[row, real]))

    x = jax.random.normal(jax.random.key(2), (R, p, WIDE.hidden_size))
    out, _ = jax.jit(lambda x, w, n: lc.mla_prefill(x, w, WIDE, n))(
        x, params["layers"][0]["attn"][0], jnp.array([p, 0]))
    assert not np.asarray(out[1]).any() and np.asarray(out[0]).any()


def test_engine_states_the_lowering_its_admission_was_built_with():
    """``status()["mla_prefill"]``: ``None`` before an admission program
    is traced, then what the trace chose — the blocked form on the CPU;
    an engine of a family without latent attention never says."""
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW

    params, policy = make()
    eng = ServingEngine(TINY, params, policy=policy,
                        num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                        max_len=32)
    assert eng.status()["mla_prefill"] is None
    eng.submit(Request(uid=0, tokens=[3, 4, 5], max_new_tokens=3,
                       temperature=0.0, seed=1))
    (done,) = eng.run_until_idle(max_chunks=10)
    assert done.uid == 0
    status = eng.status()
    assert status["mla_prefill"] == "xla"
    assert status["row_write"] == "scatter"
