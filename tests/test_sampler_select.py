"""The batched sampler's k-th largest logit found by counting against the
plain form, a full sort of every row, written here as the reference: the
threshold is the sort's own element, the cut and, under the same keys, the
tokens are the same — over widths from 16 columns to a whole chat
vocabulary, per-row k (off, 1, 25, v - 1, v, past v), ties at the k-th
value, rows mostly ``-inf``, signed zeros, bfloat16 logits, greedy and
near-greedy rows beside sampled ones, and NaNs of either sign.  There is one
algorithm whatever the width, and no sort in the traced program; where the
rows do not fit on the chip together the same rounds run a group of rows at
a time, held here to the same sort over the same cases with more than one
group and a last group that is not full, and the shape alone decides which
of the two a draw takes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.decode import Request, ServingEngine, sampler
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.sampler import (
    apply_logit_mask,
    gumbel_topk_sample_batched,
    gumbel_topk_sample_with_confidence,
)
from progen_tpu.ops import kth
from progen_tpu.ops.lowering import record_lowerings
from tests.granite_tiny import TINY, make

ROWS = 12
WIDTHS = (16, 256, 1_023, 4_096, 16_384, 25_024, 100_352)
CASES = ("own_k", "ties", "mostly_masked", "signed_zeros", "bfloat16",
         "temperatures", "nans")
# (width, rows the budget holds) at which the draw is made to go by groups:
# the 12 rows are a group of 8 and a last one of 4 (a budget of 8 rows), or
# three groups of 4 (a budget of 5 rows: the power of two under it)
TILED = ((1_280, 8), (4_480, 5))
# the rows and columns of every cell's draw (BENCHMARK.json's cells; the
# block step's is slots x block)
CELL_DRAWS = {
    "serve-small-steady": (64, 256), "serve-base-backlog": (16, 256),
    "serve-longcat-backlog": (32, 16_384),
    "serve-trinity-mixedlen-backlog": (64, 25_024),
    "serve-dsv2-decode-backlog": (64, 25_600),
    "serve-granite-chat-backlog": (32, 100_352),
    "serve-sdar-blockdiff-backlog": (256, 151_936)}


def _force_groups(monkeypatch, budget=None):
    """The chip's choice on the CPU: the gate sees a TPU and (for the tests'
    small arrays) a budget of ``budget`` bytes."""
    monkeypatch.setattr(kth, "_on_tpu", lambda: True)
    if budget is not None:
        monkeypatch.setattr(kth, "ROUNDS_ON_CHIP_BYTES", budget)


def reference_kth(scaled, k_eff):
    v = scaled.shape[-1]
    srt = jnp.sort(scaled, axis=-1)  # ascending
    return jnp.take_along_axis(srt, (v - k_eff)[:, None], axis=-1)


def reference_draw(keys, logits, top_k, temperature, mask=None):
    """``gumbel_topk_sample_batched`` as it stood while it sorted every
    row whatever its width."""
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = apply_logit_mask(logits, mask)
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-8)[:, None]
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    kth = reference_kth(scaled, k_eff)
    masked = apply_logit_mask(scaled, scaled >= kth)
    noise = jax.vmap(
        lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
    sampled = jnp.argmax(masked + noise, axis=-1)
    return jnp.where(temperature == 0.0, greedy, sampled)


def _inputs(case, v):
    """logits, per-row k, temperature and mask of one case: every row its
    own k in every case."""
    rng = np.random.default_rng(v * 31 + CASES.index(case))
    ks = np.resize([0, 1, 25, v - 1, v, v + 7], ROWS).astype(np.int32)
    logits = rng.normal(size=(ROWS, v)).astype(np.float32)
    temp = np.ones((ROWS,), np.float32)
    mask = None
    if case == "ties":
        logits = np.round(logits, 1)
    elif case == "mostly_masked":
        # more than v - k entries masked for the rows of k = 25, v - 1, v:
        # their k-th value is -inf and the cut keeps the whole row
        mask = rng.random((ROWS, v)) < min(0.5, 12 / v)
        mask[:, 3] = True
    elif case == "signed_zeros":
        logits = rng.choice(
            np.array([-1.0, -0.0, 0.0, 1.0], np.float32), size=(ROWS, v),
            p=[0.05, 0.45, 0.45, 0.05])
    elif case == "bfloat16":
        logits = jnp.asarray(logits * 4, jnp.bfloat16)
    elif case == "temperatures":
        temp = np.resize([0.0, 1e-6, 1.0, 0.7], ROWS).astype(np.float32)
    elif case == "nans":
        bits = logits.view(np.uint32)
        bits[:, 1] = 0x7FC00000
        bits[:, 2] = 0xFFC00000               # a NaN with its sign bit set
        bits[ROWS // 2:, 4::3] = 0xFFC00001   # rows that are a third NaN
    return (jnp.asarray(logits), jnp.asarray(ks), jnp.asarray(temp),
            None if mask is None else jnp.asarray(mask))


@pytest.mark.parametrize(("v", "fit"),
                         [(v, None) for v in WIDTHS] + list(TILED))
@pytest.mark.parametrize("case", CASES)
def test_selection_is_the_sorts_element_and_draws_its_tokens(
        monkeypatch, case, v, fit):
    logits, top_k, temp, mask = _inputs(case, v)
    keys = jax.vmap(jax.random.key)(jnp.arange(ROWS, dtype=jnp.uint32) + v)
    if fit:
        # (a fresh function per lowering: ``jax.jit`` would keep the trace)
        untiled = jax.jit(lambda *a: gumbel_topk_sample_with_confidence(*a))(
            keys, logits, top_k, temp, mask)
        _force_groups(monkeypatch, fit * v * 4)
    with record_lowerings() as chosen:
        jax.make_jaxpr(lambda *a: gumbel_topk_sample_batched(*a))(
            keys, logits, top_k, temp, mask)
    assert chosen == {"sample_kth": {"xla_tiled" if fit else "xla"}}

    x = logits.astype(jnp.float32)
    if mask is not None:
        x = apply_logit_mask(x, mask)
    scaled = x / jnp.maximum(temp, 1e-8)[:, None]
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    want = np.asarray(jax.jit(reference_kth)(scaled, k_eff))
    got = np.asarray(jax.jit(
        lambda *a: kth.kth_largest_by_counting(*a, "sample_kth"))(
            scaled, k_eff))
    assert got.shape == want.shape == (ROWS, 1)
    # bit for bit, but for which of two equal zeros is handed out and for
    # a NaN's payload: neither reaches the cut
    exact = ~np.isnan(want) & (want != 0)
    np.testing.assert_array_equal(got.view(np.uint32)[exact],
                                  want.view(np.uint32)[exact])
    np.testing.assert_array_equal(got, want)    # NaN where NaN, 0 where 0
    np.testing.assert_array_equal(np.asarray(scaled) >= got,
                                  np.asarray(scaled) >= want)
    if case == "mostly_masked":
        assert np.isneginf(want[np.asarray(top_k) >= 25]).all()
    if case == "nans":
        # k or more NaNs in a row: the k-th is NaN and the whole row is cut
        n_nan = np.isnan(np.asarray(scaled)).sum(axis=-1)
        np.testing.assert_array_equal(np.isnan(want[:, 0]),
                                      n_nan >= np.asarray(k_eff))
        assert np.isnan(want).any() and not np.isnan(want).all()

    drawn = np.asarray(jax.jit(lambda *a: gumbel_topk_sample_batched(*a))(
        keys, logits, top_k, temp, mask))
    plain = np.asarray(jax.jit(reference_draw)(keys, logits, top_k, temp, mask))
    np.testing.assert_array_equal(drawn, plain)
    if mask is not None:
        assert np.asarray(mask)[np.arange(ROWS), drawn].all()
    if case == "temperatures":
        np.testing.assert_array_equal(
            drawn[:2], np.argmax(np.asarray(logits), axis=-1)[:2])
    if fit:
        # the block step's draw by groups: the one loop's tokens and
        # confidences, bit for bit
        tokens, conf = jax.jit(
            lambda *a: gumbel_topk_sample_with_confidence(*a))(
                keys, logits, top_k, temp, mask)
        np.testing.assert_array_equal(np.asarray(tokens), drawn)
        np.testing.assert_array_equal(np.asarray(tokens),
                                      np.asarray(untiled[0]))
        np.testing.assert_array_equal(
            np.asarray(conf).view(np.uint32),
            np.asarray(untiled[1]).view(np.uint32))


@pytest.mark.parametrize(("rows", "v", "fit", "groups"), [
    (40, 2_560, 16, (3, 16)), (33, 1_152, 32, (2, 32)), (9, 130, 8, (2, 8)),
    (50, 384, 24, (4, 16)), (7, 96, 7, None), (64, 256, 1, (64, 1))])
def test_groups_of_other_sizes_hand_out_the_sorts_element(
        monkeypatch, rows, v, fit, groups):
    """Budgets of 1 to 32 rows: groups of the power of two under the
    budget, whole and with a last group of 8, 1, 1 and 2 rows, a width off
    the lane grid, and one loop where the budget holds every row; k from 1
    to v."""
    rng = np.random.default_rng(rows + v)
    x = np.round(rng.normal(size=(rows, v)), 1).astype(np.float32)
    x[:, ::5] = -np.inf
    x[1, :] = rng.choice(np.array([-0.0, 0.0], np.float32), size=v)
    x.view(np.uint32)[2, 7:40:3] = 0xFFC00000
    k = jnp.asarray(np.resize([1, 2, 25, v - 1, v, v // 2], rows).astype(
        np.int32))
    want = np.asarray(reference_kth(jnp.asarray(x), k))
    _force_groups(monkeypatch, fit * v * 4)
    assert kth.group_rows(rows, v) == (groups and groups[1])
    jaxpr = str(jax.make_jaxpr(
        lambda *a: kth.kth_largest_by_counting(*a, "sample_kth"))(
            jnp.asarray(x), k))
    if groups:
        assert f"u32[{groups[0]},{groups[1]},{v}]" not in jaxpr
        assert f"u32[{groups[1]},{v}]" in jaxpr
    else:
        assert f"u32[{rows},{v}]" in jaxpr
    got = np.asarray(kth.kth_largest_by_counting(jnp.asarray(x), k,
                                                 "sample_kth"))
    np.testing.assert_array_equal(got, want)
    exact = ~np.isnan(want) & (want != 0)
    np.testing.assert_array_equal(got.view(np.uint32)[exact],
                                  want.view(np.uint32)[exact])
    assert np.isnan(want[2, 0]) == (int(k[2]) <= 11)


def _traced_draw(rows, v):
    """``(jaxpr text, lowering notes)`` of a fresh trace of the block
    step's draw at ``(rows, v)`` (``jax.make_jaxpr`` keeps a function's
    trace: a lambda per call)."""
    args = (jax.vmap(jax.random.key)(jnp.arange(rows, dtype=jnp.uint32)),
            jax.ShapeDtypeStruct((rows, v), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
            jax.ShapeDtypeStruct((rows, v), jnp.bool_))
    with record_lowerings() as chosen:
        text = str(jax.make_jaxpr(
            lambda *a: gumbel_topk_sample_with_confidence(*a))(*args))
    return text, chosen


@pytest.mark.parametrize("cell", CELL_DRAWS)
def test_the_shape_decides_where_the_rounds_run(monkeypatch, cell):
    """On the chip (its choice forced here) every token-by-token cell's
    draw traces the one loop, to the letter, and notes ``"xla"``; the block
    step's 256 x 151,936 goes by groups of 32 rows, and no uint32 array of
    its whole shape is left in the program."""
    rows, v = CELL_DRAWS[cell]
    text, off_chip = _traced_draw(rows, v)
    assert off_chip == {"sample_kth": {"xla"}} and "pallas_call" not in text
    _force_groups(monkeypatch)
    forced, chosen = _traced_draw(rows, v)
    if cell != "serve-sdar-blockdiff-backlog":
        assert chosen == {"sample_kth": {"xla"}} and forced == text
        return
    assert chosen == {"sample_kth": {"xla_tiled"}}
    assert kth.group_rows(rows, v) == 32
    # the keys exist a group at a time, never as one array of the draw's
    # shape for a loop to read 32 times
    keys = "u32[{}] = bitcast_convert_type[new_dtype=uint32]"
    assert keys.format(f"{rows},{v}") in text
    assert keys.format(f"{rows},{v}") not in forced
    assert keys.format(f"32,{v}") in forced and "pallas_call" not in forced
    # a mesh in scope keeps the one loop whatever the shape
    with jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",)):
        meshed, chosen = _traced_draw(rows, v)
    assert chosen == {"sample_kth": {"xla"}} and meshed == text


@pytest.mark.parametrize("v", (256, 16_384, 25_024, 100_352))
def test_no_width_traces_a_sort(v):
    """The cells' vocabularies, ProGen's 256 columns among them: the traced
    draw holds the 32 counting rounds and orders nothing."""
    args = (jax.vmap(jax.random.key)(jnp.arange(4, dtype=jnp.uint32)),
            jax.ShapeDtypeStruct((4, v), jnp.float32),
            jax.ShapeDtypeStruct((4,), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.float32),
            jax.ShapeDtypeStruct((4, v), jnp.bool_))
    text = str(jax.make_jaxpr(gumbel_topk_sample_batched)(*args))
    assert "sort" not in text and "scan" in text
    assert "sort" in str(jax.make_jaxpr(reference_draw)(*args))


def _serve(config, params, policy):
    engine = ServingEngine(config, params, policy=policy,
                           num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                           max_len=32)
    never_zero = np.ones((config.vocab_size,), bool)
    never_zero[0] = False
    rng = np.random.default_rng(39)
    for i, (k, temp) in enumerate(
            [(25, 1.0), (1, 1.0), (None, 0.8), (config.vocab_size, 1.3),
             (7, 0.0), (25, 1.0)]):
        engine.submit(Request(
            uid=i, max_new_tokens=6 + i % 3, seed=390 + i, temperature=temp,
            top_k=k, logit_mask=never_zero,
            tokens=rng.integers(1, config.vocab_size, 3 + 4 * i).tolist()))
    done = engine.run_until_idle(200)
    return {c.uid: list(c.tokens) for c in done}


@pytest.mark.serving
@pytest.mark.parametrize("vocab", (TINY.vocab_size, 1_088))
def test_engine_serves_the_sort_forms_tokens(monkeypatch, vocab):
    """Through ``ServingEngine``, at a narrow vocabulary and a wide one: the
    tokens of the first draw (admission) and of every chunk step are those
    it serves with the sort patched in."""
    config = dataclasses.replace(TINY, vocab_size=vocab)
    params, policy = make(config)
    calls = []

    def counted(name, form):
        def kth_of(scaled, k, op):
            calls.append((name, op))
            return form(scaled, k, op)
        return kth_of

    monkeypatch.setattr(sampler, "kth_largest_by_counting",
                        counted("counting", kth.kth_largest_by_counting))
    selected = _serve(config, params, policy)
    assert calls and set(calls) == {("counting", "sample_kth")}
    del calls[:]
    monkeypatch.setattr(sampler, "kth_largest_by_counting", counted(
        "sort", lambda scaled, k, op: reference_kth(scaled, k)))
    sorted_ = _serve(config, params, policy)
    assert calls and set(calls) == {("sort", "sample_kth")}
    assert len(selected) == 6 and selected == sorted_
    assert all(len(t) >= 6 for t in selected.values())
