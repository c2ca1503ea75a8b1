"""SDAR (``progen_tpu/models/sdar.py``) against the plain reference
(``perf/lib/reference_sdar.py``: float32, no cache, the block mask as a
``(T, T)`` boolean, a dense loop over all the experts): the prefill of the
whole blocks, prefill -> denoise -> commit through the cache, the reference's
one-forward replay of a trajectory against its own forward by forward, the
router's renormalisation and the q/k norms each shown to matter, every
expert held against the dense loop, and a causal mask inside the block
shown to FAIL the comparison."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_sdar as ref
from progen_tpu.models import experts, sdar
from tests.families import jitted, reference
from tests.sdar_tiny import BLOCK, MASK_ID, TINY, as_dict, make

T, MAX_LEN = 40, 48
CFG = as_dict(TINY)


def _tokens(seed=1, rows=2):
    return np.asarray(jax.random.randint(jax.random.key(seed), (rows, T), 1,
                                         MASK_ID))


def _reference(params, row, at=None, **kwargs):
    """One compiled forward a row length (eagerly the reference is
    dispatched op by op)."""
    with jax.default_matmul_precision("highest"):
        return reference(ref, TINY, "forward_row")(
            params, jnp.asarray(row), logit_positions=at, **kwargs)


@jax.jit
def _forward_at(params, row, at):
    return ref.forward_row(params, row, CFG, logit_positions=at,
                           key_positions=at)


def _block_reference(params, row, p0):
    """``_reference`` of ``row`` (whole blocks) at the block that starts at
    ``p0``: ``(logits (BLOCK, V), routing, keys)``, through ONE compiled
    forward of the row zero-padded to ``MAX_LEN`` — the padding is later
    blocks, which no position of the row sees."""
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:len(row)] = row
    with jax.default_matmul_precision("highest"):
        return _forward_at(params, padded, np.arange(p0, p0 + BLOCK))


def test_the_tiny_model_has_every_mechanism():
    params, _ = make()
    assert len(params["layers"]) == 3 and "head" in params
    layer = params["layers"][0]
    assert layer["norm"].shape == (2, 64)
    assert layer["attn"]["wq"].shape == (64, 4 * 16)
    assert layer["attn"]["wk"].shape == (64, 2 * 16)
    assert layer["attn"]["q_norm"].shape == (16,)
    assert layer["experts"]["wg"].shape == (8, 64, 32)
    assert layer["router"]["w"].shape == (64, 8)
    assert TINY.experts_held == TINY.router_width == 8
    assert TINY.first_expert == 0 and TINY.embed_gain == 1.0
    blocks = sdar.blocks_of(TINY)
    assert all(b.window is None and b.block == BLOCK
               for b in blocks.values())
    whole = sdar.SDARConfig()       # the published sizes
    assert (whole.num_hidden_layers, whole.num_experts, whole.vocab_size,
            whole.moe_intermediate_size) == (48, 128, 151936, 768)
    assert whole.experts_held == 128 and whole.mask_token_id == 151669


def test_the_config_reads_the_published_keys_and_refuses_what_it_lacks():
    c = sdar.SDARConfig.from_dict({
        "num_hidden_layers": 6, "mlp_only_layers": [], "model_type": "x",
        "rope_scaling": None, "denoising_steps": 2})
    assert c.num_layers == 6 and c.mlp_only_layers == ()
    for bad in ({"decoder_sparse_step": 2}, {"mlp_only_layers": (0,)},
                {"attention_bias": True}, {"tie_word_embeddings": True},
                {"use_sliding_window": True}, {"mask_token_id": 151936},
                {"denoising_steps": 5}, {"denoising_steps": 0},
                {"remasking": "sequential"}, {"num_key_value_heads": 5}):
        with pytest.raises(ValueError):
            sdar.SDARConfig(**bad)


@pytest.mark.parametrize("lengths", [(40, 24), (37, 6), (3, 18)])
def test_prefill_matches_the_reference_over_each_rows_whole_blocks(lengths):
    """The logits at a position predict that position's own token, under
    the block mask; tokens past a row's last whole block are padding."""
    params, policy = make()
    toks, lengths = _tokens(), np.asarray(lengths)
    at = np.broadcast_to(np.arange(T), (2, T))
    logits, rows, stats, chosen = jitted(sdar.prefill)(
        params, toks, lengths, TINY, policy, logit_positions=at,
        with_choices=True)
    for i, n in enumerate(lengths // BLOCK * BLOCK):
        if n:
            want, sets = _reference(params, toks[i, :n])
            np.testing.assert_allclose(logits[i, :n], want, atol=2e-5)
            np.testing.assert_array_equal(
                np.sort(chosen[:, i, :n], -1), np.sort(sets, -1))
    whole = int((lengths // BLOCK * BLOCK).sum())
    assert float(stats["moe.tokens"]) == 3 * whole
    assert float(stats["moe.held_load"].sum()) == 3 * 2 * whole
    pairs = sum(n * (n + BLOCK) / 2 for n in lengths // BLOCK * BLOCK)
    assert float(stats["attn.prefill_pairs_allowed"]) == 3 * pairs


def test_bf16_prefill_stays_near_the_reference():
    """bfloat16 products (8 bits of mantissa) through 3 layers: logits of
    spread 1 within 0.15 where the routing agreed — the cell's limits at
    the published widths are set from readings on the chip."""
    params, policy = make(mixed=True)
    toks = _tokens()
    at = np.broadcast_to(np.arange(T), (2, T))
    logits, _, _, chosen = jitted(sdar.prefill)(
        params, toks, np.asarray([T, T]), TINY, policy, logit_positions=at,
        with_choices=True)
    assert params["embed"].dtype == jnp.bfloat16
    want, sets = _reference(params, toks[0])
    agreed = (np.sort(chosen[:, 0], -1) == np.sort(sets, -1)).all((0, 2))
    assert agreed.mean() > 0.7
    assert float(np.abs(np.asarray(logits[0]) - want)[agreed].max()) < 0.15


def _trajectory(seed, n):
    """A seeded trajectory over a prime of ``n``: two whole blocks after
    the prime's whole blocks, each position's fill step (-1 in the prime)."""
    rng = np.random.default_rng(seed)
    whole = n // BLOCK * BLOCK
    end = whole + 2 * BLOCK
    tokens = rng.integers(1, MASK_ID, end)
    fills = np.full(end, -1)
    for p0 in range(whole, end, BLOCK):
        order = rng.permutation([p for p in range(p0, p0 + BLOCK) if p >= n])
        fills[order[:2]], fills[order[2:]] = 0, 1
    return tokens, fills, whole, end


@pytest.mark.parametrize("mixed", [False, True])
def test_prefill_denoise_commit_through_the_cache_matches_the_reference(
        mixed):
    """Rows at every ``P mod 4``: each denoise forward's logits are the
    reference's full forward of the committed tokens and the block as it
    stood; a denoise forward leaves the cache as it found it; a commit
    writes the reference's keys at the block's rows and nowhere else."""
    params, policy = make(mixed=mixed)
    primes = np.asarray([21, 16, 7, 30])
    paths = [_trajectory(i, n) for i, n in enumerate(primes)]
    padded = np.zeros((4, 32), np.int32)
    for i, (tokens, _, _, _) in enumerate(paths):
        padded[i, :primes[i]] = tokens[:primes[i]]
    _, rows, _ = jitted(sdar.prefill)(params, padded, primes, TINY, policy)
    caches = jitted(sdar.caches_from)(rows, primes, TINY, MAX_LEN)
    live = np.ones(4, bool)
    def step(p, t, p0, c, commit):
        return jitted(sdar.block_step)(p, t, p0, c, live, commit, TINY,
                                       policy)[:2]

    tol = 0.2 if mixed else 2e-5
    for j in range(2):
        pos0 = np.asarray([w + j * BLOCK for _, _, w, _ in paths])
        for s in range(3):
            tok = np.stack([np.where(f[p0:p0 + BLOCK] < s,
                                     t[p0:p0 + BLOCK], MASK_ID)
                            for (t, f, _, _), p0 in zip(paths, pos0)])
            commit = np.full(4, s == 2) & ((np.arange(4) != 3) | (j == 0))
            logits, new = step(params, tok, pos0, caches, commit)
            for i, (t, f, _, _) in enumerate(paths):
                p0 = int(pos0[i])
                row = np.concatenate([t[:p0], tok[i]])
                want, _, keys = _block_reference(params, row, p0)
                diff = np.abs(logits[i] - want)
                # bfloat16 at these tiny widths: a routing that differs
                # (2 of 8 experts, near-ties) moves a token's logits by more
                # than any tolerance, so the MEAN difference is held; the
                # float32 case holds every logit
                assert float(diff.mean() if mixed else diff.max()) < tol
                for layer, name in enumerate(caches):
                    old = np.asarray(caches[name]["k"][i], np.float32)
                    got = np.asarray(new[name]["k"][i], np.float32)
                    changed = (old != got).any(axis=(0, 2))
                    if commit[i]:
                        # (a prime's tail rows may hold the same bits
                        # already: the prefill cached them as padding)
                        inside = [p0 <= r < p0 + BLOCK
                                  for r in range(MAX_LEN)]
                        assert not (changed & ~np.asarray(inside)).any()
                        assert changed[max(p0, int(primes[i])):
                                       p0 + BLOCK].all()
                        np.testing.assert_allclose(
                            got[:, p0:p0 + BLOCK].transpose(1, 0, 2),
                            keys[layer, 0], atol=0.5 if mixed else 2e-5)
                    else:
                        assert not changed.any()
            caches = new


def _folded_case(mixed, cursors, has, live):
    """Slots whose block in progress starts at ``cursors``, with (``has``)
    a finished block before it whose keys are not in the cache yet, or with
    every earlier row cached: ``(params, policy, caches, blk, pend, cursors,
    has & live, live)``."""
    params, policy = make(mixed=mixed)
    cursors, has, live = (np.asarray(a) for a in (cursors, has, live))
    rng = np.random.default_rng(3)
    toks = rng.integers(1, MASK_ID, (len(cursors), 32))
    cached = cursors - BLOCK * has          # rows the cache holds
    # (the prefill sees other tokens past what it caches: no row of the
    # cache holds a later block's keys by accident)
    primes = np.where(np.arange(32)[None] < cached[:, None], toks, 1)
    _, rows, _ = jitted(sdar.prefill)(params, primes, cached, TINY, policy)
    caches = jitted(sdar.caches_from)(rows, cached, TINY, MAX_LEN)
    pend = np.stack([t[c - BLOCK:c] if h else np.full(BLOCK, MASK_ID)
                     for t, c, h in zip(toks, cursors, has)])
    blk = np.stack([np.where(rng.random(BLOCK) < 0.5, t[c:c + BLOCK], MASK_ID)
                    for t, c in zip(toks, cursors)])
    return params, policy, caches, blk, pend, cursors, has & live, live


FOLDED = {
    # pending blocks mixed across slots, beside a slot just admitted
    "mixed": ((8, 12, 20, 16), (True, False, True, False), (True,) * 4),
    # nothing committed: a first block pending at rows 0..3, and a slot
    # whose prime is shorter than a block (its filler lies before row 0)
    "first-block": ((4, 0, 4, 8), (True, False, False, True), (True,) * 4),
    "dead-slot": ((8, 12, 20, 16), (True, True, False, False),
                  (True, False, True, False)),
}


@pytest.mark.parametrize("mixed", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(FOLDED))
def test_a_pending_block_rides_the_next_forward_as_its_own_commit_would(
        case, mixed):
    """ONE forward with the pending block in front against the two it
    replaces — a commit forward of the pending block, then a denoise forward
    of the next: the same keys at the pending block's rows and nowhere
    else, the same logits of the block in progress."""
    params, policy, caches, blk, pend, cursors, riding, live = _folded_case(
        mixed, *FOLDED[case])
    def step(p, t, p0, c, live, commit, pending=None):
        return jitted(sdar.block_step)(p, t, p0, c, live, commit, TINY,
                                       policy, pending=pending)[:2]

    got, new = step(params, blk, cursors, caches, live, riding, pend)
    _, committed = step(params, pend, np.maximum(cursors - BLOCK, 0), caches,
                        live, riding)
    want, after = step(params, blk, cursors, committed, live,
                       np.zeros_like(live))
    assert got.shape == want.shape == (len(cursors), BLOCK, TINY.vocab_size)
    for i in np.flatnonzero(live):
        diff = np.abs(got[i] - want[i])
        # (bfloat16 at these widths: ``..._through_the_cache_...`` says why
        # the mean is what is held)
        assert float(diff.mean() if mixed else diff.max()) < (
            0.2 if mixed else 2e-5)
    for name in caches:
        for part in ("k", "v"):
            old, mine, theirs = (np.asarray(c[name][part], np.float32)
                                 for c in (caches, new, after))
            np.testing.assert_allclose(mine, theirs,
                                       atol=0.1 if mixed else 2e-5)
            changed = (old != mine).any(axis=(1, 3))
            inside = ((np.arange(MAX_LEN)[None] >= cursors[:, None] - BLOCK)
                      & (np.arange(MAX_LEN)[None] < cursors[:, None])
                      & riding[:, None])
            np.testing.assert_array_equal(changed, inside)


def test_a_slot_without_a_pending_block_writes_nothing_and_shows_nothing():
    """Bitwise: the filler in front of a block in progress reaches no other
    row's logits, whatever it holds, and no cache row."""
    params, policy, caches, blk, pend, cursors, riding, live = _folded_case(
        False, (8, 12, 0, 16), (True, False, False, False), (True,) * 4)
    step = jax.jit(lambda pending: sdar.block_step(
        params, blk, cursors, caches, live, riding, TINY, policy,
        pending=pending))
    got, new, stats = step(pend)
    other = pend.copy()
    other[1:] = np.random.default_rng(0).integers(1, MASK_ID, (3, BLOCK))
    again, new2, stats2 = step(other)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(new2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in caches:     # slots 1..3 keep every row they had
        np.testing.assert_array_equal(np.asarray(new[name]["k"][1:]),
                                      np.asarray(caches[name]["k"][1:]))
    # and no counter counts it: 4 live blocks and the one that rides
    for key in stats:
        np.testing.assert_array_equal(np.asarray(stats[key]),
                                      np.asarray(stats2[key]))
    assert float(stats["attn.decode_rows"]) == 5 * BLOCK
    # (the last of the 3 layers needs the riding block's keys and no more:
    # its experts see the blocks in progress alone)
    assert float(stats["moe.tokens"]) == (2 * 5 + 4) * BLOCK
    assert float(stats["attn.context_tokens"]) == (8 - BLOCK) + 12 + 0 + 16


def test_a_replay_row_is_every_forward_of_the_trajectory_at_once():
    """``reference_sdar.replay_row``: the reference's ONE forward of the
    clean row and its noisy copies reads, for every kept token, the logits
    the forward that kept it read — held against that forward computed on
    its own (the committed tokens and the block as it stood)."""
    params, _ = make()
    for seed, n in enumerate([21, 16, 7]):
        tokens, fills, whole, end = _trajectory(seed, n)
        row, positions, allowed, index = ref.replay_row(
            tokens[:n], tokens[n:], fills[n:], CFG, 2, width=64)
        got, _ = _reference(params, row, index, positions=positions,
                            allowed=allowed)
        for g, q in enumerate(range(n, end)):
            p0, s = q // BLOCK * BLOCK, fills[q]
            block = np.where(fills[p0:p0 + BLOCK] < s,
                             tokens[p0:p0 + BLOCK], MASK_ID)
            want = _block_reference(
                params, np.concatenate([tokens[:p0], block]), p0)[0]
            np.testing.assert_allclose(got[g], want[q - p0], atol=2e-5)
    # a last, partial block's tokens are not replayed
    _, _, _, index = ref.replay_row(tokens[:n], tokens[n:end - 1],
                                    fills[n:end - 1], CFG, 2)
    assert (index[-3:] == -1).all() and (index[:-3] >= 0).all()


def test_a_causal_mask_inside_the_block_fails_the_comparison():
    """The error a block-diffusion server can make silently: the same
    forward under a causal mask differs from the program by the whole
    spread of the logits (a position sees less of its own block, and from
    the second layer on keys that saw less of theirs)."""
    params, policy = make()
    toks = _tokens()[:1]
    at = np.arange(T)[None]
    logits, _, _ = jitted(sdar.prefill)(params, toks, np.asarray([T]), TINY, policy,
                                logit_positions=at)
    causal, _ = _reference(params, toks[0], allowed=np.tril(np.ones((T, T),
                                                                    bool)))
    diff = np.abs(np.asarray(logits[0]) - causal).max(-1)
    assert (diff > 0.1).all() and diff.mean() > 0.5


def test_the_renormalisation_and_the_qk_norms_each_matter():
    params, policy = make()
    toks, lengths = _tokens()[:1], np.asarray([T])
    at = np.arange(T)[None]
    base = jitted(sdar.prefill)(params, toks, lengths, TINY, policy,
                        logit_positions=at)[0]
    loose = dataclasses.replace(TINY, norm_topk_prob=False)
    assert float(jnp.abs(jitted(sdar.prefill)(params, toks, lengths, loose, policy,
                                      logit_positions=at)[0] - base).max()) > 0.1
    flat = jax.tree.map(lambda a: a, params)
    flat["layers"] = [{**layer, "attn": {
        **layer["attn"], "q_norm": jnp.ones_like(layer["attn"]["q_norm"]),
        "k_norm": jnp.ones_like(layer["attn"]["k_norm"])}}
        for layer in params["layers"]]
    scaled = jitted(sdar.prefill)(flat, toks, lengths, TINY, policy,
                          logit_positions=at)[0]
    assert float(jnp.abs(scaled - base).max()) > 1e-3
    # and the router against a NumPy transcription
    u = jax.random.normal(jax.random.key(3), (10, 64))
    ids, w = sdar.route(u, params["layers"][0]["router"], TINY)
    logit = np.asarray(u, np.float64) @ np.asarray(
        params["layers"][0]["router"]["w"], np.float64)
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(top, -1))
    picked = np.take_along_axis(p, np.asarray(ids), axis=-1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


def test_every_expert_held_is_the_dense_loop():
    """``experts_held == router_width``, ``first_expert`` 0: no assignment
    is another chip's, and the grouped product adds what a loop over all
    the experts adds."""
    params, _ = make()
    layer = params["layers"][1]
    u = jax.random.normal(jax.random.key(5), (24, 64))
    live = jnp.arange(24) < 20
    ids, w = sdar.route(u, layer["router"], TINY)
    y, load = experts.held_experts(u, ids, w, live, layer["experts"], TINY)
    want = np.zeros((24, 64), np.float32)
    for e in range(8):
        w_e = np.where(np.asarray(ids) == e, np.asarray(w), 0).sum(-1)
        g = np.asarray(u) @ np.asarray(layer["experts"]["wg"][e])
        up = np.asarray(u) @ np.asarray(layer["experts"]["wu"][e])
        out = (g / (1 + np.exp(-g)) * up) @ np.asarray(
            layer["experts"]["wd"][e])
        want += w_e[:, None] * out * np.asarray(live)[:, None]
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert int(load.sum()) == 20 * 2


def test_a_block_step_counts_b_query_rows_a_live_slot():
    params, policy = make()
    toks = _tokens()
    primes = np.asarray([20, 8])
    _, rows, _ = jitted(sdar.prefill)(params, toks[:, :32], primes, TINY, policy)
    caches = jitted(sdar.caches_from)(rows, primes, TINY, MAX_LEN)
    blk = np.full((2, BLOCK), MASK_ID, np.int32)
    _, _, stats = jitted(sdar.block_step)(
        params, blk, primes, caches, np.asarray([True, False]),
        np.zeros(2, bool), TINY, policy)
    assert float(stats["attn.decode_rows"]) == BLOCK
    assert float(stats["attn.context_tokens"]) == 20
    assert float(stats["attn.full_rows_read"]) == 2 * MAX_LEN
    assert float(stats["moe.tokens"]) == 3 * BLOCK
    assert float(stats["moe.decode_layers"]) == 3
    assert float(stats["moe.expert_passes"]) == 0    # the XLA form
