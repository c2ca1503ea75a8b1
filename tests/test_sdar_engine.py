"""SDAR through ``ServingEngine``'s normal path (the seam of
``decode/family.py``) by diffusion over blocks: the tests every driver family
runs (``tests/families.py``), where the plain sampler is the reference's
``generate_block`` (the whole sequence recomputed at every forward) over
primes at every ``P mod 4`` and the probe rule is that of the forward that
kept each token; what is SDAR's own here: both remasking rules; ``stop`` and
end of sequence inside a block drop what follows; a request's tokens are the
same alone and among neighbours admitted at other steps; a block costs two
forwards; a snapshot replays a block request; the tiled draw."""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from perf.lib import reference_sdar as ref
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.observe.metrics import get_registry
from progen_tpu.ops import kth
from tests import families
from tests.families import SLOTS
from tests.sdar_tiny import BLOCK, MASK_ID, TINY, as_dict

pytestmark = pytest.mark.serving

CASE = families.CASES["sdar"]
ENGINE = CASE.engine
TOP_K, PRIMES = CASE.top_k, CASE.primes
assert CASE.draw_below == MASK_ID
DYNAMIC = dataclasses.replace(TINY, remasking="low_confidence_dynamic",
                              denoising_steps=4, confidence_threshold=0.3)


@pytest.fixture(scope="module")
def served():
    return CASE.served()


@pytest.fixture(scope="module")
def engine():
    return families.engine_of(CASE)


_allowed = functools.partial(families.never_zero, CASE)
_requests = functools.partial(families.requests, CASE)
_serve = families.serve


def _reference_tokens(params, r, config=TINY):
    """``generate_block`` over ONE compiled forward a configuration (rows
    padded to the engine's ``max_len``)."""
    return ref.generate_block(
        params, r.tokens, as_dict(config), r.max_new_tokens,
        forward=families.reference_logits(CASE, config),
        width=ENGINE["max_len"], top_k=r.top_k, temperature=r.temperature,
        allowed_tokens=r.logit_mask)


def greedy(case, reqs, done):
    """Float32: the same tokens, kept at the same denoise forwards; more
    requests than slots of an admission run, so rows sit in different
    phases of their blocks."""
    params = case.served()[0]
    assert {len(r.tokens) % BLOCK for r in reqs} == {0, 1, 2, 3}
    for r in reqs:
        tokens, fills = _reference_tokens(params, r)
        c = done[r.uid]
        assert c.finish_reason == "length" and c.ok
        assert c.tokens.tolist() == tokens, r.uid
        assert c.fill_steps.tolist() == fills, r.uid
        assert len(tokens) == r.max_new_tokens
        assert set(fills) <= {0, 1} and MASK_ID not in tokens
    # a request that does not ask is not told
    plain = Request(uid=99, tokens=reqs[0].tokens, max_new_tokens=5,
                    temperature=0.0, logit_mask=_allowed())
    assert _serve(families.engine_of(case), [plain])[99].fill_steps is None


def test_the_dynamic_rule_serves_generate_blocks_tokens(served):
    """A threshold the top-5 draws pass often: blocks take fewer denoise
    forwards than the static rule's, row by row."""
    params, policy = served
    eng = ServingEngine(DYNAMIC, params, policy=policy, **ENGINE)
    reqs = _requests(4, seed=3)
    done = _serve(eng, reqs)
    kept = np.asarray(eng.model_stats["diffusion.positions_kept"])
    assert kept.shape == (4,) and kept[0] > kept[1] > 0
    for r in reqs:
        tokens, fills = _reference_tokens(params, r, DYNAMIC)
        assert done[r.uid].tokens.tolist() == tokens
        assert done[r.uid].fill_steps.tolist() == fills


def _moved(engine, before, key):
    """How far the engine's counter ``key`` moved since ``before``."""
    return float(engine.model_stats[key]) - float(before.get(key, 0))


@pytest.mark.parametrize("new", [1, 2, 3, 5, 6, 7])
def test_stop_inside_a_block_drops_what_follows(served, engine, new):
    r = Request(uid=new, tokens=list(range(1, 7)), max_new_tokens=new,
                temperature=0.0, top_k=TOP_K, logit_mask=_allowed())
    before = dict(engine.model_stats or {})
    c = _serve(engine, [r])[new]
    tokens, _ = _reference_tokens(served[0], r)
    assert c.tokens.tolist() == tokens and len(tokens) == new
    assert c.finish_reason == "length"

    def moved(key):
        return _moved(engine, before, key)

    assert moved("diffusion.tokens_committed") == new
    # the prime of 6 leaves 2 positions of its block; whole blocks after
    blocks = 1 + -(-max(new - 2, 0) // BLOCK)
    assert moved("diffusion.tokens_dropped") == 2 + (blocks - 1) * 4 - new
    # the last block ends the request: nobody reads its keys, none written
    assert moved("diffusion.commit_forwards") == blocks - 1


@pytest.mark.parametrize("blocks,short", [(1, 0), (2, 0), (4, 0), (3, 1),
                                          (2, 3)])
def test_a_block_costs_two_forwards_and_its_commit_rides_the_next(
        served, engine, blocks, short):
    """A prime of whole blocks under the static rule (2 positions a forward
    of 4): ``n`` blocks take ``2 n`` live forwards — none is a commit's
    alone — and ``n - 1`` pending blocks are written, each by the forward
    that opens the next block; the last block's keys, which nobody reads,
    are not, whether the request ends on the block's edge or inside it."""
    new = blocks * BLOCK - short
    r = Request(uid=800 + new, tokens=list(range(1, 9)), max_new_tokens=new,
                temperature=0.0, top_k=TOP_K, logit_mask=_allowed())
    before = dict(engine.model_stats or {})
    c = _serve(engine, [r])[r.uid]
    tokens, _ = _reference_tokens(served[0], r)
    assert c.tokens.tolist() == tokens and len(tokens) == new
    assert _moved(engine, before, "diffusion.forwards") == 2 * blocks
    assert _moved(engine, before, "diffusion.commit_forwards") == blocks - 1
    assert _moved(engine, before, "diffusion.tokens_committed") == new
    assert _moved(engine, before, "diffusion.tokens_dropped") == short
    # a forward's context ends before the pending block that rides it
    starts = 8 + BLOCK * np.arange(blocks)
    assert _moved(engine, before, "attn.context_tokens") == (
        2 * starts.sum() - BLOCK * (blocks - 1))
    assert _moved(engine, before, "attn.decode_rows") == BLOCK * (
        2 * blocks + blocks - 1)
    state = engine.state
    assert state["pending"].shape == (SLOTS, BLOCK)
    assert not np.asarray(state["has_pending"]).any()


def test_a_request_that_ends_on_end_of_sequence_writes_no_last_block(
        served, engine):
    """Only tokens 0 and 7 allowed, as below: the block that holds the
    first 0 ends the request and keeps no pending block."""
    mask = np.zeros((TINY.vocab_size,), bool)
    mask[[0, 7]] = True
    hit = 0
    for seed in range(6):
        r = _requests(1, seed=seed, mask=mask, first_uid=900 + seed)[0]
        r.max_new_tokens = 14
        before = dict(engine.model_stats or {})
        c = _serve(engine, [r])[r.uid]
        if c.finish_reason != "eos":
            continue
        hit += 1
        tail = len(r.tokens) % BLOCK
        blocks = -(-(tail + len(c.tokens)) // BLOCK)
        assert blocks < -(-(tail + 14) // BLOCK) or c.tokens[-1] == 0
        assert _moved(engine, before, "diffusion.commit_forwards") == (
            blocks - 1)
        assert _moved(engine, before, "diffusion.forwards") == (
            -(-(BLOCK - tail) // 2) + 2 * (blocks - 1))
    assert hit >= 3
    assert not np.asarray(engine.state["has_pending"]).any()


def test_end_of_sequence_inside_a_block_ends_the_request(served, engine):
    """Only tokens 0 and 7 allowed: the best of the two at each position,
    so a block soon holds a 0; the tokens after it are dropped."""
    mask = np.zeros((TINY.vocab_size,), bool)
    mask[[0, 7]] = True
    hit = 0
    for seed in range(6):
        r = _requests(1, seed=seed, mask=mask, first_uid=200 + seed)[0]
        r.max_new_tokens = 14
        c = _serve(engine, [r])[r.uid]
        tokens, fills = _reference_tokens(served[0], r)
        assert c.tokens.tolist() == tokens
        assert c.fill_steps.tolist() == fills
        if 0 in tokens:
            hit += 1
            assert tokens.index(0) == len(tokens) - 1
            assert c.finish_reason == "eos"
    assert hit >= 3


def test_a_requests_tokens_do_not_depend_on_its_neighbours(served, engine):
    """Sampled: alone, and among neighbours admitted steps before it."""
    reqs = _requests(5, seed=9, sampled=True, first_uid=300)
    for r in reqs:      # the first three are mid-flight when the rest come
        r.max_new_tokens += 8
    alone = {r.uid: _serve(engine, [r])[r.uid] for r in reqs}
    for r in reqs[:3]:
        engine.submit(r)
    early = engine.step()
    assert not early
    for r in reqs[3:]:
        engine.submit(r)
    together = {c.uid: c for c in early + engine.run_until_idle(200)}
    for r in reqs:
        assert together[r.uid].tokens.tolist() == alone[r.uid].tokens.tolist()
        assert (together[r.uid].fill_steps.tolist()
                == alone[r.uid].fill_steps.tolist())
    # and they are draws: another seed, other tokens
    again = dataclasses.replace(reqs[0], uid=399, seed=1234)
    assert (_serve(engine, [again])[399].tokens.tolist()
            != alone[reqs[0].uid].tokens.tolist())


def sampled(case, reqs, done):
    """The probe rule at tiny size: the reference's replay of the served
    trajectory ranks every kept token among its 5 best allowed logits at the
    forward that kept it (float32: but for near-ties)."""
    params = case.served()[0]
    replay = families.reference(ref, TINY, "forward_row")
    for r in reqs:
        c = done[r.uid]
        row, positions, allowed, index = ref.replay_row(
            r.tokens, c.tokens, c.fill_steps, as_dict(TINY), 2, width=96)
        with jax.default_matmul_precision("highest"):
            logits, _ = replay(
                params, row, positions=positions, allowed=allowed,
                logit_positions=np.maximum(index, 0))
        logits = np.array(logits)
        logits[:, [0, MASK_ID]] = -np.inf
        ok = index >= 0
        served_logit = logits[np.arange(len(index)), c.tokens]
        kth = np.sort(logits, axis=-1)[:, -TOP_K]
        assert (served_logit[ok] >= kth[ok] - 1e-4).all()


def slot_holds(engine):
    state = engine.state
    assert state["block"].shape == (SLOTS, BLOCK)
    assert state["fill"].shape == (SLOTS, ENGINE["max_len"])
    assert state["cursor"].shape == state["dstep"].shape == (SLOTS,)
    assert state["caches"]["l0"]["k"].shape == (SLOTS, 2, 48, 16)
    assert engine.lowerings == {
        "gqa_prefill": "xla", "gqa_block_decode": "xla",
        "moe_experts": "xla", "row_write": "scatter", "sample_kth": "xla"}
    # the draw is the chunk program's alone: an admission draws nothing
    assert engine.status()["sample_kth"] == {"chunk": "xla"}
    assert engine.status()["gqa_block_decode"] == "xla"
    assert engine.block_length == BLOCK


def test_the_engine_refuses_what_a_block_cannot_take(served):
    params, policy = served
    with pytest.raises(ValueError, match="whole number"):
        ServingEngine(TINY, params, policy=policy, num_slots=SLOTS,
                      chunk_size=4, max_len=46)
    with pytest.raises(UnsupportedFamilyMode, match="logit_mask"):
        families.engine_of(CASE).submit(Request(
            uid=0, tokens=[1, 2], max_new_tokens=4,
            logit_mask=np.ones((4, TINY.vocab_size), bool)))


def states(family):
    policy = CASE.served()[1]
    assert (family.block_length, family.mask_token_id,
            family.denoising_steps, family.remasking) == (
                BLOCK, MASK_ID, 2, "low_confidence_static")
    with pytest.raises(NotImplementedError, match="block_step"):
        family.decode_step(None, None, None, None, None)
    # every other family states one token a row a step
    from progen_tpu.models.configs import SMALL
    from tests.trinity_tiny import TINY as TRINITY

    assert family_for(TRINITY, policy).block_length is None
    assert family_for(SMALL, policy).block_length is None


def counters(engine, reqs, stats, total):
    new = sum(r.max_new_tokens for r in reqs)
    assert stats["diffusion.tokens_committed"] == new
    # each request's blocks: from the prime's last whole block to stop
    spans = [(len(r.tokens) // BLOCK * BLOCK,
              len(r.tokens) + r.max_new_tokens) for r in reqs]
    blocks = sum(-(-(stop - whole) // BLOCK) for whole, stop in spans)
    generated = sum(whole + -(-(stop - whole) // BLOCK) * BLOCK
                    - len(r.tokens) for (whole, stop), r in zip(spans, reqs))
    # a block's keys ride the forward that opens the next: every block but
    # each request's last writes, and no forward is a commit's alone
    writes = stats["diffusion.commit_forwards"]
    assert writes == blocks - len(reqs)
    assert stats["diffusion.tokens_dropped"] == generated - new
    kept = np.asarray(stats["diffusion.positions_kept"])
    assert kept.sum() == generated and kept.shape == (2,)
    forwards = stats["diffusion.forwards"]
    assert blocks <= forwards <= blocks * 2
    # a pending block that rides is B more rows of its forward, in every
    # layer but the last, which needs its keys and values only
    assert stats["attn.decode_rows"] == BLOCK * (forwards + writes)
    assert stats["moe.tokens"] == BLOCK * (3 * forwards + 2 * writes) + (
        3 * sum(whole for whole, _ in spans))
    assert stats["moe.held_load"].sum() == 2 * stats["moe.tokens"]
    snap = get_registry().snapshot()
    for name in ("diffusion.forwards", "diffusion.commit_forwards",
                 "diffusion.tokens_committed", "diffusion.tokens_dropped",
                 "moe.tokens", "attn.decode_rows", "attn.context_tokens"):
        assert snap[name]["value"] == total[name], name
    kept = np.asarray(total["diffusion.positions_kept"])
    assert snap["diffusion.positions_kept.0"]["value"] == kept[0]
    assert snap["diffusion.positions_kept.1"]["value"] == kept[1]
    assert snap["moe.held_assignments"]["value"] == total[
        "moe.held_load"].sum()


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy, sampled=sampled)


def test_a_snapshot_replays_a_block_request_token_for_token(served, engine):
    """Host-side state only: the replay starts from the prime and the seed,
    so it serves the same blocks, and still reports their fill steps."""
    params, policy = served
    reqs = _requests(3, seed=21, sampled=True, first_uid=600)
    for r in reqs:      # more blocks than one chunk's forwards fill
        r.max_new_tokens += 8
    want = _serve(engine, reqs)
    for r in reqs:
        engine.submit(r)
    engine.step()
    snap = engine.snapshot()
    assert all(e["record_fill_steps"] for e in snap["requests"])
    fresh = ServingEngine(TINY, params, policy=policy, **ENGINE)
    assert fresh.restore(snap) == 3
    got = {c.uid: c for c in fresh.run_until_idle(200)}
    engine.run_until_idle(200)
    for r in reqs:
        assert got[r.uid].tokens.tolist() == want[r.uid].tokens.tolist()
        assert (got[r.uid].fill_steps.tolist()
                == want[r.uid].fill_steps.tolist())


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_the_tiled_draw_serves_the_one_loops_tokens(monkeypatch, served,
                                                    sampled):
    """Under a budget that holds 24 of the block step's 128 rows, as the
    chip's 32 MiB hold 55 of SDAR's 256: the chunk program's draw goes by
    eight groups of 16 rows (the chip's by eight of 32), the status says so
    by program, and every request's tokens and fill steps are the one
    loop's."""
    params, policy = served

    def serve():
        eng = ServingEngine(TINY, params, policy=policy, **ENGINE)
        assert eng.status()["sample_kth"] is None
        done = _serve(eng, _requests(len(PRIMES), seed=43, sampled=sampled,
                                     first_uid=700, mask=_allowed(MASK_ID)))
        return eng.status()["sample_kth"], {
            uid: (c.tokens.tolist(), c.fill_steps.tolist())
            for uid, c in done.items()}

    took, want = serve()
    assert took == {"chunk": "xla"}
    monkeypatch.setattr(kth, "_on_tpu", lambda: True)
    monkeypatch.setattr(kth, "ROUNDS_ON_CHIP_BYTES",
                        24 * TINY.vocab_size * 4)
    assert kth.group_rows(SLOTS * BLOCK, TINY.vocab_size) == 16
    took, got = serve()
    assert took == {"chunk": "xla_tiled"}
    assert len(got) == len(PRIMES) and got == want
