"""dots3 through ``ServingEngine``'s normal path (the seam of
``decode/family.py``, unchanged): the tests every driver family runs
(``tests/families.py``) over slots of mixed lengths — under, at and past the
window and ``index_topk`` at admission, all past both before they finish;
what is dots3's own here: a slot's state holds latent rows and indexer rows
for each full block and a ring of another latent shape for each sliding one;
a slot readmitted after a longer request (stale latent rows, stale indexer
rows, a stale ring) serves what a fresh one serves; the byte gauges reach the
registry and ``status()["model_stats"]``; and with the full layers' heads at
the published widths and the kernel forced, the admission says
``"mla_prefill": "pallas"``, serves the same tokens and counts the tiles it
visited."""

import jax
import numpy as np
import pytest

from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.observe.metrics import get_registry
from progen_tpu.ops import dsa
from tests import families
from tests.dots3_tiny import (TINY, TOP_K, WIDE, WIDE_TOP_K, WINDOW,
                              force_prefill_kernel, make)
from tests.families import SLOTS

pytestmark = pytest.mark.serving

CASE = families.CASES["dots3"]
MAX_LEN = CASE.max_len
assert CASE.primes == (WINDOW - 1, WINDOW, TOP_K - 1, TOP_K, TOP_K + 1, 21)


@pytest.fixture(scope="module")
def engine():
    return families.engine_of(CASE)


def greedy(case, reqs, done):
    assert all(len(r.tokens) + r.max_new_tokens > TOP_K for r in reqs)
    families.serves_the_plain_samplers_tokens(case, reqs, done)


def test_a_slot_readmitted_after_a_longer_request_serves_what_a_fresh_one_does(
        engine):
    """Every slot holds a 21-token request's latent rows, indexer rows and
    rings, then takes a prime of 2-9 tokens: stale rows past the short
    request's count, in all three, must reach nothing — the indexer must
    not select one, the ring's core must not read one."""
    long = families.requests(CASE, SLOTS, seed=7, first_uid=200, primes=(21,))
    assert len(families.serve(engine, long)) == SLOTS
    short = families.requests(
        CASE, SLOTS, seed=8, first_uid=300,
        primes=(2, WINDOW - 1, WINDOW + 1, TOP_K, TOP_K + 1))
    families.serves_the_plain_samplers_tokens(
        CASE, short, families.serve(engine, short))


def slot_holds(engine):
    assert jax.tree.map(lambda a: a.shape[1:], engine.state["caches"]) == {
        "l0": {"latent": (MAX_LEN, 20), "index": (MAX_LEN, 8)},
        "l1": {"latent": (MAX_LEN, 20), "index": (MAX_LEN, 8)},
        "l2": (WINDOW, 28), "l3": (WINDOW, 28)}
    status = engine.status()
    assert status["row_write"] == "scatter"     # the CPU's lowering
    assert status["mla_decode"] == status["gqa_prefill"] == "xla"
    # the admissions past ``index_topk`` count their thresholds, one loop
    assert status["dsa_kth"] == {"admit": "xla"}
    assert "mla_prefill" not in status or status["mla_prefill"] is None


def states(family):
    assert family.block_length is None
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings


def counters(engine, reqs, stats, total):
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["moe.tokens"] == 3 * (prime_tokens + steps)
    assert stats["mla.decode_rows"] == steps
    assert stats["moe.held_load"].sum() == 2 * stats["moe.tokens"]
    # the i-th step of a request stands on position prime + i - 1: it has
    # prime + i tokens of context, min(., 8) of them selected and min(., 5)
    # of them in a ring
    lengths = [len(r.tokens) + i for r in reqs
               for i in range(1, r.max_new_tokens)]
    assert stats["mla.context_tokens"] == sum(lengths)
    assert stats["dsa.context_tokens"] == sum(lengths)
    assert stats["dsa.keys_selected"] == sum(min(n, TOP_K) for n in lengths)
    assert stats["mla.window_tokens"] == sum(min(n, WINDOW) for n in lengths)
    # the XLA score reads every slot's every indexer row each step that ran,
    # the sparse core top-k gathered rows a slot, the ring's core the ring
    chunk_steps = stats["dsa.index_rows_read"] / (SLOTS * MAX_LEN)
    assert chunk_steps == int(chunk_steps) and chunk_steps >= max(
        r.max_new_tokens - 1 for r in reqs)
    assert stats["mla.cache_rows_read"] == chunk_steps * SLOTS * TOP_K
    assert stats["mla.window_rows_read"] == chunk_steps * SLOTS * WINDOW
    # the pairs the masked form computed beside those the selection allows
    assert stats["dsa.prefill_pairs_attended"] > stats[
        "dsa.prefill_pairs_selected"] > 0
    assert stats["dsa.prefill_pairs_scored"] > 0
    # no byte counter rides in the state: the gauges are the rows at each
    # kind's own row bytes (float32 here) times the kind's blocks
    assert not [k for k in stats if k.endswith("_bytes_read")]
    gauges = engine.status()["model_stats"]
    assert gauges["dsa.index_bytes_read"] == (
        total["dsa.index_rows_read"] * 2 * 8 * 4)
    assert gauges["mla.cache_bytes_read"] == (
        total["mla.cache_rows_read"] * 2 * 20 * 4)
    assert gauges["mla.window_bytes_read"] == (
        total["mla.window_rows_read"] * 2 * 28 * 4)
    snap = get_registry().snapshot()
    for name in ("moe.tokens", "moe.decode_layers", "moe.experts_touched",
                 "mla.decode_rows", "mla.context_tokens", "dsa.keys_selected",
                 "dsa.index_rows_read", "mla.cache_rows_read",
                 "mla.window_rows_read", "dsa.prefill_pairs_scored"):
        assert snap[name]["value"] == total[name], name
    for name in ("dsa.index_bytes_read", "mla.cache_bytes_read",
                 "mla.window_bytes_read"):
        assert snap[name]["value"] == gauges[name], name


TestEngine = families.engine_tests(
    CASE, slot_holds=slot_holds, states=states, counters=counters,
    greedy=greedy)


def test_engine_states_the_kernel_and_serves_the_same_tokens(monkeypatch):
    """The engine over ``WIDE`` (the full layers' heads 128 + 64 beside
    128, a selection of 512), a prime of 600 in the 1,024 bucket: on the CPU
    the admission is the masked blocks and says nothing under
    ``"mla_prefill"``; with the kernel forced (interpreter, tiles of 256) it
    says ``"pallas"``, the greedy tokens are the same, and
    ``dsa.prefill_pairs_attended`` is the six tiles a row of 600 visits a
    layer where the blocks count both whole segments.  Two engines by what
    it tests: each traces its admission under the lowering in force."""
    params, policy = make(WIDE)
    prime = np.random.default_rng(0).integers(1, WIDE.vocab_size, 600)

    def serve():
        eng = ServingEngine(WIDE, params, policy=policy,
                            num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                            max_len=1024 + 8)
        eng.submit(Request(uid=0, tokens=prime.tolist(), max_new_tokens=5,
                           temperature=0.0, seed=1,
                           logit_mask=families.never_zero(CASE)))
        (done,) = eng.run_until_idle(max_chunks=10)
        return list(done.tokens), eng.status(), eng.model_stats

    want, status, stats = serve()
    assert status["mla_prefill"] is None
    # one row a run, two full layers
    assert stats["dsa.prefill_pairs_attended"] == 2 * dsa.prefill_pairs(
        1024, WIDE_TOP_K)[1]
    force_prefill_kernel(monkeypatch)
    got, status, kernel_stats = serve()
    assert status["mla_prefill"] == "pallas"
    assert status["gqa_prefill"] == "xla"       # the sliding layers' blocks
    assert got == want
    assert kernel_stats["dsa.prefill_pairs_attended"] == 2 * 6 * 256 ** 2
    for name in ("dsa.prefill_pairs_scored", "dsa.prefill_pairs_selected"):
        assert kernel_stats[name] == stats[name] > 0
