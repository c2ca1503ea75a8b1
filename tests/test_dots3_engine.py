"""dots3 through ``ServingEngine``'s normal path (the seam of
``decode/family.py``, unchanged): slots of mixed lengths — under, at and
past the window and ``index_topk`` at admission, all past both before they
finish — serve the tokens of a plain sequential sampler over the reference's
full forward; a slot's state holds latent rows and indexer rows for each
full block and a ring of another latent shape for each sliding one; a slot
readmitted after a longer request (stale latent rows, stale indexer rows, a
stale ring) serves what a fresh one serves; nothing compiles after
``aot_warmup``; the modes that are ProGen's alone are refused by name; the
family's counters and byte gauges reach the registry and
``status()["model_stats"]``; and with the full layers' heads at the published
widths and the kernel forced, the admission says ``"mla_prefill": "pallas"``,
serves the same tokens and counts the tiles it visited."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_dots3 as ref
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.models.dots3 import Dots3Family
from progen_tpu.observe.metrics import get_registry
from progen_tpu.ops import dsa
from tests.dots3_tiny import (TINY, TOP_K, WIDE, WIDE_TOP_K, WINDOW, as_dict,
                              force_prefill_kernel, make)

pytestmark = pytest.mark.serving

ADMIT_ROWS = 2
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
ENGINE = dict(num_slots=SLOTS, chunk_size=4, max_len=32)
NEW, SAMPLE_K = 7, 6
# the ring's edges (window 5), the selector's (top-k 8), and one past a chunk
PRIMES = (WINDOW - 1, WINDOW, TOP_K - 1, TOP_K, TOP_K + 1, 21)


@pytest.fixture(scope="module")
def served():
    return make()


@pytest.fixture(scope="module")
def engine(served):
    params, policy = served
    eng = ServingEngine(TINY, params, policy=policy, **ENGINE)
    eng.warm = eng.aot_warmup()
    return eng


def _never_zero():
    mask = np.ones((TINY.vocab_size,), bool)
    mask[0] = False
    return mask


def _requests(n, seed=0, sampled=False, first_uid=0, primes=PRIMES):
    """Primes of 4-21 tokens (the buckets of 8, 16 and 32), 7-9 new: every
    request ends past the window and past ``index_topk``."""
    rng = np.random.default_rng(seed)
    return [Request(
        uid=first_uid + i, max_new_tokens=NEW + i % 3, seed=50 + i,
        temperature=0.8 if sampled else 0.0,
        top_k=SAMPLE_K if sampled else None, logit_mask=_never_zero(),
        tokens=rng.integers(1, TINY.vocab_size,
                            primes[i % len(primes)]).tolist())
        for i in range(n)]


def _serve(engine, reqs):
    for r in reqs:
        engine.submit(r)
    return engine.run_until_idle(200)


@jax.jit
def _reference_logits(params, row, at):
    """The reference over one row padded to the engine's ``max_len``
    (causality keeps the padding out of what is read): one program."""
    with jax.default_matmul_precision("highest"):
        return ref.forward_row(params, row, as_dict(TINY), q_block=8,
                               logit_positions=at)[0]


def _padded(seq):
    return jnp.zeros((ENGINE["max_len"],), jnp.int32).at[:len(seq)].set(
        jnp.asarray(seq))


def _sequential_greedy(params, r):
    """The plain sampler: the reference's full forward over everything so
    far, the best allowed token appended, again."""
    seq = list(r.tokens)
    for _ in range(r.max_new_tokens):
        logits = _reference_logits(params, _padded(seq),
                                   jnp.array([len(seq) - 1]))
        seq.append(1 + int(jnp.argmax(logits[0, 1:])))
    return seq[len(r.tokens):]


def test_greedy_requests_across_both_edges_serve_the_plain_samplers_tokens(
        served, engine):
    reqs = _requests(len(PRIMES))
    assert all(len(r.tokens) + r.max_new_tokens > TOP_K for r in reqs)
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, reqs)}
    assert got == {r.uid: _sequential_greedy(served[0], r) for r in reqs}


def test_a_slot_readmitted_after_a_longer_request_serves_what_a_fresh_one_does(
        served, engine):
    """Every slot holds a 21-token request's latent rows, indexer rows and
    rings, then takes a prime of 2-9 tokens: stale rows past the short
    request's count, in all three, must reach nothing — the indexer must
    not select one, the ring's core must not read one."""
    long = _requests(SLOTS, seed=7, first_uid=200, primes=(21,))
    assert len(_serve(engine, long)) == SLOTS
    short = _requests(SLOTS, seed=8, first_uid=300,
                      primes=(2, WINDOW - 1, WINDOW + 1, TOP_K, TOP_K + 1))
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, short)}
    assert got == {r.uid: _sequential_greedy(served[0], r) for r in short}


def test_sampled_requests_keep_to_the_probe_rule(served, engine):
    """Every served token is among the reference's ``top_k`` best allowed
    at its position (to a float32 rounding)."""
    reqs = _requests(ADMIT_ROWS + 2, seed=4, sampled=True, first_uid=100)
    out = {c.uid: c.tokens.tolist() for c in _serve(engine, reqs)}
    for r in reqs:
        seq = list(r.tokens) + out[r.uid]
        p = len(r.tokens)
        new = len(out[r.uid])
        logits = _reference_logits(served[0], _padded(seq),
                                   p - 1 + jnp.arange(NEW + 2))
        at = np.asarray(logits)[:new, 1:]
        tok = np.asarray(out[r.uid]) - 1
        kth = np.sort(at, axis=-1)[:, -SAMPLE_K]
        assert (kth - at[np.arange(len(tok)), tok]).max() < 1e-4
        assert 0 not in out[r.uid]


def test_nothing_compiles_after_warmup_and_a_slot_holds_all_three_caches(
        engine):
    assert sorted(k for k in engine._aot if k[0] == "admit") == [
        ("admit", 8), ("admit", 16), ("admit", 32)]
    assert engine.warm["programs"] == 4
    events = []

    def listener(name, secs, **kw):
        if name.startswith("/jax/core/compile"):
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        first = {c.uid: c.tokens.tolist() for c in _serve(
            engine, _requests(SLOTS + 5, seed=3, sampled=True))}
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert events == [] and len(first) == SLOTS + 5
    rows = ENGINE["max_len"]
    assert jax.tree.map(lambda a: a.shape[1:], engine.state["caches"]) == {
        "l0": {"latent": (rows, 20), "index": (rows, 8)},
        "l1": {"latent": (rows, 20), "index": (rows, 8)},
        "l2": (WINDOW, 28), "l3": (WINDOW, 28)}
    status = engine.status()
    assert status["row_write"] == "scatter"     # the CPU's lowering
    assert status["mla_decode"] == status["gqa_prefill"] == "xla"
    assert "mla_prefill" not in status or status["mla_prefill"] is None


@pytest.mark.parametrize("mode", [
    dict(paged=True), dict(disagg=True),
    dict(lora_bank={}), dict(quantize="weights"), dict(mesh=object())],
    ids=lambda m: next(iter(m)))
def test_a_mode_outside_the_familys_is_refused_by_name(served, mode):
    params, policy = served
    with pytest.raises(UnsupportedFamilyMode, match=next(iter(mode))):
        ServingEngine(TINY, params, policy=policy, **ENGINE, **mode)


def test_family_for_returns_the_family_and_what_it_states(served):
    family = family_for(TINY, served[1])
    assert isinstance(family, Dots3Family)
    assert family.name == "dots3" and family.modes == frozenset()
    assert family.idle_length == 0 and not family.position_masks
    assert family.block_length is None
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    assert family.buckets(20, 32) == [8, 16, 32]


def test_counters_and_byte_gauges_reach_the_registry_and_the_status(served,
                                                                    engine):
    before = dict(engine.model_stats)
    reqs = _requests(3, seed=5, first_uid=400, primes=(3, 13, 6))
    _serve(engine, reqs)
    stats = {k: v - before[k] for k, v in engine.model_stats.items()}
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
    chunk_steps = stats["dsa.index_rows_read"] / (SLOTS * ENGINE["max_len"])
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
    total = engine.model_stats
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


def test_engine_states_the_kernel_and_serves_the_same_tokens(monkeypatch):
    """The engine over ``WIDE`` (the full layers' heads 128 + 64 beside
    128, a selection of 512), a prime of 600 in the 1,024 bucket: on the CPU
    the admission is the masked blocks and says nothing under
    ``"mla_prefill"``; with the kernel forced (interpreter, tiles of 256) it
    says ``"pallas"``, the greedy tokens are the same, and
    ``dsa.prefill_pairs_attended`` is the six tiles a row of 600 visits a
    layer where the blocks count both whole segments."""
    params, policy = make(WIDE)
    prime = np.random.default_rng(0).integers(1, WIDE.vocab_size, 600)

    def serve():
        eng = ServingEngine(WIDE, params, policy=policy,
                            num_slots=SLOTS_PER_ADMIT_ROW, chunk_size=4,
                            max_len=1024 + 8)
        eng.submit(Request(uid=0, tokens=prime.tolist(), max_new_tokens=5,
                           temperature=0.0, seed=1,
                           logit_mask=_never_zero()))
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
