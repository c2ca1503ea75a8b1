"""Granite 4.0-H through ``ServingEngine``'s normal path (the seam of
``decode/family.py``; ``decode/engine.py`` unchanged): rows of mixed lengths
in one admission run — below, at and across a chunk of the scan — serve the
tokens of a plain sequential sampler over the reference's full forward and
of the family's own prefill and steps; a slot reused after idling serves as
a fresh one does; a slot's state holds a carry and a tail for each state
layer beside the attention layer's grown keys; nothing compiles after
``aot_warmup``; the modes that are ProGen's alone are refused by name; the
family's counters reach the registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_granite as ref
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.models import granite_hybrid as gh
from progen_tpu.observe.metrics import get_registry
from tests.granite_tiny import CHUNK, TINY, as_dict, make

pytestmark = pytest.mark.serving

ADMIT_ROWS = 2
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
ENGINE = dict(num_slots=SLOTS, chunk_size=4, max_len=32)
NEW, TOP_K = 7, 6
PRIMES = (3, 12, 8, 21, 9)      # below, across, at, across two, past a chunk
STATE_LAYERS = gh.mamba_layers(TINY)


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


def _requests(n, seed=0, sampled=False, first_uid=0):
    """Primes of 3-21 tokens (the buckets of 8, 16 and 32), 7-9 new."""
    rng = np.random.default_rng(seed)
    return [Request(
        uid=first_uid + i, max_new_tokens=NEW + i % 3, seed=50 + i,
        temperature=0.8 if sampled else 0.0, top_k=TOP_K if sampled else None,
        logit_mask=_never_zero(),
        tokens=rng.integers(1, TINY.vocab_size,
                            PRIMES[i % len(PRIMES)]).tolist())
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
        return ref.forward_row(params, row, as_dict(TINY), logit_positions=at)


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


def _family_greedy(served, r):
    """The family's own prefill of the one row at its bucket, then its own
    steps, the best allowed token each."""
    params, policy = served
    family = family_for(TINY, policy)
    n = len(r.tokens)
    bucket = family.bucket(n, ENGINE["max_len"])
    row = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
        jnp.asarray(r.tokens))
    logits, caches, _ = family.prefill(params, row, jnp.array([n]),
                                       ENGINE["max_len"])
    out = []
    for i in range(r.max_new_tokens):
        out.append(1 + int(jnp.argmax(logits[0, 1:])))
        logits, caches, _ = family.decode_step(
            params, jnp.array([out[-1]]), jnp.array([n + i]), caches,
            jnp.array([True]))
    return out


@pytest.mark.parametrize("n", [1, len(PRIMES)])
def test_greedy_requests_of_mixed_lengths_serve_the_plain_samplers_tokens(
        served, engine, n):
    reqs = _requests(n)
    assert {len(r.tokens) % CHUNK == 0 for r in reqs} == (
        {False} if n == 1 else {False, True})
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, reqs)}
    assert got == {r.uid: _sequential_greedy(served[0], r) for r in reqs}


def test_greedy_tokens_are_the_familys_own_step_by_step(served, engine):
    reqs = _requests(3, seed=8, first_uid=300)
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, reqs)}
    assert got == {r.uid: _family_greedy(served, r) for r in reqs}


def test_a_slot_reused_after_idling_serves_as_a_fresh_one(served, engine):
    """One request alone (every other slot steps on garbage beside it),
    then requests that fill every slot, then the first again: an admission
    overwrites all of a slot's state, so the tokens are the same."""
    alone = _requests(1, seed=6, first_uid=400)
    first = _serve(engine, alone)[0].tokens.tolist()
    _serve(engine, _requests(SLOTS + 3, seed=7, sampled=True, first_uid=410))
    again = _requests(1, seed=6, first_uid=500)
    # every slot has held a request and stepped on after it finished
    assert all(bool(jnp.abs(c["ssm"]).max(axis=(1, 2, 3)).min() > 0)
               for c in engine.state["caches"].values() if "ssm" in c)
    assert _serve(engine, again)[0].tokens.tolist() == first
    assert first == _sequential_greedy(served[0], alone[0])


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
        kth = np.sort(at, axis=-1)[:, -TOP_K]
        assert (kth - at[np.arange(len(tok)), tok]).max() < 1e-4
        assert 0 not in out[r.uid]


def test_nothing_compiles_after_warmup_and_a_slot_holds_state_and_keys(
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
    assert engine.state["lmask"].shape == (SLOTS, TINY.vocab_size)
    caches = engine.state["caches"]
    assert sorted(caches) == [f"l{i}" for i in range(6)]
    assert {n: sorted(c) for n, c in caches.items()} == {
        **{f"l{i}": ["conv", "ssm"] for i in (0, 1, 3, 4, 5)},
        "l2": ["k", "v"]}
    assert caches["l0"]["ssm"].shape == (SLOTS, 4, 32, 16)
    assert caches["l0"]["ssm"].dtype == jnp.float32
    assert caches["l2"]["k"].shape == (SLOTS, 2, ENGINE["max_len"], 16)
    # which lowering each op took, as the engine's programs were traced
    assert engine.lowerings["ssd_prefill"] == "xla"
    assert engine.lowerings["ssd_step"] == "xla"
    assert engine.program_lowerings["chunk"]["ssd_step"] == "xla"
    assert "ssd_step" not in engine.program_lowerings["admit"]
    assert engine.status()["row_write"] == "scatter"     # the CPU's lowering
    assert engine.status()["moe_experts"] is None


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
    assert isinstance(family, gh.GraniteHybridFamily)
    assert family.name == "granite_hybrid" and family.modes == frozenset()
    assert family.idle_length == 0 and not family.position_masks
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    assert family.buckets(20, 32) == [8, 16, 32]
    # no experts: the counters are scalars and none is ``moe.*``
    assert set(family.init_stats()) == set(gh.STAT_KEYS)
    assert all(v.shape == () for v in family.init_stats().values())
    assert not hasattr(TINY, "experts_held")


def test_counters_ride_the_flags_fetch_into_the_registry(served):
    params, policy = served
    eng = ServingEngine(TINY, params, policy=policy, **ENGINE)
    reqs = _requests(3, seed=5)
    _serve(eng, reqs)
    stats = eng.model_stats
    assert set(stats) == set(gh.STAT_KEYS)
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["ssm.prefill_tokens"] == STATE_LAYERS * prime_tokens
    assert stats["ssm.step_rows"] == STATE_LAYERS * steps
    assert stats["attn.decode_rows"] == steps
    # primes of 3, 12 and 8 in runs of ADMIT_ROWS rows: one run at the
    # bucket of 16 (two chunks a row) and one at the bucket of 8
    assert stats["ssm.prefill_slots"] == STATE_LAYERS * ADMIT_ROWS * (16 + 8)
    # the i-th step of a request stands on position prime + i - 1
    context = sum(len(r.tokens) + i for r in reqs
                  for i in range(1, r.max_new_tokens))
    assert stats["attn.context_tokens"] == context
    # the XLA core reads every slot's every row, each decode step that ran
    chunk_steps = stats["attn.full_rows_read"] / (SLOTS * ENGINE["max_len"])
    assert chunk_steps == int(chunk_steps) and chunk_steps >= max(
        r.max_new_tokens - 1 for r in reqs)
    snap = get_registry().snapshot()
    for name in gh.STAT_KEYS:
        assert snap[name]["value"] == stats[name], name
    assert "moe.held_assignments" not in eng.family.publish(stats)
