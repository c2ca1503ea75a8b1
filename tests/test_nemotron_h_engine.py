"""Nemotron-H through ``ServingEngine``'s normal path (the seam of
``decode/family.py``; no line of ``decode/engine.py`` names the family): rows
of mixed lengths in one admission run — primes of 1, 2 and 3 tokens, shorter
than the convolution's taps, and one past a chunk of the scan among them —
serve the reference's argmax over the same tokens wherever its top-two gap
exceeds a float32 rounding; a slot readmitted after a longer request serves
what a fresh engine serves (a stale carry, tail or key fails it); a slot's
state holds a carry and a tail for each Mamba-2 layer beside the attention
layer's grown keys and NOTHING for an expert layer; nothing compiles after
``aot_warmup``; the modes that are ProGen's alone are refused by name; the
family's counters reach the registry and ``engine.status()``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_nemotron3 as ref
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.models import nemotron_h as nh
from progen_tpu.observe.metrics import get_registry
from tests.nemotron_h_tiny import TINY, as_dict, make, share

pytestmark = pytest.mark.serving

ADMIT_ROWS = 2
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
ENGINE = dict(num_slots=SLOTS, chunk_size=4, max_len=32)
NEW, TOP_K = 7, 6
PRIMES = (3, 12, 1, 21, 2, 9)       # three shorter than the four taps
STATE_LAYERS = TINY.layers_of(nh.MAMBA)
EXPERT_LAYERS = TINY.layers_of(nh.EXPERTS)
# the reference's best logit must lead its second best by this much for a
# greedy token to be held to it: float32 on both sides, where the order of
# the sums moves a logit by a few 1e-6 (tests/test_nemotron_h_model.py)
TOP_TWO_GAP = 1e-4


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
    """Primes of 1-21 tokens (the buckets of 8, 16 and 32), 7-9 new."""
    rng = np.random.default_rng(seed)
    return [Request(
        uid=first_uid + i, max_new_tokens=NEW + i % 3, seed=50 + i,
        temperature=0.8 if sampled else 0.0, top_k=TOP_K if sampled else None,
        logit_mask=_never_zero(),
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
        return ref.forward_row(params, row, as_dict(TINY),
                               logit_positions=at)[0]


def _padded(seq):
    return jnp.zeros((ENGINE["max_len"],), jnp.int32).at[:len(seq)].set(
        jnp.asarray(seq))


def _held_to_the_reference(params, r, tokens):
    """The served greedy tokens against the reference's argmax over the
    same sequence so far, wherever its top-two gap exceeds the tolerance;
    returns how many positions were held."""
    seq = list(r.tokens) + list(tokens)
    p = len(r.tokens)
    logits = np.asarray(_reference_logits(
        params, _padded(seq), p - 1 + jnp.arange(NEW + 2)))[:len(tokens), 1:]
    held = 0
    for at, tok in zip(logits, tokens):
        top = np.sort(at)[-2:]
        if top[1] - top[0] > TOP_TWO_GAP:
            assert tok == 1 + int(np.argmax(at)), (r.uid, held)
            held += 1
    return held


def test_greedy_requests_of_mixed_lengths_serve_the_references_argmax(
        served, engine):
    reqs = _requests(len(PRIMES))
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, reqs)}
    assert sum(len(r.tokens) < TINY.conv_kernel for r in reqs) == 3
    held = sum(_held_to_the_reference(served[0], r, got[r.uid])
               for r in reqs)
    total = sum(r.max_new_tokens for r in reqs)
    assert held >= 0.9 * total              # the gap rarely excuses a token


def test_a_slot_readmitted_after_a_longer_request_serves_as_a_fresh_engine(
        served, engine):
    """Long requests fill every slot and finish (every slot then steps on
    after them), then short ones — one, two, three tokens — are admitted
    into the same slots: an admission overwrites ALL of a slot's carry,
    tail and keys, so they serve what an engine that never held anything
    serves."""
    params, policy = served
    long = _requests(SLOTS + 3, seed=7, sampled=True, first_uid=400,
                     primes=(21, 17, 19))
    _serve(engine, long)
    caches = engine.state["caches"]
    assert all(bool(jnp.abs(c["ssm"]).max(axis=(1, 2, 3)).min() > 0)
               for c in caches.values() if "ssm" in c)
    short = _requests(SLOTS, seed=6, first_uid=500, primes=(1, 2, 3, 5))
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, short)}
    fresh_engine = ServingEngine(TINY, params, policy=policy, **ENGINE)
    fresh = {c.uid: c.tokens.tolist() for c in _serve(
        fresh_engine, _requests(SLOTS, seed=6, first_uid=500,
                                primes=(1, 2, 3, 5)))}
    assert got == fresh
    assert _held_to_the_reference(served[0], short[0], got[500]) > 0


def test_sampled_requests_keep_to_the_probe_rule(served, engine):
    """Every served token is among the reference's ``top_k`` best allowed
    at its position (to a float32 rounding)."""
    reqs = _requests(ADMIT_ROWS + 3, seed=4, sampled=True, first_uid=100)
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


def test_nothing_compiles_after_warmup_and_an_expert_layer_holds_no_state(
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
    # MEM*EME: nothing for layers 1, 4 and 6
    assert {n: sorted(c) for n, c in caches.items()} == {
        **{f"l{i}": ["conv", "ssm"] for i in (0, 2, 5)}, "l3": ["k", "v"]}
    assert caches["l0"]["ssm"].shape == (SLOTS, 8, 8, 8)
    assert caches["l0"]["ssm"].dtype == jnp.float32
    assert caches["l0"]["conv"].shape == (SLOTS, 3, 128)
    assert caches["l3"]["k"].shape == (SLOTS, 2, ENGINE["max_len"], 8)
    status = engine.status()
    assert status["row_write"] == "scatter"          # the CPU's lowering
    assert status["moe_experts"] == {"chunk": "xla", "admit": "xla"}
    assert engine.lowerings["ssd_step"] == "xla"
    assert "ssd_step" not in engine.program_lowerings["admit"]


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
    assert isinstance(family, nh.NemotronHFamily)
    assert family.name == "nemotron_h" and family.modes == frozenset()
    assert family.idle_length == 0 and not family.position_masks
    assert family.block_length is None          # a token a row a step
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    assert family.buckets(20, 32) == [8, 16, 32]
    assert set(family.init_stats()) == set(nh.STAT_KEYS)
    assert family.init_stats()["moe.held_load"].shape == (TINY.experts_held,)
    assert list(family.blocks) == ["l0", "l2", "l3", "l5"]


def test_counters_ride_the_flags_fetch_into_the_registry_and_status():
    """A chip that holds 4 of the 16 experts (8-11): the counters'
    arithmetic over one small run."""
    config = share(8)
    params, policy = make(config)
    eng = ServingEngine(config, params, policy=policy, **ENGINE)
    reqs = _requests(4, seed=5)
    _serve(eng, reqs)
    stats = eng.model_stats
    assert set(stats) == set(nh.STAT_KEYS)
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["ssm.step_rows"] == STATE_LAYERS * steps
    assert stats["ssm.prefill_tokens"] == STATE_LAYERS * prime_tokens
    assert stats["ssm.prefill_slots"] >= stats["ssm.prefill_tokens"]
    assert 0 < stats["ssm.decode_steps"] <= steps
    assert stats["moe.tokens"] == EXPERT_LAYERS * (prime_tokens + steps)
    # 5 of 16 a token, 4 of 16 held: 1.25 held assignments a token
    held = stats["moe.held_load"].sum()
    assert stats["moe.held_load"].shape == (4,)
    assert 0.6 < held / stats["moe.tokens"] < 2.0
    assert 0 < stats["moe.prefill_held"] < held
    assert 0 < stats["moe.experts_touched"] <= (stats["moe.decode_layers"]
                                                * config.experts_held)
    assert stats["moe.decode_layers"] == (EXPERT_LAYERS
                                          * stats["ssm.decode_steps"])
    assert stats["attn.decode_rows"] == steps
    # the i-th step of a request stands on position prime + i - 1
    context = sum(len(r.tokens) + i for r in reqs
                  for i in range(1, r.max_new_tokens))
    assert stats["attn.context_tokens"] == context
    snap = get_registry().snapshot()
    for name in nh.STAT_KEYS:
        if name != "moe.held_load":
            assert snap[name]["value"] == stats[name], name
    assert snap["moe.held_assignments"]["value"] == held
    model_stats = eng.status()["model_stats"]
    assert model_stats["ssm.step_rows"] == stats["ssm.step_rows"]
    assert model_stats["moe.held_assignments"] == held
