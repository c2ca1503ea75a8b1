"""DeepSeek-V2 through ``ServingEngine``'s normal path (the seam of
``decode/family.py``, unchanged): greedy requests serve the tokens of a
plain sequential sampler over the reference's full forward; sampled ones
keep to the probe rule; nothing compiles after ``aot_warmup``; the modes
that are ProGen's alone are refused by name; the family's counters reach
the registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference_deepseek_v2 as ref
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.models.deepseek_v2 import DeepSeekV2Family
from progen_tpu.observe.metrics import get_registry
from tests.deepseek_v2_tiny import TINY, as_dict, make

pytestmark = pytest.mark.serving

ADMIT_ROWS = 2
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
ENGINE = dict(num_slots=SLOTS, chunk_size=4, max_len=32)
NEW, TOP_K = 5, 6


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
    """Primes of 3-14 tokens: the buckets of 8 and 16."""
    rng = np.random.default_rng(seed)
    return [Request(
        uid=first_uid + i, max_new_tokens=NEW + i % 3, seed=50 + i,
        temperature=0.8 if sampled else 0.0, top_k=TOP_K if sampled else None,
        logit_mask=_never_zero(),
        tokens=rng.integers(1, TINY.vocab_size, 3 + (5 * i) % 12).tolist())
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


def _sequential_greedy(params, r):
    """The plain sampler: the reference's full forward over everything so
    far, the best allowed token appended, again."""
    seq = list(r.tokens)
    for _ in range(r.max_new_tokens):
        logits = _reference_logits(params, _padded(seq),
                                   jnp.array([len(seq) - 1]))
        seq.append(1 + int(jnp.argmax(logits[0, 1:])))
    return seq[len(r.tokens):]


@pytest.mark.parametrize("n", [1, ADMIT_ROWS + 1])
def test_greedy_requests_serve_the_plain_samplers_tokens(served, engine, n):
    reqs = _requests(n)
    got = {c.uid: c.tokens.tolist() for c in _serve(engine, reqs)}
    assert got == {r.uid: _sequential_greedy(served[0], r) for r in reqs}


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


def test_nothing_compiles_after_warmup(engine):
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
    assert sorted(engine.state["caches"]) == ["l0", "l1", "l2"]


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
    assert isinstance(family, DeepSeekV2Family)
    assert family.name == "deepseek_v2" and family.modes == frozenset()
    assert family.idle_length == 0 and not family.position_masks
    assert family.vocab == TINY.vocab_size
    assert family.seq_len == TINY.max_position_embeddings
    assert family.buckets(20, 32) == [8, 16, 32]


def test_counters_ride_the_flags_fetch_into_the_registry(served):
    params, policy = served
    eng = ServingEngine(TINY, params, policy=policy, **ENGINE)
    reqs = _requests(3, seed=5)
    _serve(eng, reqs)
    stats = eng.model_stats
    expert_layers = TINY.num_hidden_layers - TINY.first_k_dense_replace
    prime_tokens = sum(len(r.tokens) for r in reqs)
    steps = sum(r.max_new_tokens - 1 for r in reqs)   # the first is prefill's
    assert stats["moe.tokens"] == expert_layers * (prime_tokens + steps)
    assert stats["mla.decode_rows"] == steps
    assert stats["moe.held_load"].shape == (TINY.experts_held,)
    assert stats["moe.held_load"].sum() == 3 * stats["moe.tokens"]
    assert stats["moe.held_groups_chosen"] == (TINY.topk_group
                                               * stats["moe.tokens"])
    assert stats["mla.context_tokens"] > stats["mla.decode_rows"]
    assert 0 < stats["moe.experts_touched"] <= (stats["moe.decode_layers"]
                                                * TINY.experts_held)
    snap = get_registry().snapshot()
    for name in ("moe.tokens", "moe.held_groups_chosen", "moe.decode_layers",
                 "moe.experts_touched", "mla.decode_rows",
                 "mla.context_tokens"):
        assert snap[name]["value"] == stats[name], name
    assert snap["moe.held_assignments"]["value"] == stats[
        "moe.held_load"].sum()
    assert snap["moe.held_load_max"]["value"] == stats["moe.held_load"].max()
