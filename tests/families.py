"""The driver families' tests as a table and one harness.

A family of ``decode/family.py:_DRIVER_FAMILIES`` is a ROW here
(:data:`CASES`): its module, its tiny configuration (``tests/*_tiny.py``),
its plain reference (``perf/lib/reference_*.py``), the engine's arguments and
the primes its engine file serves.  What nine files used to define alike is
here ONCE: the requests, ``serve``, the padded row, the reference's logits
and the family's own prefill and step as JITTED, CACHED programs (one
compiled program a family a padded shape, where an eager call dispatches
the model op by op and compiles a length anew), the plain sequential
sampler over them, one warmed engine a family a process
(:func:`engine_of`), and the tests every family's engine file runs
(:func:`engine_tests`).

What is a family's own is three small functions in ITS engine file, handed
to :func:`engine_tests`: what one slot's caches hold, what ``family_for``
states, what the counters read for a list of served requests (applied to how
far the shared engine's counters MOVED, so no second engine is built to read
absolute counts).  ``tests/test_families.py`` holds every registered driver
family to a row whose engine file runs these tests.

Adding a family's tests: a row here, ``tests/<family>_tiny.py``,
``tests/test_<family>_engine.py`` (``TestEngine = engine_tests(CASE, ...)``
and the family's own cases), ``tests/test_<family>_model.py`` over
:func:`jitted` / :func:`reference`, and a row of
``tests/test_chip_compile.py:WHOLE_PROGRAMS``.
"""

import dataclasses
import functools
import importlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.observe.metrics import get_registry

ADMIT_ROWS = 2
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
# every mode that is ProGen's alone, with an argument that would turn it on
MODES = {"paged": True, "disagg": True, "lora_bank": {},
         "quantize": "weights", "mesh": object()}
# the arguments of the model modules' functions that are not arrays
STATIC = ("config", "c", "policy", "max_len", "with_choices", "capacity",
          "limit")


@dataclasses.dataclass(frozen=True, eq=False)
class FamilyCase:
    """One driver family as its tests see it."""

    name: str                   # what ``family_for(...).name`` states
    module: str                 # under ``progen_tpu.models``
    family: str                 # the family's class in that module
    tiny: str                   # under ``tests``: ``TINY`` and ``make``
    ref: str                    # under ``perf.lib``
    primes: tuple               # request ``i`` has ``primes[i % len]`` tokens
    greedy: tuple               # requests of the greedy test, a case each
    new: tuple = (7, 8, 9)      # request ``i`` asks for ``new[i % len]``
    top_k: int = 6
    sampled: int = ADMIT_ROWS + 2       # requests of the sampled test
    after_warmup: int = SLOTS + 5       # requests served under the listener
    engine: dict = dataclasses.field(default_factory=lambda: dict(
        num_slots=SLOTS, chunk_size=4, max_len=32))
    buckets: tuple = (8, 16, 32)        # the admission programs of a warm-up
    refused: tuple = ("paged", "disagg", "lora_bank", "quantize", "mesh")
    # the counters test's requests, as :func:`requests` takes them
    counted: dict = dataclasses.field(
        default_factory=lambda: dict(n=3, seed=5))
    # further arguments of the reference's forward
    forward: dict = dataclasses.field(default_factory=dict)
    blocks: bool = False        # generates by blocks: ``top_k`` on every
    #                             request, fill steps recorded
    draw_below: int | None = None       # prime tokens are drawn under this

    @property
    def models(self):
        return importlib.import_module(f"progen_tpu.models.{self.module}")

    @property
    def reference(self):
        return importlib.import_module(f"perf.lib.{self.ref}")

    @property
    def tiny_module(self):
        return importlib.import_module(f"tests.{self.tiny}")

    @property
    def config(self):
        return self.tiny_module.TINY

    @property
    def max_len(self) -> int:
        return self.engine["max_len"]

    @property
    def engine_file(self) -> str:
        return f"test_{self.tiny.removesuffix('_tiny')}_engine"

    def served(self):
        """``(params, policy)`` of the tiny configuration, float32: one set
        of weights a process, read and never written."""
        return _served(self)


@functools.cache
def _served(case):
    return case.tiny_module.make()


_LADDER = (3, 8, 13, 6, 11, 4, 9, 14, 7, 12, 5, 10)    # 3 + 5 i mod 12

CASES = {case.name: case for case in (
    FamilyCase("longcat", "longcat", "LongCatFamily", "longcat_tiny",
               "reference_longcat", primes=_LADDER, new=(5, 6, 7),
               greedy=(1, ADMIT_ROWS, ADMIT_ROWS + 1)),
    FamilyCase("deepseek_v2", "deepseek_v2", "DeepSeekV2Family",
               "deepseek_v2_tiny", "reference_deepseek_v2", primes=_LADDER,
               new=(5, 6, 7), greedy=(1, ADMIT_ROWS + 1)),
    # two past the window of 8 at admission
    FamilyCase("trinity", "trinity", "TrinityFamily", "trinity_tiny",
               "reference_trinity", primes=(3, 12, 6, 21, 9), greedy=(1, 5)),
    # below, across, at, across two, past a chunk of the scan
    FamilyCase("granite_hybrid", "granite_hybrid", "GraniteHybridFamily",
               "granite_tiny", "reference_granite", primes=(3, 12, 8, 21, 9),
               greedy=(1, 5)),
    # every P mod 4; one shorter than a block
    FamilyCase("sdar", "sdar", "SDARFamily", "sdar_tiny", "reference_sdar",
               primes=(5, 6, 7, 8, 13, 3), greedy=(6,),
               new=(9, 10, 11, 12, 13), top_k=5, sampled=4, after_warmup=6,
               engine=dict(num_slots=SLOTS, chunk_size=6, max_len=48),
               buckets=(8, 16, 32, 48),
               refused=("paged", "disagg", "quantize", "lora_bank"),
               blocks=True, draw_below=95),
    # two shorter than the three taps
    FamilyCase("lfm2", "lfm2", "LFM2Family", "lfm2_tiny", "reference_lfm2",
               primes=(3, 12, 1, 21, 2, 9), greedy=(1, 6),
               sampled=ADMIT_ROWS + 3, counted=dict(n=4, seed=5)),
    # three shorter than the four taps
    FamilyCase("nemotron_h", "nemotron_h", "NemotronHFamily",
               "nemotron_h_tiny", "reference_nemotron3",
               primes=(3, 12, 1, 21, 2, 9), greedy=(6,),
               sampled=ADMIT_ROWS + 3, counted=dict(n=4, seed=5)),
    # the ring's three edges (window 4), one token, and one past a chunk
    FamilyCase("mimo_v2", "mimo_v2", "MiMoV2Family", "mimo_v2_tiny",
               "reference_mimo", primes=(1, 3, 4, 5, 13, 21), greedy=(6,),
               counted=dict(n=3, seed=5, primes=(3, 13, 6))),
    # the ring's edges (window 5), the selector's (top-k 8), one past a chunk
    FamilyCase("dots3", "dots3", "Dots3Family", "dots3_tiny",
               "reference_dots3", primes=(4, 5, 7, 8, 9, 21), greedy=(6,),
               counted=dict(n=3, seed=5, primes=(3, 13, 6)),
               forward=dict(q_block=8)),
    # two tokens, the selector's edges (top-k 8), one past a chunk
    FamilyCase("glm_dsa", "glm_dsa", "GLMDSAFamily", "glm_dsa_tiny",
               "reference_glm52", primes=(2, 5, 7, 8, 9, 21), greedy=(6,),
               counted=dict(n=3, seed=5, primes=(3, 13, 6)),
               forward=dict(q_block=8)),
    # three shorter than the four taps; 9, 12 and 21 cross chunks of 4
    FamilyCase("qwen3_next", "qwen3_next", "Qwen3NextFamily",
               "qwen3_next_tiny", "reference_qwen3next",
               primes=(3, 12, 1, 21, 2, 9), greedy=(6,),
               sampled=ADMIT_ROWS + 3, counted=dict(n=4, seed=5)),
    # three shorter than the four taps; 9, 12, 19 and 21 cross blocks of 4
    # and chunks of 8
    FamilyCase("bailing_hybrid", "bailing_hybrid", "BailingHybridFamily",
               "bailing_hybrid_tiny", "reference_ling3",
               primes=(3, 12, 1, 19, 2, 9), greedy=(6,),
               sampled=ADMIT_ROWS + 3, counted=dict(n=4, seed=5),
               forward=dict(q_block=8)),
)}


# -- compiled programs -------------------------------------------------------

@functools.cache
def jitted(fn):
    """``fn`` of a model module (``prefill``, ``decode_step``, ``block_step``,
    ``caches_from``, ...) as ONE compiled program a (configuration, policy,
    shape): what is not an array is static.  A test that forces a lowering
    by monkeypatch, or reads what a trace notes (``record_lowerings``),
    takes :func:`fresh` instead: ``jax.jit`` keeps a trace across a patch."""
    return fresh(fn)


def fresh(fn):
    """``fn`` jitted anew, traced again under whatever is patched now.  JAX
    keeps its traces by the FUNCTION, so a second ``jax.jit`` of ``fn``
    itself would find the first one's: the wrapper is a function of its
    own (with ``fn``'s signature, by which the static names are found)."""
    parameters = inspect.signature(fn).parameters
    open_ended = any(p.kind is p.VAR_KEYWORD for p in parameters.values())

    @functools.wraps(fn)
    def anew(*args, **kwargs):
        return fn(*args, **kwargs)

    return jax.jit(anew, static_argnames=tuple(
        n for n in STATIC if open_ended or n in parameters))


@functools.cache
def reference(ref, config, name="forward", **static):
    """``ref.<name>(*arrays, as_dict(config), **static, **more_arrays)`` as
    one compiled program a shape: ``forward(params, tokens, cfg)``,
    ``forward_row(params, row, cfg, logit_positions=...)``, a layer's
    ``moe(u, router, experts, cfg)``.  Call it where the eager call stood:
    under ``jax.default_matmul_precision`` it compiles at that precision."""
    cfg, fn = dataclasses.asdict(config), getattr(ref, name)
    return jax.jit(lambda *arrays, **more: fn(*arrays, cfg, **static, **more))


def reference_logits(case, config=None):
    """``(params, row (max_len,), at (K,)) -> (K, V)``: the reference over
    one row padded to the engine's ``max_len`` (causality keeps the padding
    out of what is read), one program for every length."""
    forward = reference(case.reference, config or case.config, "forward_row",
                        **case.forward)

    def logits(params, row, at):
        with jax.default_matmul_precision("highest"):
            out = forward(params, row, logit_positions=at)
        return out[0] if isinstance(out, tuple) else out

    return logits


@functools.cache
def _family(case):
    return family_for(case.config, case.served()[1])


def family_prefill(case):
    """The family's own ``prefill(params, rows, lengths)`` at the engine's
    ``max_len``, one compiled program a bucket."""
    return functools.partial(jitted(_family(case).prefill),
                             max_len=case.max_len)


def family_step(case):
    """The family's own ``decode_step(params, tok, pos, caches, live)``."""
    return jitted(_family(case).decode_step)


# -- requests and the plain sampler ------------------------------------------

def never_zero(case, *also_banned):
    mask = np.ones((case.config.vocab_size,), bool)
    mask[[0, *also_banned]] = False
    return mask


def requests(case, n, seed=0, sampled=False, first_uid=0, primes=None,
             mask=None):
    """``n`` requests over the case's primes and new-token counts, token 0
    banned (or ``mask``)."""
    rng = np.random.default_rng(seed)
    primes = primes or case.primes
    below = case.draw_below or case.config.vocab_size
    return [Request(
        uid=first_uid + i, max_new_tokens=case.new[i % len(case.new)],
        seed=50 + i, temperature=0.8 if sampled else 0.0,
        top_k=case.top_k if sampled or case.blocks else None,
        logit_mask=never_zero(case) if mask is None else mask,
        record_fill_steps=case.blocks,
        tokens=rng.integers(1, below, primes[i % len(primes)]).tolist())
        for i in range(n)]


def serve(engine, reqs) -> dict:
    """``uid -> Completion`` of ``reqs`` served to the end."""
    for r in reqs:
        engine.submit(r)
    return {c.uid: c for c in engine.run_until_idle(200)}


def tokens_of(done) -> dict:
    return {uid: c.tokens.tolist() for uid, c in done.items()}


def padded(case, seq):
    """``seq`` as a row of the engine's ``max_len`` (made on the host: a
    device scatter would compile anew at every length)."""
    row = np.zeros((case.max_len,), np.int32)
    row[:len(seq)] = seq
    return row


def sequential_greedy(case, r):
    """The plain sampler: the reference's full forward over everything so
    far, the best allowed token appended, again."""
    params, logits_of = case.served()[0], reference_logits(case)
    seq = list(r.tokens)
    for _ in range(r.max_new_tokens):
        logits = logits_of(params, padded(case, seq),
                           np.array([len(seq) - 1]))
        seq.append(1 + int(np.argmax(np.asarray(logits)[0, 1:])))
    return seq[len(r.tokens):]


def family_greedy(case, r):
    """The family's own prefill of the one row at its bucket, then its own
    steps, the best allowed token each."""
    params = case.served()[0]
    n = len(r.tokens)
    bucket = _family(case).bucket(n, case.max_len)
    row = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
        jnp.asarray(r.tokens))
    logits, caches, _ = family_prefill(case)(params, row, jnp.array([n]))
    out = []
    for i in range(r.max_new_tokens):
        out.append(1 + int(jnp.argmax(logits[0, 1:])))
        logits, caches, _ = family_step(case)(
            params, jnp.array([out[-1]]), jnp.array([n + i]), caches,
            jnp.array([True]))
    return out


def probe_logits(case, r, tokens):
    """The reference's allowed logits ``(len(tokens), V - 1)`` at the
    positions that drew ``tokens`` after ``r``'s prime."""
    seq = list(r.tokens) + list(tokens)
    logits = reference_logits(case)(
        case.served()[0], padded(case, seq),
        len(r.tokens) - 1 + np.arange(max(case.new)))
    return np.asarray(logits)[:len(tokens), 1:]


def serves_the_plain_samplers_tokens(case, reqs, done):
    assert tokens_of(done) == {r.uid: sequential_greedy(case, r)
                               for r in reqs}


def keeps_to_the_probe_rule(case, reqs, done):
    """Every served token is among the reference's ``top_k`` best allowed
    at its position (to a float32 rounding)."""
    for r in reqs:
        out = done[r.uid].tokens.tolist()
        at = probe_logits(case, r, out)
        tok = np.asarray(out) - 1
        kth = np.sort(at, axis=-1)[:, -case.top_k]
        assert (kth - at[np.arange(len(tok)), tok]).max() < 1e-4
        assert 0 not in out


# -- one engine a family -----------------------------------------------------

@functools.cache
def engine_of(case):
    """The family's ONE engine of a test process, warmed: every test of the
    family's engine file serves through it and reads how far its counters
    moved."""
    params, policy = case.served()
    engine = ServingEngine(case.config, params, policy=policy,
                           **case.engine)
    engine.warm = engine.aot_warmup()
    return engine


def moved(engine, before) -> dict:
    """How far each of the engine's counters moved since ``before`` (a copy
    of ``engine.model_stats``, which is ``{}`` until a fetch)."""
    return {k: np.asarray(v) - np.asarray(before.get(k, 0))
            for k, v in engine.model_stats.items()}


def compile_events():
    """A listener for JAX's compile events and the list it fills."""
    events = []

    def listener(name, secs, **kw):
        if name.startswith("/jax/core/compile"):
            events.append(name)

    return listener, events


def engine_tests(case, *, slot_holds, states, counters,
                 greedy=serves_the_plain_samplers_tokens,
                 sampled=keeps_to_the_probe_rule):
    """The tests every driver family's engine file runs, over the family's
    one engine.  The family's own: ``slot_holds(engine)`` — what the state
    holds for a slot, and which lowerings the programs took;
    ``states(family)`` — what ``family_for`` returns; ``counters(engine,
    reqs, moved, total)`` — what the counters moved by over ``reqs`` and
    what the gauges read of the totals; and, where the plain sampler or the
    probe rule is not the family's, ``greedy(case, reqs, done)`` /
    ``sampled(case, reqs, done)``."""

    class EngineTests:
        @pytest.mark.parametrize("n", case.greedy)
        def test_greedy_requests_serve_the_plain_samplers_tokens(self, n):
            reqs = requests(case, n)
            greedy(case, reqs, serve(engine_of(case), reqs))

        def test_sampled_requests_keep_to_the_probe_rule(self):
            reqs = requests(case, case.sampled, seed=4, sampled=True,
                            first_uid=100)
            sampled(case, reqs, serve(engine_of(case), reqs))

        def test_nothing_compiles_after_warmup_and_a_slot_holds_its_caches(
                self):
            engine = engine_of(case)
            assert sorted(k for k in engine._aot if k[0] == "admit") == [
                ("admit", b) for b in case.buckets]
            assert engine.warm["programs"] == len(case.buckets) + 1
            listener, events = compile_events()
            jax.monitoring.register_event_duration_secs_listener(listener)
            try:
                first = serve(engine, requests(case, case.after_warmup,
                                               seed=3, sampled=True))
            finally:
                jax.monitoring.unregister_event_duration_listener(listener)
            assert events == [] and len(first) == case.after_warmup
            assert engine.state["lmask"].shape == (
                SLOTS, case.config.vocab_size)
            slot_holds(engine)

        @pytest.mark.parametrize("mode", case.refused)
        def test_a_mode_outside_the_familys_is_refused_by_name(self, mode):
            params, policy = case.served()
            with pytest.raises(UnsupportedFamilyMode, match=mode):
                ServingEngine(case.config, params, policy=policy,
                              **case.engine, **{mode: MODES[mode]})

        def test_family_for_returns_the_family_and_what_it_states(self):
            policy = case.served()[1]
            family = family_for(case.config, policy)
            assert isinstance(family, getattr(case.models, case.family))
            assert family.name == case.name and family.modes == frozenset()
            assert family.idle_length == 0 and not family.position_masks
            assert family.buckets(20, case.max_len) == [8, 16, 32]
            with pytest.raises(TypeError, match="no model family"):
                family_for(object(), policy)
            states(family)

        def test_counters_ride_the_flags_fetch_into_the_registry(self):
            """The family's device counters are read by the harvest's own
            fetch and published as gauges; the stage histograms are observed
            as for ProGen."""
            engine, registry = engine_of(case), get_registry()
            reqs = requests(case, first_uid=400, **case.counted)
            observed = {k: registry.histogram(k).count for k in (
                "engine.decode_chunk_s", "engine.prefill_s",
                "engine.admit_rows")}
            before = dict(engine.model_stats)
            serve(engine, reqs)
            counters(engine, reqs, moved(engine, before), engine.model_stats)
            for name, count in observed.items():
                assert registry.histogram(name).count > count

    EngineTests.case = case
    return EngineTests
