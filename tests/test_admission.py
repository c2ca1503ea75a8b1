"""Admission prefills only the rows it admits, whatever the cache layout.

One admission program per prefill bucket takes ``R = engine.admit_rows``
rows (``num_slots // SLOTS_PER_ADMIT_ROW``, at least one), prefills them
and gathers the R-row handle into the slots the host chose; a group of n
newcomers is ⌈n / R⌉ runs of it inside one ``step()``.  These tests hold
the engine to: the same tokens whatever the group size; no executable built
after ``aot_warmup``; R-row inputs (no all-slots host mask); fault handling
per run; one ``engine.admit_rows`` observation per run and one
``engine.prefill_s`` per admitting step; no host fetch between runs, and
one run in flight at a time.  The ``paged`` cases run the same program
with the gate rows in a page pool (the merge scatters them through an
R-row write table) and are held to the fixed-slot engine's tokens.
"""

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode import engine as engine_mod
from progen_tpu.decode.engine import FAILED_FAULT, SLOTS_PER_ADMIT_ROW
from progen_tpu.decode.prefill import prime_buckets
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.parallel import unbox
from progen_tpu.resilience import RetryError, faults
from progen_tpu.workloads import random_lora_bank

pytestmark = pytest.mark.serving

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=3, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)
ADMIT_ROWS = 3      # so that 1, R - 1, R, R + 1 and SLOTS all differ
SLOTS = ADMIT_ROWS * SLOTS_PER_ADMIT_ROW
ENGINE = dict(num_slots=SLOTS, chunk_size=4, max_len=24)
GROUPS = sorted({1, ADMIT_ROWS - 1, ADMIT_ROWS, ADMIT_ROWS + 1, SLOTS})
# the cache layouts, by the engine keywords that choose them
LAYOUTS = {"slots": {}, "paged": dict(paged=True, page_size=4)}
PAGES_PER_ROW = -(-ENGINE["max_len"] // LAYOUTS["paged"]["page_size"])


@pytest.fixture(scope="module")
def served():
    policy = make_policy(False)
    model = ProGen(config=CFG, policy=policy)
    params = unbox(model.init(jax.random.key(7),
                              jnp.zeros((2, CFG.seq_len), jnp.int32)))
    return params, policy


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.configure("")  # never leak a plan into the next test


def _requests(n, *, sampled=False, masked=False, max_new=6, seed=0,
              stagger=False):
    """``n`` requests with primes of 2-7 tokens (two prefill buckets).
    ``masked`` bars end of sequence and odd tokens from every generated
    position; ``stagger`` gives each its own length, so that slots finish
    one at a time."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        new = max_new + (i % 4 if stagger else 0)
        mask = None
        if masked:
            mask = np.ones((new, CFG.num_tokens), bool)
            mask[:, 0] = False
            mask[:, 1::2] = False
        out.append(Request(
            uid=i, max_new_tokens=new, seed=100 + i,
            temperature=0.9 if sampled else 0.0,
            top_k=8 if sampled else None,
            tokens=rng.integers(1, CFG.num_tokens,
                                int(rng.integers(2, 8))).tolist(),
            logit_mask=mask, tenant=0))
    return out


def _engine(served, **kw):
    params, policy = served
    return ServingEngine(CFG, params, policy=policy, **{**ENGINE, **kw})


def _tokens(comps):
    return {c.uid: (c.tokens.tolist(), c.status) for c in comps}


def _one_at_a_time(eng, reqs):
    """``reqs`` through a one-slot engine, which admits groups of one, each
    in its own prefill bucket."""
    assert eng.num_slots == 1 and not eng.has_work
    for r in reqs:
        eng.submit(r)
    return _tokens(eng.run_until_idle(max_chunks=100 * len(reqs)))


def _alone(served, reqs, **kw):
    """Every request served by itself."""
    return _one_at_a_time(_engine(served, **{**kw, "num_slots": 1}), reqs)


def _runs(eng):
    h = eng._admit_rows_hist
    return h.count, h.sum


@pytest.fixture(scope="module")
def idle_engine(served):
    """``layout -> `` ONE engine a layout for the tests that read how far its
    counters moved: handed over idle — nothing queued, no slot taken and,
    for ``paged``, the pool whole — or the case before it left something."""
    engines = {}

    def engine_of(layout):
        if layout not in engines:
            engines[layout] = _engine(served, **LAYOUTS[layout])
        eng = engines[layout]
        assert not eng._queue and not eng._inflight and not eng.num_active
        if layout == "paged":
            assert eng._pool.free_pages + eng._pool.cached_pages == \
                eng._pool.capacity
        return eng

    return engine_of


# ------------------------------------------- (a) the same tokens, any group


@pytest.fixture(scope="module")
def alone(served):
    """Reference completions, one request at a time, per sampling mode
    (one one-slot engine for the four: the modes are the requests')."""
    eng = _engine(served, num_slots=1)
    return {(sampled, masked): _one_at_a_time(
        eng, _requests(SLOTS, sampled=sampled, masked=masked))
        for sampled in (False, True) for masked in (False, True)}


@pytest.mark.parametrize("n,sampled,masked,layout", [
    pytest.param(n, sampled, masked, layout, id="-".join((
        str(n), "sampled" if sampled else "greedy",
        "masked" if masked else "free", layout)))
    for layout in LAYOUTS for n in GROUPS
    for sampled in (False, True) for masked in (False, True)
    if layout == "slots" or sampled == masked])
def test_group_of_n_serves_the_tokens_of_requests_served_alone(
        idle_engine, alone, n, sampled, masked, layout):
    eng = idle_engine(layout)
    runs0, rows0 = _runs(eng)
    for r in _requests(n, sampled=sampled, masked=masked):
        eng.submit(r)
    first = eng.step()      # the whole group is admitted in this step
    assert eng.num_active + len(first) == n
    runs, rows = _runs(eng)
    assert runs - runs0 == math.ceil(n / ADMIT_ROWS)
    assert rows - rows0 == n
    got = _tokens(first + eng.run_until_idle(max_chunks=100))
    want = alone[(sampled, masked)]
    assert got == {u: want[u] for u in range(n)}
    if masked:
        assert all(t % 2 == 0 and t for toks, _ in got.values()
                   for t in toks)
    if layout == "paged":
        assert eng._pool.free_pages + eng._pool.cached_pages == \
            eng._pool.capacity


@pytest.mark.parametrize("n", [ADMIT_ROWS + 1, SLOTS])
def test_group_under_lora_keeps_each_rows_tenant(served, n):
    """The tenant rides the R-row handle: row k of a run carries request
    k's adapter into whichever slot it lands in."""
    bank = random_lora_bank(CFG, num_tenants=4, rank=2, seed=3, scale=0.5)
    reqs = _requests(n, sampled=True)
    for i, r in enumerate(reqs):
        r.tenant = i % 4
    want = _alone(served, reqs, lora_bank=bank)
    eng = _engine(served, lora_bank=bank)
    for r in reqs:
        eng.submit(r)
    got = _tokens(eng.run_until_idle(max_chunks=100))
    assert got == want
    base = _alone(served, [dataclasses.replace(r, tenant=0)
                           for r in reqs if r.tenant], lora_bank=bank)
    assert any(got[u] != base[u] for u in base)  # the adapters did act


def test_slots_are_taken_in_queue_order(served):
    eng = _engine(served)
    for r in _requests(SLOTS):
        eng.submit(r)
    eng._admit_pending()
    assert {s: r.uid for s, r in eng._inflight.items()} == {
        i: i for i in range(SLOTS)}
    assert [eng._admit_order[s] for s in range(SLOTS)] == list(range(SLOTS))


# ------------------------------------------ (b) nothing compiles once warm


@pytest.mark.parametrize("layout", LAYOUTS)
def test_no_compilation_after_aot_warmup(served, layout):
    """Groups of every size, slots finishing one and several at a time,
    chunks in between: every executable ``step()`` dispatches was built by
    ``aot_warmup``."""
    eng = _engine(served, **LAYOUTS[layout])
    info = eng.aot_warmup()
    buckets = prime_buckets(CFG.window_size, CFG.seq_len, eng.max_len - 1)
    admits = [k for k in eng._aot if k[0] == "admit"]
    assert sorted(admits) == [("admit", p) for p in buckets]
    assert info["programs"] == len(buckets) + 1
    assert ("chunk",) in eng._aot and ("release",) in eng._aot

    events = []

    def listener(name, secs, **kw):
        if name.startswith("/jax/core/compile"):
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        served_n = 0
        for n in range(1, SLOTS + 1):
            # odd groups finish one slot at a time, even ones together
            for r in _requests(n, sampled=True, masked=n % 3 == 0,
                               stagger=n % 2 == 1, seed=n):
                eng.submit(r)
            served_n += len(eng.run_until_idle(max_chunks=100))
        # a group larger than the free slots, admitted as slots free up
        for r in _requests(2 * SLOTS, stagger=True, seed=9):
            eng.submit(r)
        served_n += len(eng.run_until_idle(max_chunks=200))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert served_n == sum(range(1, SLOTS + 1)) + 2 * SLOTS
    assert events == []


def test_aot_state_round_trips_between_the_programs(served):
    """The state each program returns is what ``_init_state`` made, leaf
    for leaf: shape, dtype and weak type — or the next AOT executable would
    refuse it (and a jitted one retrace)."""
    eng = _engine(served)
    eng.aot_warmup()
    want = jax.tree.map(lambda a: (a.shape, a.dtype, a.weak_type),
                        eng._init_state())
    for r in _requests(ADMIT_ROWS + 1, stagger=True):
        eng.submit(r)
    for _ in range(4):
        eng.step()
        assert jax.tree.map(lambda a: (a.shape, a.dtype, a.weak_type),
                            eng.state) == want


# ---------------------------------------------- (c) R-row inputs, no S-row mask


@pytest.mark.parametrize("layout", LAYOUTS)
def test_admission_inputs_have_r_rows(served, monkeypatch, layout):
    eng = _engine(served, **LAYOUTS[layout])
    seen, masks = [], []
    real_call, real_mask = eng._admit_call, eng._build_lmask

    def call(p_pad, *args):
        seen.append((p_pad, [np.shape(a) for a in args]))
        return real_call(p_pad, *args)

    def build(n_rows, rows):
        out = real_mask(n_rows, rows)
        masks.append(out.shape)
        return out

    monkeypatch.setattr(eng, "_admit_call", call)
    monkeypatch.setattr(eng, "_build_lmask", build)
    for r in _requests(SLOTS, masked=True):
        eng.submit(r)
    eng.run_until_idle(max_chunks=100)
    R, L, V = ADMIT_ROWS, eng.max_len, CFG.num_tokens
    assert eng.admit_rows == R
    assert len(seen) == math.ceil(SLOTS / R)
    for p_pad, shapes in seen:
        if layout == "paged":       # the write table: R rows, like the rest
            assert shapes.pop() == (R, PAGES_PER_ROW)
        src, mask, tokens, *per_row, lmask = shapes
        assert src == mask == (SLOTS,)
        assert tokens == (R, p_pad)
        assert per_row == [(R,)] * 5
        assert lmask == (R, L, V)
    assert masks == [(R, L, V)] * len(seen)


@pytest.mark.parametrize("slots,rows", [(2, 1), (16, 1), (31, 1), (32, 2),
                                        (64, 4)])
def test_rows_scale_with_the_slots(served, slots, rows):
    eng = _engine(served, num_slots=slots)
    assert eng.admit_rows == rows
    for r in _requests(3):
        eng.submit(r)
    assert len(eng.run_until_idle(max_chunks=100)) == 3


# ------------------------------------------------- (d) fault handling per run


@pytest.fixture(scope="module")
def clean2r(served):
    return _alone(served, _requests(2 * ADMIT_ROWS))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_fatal_fault_in_the_second_run_sheds_only_its_requests(
        served, clean2r, layout):
    eng = _engine(served, **LAYOUTS[layout])
    for r in _requests(2 * ADMIT_ROWS):
        eng.submit(r)
    faults.configure("serve.prefill:fatal:at=2", seed=0)
    got = _tokens(eng.run_until_idle(max_chunks=100))
    second = set(range(ADMIT_ROWS, 2 * ADMIT_ROWS))
    assert {u for u, (_, s) in got.items() if s == FAILED_FAULT} == second
    assert eng.robust.failed_faults == ADMIT_ROWS
    for u in range(ADMIT_ROWS):
        assert got[u] == clean2r[u]
    if layout == "paged":       # the lost run's pages came back
        assert eng._pool.free_pages + eng._pool.cached_pages == \
            eng._pool.capacity


@pytest.mark.parametrize("layout", LAYOUTS)
def test_transient_exhaustion_in_the_second_run_requeues_it_in_order(
        served, clean2r, layout):
    eng = _engine(served, fault_retries=0, **LAYOUTS[layout])
    for r in _requests(2 * ADMIT_ROWS):
        eng.submit(r)
    faults.configure("serve.prefill:unavailable:at=2", seed=0)
    with pytest.raises(RetryError):
        eng.step()
    faults.configure("")
    # the first run holds its slots; the second is back at the queue's
    # front, in order, and no slot is booked for it
    assert sorted(r.uid for r in eng._inflight.values()) == list(
        range(ADMIT_ROWS))
    if layout == "paged":       # and no page: only the first run holds any
        assert sorted(eng._layout.slot_pages) == sorted(eng._inflight)
    assert [eng._queue.popleft().uid for _ in range(ADMIT_ROWS)] == list(
        range(ADMIT_ROWS, 2 * ADMIT_ROWS))
    assert not eng._queue
    for r in reversed(_requests(2 * ADMIT_ROWS)[ADMIT_ROWS:]):
        eng._queue.appendleft(r)
    assert _tokens(eng.run_until_idle(max_chunks=100)) == clean2r


# ----------------------------------------- (e) the counter and the stage time


@pytest.mark.parametrize("n", GROUPS)
def test_admit_rows_per_run_and_prefill_s_per_admitting_step(idle_engine, n):
    eng = idle_engine("slots")
    rows_h, stage_h = eng._admit_rows_hist, eng._stage_hist["prefill_s"]
    runs0, rows0, stages0 = rows_h.count, rows_h.sum, stage_h.count
    for r in _requests(n):
        eng.submit(r)
    eng.step()
    assert rows_h.count - runs0 == math.ceil(n / ADMIT_ROWS)
    assert rows_h.sum - rows0 == n
    assert stage_h.count - stages0 == 1
    assert not eng._open_stages
    eng.step()                      # nothing queued: no admission
    assert rows_h.count - runs0 == math.ceil(n / ADMIT_ROWS)
    assert stage_h.count - stages0 == 1
    eng.run_until_idle(max_chunks=100)      # the next case takes it idle


def test_prefill_s_runs_from_the_first_dispatch_to_the_flags_fetch(
        served, monkeypatch):
    """Two runs in one step: ONE stage, opened at the first run's dispatch
    and closed by the fetch, holding every request of the group."""
    eng = _engine(served)
    eng.aot_warmup()
    real_call, real_fetch = eng._admit_call, engine_mod._host_fetch
    stamps = {"dispatch": [], "fetch": []}

    def call(*args):
        stamps["dispatch"].append(time.perf_counter())
        return real_call(*args)

    def fetch(tree):
        out = real_fetch(tree)
        stamps["fetch"].append(time.perf_counter())
        return out

    monkeypatch.setattr(eng, "_admit_call", call)
    monkeypatch.setattr(engine_mod, "_host_fetch", fetch)
    for r in _requests(ADMIT_ROWS + 1):
        eng.submit(r)
    before = eng.stage_seconds["prefill_s"]
    eng._admit_pending()
    (stage, kind, t0, batch), = eng._open_stages
    assert (stage, kind) == ("prefill_s", "admit")
    assert sorted(r.uid for r in batch) == list(range(ADMIT_ROWS + 1))
    assert len(stamps["dispatch"]) == 2 and t0 <= stamps["dispatch"][0]
    eng._harvest_done()
    took = eng.stage_seconds["prefill_s"] - before
    assert took >= stamps["fetch"][0] - stamps["dispatch"][0]
    assert took <= time.perf_counter() - t0
    assert len(eng._ttft) == ADMIT_ROWS + 1


# ------------------------- (f) no fetch between runs, one run in flight


@pytest.mark.parametrize("n", [ADMIT_ROWS, SLOTS])
def test_no_host_fetch_between_the_runs_of_a_group(served, n, monkeypatch):
    """An admitting step fetches the flags once after its admission and
    once after its chunk, whether the group took one run or sixteen."""
    calls = []
    real_fetch, real_get = engine_mod._host_fetch, jax.device_get
    eng = _engine(served)
    real_call = eng._admit_call

    def fetch(tree):
        calls.append("fetch")
        return real_fetch(tree)

    def device_get(x):
        calls.append("get")
        return real_get(x)

    def call(*args):
        calls.append("admit")
        return real_call(*args)

    def wait(x):
        calls.append("wait")
        return real_wait(x)

    real_wait = jax.block_until_ready
    monkeypatch.setattr(engine_mod, "_host_fetch", fetch)
    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    monkeypatch.setattr(eng, "_admit_call", call)
    for r in _requests(n, masked=True):     # no early end of sequence
        eng.submit(r)
    eng.step()
    runs = math.ceil(n / ADMIT_ROWS)
    # the state is not donated: a later run waits (no transfer) for the
    # run before it, so two copies of the state live at once, not runs + 1
    assert calls == (["admit"] + ["wait", "admit"] * (runs - 1)
                     + ["fetch", "get"] * 2)
