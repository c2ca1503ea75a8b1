"""QoS under overload: priority preemption, weighted-fair tenancy, EDF.

The contract under test (docs/SERVING.md §10): the engine's admission
queue is a :class:`QoSQueue` — strict priority classes, deficit-weighted
round robin across tenants inside a class, EDF within a tenant — that
degrades to EXACT FIFO with one class/one tenant/no deadlines, so every
pre-QoS behavior is unchanged.  A high-priority arrival preempts
lower-priority in-flight work (pause-free restart replay), and because
each request's trajectory depends only on (params, prime, seed, knobs),
preemption trades latency, never tokens — asserted here across dense,
paged, and real 2-process cluster serving.
"""

import time
from collections import Counter, deque

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import Request, ServingEngine
from progen_tpu.decode.engine import SHED_QUEUE_FULL
from progen_tpu.decode.handoff import request_from_wire, request_to_wire
from progen_tpu.decode.qos import QoSQueue
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.parallel import unbox

pytestmark = [pytest.mark.serving, pytest.mark.qos]

# depth=2 keeps compile wall low: every engine here is tiny and the
# interesting behavior is host-side scheduling, not numerics
CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=2, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)


@pytest.fixture(scope="module")
def trained():
    policy = make_policy(False)
    model = ProGen(config=CFG, policy=policy)
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    params = unbox(model.init(jax.random.key(7), tokens))
    return model, params, policy


class _R:
    """Bare request stand-in for pure queue tests (no engine)."""

    def __init__(self, uid, priority=0, tenant=0, ttl=None, deadline=None,
                 submit_time=0.0):
        self.uid = uid
        self.priority = priority
        self.tenant = tenant
        self.ttl = ttl
        self.deadline = deadline
        self.submit_time = submit_time

    def __repr__(self):
        return f"_R({self.uid})"


# ------------------------------------------------------- queue: FIFO parity


def test_fifo_degeneracy_random_ops():
    """One class, one tenant, no deadlines: QoSQueue must be bit-equal
    to collections.deque over a random append/appendleft/popleft/remove
    workload — the pre-QoS engine contract."""
    import random

    rng = random.Random(0)
    q, d = QoSQueue(), deque()
    for i in range(300):
        op = rng.random()
        if op < 0.5 or not d:
            r = _R(i)
            q.append(r)
            d.append(r)
        elif op < 0.7:
            assert q.popleft() is d.popleft()
        elif op < 0.85:
            r = _R(1000 + i)
            q.appendleft(r)
            d.appendleft(r)
        else:
            r = rng.choice(list(d))
            d.remove(r)
            q.remove(r)
        assert len(q) == len(d)
        assert list(q) == list(d)
        if d:
            assert q[0] is d[0]
    while d:
        assert q.popleft() is d.popleft()
    assert not q


def test_remove_missing_raises():
    q = QoSQueue()
    q.append(_R(0))
    with pytest.raises(ValueError):
        q.remove(_R(1))


# -------------------------------------------------- queue: the three levels


def test_priority_classes_strictly_ordered():
    q = QoSQueue()
    for uid, p in [(0, 0), (1, 2), (2, 1), (3, 2), (4, 0)]:
        q.append(_R(uid, priority=p))
    assert [q.popleft().uid for _ in range(5)] == [1, 3, 2, 0, 4]


def test_edf_within_tenant_then_fifo():
    q = QoSQueue()
    q.append(_R(0, deadline=9.0))
    q.append(_R(1, deadline=3.0))
    q.append(_R(2))            # no deadline: after every deadlined one
    q.append(_R(3, ttl=1.0, submit_time=1.0))  # deadline 2.0, earliest
    assert [q.popleft().uid for _ in range(4)] == [3, 1, 0, 2]


def test_dwrr_converges_to_weight_ratio():
    q = QoSQueue(weights={0: 1.0, 1: 2.0})
    for i in range(60):
        q.append(_R(i, tenant=i % 2))
    served = Counter(q.popleft().tenant for _ in range(30))
    # long-run shares converge to 1:2 (integer rounding at the margin)
    assert abs(served[1] - 2 * served[0]) <= 2


def test_zero_weight_tenant_is_background():
    """A zero-weight tenant is served only when no positive-weight
    tenant in the class has queued work — work-conserving, never ahead."""
    q = QoSQueue(weights={5: 0.0, 1: 1.0})
    for i in range(4):
        q.append(_R(i, tenant=5))
    for i in range(4, 8):
        q.append(_R(i, tenant=1))
    order = [q.popleft().tenant for _ in range(8)]
    assert order == [1, 1, 1, 1, 5, 5, 5, 5]


def test_nonzero_weight_tenant_never_starves():
    """Even a tiny weight accumulates credit every rotation: tenant 1
    (weight 0.25) must be served within ceil(1/0.25)=4 pops of heavy
    tenant-0 traffic."""
    q = QoSQueue(weights={0: 1.0, 1: 0.25})
    for i in range(20):
        q.append(_R(i, tenant=0))
    q.append(_R(100, tenant=1))
    first = next(i for i in range(8)
                 if q.popleft().tenant == 1)
    assert first <= 4


def test_peek_pop_agree_under_dwrr_and_priorities():
    q = QoSQueue(weights={0: 1.0, 1: 2.0, 2: 0.0})
    for i in range(40):
        q.append(_R(i, tenant=i % 3, priority=i % 2))
    while q:
        head = q[0]
        assert q.popleft() is head


def test_front_stack_is_lifo_and_beats_policy():
    """appendleft is the deterministic-replay path: LIFO, consulted
    before any class — even a higher-priority policy enqueue."""
    q = QoSQueue()
    q.append(_R(0, priority=9))
    q.appendleft(_R(1))
    q.appendleft(_R(2))
    assert [q.popleft().uid for _ in range(3)] == [2, 1, 0]


def test_preempted_request_keeps_seniority():
    """Policy re-enqueue (the preemption path) preserves the original
    sequence number: a preempted request resumes ahead of same-class
    peers that arrived after it."""
    q = QoSQueue()
    a, b = _R(0), _R(1)
    q.append(a)
    q.append(b)
    got = q.popleft()           # a heads to a slot...
    assert got is a
    q.append(a)                 # ...and is preempted back
    assert q.popleft() is a     # still ahead of b
    assert q.popleft() is b


def test_shed_victim_lowest_class_then_oldest():
    q = QoSQueue()
    hi, old_lo, new_lo = _R(0, priority=2), _R(1), _R(2)
    for r in (hi, old_lo, new_lo):
        q.append(r)
    assert q.shed_victim() is old_lo
    q.remove(old_lo)
    assert q.shed_victim() is new_lo
    q.remove(new_lo)
    assert q.shed_victim() is hi    # only the high class left
    q.remove(hi)
    assert q.shed_victim() is None


def test_stats_shape():
    q = QoSQueue(weights={1: 2.0})
    q.append(_R(0, priority=2, tenant=1))
    q.append(_R(1))
    q.popleft()
    s = q.stats()
    assert s["queue_by_class"] == {0: 1}
    assert s["queue_by_tenant"] == {0: 1}
    assert s["served_by_class"] == {2: 1}
    assert s["served_by_tenant"] == {1: 1}
    assert s["weights"] == {1: 2.0}


# ------------------------------------------------------ engine: admission


def _req(uid, tokens, *, priority=0, tenant=0, max_new=6, seed=None):
    return Request(uid=uid, tokens=list(tokens), max_new_tokens=max_new,
                   top_k=(None if uid % 2 else 8),
                   temperature=(0.0 if uid % 2 else 1.0),
                   seed=(100 + uid if seed is None else seed),
                   submit_time=time.perf_counter(),
                   priority=priority, tenant=tenant)


def _primes(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.num_tokens,
                         int(rng.integers(3, 9))).tolist()
            for _ in range(n)]


def test_priority_aware_shed_oldest(trained):
    """shed-oldest must never shed a strictly higher-priority queued
    request in favor of a lower-priority arrival: the victim is always
    the oldest request of the LOWEST queued class, and when even that
    victim outranks the arrival, the ARRIVAL sheds instead."""
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, max_queue=2,
                        shed_policy="shed-oldest")
    pr = _primes(6)
    eng.submit(_req(0, pr[0]))
    eng.step()                                 # uid 0 -> the only slot
    eng.submit(_req(1, pr[1], priority=2))     # queued, high
    eng.submit(_req(2, pr[2]))                 # queued, low; queue full
    # equal-priority overflow: the OLDEST low request (uid 2) sheds
    eng.submit(_req(3, pr[3]))
    # higher-priority arrival: the low victim (uid 3) sheds, never uid 1
    eng.submit(_req(4, pr[4], priority=1))
    # lower-priority arrival vs a queue that outranks it: ARRIVAL sheds
    eng.submit(_req(5, pr[5]))
    shed = [c for c in eng.completions if c.status == SHED_QUEUE_FULL]
    assert [c.uid for c in shed] == [2, 3, 5]
    assert sorted(r.uid for r in eng._queue) == [1, 4]
    done = eng.run_until_idle(max_chunks=100)
    assert {c.uid for c in done if c.ok} == {0, 1, 4}


@pytest.mark.parametrize("variant", ["dense", "paged"])
def test_preemption_token_identity(trained, variant):
    """A high-priority arrival preempts the low-priority in-flight
    request; the victim replays from scratch and its tokens are
    IDENTICAL to an uncontended run — bit-exact by construction, in
    every engine mode."""
    _, params, policy = trained
    kw = {"paged": dict(paged=True, page_size=4, num_pages=32),
          "dense": {}}[variant]
    pr = _primes(2, seed=3)
    reqs = [_req(0, pr[0], max_new=8), _req(1, pr[1], priority=2)]

    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, **kw)
    eng.submit(reqs[0])
    eng.step()                       # uid 0 admitted and decoding
    assert 0 in {r.uid for r in eng._inflight.values()}
    eng.submit(reqs[1])              # high-priority arrival
    done = {c.uid: c.tokens.tolist()
            for c in eng.run_until_idle(max_chunks=200)}
    assert eng.robust.preemptions >= 1
    assert eng.status()["qos"]["preemptions"] >= 1

    clean = ServingEngine(CFG, params, policy=policy, num_slots=2,
                          chunk_size=4, max_len=20, **kw)
    for r in reqs:
        clean.submit(Request(uid=r.uid, tokens=r.tokens,
                             max_new_tokens=r.max_new_tokens,
                             top_k=r.top_k, temperature=r.temperature,
                             seed=r.seed))
    want = {c.uid: c.tokens.tolist()
            for c in clean.run_until_idle(max_chunks=200)}
    assert done == want


def test_no_preemption_under_disagg(trained):
    """Disaggregated serving admits from the handoff queue — prefill
    work already paid for is never thrown away, so the preemption path
    must stay off (cluster QoS lives at the prefill-worker queues)."""
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, disagg=True,
                        prefill_batch=1, handoff_depth=2)
    pr = _primes(3, seed=5)
    eng.submit(_req(0, pr[0]))
    eng.step()
    eng.submit(_req(1, pr[1], priority=2))
    done = eng.run_until_idle(max_chunks=200)
    assert eng.robust.preemptions == 0
    assert {c.uid for c in done if c.ok} == {0, 1}


def test_dwrr_admission_order_in_engine(trained):
    """Tenant weights steer ADMISSION order end to end: with weight 2:1
    and one slot, tenant 1 clears its backlog roughly twice as fast."""
    _, params, policy = trained
    from progen_tpu.workloads.lora import random_lora_bank

    bank = random_lora_bank(CFG, 2, 4, seed=11)
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, lora_bank=bank,
                        qos_weights={0: 1.0, 1: 2.0})
    pr = _primes(8, seed=7)
    for i in range(8):
        eng.submit(_req(i, pr[i], tenant=i % 2, max_new=4))
    done = eng.run_until_idle(max_chunks=300)
    assert len([c for c in done if c.ok]) == 8
    served = eng._queue.served_by_tenant
    assert served == {0: 4, 1: 4}
    # of the first four admissions, tenant 1 got at least two slots
    order = [c.uid % 2 for c in sorted(done, key=lambda c: c.finish_time)]
    assert sum(1 for t in order[:4] if t == 1) >= 2


# ------------------------------------------- persistence + wire round-trips


def test_priority_survives_snapshot_restore(trained):
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20)
    pr = _primes(3, seed=9)
    eng.submit(_req(0, pr[0]))
    eng.step()
    eng.submit(_req(1, pr[1], priority=2, tenant=0))
    eng.submit(_req(2, pr[2]))
    snap = eng.snapshot()
    fresh = ServingEngine(CFG, params, policy=policy, num_slots=1,
                         chunk_size=4, max_len=20)
    fresh.restore(snap)
    by_uid = {r.uid: r for r in fresh._queue}
    assert by_uid[1].priority == 2
    assert by_uid[2].priority == 0
    want = {c.uid: c.tokens.tolist()
            for c in eng.run_until_idle(max_chunks=200)}
    got = {c.uid: c.tokens.tolist()
           for c in fresh.run_until_idle(max_chunks=200)}
    assert got == want


def test_priority_rides_the_wire():
    r = Request(uid=3, tokens=[1, 2, 3], max_new_tokens=4, top_k=8,
                temperature=1.0, seed=5, priority=2, tenant=1)
    d = request_to_wire(r)
    assert d["priority"] == 2
    rt = request_from_wire(d)
    assert rt.priority == 2 and rt.tenant == 1
    # zero priority is elided from the wire (compat with old frames)
    d0 = request_to_wire(Request(uid=4, tokens=[1], max_new_tokens=1))
    assert "priority" not in d0
    assert request_from_wire(d0).priority == 0


# -------------------------------------------------------------- observability


def test_qos_status_and_gauges(trained):
    _, params, policy = trained
    from progen_tpu.observe import metrics as _metrics

    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20,
                        qos_weights={0: 1.0, 1: 2.0})
    pr = _primes(3, seed=13)
    eng.submit(_req(0, pr[0]))
    eng.step()
    eng.submit(_req(1, pr[1], priority=2))
    eng.submit(_req(2, pr[2]))
    qos = eng.qos_status()
    assert qos["weights"] == {0: 1.0, 1: 2.0}
    assert sum(qos["queue_by_class"].values()) == len(eng._queue)
    assert sum(qos["inflight_by_class"].values()) == len(eng._inflight)
    reg = _metrics.get_registry()
    key = _metrics.labeled("engine.queue_depth", priority=2)
    assert reg.gauge(key).value >= 1
    rc = eng.robustness_counters()
    assert "preemptions" in rc and "qos" in rc
    assert rc["qos"]["weights"] == {0: 1.0, 1: 2.0}
    eng.run_until_idle(max_chunks=200)
    eng.qos_status()
    # drained: every stale label key re-reads 0, not its last value
    assert reg.gauge(key).value == 0


# ------------------------------------------------------- 2-process cluster


@pytest.mark.multiproc
def test_cluster_priority_mix_token_identity(trained):
    """Real 2-process cluster (prefill worker + decode replica): a mixed
    priority/tenant workload completes token-identical to the
    single-process engine — priorities steer scheduling, never tokens —
    and the router's class-load bookkeeping drains to zero."""
    from progen_tpu.serve.cluster import ServeCluster
    from progen_tpu.serve.worker import build_engine_from_spec, make_spec

    engine_kw = dict(num_slots=4, chunk_size=4, max_len=24,
                     prefill_batch=2, handoff_depth=2)
    spec = make_spec(CFG, mixed_precision=False, init_seed=7,
                     engine={**engine_kw,
                             "qos_weights": {0: 1.0, 1: 2.0}})
    # tenant 0 throughout: the worker spec ships no LoRA bank, and the
    # weights/tenant plumbing is covered by the in-process tests above —
    # this test pins PRIORITY transport + scheduling across processes
    reqs = [Request(uid=i, tokens=[1 + i, 2, 3], max_new_tokens=6,
                    top_k=(None if i % 2 else 8),
                    temperature=(0.0 if i % 2 else 1.0), seed=100 + i,
                    priority=(2 if i % 3 == 0 else 0))
            for i in range(4)]
    cluster = ServeCluster(spec)
    try:
        for r in reqs:
            cluster.submit(r)
        done = cluster.drain(timeout=300.0)
    finally:
        cluster.shutdown()
    assert len(done) == 4 and all(c.ok for c in done)

    # the oracle: same spec WITHOUT priorities/weights, single process
    ref = build_engine_from_spec(make_spec(CFG, mixed_precision=False,
                                           init_seed=7, engine=engine_kw))
    for r in reqs:
        ref.submit(Request(uid=r.uid, tokens=r.tokens,
                           max_new_tokens=r.max_new_tokens, top_k=r.top_k,
                           temperature=r.temperature, seed=r.seed))
    want = {c.uid: [int(t) for t in c.tokens]
            for c in ref.run_until_idle(max_chunks=200)}
    assert {c.uid: [int(t) for t in c.tokens] for c in done} == want
    assert cluster.router.queued_by_class() == {}
