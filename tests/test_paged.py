"""Paged serving subsystem tests: page pool, paged prefill harvest,
ragged paged gate-mix kernel, and the paged engine.

The load-bearing ones:

* pool bookkeeping — refcounted alloc/free, prefix-cache LRU eviction,
  and the reserved NULL/DUMP pages staying out of circulation;
* harvest parity — prefill scattered into pages, gathered back through
  the page table, must equal the contiguous dense gate cache bit for bit;
* engine parity — greedy completions from the paged engine are
  TOKEN-IDENTICAL to the fixed-slot engine, including under slot/page
  reuse, pool starvation (pausing) and eviction-restart;
* kernel parity — the Pallas ragged mix agrees with the XLA gather
  fallback to 1e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import (
    DUMP_PAGE,
    NULL_PAGE,
    PagePool,
    Request,
    ServingEngine,
    harvest_caches,
    scatter_gate_rows,
    init_gate_pool,
    pages_for_span,
    prefix_key,
)
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.ops.pallas_paged_attention import paged_gate_mix
from progen_tpu.parallel import unbox

pytestmark = [pytest.mark.serving, pytest.mark.paged]

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=3, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)


@pytest.fixture(scope="module")
def trained():
    policy = make_policy(False)  # f32 end to end: parity mode
    model = ProGen(config=CFG, policy=policy)
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    params = unbox(model.init(jax.random.key(7), tokens))
    return model, params, policy


# ------------------------------------------------------------------ pool


def test_pages_for_span():
    assert pages_for_span(-1, 4) == 0
    assert pages_for_span(0, 4) == 1
    assert pages_for_span(3, 4) == 1
    assert pages_for_span(4, 4) == 2
    assert pages_for_span(15, 16) == 1


def test_pool_alloc_free_refcount():
    pool = PagePool(8, 4)
    assert pool.capacity == 6 and pool.free_pages == 6
    a = pool.allocate(4)
    assert len(a) == 4 and pool.free_pages == 2
    # reserved pages never circulate
    assert NULL_PAGE not in a and DUMP_PAGE not in a
    pool.retain(a[0])
    pool.release(a[0])
    assert pool.refcount(a[0]) == 1  # still held by the original owner
    for pid in a:
        pool.release(pid)
    assert pool.free_pages == 6
    assert pool.allocate(7) is None  # over capacity
    with pytest.raises(ValueError):
        pool.release(a[0])  # double free
    with pytest.raises(ValueError):
        pool.retain(NULL_PAGE)


def test_pool_prefix_cache_lru_eviction():
    pool = PagePool(2 + 3, 4)
    keys = [prefix_key(8, list(range(1, 9)), u) for u in (4, 8)]
    pages = pool.allocate(2)
    for k, p in zip(keys, pages):
        pool.register_prefix(k, p)
        pool.release(p)  # owner done; index holds the last ref
    assert pool.free_pages == 1 and pool.cached_pages == 2
    assert pool.lookup_prefix(keys[1]) == pages[1]
    # allocating past the free list reclaims cached pages LRU-first:
    # keys[0] is least recently used (keys[1] was just touched)
    got = pool.allocate(2)
    assert got is not None and pool.cached_pages == 1
    assert pool.lookup_prefix(keys[0]) is None
    assert pool.lookup_prefix(keys[1]) == pages[1]


def test_prefix_key_includes_pad_shape():
    toks = list(range(1, 17))
    assert prefix_key(16, toks, 8) == prefix_key(16, toks, 8)
    assert prefix_key(16, toks, 8) != prefix_key(24, toks, 8)
    assert prefix_key(16, toks, 8) != prefix_key(16, toks, 16)
    assert prefix_key(16, toks, 8) != prefix_key(16, [99] + toks[1:], 8)


# --------------------------------------------------------------- harvest


def test_scatter_gate_rows_matches_contiguous(trained):
    """A prefill's dense gate rows scattered into pool pages, gathered
    back through the page table, equal the contiguous harvest bit for
    bit (and rows past each prime stay out of the pages)."""
    model, params, policy = trained
    lengths = np.asarray([5, 8, 1])
    p_pad = 8
    rng = np.random.default_rng(0)
    toks = np.zeros((3, p_pad), np.int32)
    for b, p in enumerate(lengths):
        toks[b, :p] = rng.integers(1, CFG.num_tokens, p)

    _, varz = model.apply(params, jnp.asarray(toks), mutable=["cache"])
    dense = harvest_caches(CFG, varz["cache"], jnp.asarray(lengths), policy,
                           CFG.seq_len)

    ps = 4
    ppr = -(-CFG.seq_len // ps)
    pool = init_gate_pool(CFG, 2 + 3 * ppr, ps, policy)
    table = np.full((3, ppr), NULL_PAGE, np.int32)
    wtable = np.full((3, ppr), DUMP_PAGE, np.int32)
    nxt = 2
    for b, p in enumerate(lengths):
        n = pages_for_span(int(p) - 1, ps)
        table[b, :n] = wtable[b, :n] = range(nxt, nxt + n)
        nxt += n
    pool = scatter_gate_rows(CFG, dense["sgu_gate"], jnp.asarray(lengths),
                             pool, jnp.asarray(wtable))

    for i in range(CFG.depth):
        if not CFG.layer_uses_gmlp(i):
            continue
        rows = np.asarray(pool[str(i)])[table]  # (3, ppr, ps, half)
        rows = rows.reshape(3, ppr * ps, -1)[:, :CFG.seq_len]
        np.testing.assert_array_equal(
            rows, np.asarray(dense["sgu_gate"][str(i)]))


# ---------------------------------------------------------------- kernel


@pytest.mark.parametrize("seed", [0, 3])
def test_paged_mix_pallas_matches_xla(seed):
    """The Pallas ragged page-walk kernel agrees with the XLA gather
    fallback (rtol 1e-5) on ragged positions and partially-NULL tables."""
    rng = np.random.default_rng(seed)
    n, d, ps, B = 24, 8, 4, 3
    ppr = n // ps
    num_pages = 2 + B * ppr
    weights = jnp.asarray(rng.normal(size=(n, n)), jnp.float32)
    biases = jnp.asarray(rng.normal(size=(n, 1)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(num_pages, ps, d)), jnp.float32)
    pool = pool.at[NULL_PAGE].set(0.0)
    pos = jnp.asarray([0, 7, n - 1], jnp.int32)
    table = np.full((B, ppr), NULL_PAGE, np.int32)
    for b in range(B):
        need = int(pos[b]) // ps + 1
        table[b, :need] = 2 + b * ppr + np.arange(need)
    table = jnp.asarray(table)

    xla = paged_gate_mix(weights, biases, pool, table, pos, n_rows=n,
                         impl="xla")
    pal = paged_gate_mix(weights, biases, pool, table, pos, n_rows=n,
                         impl="pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(xla),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        paged_gate_mix(weights, biases, pool, table, pos, n_rows=n,
                       impl="nope")


# ---------------------------------------------------------------- engine


def _mk_requests(n, *, seed=0, max_new=8, greedy=True):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(1, 9))
        reqs.append(Request(
            uid=i, tokens=rng.integers(1, CFG.num_tokens, p).tolist(),
            max_new_tokens=max_new,
            top_k=None if greedy else 8,
            temperature=0.0 if greedy else 0.9, seed=100 + i,
        ))
    return reqs


def _run_engine(params, policy, reqs, **kw):
    eng = ServingEngine(CFG, params, policy=policy, **kw)
    for r in reqs:
        eng.submit(r)
    comps = eng.run_until_idle(max_chunks=300)
    return eng, {c.uid: (c.tokens.tolist(), c.finish_reason) for c in comps}


def test_paged_engine_greedy_matches_fixed_slot(trained):
    """Greedy completions from the paged engine are token-identical to
    the fixed-slot engine, across slot AND page reuse."""
    _, params, policy = trained
    _, dense = _run_engine(params, policy, _mk_requests(7), num_slots=3,
                           chunk_size=4, max_len=20)
    peng, paged = _run_engine(params, policy, _mk_requests(7), num_slots=3,
                              chunk_size=4, max_len=20, paged=True,
                              page_size=4)
    assert set(paged) == set(range(7))
    assert paged == dense
    assert peng._pool.free_pages + peng._pool.cached_pages == \
        peng._pool.capacity  # every request's pages returned


def test_paged_engine_sampled_matches_fixed_slot(trained):
    """Seeded top-k sampling also agrees: the paged step feeds the SAME
    logits into the same per-request key schedule."""
    _, params, policy = trained
    _, dense = _run_engine(params, policy, _mk_requests(5, greedy=False),
                           num_slots=2, chunk_size=3, max_len=20)
    _, paged = _run_engine(params, policy, _mk_requests(5, greedy=False),
                           num_slots=2, chunk_size=3, max_len=20,
                           paged=True, page_size=4)
    assert paged == dense


def test_paged_engine_tight_pool_pauses_and_evicts(trained):
    """A starved pool pauses/evicts under load yet changes NO tokens —
    eviction restarts replay the identical deterministic trajectory."""
    _, params, policy = trained
    _, dense = _run_engine(params, policy, _mk_requests(7), num_slots=3,
                           chunk_size=4, max_len=20)
    eng, paged = _run_engine(params, policy, _mk_requests(7), num_slots=3,
                             chunk_size=4, max_len=20, paged=True,
                             page_size=4, num_pages=8, prefix_cache=False)
    assert paged == dense
    assert eng.pause_events > 0  # the tiny pool did starve
    assert eng._pool.free_pages == eng._pool.capacity


def test_paged_engine_pallas_impl_matches(trained):
    """paged_impl='pallas' (interpret off-TPU) produces the same greedy
    completions as the XLA gather path."""
    _, params, policy = trained
    _, xla = _run_engine(params, policy, _mk_requests(4), num_slots=2,
                         chunk_size=4, max_len=20, paged=True, page_size=4)
    _, pal = _run_engine(params, policy, _mk_requests(4), num_slots=2,
                         chunk_size=4, max_len=20, paged=True, page_size=4,
                         paged_impl="pallas")
    assert pal == xla


def test_paged_engine_prefix_cache_shares_pages(trained):
    """Identical primes hit the prefix cache: later requests reuse the
    first one's full prefix pages, and the index's references keep the
    accounting exact after every request frees."""
    _, params, policy = trained
    prime = list(np.random.default_rng(3).integers(1, CFG.num_tokens, 9))
    reqs = [Request(uid=i, tokens=[int(t) for t in prime],
                    max_new_tokens=6, top_k=None, temperature=0.0,
                    seed=i) for i in range(3)]
    eng, by_uid = _run_engine(params, policy, reqs, num_slots=1,
                              chunk_size=4, max_len=20, paged=True,
                              page_size=4)
    # one slot => requests run one after another; 2nd and 3rd share the
    # first's two full prefix pages (rows 0..7 of the 9-token prime)
    assert eng.prefix_hits == 4
    assert len({tuple(t) for t, _ in by_uid.values()}) == 1
    assert eng._pool.cached_pages == 2
    assert eng._pool.free_pages + eng._pool.cached_pages == \
        eng._pool.capacity


def test_paged_engine_admission_defers_on_exhaustion(trained):
    """Admission is gated by free pages: with slots for 3 but pages for
    ~1, requests defer (FIFO) instead of over-committing, and the engine
    still drains them all."""
    _, params, policy = trained
    reqs = [Request(uid=i, tokens=[3, 4, 5, 6, 7], max_new_tokens=6,
                    top_k=None, temperature=0.0, seed=i) for i in range(3)]
    eng = ServingEngine(CFG, params, policy=policy, num_slots=3,
                        chunk_size=4, max_len=16, paged=True, page_size=4,
                        num_pages=2 + 4, prefix_cache=False)
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert eng.num_active < 3 and eng.num_active >= 1
    comps = eng.run_until_idle(max_chunks=300)
    assert sorted(c.uid for c in comps) == [0, 1, 2]
    assert eng._pool.free_pages == eng._pool.capacity


def test_paged_engine_rejects_request_exceeding_pool(trained):
    """A request whose worst case cannot EVER fit the pool is rejected at
    submit (it would deadlock the FIFO queue)."""
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=4, max_len=20, paged=True, page_size=4,
                        num_pages=2 + 2)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, tokens=list(range(1, 9)),
                           max_new_tokens=10))


# --------------------------------------------------------------- sharded


def test_paged_engine_tp2_sharded_matches_dense(trained, devices8):
    """Paged vs fixed-slot greedy parity holds SPMD too: on a tensor-
    parallel mesh the pooled gate pages and the page-table walk produce
    the same tokens as the per-slot slabs, request for request."""
    from progen_tpu.core import MeshConfig, make_mesh
    from progen_tpu.parallel.sharding import param_shardings

    model, params, policy = trained
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2), devices=devices8)
    strategies = ("fsdp", "tp")
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    shardings = param_shardings(model, tokens, mesh, strategies)["params"]
    mesh_kw = dict(mesh=mesh, strategies=strategies,
                   params_shardings=shardings)

    _, dense = _run_engine(params, policy, _mk_requests(5, max_new=6),
                           num_slots=2, chunk_size=3, max_len=20,
                           **mesh_kw)
    peng, paged = _run_engine(params, policy, _mk_requests(5, max_new=6),
                              num_slots=2, chunk_size=3, max_len=20,
                              paged=True, page_size=4, **mesh_kw)
    assert set(paged) == set(range(5))
    assert paged == dense
    assert peng._pool.free_pages + peng._pool.cached_pages == \
        peng._pool.capacity


# ---------------------------------------------------------------- memory


def test_serving_plan_equal_budget():
    """equal_budget_pages sizes the paged pool to exactly the dense
    engines' pageable gate-row HBM."""
    from progen_tpu.train.memory import (
        equal_budget_pages, gate_row_bytes, serving_plan,
    )

    dense = serving_plan(CFG, num_slots=2, max_len=16)
    pages = equal_budget_pages(CFG, dense_slots=2, max_len=16, page_size=4)
    paged = serving_plan(CFG, num_slots=8, max_len=16, paged=True,
                         page_size=4, num_pages=pages)
    assert paged.pool_bytes == dense.pageable_bytes
    assert dense.pageable_bytes == 2 * 16 * gate_row_bytes(CFG)
    # paged mode trades the per-slot slabs for the pool: at 4x the slots
    # the pageable resource cost is identical
    assert paged.pageable_bytes == paged.pool_bytes
    assert paged.total_bytes > 0 and dense.total_bytes > 0
