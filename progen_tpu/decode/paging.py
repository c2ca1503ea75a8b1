"""Host-side page-pool bookkeeping for the paged serving engine.

The fixed-slot ServingEngine prices every request at the worst case: one
slot owns ``max_len`` rows of SGU gate cache for its whole lifetime, so
HBM per request is ``max_len`` rows even when the request uses 40.  The
paged mode (vLLM / "Ragged Paged Attention", PAPERS.md) replaces the
per-slot allocation with a GLOBAL POOL of fixed-size pages (``page_size``
token rows each) and a per-request PAGE TABLE mapping row index
``i -> pool page table[i // page_size]``:

* pages are allocated on demand as a request's position advances and
  freed (refcounted) when it completes — concurrency is bounded by
  actual live tokens, not ``slots x max_len``;
* requests sharing a prompt prefix share the read-only pages that are
  fully inside the common prefix (hash-keyed prefix cache), so a popular
  prompt's gate rows exist once in HBM no matter how many requests are
  decoding from it.

``PagePool`` is the HOST side: free lists, refcounts and the prefix
index are plain Python (they make per-request decisions between device
dispatches).  The device side — the pooled gate arrays, the page-table
walk in the decode step, and the ragged paged mix kernel — lives in
``decode/incremental.py`` and ``ops/pallas_paged_attention.py``.

The two CACHE LAYOUTS at the end of the module are what ``ServingEngine``
knows of all this: ``SlotCaches`` (every cache row in its slot) and
``PagedGates`` (ProGen's gate rows in the pool).  It picks one at
construction, and its chunk body, its merge and its place / undo pair
ask the layout for whatever differs between the two.

Two pool pages are reserved:

* page 0 (``NULL_PAGE``) is all-zeros and never written: page-table
  entries for slots a request does not own point here, so the XLA
  gather fallback reads exact zeros for unowned rows (bit-matching the
  dense engine's zero-initialized cache tail);
* page 1 (``DUMP_PAGE``) is a write sink that is never read: masked
  scatter lanes (pad rows, prefix-shared pages, non-live slots) are
  redirected here instead of needing a predicated scatter.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.decode.incremental import (
    ProGenPagedDecodeStep,
    init_caches,
    init_gate_pool,
    init_gate_scale,
)
from progen_tpu.decode.prefill import scatter_gate_rows

NULL_PAGE = 0
DUMP_PAGE = 1
RESERVED_PAGES = 2


def pages_for_span(last_row: int, page_size: int) -> int:
    """Number of pages covering rows ``[0, last_row]`` inclusive."""
    if last_row < 0:
        return 0
    return last_row // page_size + 1


def token_span_digest(tokens: Sequence[int], upto: int) -> str:
    """Content hash of the first ``upto`` prime tokens.  Shared between
    ``prefix_key`` (pool-local identity) and the fleet router's digest
    matching: the router scores replicas by ``(upto, digest)`` alone, so
    it can rank placements without knowing which prefill bucket a worker
    will land the request in."""
    h = hashlib.blake2b(digest_size=16)
    for t in tokens[:upto]:
        h.update(b"%d," % int(t))
    return h.hexdigest()


def prefix_key(p_pad: int, tokens: Sequence[int], upto: int) -> tuple:
    """Hash key for the prefix page covering rows ``[upto-page_size,
    upto)``: the first ``upto`` prime tokens plus the padded prefill
    length.  ``p_pad`` is part of the key because gate rows are only
    guaranteed BIT-identical across requests when they came out of the
    same-shape prefill program (same summation trees); two requests whose
    primes land in different prefill buckets recompute rather than share.
    """
    return (p_pad, upto, token_span_digest(tokens, upto))


class PagePool:
    """Free list + refcounts + LRU prefix index over ``num_pages`` pages.

    ``num_pages`` counts the DEVICE pool's first axis, including the two
    reserved pages; ``capacity`` is the allocatable remainder.  Reference
    counting: every in-flight request holds one reference per page in its
    table (shared or private), and the prefix index holds one reference
    per cached page.  A page returns to the free list when its count hits
    zero; cached pages idle at refcount 1 and are reclaimed LRU-first
    when an allocation would otherwise fail.
    """

    def __init__(self, num_pages: int, page_size: int, *,
                 prefix_caching: bool = True, gate_dtype: str = "bf16"):
        if num_pages < RESERVED_PAGES + 1:
            raise ValueError(
                f"num_pages {num_pages} leaves no allocatable pages "
                f"({RESERVED_PAGES} are reserved)")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if gate_dtype not in ("bf16", "int8"):
            raise ValueError(f"gate_dtype {gate_dtype!r}: want 'bf16' "
                             "or 'int8'")
        self.num_pages = num_pages
        self.page_size = page_size
        self.prefix_caching = prefix_caching
        # bookkeeping only — the device pools live in engine state; the
        # pool records the page format so stats/capacity reports can say
        # what a page costs (int8 rows are ~2x denser than bf16)
        self.gate_dtype = gate_dtype
        # LIFO free list: recently-freed pages are reused first, which
        # keeps the working set dense and makes tests deterministic
        self._free: list[int] = list(range(num_pages - 1,
                                           RESERVED_PAGES - 1, -1))
        self._ref: dict[int, int] = {}
        self._prefix: OrderedDict[tuple, int] = OrderedDict()
        self._key_of: dict[int, tuple] = {}

    # ------------------------------------------------------------- queries

    @property
    def capacity(self) -> int:
        return self.num_pages - RESERVED_PAGES

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        return len(self._prefix)

    def refcount(self, pid: int) -> int:
        return self._ref.get(pid, 0)

    def _evictable(self) -> int:
        # cached pages held only by the index (refcount 1) can be dropped
        return sum(1 for pid in self._prefix.values()
                   if self._ref.get(pid, 0) == 1)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) + self._evictable() >= n

    # ---------------------------------------------------------- allocation

    def allocate(self, n: int) -> list[int] | None:
        """``n`` fresh private pages (refcount 1 each), or None when the
        pool cannot supply them even after evicting idle cached pages."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if not self.can_allocate(n):
            return None
        while len(self._free) < n:
            self._evict_one_cached()
        out = [self._free.pop() for _ in range(n)]
        for pid in out:
            self._ref[pid] = 1
        return out

    def _evict_one_cached(self) -> None:
        for key, pid in self._prefix.items():  # insertion order = LRU
            if self._ref.get(pid, 0) == 1:
                del self._prefix[key]
                del self._key_of[pid]
                self._release_ref(pid)
                return
        raise RuntimeError("no evictable cached page")  # guarded by caller

    def retain(self, pid: int) -> None:
        if pid < RESERVED_PAGES:
            raise ValueError(f"cannot retain reserved page {pid}")
        if self._ref.get(pid, 0) < 1:
            raise ValueError(f"retain of unallocated page {pid}")
        self._ref[pid] += 1

    def release(self, pid: int) -> None:
        if self._ref.get(pid, 0) < 1:
            raise ValueError(f"release of unallocated page {pid}")
        self._release_ref(pid)

    def _release_ref(self, pid: int) -> None:
        self._ref[pid] -= 1
        if self._ref[pid] == 0:
            del self._ref[pid]
            self._free.append(pid)

    # -------------------------------------------------------- prefix cache

    def lookup_prefix(self, key: tuple) -> int | None:
        """Cached page for ``key`` (touches LRU), or None."""
        if not self.prefix_caching:
            return None
        pid = self._prefix.get(key)
        if pid is not None:
            self._prefix.move_to_end(key)
        return pid

    def register_prefix(self, key: tuple, pid: int) -> None:
        """Publish a just-filled full-prefix page for future sharing; the
        index takes its own reference."""
        if not self.prefix_caching or key in self._prefix or \
                pid in self._key_of:
            return
        self._prefix[key] = pid
        self._key_of[pid] = key
        self._ref[pid] = self._ref.get(pid, 0) + 1

    # ---------------------------------------------------------------- stats

    @property
    def shared_pages(self) -> int:
        """Page-holder edges beyond the index's own reference: a cached
        page referenced by ``k`` in-flight requests contributes ``k``.
        Zero when nothing is actively sharing."""
        return sum(self._ref.get(pid, 0) - 1
                   for pid in self._prefix.values()
                   if self._ref.get(pid, 0) > 1)

    def prefix_digest(self) -> dict:
        """Compact JSON-safe advertisement of cache contents for the
        fleet router: one ``[p_pad, upto, digest, refcount]`` row per
        cached prefix page in LRU order (coldest first), plus pool
        pressure.  Cheap enough to ride every heartbeat — the index is
        bounded by the pool size."""
        return {
            "page_size": self.page_size,
            "keys": [[k[0], k[1], k[2], self._ref.get(pid, 0)]
                     for k, pid in self._prefix.items()],
            "free": self.free_pages,
            "cached": self.cached_pages,
            "capacity": self.capacity,
        }

    def stats(self) -> dict:
        """Host-side accounting snapshot (robustness/chaos records)."""
        return {
            "num_pages": self.num_pages,
            "capacity": self.capacity,
            "free_pages": self.free_pages,
            "cached_pages": self.cached_pages,
            "shared_pages": self.shared_pages,
            "pages_in_use": self.capacity - self.free_pages,
            "gate_dtype": self.gate_dtype,
        }


def _where_rows(live, new, old):
    """``new`` in the ``live`` rows of a cache pytree, ``old`` elsewhere."""
    def mrg(n, o):
        return jnp.where(live.reshape((-1,) + (1,) * (o.ndim - 1)), n, o)
    return jax.tree.map(mrg, new, old)


class SlotCaches:
    """Every cache row lives in its slot: the model family's own caches,
    stepped by the family.  Nothing is allocated between dispatches, so
    the host side of the layout is empty."""

    pool = None
    merge_operands = 0      # operands of the merge after ``src, mask``
    prefix_hits = prefix_lookups = 0

    def __init__(self, family):
        self.family = family

    # -- the device side: called while the engine's programs are traced

    def init_caches(self, slots: int, max_len: int):
        return self.family.init_caches(slots, max_len)

    def live(self, state, operands):
        return state["active"] & ~state["done"]

    def step(self, params, tok, pos, caches, live, adapters, tenant,
             operands):
        return self.family.decode_step(params, tok, pos, caches, live,
                                       adapters, tenant)

    def idle_keeps(self, live, new, old):
        """After a plain step: an idle slot is done or empty, and nothing
        reads its caches before an admission overwrites them."""
        return new

    def split_handle(self, hstate):
        """``(handle, gate rows)``: what of a handle the merge may donate
        and what it scatters."""
        return hstate, {}

    def merge(self, take, caches, hstate, gate_rows, operands):
        return jax.tree.map(take, hstate["caches"], caches)

    # -- the host side: called between dispatches

    def chunk_operands(self) -> tuple:
        return ()

    def covers(self, requests) -> bool:
        return True

    def write_tables(self, rows: int) -> tuple:
        return ()

    def plan(self, guard, slot, row, request, p_pad, tables,
             pending_prefix) -> None:
        pass

    def free(self, slot: int) -> None:
        pass


class PagedGates:
    """ProGen's caches with the SGU gate rows in a global page pool: the
    rings and carries stay per slot, ``sgu_pool`` (and, for 8-bit pages,
    ``sgu_pool_scale``) is shared, and the page ``table`` and the
    ``paused`` rows ride into the chunk program as data, so the host's
    allocation decisions never retrace it.

    The host side is the pool's bookkeeping per slot: ``slot_pages``,
    the page ``table`` and ``paused`` (rows the pool could not cover
    this chunk)."""

    merge_operands = 1      # the handle-ROW-indexed write table
    _RING_KEYS = ("attn_prev", "ff_prev", "k", "v")

    def __init__(self, config, policy, *, num_slots: int, max_len: int,
                 page_size: int, num_pages: int | None, impl: str,
                 weights: str, gate_dtype: str, prefix_caching: bool):
        self.config, self.policy = config, policy
        self.max_len, self.weights = max_len, weights
        self.gate_dtype = gate_dtype
        self.page_size = page_size
        self.pages_per_row = -(-max_len // page_size)
        if num_pages is None:
            num_pages = RESERVED_PAGES + num_slots * self.pages_per_row
        self.pool = PagePool(num_pages, page_size,
                             prefix_caching=prefix_caching,
                             gate_dtype=gate_dtype)
        # slot -> its pages in row order: ``pages[j]`` covers rows
        # ``[j * page_size, (j + 1) * page_size)``, prefix-cache hits first
        self.slot_pages: dict[int, list[int]] = {}
        self.table = np.zeros((num_slots, self.pages_per_row), np.int32)
        self.paused = np.zeros((num_slots,), bool)
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.use_impl(impl)

    def use_impl(self, impl: str) -> None:
        """The ragged kernel (``"pallas"``) or its bit-identical gather
        fallback (``"xla"``) for the gate mix of the step."""
        self.impl = impl
        self.step_model = ProGenPagedDecodeStep(
            config=self.config, n_rows=self.max_len, policy=self.policy,
            impl=impl, weights=self.weights, gate_dtype=self.gate_dtype)

    # -- the device side

    def init_caches(self, slots: int, max_len: int):
        caches = init_caches(self.config, slots, self.policy,
                             decode_len=max_len, with_sgu=False)
        caches.pop("sgu_gate")
        caches["sgu_pool"] = init_gate_pool(
            self.config, self.pool.num_pages, self.page_size, self.policy,
            gate_dtype=self.gate_dtype)
        if self.gate_dtype == "int8":
            caches["sgu_pool_scale"] = init_gate_scale(
                self.config, self.pool.num_pages, self.page_size)
        return caches

    def live(self, state, operands):
        _, paused = operands
        return state["active"] & ~state["done"] & ~paused

    def step(self, params, tok, pos, caches, live, adapters, tenant,
             operands):
        table, _ = operands
        logits, caches = self.step_model.apply(
            params, tok, pos, caches, table, live, adapters, tenant)
        return logits, caches, {}

    def idle_keeps(self, live, new, old):
        """A paused row runs the step fully masked and resumes later: it
        keeps its rings and carries, which still hold position ``pos -
        1``'s activations; the pool (and its scales) is written through,
        because its writes are masked inside the step (``write_ok``)."""
        return {**new, **{k: _where_rows(live, new[k], old[k])
                          for k in self._RING_KEYS}}

    def split_handle(self, hstate):
        # the gate slabs scatter into the pool, so they can alias nothing:
        # donating them with the handle would only warn
        caches = dict(hstate["caches"])
        gate = caches.pop("sgu_gate")
        return {**hstate, "caches": caches}, gate

    def merge(self, take, caches, hstate, gate_rows, operands):
        """Rings and carries are gathered like any slot row; the handle's
        dense gate rows (compute dtype: they quantize here, at the pool's
        boundary) scatter through the handle-ROW-indexed write table
        (DUMP for shared pages, unused rows and pad tails)."""
        (row_wtable,) = operands
        out = {k: jax.tree.map(take, hstate["caches"][k], caches[k])
               for k in self._RING_KEYS}
        pool = scatter_gate_rows(
            self.config, gate_rows, hstate["start"], caches["sgu_pool"],
            row_wtable, pool_scale=caches.get("sgu_pool_scale"))
        if self.gate_dtype == "int8":
            pool, out["sgu_pool_scale"] = pool
        return {**out, "sgu_pool": pool}

    # -- the host side

    def chunk_operands(self) -> tuple:
        return self.table.copy(), self.paused.copy()

    def covers(self, requests) -> bool:
        """Whether the pool can hold every prime of ``requests`` plus its
        first sampled token WITHOUT prefix sharing: the conservative
        reservation admission is gated by."""
        return self.pool.can_allocate(sum(
            pages_for_span(len(r.tokens), self.page_size)
            for r in requests))

    def write_tables(self, rows: int) -> tuple:
        return (np.full((rows, self.pages_per_row), DUMP_PAGE, np.int32),)

    def plan(self, guard: Callable, slot: int, row: int, request,
             p_pad: int, tables: tuple, pending_prefix: list) -> None:
        """Book ``slot`` for ``request``, handle row ``row``.  Planning
        allocates (and retains shared) pages — a faultable operation,
        guarded at the SAME point as the chunk-growth allocator."""
        self.paused[slot] = False
        guard("serve.page_alloc", self._plan_pages, slot, request.tokens,
              p_pad, tables[0][row], pending_prefix)

    def _plan_pages(self, slot: int, tokens, p_pad: int, wrow,
                    pending_prefix: list) -> None:
        """Build the slot's page list for rows ``[0, P]`` (prime + first
        sampled token): longest run of prefix-cache hits first, fresh
        private pages for the rest.  Fills the slot's ``table`` row and
        the handle row's write-table row ``wrow`` (private pages only —
        shared pages were filled by the request that first computed them
        and MUST stay read-only: rewriting them from a different prefill
        batch shape could perturb the sharer's bits).

        Fresh full-prefix pages are NOT registered here: registrations
        collect in ``pending_prefix`` and commit only after the guarded
        prefill dispatch succeeds — a failed prefill must never leave the
        index pointing at pages that were never filled."""
        ps = self.page_size
        p = len(tokens)
        n_pages = p // ps + 1  # decode writes row P before any page grows
        n_full = p // ps       # full pages strictly inside the prime
        shared: list[int] = []
        for j in range(n_full):
            pid = self.pool.lookup_prefix(prefix_key(p_pad, tokens,
                                                     (j + 1) * ps))
            if pid is None:
                break
            shared.append(pid)
        fresh = self.pool.allocate(n_pages - len(shared))
        assert fresh is not None, "admission reserved pages conservatively"
        for pid in shared:
            self.pool.retain(pid)
        self.prefix_hits += len(shared)
        self.prefix_lookups += n_full
        pages = shared + fresh
        for j in range(len(shared), n_full):
            pending_prefix.append(
                (prefix_key(p_pad, tokens, (j + 1) * ps), pages[j]))
        self.slot_pages[slot] = pages
        self.table[slot, :] = NULL_PAGE
        self.table[slot, : n_pages] = pages
        wrow[: n_pages] = [DUMP_PAGE] * len(shared) + fresh

    def free(self, slot: int) -> None:
        pages = self.slot_pages.pop(slot, None)
        if pages is None:
            return
        for pid in pages:
            self.pool.release(pid)
        self.table[slot, :] = NULL_PAGE
        self.paused[slot] = False
