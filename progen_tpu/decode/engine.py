"""Continuous-batching serving engine: slots, chunked decode, refill.

The batch-synchronous sampler (``decode/sampler.py``) is the wrong shape
for serving: every request in a batch waits for the slowest one, and a
new request waits for the whole batch to drain.  This engine serves a
request QUEUE through a fixed set of SLOTS (vLLM/Ragged-Paged-Attention
style, PAPERS.md), with all device programs compiled once:

* **slots** — a fixed-size batch of per-slot state (sequence row, decode
  caches, position, done flag, RNG key, top-k/temperature).  Slots are
  independent: the decode step takes a ``(S,)`` position VECTOR
  (``ProGenDecodeStep``), so slot 3 can be at position 900 while slot 4
  is at position 12;
* **chunked decode** — ``chunk_size`` single-token steps per device
  program (one compile; position/done are data, not shape).  Rows that
  finish mid-chunk stop advancing; the host sees the done-mask between
  chunks, so cost is bounded by emitted tokens plus at most one chunk of
  slack per row;
* **refill** — between chunks, finished slots are harvested (completion
  callbacks fire) and refilled from the queue via the one-pass parallel
  prefill (``decode/prefill.py``): queued primes are padded into
  ``(R, P_pad)`` ragged batches of ``R = admit_rows`` rows (``P_pad``
  bucketed to ``window · 2^k`` so admission compiles O(log) programs,
  then cached), each prefilled in ONE forward and gathered into the
  free slots while live slots' state rides through untouched.

A family that GENERATES BY DIFFUSION OVER BLOCKS (it states a
``block_length``, ``decode/family.py``) gets another chunk body
(``_block_chunk_impl``) and another prefill half of admission
(``_block_prefill_impl``), chosen from that statement and from nothing
else: a scan step is one forward a slot, a block of mask tokens is denoised
a few positions a forward under the family's remasking rule and then
committed — entered into ``seq`` by the forward that fills its last mask,
written to the cache by the forward that opens the next block, which
carries it in front of the block in progress —, ``pos``, ``done``,
``stop``, end of sequence and the harvest move by finished blocks, and
admission hands over a prime whose last ``P
mod B`` tokens open the first block instead of a sampled first token
(docs/SERVING.md, "Generation by blocks").

Two device programs serve every mode: the decode chunk (one of the two
bodies above) and admission (= prefill ∘ merge, the two halves
disaggregated serving runs apart).  Where a slot's cache rows live —
in the slot, or its gate rows in a page pool — is a CACHE LAYOUT
(``decode/paging.py``) chosen at construction; the programs and the one
place / undo pair of admission take what differs from it.

Determinism: each request carries its own seed; a request's token
trajectory depends only on (params, prime, seed, sampling knobs), never
on which slot it lands in or what else is in flight — asserted by
``tests/test_serving.py``.

Mesh-aware: pass ``mesh``/``strategies``/``params_shardings`` and the
engine runs SPMD with params left in their training shardings and
tp-sharded caches (``_constrain_caches``), same as the samplers.

EOS convention: primes are served verbatim (no BOS prepend); generation
stops at the first sampled pad/EOS token (id 0) or after
``max_new_tokens``.  The reference's "second zero" truncation is a
sampler-level concern; a serving request's prime is explicit.

Disaggregated mode (``disagg=True``, docs/SERVING.md §6) splits the step
into an explicit PREFILL stage (a worker program per bucket producing
cache HANDLES into a bounded handoff queue, ``decode/handoff.py``) and a
DECODE stage that admits from the queue via a donating merge program —
decode chunks dispatch BEFORE the round's prefill, so a long prefill
never stalls in-flight decode.

Robustness (docs/RESILIENCE.md): every serving phase runs behind a named
fault-injection point (``serve.submit`` / ``serve.admit`` /
``serve.prefill`` / ``serve.decode_chunk`` / ``serve.harvest`` /
``serve.page_alloc``, plus ``serve.handoff`` for the disaggregated
merge).  Because each phase is FUNCTIONAL — state in, state out,
``self.state`` replaced only on success — a transient fault is
contained by re-running the failed dispatch in place; a fatal fault sheds
only the requests whose work was lost, as typed completions
(``FAILED_FAULT``) rather than exceptions.  Requests carry optional
deadlines (``deadline``/``ttl`` → ``SHED_DEADLINE``), admission is
bounded (``max_queue`` → ``SHED_QUEUE_FULL``), and the lifecycle is
crash-safe: ``snapshot()`` persists host-side request state only (prompt,
sampling params, seeds — never device caches), and ``restore()`` +
seed-determinism replays in-flight requests token-identically
(:func:`run_with_restarts`).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from collections import defaultdict, deque
from functools import partial, wraps
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.observe import compiles as _compiles
from progen_tpu.observe import metrics as _metrics
from progen_tpu.observe import trace as _obs_trace
from progen_tpu.observe.robustness import RobustnessCounters
from progen_tpu.resilience import faults
from progen_tpu.resilience.retry import RetryError, default_classifier
from progen_tpu.resilience.watchdog import Watchdog
from progen_tpu.decode.family import UnsupportedFamilyMode, family_for
from progen_tpu.decode.paging import PagedGates, SlotCaches, pages_for_span
from progen_tpu.decode.handoff import Handle, HandoffQueue
from progen_tpu.decode.qos import QoSQueue
from progen_tpu.decode.prefill import _constrain_caches, mesh_trace_ctx
from progen_tpu.decode.sampler import (
    confident_positions,
    gumbel_topk_sample_batched,
    gumbel_topk_sample_with_confidence,
    split_keys_batched,
    transfer_counts,
)
from progen_tpu.models.progen import ProGenConfig
from progen_tpu.ops.lowering import record_lowerings
from progen_tpu.ops.row_write import write_rows

EOS_ID = 0

# typed Completion.status values — sheds are COMPLETIONS, not exceptions,
# so callbacks/benchmarks see every request exactly once either way
STATUS_OK = "ok"
SHED_QUEUE_FULL = "shed_queue_full"
SHED_DEADLINE = "shed_deadline"
FAILED_FAULT = "failed_fault"
DRAIN_TIMEOUT = "drain_timeout"

# consecutive rounds a phase may defer (fatal-fault containment) before
# the engine concludes the fault is permanent and gives up
_MAX_DEFER_STREAK = 16

# slots per row of the admission program: admission prefills the
# requests it admits, ``num_slots // SLOTS_PER_ADMIT_ROW`` (at least one)
# at a time, not every slot.  The group a step brings grows with the
# slots (slots x chunk_size / generated length finish per chunk), a row of
# padding costs a whole prime's prefill and another run of the program a
# fixed pass over the state; PERF.md section 6 (PR 26) has the sweep on
# the chip: 4 rows at 64 slots, 1 at 16
SLOTS_PER_ADMIT_ROW = 16

# A step STOOD STILL (incident ``serve.slow_step``) when its host self time,
# the gap before it or one of its closed stages is over SLOW_FACTOR times the
# engine's own running mean of that quantity AND SLOW_FLOOR_S above it.  A
# quantity is judged against its own REGIME: the chunk program by the power
# of two at or above its rows in flight (its cache reads follow the live
# rows and their context: a full chunk of 128 rows takes 2.3 times a probe's
# near-empty one, within a bucket a chunk stays within a third of the mean
# on the cells' traffic), an admission group by the padded lengths of its
# runs, the host self time by the power of two at or above the admission
# runs the step built (each costs the host its arrays and its mask: the
# step that fills 128 slots in 16 runs takes 74 ms of host time where a
# step of one run takes 8).  The factor: inside a regime a program takes
# the same time every run, so twice the mean is no ordinary run.  The
# floor: host self time and gaps are a few ms with a harvest ten times the
# rest, so a multiple alone would file one incident a harvest; 50 ms is a
# seventh of what a cell's rate may lose in its window (1 % of 35 s) and
# twice the shortest step the cells run, so an incident names seconds that
# can show end to end and a warmed engine files none.  A mean judges
# nothing before it has SLOW_MIN_SAMPLES observations, and the same number
# of refusals IN A ROW is what it takes for a mean to call them its regime:
# a stall does not come back eight times running, a longer context or a
# busier host does, so those eight are filed and the mean starts over from
# them.
SLOW_FACTOR = 2.0
SLOW_FLOOR_S = 0.050
SLOW_MIN_SAMPLES = 8
# step records in ``status()``: an admitting step and the chunk steps around
# it, on one screen of /statusz; /tracez serves the newest 512 of the log
LAST_STEPS = 8


def _bucket(n: int) -> int:
    """The power of two at or above ``n`` (0 for none): a regime's key."""
    return 1 << (n - 1).bit_length() if n > 0 else 0


class _RegimeMean:
    """Running mean of one quantity in one regime, and the rule above."""

    __slots__ = ("n", "mean", "refused", "refused_sum")

    def __init__(self):
        self.n = 0
        self.mean = 0.0
        self.refused = 0        # slow observations in a row, and their sum
        self.refused_sum = 0.0

    def observe(self, v: float) -> float:
        """Seconds of ``v`` over the mean where ``v`` is slow, else 0.  A
        slow ``v`` stays out of the mean, unless it is the last of
        SLOW_MIN_SAMPLES in a row: then they are the mean."""
        over = v - self.mean
        if (self.n >= SLOW_MIN_SAMPLES and over >= SLOW_FLOOR_S
                and v > SLOW_FACTOR * self.mean):
            self.refused += 1
            self.refused_sum += v
            if self.refused == SLOW_MIN_SAMPLES:
                self.n = self.refused
                self.mean = self.refused_sum / self.n
                self.refused, self.refused_sum = 0, 0.0
            return over
        self.refused, self.refused_sum = 0, 0.0
        self.n += 1
        self.mean += (v - self.mean) / self.n
        return 0.0


# what a slot of a family that generates by blocks holds beside the rest
# (``_block_chunk_impl``): where the block in progress starts, its tokens,
# the denoise forwards it has had, for each position of the row the denoise
# forward that filled it (-1: a prime token, or not filled yet), and the
# block finished last where no forward has written its keys yet
_BLOCK_STATE = ("cursor", "block", "dstep", "fill", "pending",
                "has_pending")
# the block step's counters, summed on the device beside the family's
_DIFFUSION_STATS = ("diffusion.forwards", "diffusion.commit_forwards",
                    "diffusion.tokens_committed", "diffusion.tokens_dropped",
                    "diffusion.positions_kept")


@jax.jit
def _clear_rows(active, freed):
    """The slots' ``active`` flags with the ``freed`` ones cleared."""
    return active & ~freed


def _host_fetch(tree):
    """Batched device→host fetch that also handles PROCESS-SPANNING
    arrays (tp-group engines, docs/SERVING.md §13): ``jax.device_get``
    refuses an array with non-addressable shards, but every host-read
    engine output is replicated across the group — the local shard IS
    the global value.  A non-replicated process-spanning leaf falls
    back to a collective re-gather, which is safe because every group
    member runs the same fetch at the same point in lockstep."""

    def _one(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            if x.sharding.is_fully_replicated:
                return np.asarray(x.addressable_data(0))
            from jax.experimental import multihost_utils

            return multihost_utils.process_allgather(x, tiled=True)
        return x

    return jax.device_get(jax.tree_util.tree_map(_one, tree))


@dataclasses.dataclass
class _Placement:
    """Requests booked into slots for one admission dispatch, until it is
    dispatched: what ``_unplace`` takes back if it never is."""

    batch: list             # (slot, request), in booking order
    src: np.ndarray         # (S,) slot -> handle row
    mask: np.ndarray        # (S,) the slots booked
    operands: tuple         # the layout's merge operands (write table)
    prefixes: list          # prefix registrations deferred to the dispatch


class _ContainedFault(Exception):
    """Internal: a phase failed NON-transiently; the caller sheds the
    affected requests per its containment rule.  ``__cause__`` is the
    underlying fault."""

    def __init__(self, point: str):
        super().__init__(f"non-transient fault at {point}")
        self.point = point


@dataclasses.dataclass
class Request:
    """One generation request.

    ``tokens``: the prime, served verbatim (encode + add BOS upstream if
    desired); must be non-empty and leave room for at least one new
    token.  ``top_k=None`` disables top-k; ``temperature=0`` is greedy.

    SLO knobs: ``deadline`` is an absolute ``time.perf_counter()``
    instant, ``ttl`` a budget in seconds from ``submit_time``
    (``deadline`` wins when both are set).  Past it the request is shed
    with a ``SHED_DEADLINE`` completion — queued requests before they
    cost a prefill, in-flight ones mid-decode with their partial tokens.

    Workload knobs: ``logit_mask`` is an optional ``(G, V)`` bool array
    (``G ≤ max_new_tokens``) constraining generated position ``g`` to
    its true entries (``workloads/infill.ScaffoldSpec`` builds these;
    positions past ``G`` are unconstrained), or a ``(V,)`` array that
    constrains EVERY generated position alike — the one form a family
    whose slot state holds a mask row per slot, not per position, takes
    (``decode/family.py``); ``tenant`` selects a row of
    the engine's LoRA adapter bank (0 = base model; nonzero requires the
    engine to hold a bank).

    QoS knobs (docs/SERVING.md §10): ``priority`` picks the scheduling
    class (higher = more urgent; classes are served strictly in order
    and a high-priority arrival may PREEMPT a lower-priority in-flight
    request — the replay is bit-exact); within a class, tenants share
    by weight (``qos_weights``) and deadlines order EDF.
    """

    uid: Any
    tokens: Sequence[int]
    max_new_tokens: int = 128
    top_k: int | None = None
    temperature: float = 1.0
    seed: int = 0
    deadline: float | None = None
    ttl: float | None = None
    on_complete: Callable[["Completion"], None] | None = None
    submit_time: float = dataclasses.field(default_factory=time.perf_counter)
    logit_mask: Any = None
    tenant: int = 0
    # request class for routing ("generate" | "embed") — the cluster
    # frontend sets "embed" via submit_embed(); in-process callers use
    # the engine's submit()/submit_embed() methods directly
    workload: str = "generate"
    priority: int = 0
    # of a family that generates by blocks: report, for each generated
    # token, the denoise forward of its block that filled it
    # (``Completion.fill_steps``)
    record_fill_steps: bool = False


@dataclasses.dataclass
class Completion:
    """A finished request: ``tokens`` is the generated tail only (EOS
    included when the model emitted one).

    ``status`` is ``STATUS_OK`` for served requests (``finish_reason`` is
    ``"eos"``/``"length"``) or a shed type (``SHED_QUEUE_FULL`` /
    ``SHED_DEADLINE`` / ``FAILED_FAULT``, mirrored into
    ``finish_reason``) — load shedding produces a COMPLETION, never an
    exception, so every submitted request is answered exactly once.
    """

    uid: Any
    prime: np.ndarray
    tokens: np.ndarray
    finish_reason: str  # "eos" | "length" | "embed" | shed status
    submit_time: float
    finish_time: float
    status: str = STATUS_OK
    embedding: np.ndarray | None = None  # (D,) f32 for embed requests
    # weight generation that primed the request — the serving control
    # plane bumps this on swap_weights; 0 for a never-swapped engine
    generation: int = 0
    # instant the request's FIRST generated token was known to exist: the
    # return of the first host fetch of the slot flags after its admission
    # dispatch (a dispatch returns before the device has run) — None for
    # sheds and embed completions.  The cluster rewrites this onto the
    # driver clock so ``ttft`` is end-to-end (queue + prefill + transport
    # + merge) fleet-wide.
    first_token_time: float | None = None
    # latency as measured on the WORKER's clock (submit→finish inside
    # the remote engine); 0.0 for local completions, where ``latency``
    # already is that number.  The difference vs ``latency`` is the
    # transport + merge overhead the fleet adds on top of the engine.
    worker_latency: float = 0.0
    # instant the request left the queue (the start of the admission
    # round that took it; the earliest across evict/replay) — None for a
    # request shed before any admission
    admit_time: float | None = None
    # ``(len(tokens),)`` small integers where the request asked
    # (``Request.record_fill_steps``): the denoise forward of its block, 0
    # the first, at which each token was kept
    fill_steps: np.ndarray | None = None

    @property
    def latency(self) -> float:
        return self.finish_time - self.submit_time

    @property
    def queue_wait(self) -> float | None:
        """Seconds between submission and leaving the queue."""
        if self.admit_time is None:
            return None
        return self.admit_time - self.submit_time

    @property
    def ttft(self) -> float | None:
        """Time to first token, or None when it was never produced."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def ok(self) -> bool:
        return self.status == STATUS_OK


class ServingEngine:
    """Slot-based continuous-batching engine over a fixed device batch.

    ``num_slots`` is the max concurrent requests; ``chunk_size`` the
    decode steps per device program; ``max_len`` the sequence budget per
    slot (prime + generated, ≤ ``config.seq_len``).

    **Paged mode** (``paged=True``): the per-slot SGU gate cache — the
    one ``max_len``-sized buffer, i.e. this architecture's pageable "KV"
    — moves into a global page pool (``decode/paging.py``): pages are
    allocated on demand as positions advance, freed (refcounted) at
    completion, and shared across requests with a common prompt prefix.
    Admission is the same program as in the fixed-slot engine (the merge
    scatters the admitted rows' gates into their pages), gated by free
    PAGES as well as free slots; when the pool
    runs dry mid-decode, starved slots are PAUSED (their rows freeze —
    position, key and sequence do not advance, so the trajectory is
    delayed, never altered) and, if every live slot is starved, the most
    recently admitted one is evicted back to the queue head (restart
    preemption: determinism means replaying it reproduces the identical
    prefix of tokens).  Greedy outputs are token-for-token identical to
    the fixed-slot engine — the XLA fallback contraction is bit-matched
    to the dense decode path (``ops/pallas_paged_attention.py``).

    ``num_pages`` counts pool pages incl. the 2 reserved ones (default:
    full budget — every slot can reach ``max_len``); ``paged_impl`` picks
    the ragged kernel (``"pallas"``) or the gather fallback (``"xla"``).

    Robustness knobs: ``max_queue`` bounds admission (``None`` =
    unbounded; overflow sheds the incoming request, or the OLDEST queued
    one under ``shed_policy="shed-oldest"``); ``fault_retries`` is the
    in-place retries per phase for transient faults (exhaustion escapes
    as :class:`RetryError` for the restart-and-replay loop);
    ``watchdog`` receives a heartbeat per ``step()`` and is paused around
    first-time compiles.  Counters live in ``self.robust``
    (:func:`robustness_counters` merges everything).

    QoS knobs (docs/SERVING.md §10): admission runs through a
    priority / weighted-fair / EDF scheduling queue
    (``decode/qos.py``) — ``qos_weights`` maps tenant -> relative share
    (missing tenants weigh 1.0; nonzero-weight tenants are
    starvation-free).  A high-priority arrival blocked on slots or
    pages PREEMPTS the lowest-priority in-flight request
    (:meth:`_maybe_preempt`): the victim replays from scratch
    bit-exactly, so preemption trades latency, never tokens.  Under
    ``shed_policy="shed-oldest"`` the victim is the lowest class's
    oldest request, never a strictly higher class than the newcomer.

    **Disaggregated serving** (``disagg=True``): prefill runs as its own
    worker program over FIFO-prefix batches of up to ``prefill_batch``
    requests sharing a bucket, producing cache handles into a bounded
    queue of ``handoff_depth`` (``decode/handoff.py``); the decode stage
    admits by merging handles into free slots with the handle DONATED
    (caches move, not copy).  ``step()`` dispatches the decode chunk
    before the round's prefill, so long prefills stop stalling in-flight
    decode.
    """

    def __init__(self, config: ProGenConfig, params, *,
                 policy: Policy | None = None, num_slots: int = 8,
                 chunk_size: int = 32, max_len: int | None = None,
                 mesh: Mesh | None = None,
                 strategies: Sequence[str] = ("dp",),
                 params_shardings=None,
                 paged: bool = False, page_size: int = 16,
                 num_pages: int | None = None, paged_impl: str = "xla",
                 prefix_cache: bool = True,
                 max_queue: int | None = None, shed_policy: str = "reject",
                 fault_retries: int = 3, watchdog: Watchdog | None = None,
                 disagg: bool = False, prefill_batch: int | None = None,
                 handoff_depth: int = 2, remote_prefill: bool = False,
                 lora_bank=None, qos_weights: dict | None = None,
                 quantize: str | None = None):
        self.config = config
        self.policy = policy or make_policy()
        # the model behind the seam (decode/family.py): the plain dense
        # path calls nothing else of it, and a mode the family does not
        # state is refused here, by name and with no fallback
        self._weights_mode = "int8" if quantize else "bf16"
        self.family = family_for(config, self.policy, self._weights_mode)
        asked = {"paged": paged, "disagg": disagg,
                 "lora_bank": lora_bank is not None,
                 "quantize": quantize is not None, "mesh": mesh is not None}
        for mode, on in asked.items():
            if on and mode not in self.family.modes:
                raise UnsupportedFamilyMode(
                    f"the {self.family.name} family is served by the plain "
                    f"dense path only: {mode} is not supported for it")
        self.num_slots = num_slots
        self.admit_rows = max(1, num_slots // SLOTS_PER_ADMIT_ROW)
        self.chunk_size = chunk_size
        self.max_len = min(max_len or config.seq_len, config.seq_len)
        # how the family generates: None a token a row a step, else the
        # length of the blocks it denoises and commits (decode/family.py)
        self.block_length = self.family.block_length
        if self.block_length and self.max_len % self.block_length:
            raise ValueError(
                f"max_len {self.max_len} is not a whole number of the "
                f"{self.family.name} family's blocks of {self.block_length}")
        self.mesh = mesh
        self.strategies = tuple(strategies)
        # priority / weighted-fair / EDF scheduling queue — with default
        # weights, a single tenant and no deadlines it is exact FIFO
        self._queue = QoSQueue(weights=qos_weights)
        self.qos_weights = dict(qos_weights or {})
        self._qos_gauge_keys: set = set()
        self._inflight: dict[int, Request] = {}  # slot -> request
        # shared-prefix forking (submit_fork): leader uid -> followers
        # held back until the leader's prefix pages are published, plus
        # first-token instants for the TTFT field on completions
        self._fork_wait: dict[Any, list[Request]] = {}
        self.fork_groups = 0
        self._ttft: dict[Any, float] = {}
        # request timeline, host clock: when each uid left the queue
        self._admitted: dict[Any, float] = {}
        # admission recency (slot -> monotone seq) across ALL modes: the
        # preemption and pool-starvation paths evict youngest-first
        self._admit_seq = 0
        self._admit_order: dict[int, int] = {}
        self.completions: list[Completion] = []
        self.chunks_run = 0
        if shed_policy not in ("reject", "shed-oldest"):
            raise ValueError(f"shed_policy {shed_policy!r}: want 'reject' "
                             f"or 'shed-oldest'")
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.fault_retries = fault_retries
        self._watchdog = watchdog
        self.robust = RobustnessCounters()
        self._pending: list[Completion] = []   # sheds awaiting step() return
        self._draining = False
        self._aot: dict[tuple, Any] = {}       # AOT-compiled executables
        self._compiled_keys: set[tuple] = set()
        self._defer_streak: dict[str, int] = {}
        # wall per stage, from the start of its dispatch to the return of
        # the host fetch that shows its work done (docs/OBSERVABILITY.md
        # lists the one stage that is dispatch-only) — multi-process bench
        # records prove prefill wall LEAVES the decode process (its
        # prefill_s stays 0.0)
        self.stage_seconds = {"prefill_s": 0.0, "merge_s": 0.0,
                              "decode_chunk_s": 0.0, "embed_s": 0.0}
        # the same durations feed the shared metrics registry's per-stage
        # histograms; spans go through the process tracer (profiler
        # annotation always, ring when enabled)
        self._tracer = _obs_trace.get_tracer()
        registry = _metrics.get_registry()
        self._stage_hist = {
            "prefill_s": registry.histogram("engine.prefill_s"),
            "merge_s": registry.histogram("engine.merge_s"),
            "decode_chunk_s": registry.histogram("engine.decode_chunk_s"),
            "embed_s": registry.histogram("engine.embed_s"),
        }
        # requests in each run of the admission program (a count,
        # not a latency: mean fill = sum / count, of ``admit_rows``)
        self._admit_rows_hist = registry.histogram("engine.admit_rows")
        self._queue_wait_hist = registry.histogram("engine.queue_wait_s")
        self._ttft_hist = registry.histogram("engine.ttft_s")
        self._step_host_hist = registry.histogram("engine.step_host_s")
        # token slots an admission run computes and the real prime tokens
        # among them (host integers; real / slots = the share of a prefill
        # that is not padding), and the rows a chunk was dispatched with
        self._prefill_real = registry.counter("engine.prefill_tokens_real")
        self._prefill_slots = registry.counter("engine.prefill_token_slots")
        self._chunk_rows_hist = registry.histogram("engine.chunk_rows")
        # compiles and collector pauses come from the process's listeners
        # (observe/compiles.py); a step compares their totals at its two
        # ends.  ``engine.compiles_in_step`` reads 0 for ever once warm
        _compiles.install()
        self._xla_compiles = registry.counter("xla.compiles")
        self._gc_pauses = registry.histogram("host.gc_pause_s")
        self._steps = registry.counter("engine.steps")
        self._compiles_in_step = registry.counter("engine.compiles_in_step")
        # the slow-step rule's means (SLOW_FACTOR above), one a regime: the
        # gap between steps; host self time by the bucket of the step's
        # admission runs; each stage program's time — the chunk program by
        # its rows' bucket, an admission group by the padded lengths of its
        # runs
        self._mean_gap = _RegimeMean()
        self._mean_host: dict[int, _RegimeMean] = defaultdict(_RegimeMean)
        self._mean_stage: dict[Any, _RegimeMean] = defaultdict(_RegimeMean)
        # what the step() in progress leaves for its record (_judge_step):
        # (program, seconds) of the stages it closed, None outside a step (a
        # prefill worker runs rounds and never steps); (requests, real
        # tokens, token slots) of each admission run; the rows its chunk was
        # dispatched with; the requests it harvested; the return of its
        # last flags fetch
        self._step_stages: list[tuple] | None = None
        self._step_admits: list[tuple] = []
        self._step_chunk_rows = 0
        self._step_finished = 0
        self._step_done: float | None = None
        self._admit_pads: list[int] = []      # p_pad of each run in flight
        # return instant of the last step() that left work behind, else
        # None: a caller that sleeps with nothing to do is not a stall
        self._last_return: float | None = None
        # stages dispatched and not yet known done, ``(stage, kind, t0,
        # requests)``: a dispatch returns before the device has run, so
        # the next fetch of the slot flags closes them (_close_stages).
        # ``kind`` is "admit" (the requests' first tokens exist once it
        # has run) or "chunk"
        self._open_stages: list[tuple] = []
        self._step_no = 0       # step() calls so far: ``step=`` on spans
        self._step_wait = 0.0   # seconds of this step() inside that fetch

        if params_shardings is not None:
            params = jax.device_put(params, {"params": params_shardings})

        # opt-in quantized serving: "weights" re-types every dense kernel
        # and SGU spatial weight to int8 (f32 scales in a parallel
        # "qscale" collection); "weights+pages" additionally stores the
        # paged SGU gate cache as 8-bit pages.  None (default) is the
        # unchanged bit-gated full-precision engine.
        if quantize not in (None, "weights", "weights+pages"):
            raise ValueError(f"quantize {quantize!r}: want None, "
                             f"'weights' or 'weights+pages'")
        if quantize == "weights+pages" and not paged:
            raise ValueError("quantize='weights+pages' requires paged=True "
                             "(the 8-bit gate format is a page format)")
        self.quantize = quantize
        self.gate_dtype = "int8" if quantize == "weights+pages" else "bf16"
        if quantize:
            params = self._quantize_variables(params)

        self.disagg = disagg
        self.lora = lora_bank is not None
        if self.lora:
            # the adapter gather composes with dense, paged and
            # disaggregated decode (the handle carries a ``tenant`` leaf
            # in its state tree)
            from progen_tpu.workloads.lora import validate_lora_bank

            self.num_tenants = validate_lora_bank(config, lora_bank)
            lora_bank = jax.tree.map(jnp.asarray, lora_bank)
            # the bank rides the params pytree so every AOT program takes
            # it as a real argument (hot-swappable without recompiles)
            self._params = {"base": params, "adapters": lora_bank}
        else:
            self.num_tenants = 1
            self._params = params
        # weight generation: bumped by reload_weights(); completions are
        # stamped with the generation current when they finish (in the
        # multi-process cluster the driver stamps from router bookkeeping
        # instead — a uid's generation is the one that PRIMED it)
        self.generation = 0

        self._trace_ctx = mesh_trace_ctx(mesh, self.strategies)

        # where a slot's cache rows live (decode/paging.py), chosen once:
        # the programs and the admission routines below ask the layout for
        # whatever differs between slots and pages
        self.paged = paged
        self.paged_impl = paged_impl if paged else None
        if paged:
            self._layout = PagedGates(
                config, self.policy, num_slots=num_slots,
                max_len=self.max_len, page_size=page_size,
                num_pages=num_pages, impl=paged_impl,
                weights=self._weights_mode, gate_dtype=self.gate_dtype,
                prefix_caching=prefix_cache)
            self.page_size = page_size
            self.evictions = 0
            self.pause_events = 0
        else:
            self._layout = SlotCaches(self.family)
        self._pool = self._layout.pool
        # op -> the lowering its calls took in the programs traced so far
        self.lowerings: dict[str, str] = {}
        # the same per program ("chunk", "admit"): the experts' product is
        # in both and takes another lowering in each
        self.program_lowerings: dict[str, dict[str, str]] = {}
        self._decode_chunk = self._jit_noting(self._chunk_impl(), "chunk")
        self._admit = self._jit_noting(self._admit_impl, "admit")
        self.model_stats: dict = {}     # the family's counters as last fetched
        self.model_gauges: dict = {}    # ... as published: name -> float
        if remote_prefill and not disagg:
            raise ValueError("remote_prefill requires disagg=True")
        self.remote_prefill = remote_prefill
        if disagg:
            self.prefill_batch = max(1, min(prefill_batch or num_slots,
                                            num_slots))
            self._handoff = HandoffQueue(handoff_depth)
            self._prefill_worker = jax.jit(self._prefill_worker_impl)
            # the handle is donated: its cache buffers are dead after the
            # merge, so XLA may move them into the slot state
            self._merge = jax.jit(self._merge_impl, donate_argnums=(1,))
        else:
            self._handoff = None
        # embeddings endpoint: a separate request class served by a
        # prefill-shaped program — consumes no decode slots, batches per
        # prime bucket, AOT-warmable like admission
        self._embed_queue: deque[Request] = deque()
        self.embed_batch = num_slots
        self._embedder = self.family.embedder(mesh, self.strategies)
        self.state = self._init_state()

    # ---------------------------------------------------------------- state

    def _init_state(self) -> dict:
        s, L = self.num_slots, self.max_len
        with self._trace_ctx():
            caches = self._layout.init_caches(s, L)
            caches = _constrain_caches(caches, self.mesh, self.strategies)
        keys = jax.vmap(jax.random.key)(jnp.zeros((s,), jnp.uint32))
        state = {
            "seq": jnp.zeros((s, L), jnp.int32),
            "caches": caches,
            "pos": jnp.zeros((s,), jnp.int32),     # index of newest token
            "start": jnp.zeros((s,), jnp.int32),   # prime length
            "stop": jnp.zeros((s,), jnp.int32),    # start + max_new (≤ L)
            "active": jnp.zeros((s,), bool),
            "done": jnp.zeros((s,), bool),
            "keys": jax.random.key_data(keys),     # raw uint32 key data
            "top_k": jnp.zeros((s,), jnp.int32),   # 0 = disabled
            "temp": jnp.ones((s,), jnp.float32),
            # per-slot per-position logit mask, indexed by WRITE position;
            # all-true rows are bit-identical to no masking at all, so the
            # plain generate path pays only the (S, L, V)-bool gather.  A
            # family without position masks holds one (V,) row per slot
            "lmask": jnp.ones(self._lmask_shape(s), bool),
        }
        stats = self.family.init_stats()
        if self.block_length:
            b = self.block_length
            state.update(
                cursor=jnp.zeros((s,), jnp.int32),
                block=jnp.full((s, b), self.family.mask_token_id, jnp.int32),
                dstep=jnp.zeros((s,), jnp.int32),
                fill=jnp.full((s, L), -1, jnp.int8),
                pending=jnp.full((s, b), self.family.mask_token_id,
                                 jnp.int32),
                has_pending=jnp.zeros((s,), bool))
            stats = {**stats, **self._diffusion_zeros()}
        if stats:
            # the family's device-side counters: summed by its programs,
            # read with the flags fetch the harvest makes anyway
            state["stats"] = stats
        if self.lora:
            state["tenant"] = jnp.zeros((s,), jnp.int32)
        return state

    def _diffusion_zeros(self) -> dict:
        """The block step's counters at zero: scalars, and the positions
        kept by denoise forward."""
        zeros = {k: jnp.zeros((), jnp.float32) for k in _DIFFUSION_STATS}
        zeros["diffusion.positions_kept"] = jnp.zeros(
            (self.family.denoising_steps,), jnp.float32)
        return zeros

    def _lmask_shape(self, rows: int) -> tuple:
        if self.family.position_masks:
            return (rows, self.max_len, self.family.vocab)
        return (rows, self.family.vocab)

    # ------------------------------------------------------ fault containment

    def _span(self, name: str, **args):
        """The one span call: profiler annotation and (when enabled) ring,
        tagged with the ``step()`` it belongs to."""
        return self._tracer.span(name, step=self._step_no, **args)

    def _record_stage(self, stage: str, dt: float, program=None) -> None:
        """``program`` names what ran, for the slow-step rule: the time of
        a stage is judged against the mean of its own program."""
        self.stage_seconds[stage] += dt
        self._stage_hist[stage].observe(dt)
        if program is not None and self._step_stages is not None:
            self._step_stages.append((program, dt))

    def _close_stages(self, now: float) -> None:
        """The flags fetch returned at ``now``: every dispatched stage has
        run.  Each gets the wall from its dispatch to the next stage's (the
        device runs them in order), the last one to ``now``; the ring gets
        ``serve.<kind>_work`` with those instants, and the requests of an
        admission their first-token instant."""
        stages, self._open_stages = self._open_stages, []
        ends = [t0 for _, _, t0, _ in stages[1:]] + [now]
        for (stage, kind, t0, batch), end in zip(stages, ends):
            program = None
            if stage == "decode_chunk_s":
                program = "chunk"
            elif stage == "prefill_s":
                program = ("admit", *self._admit_pads)
                self._admit_pads.clear()
            self._record_stage(stage, end - t0, program)
            if self._tracer.enabled:
                self._tracer.add(f"serve.{kind}_work", t0, end - t0,
                                 step=self._step_no, stage=stage,
                                 uids=[r.uid for r in batch])
            if kind != "admit":
                continue
            for r in batch:
                # the earliest stamp survives an evict/replay round trip
                if r.uid not in self._ttft:
                    self._ttft[r.uid] = now
                    self._ttft_hist.observe(now - r.submit_time)

    def _note_admitted(self, batch, now: float) -> None:
        """``batch`` left the queue at ``now`` (earliest wins across
        evict/replay, like ``_ttft``)."""
        for r in batch:
            if r.uid not in self._admitted:
                self._admitted[r.uid] = now
                self._queue_wait_hist.observe(now - r.submit_time)

    def _guard(self, point: str, fn: Callable | None = None, *args,
               key: tuple | None = None):
        """Run ``faults.inject(point)`` + ``fn(*args)`` with transient
        faults retried in place (no backoff — the retried work is an
        in-process dispatch of a pure function, so re-running it is both
        safe and deterministic).  Non-transient faults raise
        :class:`_ContainedFault` for the caller's shed rule; transient
        exhaustion raises :class:`RetryError`, the signal the
        restart-and-replay loop (:func:`run_with_restarts`) catches.

        ``key`` names the compiled program ``fn`` dispatches: its first
        run pauses the watchdog (cold compiles are legitimately slow).
        """
        last: BaseException | None = None
        for attempt in range(max(0, self.fault_retries) + 1):
            try:
                faults.inject(point)
                if fn is None:
                    out = None
                elif (self._watchdog is not None and key is not None
                        and key not in self._compiled_keys):
                    with self._watchdog.paused():
                        out = fn(*args)
                else:
                    out = fn(*args)
                if key is not None:
                    self._compiled_keys.add(key)
                if attempt:
                    self.robust.faults_contained += attempt
                return out
            except Exception as e:
                if not default_classifier(e):
                    raise _ContainedFault(point) from e
                last = e
        raise RetryError(
            f"{point}: transient fault persisted through "
            f"{max(0, self.fault_retries) + 1} attempt(s)",
            attempts=max(0, self.fault_retries) + 1, elapsed=0.0,
        ) from last

    def _defer(self, phase: str, cause: BaseException) -> None:
        """Record one deferred round of ``phase`` (fatal-fault
        containment: skip the phase this step, retry next step).  A
        streak past ``_MAX_DEFER_STREAK`` means the fault is permanent —
        give up loudly instead of spinning."""
        streak = self._defer_streak.get(phase, 0) + 1
        self._defer_streak[phase] = streak
        if streak > _MAX_DEFER_STREAK:
            raise RuntimeError(
                f"serve.{phase} failed {streak} consecutive rounds — "
                f"fault is not transient and not shedding") from cause

    def _admit_call(self, p_pad, *args):
        """Dispatch the admission (prefill) program: the AOT executable
        for this prefill bucket when warmed, the jit wrapper otherwise."""
        fn = self._aot.get(("admit", p_pad), self._admit)
        return fn(self._params, self.state, *args)

    def _deactivate(self, slots) -> None:
        """Clear the ``active`` flag of ``slots`` in one dispatch (their
        requests finished, were shed, or went back to the queue)."""
        freed = np.zeros((self.num_slots,), bool)
        freed[list(slots)] = True
        fn = self._aot.get(("release",), _clear_rows)
        self.state = {**self.state,
                      "active": fn(self.state["active"], freed)}

    def _chunk_call(self, *args):
        fn = self._aot.get(("chunk",), self._decode_chunk)
        return fn(self._params, self.state, *args)

    def _embed_call(self, tokens, lengths):
        """Dispatch the embedding program for this prefill bucket (AOT
        executable when warmed).  Embeddings always run the BASE model —
        no sampling, no adapters, no slot state."""
        fn = self._aot.get(("embed", tokens.shape[1]), self._embedder)
        return fn(self._target_params(self._params), tokens, lengths)

    def _target_params(self, params):
        """The model's own weights: under LoRA ``self._params`` bundles the
        base tree and the adapter bank, otherwise it is the tree itself."""
        return params["base"] if self.lora else params

    def _adapters(self, params):
        """The stacked adapter bank when serving LoRA, else ``None`` (the
        model applies no delta and traces exactly as before)."""
        return params["adapters"] if self.lora else None

    @staticmethod
    def _quantize_variables(variables):
        """Re-type a full-precision variables dict for int8 serving:
        dense kernels and SGU spatial weights become int8 leaves (same
        tree structure, so shardings and AOT shapes carry over) and the
        per-channel f32 scales ride in a parallel ``qscale`` collection.
        LoRA adapter banks are NOT quantized — deltas stay full precision
        on top of the int8 base."""
        from progen_tpu.ops.quant import quantize_params

        qtree, scales = quantize_params(variables["params"])
        return {**variables, "params": qtree, "qscale": scales}

    def _activate_xla_fallback(self) -> None:
        """Degrade the paged decode step from the Pallas ragged kernel to
        its bit-identical XLA gather fallback (``ops/
        pallas_paged_attention.py``) — counted and logged, never fatal.
        Token streams are unaffected: the two impls are numerically
        matched, which is exactly why the fallback is safe mid-request.
        """
        self.robust.fallback_activations += 1
        self.paged_impl = "xla"
        self._layout.use_impl("xla")
        self._decode_chunk = self._jit_noting(self._chunk_impl(), "chunk")
        self._aot.pop(("chunk",), None)
        self._compiled_keys.discard(("chunk",))
        print("serving: pallas paged kernel failed; degraded to the "
              "bit-identical XLA fallback", flush=True)

    # ------------------------------------------------------------- decoding

    def _jit_noting(self, impl, program):
        """``jax.jit(impl)`` for a decode-chunk or admission program
        (``program``: ``"chunk"`` / ``"admit"``); tracing it notes which
        lowering each op that owns two took (``ops/lowering.py``) for
        ``status()``: the step's cache writes (``"row_write"``:
        ``"pallas"`` on a TPU, ``"scatter"`` elsewhere, both joined by
        ``+`` where the shapes split them), a latent attention's prefill
        and absorbed decode cores (``"mla_prefill"``, ``"mla_decode"``:
        ``"pallas"`` / ``"xla"``), a grouped-query attention's prefill core
        and one-query decode core (``"gqa_prefill"``, ``"gqa_decode"``: the
        same two), the core of a step of B queries a slot
        (``"gqa_block_decode"``: the same two) and the held experts'
        product (``"moe_experts"``: ``"pallas"`` / ``"pallas_grouped"`` /
        ``"pallas_sorted"`` / ``"xla"``, stated per program: it is in
        both, and an engine's admission programs, one a bucket, may differ
        — joined by ``+``), how an admission's sorted experts' terms reach
        their tokens (``"moe_combine"``, per program too) and
        the draw's k-th largest logit and a learned selection's k-th
        largest score (``"sample_kth"``, ``"dsa_kth"``: ``"xla"`` /
        ``"xla_tiled"``, per program too)."""

        @wraps(impl)
        def traced(*args):
            with record_lowerings() as chosen:
                out = impl(*args)
            took = {op: "+".join(sorted(paths))
                    for op, paths in chosen.items()}
            self.lowerings.update(took)
            # an engine traces one admission program a bucket, and an op
            # may take another lowering at another bucket's shapes: a
            # program's entry names all that its traces took
            mine = self.program_lowerings.setdefault(program, {})
            for op, paths in chosen.items():
                seen = paths | set(filter(None, mine.get(op, "").split("+")))
                mine[op] = "+".join(sorted(seen))
            return out

        return jax.jit(traced)

    def _chunk_impl(self):
        """The chunk program's body, chosen from what the family states
        about how it generates."""
        if self.block_length:
            return self._block_chunk_impl
        return self._decode_chunk_impl

    def _decode_chunk_impl(self, params, state, *operands):
        """``chunk_size`` single-token steps of every slot.  ``operands``
        are the cache layout's (the page table and the paused rows, for
        paged gates).  A row that is not live runs the step fully masked:
        sequence, position and key freeze, and its caches keep what the
        layout says an idle row keeps."""
        lay = self._layout
        with self._trace_ctx():
            state = {**state, "caches": _constrain_caches(
                state["caches"], self.mesh, self.strategies)}

            def body(st, _):
                with jax.named_scope("engine.advance"):
                    live = lay.live(st, operands)
                    pos = st["pos"]
                    tok = jnp.take_along_axis(st["seq"], pos[:, None],
                                              axis=1)[:, 0]
                logits, caches, stats = lay.step(
                    self._target_params(params), tok, pos, st["caches"],
                    live, self._adapters(params), st.get("tenant"),
                    operands)
                # the draw names itself (``sample.draw``)
                with jax.named_scope("engine.advance"):
                    caches = lay.idle_keeps(live, caches, st["caches"])
                    kd, sub = split_keys_batched(st["keys"])
                    writepos = jnp.clip(pos + 1, 0, self.max_len - 1)
                    # the infill mask row for the position this step WRITES;
                    # all-pass rows leave sampling bit-identical
                    mrow = jnp.take_along_axis(
                        st["lmask"], writepos[:, None, None], axis=1
                    )[:, 0] if self.family.position_masks else st["lmask"]
                    nxt = gumbel_topk_sample_batched(
                        sub, logits, st["top_k"], st["temp"],
                        mask=mrow).astype(jnp.int32)
                    cur = jnp.take_along_axis(st["seq"], writepos[:, None],
                                              axis=1)[:, 0]
                    val = jnp.where(live, nxt, cur)
                    seq = write_rows(st["seq"], val, writepos, axis=0)
                    new_pos = jnp.where(live, pos + 1, pos)
                    done = st["done"] | (live & (
                        (val == EOS_ID) | (new_pos + 1 >= st["stop"])))
                    # a slot's key advances only on its own live steps, so a
                    # request's trajectory is independent of its neighbours
                    # (and pausing delays it, never alters it)
                    new_keys = jnp.where(live[:, None], kd, st["keys"])
                    out = {**st, "seq": seq, "caches": caches, "pos": new_pos,
                           "done": done, "keys": new_keys}
                    if stats:
                        out["stats"] = jax.tree.map(jnp.add, st["stats"],
                                                    stats)
                return out, None

            state, _ = jax.lax.scan(body, state, None,
                                    length=self.chunk_size)
        return state

    def _block_chunk_impl(self, params, state, *operands):
        """``chunk_size`` forwards of every slot of a family that generates
        by diffusion over blocks (``decode/family.py``).  A slot holds the
        block in progress at ``cursor .. cursor + B - 1`` — tokens, and the
        mask token where none is kept yet — and, where ``has_pending``, the
        block it finished last (``pending``, at ``cursor - B .. cursor - 1``),
        whose keys no forward has written yet.  Every scan step is ONE
        forward of all ``S x 2B`` positions, and every live row's is a
        DENOISE forward:

        * a draw at every position of the block in progress from that
          position's own logits (the request's top-k, temperature and logit
          mask; the mask token is never allowed) with the drawn token's
          confidence; the masked positions the family's remasking rule
          picks keep their draw.  The block's own keys are not stored;
        * where a pending block rides, the same forward computes its keys
          and values, the block in progress sees them beside its own, and
          they are written at the pending block's rows: a block's commit
          costs no forward of its own;
        * where the forward fills the block's last mask, the block is
          FINISHED in the same step, with no forward: it enters ``seq``,
          the cursor moves on, a block of masks opens and the finished
          block becomes the slot's pending one.  ``pos`` — the newest token
          that counts — moves to the block's end, or to the first
          end-of-sequence token in it, or to ``stop - 1``: tokens after
          either are dropped and the row is done.  A row that is done keeps
          no pending block: nobody will read those keys.

        A row that is not live runs fully masked and keeps its state; the
        pending half of a row without a pending block is filler
        (``models/driver.py:block_step``).  A slot's key advances on its
        own live forwards only (each forward's B draws come from one split
        of it), so a request's tokens depend on neither its neighbours nor
        the step it was admitted at."""
        lay, fam = self._layout, self.family
        b, steps = fam.block_length, fam.denoising_steps
        mask_id = fam.mask_token_id
        per_step = jnp.asarray(transfer_counts(b, steps), jnp.int32)
        threshold = (fam.confidence_threshold
                     if fam.remasking == "low_confidence_dynamic" else None)
        s, at = self.num_slots, jnp.arange(b)
        col = jnp.arange(self.max_len)[None, :]
        f32 = jnp.float32

        def spread(block, p0):
            """``block (S, B)`` laid along the row at ``p0 ..``: ``(the
            values (S, L), where they lie (S, L))``."""
            off = col - p0[:, None]
            # B selects and no gather, which the chip runs row by row
            values = sum(jnp.where(off == j, block[:, j, None], 0)
                         for j in range(b))
            return values, (off >= 0) & (off < b)

        with self._trace_ctx():
            def body(st, _):
                with jax.named_scope("engine.advance"):
                    live = lay.live(st, operands)
                    blk, p0, dstep = st["block"], st["cursor"], st["dstep"]
                    riding = live & st["has_pending"]
                logits, caches, stats = fam.block_step(
                    self._target_params(params), blk, p0, st["caches"],
                    live, riding, st["pending"])
                # the draw names itself (``sample.confidence``)
                with jax.named_scope("engine.advance"):
                    kd, sub = split_keys_batched(st["keys"])
                    keys = jax.vmap(lambda k: jax.random.split(k, b))(
                        sub).reshape(s * b)
                    drawn, conf = gumbel_topk_sample_with_confidence(
                        keys, logits.reshape(s * b, -1),
                        jnp.repeat(st["top_k"], b), jnp.repeat(st["temp"], b),
                        mask=jnp.repeat(st["lmask"], b, axis=0))
                    take = live[:, None] & confident_positions(
                        conf.reshape(s, b), blk == mask_id,
                        per_step[jnp.clip(dstep, 0, steps - 1)], threshold)
                    filled = jnp.where(take, drawn.reshape(s, b).astype(
                        jnp.int32), blk)
                    finish = live & ~jnp.any(filled == mask_id, axis=1)

                    # a finished block: which of its tokens count
                    where = p0[:, None] + at
                    generated = where >= st["start"][:, None]
                    eos = (generated & (where < st["stop"][:, None])
                           & (filled == EOS_ID))
                    ended = jnp.any(eos, axis=1)
                    last = jnp.where(ended, p0 + jnp.argmax(eos, axis=1),
                                     jnp.minimum(p0 + b, st["stop"]) - 1)
                    counted = jnp.where(finish, last - st["pos"], 0)
                    done = finish & (ended | (p0 + b >= st["stop"]))
                    values, here = spread(filled, p0)
                    seq = jnp.where(here & finish[:, None], values, st["seq"])
                    steps_at, _ = spread(jnp.where(take, dstep[:, None], -1), p0)
                    fill = jnp.where(here & (steps_at >= 0),
                                     steps_at.astype(jnp.int8), st["fill"])
                    out = {
                        **st, "seq": seq, "caches": caches, "fill": fill,
                        "pos": jnp.where(finish, last, st["pos"]),
                        "done": st["done"] | done,
                        "cursor": jnp.where(finish, p0 + b, p0),
                        "block": jnp.where(finish[:, None], mask_id, filled),
                        "dstep": jnp.where(finish, 0, dstep + live),
                        "pending": jnp.where(finish[:, None], filled,
                                             st["pending"]),
                        # what rode is written; a row that is not live keeps
                        # what it has
                        "has_pending": jnp.where(live, finish & ~done,
                                                 st["has_pending"]),
                        "keys": jnp.where(live[:, None], kd, st["keys"]),
                    }
                    kept = jnp.sum(take, axis=1)
                    stats = {
                        **stats,
                        "diffusion.forwards": jnp.sum(live).astype(f32),
                        "diffusion.commit_forwards": jnp.sum(riding).astype(f32),
                        "diffusion.tokens_committed": jnp.sum(counted).astype(
                            f32),
                        "diffusion.tokens_dropped": jnp.sum(jnp.where(
                            finish, jnp.sum(generated, axis=1) - counted,
                            0)).astype(f32),
                        "diffusion.positions_kept": jnp.sum(jnp.where(
                            dstep[:, None] == jnp.arange(steps)[None, :],
                            kept[:, None], 0), axis=0).astype(f32),
                    }
                    out["stats"] = jax.tree.map(jnp.add, st["stats"], stats)
                return out, None

            state, _ = jax.lax.scan(body, state, None,
                                    length=self.chunk_size)
        return state

    def _admit_impl(self, params, state, src, mask, *args):
        """One admission run: prefill only the rows being admitted —
        ``args`` is ``_prefill_worker_impl``'s arguments over ``R =
        self.admit_rows`` rows, ``tokens (R, P_pad)`` first, then the
        layout's merge operands (none, or the R-row write table) — and
        gather the R-row handle into the slots the host chose (``src
        (S,)`` slot -> handle row, ``mask (S,)`` the slots admitted).  The
        composition of the two halves disaggregated serving runs as
        separate programs; unused handle rows carry a dummy one-token
        prime and land nowhere."""
        n = len(args) - self._layout.merge_operands
        prefill = (self._block_prefill_impl if self.block_length
                   else self._prefill_worker_impl)
        handle = prefill(params, *args[:n])
        return self._merge_impl(state, *self._layout.split_handle(handle),
                                src, mask, *args[n:])

    # ------------------------------------------------- disaggregated serving

    def _prefill_worker_impl(self, params, tokens, lengths, stops, seeds,
                             top_k, temp, lmask, tenant=None):
        """The prefill half of admission, with NO slot state in scope:
        one parallel forward over ``tokens (rows, P_pad)`` whose product
        is a handle of ``(rows, ...)`` slabs that ``_merge_impl`` gathers
        into slots — inside the same program for inline admission
        (``_admit_impl``), as a program of its own under disaggregated
        serving.  Gate rows stay dense here whatever the cache layout (the
        worker cannot know which pool pages the rows will land in; a paged
        merge scatters them through a row-indexed write table).
        ``tenant (S,)`` rides only under LoRA and travels in the handle
        state so the decode side keeps gathering the right adapter."""
        with self._trace_ctx():
            last, caches, stats = self.family.prefill(
                self._target_params(params), tokens, lengths, self.max_len,
                self._adapters(params), tenant)
            caches = _constrain_caches(caches, self.mesh, self.strategies)

        # the handle's own rows; the draw names itself (``sample.draw``)
        with jax.named_scope("engine.prime"):
            keys = jax.vmap(jax.random.key)(seeds.astype(jnp.uint32))
            split = jax.vmap(jax.random.split)(keys)
            # the first generated token writes at position ``lengths`` — its
            # mask row applies here, not in the decode chunk
            first_mrow = jnp.take_along_axis(
                lmask, lengths[:, None, None], axis=1
            )[:, 0] if self.family.position_masks else lmask
            first = gumbel_topk_sample_batched(
                split[:, 1], last, top_k, temp,
                mask=first_mrow).astype(jnp.int32)

            rows = tokens.shape[0]
            seq = self._prime_rows(tokens, lengths)
            seq = seq.at[jnp.arange(rows), lengths].set(first)
            out = {
                "seq": seq,
                "caches": caches,
                "pos": lengths,
                "start": lengths,
                "stop": stops,
                "done": (first == EOS_ID) | (lengths + 1 >= stops),
                "keys": jax.random.key_data(split[:, 0]),
                "top_k": top_k,
                "temp": temp,
                "lmask": lmask,
            }
        if self.lora:
            out["tenant"] = tenant
        if stats:
            out["stats"] = stats
        return out

    def _prime_rows(self, tokens, lengths):
        """``tokens (rows, P_pad)`` as rows of the slots' ``seq``: ``max_len``
        wide, zero past each row's length.  ``P_pad`` is bucket-aligned and
        may overshoot ``max_len``; real tokens never do (submit enforces
        prime + 1 <= max_len), so truncation drops padding only."""
        L, p_pad = self.max_len, tokens.shape[1]
        tok_L = tokens[:, :L] if p_pad >= L else jnp.pad(
            tokens, ((0, 0), (0, L - p_pad)))
        return tok_L * (jnp.arange(L)[None, :] < lengths[:, None])

    def _block_prefill_impl(self, params, tokens, lengths, stops, seeds,
                            top_k, temp, lmask):
        """``_prefill_worker_impl`` for a family that generates by blocks:
        the prime's whole blocks are prefilled and cached, its last ``P mod
        B`` tokens open the block in progress beside mask tokens, and NO
        token is drawn here — the row's key starts at its seed and ``pos``
        on the prime's last token."""
        fam, b = self.family, self.block_length
        with self._trace_ctx():
            _, caches, stats = fam.prefill(
                self._target_params(params), tokens, lengths, self.max_len)
        with jax.named_scope("engine.prime"):
            L, rows = self.max_len, tokens.shape[0]
            seq = self._prime_rows(tokens, lengths)
            whole = lengths // b * b
            where = whole[:, None] + jnp.arange(b)
            block = jnp.where(
                where < lengths[:, None],
                jnp.take_along_axis(seq, jnp.minimum(where, L - 1), axis=1),
                fam.mask_token_id)
            keys = jax.vmap(jax.random.key)(seeds.astype(jnp.uint32))
            return {
                "seq": seq, "caches": caches, "pos": lengths - 1,
                "start": lengths, "stop": stops,
                "done": jnp.zeros((rows,), bool),
                "keys": jax.random.key_data(keys), "top_k": top_k,
                "temp": temp, "lmask": lmask, "cursor": whole, "block": block,
                "dstep": jnp.zeros((rows,), jnp.int32),
                "fill": jnp.full((rows, L), -1, jnp.int8),
                "pending": jnp.full((rows, b), fam.mask_token_id, jnp.int32),
                "has_pending": jnp.zeros((rows,), bool),
                "stats": {**stats, **self._diffusion_zeros()},
            }

    def _merge_impl(self, state, hstate, gate_rows, src, mask, *extra):
        """The merge half of admission: gather handle rows (as many as the
        handle has) into slot state.  ``src (S,)`` gives each slot its
        handle row (any value where ``mask`` is False), ``mask (S,)`` the
        slots being admitted.  As the decode-side program of the handoff
        the handle is DONATED (``donate_argnums=(1,)``) — its buffers
        alias the merged state outputs, so the caches move rather than
        copy; inside ``_admit_impl`` it is that program's own
        intermediate.  A gather (host-inverted mapping) rather than a scatter of
        handle rows: no duplicate-index hazard, and dead rows vanish for
        free.  ``gate_rows`` and ``extra`` are the cache layout's: what it
        split out of the handle (NOT donated) and its merge operands."""
        with jax.named_scope("engine.merge"):
            csrc = jnp.clip(src, 0, hstate["pos"].shape[0] - 1)

            def take(h, old):
                m = mask.reshape((-1,) + (1,) * (old.ndim - 1))
                return jnp.where(m, jnp.take(h, csrc, axis=0), old)

            caches = self._layout.merge(take, state["caches"], hstate,
                                        gate_rows, extra)
            out = {
                "seq": take(hstate["seq"], state["seq"]),
                "caches": caches,
                "pos": take(hstate["pos"], state["pos"]),
                "start": take(hstate["start"], state["start"]),
                "stop": take(hstate["stop"], state["stop"]),
                "active": state["active"] | mask,
                "done": take(hstate["done"], state["done"]),
                "keys": take(hstate["keys"], state["keys"]),
                "top_k": take(hstate["top_k"], state["top_k"]),
                "temp": take(hstate["temp"], state["temp"]),
                "lmask": take(hstate["lmask"], state["lmask"]),
            }
            if self.block_length:
                out.update({k: take(hstate[k], state[k])
                            for k in _BLOCK_STATE})
            if self.lora:
                out["tenant"] = take(hstate["tenant"], state["tenant"])
            if "stats" in state:
                # counters are sums, not rows: the handle's join the state's
                out["stats"] = jax.tree.map(jnp.add, state["stats"],
                                            hstate["stats"])
            return out

    def _prefill_worker_call(self, *args):
        fn = self._aot.get(("prefill", args[0].shape[1]),
                           self._prefill_worker)
        return fn(self._params, *args)

    def _merge_call(self, hstate, *args):
        fn = self._aot.get(("merge",), self._merge)
        return fn(self.state, *self._layout.split_handle(hstate), *args)

    # ----------------------------------------------------------------- API

    def submit(self, request: Request) -> None:
        """Queue a request.  Structural errors (empty prime, no room to
        generate) still raise — they are caller bugs; OPERATIONAL
        conditions (injected faults, expired deadline, full queue) shed
        the request as a typed completion instead, so a loaded or faulty
        server answers every request rather than crashing on admission.
        """
        n = len(request.tokens)
        if n < 1:
            raise ValueError(f"request {request.uid!r}: empty prime")
        if n + 1 > self.max_len:
            raise ValueError(
                f"request {request.uid!r}: prime length {n} leaves no room "
                f"for generation (max_len {self.max_len})"
            )
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.uid!r}: max_new_tokens must be >= 1")
        if request.logit_mask is not None:
            m = np.asarray(request.logit_mask, bool)
            vocab = self.family.vocab
            if m.ndim == 2 and not self.family.position_masks:
                raise UnsupportedFamilyMode(
                    f"request {request.uid!r}: the {self.family.name} "
                    f"family takes a logit_mask that is the same at every "
                    f"position, as one ({vocab},) row, not {m.shape}")
            if m.ndim == 1 and m.shape[0] == vocab:
                m = m[None, :]      # checked as one row, kept as (V,)
            if m.ndim != 2 or m.shape[1] != vocab:
                raise ValueError(
                    f"request {request.uid!r}: logit_mask must be "
                    f"(G, {vocab}) or ({vocab},), got {m.shape}")
            if m.shape[0] > request.max_new_tokens:
                raise ValueError(
                    f"request {request.uid!r}: logit_mask has {m.shape[0]} "
                    f"rows but max_new_tokens={request.max_new_tokens}")
            if n + m.shape[0] > self.max_len:
                raise ValueError(
                    f"request {request.uid!r}: mask rows run past max_len "
                    f"{self.max_len} (prime {n} + {m.shape[0]} rows)")
            if not m.any(axis=1).all():
                raise ValueError(
                    f"request {request.uid!r}: logit_mask has an all-False "
                    f"row — every constrained position needs >= 1 allowed "
                    f"token")
            request.logit_mask = (
                m[0] if np.ndim(request.logit_mask) == 1 else m)
        tenant = int(request.tenant)
        if tenant != 0 and not self.lora:
            raise ValueError(
                f"request {request.uid!r}: tenant={tenant} but the engine "
                f"was built without a lora_bank")
        if not (0 <= tenant < self.num_tenants):
            raise ValueError(
                f"request {request.uid!r}: tenant {tenant} outside the "
                f"bank's [0, {self.num_tenants})")
        if self.paged:
            stop = min(n + request.max_new_tokens, self.max_len)
            worst = pages_for_span(stop - 1, self.page_size)
            if worst > self._pool.capacity:
                raise ValueError(
                    f"request {request.uid!r}: needs up to {worst} pages "
                    f"but the pool only has {self._pool.capacity} — "
                    f"raise num_pages or lower max_new_tokens")
        try:
            self._guard("serve.submit")
        except (_ContainedFault, RetryError):
            self._shed(request, FAILED_FAULT)
            return
        deadline = self._deadline_of(request)
        if deadline is not None and time.perf_counter() > deadline:
            self._shed(request, SHED_DEADLINE)
            return
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            if self.shed_policy == "shed-oldest":
                # priority-aware: drop the LOWEST class (oldest within
                # it); when the newcomer ranks below everything queued,
                # the newcomer is the victim — a strictly higher-priority
                # request is never shed while a lower one sits queued
                victim = self._queue.shed_victim()
                if (victim is not None
                        and victim.priority <= request.priority):
                    self._queue.remove(victim)
                    self._shed(victim, SHED_QUEUE_FULL)
                else:
                    self._shed(request, SHED_QUEUE_FULL)
                    return
            else:
                self._shed(request, SHED_QUEUE_FULL)
                return
        self._queue.append(request)
        self._tracer.event("serve.submit", trace=request.uid,
                           queue=len(self._queue))

    def submit_embed(self, request: Request) -> None:
        """Queue an EMBEDDING request: one prefill-shaped forward, mean-
        pooled final hidden state, no decode slot consumed.  Same shed
        rules as :meth:`submit`; ``max_new_tokens``/``top_k``/``temp``/
        ``seed`` are ignored (nothing is sampled)."""
        if self._embedder is None:
            raise UnsupportedFamilyMode(
                f"the {self.family.name} family has no embedding program")
        n = len(request.tokens)
        if n < 1:
            raise ValueError(f"request {request.uid!r}: empty prime")
        if n > self.config.seq_len:
            raise ValueError(
                f"request {request.uid!r}: prime length {n} exceeds "
                f"seq_len {self.config.seq_len}")
        if request.logit_mask is not None:
            raise ValueError(
                f"request {request.uid!r}: embed requests take no "
                f"logit_mask (nothing is sampled)")
        if int(request.tenant) != 0:
            raise ValueError(
                f"request {request.uid!r}: embed requests run the base "
                f"model (tenant must be 0)")
        try:
            self._guard("serve.submit")
        except (_ContainedFault, RetryError):
            self._shed(request, FAILED_FAULT)
            return
        deadline = self._deadline_of(request)
        if deadline is not None and time.perf_counter() > deadline:
            self._shed(request, SHED_DEADLINE)
            return
        if (self.max_queue is not None
                and len(self._embed_queue) >= self.max_queue):
            if self.shed_policy == "shed-oldest":
                self._shed(self._embed_queue.popleft(), SHED_QUEUE_FULL)
            else:
                self._shed(request, SHED_QUEUE_FULL)
                return
        self._embed_queue.append(request)
        self._tracer.event("serve.submit_embed", trace=request.uid,
                           queue=len(self._embed_queue))

    def submit_fork(self, request: Request, n_samples: int) -> list:
        """Best-of-N: fork ``n_samples`` trajectories off one shared
        prime.  Fork ``k`` is ``request`` with ``uid + k`` and ``seed +
        k`` — each completion is token-identical to submitting that
        request independently (a trajectory depends only on (params,
        prime, seed, knobs)), so callers may rank or dedup the samples
        freely.  The caller owns uid-space: ``uid .. uid+n-1`` must be
        unused.

        On a paged engine with the prefix cache enabled the forks share
        the prime's full prefix pages through the pool's refcounts — the
        leader (fork 0) is submitted immediately and primes the pages;
        the followers are held until the leader's registrations publish
        (or the leader sheds), then admitted as cache hits, so N samples
        cost one set of prime pages instead of N.  Dense engines and
        ``prefix_cache=False`` pools submit all forks immediately (same
        tokens, no sharing to exploit).  Returns the fork uids in order;
        sheds still answer per-fork as typed completions."""
        if n_samples < 1:
            raise ValueError(f"request {request.uid!r}: n_samples must "
                             f"be >= 1, got {n_samples}")
        if not isinstance(request.uid, int):
            raise ValueError(f"request {request.uid!r}: submit_fork "
                             f"derives fork uids by offset — uid must "
                             f"be an int")
        forks = [dataclasses.replace(request, uid=request.uid + k,
                                     seed=request.seed + k)
                 for k in range(n_samples)]
        self.fork_groups += 1
        share = (self.paged and self._pool.prefix_caching
                 and n_samples > 1
                 and len(request.tokens) >= self.page_size)
        self.submit(forks[0])
        if not share:
            for f in forks[1:]:
                self.submit(f)
        else:
            # hold the followers until the leader's prefix pages are
            # published — released by _release_forks on the step after
            # the leader leaves the queue (admitted OR shed), so a shed
            # leader never strands its followers
            self._fork_wait[forks[0].uid] = forks[1:]
        self._tracer.event("serve.submit_fork", trace=request.uid,
                           n_samples=n_samples)
        return [f.uid for f in forks]

    def forget_ttft(self, uids) -> None:
        """Drop the timeline stamps of requests that leave this engine
        for another process (prefill workers hand off and never harvest
        locally), so the stamp map cannot grow without bound."""
        for u in uids:
            self._ttft.pop(u, None)
            self._admitted.pop(u, None)

    def _release_forks(self) -> None:
        """Submit fork followers whose leader has left the queue (its
        admission committed the shared prefix registrations — or it shed,
        in which case the followers proceed unshared).  Runs at the top
        of :meth:`step` so followers land one admission round behind
        their leader."""
        if not self._fork_wait:
            return
        queued = {r.uid for r in self._queue}
        ready = [uid for uid in self._fork_wait if uid not in queued]
        for uid in ready:
            for f in self._fork_wait.pop(uid):
                self.submit(f)

    @property
    def pending(self) -> int:
        return (len(self._queue) + len(self._embed_queue)
                + sum(len(v) for v in self._fork_wait.values()))

    @property
    def prefix_hits(self) -> int:
        return self._layout.prefix_hits

    @property
    def prefix_lookups(self) -> int:
        return self._layout.prefix_lookups

    @property
    def num_active(self) -> int:
        return len(self._inflight)

    @property
    def has_work(self) -> bool:
        """True while anything remains for ``step()`` to do or report —
        queued requests, held fork followers, in-flight slots, or shed
        completions not yet returned by a ``step()`` call."""
        n = (len(self._queue) + len(self._embed_queue)
             + len(self._inflight) + len(self._pending)
             + sum(len(v) for v in self._fork_wait.values()))
        if self.disagg:
            n += len(self._handoff)
        return n > 0

    # ---------------------------------------------------------- shedding

    @staticmethod
    def _deadline_of(r: Request) -> float | None:
        if r.deadline is not None:
            return r.deadline
        if r.ttl is not None:
            return r.submit_time + r.ttl
        return None

    def _shed(self, r: Request, status: str, tokens=None) -> Completion:
        """Answer ``r`` with a typed shed completion (callback fires,
        counters bump); ``tokens`` carries any partial generation an
        in-flight deadline cancellation salvaged."""
        if status == SHED_QUEUE_FULL:
            self.robust.sheds_queue_full += 1
        elif status == SHED_DEADLINE:
            self.robust.sheds_deadline += 1
        else:
            self.robust.failed_faults += 1
        comp = Completion(
            uid=r.uid,
            prime=np.asarray(  # graftcheck: disable=host-sync
                r.tokens, np.int32),
            tokens=np.asarray(  # graftcheck: disable=host-sync
                [] if tokens is None else tokens, np.int32),
            finish_reason=status, status=status,
            submit_time=r.submit_time, finish_time=time.perf_counter(),
            generation=self.generation,
            first_token_time=self._ttft.pop(r.uid, None),
            admit_time=self._admitted.pop(r.uid, None))
        self.completions.append(comp)
        self._pending.append(comp)
        self._tracer.event("serve.shed", trace=r.uid, status=status)
        if r.on_complete is not None:
            r.on_complete(comp)
        return comp

    def _drain_pending(self) -> list[Completion]:
        out, self._pending = self._pending, []
        return out

    def _shed_expired(self) -> None:
        """Shed every queued request past its deadline (before it costs a
        prefill) and cancel expired in-flight slots (their partial tokens
        ride along in the shed completion)."""
        now = time.perf_counter()
        for q in (self._queue, self._embed_queue):
            expired_q = [r for r in q
                         if self._deadline_of(r) is not None
                         and now > self._deadline_of(r)]
            for r in expired_q:
                q.remove(r)
                self._shed(r, SHED_DEADLINE)
        slots = [s for s, r in self._inflight.items()
                 if self._deadline_of(r) is not None
                 and now > self._deadline_of(r)]
        if not slots:
            return
        active, seq, pos, start = _host_fetch(
            (self.state["active"], self.state["seq"], self.state["pos"],
             self.state["start"]))
        for slot in slots:
            toks = (seq[slot, start[slot]: pos[slot] + 1].copy()
                    if active[slot] else None)
            self._shed(self._vacate(slot), SHED_DEADLINE, tokens=toks)
        self._deactivate(slots)

    # ----------------------------------------------------------- admission

    def _maybe_preempt(self) -> None:
        """Priority preemption: while the scheduler's head is blocked
        (no free slot, or — paged — no pages for its prime) and some
        in-flight request ranks STRICTLY below it, cancel the victim and
        re-enqueue it through the scheduler.  Victim choice: lowest
        priority class first, then most recently admitted (least decode
        work thrown away).  Replay-from-scratch is bit-exact — a
        trajectory depends only on (params, prime, seed, knobs) — so
        preemption trades only latency, never correctness.  Disabled
        under disagg: a remote-prefill replica cannot replay locally, so
        cluster QoS is enforced at each prefill worker's queue instead.
        """
        if self.disagg:
            return
        while self._queue and self._inflight:
            head = self._queue[0]
            if (len(self._inflight) < self.num_slots
                    and self._layout.covers([head])):
                return
            victim = min(
                self._inflight,
                key=lambda s: (self._inflight[s].priority,
                               -self._admit_order.get(s, 0)))
            if self._inflight[victim].priority >= head.priority:
                return
            self._preempt_slot(victim)

    def _preempt_slot(self, slot: int) -> None:
        """Cancel ``slot``'s in-flight request for a higher class and
        re-enqueue it THROUGH the scheduler: it keeps its original queue
        seniority among same-class peers but waits behind the class that
        displaced it (contrast :meth:`_evict_slot`, whose front-of-queue
        requeue is the pool-starvation replay path)."""
        r = self._vacate(slot)
        self._deactivate([slot])
        self._queue.append(r)
        self.robust.preemptions += 1
        self._tracer.event("serve.preempt", trace=r.uid, slot=slot)

    def _admit_pending(self) -> None:
        """Inline admission: admit what fits, ``admit_rows`` requests per
        run of the admission program and as many runs as the group needs,
        one in flight at a time and with no fetch between them.
        Bookkeeping and fault handling are per run: a run whose prefill
        was lost sheds or re-queues its own requests and leaves the
        earlier runs of the group in their slots."""
        if not self._queue:
            return
        self._maybe_preempt()
        if (len(self._inflight) >= self.num_slots
                or not self._admission_open(self._queue)):
            return
        stage = None
        while self._queue and len(self._inflight) < self.num_slots:
            placed = self._new_placement(self.admit_rows)
            requests: list[Request] = []
            try:
                # host work with the device idle (the first run) or busy
                # with the run before: slots, pages, host arrays, the mask
                with self._span("serve.admit_build") as build:
                    t_build = time.perf_counter()
                    requests = self._take_requests()
                    if not requests:
                        return      # the head waits for pages
                    self._note_admitted(requests, t_build)
                    uids = [r.uid for r in requests]
                    build.note(uids=uids)
                    p_pad = self.family.bucket(
                        max(len(r.tokens) for r in requests), self.max_len)
                    self._place(placed, list(enumerate(requests)), p_pad)
                    args = (placed.src, placed.mask,
                            *self._prefill_args(self.admit_rows, requests,
                                                p_pad),
                            *placed.operands)
                if stage is not None:
                    # the state is not donated, so every run in flight
                    # holds a copy of it: the run before has to be done (no
                    # transfer, the arrays above are built already) before
                    # this one is dispatched, and two copies live at once
                    # however large the group — what the chunk program
                    # needs anyway
                    t_wait = time.perf_counter()
                    with self._span("serve.device_wait", after="admit"):
                        # graftcheck: disable=host-sync
                        jax.block_until_ready(self.state["pos"])
                    self._step_wait += time.perf_counter() - t_wait

                t0 = time.perf_counter()
                with self._span("serve.admit_prefill", uids=uids,
                                p_pad=p_pad):
                    self.state = self._guard(
                        "serve.prefill", self._admit_call, p_pad, *args,
                        key=("admit", p_pad))
            except _ContainedFault as e:
                self._unplace(placed)
                if e.point == "serve.page_alloc":
                    # only the request whose planning faulted is shed; the
                    # innocents (planned before it or never reached) go
                    # back to the queue front in order
                    lost = [placed.batch[-1][1]]
                    for r in reversed(requests):
                        if r is not lost[0]:
                            self._queue.appendleft(r)
                else:
                    # the run's prefill never merged: shed exactly the
                    # requests whose work was lost; what is still queued
                    # waits for the next step
                    lost = requests
                for r in lost:
                    self._shed(r, FAILED_FAULT)
                return
            except RetryError:
                # escape for restart-and-replay, but leave the engine
                # consistent: the un-prefilled run goes back to the queue
                # front in its original order
                self._unplace(placed)
                for r in reversed(requests):
                    self._queue.appendleft(r)
                raise
            self._publish_prefixes(placed)
            real = sum(len(r.tokens) for r in requests)
            self._admit_rows_hist.observe(len(requests))
            self._prefill_real.inc(real)
            self._prefill_slots.inc(self.admit_rows * p_pad)
            self._step_admits.append(
                (len(requests), real, self.admit_rows * p_pad))
            self._admit_pads.append(p_pad)
            # the admit program samples each request's first token; that
            # it has RUN is known at the next flags fetch, which stamps
            # first-token time and closes the stage: ONE stage per
            # admitting step, from the dispatch of the group's first run
            if stage is None:
                stage = []
                self._open_stages.append(("prefill_s", "admit", t0, stage))
            stage.extend(requests)

    def _admission_open(self, queue) -> bool:
        """``serve.admit``, before a round takes from ``queue``.  False
        when the admission machinery is poisoned for this round: the
        queue's head is shed (livelock breaker — a permanently faulting
        point must not starve the whole queue) and the rest waits."""
        try:
            self._guard("serve.admit")
        except _ContainedFault:
            self._shed(queue.popleft(), FAILED_FAULT)
            return False
        return True

    def _take_requests(self) -> list[Request]:
        """Up to ``admit_rows`` requests off the queue, as many as there
        are free slots and — through the cache layout — free pages.

        The head of the queue is taken only if the pool can cover its
        whole prime plus the first sampled token WITHOUT prefix sharing
        (a conservative bound — planning shares whatever it can, so the
        allocation never exceeds the reservation); a blocked head DEFERS
        everything behind it.  "Head" is whatever the QoS scheduler ranks
        first RIGHT NOW (priority, then weighted-fair tenant share, then
        EDF) — within one admission round the order is fixed, across
        rounds a higher-priority arrival may overtake a deferred head
        (that, plus :meth:`_maybe_preempt`, is the QoS contract; pre-QoS
        FIFO deferral is the degenerate single-class case)."""
        room = min(self.admit_rows, self.num_slots - len(self._inflight))
        requests: list[Request] = []
        while self._queue and len(requests) < room:
            if not self._layout.covers(requests + [self._queue[0]]):
                break  # head-of-line blocks: deferral, not reordering
            requests.append(self._queue.popleft())
        return requests

    # ---- booking slots: shared by inline and handed-off admission

    def _new_placement(self, n_rows: int) -> "_Placement":
        s = self.num_slots
        return _Placement(batch=[], src=np.zeros((s,), np.int32),
                          mask=np.zeros((s,), bool),
                          operands=self._layout.write_tables(n_rows),
                          prefixes=[])

    def _place(self, placed: "_Placement", rows: list, p_pad: int) -> None:
        """Book each ``(handle row, request)`` of ``rows`` into a free
        slot (the caller has counted them) and let the cache layout plan
        its pages.  ``placed`` is filled as it goes, so that whatever
        raises — a fault while planning, or the caller's dispatch later —
        :meth:`_unplace` takes back exactly what was booked."""
        free = [i for i in range(self.num_slots) if i not in self._inflight]
        for slot, (row, r) in zip(free, rows):
            placed.src[slot] = row
            placed.mask[slot] = True
            self._inflight[slot] = r
            self._admit_order[slot] = self._admit_seq
            self._admit_seq += 1
            placed.batch.append((slot, r))
            self._layout.plan(self._guard, slot, row, r, p_pad,
                              placed.operands, placed.prefixes)

    def _unplace(self, placed: "_Placement") -> None:
        """The dispatch never ran: free the slots and the planned pages.
        They hold nothing, and no prefix registration was committed (the
        deferred ones die with ``placed``), so the index cannot serve a
        garbage page."""
        for slot, _ in reversed(placed.batch):
            self._vacate(slot)

    def _publish_prefixes(self, placed: "_Placement") -> None:
        """The prefill is dispatched: NOW the freshly-filled full-prefix
        pages may be published for sharing."""
        for key, pid in placed.prefixes:
            self._pool.register_prefix(key, pid)

    def _vacate(self, slot: int) -> Request:
        """Take ``slot``'s request out of it: the booking, and what the
        cache layout holds for the slot on the host."""
        self._admit_order.pop(slot, None)
        self._layout.free(slot)
        return self._inflight.pop(slot)

    def _build_lmask(self, n_rows: int, rows: list) -> np.ndarray:
        """``(n_rows, max_len, V)`` write-position-indexed logit masks for
        the rows being admitted (``rows`` pairs a slot/handle-row index
        with its request).  Unconstrained rows stay all-True —
        bit-identical to serving without masks at all.  Request row ``g``
        constrains the token written at absolute position
        ``len(prime) + g``."""
        lmask = np.ones(self._lmask_shape(n_rows), bool)
        for idx, r in rows:
            if r.logit_mask is not None:
                m = np.asarray(r.logit_mask, bool)
                p = len(r.tokens)
                if not self.family.position_masks:
                    lmask[idx] = m
                elif m.ndim == 1:
                    lmask[idx, p: p + r.max_new_tokens] = m
                else:
                    lmask[idx, p: p + m.shape[0]] = m
        if self.block_length:       # the mask token is never a draw
            lmask[..., self.family.mask_token_id] = False
        return lmask

    def _prefill_args(self, n_rows: int, rows: list, p_pad: int) -> tuple:
        """Host arrays of one prefill over ``n_rows`` handle rows, request
        ``rows[k]`` in row ``k``: the arguments of
        ``_prefill_worker_impl`` after ``params``.  Unused rows carry the
        family's ``idle_length``: a dummy one-token prime, or no token."""
        tokens = np.zeros((n_rows, p_pad), np.int32)
        lengths = np.full((n_rows,), self.family.idle_length, np.int32)
        stops = np.full((n_rows,), 2, np.int32)
        seeds = np.zeros((n_rows,), np.uint32)
        top_k = np.zeros((n_rows,), np.int32)
        temp = np.ones((n_rows,), np.float32)
        tenant = np.zeros((n_rows,), np.int32)
        for row, r in enumerate(rows):
            t = np.asarray(r.tokens, np.int32)
            tokens[row, : len(t)] = t
            lengths[row] = len(t)
            stops[row] = min(len(t) + r.max_new_tokens, self.max_len)
            seeds[row] = np.uint32(int(r.seed) & 0xFFFFFFFF)
            top_k[row] = 0 if r.top_k is None else int(r.top_k)
            temp[row] = float(r.temperature)
            tenant[row] = int(r.tenant)
        lmask = self._build_lmask(n_rows, list(enumerate(rows)))
        extra = (tenant,) if self.lora else ()
        return (tokens, lengths, stops, seeds, top_k, temp, lmask, *extra)

    # ---------------------------------------------------------- embeddings

    def _embed_round(self) -> None:
        """Serve one batch of embedding requests: a FIFO prefix of the
        embed queue sharing the head's prefill bucket, padded to
        ``embed_batch`` rows, one pooled forward, completions with the
        ``(D,)`` vector attached.  No slot state is touched — embedding
        traffic composes with any decode configuration."""
        if not (self._embed_queue
                and self._admission_open(self._embed_queue)):
            return
        bucket = self.family.bucket
        p_pad = bucket(len(self._embed_queue[0].tokens), self.max_len)
        batch: list[Request] = []
        while (self._embed_queue and len(batch) < self.embed_batch
               and bucket(len(self._embed_queue[0].tokens),
                          self.max_len) == p_pad):
            batch.append(self._embed_queue.popleft())

        b = self.embed_batch
        tokens = np.zeros((b, p_pad), np.int32)
        lengths = np.ones((b,), np.int32)  # dummy rows: 1-token prime
        for row, r in enumerate(batch):
            t = np.asarray(r.tokens, np.int32)
            tokens[row, : len(t)] = t
            lengths[row] = len(t)
        t0 = time.perf_counter()
        try:
            # the span and the stage end at the fetch of the vectors
            with self._span("serve.embed", uids=[r.uid for r in batch],
                            p_pad=p_pad):
                vecs = self._guard(
                    "serve.embed", self._embed_call, tokens, lengths,
                    key=("embed", p_pad))
                vecs = np.asarray(jax.device_get(
                    vecs))
        except _ContainedFault:
            for r in batch:
                self._shed(r, FAILED_FAULT)
            return
        except RetryError:
            for r in reversed(batch):
                self._embed_queue.appendleft(r)
            raise
        now = time.perf_counter()
        self._record_stage("embed_s", now - t0)
        for row, r in enumerate(batch):
            comp = Completion(
                uid=r.uid, prime=np.asarray(r.tokens, np.int32),
                tokens=np.zeros((0,), np.int32), finish_reason="embed",
                submit_time=r.submit_time, finish_time=now,
                embedding=vecs[row], generation=self.generation)
            self.completions.append(comp)
            self._pending.append(comp)
            if r.on_complete is not None:
                r.on_complete(comp)

    # ------------------------------------------- disaggregated admission

    def _prefill_round(self) -> None:
        """Prefill stage of a disaggregated step: run the worker over a
        FIFO prefix of the queue sharing the head's bucket and push the
        handle.  A full handoff queue skips the round entirely —
        backpressure: prefilled caches are the expensive thing to hold,
        so the wait is absorbed by the cheap token queue instead."""
        if (not self._queue or self._handoff.full()
                or not self._admission_open(self._queue)):
            return
        bucket = self.family.bucket
        p_pad = bucket(len(self._queue[0].tokens), self.max_len)
        t_build = time.perf_counter()
        batch: list[Request] = []
        while (self._queue and len(batch) < self.prefill_batch
               and bucket(len(self._queue[0].tokens),
                          self.max_len) == p_pad):
            batch.append(self._queue.popleft())
        self._note_admitted(batch, t_build)

        # handle-ROW-indexed, like every slab the worker produces
        args = self._prefill_args(self.num_slots, batch, p_pad)
        t0 = time.perf_counter()
        try:
            with self._span("serve.prefill", uids=[r.uid for r in batch],
                            p_pad=p_pad):
                h = self._guard(
                    "serve.prefill", self._prefill_worker_call, *args,
                    key=("prefill", p_pad))
        except _ContainedFault:
            for r in batch:
                self._shed(r, FAILED_FAULT)
            return
        except RetryError:
            for r in reversed(batch):
                self._queue.appendleft(r)
            raise
        # DISPATCH-ONLY: nothing in this round fetches the handle (a
        # prefill worker process serializes it on its transport thread),
        # so this is the one stage time that does not end at a fetch.  The
        # worker samples each request's first token; when it existed is
        # known where the handle is next read: at the flags fetch after
        # the decode-side merge here, on the driver's clock in a cluster
        self._record_stage("prefill_s", time.perf_counter() - t0,
                           ("prefill", p_pad))
        self._handoff.put(Handle(requests=batch, state=h, p_pad=p_pad))

    def _admit_from_handoff(self) -> None:
        """Decode-side admission: move queued handles into free slots via
        the donating merge program.  The head handle DEFERS (never
        reorders) while slots or pages are short, exactly like inline
        admission."""
        while self._handoff:
            h = self._handoff.peek()
            now = time.perf_counter()
            expired: list[Request] = []
            live_rows: list[tuple[int, Request]] = []
            for row, r in enumerate(h.requests):
                d = self._deadline_of(r)
                if d is not None and now > d:
                    expired.append(r)
                else:
                    live_rows.append((row, r))
            admitted = [r for _, r in live_rows]
            if (self.num_slots - len(self._inflight) < len(live_rows)
                    or not self._layout.covers(admitted)):
                return
            # peek-then-pop: ``h`` above came from front() without
            # consuming; this get() pops that same handle now that
            # admission is committed — ownership continues in ``h``
            # graftcheck: disable=resource-leak
            self._handoff.get()
            # a remote-prefill handle's requests were never in this
            # engine's queue: they are admitted here (a local prefill
            # round's earlier stamp stands)
            self._note_admitted(admitted, now)
            if live_rows:
                # the write table is handle-ROW-indexed, like every slab
                # the worker produced
                placed = self._new_placement(len(h.state["pos"]))
                try:
                    self._place(placed, live_rows, h.p_pad)
                    t0 = time.perf_counter()
                    # the merge DONATES the handle's buffers; this stays
                    # retry/requeue-safe because faults.inject raises
                    # BEFORE the jitted program dispatches — a contained
                    # or transient failure here has not consumed them
                    with self._span("serve.merge",
                                    uids=[r.uid for r in admitted]):
                        self.state = self._guard(
                            "serve.handoff", self._merge_call, h.state,
                            placed.src, placed.mask, *placed.operands,
                            key=("merge",))
                except _ContainedFault:
                    self._unplace(placed)
                    for r in admitted:
                        self._shed(r, FAILED_FAULT)
                except RetryError:
                    self._unplace(placed)
                    # expired rows were NOT shed yet, so the requeued
                    # handle replays them all exactly once after restart
                    self._handoff.requeue(h)
                    raise
                else:
                    self._publish_prefixes(placed)
                    # the first tokens came with the handle; the next
                    # flags fetch shows the merged state and stamps them
                    self._open_stages.append(
                        ("merge_s", "admit", t0, admitted))
                    # prefilled elsewhere: a run of no token slots here
                    self._step_admits.append((len(admitted), 0, 0))
            for r in expired:
                self._shed(r, SHED_DEADLINE)

    def _evict_slot(self, slot: int) -> None:
        """Restart preemption: free the slot's pages and push its request
        back to the FRONT of the queue.  Replaying from scratch is safe —
        a trajectory depends only on (params, prime, seed, knobs), so the
        re-decode reproduces the identical token prefix."""
        r = self._vacate(slot)
        self._deactivate([slot])
        self._queue.appendleft(r)
        self.evictions += 1

    def _ensure_chunk_pages(self) -> None:
        """Before each chunk, grow every live slot's page list to cover
        all positions the chunk can write (``[pos, min(pos+chunk,
        stop)-1]``).  Slots the pool cannot cover are PAUSED for this
        chunk (their rows freeze entirely); if the pool starves every
        live slot, the youngest is evicted until someone can run."""
        if not self._inflight:
            return
        lay = self._layout
        try:
            self._guard("serve.page_alloc")
        except _ContainedFault as e:
            # contain an allocator fault like pool starvation: pause every
            # live slot for this chunk (their rows freeze — trajectories
            # are delayed, never altered) and retry next round
            self._defer("page_alloc", e)
            for slot in self._inflight:
                if not lay.paused[slot]:
                    self.pause_events += 1
                lay.paused[slot] = True
            return
        self._defer_streak.pop("page_alloc", None)
        pos = _host_fetch(
            self.state["pos"])
        for _ in range(len(self._inflight) + 1):
            slots = sorted(self._inflight, key=self._admit_order.__getitem__)
            for slot in slots:
                # last position the chunk can consume: done fires when
                # new_pos + 1 >= stop, so a live slot never consumes past
                # stop - 2; gate rows are written at consumed positions
                r = self._inflight[slot]
                stop = min(len(r.tokens) + r.max_new_tokens, self.max_len)
                last = min(int(pos[slot]) + self.chunk_size - 1, stop - 2)
                need = pages_for_span(last, self.page_size)
                pages = lay.slot_pages[slot]
                delta = need - len(pages)
                if delta <= 0:
                    lay.paused[slot] = False
                    continue
                fresh = lay.pool.allocate(delta)
                if fresh is None:
                    if not lay.paused[slot]:
                        self.pause_events += 1
                    lay.paused[slot] = True
                    continue
                base = len(pages)
                pages.extend(fresh)
                lay.table[slot, base: base + delta] = fresh
                lay.paused[slot] = False
            if any(not lay.paused[s] for s in self._inflight):
                return
            # every live slot starved: evict the most recently admitted
            victim = max(self._inflight, key=self._admit_order.__getitem__)
            if len(self._inflight) == 1:
                raise RuntimeError(
                    f"page pool too small for any progress: slot {victim} "
                    f"needs pages beyond capacity {lay.pool.capacity} "
                    f"with nothing left to evict")
            self._evict_slot(victim)

    def _harvest_done(self) -> list[Completion]:
        try:
            self._guard("serve.harvest")
        except _ContainedFault as e:
            # finished slots stay done-but-active; the next step's harvest
            # picks them up (their state is inert — done rows are masked
            # no-ops in the chunk body)
            self._defer("harvest", e)
            return []
        self._defer_streak.pop("harvest", None)
        # two-phase fetch: one small transfer of the per-slot flags gates
        # the call (the common case is "nothing finished"); the big seq
        # buffer only crosses the wire when some slot actually completed.
        # The flags fetch is the engine's one sync point: it returns when
        # every program dispatched before it has run, so the open stages
        # and the first-token stamps of their requests close here
        after = self._open_stages[-1][1] if self._open_stages else "idle"
        t0 = time.perf_counter()
        with self._span("serve.device_wait", after=after):
            done, active, stats = _host_fetch(
                (self.state["done"], self.state["active"],
                 self.state.get("stats")))
        now = time.perf_counter()
        self._step_wait += now - t0
        self._step_done = now
        self._close_stages(now)
        if stats:
            self._publish_model_stats(stats)
        ready = [i for i in range(self.num_slots)
                 if done[i] and active[i] and i in self._inflight]
        if not ready:
            return []
        with self._span("serve.harvest") as harvest:
            seq, pos, start = _host_fetch(
                (self.state["seq"], self.state["pos"], self.state["start"]))
            fill = (_host_fetch(self.state["fill"])
                    if self.block_length and any(
                        self._inflight[i].record_fill_steps for i in ready)
                    else None)
            out = []
            now = time.perf_counter()
            for i in ready:
                r = self._inflight[i]
                self._vacate(i)
                toks = seq[i, start[i]: pos[i] + 1].copy()
                reason = ("eos" if (toks.size and toks[-1] == EOS_ID)
                          else "length")
                comp = Completion(
                    uid=r.uid, prime=np.asarray(r.tokens, np.int32),
                    tokens=toks, finish_reason=reason,
                    submit_time=r.submit_time, finish_time=now,
                    generation=self.generation,
                    first_token_time=self._ttft.pop(r.uid, None),
                    admit_time=self._admitted.pop(r.uid, None))
                if r.record_fill_steps and fill is not None:
                    comp.fill_steps = fill[i, start[i]: pos[i] + 1].copy()
                out.append(comp)
                if r.on_complete is not None:
                    r.on_complete(comp)
            self._deactivate(ready)
            self.completions.extend(out)
            self._step_finished += len(out)
            harvest.note(uids=[c.uid for c in out])
        return out

    def _publish_model_stats(self, stats: dict) -> None:
        """The family's counters, fetched with the slot flags, as registry
        gauges (cumulative since the engine was built)."""
        self.model_stats = stats
        registry = _metrics.get_registry()
        own = {k: stats[k] for k in _DIFFUSION_STATS if k in stats}
        kept = own.pop("diffusion.positions_kept", ())
        gauges = {**{k: float(v) for k, v in own.items()},
                  **{f"diffusion.positions_kept.{i}": float(v)
                     for i, v in enumerate(kept)},
                  **self.family.publish({k: v for k, v in stats.items()
                                         if k not in _DIFFUSION_STATS})}
        self.model_gauges = gauges
        for name, value in gauges.items():
            registry.gauge(name).set(value)

    def _dispatch_chunk(self) -> None:
        """Run one guarded decode chunk.  A fatal fault on the paged
        Pallas kernel degrades to the bit-identical XLA fallback and
        retries — but only once the chunk program has run: until then
        what it raises comes from tracing, lowering or compiling the
        kernel, and a kernel the chip's compiler refuses is raised to the
        caller, not hidden behind one ``fallback_activations`` count
        (injected faults fire before the program and still degrade).  A
        fatal fault anywhere else sheds the in-flight batch
        (``_fail_inflight``) and the engine keeps serving; transient
        exhaustion escapes as :class:`RetryError` (restart-and-replay).
        """
        if self.paged:
            self._ensure_chunk_pages()
            if not self._inflight:
                return  # everything got evicted back to the queue
        args = self._layout.chunk_operands()
        while True:
            t0 = time.perf_counter()
            try:
                batch = list(self._inflight.values())
                with self._span("serve.decode_chunk",
                                uids=[r.uid for r in batch]):
                    out = self._guard("serve.decode_chunk",
                                      self._chunk_call, *args,
                                      key=("chunk",))
                self._open_stages.append(
                    ("decode_chunk_s", "chunk", t0, batch))
                self._chunk_rows_hist.observe(len(batch))
                self._step_chunk_rows = len(batch)
                self.state = out
                self.chunks_run += 1
                return
            except (_ContainedFault, RetryError) as e:
                if self.paged and self.paged_impl == "pallas":
                    cause = e.__cause__
                    if (("chunk",) not in self._compiled_keys
                            and cause is not None and not isinstance(
                                cause, faults.InjectedFault)):
                        raise cause
                    self._activate_xla_fallback()
                    continue  # bit-identical retry on the degraded path
                if isinstance(e, RetryError):
                    raise
                self._fail_inflight()
                return

    def _fail_inflight(self) -> None:
        """Shed every in-flight request (``FAILED_FAULT``) after a fatal
        decode fault: the batch's device state can no longer be trusted
        to advance, but queued requests are untouched — the engine keeps
        serving."""
        slots = sorted(self._inflight)
        for slot in slots:
            self._shed(self._vacate(slot), FAILED_FAULT)
        self._deactivate(slots)

    def step(self) -> list[Completion]:
        """One engine iteration: shed expired requests, admit queued ones
        into free slots, decode one chunk, harvest newly finished slots.
        The return includes typed SHED completions recorded since the
        last step (e.g. queue-full sheds from ``submit()``)."""
        t_step = time.perf_counter()
        self._step_no += 1
        self._step_wait = 0.0
        # the PROCESS's number of this step, which its record and incidents
        # carry: the counter a reader finds the last N steps by, so two
        # engines in one process number their steps in one sequence
        self._steps.inc()
        step = self._steps.value
        gap = (None if self._last_return is None
               else t_step - self._last_return)
        self._last_return = None
        compiles_before = self._xla_compiles.value
        gc_before = self._gc_pauses.sum
        chunks_before = self.chunks_run
        self._step_stages = []
        self._step_admits = []
        self._step_chunk_rows = 0
        self._step_finished = 0
        self._step_done = None
        _compiles.set_step(step)   # on compiles and pauses filed meanwhile
        try:
            completed = self._run_step()
            now = time.perf_counter()
            # the host's self time: the step's wall less what it spent
            # waiting for the device in the flags fetch
            host = now - t_step - self._step_wait
            if self.chunks_run > chunks_before:
                self._step_host_hist.observe(host)
            compiled = self._xla_compiles.value - compiles_before
            if compiled:
                self._compiles_in_step.inc(compiled)
            self._judge_step(step, t_step, now, host, gap, compiled,
                             self._gc_pauses.sum - gc_before)
        finally:
            # a step that raised judges nothing and leaves nothing of
            # itself to the next one
            self._step_stages = None
            _compiles.set_step(None)
        self._last_return = now if self.has_work else None
        return completed

    def _run_step(self) -> list[Completion]:
        completed = self._drain_pending()
        if self._watchdog is not None:
            self._watchdog.beat("serve.step")
        self._shed_expired()
        self._release_forks()
        if not self._draining:
            if self.disagg:
                self._admit_from_handoff()
            else:
                self._admit_pending()
        completed += self._drain_pending()
        completed += self._harvest_done()  # instant EOS/length at admission
        if self._inflight:
            self._dispatch_chunk()
            completed += self._drain_pending()
            completed += self._harvest_done()
        if self._embed_queue and not self._draining:
            # embed AFTER the decode chunk for the same reason the disagg
            # prefill round runs there: in-flight decode never stalls
            # behind prefill-shaped work
            self._embed_round()
            completed += self._drain_pending()
        if self.disagg and not self._draining:
            # prefill AFTER the decode chunk: in-flight decode never
            # stalls behind a long prefill (the disaggregation p95 win);
            # when the decode pool is idle there is nothing to protect,
            # so admit eagerly rather than pay a step of TTFT latency.
            # A remote-prefill replica never runs the prefill stage at
            # all — handles arrive via admit_handle() from the transport
            if not self.remote_prefill:
                self._prefill_round()
            if not self._inflight and self._handoff:
                self._admit_from_handoff()
                completed += self._drain_pending()
                completed += self._harvest_done()
        # refresh the per-class/per-tenant gauges once per step so
        # heartbeat-ridden registry snapshots carry current depths
        self.qos_status()
        if self.paged:
            self._publish_cache_gauges()
        return completed

    def _judge_step(self, step: int, t_step: float, now: float, host: float,
                    gap: float | None, compiled: int, gc_s: float) -> None:
        """The end of a step that did not raise.  ONE record of it goes to
        the tracer's step log (docs/OBSERVABILITY.md section 3 has the
        fields), built from host numbers the step already holds.  The
        slow-step rule (SLOW_FACTOR above) judges the same numbers: where
        any of them stood still the record is filed a second time, as the
        incident ``serve.slow_step`` with ``which`` and ``excess``.  A
        stage's clock starts before its dispatch, so host seconds spent
        there (a compile) are in the stage too: they count once, as the
        host's."""
        stages = self._step_stages
        rows = self._step_chunk_rows
        runs = self._step_admits
        host_x = self._mean_host[_bucket(len(runs))].observe(host)
        gap_x = 0.0 if gap is None else self._mean_gap.observe(gap)
        device_x = 0.0
        for program, dt in stages:
            if program == "chunk":
                program = ("chunk", _bucket(rows))
            device_x += self._mean_stage[program].observe(dt)
        if host_x:
            device_x -= host_x
            if device_x < SLOW_FLOOR_S:
                device_x = 0.0
        rec = {
            "step": step, "t0": t_step, "wall": now - t_step, "host": host,
            "device_wait": self._step_wait, "gap": gap, "gc_s": gc_s,
            "compiles": compiled,
            "stages": [[str(p), dt] for p, dt in stages],
            "t_done": self._step_done, "chunk_rows": rows,
            "admitted": sum(n for n, _, _ in runs), "admit_runs": len(runs),
            "prefill_tokens_real": sum(real for _, real, _ in runs),
            "prefill_token_slots": sum(slots for _, _, slots in runs),
            "finished": self._step_finished,
        }
        self._tracer.step_record(rec)
        excess = host_x + gap_x + device_x
        if excess:
            which = max((host_x, "host"), (gap_x, "gap"),
                        (device_x, "device"))[1]
            self._tracer.incident("serve.slow_step", t_step, now - t_step,
                                  **rec, which=which, excess=excess)

    # ----------------------------------------- multi-process handoff API

    def admit_handle(self, handle: Handle) -> bool:
        """Remote-handoff admission source (docs/SERVING.md §7): push a
        deserialized prefill product into the bounded handoff queue
        beside the in-process path.  False when the queue is at depth —
        the transport keeps the frame buffered and retries after a
        ``step()`` frees a slot (cross-process backpressure)."""
        if not self.disagg:
            raise RuntimeError("admit_handle() requires disagg=True")
        if self._handoff.full():
            return False
        return self._handoff.put(handle)

    def run_prefill_round(self) -> Handle | None:
        """Run one prefill round and POP the produced handle instead of
        leaving it queued — the prefill-worker process serializes it onto
        the wire, so the local queue must not absorb the backpressure
        that belongs to the remote replicas (the worker's credit window
        does that).  None when the queue was empty or the round shed."""
        if not self.disagg:
            raise RuntimeError("run_prefill_round() requires disagg=True")
        before = len(self._handoff)
        self._prefill_round()
        if len(self._handoff) > before:
            return self._handoff.get()
        return None

    @property
    def embed_pending(self) -> int:
        return len(self._embed_queue)

    def run_embed_round(self) -> None:
        """Serve one embedding batch (if queued).  The prefill-worker
        process never calls ``step()``, so this is its path for running
        embed traffic; completions land in the pending list and ship
        home via :meth:`drain_sheds`."""
        self._embed_round()

    def drain_sheds(self) -> list[Completion]:
        """Collect typed shed completions recorded since the last call
        (submit-time sheds, failed prefill rounds).  The prefill-worker
        process never calls ``step()``, so this is its path for shipping
        sheds home as completion messages."""
        return self._drain_pending()

    def run_until_idle(self, max_chunks: int | None = None) -> list[Completion]:
        """Drain the queue and all in-flight slots; returns completions
        (served and shed) in finish order."""
        out: list[Completion] = []
        chunks0 = self.chunks_run
        while self.has_work:
            out.extend(self.step())
            if (max_chunks is not None
                    and self.chunks_run - chunks0 >= max_chunks):
                raise RuntimeError(
                    f"engine exceeded {max_chunks} chunks without draining "
                    f"({self.num_active} active, {self.pending} pending)"
                )
        return out

    # ----------------------------------------------------------- lifecycle

    def drain(self, max_chunks: int | None = None) -> list[Completion]:
        """Stop admission and finish all IN-FLIGHT requests.  The queue
        is left intact (snapshot it, or resume stepping); returns the
        completions finished during the drain."""
        self._draining = True
        try:
            out = self._drain_pending()
            chunks0 = self.chunks_run
            while self._inflight or self._pending:
                out.extend(self.step())
                if (max_chunks is not None
                        and self.chunks_run - chunks0 >= max_chunks):
                    raise RuntimeError(
                        f"drain exceeded {max_chunks} chunks with "
                        f"{self.num_active} slot(s) still active")
        finally:
            self._draining = False
        return out

    def snapshot(self, path: str | None = None) -> dict:
        """Host-side request state, enough to REPLAY every unfinished
        request on a fresh engine: prompt, sampling params, seed, and the
        remaining deadline budget.  Device caches are deliberately
        absent — trajectories depend only on (params, prime, seed,
        knobs), so replay-from-scratch is token-identical and the
        snapshot stays tiny and restore-compatible across engine shapes
        (slots, chunk size, paged or dense).  The generated-so-far prefix
        is stored for observability, not for resumption.

        In-flight slots are ordered before the queue so a restore serves
        older work first.  With ``path`` the snapshot is also written as
        JSON (atomic rename).
        """
        entries = []
        if self._inflight:
            active, seq, pos, start = _host_fetch(
                (self.state["active"], self.state["seq"],
                 self.state["pos"], self.state["start"]))
            for slot in sorted(self._inflight):
                r = self._inflight[slot]
                gen = (seq[slot, start[slot]: pos[slot] + 1].tolist()
                       if active[slot] else [])
                entries.append(self._snap_request(r, gen))
        if self.disagg:
            # handed-off-but-unmerged requests replay from scratch like
            # queued ones (their caches are rebuilt; token-identical)
            for h in self._handoff:
                for r in h.requests:
                    entries.append(self._snap_request(r, []))
        for r in self._queue:
            entries.append(self._snap_request(r, []))
        for followers in self._fork_wait.values():
            # held fork followers are queue-like: replay from scratch
            for r in followers:
                entries.append(self._snap_request(r, []))
        for r in self._embed_queue:
            e = self._snap_request(r, [])
            e["workload"] = "embed"
            entries.append(e)
        snap = {"version": 1, "kind": "serving_snapshot",
                "requests": entries}
        if path is not None:
            tmp = f"{path}.tmp"
            with open(tmp, "w") as fh:
                json.dump(snap, fh)
            os.replace(tmp, path)
        return snap

    def _snap_request(self, r: Request, generated) -> dict:
        entry = {
            "uid": r.uid,
            "tokens": [int(t) for t in r.tokens],
            "max_new_tokens": int(r.max_new_tokens),
            "top_k": None if r.top_k is None else int(r.top_k),
            "temperature": float(r.temperature),
            "seed": int(r.seed),
            "generated": [int(t) for t in generated],
        }
        if r.logit_mask is not None:
            from progen_tpu.workloads.infill import mask_to_wire
            m = np.asarray(r.logit_mask, bool)
            # a (V,) mask of every position travels as its allowed ids
            entry["logit_mask"] = ({"every": np.flatnonzero(m).tolist()}
                                   if m.ndim == 1 else mask_to_wire(m))
        if int(r.tenant) != 0:
            entry["tenant"] = int(r.tenant)
        if int(r.priority) != 0:
            entry["priority"] = int(r.priority)
        if r.record_fill_steps:
            entry["record_fill_steps"] = True
        deadline = self._deadline_of(r)
        if deadline is not None:
            # perf_counter instants do not survive a process restart;
            # the REMAINING budget does
            entry["deadline_remaining"] = max(
                0.0, deadline - time.perf_counter())
        return entry

    def restore(self, snap, *, on_complete=None) -> int:
        """Resubmit every request from a :meth:`snapshot` (dict or JSON
        path) onto this (idle) engine; returns the number accepted.
        Deadlines resume with their remaining budget.  Restored requests
        pass through the normal ``submit()`` path, so queue bounds and
        expired budgets shed exactly as live traffic would."""
        if isinstance(snap, (str, os.PathLike)):
            with open(snap) as fh:
                snap = json.load(fh)
        if snap.get("kind") != "serving_snapshot":
            raise ValueError("not a serving snapshot")
        if self._inflight or self._queue or self._embed_queue or \
                self._fork_wait or (self.disagg and self._handoff):
            raise RuntimeError("restore() requires an idle engine")
        now = time.perf_counter()
        accepted = 0
        for e in snap["requests"]:
            lmask = None
            if e.get("logit_mask") is not None:
                from progen_tpu.workloads.infill import mask_from_wire
                wire = e["logit_mask"]
                if isinstance(wire, dict):
                    lmask = np.zeros((self.family.vocab,), bool)
                    lmask[wire["every"]] = True
                else:
                    lmask = mask_from_wire(wire, self.family.vocab)
            r = Request(
                uid=e["uid"], tokens=e["tokens"],
                max_new_tokens=e["max_new_tokens"], top_k=e["top_k"],
                temperature=e["temperature"], seed=e["seed"],
                on_complete=on_complete, submit_time=now,
                logit_mask=lmask, tenant=int(e.get("tenant", 0)),
                priority=int(e.get("priority", 0)),
                record_fill_steps=bool(e.get("record_fill_steps", False)))
            if "deadline_remaining" in e:
                r.deadline = now + e["deadline_remaining"]
            if e.get("workload") == "embed":
                self.submit_embed(r)
            else:
                self.submit(r)
            accepted += 1
        return accepted

    # ----------------------------------------------------- warmup + counters

    def reload_weights(self, params=None, lora_bank=None, *,
                       generation: int | None = None) -> int:
        """Swap the served weights in place — no recompiles, no dropped
        slots.  Params (and the LoRA adapter bank) are real ARGUMENTS of
        every compiled program, so replacing the pytree with an
        identically-shaped one is just a different argument on the next
        dispatch; in-flight slots continue on the new weights from their
        next step, which is why the serving control plane instead swaps
        at WORKER granularity (drain old, route new) to keep
        per-generation determinism.  Returns the new generation tag
        (``generation`` when given, else the old tag + 1); completions
        finishing after the swap carry it.
        """
        if params is None and lora_bank is None:
            raise ValueError("reload_weights needs params and/or lora_bank")
        if lora_bank is not None and not self.lora:
            raise ValueError("engine was built without a LoRA bank; the "
                             "bank's shape is baked into its programs")
        if params is not None and self.quantize:
            # the serving tree is int8 + qscale; incoming checkpoints
            # arrive full precision and re-quantize at the door
            params = self._quantize_variables(
                jax.tree.map(jnp.asarray, params))

        def _swap(new, old, what):
            new = jax.tree.map(jnp.asarray, new)
            if jax.tree.structure(new) != jax.tree.structure(old):
                raise ValueError(f"reload_weights: {what} tree structure "
                                 "does not match the serving tree")
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
                if a.shape != b.shape or a.dtype != b.dtype:
                    raise ValueError(
                        f"reload_weights: {what} leaf mismatch "
                        f"{a.shape}/{a.dtype} vs {b.shape}/{b.dtype}")
            return new

        if self.lora:
            bundle = dict(self._params)
            if params is not None:
                bundle["base"] = _swap(params, self._params["base"],
                                       "params")
            if lora_bank is not None:
                from progen_tpu.workloads.lora import validate_lora_bank

                validate_lora_bank(self.config, lora_bank)
                bundle["adapters"] = _swap(
                    lora_bank, self._params["adapters"], "lora_bank")
            self._params = bundle
        else:
            self._params = _swap(params, self._params, "params")
        self.generation = (int(generation) if generation is not None
                           else self.generation + 1)
        return self.generation

    def aot_warmup(self, max_prime: int | None = None, *,
                   embed: bool = False) -> dict:
        """Explicitly compile the engine's whole program grid ahead of
        serving: one admission program per prefill bucket (``window *
        2^k`` up to ``max_prime``, default ``max_len - 1``) plus the
        decode-chunk program, via ``jit(...).lower().compile()``.  With
        ``embed=True`` the per-bucket embedding programs compile too
        (opt-in — engines that never see embed traffic skip the cost).  The
        compiled executables are dispatched directly afterwards, so a
        fresh (or restarted) process pays zero first-request compiles —
        the cold-start TTFT story (``benchmarks/bench_coldstart.py``).
        Composes with the persistent compilation cache
        (``core/cache.py``), which turns these compiles into disk hits.
        """
        t0 = time.perf_counter()
        as_shape = partial(jax.tree.map,
                           lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype))
        s = self.num_slots

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        # lower with the CONCRETE params/state so their shardings (mesh
        # mode) are captured; per-call host arrays lower as abstract
        params_sd, state_sd = as_shape(self._params), as_shape(self.state)
        programs = 0
        cap = min(max_prime or self.max_len - 1, self.max_len - 1)
        buckets = self.family.buckets(cap, self.max_len)
        u32 = partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
        f32 = partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        b8 = partial(jax.ShapeDtypeStruct, dtype=jnp.bool_)

        def prefill_sd(rows, p_pad):
            """``_prefill_worker_impl``'s arguments after ``params``."""
            sd = [i32(rows, p_pad), i32(rows), i32(rows), u32((rows,)),
                  i32(rows), f32((rows,)), b8(self._lmask_shape(rows))]
            return sd + [i32(rows)] if self.lora else sd

        def build(key, program, *shapes) -> int:
            """Compile ``program`` for ``shapes`` under ``key`` unless it
            is there already; the number of programs built."""
            if key in self._aot:
                return 0
            self._aot[key] = program.lower(*shapes).compile()
            self._compiled_keys.add(key)
            return 1

        rows, lay = self.admit_rows, self._layout
        for p_pad in buckets:
            if embed and self._embedder is not None:
                programs += build(
                    ("embed", p_pad), self._embedder,
                    as_shape(self._target_params(self._params)),
                    i32(s, p_pad), i32(s))
            if self.disagg:
                programs += build(("prefill", p_pad), self._prefill_worker,
                                  params_sd, *prefill_sd(s, p_pad))
            else:
                programs += build(
                    ("admit", p_pad), self._admit, params_sd, state_sd,
                    i32(s), b8((s,)), *prefill_sd(rows, p_pad),
                    *as_shape(lay.write_tables(rows)))
        if self.disagg and ("merge",) not in self._aot:
            # the handle's shape is bucket-independent (everything is
            # harvested to max_len), so any bucket's worker sizes it
            h_sd = jax.eval_shape(self._prefill_worker_impl, params_sd,
                                  *prefill_sd(s, buckets[0]))
            programs += build(
                ("merge",), self._merge, state_sd, *lay.split_handle(h_sd),
                i32(s), b8((s,)), *as_shape(lay.write_tables(s)))
        programs += build(("chunk",), self._decode_chunk, params_sd,
                          state_sd, *as_shape(lay.chunk_operands()))
        # the one-operation program that clears finished slots' flags: not
        # counted, but built here like everything step() runs
        build(("release",), _clear_rows, state_sd["active"], b8((s,)))
        return {"programs": programs,
                "seconds": time.perf_counter() - t0}

    def status(self) -> dict:
        """Live engine state for the /statusz endpoint — HOST bookkeeping
        only (queues, slot maps, counters, stage walls).  This is served
        from the statusz HTTP thread concurrently with the stage loop, so
        it must never sync the device: everything read here is a plain
        host dict/int the GIL keeps coherent."""
        active = len(self._inflight)
        return {
            "slots": {"total": self.num_slots, "active": active,
                      "free": self.num_slots - active},
            "queue_depth": len(self._queue),
            "embed_queue_depth": len(self._embed_queue),
            "pending_completions": len(self._pending),
            "inflight_uids": sorted(r.uid for r in
                                    list(self._inflight.values())),
            "chunks_run": self.chunks_run,
            # programs compiled inside a step(): 0 for ever once warm
            "compiles_in_step": self._compiles_in_step.value,
            # the newest records of the process's step log (_judge_step)
            "last_steps": self._tracer.steps()[-LAST_STEPS:],
            # lowering of the chunk program's cache writes, of a latent
            # attention's prefill and decode cores and of a grouped-query
            # attention's prefill and decode cores; None until a program
            # that holds the op has been traced
            "row_write": self.lowerings.get("row_write"),
            "mla_prefill": self.lowerings.get("mla_prefill"),
            "mla_decode": self.lowerings.get("mla_decode"),
            "gqa_prefill": self.lowerings.get("gqa_prefill"),
            "gqa_decode": self.lowerings.get("gqa_decode"),
            # the core of a step of B queries a slot (a family that
            # generates by blocks)
            "gqa_block_decode": self.lowerings.get("gqa_block_decode"),
            # the held experts' product, by program: {"chunk": ..,
            # "admit": ..} as far as traced, None for a family without
            "moe_experts": self._lowering_by_program("moe_experts"),
            # how the terms of an admission's sorted experts reach their
            # tokens' rows (models/experts.py:_sorted), by program; None
            # where no program sorts
            "moe_combine": self._lowering_by_program("moe_combine"),
            # how the draw's k-th largest logit is counted: all rows in
            # one loop ("xla") or a group of rows at a time, where only a
            # group's keys stay on the chip ("xla_tiled"), by program
            "sample_kth": self._lowering_by_program("sample_kth"),
            # the same of an admission's learned selection (ops/dsa.py);
            # None for a family without
            "dsa_kth": self._lowering_by_program("dsa_kth"),
            # every lowering the traced programs noted (ops/lowering.py),
            # whole: an op a family brings (``ssd_*``, ``gdn_*``,
            # ``kda_*``) shows here without a key of its own above
            "lowerings": dict(self.lowerings),
            # the device counters as last fetched with the slot flags,
            # under their registry names ({} for a family without)
            "model_stats": dict(self.model_gauges),
            "paged": self.paged,
            "disagg": self.disagg,
            "stage_seconds": {k: round(v, 6) for k, v in
                              list(self.stage_seconds.items())},
            "qos": self.qos_status(),
            "cache": self.cache_status(),
            "robust": self.robustness_counters(),
        }

    def _lowering_by_program(self, op: str) -> dict | None:
        """``{"chunk": .., "admit": ..}`` as far as traced: the lowering
        ``op`` took in each program that holds it; None where none does."""
        return {program: took[op]
                for program, took in self.program_lowerings.items()
                if op in took} or None

    def cache_status(self) -> dict | None:
        """Prefix-cache occupancy and sharing for /statusz — host dicts
        only, safe from the statusz thread.  None on dense engines."""
        if not self.paged:
            return None
        pool = self._pool.stats()
        hits, lookups = self.prefix_hits, self.prefix_lookups
        return {
            "prefix_hits": hits,
            "prefix_lookups": lookups,
            "hit_rate": (hits / lookups) if lookups else 0.0,
            "pages_shared": pool["shared_pages"],
            "cached_pages": pool["cached_pages"],
            "free_pages": pool["free_pages"],
            "capacity": pool["capacity"],
            "fork_groups": self.fork_groups,
        }

    def prefix_digest(self) -> dict | None:
        """Compact advertisement of this engine's cached prefixes for
        fleet-scope routing (rides worker heartbeat/stats frames); None
        on dense engines, which cache nothing."""
        if not self.paged:
            return None
        return self._pool.prefix_digest()

    def _publish_cache_gauges(self) -> None:
        """Mirror cache counters into registry gauges so heartbeats and
        /metricsz carry per-worker hit-rate inputs without a bench run."""
        registry = _metrics.get_registry()
        registry.gauge("engine.prefix_hits").set(self.prefix_hits)
        registry.gauge("engine.prefix_lookups").set(self.prefix_lookups)
        registry.gauge("engine.prefix_pages_shared").set(
            self._pool.shared_pages)
        registry.gauge("engine.pool_free_pages").set(self._pool.free_pages)
        registry.gauge("engine.pool_pages_in_use").set(
            self._pool.capacity - self._pool.free_pages)

    def qos_status(self) -> dict:
        """Per-class / per-tenant queue + in-flight occupancy and the
        scheduler's cumulative tallies — host dicts only, safe from the
        statusz thread.  Also refreshes the labeled Prometheus gauges so
        a /metricsz scrape sees current depths."""
        out = dict(self._queue.stats())
        inflight_by_class: dict = {}
        inflight_by_tenant: dict = {}
        for r in list(self._inflight.values()):
            inflight_by_class[r.priority] = (
                inflight_by_class.get(r.priority, 0) + 1)
            inflight_by_tenant[r.tenant] = (
                inflight_by_tenant.get(r.tenant, 0) + 1)
        out["inflight_by_class"] = inflight_by_class
        out["inflight_by_tenant"] = inflight_by_tenant
        out["preemptions"] = self.robust.preemptions
        self._publish_qos_gauges(out)
        return out

    def _publish_qos_gauges(self, qos: dict) -> None:
        """Mirror the per-class/per-tenant occupancy into labeled
        registry gauges (Prometheus exposition + worker heartbeats).
        Label keys ever seen are re-set every refresh so a drained class
        reads 0 instead of its last nonzero value."""
        registry = _metrics.get_registry()
        fresh: set = set()
        for name, label, table in (
                ("engine.queue_depth", "priority", qos["queue_by_class"]),
                ("engine.queue_depth", "tenant", qos["queue_by_tenant"]),
                ("engine.inflight", "priority", qos["inflight_by_class"]),
                ("engine.inflight", "tenant", qos["inflight_by_tenant"])):
            for key, n in table.items():
                gname = _metrics.labeled(name, **{label: key})
                registry.gauge(gname).set(n)
                fresh.add(gname)
        for gname in self._qos_gauge_keys - fresh:
            registry.gauge(gname).set(0)
        self._qos_gauge_keys |= fresh
        registry.gauge("engine.preemptions").set(self.robust.preemptions)

    def robustness_counters(self) -> dict:
        """Everything a chaos record needs: shed/containment tallies,
        faults fired by the armed plan, QoS scheduling tallies, and
        (paged) pool pressure."""
        out = dict(self.robust.as_dict())
        injector = faults.get()
        out["faults_fired"] = injector.fired() if injector is not None else 0
        out["qos"] = self._queue.stats()
        if self.paged:
            out["evictions"] = self.evictions
            out["pause_events"] = self.pause_events
            out["prefix_hits"] = self.prefix_hits
            out["prefix_lookups"] = self.prefix_lookups
            out["fork_groups"] = self.fork_groups
            out["pool"] = self._pool.stats()
        if self.disagg:
            out["handoff"] = self._handoff.stats()
        return out


def run_with_restarts(engine_factory, requests=(), *, attempts: int = 3,
                      snapshot_path: str | None = None,
                      max_chunks: int | None = None,
                      classifier=default_classifier) -> list[Completion]:
    """Serve ``requests`` to completion across engine crashes: the
    serving twin of the trainer's ``--run_attempts`` resume loop.

    When a transient failure escapes the engine's in-place containment
    (a :class:`RetryError`, or anything ``classifier`` calls transient),
    the unfinished requests are snapshotted, a FRESH engine is built via
    ``engine_factory()``, the snapshot is restored onto it, and serving
    resumes.  Completions harvested before a crash are final (they are
    absent from the snapshot, so nothing double-serves); replayed
    requests are token-identical to an uninterrupted run because
    trajectories depend only on (params, prime, seed, knobs).
    Non-transient failures and attempt exhaustion re-raise.
    """
    out: list[Completion] = []
    engine = engine_factory()
    for r in requests:
        engine.submit(r)
    for attempt in range(1, max(1, attempts) + 1):
        try:
            out.extend(engine.run_until_idle(max_chunks=max_chunks))
            return out
        except Exception as e:
            if attempt >= attempts or not classifier(e):
                raise
            out.extend(engine.completions[:])
            snap = engine.snapshot(snapshot_path)
            print(f"serving: attempt {attempt} crashed ({e!r}); "
                  f"restarting and replaying {len(snap['requests'])} "
                  f"request(s)", flush=True)
            engine = engine_factory()
            engine.restore(snap)
    return out
