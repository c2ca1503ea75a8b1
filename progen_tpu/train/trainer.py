"""Training driver — the framework equivalent of the reference's
``train.py`` main loop (``/root/reference/train.py:59-228``), re-structured
for TPU:

* resume -> model/optimizer/state assembly -> epoch/step loop with
  grad-accum micro-steps, periodic validation, sampling and checkpointing
  (same cadence semantics, same resume-by-skip data contract);
* the loss is fetched to host only every ``log_every`` steps — the
  reference blocks on ``loss.item()`` EVERY step (``train.py:198``), a
  per-step device→host sync listed as a conscious drop in SURVEY.md §7;
* checkpoint step ids are global optimizer steps (monotonic across
  epochs), not the reference's per-epoch ``i`` which re-checkpoints at
  ``i == 0`` of every epoch;
* sampling uses the cached scan decoder, not O(L) full forwards;
* multi-host aware: per-host data sharding follows the mesh's batch
  shards (``core.mesh.process_batch_shards``) so inner mesh axes —
  tensor/seq — may span processes, with one writer for checkpoints/logs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.checkpoint import CheckpointStore, abstract_state_like
from progen_tpu.parallel.sharding import (
    batch_sharding, superbatch_sharding, validate_tp_divisibility,
)
from progen_tpu.core.mesh import (
    Mesh, MeshConfig, make_mesh, process_batch_shards,
)
from progen_tpu.core.precision import make_policy
from progen_tpu.core.rng import KeySeq
from progen_tpu.data import decode_tokens, iterator_from_tfrecords_folder
from progen_tpu.data.prefetch import DevicePrefetcher, SuperbatchStager
from progen_tpu.decode import make_sampler
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.observe import compiles
from progen_tpu.observe import (
    ThroughputMeter,
    Tracker,
    get_registry,
    get_tracer,
    mfu,
    model_flops_per_token,
    peak_flops_per_chip,
    profile_trace,
)
from progen_tpu.resilience import faults
from progen_tpu.resilience.retry import RetryError, default_classifier
from progen_tpu.resilience.watchdog import FlightRecorder, Watchdog
from progen_tpu.train.memory import check_fits, device_hbm_bytes
from progen_tpu.train.memory import plan as memory_plan
from progen_tpu.train.optimizer import make_optimizer
from progen_tpu.train.schedule import make_lr_schedule
from progen_tpu.train.step import make_train_functions


def superstep_span(global_step: int, k_max: int, cadences: Sequence[int],
                   remaining: int) -> int:
    """Optimizer steps the next fused dispatch may cover: the distance
    from ``global_step`` to the NEAREST hook boundary among ``cadences``
    (every-N step counts; a hook fires when ``global_step % every == 0``),
    capped by ``k_max`` and the ``remaining`` epoch/max_steps budget.

    Always >= 1.  A span never crosses a boundary, and it ENDS exactly on
    the nearest boundary whenever that is within ``k_max`` steps — so
    every hook fires at the same global_step as the per-step loop, never
    skipped and never doubled."""
    span = min(k_max, remaining)
    for every in cadences:
        if every and every > 0:
            span = min(span, every - global_step % every)
    return max(1, span)


@dataclasses.dataclass
class TrainerConfig:
    # reference train.py:36-58 flags
    seed: int = 42
    batch_size: int = 4            # per-host micro-batch
    grad_accum_every: int = 4
    epochs: int = 100
    learning_rate: float = 2e-4
    weight_decay: float = 1e-3
    max_grad_norm: float = 0.5
    validate_every: int = 100
    sample_every: int = 500
    checkpoint_every: int = 1000
    checkpoint_keep_n: int = 500
    prime_length: int = 25
    mixed_precision: bool = True
    # tf.data sliding-window shuffle over the (pre-shuffled-at-prep) record
    # stream; 0 = off, matching the reference, whose only shuffle happens
    # at data prep (generate_data.py:119). Resume-by-skip is deterministic
    # even when shuffled: the skip applies to the seeded shuffle's OUTPUT
    # (data/tfrecord.py), replaying the interrupted run's record order.
    shuffle_buffer: int = 0
    # LR schedule (reference is constant-lr; warmup/decay needed >=1.2B)
    lr_schedule: str = "constant"  # "constant" | "cosine" | "linear"
    warmup_steps: int = 0
    schedule_steps: int | None = None  # decay horizon; defaults to max_steps
    lr_min_ratio: float = 0.1
    # TPU-native additions
    strategies: Sequence[str] = ("dp",)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots" | "attn" (ProGen.remat_policy)
    attn_impl: str = "xla"  # "xla" | "pallas"
    sgu_impl: str = "xla"  # "xla" | "pallas" (blocked-causal fused SGU)
    # input-feed double buffering: batches transferred to device ahead of
    # the step that consumes them (0 = synchronous reference-style feed)
    prefetch_depth: int = 2
    # fused multi-step training: up to K optimizer steps per XLA dispatch
    # (train_multi_step's lax.scan over a staged (K, accum, B, L)
    # superbatch; 1 = classic per-step dispatch).  Spans shrink
    # automatically to land exactly on hook boundaries, so cadence
    # semantics are unchanged; costs ~2 superbatches of extra HBM
    # (train/memory.py accounts it).
    superstep: int = 1
    # checkpoint without stalling training: snapshot the state on-device
    # (one extra state-sized HBM copy) and run the device->host fetch +
    # write in a background thread.  The fetch is the dominant cost on
    # slow host links (orbax's async mode only backgrounds the DISK
    # write, its device->host copy blocks by design; the stall per save
    # is not measured on today's code).  Disable when HBM headroom
    # cannot afford the snapshot copy.
    background_checkpoint: bool = True
    log_every: int = 10
    sample_top_k: int = 25         # reference hardcodes 25 (train.py:224)
    profile_dir: str | None = None
    max_steps: int | None = None   # optional hard stop (tests/benches)
    # -- resilience ---------------------------------------------------------
    # pre-loop sampler warm execution (minutes of decode compile on real
    # configs): off, a cold compile stalls the loop at the first
    # sample_every hook instead; independent of the flag, the warm-up is
    # skipped whenever no sample hook can fire in this run (e.g. a
    # preemption restart close to max_steps)
    warm_sampler: bool = True
    # total tries of the train loop: on a TRANSIENT failure (I/O retry
    # exhaustion, a dropped connection...) the trainer re-restores from the
    # latest checkpoint and continues, up to run_attempts-1 times; fatal
    # errors always propagate immediately.  1 = fail fast (library
    # default; the train.py CLI defaults to 3).
    run_attempts: int = 1
    # seconds without a completed step before the watchdog dumps all
    # thread stacks + the flight-recorder ring to watchdog_dir and exits
    # nonzero (None = off).  Size it to several worst-case step times —
    # a hung collective never returns, a slow step does.
    watchdog_timeout: float | None = None
    watchdog_dir: str | None = None   # default: the tracker's run dir
    flight_recorder_n: int = 64       # last-N-events ring
    # live introspection: /healthz /statusz /metricsz /tracez /flightz on
    # a loopback port (0 = ephemeral, printed at startup; None = off).
    # Handlers read host-side state only — never a device sync.
    statusz_port: int | None = None


class Trainer:
    def __init__(
        self,
        model_config: ProGenConfig,
        cfg: TrainerConfig,
        data_path: str,
        checkpoint_path: str,
        tracker: Tracker | None = None,
        use_mesh: bool = True,
    ):
        self.model_config = model_config
        self.cfg = cfg
        self.data_path = data_path
        if cfg.superstep < 1:
            raise ValueError(f"superstep must be >= 1, got {cfg.superstep}")
        self.policy = make_policy(cfg.mixed_precision)
        self.mesh: Mesh | None = make_mesh(cfg.mesh) if use_mesh else None
        if (
            self.mesh is not None
            and self.mesh.shape.get("seq", 1) > 1
            and "sp" not in cfg.strategies
        ):
            raise ValueError(
                "mesh has seq axis "
                f"{self.mesh.shape['seq']} but 'sp' is not in strategies "
                f"{tuple(cfg.strategies)} — the seq devices would replicate "
                "work; add 'sp' or set MeshConfig(seq=1)"
            )
        if (
            self.mesh is not None
            and self.mesh.shape.get("tensor", 1) > 1
            and "tp" not in cfg.strategies
        ):
            raise ValueError(
                "mesh has tensor axis "
                f"{self.mesh.shape['tensor']} but 'tp' is not in strategies "
                f"{tuple(cfg.strategies)} — the tensor devices would "
                "replicate work; add 'tp' or set MeshConfig(tensor=1)"
            )
        if self.mesh is not None:
            # a tensor size that can't divide the model dims fails GSPMD
            # deep inside partitioning; fail here with the actual mistake
            validate_tp_divisibility(
                model_config, self.mesh.shape.get("tensor", 1),
                cfg.strategies)
        # Data-loading topology: the batch dim shards over ('data','fsdp')
        # only, so on a process-SPANNING tensor/seq axis several processes
        # sit at the same batch coordinates and must load IDENTICAL rows.
        # All per-process batch math below keys off the number of distinct
        # batch shards across processes — NOT jax.process_count(), which
        # over-counts whenever an inner axis spans processes.
        if self.mesh is not None and jax.process_count() > 1:
            self.data_shard_count, self.data_shard_index = (
                process_batch_shards(self.mesh))
        else:
            self.data_shard_count = jax.process_count()
            self.data_shard_index = jax.process_index()
        # The model needs the mesh when sequence mixing must be explicit:
        # sp routes attention/SGU through the context-parallel ops, and
        # pallas attention/SGU always run full-manual inside shard_map on a
        # mesh (pallas_call has no GSPMD partitioning rule).
        cp_mesh = (
            self.mesh
            if self.mesh is not None
            and ("sp" in cfg.strategies
                 or cfg.attn_impl == "pallas"
                 or cfg.sgu_impl == "pallas")
            else None
        )
        self.model = ProGen(config=model_config, policy=self.policy,
                            remat=cfg.remat, remat_policy=cfg.remat_policy,
                            attn_impl=cfg.attn_impl, sgu_impl=cfg.sgu_impl,
                            mesh=cp_mesh)
        self.lr_schedule = make_lr_schedule(
            cfg.lr_schedule,
            cfg.learning_rate,
            warmup_steps=cfg.warmup_steps,
            decay_steps=cfg.schedule_steps or cfg.max_steps,
            min_lr_ratio=cfg.lr_min_ratio,
        )
        self.optimizer = make_optimizer(
            learning_rate=self.lr_schedule,
            weight_decay=cfg.weight_decay,
            max_grad_norm=cfg.max_grad_norm,
            grad_accum_every=cfg.grad_accum_every,
        )
        # fail fast on configurations that cannot fit the chip — the
        # planner is calibrated to ~1% of XLA's buffer assignment
        # (progen_tpu/train/memory.py), so this replaces a many-minute
        # compile ending in RESOURCE_EXHAUSTED with an instant, actionable
        # error.  PROGEN_SKIP_MEMORY_CHECK=1 overrides.
        import os as _os

        if _os.environ.get("PROGEN_SKIP_MEMORY_CHECK") != "1":
            self.memory_plan = memory_plan(
                model_config,
                batch_size=cfg.batch_size * self.data_shard_count,
                mesh_shape=dict(self.mesh.shape) if self.mesh else None,
                strategies=cfg.strategies,
                remat=cfg.remat,
                remat_policy=cfg.remat_policy,
                attn_impl=cfg.attn_impl,
                sgu_impl=cfg.sgu_impl,
                mixed_precision=cfg.mixed_precision,
                grad_accum_every=cfg.grad_accum_every,
                checkpoint_snapshot=(cfg.background_checkpoint
                                     and jax.process_count() == 1),
                superstep_k=cfg.superstep,
            )
            gate_device = jax.local_devices()[0]
            err = check_fits(self.memory_plan, device_hbm_bytes(gate_device),
                             device_kind=gate_device.device_kind)
            if err is not None:
                raise ValueError(err)

        sample_tokens = jnp.zeros(
            (cfg.batch_size, model_config.seq_len), jnp.int32
        )
        self.fns = make_train_functions(
            self.model, self.optimizer, sample_tokens,
            mesh=self.mesh, strategies=cfg.strategies,
            grad_accum_every=cfg.grad_accum_every,
            lr_schedule=self.lr_schedule,
        )
        self.data_sharding = (
            batch_sharding(self.mesh) if self.mesh is not None else None
        )
        self.super_sharding = (
            superbatch_sharding(self.mesh) if self.mesh is not None else None
        )
        self.store = CheckpointStore(checkpoint_path, cfg.checkpoint_keep_n)
        self.tracker = tracker or Tracker(disabled=True)
        # in-training sampling runs against the params IN their training
        # shardings — they are never gathered to one chip
        self.sampler = make_sampler(
            model_config, self.policy, mesh=self.mesh,
            strategies=cfg.strategies,
            params_shardings=(
                self.fns.state_shardings.params
                if self.fns.state_shardings is not None else None
            ),
        )
        self.keys = KeySeq(cfg.seed)
        # 12 sync intervals (~300 steps at log_every 25): long enough to
        # be "sustained", short enough that the logged rate actually
        # slides past cold-start artifacts instead of averaging over the
        # whole run forever
        self.meter = ThroughputMeter(window=12)
        # Preemption safety (TPU VMs are preemptible; the reference's only
        # fault story is its periodic checkpoint): single-process runs get
        # a SIGTERM handler that requests a checkpoint at the next step
        # boundary; multi-host runs use orbax's coordination-service-backed
        # reached_preemption so all hosts agree (a per-host signal flag
        # would desync the cooperative save).
        self._preempt_requested = False
        self._ckpt_thread = None
        # flight recorder always on (O(1) dict appends); the watchdog
        # only when configured.  The recorder outlives run() attempts so
        # a post-retry dump still shows the pre-failure history.
        self._recorder = FlightRecorder(cfg.flight_recorder_n)
        # span ring shares the process tracer (enabled via
        # configure_tracing by the entry point); every trainer span also
        # lands in the flight recorder so a watchdog trip shows the
        # loop's recent phases even when tracing is off
        self._tracer = get_tracer()
        # compiles come from the process's listeners (observe/compiles.py);
        # ``train.recompiles`` counts those that fell in the dispatch of a
        # step program that had been dispatched before: 0 for ever
        compiles.install()
        self._xla_compiles = get_registry().counter("xla.compiles")
        self._recompiles = get_registry().counter("train.recompiles")
        self._watchdog: Watchdog | None = None
        # live introspection plane: health/status read the flight
        # recorder and registry (host floats published at the loop's one
        # batched device_get) — an enabled trainer runs the identical
        # step sequence, the plane never syncs the device
        self._statusz = None
        if cfg.statusz_port is not None and jax.process_index() == 0:
            from progen_tpu.observe.statusz import StatuszServer

            self._statusz = StatuszServer(
                role="trainer", port=cfg.statusz_port,
                providers={"health": self._statusz_health,
                           "status": self._statusz_status,
                           "flight": self._recorder.snapshot})
            port = self._statusz.start()
            print(f"trainer statusz on http://127.0.0.1:{port}",
                  flush=True)
        if jax.process_count() == 1:
            import signal

            try:
                signal.signal(signal.SIGTERM, self._request_preempt_checkpoint)
            except ValueError:
                pass  # not the main thread (e.g. under a test runner)

    def _request_preempt_checkpoint(self, signum=None, frame=None) -> None:
        self._preempt_requested = True

    @contextlib.contextmanager
    def _phase(self, name: str, **fields: Any):
        """One loop phase -> the tracer's span (profiler annotation, and
        the ring when enabled) AND a flight-recorder event, so a watchdog
        trip shows the recent phase history whether or not the process is
        tracing (the recorder is always on).  Yields ``fields``: the
        block may add what it learns (a validation loss)."""
        t0 = time.perf_counter()
        with self._tracer.span(name) as span:
            yield fields
            span.note(**fields)
        self._recorder.record(name, dur_s=round(time.perf_counter() - t0, 6),
                              **fields)

    def _statusz_health(self) -> dict:
        events = self._recorder.snapshot()
        last_step = None
        for e in reversed(events):
            if e.get("kind") == "step":
                last_step = e
                break
        return {"last_step": last_step,
                "watchdog": self._watchdog is not None,
                "preempt_requested": self._preempt_requested}

    def _statusz_status(self) -> dict:
        return {"model": self.model_config.to_dict(),
                "superstep": self.cfg.superstep,
                "batch_size": self.cfg.batch_size,
                "max_steps": self.cfg.max_steps,
                "recent": self._recorder.snapshot()[-16:]}

    def _publish_train_health(self, log: dict, step: int) -> None:
        """Training-health sentinels into the shared registry: the
        trainer's /statusz shows training health, not just serving.
        ``log`` holds host floats from the loop's one batched
        ``jax.device_get`` — this publishes them without any extra
        device sync."""
        registry = get_registry()
        registry.gauge("train.step").set(step)
        registry.gauge("train.loss").set(log["loss"])
        registry.gauge("train.grad_norm").set(log["grad_norm"])
        registry.gauge("train.lr").set(log["lr"])
        if not (math.isfinite(log["loss"])
                and math.isfinite(log["grad_norm"])):
            registry.counter("train.nonfinite_steps").inc()

    def _to_device(self, np_batch) -> jax.Array:
        """Host batch -> device array for the jitted step.

        Multi-process (one controller per host): every host holds only ITS
        data shard's rows of the global batch (processes sharing a batch
        coordinate — e.g. the members of a process-spanning tensor axis —
        hold identical copies); ``make_array_from_process_local_data``
        assembles the global sharded array without any host ever
        materializing the full batch.  The global shape is passed
        explicitly: with replication across tensor-axis processes the
        per-dimension inference would over-scale the batch dim.  Single
        process: a plain transfer (jit's in_shardings lay it out)."""
        if self.mesh is not None and jax.process_count() > 1:
            local = np.asarray(np_batch)
            return jax.make_array_from_process_local_data(
                self.data_sharding, local,
                (local.shape[0] * self.data_shard_count,) + local.shape[1:],
            )
        return jnp.asarray(np_batch)

    def _super_to_device(self, np_superbatch) -> jax.Array:
        """Host ``(K, accum, B, L)`` superbatch -> device array for the
        fused step; multi-process, every host contributes its data shard's
        rows of the batch dim (axis 2) — K and accum are replicated scan
        axes, and tensor-axis processes contribute identical copies."""
        if self.mesh is not None and jax.process_count() > 1:
            local = np.asarray(np_superbatch)
            gshape = (local.shape[0], local.shape[1],
                      local.shape[2] * self.data_shard_count, local.shape[3])
            return jax.make_array_from_process_local_data(
                self.super_sharding, local, gshape
            )
        return jnp.asarray(np_superbatch)

    def _warm_compiles(self, state, global_step: int = 0) -> None:
        """AOT-compile every jitted program the loop will call, BEFORE the
        throughput meter starts — the decode scan alone is minutes of
        compile cold, and paying it mid-loop stalls training (measured: a
        ~5.5-minute sampler compile at the first sample_every hook of the
        round-3 run).  Only active when the persistent XLA cache is on
        (the CLIs enable it): ``lower().compile()`` populates the on-disk
        cache the later jit call reads, but without that cache the warm
        work could not be reused and would just double compile time."""
        cfg = self.cfg
        have_disk_cache = bool(jax.config.jax_compilation_cache_dir)

        def abstract(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    jnp.shape(x), x.dtype, sharding=getattr(x, "sharding", None)
                ),
                tree,
            )

        st = abstract(state)
        # the REAL batch is global — cfg.batch_size rows per data shard
        # assembled via make_array_from_process_local_data (_to_device) —
        # so the warm program must match that shape+sharding or multi-host
        # runs (the ones that compile slowest) still compile cold at step 1
        batch = jax.ShapeDtypeStruct(
            (cfg.batch_size * self.data_shard_count,
             self.model_config.seq_len + 1),
            jnp.int32,
            sharding=self.data_sharding,
        )
        # the real sampler call feeds prime/key REPLICATED over the global
        # mesh (_replicated_prime_and_key); the warm program must carry the
        # same shardings or the multi-host compile-cache entry never
        # matches the mid-loop call and step-1 still compiles cold
        repl = None
        if self.mesh is not None and jax.process_count() > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
        prime = jax.ShapeDtypeStruct((1, cfg.prime_length), jnp.int32,
                                     sharding=repl)
        key0 = jax.random.key(0)
        key_abstract = jax.ShapeDtypeStruct(key0.shape, key0.dtype,
                                            sharding=repl)

        # a hook that cannot fire between here and the end of the run
        # (resume near max_steps, or a cadence past the horizon) buys
        # nothing from warming — notably the sampler's minutes-long decode
        # compile on a preemption restart
        ms = cfg.max_steps  # None = epochs-bounded: assume hooks fire

        def hook_due(every: int) -> bool:
            next_hook = (global_step // every + 1) * every
            return ms is None or next_hook <= ms

        validate_due = hook_due(cfg.validate_every)
        sample_due = cfg.warm_sampler and hook_due(cfg.sample_every)

        if cfg.superstep > 1:
            # the superstep loop dispatches exactly two program shapes:
            # the full-K fused scan and the K=1 residual used to walk up
            # to hook boundaries (_run_loop_superstep)
            def super_abstract(k):
                return jax.ShapeDtypeStruct(
                    (k, max(1, cfg.grad_accum_every),
                     cfg.batch_size * self.data_shard_count,
                     self.model_config.seq_len + 1),
                    jnp.int32,
                    sharding=self.super_sharding,
                )

            programs = [
                ("train_multi_step", lambda: self.fns.train_multi_step.lower(
                    st, super_abstract(cfg.superstep))),
                ("train_multi_step[k=1]",
                 lambda: self.fns.train_multi_step.lower(
                     st, super_abstract(1))),
            ]
        else:
            programs = [
                ("train_step", lambda: self.fns.train_step.lower(st, batch)),
            ]
        if validate_due:
            programs.append(
                ("eval_step", lambda: self.fns.eval_step.lower(st, batch)))
        if sample_due:
            programs.append(
                ("sampler", lambda: self.sampler.lower(
                    {"params": st.params}, key_abstract, prime,
                    length=self.model_config.seq_len,
                    top_k=cfg.sample_top_k,
                )))
        if have_disk_cache:
            # without the persistent cache, lower().compile() work could
            # not be reused by the later jit calls and would just double
            # compile time; the execution warm-up below covers that case
            for name, lower in programs:
                try:
                    lower().compile()
                except Exception as e:
                    # warming is an optimization; the loop compiles on
                    # demand
                    if jax.process_index() == 0:
                        print(f"warning: {name} precompile failed ({e!r})")

        # lower().compile() fills the DISK cache, but the loop's jit calls
        # still pay a fresh trace + cache deserialization the first time
        # they run — measured ~20s at the first validate_every hook of a
        # small-config run, a mid-loop stall the throughput window eats.
        # Execute the two NON-DONATING programs once here so their
        # in-memory executables exist before the meter starts (train_step
        # donates its state buffers, so its first-call load stays at step
        # 1, inside the startup ramp).  Runs with or without the disk
        # cache; skipped for hooks the run can provably never reach.
        # separate try blocks: a failed eval warm-up must not skip the
        # sampler warm-up (whose mid-loop stall is the larger one)
        if validate_due:
            try:
                dummy = self._to_device(np.zeros(
                    (cfg.batch_size, self.model_config.seq_len + 1),
                    np.int32))
                jax.block_until_ready(self.fns.eval_step(state, dummy))
            except Exception as e:
                if jax.process_index() == 0:
                    print(f"warning: eval warm execution failed ({e!r})")
        if sample_due:
            try:
                prime_arr, key = self._replicated_prime_and_key(
                    np.zeros((1, cfg.prime_length), np.int32),
                    jax.random.key(0))
                jax.block_until_ready(self.sampler(
                    {"params": state.params}, key, prime_arr,
                    length=self.model_config.seq_len, top_k=cfg.sample_top_k,
                ))
            except Exception as e:
                if jax.process_index() == 0:
                    print(f"warning: sampler warm execution failed ({e!r})")

    # -- state ---------------------------------------------------------------

    def restore_or_init(self):
        """Returns (state, start_seq_index, run_id). Restores the latest
        checkpoint when one exists (model config in the checkpoint wins —
        reference train.py:101-102)."""
        meta = self.store.restore_meta()
        if meta is None:
            state = self.fns.init_state(next(self.keys))
            return state, 0, None
        stored_cfg = ProGenConfig.from_dict(meta["model_config"])
        if stored_cfg != self.model_config:
            raise ValueError(
                "checkpoint model config differs from requested config; "
                "rebuild the Trainer with the stored config: "
                f"{stored_cfg}"
            )
        state = self.store.restore_state(abstract_state_like(self.fns))
        return state, meta["next_seq_index"], meta.get("run_id")

    # -- loop ----------------------------------------------------------------

    def run(self) -> dict[str, Any]:
        """Crash-safe driver: up to ``cfg.run_attempts`` tries of the train
        loop.  A TRANSIENT failure (I/O retry exhaustion, a dropped
        connection, injected fault) re-restores from the latest checkpoint — at worst
        replaying the steps since the last save — and continues; fatal
        errors (and exhaustion of the attempt budget) propagate."""
        attempts = max(1, self.cfg.run_attempts)
        for attempt in range(1, attempts + 1):
            try:
                return self._run_attempt()
            except Exception as e:
                # RetryError means the I/O layer already burned its finer-
                # grained budget on something transient; the coarse answer
                # is a re-restore, not a crash
                transient = isinstance(e, RetryError) or default_classifier(e)
                if attempt >= attempts or not transient:
                    raise
                self._recorder.record("run-retry", attempt=attempt,
                                      error=repr(e))
                if jax.process_index() == 0:
                    print(
                        f"transient training failure (attempt "
                        f"{attempt}/{attempts}): {e!r}; re-restoring from "
                        "the latest checkpoint",
                        flush=True,
                    )
                try:
                    # let any in-flight background save commit so the
                    # re-restore starts from the newest durable step
                    self._join_checkpoint_thread()
                    self.store.wait_until_finished()
                except Exception:
                    pass  # the save that failed is why we are here

    def _run_attempt(self) -> dict[str, Any]:
        cfg = self.cfg
        seq_len = self.model_config.seq_len
        # data sharding follows the mesh's batch shards, not raw process
        # counts: tensor/seq-axis processes share a shard (identical rows)
        shard_count = self.data_shard_count
        shard_index = self.data_shard_index

        total_train, get_train = iterator_from_tfrecords_folder(
            self.data_path, "train")
        total_valid, get_valid = iterator_from_tfrecords_folder(
            self.data_path, "valid")
        assert total_train > 0, "no protein sequences found for training"
        assert total_valid > 0, "no protein sequences found for validation"

        state, start_seq_index, _ = self.restore_or_init()
        # The stored cursor is UN-WRAPPED (monotonic across epochs).  A
        # shuffled stream orders each corpus pass differently (the sliding
        # buffer mixes across epoch boundaries), so resuming a multi-epoch
        # run must skip the interrupted stream's full OUTPUT count — the
        # wrapped first-pass position would replay epoch-1 record order.
        # Unshuffled passes are identical, so the cheap wrapped skip is
        # exact there and avoids decompressing whole skipped epochs.
        # (Skip past-the-end is safe either way: the reader repeats the
        # record stream BEFORE skipping, data/tfrecord.py.)
        epoch_position = start_seq_index % total_train
        skip = start_seq_index if cfg.shuffle_buffer else epoch_position

        # global effective batch: all data shards' micro-batches x accum
        effective_batch = cfg.batch_size * cfg.grad_accum_every * shard_count

        train_it = get_train(
            seq_len=seq_len, batch_size=cfg.batch_size, skip=skip,
            loop=True, process_count=shard_count, process_index=shard_index,
            shuffle_buffer=cfg.shuffle_buffer, seed=cfg.seed,
        )
        stager = None
        if cfg.superstep > 1:
            # fused loop: the stager owns the iterator and assembles
            # (K, accum, B, L) superbatches, transferring the next one
            # while the current superstep executes
            stager = SuperbatchStager(
                train_it, self._super_to_device,
                accum=cfg.grad_accum_every, k_max=cfg.superstep,
                depth=max(1, cfg.prefetch_depth),
            )
        elif cfg.prefetch_depth > 0:
            train_it = DevicePrefetcher(
                train_it, self._to_device, depth=cfg.prefetch_depth
            )
        valid_it = get_valid(
            seq_len=seq_len, batch_size=cfg.batch_size, loop=True,
            process_count=shard_count, process_index=shard_index,
        )

        num_params = sum(x.size for x in jax.tree.leaves(state.params))
        if jax.process_index() == 0:
            print(f"params: {num_params:,}")
            print(f"sequence length: {seq_len}")
            print(f"num sequences: {total_train}")
            print(f"starting from sequence {start_seq_index}")

        # TrainState.step counts MICRO-steps (one per train_step call);
        # the driver's global_step counts optimizer-effective steps.
        global_step = int(state.step) // cfg.grad_accum_every
        seq_cursor = start_seq_index
        last_loss = None
        pending_tokens = 0

        self._warm_compiles(state, global_step)

        watchdog = None
        if cfg.watchdog_timeout:
            out_dir = cfg.watchdog_dir or str(
                getattr(self.tracker, "_dir", None) or ".")
            watchdog = Watchdog(
                cfg.watchdog_timeout, out_dir=out_dir,
                recorder=self._recorder,
                label=f"train from step {global_step}",
            )
            watchdog.start()
        self._watchdog = watchdog

        try:
            if stager is not None:
                return self._run_loop_superstep(
                    state, stager, valid_it, total_train, epoch_position,
                    effective_batch, global_step, seq_cursor, last_loss,
                    pending_tokens,
                )
            return self._run_loop(
                state, train_it, valid_it, total_train, epoch_position,
                effective_batch, global_step, seq_cursor, last_loss,
                pending_tokens,
            )
        finally:
            compiles.set_step(None)
            if watchdog is not None:
                watchdog.stop()
            self._watchdog = None
            if stager is not None:
                stager.close()
            elif isinstance(train_it, DevicePrefetcher):
                train_it.close()
            # an exception/KeyboardInterrupt must not kill the daemon
            # checkpoint thread mid-write and lose the last save
            self._join_checkpoint_thread()
            self.store.wait_until_finished()

    def _run_loop(self, state, train_it, valid_it, total_train,
                  epoch_position, effective_batch, global_step, seq_cursor,
                  last_loss, pending_tokens):
        cfg = self.cfg
        seq_len = self.model_config.seq_len
        process_index = jax.process_index()
        num_params = sum(x.size for x in jax.tree.leaves(state.params))
        flops_per_token = model_flops_per_token(self.model_config, num_params,
                                                sgu_impl=cfg.sgu_impl)
        peak = peak_flops_per_chip()  # None off-TPU -> mfu not logged
        # the prefetcher already returns device arrays
        prefetched = isinstance(train_it, DevicePrefetcher)
        watchdog = self._watchdog

        with profile_trace(cfg.profile_dir):
            for epoch in range(1, cfg.epochs + 1):
                if process_index == 0:
                    print(f"==== starting epoch: {epoch} ====")
                epoch_start = epoch_position if epoch == 1 else 0
                steps_per_epoch = max(
                    1, (total_train - epoch_start) // effective_batch
                )
                for i in range(steps_per_epoch):
                    if watchdog is not None:
                        watchdog.beat(f"step {global_step + 1}")
                    faults.inject("train.step")
                    # the attempt's FIRST step compiles train_step inline
                    # (its donated buffers keep it out of _warm_compiles'
                    # execution warm-up) — minutes of legitimate stall the
                    # watchdog must not book as a hang
                    grace = (
                        watchdog.paused()
                        if watchdog is not None and epoch == 1 and i == 0
                        else contextlib.nullcontext()
                    )
                    # on incidents filed during this iteration
                    compiles.set_step(global_step + 1)
                    compiled = self._xla_compiles.value
                    # dispatch time only (the step runs async on device);
                    # a long span here means input starvation or a compile:
                    # the feed's child span inside it says which
                    with self._phase("train.step_dispatch",
                                     step=global_step + 1), grace:
                        for _ in range(cfg.grad_accum_every):
                            with self._tracer.span("train.feed_wait",
                                                   step=global_step + 1):
                                batch = (next(train_it) if prefetched else
                                         self._to_device(next(train_it)))
                            state, metrics = self.fns.train_step(state, batch)
                    if (self._xla_compiles.value != compiled
                            and not (epoch == 1 and i == 0)):
                        self._recompiles.inc(
                            self._xla_compiles.value - compiled)
                    global_step += 1
                    # monotonic, never wrapped: the checkpointed cursor must
                    # identify the position in the multi-epoch STREAM
                    seq_cursor = seq_cursor + effective_batch
                    pending_tokens += effective_batch * seq_len

                    will_hook = (
                        global_step % cfg.checkpoint_every == 0
                        or global_step % cfg.validate_every == 0
                        or global_step % cfg.sample_every == 0
                    )
                    if global_step % cfg.log_every == 0:
                        # one batched transfer blocks until the step chain
                        # is executed — the only trustworthy sync point, so
                        # the meter ticks HERE with the tokens since the
                        # last sync (one device_get, not one per metric)
                        with self._phase("train.log", step=global_step):
                            # the span covers the device_get sync + metric
                            # assembly — the loop's only blocking point
                            host_metrics = jax.device_get(metrics)  # graftcheck: disable=host-sync
                            last_loss = float(host_metrics["loss"])
                            self.meter.tick(pending_tokens)
                            pending_tokens = 0
                            log = {
                                "loss": last_loss,
                                "grad_norm": float(host_metrics["grad_norm"]),
                                # computed on device by the step itself: the
                                # schedule value this update was actually
                                # scaled with (no host-side reconstruction
                                # from global_step)
                                "lr": float(host_metrics["lr"]),
                            }
                            tps = self.meter.tokens_per_sec_per_chip
                            if tps is not None:
                                log["tokens_per_sec_per_chip"] = tps
                                util = mfu(tps, flops_per_token, peak)
                                if util is not None:
                                    log["mfu"] = util
                            self.tracker.log(log, global_step)
                            self._recorder.record("step", step=global_step,
                                                  **log)
                        self.meter.publish(get_registry())
                        self._publish_train_health(log, global_step)
                        if process_index == 0:
                            print(f"step {global_step} loss: {last_loss:.4f}")

                    if will_hook and pending_tokens:
                        # hook cadences need not align with log_every: sync
                        # and tick BEFORE the hooks so their wall time is
                        # never rated against these steps' tokens (and the
                        # hook's own blocking never absorbs them)
                        # a pure barrier: no value is needed, so don't pay
                        # for a transfer on top of the wait
                        jax.block_until_ready(metrics["grad_norm"])  # graftcheck: disable=host-sync
                        self.meter.tick(pending_tokens)
                        pending_tokens = 0

                    hooks_ran = False
                    if global_step % cfg.checkpoint_every == 0:
                        with self._phase("train.checkpoint",
                                         step=global_step):
                            self._checkpoint(state, seq_cursor)
                        hooks_ran = True

                    if global_step % cfg.validate_every == 0:
                        with self._phase("train.validate",
                                         step=global_step) as fields:
                            vbatch = self._to_device(next(valid_it))
                            vmetrics = self.fns.eval_step(state, vbatch)
                            vloss = float(jax.device_get(vmetrics["loss"]))  # graftcheck: disable=host-sync
                            self.tracker.log({"valid_loss": vloss},
                                             global_step)
                            fields["loss"] = vloss
                        if process_index == 0:
                            print(f"valid_loss: {vloss:.4f}")
                        hooks_ran = True

                    if global_step % cfg.sample_every == 0:
                        self._sample_and_log(state, next(valid_it), global_step)
                        hooks_ran = True

                    if hooks_ran:
                        # hook time (eval/sampling/checkpoint IO) is not
                        # training time; drop it from the meter's window
                        self.meter.rebase()
                        # ...nor is it a stall: re-arm the watchdog clock
                        if watchdog is not None:
                            watchdog.beat(f"hooks at step {global_step}")

                    if (self._preempt_requested
                            or self.store.reached_preemption(global_step)):
                        # the process exits right after: the save must
                        # fully commit before we let it
                        self._checkpoint(state, seq_cursor, wait=True)
                        if process_index == 0:
                            print(
                                f"preemption checkpoint at step {global_step}; "
                                "exiting (resume restarts here)"
                            )
                        return {"state": state, "loss": last_loss,
                                "step": global_step, "preempted": True}

                    if cfg.max_steps is not None and global_step >= cfg.max_steps:
                        self._checkpoint(state, seq_cursor, wait=True)
                        return self._finish(state, last_loss, global_step)
        return self._finish(state, last_loss, global_step)

    def _run_loop_superstep(self, state, stager, valid_it, total_train,
                            epoch_position, effective_batch, global_step,
                            seq_cursor, last_loss, pending_tokens):
        """Fused-superstep variant of :meth:`_run_loop` (cfg.superstep > 1).

        Each iteration advances a SPAN of optimizer steps with
        ``train_multi_step`` dispatches: :func:`superstep_span` sizes the
        span to land exactly on the nearest hook boundary, so every
        log/checkpoint/validate/sample/epoch boundary fires at the same
        global_step as the per-step loop.  A full span is ONE K=superstep
        dispatch; a residual span (boundary closer than K) walks up with
        the K=1 program instead of compiling one XLA program per distinct
        span length — the loop only ever compiles two shapes."""
        cfg = self.cfg
        seq_len = self.model_config.seq_len
        process_index = jax.process_index()
        num_params = sum(x.size for x in jax.tree.leaves(state.params))
        flops_per_token = model_flops_per_token(self.model_config, num_params,
                                                sgu_impl=cfg.sgu_impl)
        peak = peak_flops_per_chip()
        watchdog = self._watchdog
        k_max = cfg.superstep
        cadences = (cfg.log_every, cfg.checkpoint_every, cfg.validate_every,
                    cfg.sample_every)
        pending_steps = 0
        compiled_ks: set = set()

        with profile_trace(cfg.profile_dir):
            for epoch in range(1, cfg.epochs + 1):
                if process_index == 0:
                    print(f"==== starting epoch: {epoch} ====")
                epoch_start = epoch_position if epoch == 1 else 0
                steps_per_epoch = max(
                    1, (total_train - epoch_start) // effective_batch
                )
                done = 0
                while done < steps_per_epoch:
                    remaining = steps_per_epoch - done
                    if cfg.max_steps is not None:
                        remaining = min(remaining,
                                        cfg.max_steps - global_step)
                    span = superstep_span(global_step, k_max, cadences,
                                          remaining)
                    if watchdog is not None:
                        watchdog.beat(
                            f"steps {global_step + 1}..{global_step + span}")
                    # one inject per optimizer step: a fault plan's at=N
                    # fires before step N runs, as in the per-step loop
                    for _ in range(span):
                        faults.inject("train.step")
                    k = k_max if span == k_max else 1
                    # each of the two program shapes compiles inline on
                    # its first dispatch (donated buffers keep them out of
                    # _warm_compiles' execution warm-up) — legitimate
                    # stall the watchdog must not book as a hang
                    grace = (
                        watchdog.paused()
                        if watchdog is not None and k not in compiled_ks
                        else contextlib.nullcontext()
                    )
                    warm = k in compiled_ks
                    compiled_ks.add(k)
                    # on incidents filed during this iteration
                    compiles.set_step(global_step + span)
                    compiled = self._xla_compiles.value
                    with self._phase("train.step_dispatch",
                                     step=global_step + span,
                                     span=span), grace:
                        for _ in range(span // k):
                            with self._tracer.span("train.feed_wait",
                                                   step=global_step + span):
                                superbatch = stager.get(k)
                            state, metrics = self.fns.train_multi_step(
                                state, superbatch)
                    if warm and self._xla_compiles.value != compiled:
                        self._recompiles.inc(
                            self._xla_compiles.value - compiled)
                    done += span
                    global_step += span
                    seq_cursor = seq_cursor + effective_batch * span
                    pending_tokens += effective_batch * seq_len * span
                    pending_steps += span

                    will_hook = (
                        global_step % cfg.checkpoint_every == 0
                        or global_step % cfg.validate_every == 0
                        or global_step % cfg.sample_every == 0
                    )
                    if global_step % cfg.log_every == 0:
                        # ONE batched transfer fetches the whole span's
                        # K-stacked metrics — the sync point the meter
                        # ticks at, now rating K steps per sync
                        with self._phase("train.log", step=global_step):
                            # the span covers the device_get sync + metric
                            # assembly — the loop's only blocking point
                            host_metrics = jax.device_get(metrics)  # graftcheck: disable=host-sync
                            last_loss = float(host_metrics["loss"][-1, -1])
                            self.meter.tick(pending_tokens, steps=pending_steps)
                            pending_tokens = 0
                            pending_steps = 0
                            log = {
                                "loss": last_loss,
                                "grad_norm": float(
                                    host_metrics["grad_norm"][-1, -1]),
                                # computed on device by the step itself: the
                                # schedule value the final update in the span
                                # was actually scaled with
                                "lr": float(host_metrics["lr"][-1]),
                            }
                            tps = self.meter.tokens_per_sec_per_chip
                            if tps is not None:
                                log["tokens_per_sec_per_chip"] = tps
                                util = mfu(tps, flops_per_token, peak)
                                if util is not None:
                                    log["mfu"] = util
                            sps = self.meter.steps_per_sec
                            if sps is not None:
                                log["steps_per_sec"] = sps
                            self.tracker.log(log, global_step)
                            self._recorder.record("step", step=global_step,
                                                  **log)
                        self.meter.publish(get_registry())
                        self._publish_train_health(log, global_step)
                        if process_index == 0:
                            print(f"step {global_step} loss: {last_loss:.4f}")

                    if will_hook and pending_tokens:
                        # hook cadences need not align with log_every:
                        # sync and tick BEFORE the hooks so their wall
                        # time is never rated against these steps' tokens
                        jax.block_until_ready(metrics["grad_norm"])  # graftcheck: disable=host-sync
                        self.meter.tick(pending_tokens, steps=pending_steps)
                        pending_tokens = 0
                        pending_steps = 0

                    hooks_ran = False
                    if global_step % cfg.checkpoint_every == 0:
                        with self._phase("train.checkpoint",
                                         step=global_step):
                            self._checkpoint(state, seq_cursor)
                        hooks_ran = True

                    if global_step % cfg.validate_every == 0:
                        with self._phase("train.validate",
                                         step=global_step) as fields:
                            vbatch = self._to_device(next(valid_it))
                            vmetrics = self.fns.eval_step(state, vbatch)
                            vloss = float(jax.device_get(vmetrics["loss"]))  # graftcheck: disable=host-sync
                            self.tracker.log({"valid_loss": vloss},
                                             global_step)
                            fields["loss"] = vloss
                        if process_index == 0:
                            print(f"valid_loss: {vloss:.4f}")
                        hooks_ran = True

                    if global_step % cfg.sample_every == 0:
                        self._sample_and_log(state, next(valid_it),
                                             global_step)
                        hooks_ran = True

                    if hooks_ran:
                        # hook time (eval/sampling/checkpoint IO) is not
                        # training time; drop it from the meter's window
                        self.meter.rebase()
                        if watchdog is not None:
                            watchdog.beat(f"hooks at step {global_step}")

                    if (self._preempt_requested
                            or self.store.reached_preemption(global_step)):
                        self._checkpoint(state, seq_cursor, wait=True)
                        if process_index == 0:
                            print(
                                f"preemption checkpoint at step "
                                f"{global_step}; exiting (resume restarts "
                                "here)"
                            )
                        return {"state": state, "loss": last_loss,
                                "step": global_step, "preempted": True}

                    if (cfg.max_steps is not None
                            and global_step >= cfg.max_steps):
                        self._checkpoint(state, seq_cursor, wait=True)
                        return self._finish(state, last_loss, global_step)
        return self._finish(state, last_loss, global_step)

    def _finish(self, state, last_loss, global_step: int) -> dict[str, Any]:
        """Full-validation eval loss (BASELINE.md's second metric) at the
        end of training, logged and returned."""
        self._join_checkpoint_thread()
        self.store.wait_until_finished()  # commit any in-flight async save
        valid_loss = self.evaluate(state)
        if valid_loss is not None:
            self.tracker.log({"full_valid_loss": valid_loss}, global_step)
            if jax.process_index() == 0:
                print(f"full valid loss: {valid_loss:.4f}")
        return {"state": state, "loss": last_loss, "step": global_step,
                "valid_loss": valid_loss}

    def evaluate(self, state, max_batches: int | None = None) -> float | None:
        """Mean per-row loss over the ENTIRE validation split, one pass —
        the honest "eval loss" number for BASELINE.md (the in-loop
        ``validate_every`` probe times a single batch, matching the
        reference ``train.py:213-217``).

        The final partial batch is zero-padded up to the static batch shape
        (no jit retrace) and the pad rows are masked out via the step's
        ``real_rows`` output, so the mean is exact over all records.
        Multi-host: every host feeds its shard; outputs are replicated, so
        all hosts return the same number.
        """
        cfg = self.cfg
        total_valid, get_valid = iterator_from_tfrecords_folder(
            self.data_path, "valid")
        if total_valid == 0:
            return None
        shard_count = self.data_shard_count
        it = get_valid(
            seq_len=self.model_config.seq_len, batch_size=cfg.batch_size,
            loop=False, process_count=shard_count,
            process_index=self.data_shard_index,
        )
        # every host must run the SAME number of eval_step calls (SPMD);
        # round-robin sharding leaves data shards with up to 1 extra
        # record, so the count comes from the largest shard, and exhausted
        # shards feed all-pad batches (masked out by real_rows).
        width = self.model_config.seq_len + 1
        max_host_records = -(-total_valid // shard_count)
        n_batches = -(-max_host_records // cfg.batch_size)
        if max_batches is not None:
            n_batches = min(n_batches, max_batches)
        loss_sum, rows = 0.0, 0
        for _ in range(n_batches):
            np_batch = next(it, None)
            if np_batch is None:
                np_batch = np.zeros((cfg.batch_size, width), np.int32)
            elif np_batch.shape[0] < cfg.batch_size:
                pad = np.zeros(
                    (cfg.batch_size - np_batch.shape[0], np_batch.shape[1]),
                    np_batch.dtype,
                )
                np_batch = np.concatenate([np_batch, pad])
            metrics = self.fns.eval_step(state, self._to_device(np_batch))
            # one transfer for both reductions instead of two np.asarray
            # syncs plus two scalar pulls
            host = jax.device_get(metrics)  # graftcheck: disable=host-sync
            per_row = np.asarray(host["per_row_loss"])
            real = np.asarray(host["real_rows"])
            loss_sum += float((per_row * real).sum())
            rows += int(real.sum())
        return loss_sum / rows if rows else None

    # -- hooks ---------------------------------------------------------------

    def _join_checkpoint_thread(self) -> None:
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._ckpt_thread = None

    def _checkpoint(self, state, next_seq_index: int, wait: bool = False) -> None:
        step = int(state.step)
        run_id = self.tracker.run_id
        model_config = self.model_config.to_dict()

        def do_save(snapshot) -> None:
            # save() skips steps already in the store, so the
            # exit/preemption save after a same-step periodic hook costs
            # nothing
            self._recorder.record("checkpoint-start", step=step,
                                  next_seq_index=next_seq_index)
            saved = self.store.save(
                step, snapshot,
                next_seq_index=next_seq_index,
                model_config=model_config,
                run_id=run_id,
            )
            self._recorder.record("checkpoint-done", step=step,
                                  saved=bool(saved))
            if saved and jax.process_index() == 0:
                print(
                    f"checkpoint to start at sequence index of {next_seq_index}"
                )

        if not self.cfg.background_checkpoint or jax.process_count() > 1:
            # multi-host: the cooperative orbax save is a collective —
            # every host must enter it in lockstep, so keep it on the
            # main thread
            do_save(state)
            if wait:
                self.store.wait_until_finished()
            return

        # one save in flight at a time (bounds the extra HBM to one
        # state-sized snapshot and keeps store calls single-threaded).
        # A PERIODIC save that lands while the previous one is still
        # draining is SKIPPED, not queued: on slow host links the fetch
        # can exceed the checkpoint cadence, and blocking training to wait would
        # reintroduce the very stall this path removes — you cannot
        # durably checkpoint faster than the link drains.  Exit and
        # preemption saves (wait=True) always join and write.
        if self._ckpt_thread is not None and self._ckpt_thread.is_alive():
            if not wait:
                if jax.process_index() == 0:
                    print(f"checkpoint at step {step} skipped: previous "
                          "save still writing")
                return
        self._join_checkpoint_thread()
        # on-device copy: O(ms), and donation of `state` by the next
        # train_step cannot invalidate it (XLA sequences the copy before
        # the donated buffers are reused)
        snapshot = jax.tree.map(jnp.copy, state)
        import threading

        self._ckpt_thread = threading.Thread(
            target=do_save, args=(snapshot,), name="progen-checkpoint",
            daemon=True,
        )
        self._ckpt_thread.start()
        if wait:
            self._join_checkpoint_thread()
            self.store.wait_until_finished()

    def _replicated_prime_and_key(self, prime_np, key):
        """Sampler inputs for the global mesh: in multi-process runs both
        the prime and the rng key must be re-materialized replicated over
        ALL devices — a host-local array is rejected by jit as an
        incompatible device set.  (KeySeq is seeded identically on every
        host, so replicating the key VALUE is sound.)  Single process:
        plain transfers."""
        if self.mesh is not None and jax.process_count() > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(self.mesh, PartitionSpec())
            prime = jax.make_array_from_process_local_data(
                repl, np.asarray(prime_np, np.int32))
            key_data = jax.make_array_from_process_local_data(
                repl, np.asarray(jax.random.key_data(key)))
            key = jax.random.wrap_key_data(key_data)
            return prime, key
        return jnp.asarray(prime_np), key

    def _sample_and_log(self, state, valid_batch, step: int) -> None:
        """In-training sampling (reference train.py:219-228): prime with the
        first ``prime_length`` tokens of a validation row, decode, log.

        Multi-host: the per-host valid streams are disjoint, so process 0's
        prime row is broadcast to every host and placed replicated over the
        global mesh (the sampler then runs as one SPMD program against the
        globally-sharded params — a host-local prime would be rejected by
        jit as an incompatible device set)."""
        cfg = self.cfg
        prime_np = np.asarray(valid_batch[:1, : cfg.prime_length], np.int32)
        if self.mesh is not None and jax.process_count() > 1:
            from jax.experimental import multihost_utils

            prime_np = multihost_utils.broadcast_one_to_all(prime_np)
        prime, key = self._replicated_prime_and_key(prime_np, next(self.keys))
        sampled = self.sampler(
            {"params": state.params}, key, prime,
            length=self.model_config.seq_len, top_k=cfg.sample_top_k,
        )
        prime_str = decode_tokens(np.asarray(prime[0]))
        sampled_str = decode_tokens(np.asarray(sampled[0, cfg.prime_length:]))
        if jax.process_index() == 0:
            print(prime_str, "\n", "*" * 40, "\n", sampled_str)
        self.tracker.log_sample(prime_str, sampled_str, step)
