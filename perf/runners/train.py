"""Training cells: the job goes through ``Trainer.run()`` as ``train.py``
builds it; the benchmark only injects its own tracker and writes the data.

The tracker's ``log()`` is called right after the loop's ``device_get``, so
its clock ticks at the device syncs and nowhere else.  Step 1 is logged (for
the reference check of the first loss), then every ``log_every`` steps.  The
window opens at the sync ``warm_syncs`` later and closes at the first sync
``--seconds`` after that; the run is then stopped through the trainer's own
preemption path (SIGTERM -> checkpoint -> return), which skips the final
validation pass that ``max_steps`` would run.  Everything lands in a
temporary directory that is removed.
"""

from __future__ import annotations

import dataclasses
import math
import os
import shutil
import signal
import tempfile
import time
from functools import partial

import numpy as np

from perf.lib import reference, traffic
from perf.lib.harness import Phases, TraceStretch

NEVER = 10 ** 9  # a hook cadence no run reaches


class SyncClock:
    """The tracker injected into the trainer: records ``(instant, step,
    loss)`` at every sync, opens and closes the window, starts and stops
    the profiler's stretch, and asks the trainer to stop."""

    run_id = "perf"

    def __init__(self, *, seconds: float, log_every: int, warm_syncs: int,
                 stretch: TraceStretch | None, trace_after_syncs: int):
        self.seconds = seconds
        self.log_every = log_every
        self.warm_syncs = warm_syncs
        self.stretch = stretch
        self.trace_after_syncs = trace_after_syncs
        self.trainer = None
        self.phases: Phases | None = None
        self.syncs: list[tuple[float, int, float]] = []
        self.open: tuple[float, int] | None = None
        self.close: tuple[float, int] | None = None
        self.paused = 0.0

    def log(self, metrics: dict, step: int) -> None:
        if "loss" not in metrics:
            return
        now = time.perf_counter()
        self.syncs.append((now, int(step), float(metrics["loss"])))
        n = len(self.syncs)
        if self.open is None and self.phases is not None:
            self.phases.mark("first step (compile or cache)" if n == 1
                             else "warm steps")
        if n == 1:
            # TrainerConfig is read every step: from here on, sync at the
            # cell's cadence
            self.trainer.cfg.log_every = self.log_every
        if self.open is None:
            if n > self.warm_syncs:
                self.open = (now, int(step))
                self._open_index = n
            return
        if self.close is not None:
            return
        if now - self.open[0] - self.paused >= self.seconds:
            if self.stretch is not None and self.stretch.active:
                self.stretch.stop()
            self.close = (now, int(step))
            if self.phases is not None:
                self.phases.mark("window")
            signal.raise_signal(signal.SIGTERM)
        elif self.stretch is not None:
            # at a sync every dispatched step has run, so the device idles
            # while the profiler starts or stops: that time is taken off
            # the window's clock
            k = n - self._open_index
            if k == self.trace_after_syncs and not self.stretch.done:
                self.stretch.start()
            elif self.stretch.active:
                self.stretch.stop()
            self.paused += time.perf_counter() - now

    def log_sample(self, prime, sampled, step) -> None:
        pass

    def finish(self) -> None:
        pass


def _write_data(folder: str, records: list[bytes], group: int) -> None:
    from progen_tpu.data.tfrecord import shard_filename, write_tfrecord

    os.makedirs(folder)
    write_tfrecord(os.path.join(
        folder, shard_filename(0, len(records), "train")), records)
    # the trainer insists on a validation split; no hook reads it here
    write_tfrecord(os.path.join(
        folder, shard_filename(0, group, "valid")), records[:group])


def _collate(records: list[bytes], seq_len: int) -> np.ndarray:
    """Rows as the trainer's reader builds them: BOS column, byte + 1,
    zero padding (SURVEY.md 2.b; progen_tpu.data.tfrecord.collate)."""
    batch = np.zeros((len(records), seq_len + 1), np.int32)
    for i, rec in enumerate(records):
        toks = np.frombuffer(rec, np.uint8)[:seq_len].astype(np.int32) + 1
        batch[i, 1:1 + len(toks)] = toks
    return batch


def _reference_checks(trainer, config: dict, first_batch: np.ndarray,
                      check: dict) -> dict:
    """Before the window, on the weights the run will start from (the same
    key gives the same state): logits of the model as the cell runs it
    against the reference on the first ``logit_rows`` rows, and the
    reference's loss of the first batch, in chunks of ``loss_chunk`` rows."""
    import flax.linen as nn
    import jax

    from progen_tpu.core.rng import KeySeq
    from progen_tpu.parallel.sharding import logical_rules

    state0 = trainer.fns.init_state(next(KeySeq(trainer.cfg.seed)))
    params = state0.params
    rows = check["logit_rows"]
    ids = first_batch[:rows, :-1]

    def apply(p, x):
        if trainer.mesh is not None:
            with trainer.mesh, nn.logical_axis_rules(
                    logical_rules(trainer.cfg.strategies)):
                return trainer.model.apply({"params": p}, x)
        return trainer.model.apply({"params": p}, x)

    # the model sees the whole batch (its rows divide over the mesh)
    got = np.asarray(jax.jit(apply)(params, first_batch[:, :-1])[:rows])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(partial(reference.forward, cfg=config))(
            params, ids))
        chunk = check["loss_chunk"]
        ref_loss = jax.jit(partial(reference.loss, cfg=config))
        losses = [float(ref_loss(params, first_batch[i:i + chunk]))
                  for i in range(0, len(first_batch), chunk)]
    del state0, params
    return {"logit_err": float(np.abs(got - want).max() / np.abs(want).max()),
            "reference_loss": float(np.mean(losses))}


def run(*, workload, config, seed, seconds, trace, chips):
    phases = Phases()
    import jax

    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.core.mesh import MeshConfig
    from progen_tpu.models import ProGenConfig
    from progen_tpu.observe.trace import configure_tracing, get_tracer
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    enable_compilation_cache()
    phases.mark("imports")
    model_config = ProGenConfig(**{
        f.name: config[f.name] for f in dataclasses.fields(ProGenConfig)})
    options = dict(workload["trainer"])
    options["mesh"] = MeshConfig.parse(options["mesh"])
    options["strategies"] = tuple(options["strategies"])
    log_every = options.pop("log_every")
    cfg = TrainerConfig(
        seed=int(seed) & 0xFFFFFFFF, log_every=1, max_steps=NEVER - 1,
        validate_every=NEVER, sample_every=NEVER, checkpoint_every=NEVER,
        warm_sampler=False, run_attempts=1, **options)
    if math.prod(options["mesh"].resolve(len(jax.devices()))) != chips:
        raise SystemExit(
            f"the cell names {chips} chip(s); the trainer's mesh would span "
            f"{len(jax.devices())} devices")

    data = dict(workload["traffic"], group=cfg.batch_size)
    records = traffic.train_records(data, seed)
    tokens = traffic.record_tokens(records, model_config.seq_len)
    rows_per_step = cfg.batch_size * cfg.grad_accum_every
    check = workload["correct"]

    tmp = tempfile.mkdtemp(prefix="perf-train-")
    stretch = TraceStretch(os.path.join(tmp, "trace")) if trace else None
    clock = SyncClock(seconds=seconds, log_every=log_every,
                      warm_syncs=workload["window"]["warm_syncs"],
                      stretch=stretch,
                      trace_after_syncs=workload["window"]["trace_after_syncs"])
    if trace:
        configure_tracing(enabled=True, capacity=1 << 16)
    try:
        _write_data(os.path.join(tmp, "data"), records, cfg.batch_size)
        trainer = Trainer(model_config=model_config, cfg=cfg,
                          data_path=os.path.join(tmp, "data"),
                          checkpoint_path=os.path.join(tmp, "ckpt"),
                          tracker=clock)
        clock.trainer = trainer
        clock.phases = phases
        phases.mark("data and trainer")
        first_batch = _collate(records[:rows_per_step], model_config.seq_len)
        ref = _reference_checks(trainer, config, first_batch, check)
        phases.mark("reference checks")
        trainer.run()  # the returned state is dropped with the call
        phases.mark("exit checkpoint")
        if stretch is not None and stretch.active:
            stretch.stop()
        spans = get_tracer().ring() if trace else []
        reduced = None
        if stretch is not None:
            reduced = stretch.reduce(
                [(s["name"], s["ts"], s["ts"] + s["dur"]) for s in spans
                 if s["name"].startswith("train.")])
    finally:
        configure_tracing(enabled=False)
        shutil.rmtree(tmp, ignore_errors=True)

    if clock.open is None or clock.close is None:
        raise SystemExit("the trainer returned before the window closed")
    (t_open, step_open), (t_close, step_close) = clock.open, clock.close
    steps = step_close - step_open
    wall = t_close - t_open - clock.paused
    # records are consumed in file order, the file repeats
    index = np.arange(step_open * rows_per_step,
                      step_close * rows_per_step) % len(records)
    nonpad = int(tokens[index].sum())
    slots = steps * rows_per_step * model_config.seq_len
    losses = [loss for _, _, loss in clock.syncs]
    loss_err = abs(losses[0] - ref["reference_loss"])
    correct = (all(math.isfinite(x) for x in losses)
               and ref["logit_err"] <= check["logit_tolerance"]
               and loss_err <= check["loss_tolerance"])
    phases.report("train")
    print(f"train: {steps} steps in {wall:.3f} s, {len(clock.syncs)} syncs, "
          f"logits vs reference {ref['logit_err']:.3e} of max "
          f"(tolerance {check['logit_tolerance']}), step-1 loss "
          f"{losses[0]:.6f} vs reference {ref['reference_loss']:.6f} "
          f"(|diff| {loss_err:.3e}, tolerance {check['loss_tolerance']}), "
          f"last loss {losses[-1]:.4f}", flush=True)
    in_window = [s["dur"] for s in spans
                 if s["name"] == "train.step_dispatch"
                 and t_open <= s["ts"] <= t_close]
    return {
        "correct": correct,
        "attempted": steps,
        "failed": 0 if correct else steps,
        "window_open": t_open,
        "end_to_end": {"train_tok_s": nonpad / wall / chips},
        "observations": {
            "counters": {"steps": steps, "window_s": wall, "slots": slots,
                         "nonpad_tokens": nonpad, "syncs": len(clock.syncs)},
            "spans": {"train.step_dispatch": in_window},
            "trace": reduced,
        },
    }
