"""Jitted SPMD train/eval steps over the device mesh.

Replaces the reference's ``get_loss_fn`` + Python-side optimizer calls
(``/root/reference/progen_transformer/utils.py:61-93``,
``train.py:191-196``).  Key structural changes, all TPU-motivated:

* ONE jitted ``train_step`` contains forward, backward, clip, Adam and the
  param update — the reference runs optimizer steps outside jit, paying a
  host round-trip per micro-batch;
* parallelism comes from ``in_shardings``/``out_shardings`` over the mesh
  (GSPMD), not ``pmap``; the same step function serves 1 chip or a pod;
* the reference differentiates THROUGH its pmap (``utils.py:72``) and
  re-transfers params every call; here params live sharded on device across
  steps (donated buffers, zero copies);
* state sharding is derived from the model's logical axis annotations by
  propagating flax metadata boxes through ``optax``'s ``init`` (zeros_like
  preserves the boxes), so optimizer moments shard exactly like their
  params;
* ``train_multi_step`` goes one further: a ``lax.scan`` fuses K optimizer
  steps (each ``grad_accum_every`` micro-batches) into ONE XLA program
  over a staged ``(K, accum, B, L)`` superbatch, so the steady-state loop
  pays one host dispatch per K steps instead of ``K * accum`` — the
  pjit-paper loop-fusion pattern (PAPERS.md), with GSPMD propagating the
  same shardings through the scanned body.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from progen_tpu.parallel.sharding import (
    batch_sharding,
    logical_rules,
    superbatch_sharding,
    unbox,
)
from progen_tpu.train.loss import batch_loss, cross_entropy


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any


@dataclasses.dataclass(frozen=True)
class TrainFunctions:
    """Bundle returned by :func:`make_train_functions`.

    ``init_state(key)`` creates the (sharded) state; ``train_step(state,
    batch)`` and ``eval_step(state, batch)`` are jitted and mesh-aware.
    ``batch`` is the data-pipeline layout ``(B, seq_len + 1)`` int tokens.
    ``train_multi_step(state, superbatch)`` fuses K optimizer steps into
    one XLA program over a ``(K, accum, B, seq_len + 1)`` superbatch and
    returns K-stacked metrics (see :func:`make_train_functions`).
    """

    init_state: Callable
    train_step: Callable
    eval_step: Callable
    state_shardings: Any
    train_multi_step: Callable | None = None


def _boxed_state_factory(model, optimizer, sample_tokens):
    def init_boxed(key):
        variables = model.init(key, sample_tokens)
        params = variables["params"]
        opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state)

    return init_boxed


def make_train_functions(
    model,
    optimizer: optax.GradientTransformation,
    sample_tokens,
    mesh: Mesh | None = None,
    strategies: Sequence[str] = ("dp",),
    grad_accum_every: int = 1,
    lr_schedule: float | Callable | None = None,
) -> TrainFunctions:
    """Build the jitted step functions.

    ``grad_accum_every`` must match the accumulation ``optimizer`` was
    built with: when > 1 (an ``optax.MultiSteps``-wrapped optimizer),
    ``train_multi_step`` replaces the ``grad_accum_every`` host dispatches
    per optimizer step with one on-device scan whose carry holds the f32
    gradient accumulator — bit-exact with the sequential path (see its
    docstring for why the body graph is kept identical).

    ``lr_schedule`` (the float or optax schedule behind the optimizer's
    learning rate): when given, every step's metrics carry ``"lr"`` — the
    schedule read at the count the update was actually scaled with —
    computed on device, so loggers need no host-side reconstruction.
    """
    init_boxed = _boxed_state_factory(model, optimizer, sample_tokens)
    accum = max(1, int(grad_accum_every))
    if accum > 1 and not isinstance(optimizer, optax.MultiSteps):
        raise ValueError(
            f"grad_accum_every={grad_accum_every} requires an "
            "optax.MultiSteps optimizer (make_optimizer builds one); got "
            f"{type(optimizer).__name__}"
        )

    if mesh is not None:
        abstract = jax.eval_shape(init_boxed, jax.random.key(0))
        logical_spec = nn.get_partition_spec(abstract)
        state_shardings = nn.logical_to_mesh_sharding(
            logical_spec, mesh, logical_rules(strategies)
        )
        data_sharding = batch_sharding(mesh)
        repl = NamedSharding(mesh, PartitionSpec())
    else:
        state_shardings = None
        data_sharding = None
        repl = None

    # a real jitted function (not a closure re-jitting per call) so callers
    # can AOT-compile it (.lower) — multi-process launchers stagger compiles
    # through the persistent cache that way
    _init_fn = lambda key: unbox(init_boxed(key))
    if mesh is not None:
        init_state = jax.jit(_init_fn, out_shardings=state_shardings)
    else:
        init_state = jax.jit(_init_fn)

    def apply_model(params, ids):
        # Activate the logical-axis rules (and the mesh, which
        # with_sharding_constraint needs in scope) while TRACING the model so
        # every nn.with_logical_constraint in the forward becomes a real GSPMD
        # sharding constraint; without the context they are no-ops and XLA
        # must guess intermediate layouts.
        if mesh is not None:
            with mesh, nn.logical_axis_rules(logical_rules(strategies)):
                return model.apply({"params": params}, ids)
        return model.apply({"params": params}, ids)

    def loss_from_batch(params, batch):
        ids, labels = batch[:, :-1], batch[:, 1:]
        logits = apply_model(params, ids)
        with jax.named_scope("loss.xent"):
            return batch_loss(logits, labels)

    def _lr_value(count):
        # the lr the update at optimizer-step count `count` was scaled
        # with (optax schedules read the count BEFORE incrementing it)
        if callable(lr_schedule):
            return jnp.asarray(lr_schedule(count), jnp.float32)
        return jnp.asarray(lr_schedule, jnp.float32)

    def _opt_count(state: TrainState):
        # optimizer-step count BEFORE this update: MultiSteps carries it
        # explicitly; unaccumulated states advance one per micro-step
        if accum > 1:
            return state.opt_state.gradient_step
        return state.step

    def _train_step_body(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_from_batch)(state.params, batch)
        with jax.named_scope("optim.update"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state)
        metrics = {"loss": loss, "grad_norm": grad_norm}
        if lr_schedule is not None:
            metrics["lr"] = _lr_value(_opt_count(state))
        return new_state, metrics

    train_step = _train_step_body

    def train_multi_step(state: TrainState, superbatch):
        """K fused optimizer steps: ``superbatch`` is ``(K, accum, B, L)``
        int tokens; returns the advanced state plus K-stacked metrics
        ``{"loss": (K, accum), "grad_norm": (K, accum)[, "lr": (K,)]}`` —
        the trailing ``[-1, -1]`` element of loss/grad_norm is exactly
        what the per-dispatch loop would have logged, and ``lr`` is the
        schedule value each optimizer step's update was scaled with.

        The scan body is the EXACT per-dispatch step graph, so the fused
        path is bit-identical to ``K * accum`` sequential ``train_step``
        calls: under accumulation the f32 gradient accumulator
        (``MultiStepsState.acc_grads``) rides in the on-device scan carry
        instead of round-tripping through ``accum`` host dispatches.  (An
        algebraically-restructured variant — accumulate all micro-grads,
        then one inner update — was measured 1 ULP off the sequential
        path: restructuring the graph changes XLA's FMA fusion.  Keeping
        the same body graph keeps parity exact; the redundant non-emit
        optimizer math it carries is elementwise-O(params), noise next to
        the fwd+bwd FLOPs.)"""
        k = superbatch.shape[0]
        flat = superbatch.reshape((k * accum,) + superbatch.shape[2:])
        new_state, metrics = jax.lax.scan(_train_step_body, state, flat)
        out = {"loss": metrics["loss"].reshape(k, accum),
               "grad_norm": metrics["grad_norm"].reshape(k, accum)}
        if lr_schedule is not None:
            # one lr per OPTIMIZER step: the group's update is scaled with
            # the schedule read at its last micro-step (the emit)
            out["lr"] = metrics["lr"].reshape(k, accum)[:, -1]
        return new_state, out

    def eval_step(state: TrainState, batch):
        ids, labels = batch[:, :-1], batch[:, 1:]
        logits = apply_model(state.params, ids)
        with jax.named_scope("loss.xent"):
            # all-zero rows are padding added to square off a final partial
            # eval batch; callers drop them via this mask (a real collated
            # row always has content after the BOS column)
            real_rows = jnp.any(batch != 0, axis=1)
            return {"loss": batch_loss(logits, labels),
                    "per_row_loss": cross_entropy(logits, labels),
                    "real_rows": real_rows}

    if mesh is not None:
        super_sharding = superbatch_sharding(mesh)
        train_step = jax.jit(
            train_step,
            in_shardings=(state_shardings, data_sharding),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,),
        )
        # the superbatch is donated too: its (K, accum, B, L) buffer is
        # dead once scanned, and XLA reuses the HBM for scan temporaries
        train_multi_step = jax.jit(
            train_multi_step,
            in_shardings=(state_shardings, super_sharding),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0, 1),
        )
        eval_step = jax.jit(
            eval_step,
            in_shardings=(state_shardings, data_sharding),
            # replicated outputs: every host must be able to fetch the
            # full per-row metrics (multi-process full-validation eval)
            out_shardings=repl,
        )
    else:
        train_step = jax.jit(train_step, donate_argnums=(0,))
        train_multi_step = jax.jit(train_multi_step, donate_argnums=(0, 1))
        eval_step = jax.jit(eval_step)

    return TrainFunctions(
        init_state=init_state,
        train_step=train_step,
        eval_step=eval_step,
        state_shardings=state_shardings,
        train_multi_step=train_multi_step,
    )
