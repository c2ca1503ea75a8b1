"""Benchmark: training-step throughput, tokens/sec/chip.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
"mfu": N, "params": N}``

The metric matches BASELINE.md: Uniref50-shaped training throughput
(ProGen-small class model, seq_len 1024, bf16 compute).  ``vs_baseline``
is measured against the driver BASELINE.json north star of 40k
tokens/sec/chip (at 1.2B on v4-32); >1.0 beats it.  ``mfu`` is the
model-FLOPs-utilization estimate (6N dense + windowed-attention matmul
FLOPs, fwd+bwd, over the chip's peak bf16 FLOP/s) so throughput numbers
are honest about model scale.

Env overrides: PROGEN_BENCH_CONFIG (default "small"),
PROGEN_BENCH_BATCH (default 8), PROGEN_BENCH_STEPS (default 10),
PROGEN_BENCH_ATTN ("xla" | "pallas", default "pallas" — measured faster
at every config, see benchmarks/attention.md),
PROGEN_BENCH_SGU ("xla" | "pallas", default "pallas" — blocked-causal
fused SGU kernel, see benchmarks/sgu.md),
PROGEN_BENCH_REMAT ("0"/"1", default on for base/large/xl),
PROGEN_BENCH_MODE ("train" | "fwdbwd", default "train") — "fwdbwd" times
loss+gradients WITHOUT optimizer state, the only way to run the 1.2B+
configs on a single 16GB v5e chip (f32 Adam moments alone exceed HBM;
the north-star v4-32 setting shards them over fsdp).  The metric string
labels the mode so the numbers cannot be confused.
PROGEN_BENCH_SUPERSTEP (default 1) — fuse K optimizer steps per dispatch
via train_multi_step (train mode only); benchmarks/bench_superstep.py
sweeps K and records the steps/s ladder.

The only output is a device rate, so this runs on a TPU or not at all:
off-TPU it exits non-zero naming the platform JAX found, a device kind
missing from the one peak table (``progen_tpu/observe/flops.py``) is an
error, and any failure inside a run (backend init, OOM, compile error)
is a traceback and a non-zero exit — never a record.  JAX is initialized
in this process only (a chip belongs to one process at a time).

Compiled executables persist in the compile cache
(``progen_tpu/core/cache.py``: ``JAX_COMPILATION_CACHE_DIR`` or the
checkout's ``.jax_cache``) so repeat invocations skip recompilation.

PROGEN_BENCH_CONFIGS=small,base,large runs the whole ladder — one JSON
line per config, each with the per-config defaults from LADDER (the
best-known single-chip setting for that scale, benchmarks/configs.md) —
so a single driver invocation captures every scale, not just small.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.core.cache import enable_compilation_cache
from progen_tpu.observe.platform import require_tpu, stamp_record

NORTH_STAR_TOKENS_PER_SEC_PER_CHIP = 40_000.0


def synthetic_uniref_batch(rng: np.random.Generator, batch: int, seq_len: int):
    """Uniref50-shaped rows: '# ' + uppercase residues, +1 offset, BOS col,
    pad tail — same layout the tfrecord collate emits."""
    out = np.zeros((batch, seq_len + 1), dtype=np.int32)
    for i in range(batch):
        n = int(rng.integers(seq_len // 2, seq_len + 1))
        residues = rng.integers(ord("A"), ord("Z") + 1, size=n - 2)
        row = np.concatenate(([ord("#"), ord(" ")], residues)) + 1
        out[i, 1 : 1 + n] = row
    return out


# Per-config ladder defaults: the best-known single-chip setting for each
# scale (measured, benchmarks/configs.md).  large trains its full step
# only sharded (f32 Adam state > one chip's HBM), so its single-chip row
# is fwd+bwd -- the metric string says so.
LADDER = {
    "small": dict(batch=8, mode="train", remat=False, remat_policy="full"),
    "base": dict(batch=4, mode="train", remat=True, remat_policy="attn"),
    "large": dict(batch=4, mode="fwdbwd", remat=True, remat_policy="full"),
}


def run_one(config_name: str, *, batch: int, steps: int, attn_impl: str,
            sgu_impl: str, mode: str, remat: bool,
            remat_policy: str, superstep: int = 1) -> dict:
    from progen_tpu.core.mesh import MeshConfig, make_mesh
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen
    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.observe import model_flops_per_token, peak_flops_per_chip
    from progen_tpu.train import make_optimizer, make_train_functions

    warmup = 3

    cfg = CONFIGS[config_name]
    n_chips = jax.device_count()
    mesh = make_mesh(MeshConfig()) if n_chips > 1 else None

    # pallas on a >1-chip mesh must run full-manual inside shard_map — the
    # model needs the mesh (same rule the Trainer applies).
    needs_mesh = attn_impl == "pallas" or sgu_impl == "pallas"
    model = ProGen(config=cfg, policy=make_policy(mixed_precision=True),
                   attn_impl=attn_impl, sgu_impl=sgu_impl, remat=remat,
                   remat_policy=remat_policy,
                   mesh=mesh if needs_mesh else None)
    sample = jnp.zeros((batch, cfg.seq_len), jnp.int32)

    rng = np.random.default_rng(0)
    batches = [
        jnp.asarray(synthetic_uniref_batch(rng, batch, cfg.seq_len))
        for _ in range(4)
    ]

    superstep = max(1, int(superstep))
    if superstep > 1 and mode != "train":
        raise SystemExit(
            f"PROGEN_BENCH_SUPERSTEP={superstep} needs "
            f"PROGEN_BENCH_MODE=train (got {mode!r})")

    if mode == "train":
        fns = make_train_functions(
            model, make_optimizer(2e-4), sample,
            mesh=mesh, strategies=("dp",),
        )
        state = fns.init_state(jax.random.key(0))
        num_params = sum(x.size for x in jax.tree.leaves(state.params))
        if superstep > 1:
            # one (K, 1, B, L) superbatch, re-transferred per dispatch:
            # train_multi_step donates its superbatch buffer
            host_super = np.stack([
                synthetic_uniref_batch(rng, batch, cfg.seq_len)
                for _ in range(superstep)
            ])[:, None]
            run = lambda s, b: fns.train_multi_step(
                s, jnp.asarray(host_super))
        else:
            run = lambda s, b: fns.train_step(s, b)
    elif mode == "fwdbwd":
        if n_chips > 1:
            # fwdbwd_step is jitted without mesh shardings; dividing by
            # n_chips would report a per-chip rate no chip actually ran
            raise SystemExit(
                "PROGEN_BENCH_MODE=fwdbwd is single-chip only "
                f"(found {n_chips} devices); use mode=train for multi-chip"
            )
        # loss + gradients only: no optimizer state, so the 1.2B+ configs
        # fit a single 16GB chip.  The grad norm is a returned output, so
        # the backward cannot be dead-code-eliminated — and no param-sized
        # copy is written (this mode exists to live at the HBM edge).
        import optax

        from progen_tpu.parallel import unbox
        from progen_tpu.train.loss import batch_loss

        params = unbox(jax.jit(model.init)(jax.random.key(0), sample))["params"]
        num_params = sum(x.size for x in jax.tree.leaves(params))

        def loss_fn(p, b):
            logits = model.apply({"params": p}, b[:, :-1])
            return batch_loss(logits, b[:, 1:])

        @jax.jit
        def fwdbwd_step(p, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            return {"loss": loss, "grad_norm": optax.global_norm(grads)}

        state = params
        run = lambda s, b: (s, fwdbwd_step(s, b))
    else:
        raise ValueError(f"unknown PROGEN_BENCH_MODE {mode!r}")

    # JAX returns before the device finishes: the clock stops only after
    # block_until_ready.  grad_norm is among the outputs in both modes,
    # so the backward is live.
    sync = jax.block_until_ready

    # dispatch count: each fused dispatch covers `superstep` optimizer
    # steps, so a K-sweep at fixed PROGEN_BENCH_STEPS compares equal work
    dispatches = max(1, steps // superstep)
    steps = dispatches * superstep

    for i in range(warmup):
        state, metrics = run(state, batches[i % len(batches)])
    sync(metrics)

    t0 = time.perf_counter()
    for i in range(dispatches):
        state, metrics = run(state, batches[i % len(batches)])
    sync(metrics)
    dt = time.perf_counter() - t0

    tokens = steps * batch * cfg.seq_len
    tps_chip = tokens / dt / n_chips

    peak = peak_flops_per_chip()  # raises on a kind not in the table
    mfu = (model_flops_per_token(cfg, num_params, sgu_impl=sgu_impl)
           * tps_chip / peak)

    return stamp_record({
        "metric": (
            f"uniref50-shaped "
            f"{'train' if mode == 'train' else 'fwd+bwd (no optimizer)'}"
            f" throughput, ProGen-{config_name} "
            f"(seq_len {cfg.seq_len}, batch {batch}, bf16, "
            f"{attn_impl} attn, {sgu_impl} sgu"
            f"{(', remat:' + remat_policy) if remat else ''}"
            f"{f', superstep {superstep}' if superstep > 1 else ''}, "
            f"{n_chips} chip(s))"
        ),
        "value": round(tps_chip, 1),
        "unit": "tokens/sec/chip",
        "steps_per_sec": round(steps / dt, 3),
        "superstep": superstep,
        # vs_baseline compares TRAIN steps to the train-step north
        # star; a lighter fwd+bwd-only run must not claim the ratio
        "vs_baseline": (
            round(tps_chip / NORTH_STAR_TOKENS_PER_SEC_PER_CHIP, 3)
            if mode == "train" else None
        ),
        "mfu": round(mfu, 4),
        "params": num_params,
        "sgu_impl": sgu_impl,
    })


def main() -> None:
    enable_compilation_cache()
    require_tpu()
    steps = int(os.environ.get("PROGEN_BENCH_STEPS", "10"))
    attn_impl = os.environ.get("PROGEN_BENCH_ATTN", "pallas")
    sgu_impl = os.environ.get("PROGEN_BENCH_SGU", "pallas")
    superstep = int(os.environ.get("PROGEN_BENCH_SUPERSTEP", "1"))

    ladder = os.environ.get("PROGEN_BENCH_CONFIGS")
    if ladder:
        n_chips = jax.device_count()
        for name in (n.strip() for n in ladder.split(",")):
            if name not in LADDER:
                print(f"skipping unknown ladder config {name!r} "
                      f"(known: {', '.join(sorted(LADDER))})",
                      file=sys.stderr, flush=True)
                continue
            spec = dict(LADDER[name])
            if spec["mode"] == "fwdbwd" and n_chips > 1:
                # fwdbwd is the single-chip stand-in for configs whose
                # full train state exceeds one chip; on a real slice the
                # sharded train mode is the meaningful measurement
                spec.update(mode="train")
            print(json.dumps(run_one(
                name, batch=spec["batch"], steps=steps,
                attn_impl=attn_impl, sgu_impl=sgu_impl, mode=spec["mode"],
                remat=spec["remat"], remat_policy=spec["remat_policy"],
                superstep=superstep if spec["mode"] == "train" else 1,
            )), flush=True)
        return

    config_name = os.environ.get("PROGEN_BENCH_CONFIG", "small")
    remat_default = config_name in ("base", "large", "xl")
    print(json.dumps(run_one(
        config_name,
        batch=int(os.environ.get("PROGEN_BENCH_BATCH", "8")),
        steps=steps,
        attn_impl=attn_impl,
        sgu_impl=sgu_impl,
        mode=os.environ.get("PROGEN_BENCH_MODE", "train"),
        remat=os.environ.get("PROGEN_BENCH_REMAT",
                             "1" if remat_default else "0") == "1",
        remat_policy=os.environ.get("PROGEN_BENCH_REMAT_POLICY", "full"),
        superstep=superstep,
    )), flush=True)


if __name__ == "__main__":
    main()
