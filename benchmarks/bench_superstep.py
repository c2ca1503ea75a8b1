"""Superstep microbench: fused K-step dispatch vs the per-step loop.

Sweeps ``train_multi_step``'s fusion factor K over the same total number
of optimizer steps and reports steps/sec per K — the dispatch-overhead
curve behind ``benchmarks/superstep.md``.  One JSON LINE per K::

    {"bench": "superstep", "k": 8, "accum": 1, "batch": 8, "seq_len": 64,
     "dim": 64, "depth": 2, "steps_per_sec": ..., "tokens_per_sec": ...,
     "speedup_vs_k1": ..., "platform": "cpu", "git_sha": ...}

K=1 is measured through ``train_step`` — the exact per-dispatch path the
trainer runs at ``--superstep 1`` — so ``speedup_vs_k1`` is the honest
"what does fusing buy" number.  Fused dispatches re-transfer a fresh
host-staged superbatch every call (the buffer is donated), matching the
trainer's stager feed.

The default shapes are TINY on purpose: on a tiny model the step's
compute is small, so host-dispatch overhead dominates and the K-curve is
visible even on a CPU host (where a big model would drown it in FLOPs).
On real accelerators pass ``--config small`` for production shapes.
Backend-init failures reuse ``bench.py``'s retried subprocess probe and
emit its parseable JSON error record instead of a traceback.

Usage::

    python benchmarks/bench_superstep.py                  # K in {1,4,8,16}
    python benchmarks/bench_superstep.py --steps 16 --reps 1 --ks 1,8
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.observe.platform import stamp_record

DEFAULT_KS = (1, 4, 8, 16)


def build(config_name: str, batch: int, accum: int):
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.train import make_optimizer, make_train_functions

    if config_name == "tiny":
        cfg = ProGenConfig(
            num_tokens=128, dim=64, seq_len=64, depth=2, window_size=32,
            global_mlp_depth=1, heads=2, dim_head=32, ff_mult=2,
        )
        policy = make_policy(mixed_precision=False)  # f32: CPU-honest
    else:
        from progen_tpu.models.configs import CONFIGS

        cfg = CONFIGS[config_name]
        policy = make_policy(mixed_precision=True)

    model = ProGen(config=cfg, policy=policy)
    optimizer = make_optimizer(2e-4, grad_accum_every=accum)
    sample = jnp.zeros((batch, cfg.seq_len), jnp.int32)
    fns = make_train_functions(model, optimizer, sample,
                               grad_accum_every=accum)
    return cfg, fns


def time_k(fns, cfg, k: int, batch: int, accum: int, steps: int,
           reps: int) -> float:
    """Median steps/sec running ``steps`` optimizer steps at fusion K
    (K=1 = per-step train_step dispatches, the trainer's unfused path)."""
    from bench import synthetic_uniref_batch

    rng = np.random.default_rng(0)
    state = fns.init_state(jax.random.key(0))

    def sync(metrics):
        float(np.asarray(metrics["grad_norm"]).ravel()[-1])

    if k == 1:
        hosts = [
            synthetic_uniref_batch(rng, batch, cfg.seq_len)
            for _ in range(4)
        ]

        def run_steps(state):
            for i in range(steps * accum):
                # fresh transfer per micro-batch: train_step donates
                b = jnp.asarray(hosts[i % len(hosts)])
                state, metrics = fns.train_step(state, b)
            return state, metrics
    else:
        host_super = np.stack([
            synthetic_uniref_batch(rng, batch, cfg.seq_len)
            for _ in range(k * accum)
        ]).reshape(k, accum, batch, cfg.seq_len + 1)
        dispatches = steps // k

        def run_steps(state):
            for _ in range(dispatches):
                # fresh transfer per dispatch: the superbatch is donated
                state, metrics = fns.train_multi_step(
                    state, jnp.asarray(host_super))
            return state, metrics

    state, metrics = run_steps(state)  # compile + warm
    sync(metrics)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, metrics = run_steps(state)
        sync(metrics)
        times.append(time.perf_counter() - t0)
    return steps / statistics.median(times)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tiny",
                    help="'tiny' (CPU-honest default) or a model config "
                         "name (small/base/...)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1,
                    help="grad_accum_every (superbatch is (K, accum, B, L))")
    ap.add_argument("--steps", type=int, default=48,
                    help="optimizer steps per rep; must be divisible by "
                         "every K in --ks")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--ks", default=",".join(map(str, DEFAULT_KS)))
    args = ap.parse_args()

    ks = tuple(int(x) for x in args.ks.split(","))
    bad = [k for k in ks if args.steps % k]
    if bad:
        ap.error(f"--steps {args.steps} not divisible by K in {bad}")

    cfg, fns = build(args.config, args.batch, args.accum)
    platform = jax.default_backend()
    results = {}
    for k in ks:
        results[k] = time_k(fns, cfg, k, args.batch, args.accum,
                            args.steps, args.reps)
    base = results.get(1)
    for k in ks:
        sps = results[k]
        print(json.dumps(stamp_record({
            "bench": "superstep",
            "k": k,
            "accum": args.accum,
            "batch": args.batch,
            "seq_len": cfg.seq_len,
            "dim": cfg.dim,
            "depth": cfg.depth,
            "steps": args.steps,
            "steps_per_sec": round(sps, 3),
            "tokens_per_sec": round(
                sps * args.batch * args.accum * cfg.seq_len, 1),
            "speedup_vs_k1": round(sps / base, 3) if base else None,
            "platform": platform,
        })), flush=True)


if __name__ == "__main__":
    main()
