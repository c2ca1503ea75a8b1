"""Windowed-attention microbench: Pallas kernel vs XLA path.

The committed script behind ``benchmarks/attention.md``'s op table.
Method (one chip; per-dispatch overhead would otherwise dominate a
sub-millisecond op):

* each impl runs inside ONE jitted ``lax.scan`` of ``--iters``
  iterations, chaining the output into the next iteration's input so XLA
  cannot dead-code or overlap the iterations;
* timing is wall-clock around ``block_until_ready`` of the final scalar
  (JAX returns before the device finishes);
* ``--reps`` repetitions per impl, INTERLEAVED (xla, pallas, xla, ...)
  so drift of the machine hits both equally; medians reported.

Usage::

    python benchmarks/bench_attention.py            # both table shapes
    python benchmarks/bench_attention.py --shape 8,8,1024,128,256
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

from progen_tpu.observe.platform import stamp_record

SHAPES = [
    (8, 8, 1024, 128, 256),   # ProGen-small class
    (4, 12, 2048, 128, 512),  # ProGen-base class
]


def make_runner(impl: str, backward: bool, shape, iters: int):
    b, h, l, dh, wsz = shape
    scale = dh ** -0.5

    if impl == "pallas":
        from progen_tpu.ops.pallas_attention import pallas_local_attention

        def op(q, k, v):
            return pallas_local_attention(q, k, v, wsz, scale)
    else:
        from progen_tpu.ops.local_attention import local_attention

        def op(q, k, v):
            return local_attention(q, k, v, window_size=wsz, scale=scale)

    if backward:
        def once(q, k, v):
            def loss(q, k, v):
                return jnp.sum(op(q, k, v).astype(jnp.float32))

            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return dq, dk, dv
    else:
        def once(q, k, v):
            o = op(q, k, v)
            return o, o, o

    @jax.jit
    def run(q, k, v):
        def body(carry, _):
            q, k, v = carry
            a, b_, c = once(q, k, v)
            # chain outputs into inputs: iterations cannot be elided
            return (q + 1e-6 * a.astype(q.dtype),
                    k + 1e-6 * b_.astype(k.dtype),
                    v + 1e-6 * c.astype(v.dtype)), None

        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None, length=iters)
        return jnp.sum(q.astype(jnp.float32))

    return run


def time_one(run, shape) -> float:
    b, h, l, dh, _ = shape
    key = jax.random.key(0)
    qkv = [
        jax.random.normal(k, (b, h, l, dh), jnp.bfloat16)
        for k in jax.random.split(key, 3)
    ]
    t0 = time.perf_counter()
    jax.block_until_ready(run(*qkv))
    return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=str, default=None,
                    help="B,H,L,Dh,wsz (default: both table shapes)")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args()

    shapes = ([tuple(int(x) for x in args.shape.split(","))]
              if args.shape else SHAPES)
    for shape in shapes:
        for backward in (False, True):
            runners = {
                impl: make_runner(impl, backward, shape, args.iters)
                for impl in ("xla", "pallas")
            }
            for impl, run in runners.items():
                time_one(run, shape)  # compile + warm
            times: dict[str, list[float]] = {"xla": [], "pallas": []}
            for _ in range(args.reps):
                for impl, run in runners.items():  # interleaved
                    times[impl].append(time_one(run, shape))
            med = {impl: statistics.median(ts) / args.iters * 1e3
                   for impl, ts in times.items()}
            print(
                f"shape={shape} pass={'fwd+bwd' if backward else 'fwd'} "
                f"xla={med['xla']:.3f}ms pallas={med['pallas']:.3f}ms "
                f"speedup={med['xla'] / med['pallas']:.2f}x",
                flush=True,
            )
            b, h, l, dh, wsz = shape
            print(json.dumps(stamp_record({
                "bench": "attention",
                "batch": b, "heads": h, "len": l, "dim_head": dh,
                "window": wsz,
                "pass": "fwd+bwd" if backward else "fwd",
                "platform": jax.default_backend(),
                "xla_ms": round(med["xla"], 4),
                "pallas_ms": round(med["pallas"], 4),
                "speedup": round(med["xla"] / med["pallas"], 3),
            })), flush=True)


if __name__ == "__main__":
    main()
