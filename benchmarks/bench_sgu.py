"""SGU spatial-gate microbench: blocked-causal Pallas kernel vs XLA path.

The committed script behind ``benchmarks/sgu.md``'s op table.  Same
method as ``bench_attention.py`` (one jitted ``lax.scan`` per impl
chaining outputs into inputs, interleaved reps, medians) but emits ONE
JSON LINE per (n, pass) so driver runs can ingest the sweep directly::

    {"bench": "sgu", "n": 1024, "d": 2048, "pass": "fwd", "xla_ms": ...,
     "pallas_ms": ..., "speedup": ..., "block": 128,
     "blocks_executed": 36, "blocks_dense": 64, "flop_ratio": 0.5625}

The static block-skip fields come from
:func:`progen_tpu.ops.pallas_sgu.sgu_block_flops` — on a CPU-only host
the timings measure the INTERPRETER (meaningless for kernel speed; the
block-skip counts are the honest artifact there), so the record carries
a ``"platform"`` stamp.  A run that raises exits non-zero with its
traceback.

Usage::

    python benchmarks/bench_sgu.py                 # n in {512, 1024, 2048}
    python benchmarks/bench_sgu.py --n 1024 --d 512 --iters 20
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

# d = dim * ff_mult / 2 of the ProGen-small class (the gmlp hidden half)
SWEEP_N = (512, 1024, 2048)
DEFAULT_D = 2048


def make_runner(impl: str, backward: bool, n: int, d: int, batch: int,
                iters: int):
    if impl == "pallas":
        from progen_tpu.ops.pallas_sgu import pallas_spatial_gate as op
    else:
        from progen_tpu.ops.sgu import spatial_gate

        def op(res, gate, w, bias):
            return res * spatial_gate(gate, w, bias)

    if backward:
        def once(res, gate, w, bias):
            def loss(res, gate, w, bias):
                return jnp.sum(op(res, gate, w, bias).astype(jnp.float32))

            return jax.grad(loss, argnums=(0, 1, 2, 3))(res, gate, w, bias)
    else:
        def once(res, gate, w, bias):
            o = op(res, gate, w, bias)
            return o, o, w, bias

    @jax.jit
    def run(res, gate, w, bias):
        def body(carry, _):
            res, gate, w, bias = carry
            dr, dg, dw, db = once(res, gate, w, bias)
            # chain outputs into inputs: iterations cannot be elided
            return (res + 1e-6 * dr.astype(res.dtype),
                    gate + 1e-6 * dg.astype(gate.dtype),
                    w + 1e-6 * dw.astype(w.dtype),
                    bias + 1e-6 * db.astype(bias.dtype)), None

        carry, _ = jax.lax.scan(body, (res, gate, w, bias), None,
                                length=iters)
        return jnp.sum(carry[0].astype(jnp.float32))

    return run


def time_one(run, n: int, d: int, batch: int) -> float:
    k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
    res = jax.random.normal(k1, (batch, n, d), jnp.bfloat16)
    gate = jax.random.normal(k2, (batch, n, d), jnp.bfloat16)
    # benchmark input magnitude only — bf16 rounding of the scale
    # cannot affect a timing measurement
    # graftcheck: disable=dtype-f32-literal
    w = jax.random.normal(k3, (n, n), jnp.bfloat16) * 0.001
    bias = jnp.ones((n, 1), jnp.bfloat16)
    t0 = time.perf_counter()
    float(run(res, gate, w, bias))  # host transfer = the only reliable sync
    return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None,
                    help="sequence length (default: sweep 512/1024/2048)")
    ap.add_argument("--d", type=int, default=DEFAULT_D)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    from progen_tpu.ops.pallas_sgu import sgu_block_flops

    platform = jax.default_backend()
    for n in ([args.n] if args.n else SWEEP_N):
        skip = sgu_block_flops(n, args.d)
        for backward in (False, True):
            runners = {
                impl: make_runner(impl, backward, n, args.d, args.batch,
                                  args.iters)
                for impl in ("xla", "pallas")
            }
            for run in runners.values():
                time_one(run, n, args.d, args.batch)  # compile + warm
            times = {"xla": [], "pallas": []}
            for _ in range(args.reps):
                for impl, run in runners.items():  # interleaved
                    times[impl].append(time_one(run, n, args.d, args.batch))
            med = {impl: statistics.median(ts) / args.iters * 1e3
                   for impl, ts in times.items()}
            print(json.dumps(stamp_record({
                "bench": "sgu",
                "n": n,
                "d": args.d,
                "batch": args.batch,
                "pass": "fwd+bwd" if backward else "fwd",
                "platform": platform,
                "xla_ms": round(med["xla"], 4),
                "pallas_ms": round(med["pallas"], 4),
                "speedup": round(med["xla"] / med["pallas"], 3),
                "block": skip["block"],
                "blocks_executed": skip["blocks_executed"],
                "blocks_dense": skip["blocks_dense"],
                "flop_ratio": round(skip["ratio"], 5),
            })), flush=True)


if __name__ == "__main__":
    main()
