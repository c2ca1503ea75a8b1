"""Cold-start latency: engine build, warmup, and first-request TTFT.

A serving replica that just restarted (crash, preemption, scale-up) pays
JIT compilation on the first request unless the programs were built
ahead of time.  This bench measures that tax end to end, once per
invocation::

    JAX_PLATFORMS=cpu python benchmarks/bench_coldstart.py \
        --config small --no-aot
    JAX_PLATFORMS=cpu python benchmarks/bench_coldstart.py \
        --config small --aot

and prints ONE JSON line::

    {"metric": "coldstart", "aot": ..., "build_s": ..., "warmup_s": ...,
     "ttft_s": ..., "total_s": ..., ...}

``build_s`` is engine construction, ``warmup_s`` the AOT
``lower().compile()`` sweep over the (prefill bucket, decode chunk)
program grid (0 without ``--aot``), ``ttft_s`` the time from submitting
the first request until its first decode chunk has run — with ``--aot``
this is pure execution, without it the JIT pauses land here.  The JAX
persistent compilation cache is turned OFF here
(``jax_enable_compilation_cache``): the uncached start is what this
measures, and a warm cache would make every start warm.  ``--out`` appends to a JSONL file
(``benchmarks/coldstart.jsonl`` by convention).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.observe.platform import stamp_record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="small")
    ap.add_argument("--aot", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="AOT-compile the (bucket, chunk) program grid "
                         "before the first request")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prime", type=int, default=32,
                    help="prime length of the measured first request")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--out", metavar="FILE", default=None,
                    help="also append the record to this JSONL file")
    args = ap.parse_args()

    # a warm cache is not a cold start: the uncached arm is the measurement
    jax.config.update("jax_enable_compilation_cache", False)

    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode import Request, ServingEngine
    from progen_tpu.models import ProGen
    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.parallel import unbox

    cfg = CONFIGS[args.config]
    policy = make_policy(True)
    model = ProGen(config=cfg, policy=policy)
    toks = jnp.zeros((1, cfg.seq_len), jnp.int32)
    params = unbox(jax.jit(model.init)(jax.random.key(0), toks))

    prime = min(args.prime, cfg.seq_len - args.max_new - 1)
    max_len = min(cfg.seq_len, prime + args.max_new + 1)
    paged_kwargs = dict(paged=True, page_size=args.page_size) \
        if args.paged else {}

    t = time.perf_counter()
    engine = ServingEngine(cfg, params, policy=policy,
                           num_slots=args.slots, chunk_size=args.chunk,
                           max_len=max_len, **paged_kwargs)
    build_s = time.perf_counter() - t

    warmup_s = 0.0
    programs = 0
    if args.aot:
        stats = engine.aot_warmup(max_prime=prime)
        warmup_s = stats["seconds"]
        programs = stats["programs"]

    rng = np.random.default_rng(args.seed)
    req = Request(uid=0,
                  tokens=rng.integers(1, cfg.num_tokens, prime).tolist(),
                  max_new_tokens=args.max_new, top_k=25, temperature=1.0,
                  seed=args.seed)

    t = time.perf_counter()
    engine.submit(req)
    done = engine.step()  # prefill + first chunk (JIT pauses land here)
    ttft_s = time.perf_counter() - t
    done += engine.run_until_idle()
    total_s = time.perf_counter() - t
    assert len(done) == 1 and done[0].ok

    record = stamp_record({
        "metric": "coldstart",
        "config": args.config,
        "aot": args.aot,
        "paged": args.paged,
        "slots": args.slots,
        "chunk": args.chunk,
        "prime": prime,
        "max_new_tokens": args.max_new,
        "aot_programs": programs,
        "build_s": round(build_s, 3),
        "warmup_s": round(warmup_s, 3),
        "ttft_s": round(ttft_s, 3),
        "total_s": round(total_s, 3),
        "generated_tokens": int(len(done[0].tokens)),
        "platform": jax.devices()[0].platform,
    })
    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
