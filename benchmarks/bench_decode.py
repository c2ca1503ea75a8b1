"""Autoregressive decode throughput, split by phase.

The reference samples by re-running a FULL forward over the whole padded
sequence per generated token (``/root/reference/progen_transformer/
utils.py:106-135``) — O(L) jitted full-sequence forwards.  This
framework's sampler is one ``lax.scan`` of cached single-token steps
(O(window) attention per token); this bench reports its tokens/sec so
the decode path has a number, not just an asymptotic claim.

Reported PER PHASE (serving cares about them separately):

* **prefill** — consuming the prime.  Two implementations: the one-pass
  parallel prefill (``decode/prefill.py``: ONE batched forward, harvest
  caches) vs the sequential scan of single-token decode steps the
  sampler historically used.  The speedup column is the whole point of
  the prefill subsystem;
* **decode** — generating new tokens after the prime (chunked early-exit
  sampler), the steady-state serving cost per token.

Timing wraps work that ends in a host transfer of the sampled ids or in
``block_until_ready`` (JAX returns before the device finishes).  Usage::

    python benchmarks/bench_decode.py [--config small] [--length 1024]

Sharded decode (models too big for one chip, BASELINE's XL row) runs the
same bench over a mesh — e.g. ProGen-large executed on the virtual
8-device CPU mesh::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/bench_decode.py --config large \
        --mesh 1,4,2,1 --strategies fsdp,tp --length 64 --prime 8 \
        --batches 1 --reps 2
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from progen_tpu.observe.platform import stamp_record

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="small")
    ap.add_argument("--length", type=int, default=1024)
    ap.add_argument("--prime", type=int, default=32)
    ap.add_argument("--batches", type=int, default=(1, 8), nargs="+")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--mesh", default=None,
                    help="mesh spec data,fsdp,tensor,seq — decode with "
                         "params sharded over it (never gathered)")
    ap.add_argument("--strategies", default="fsdp,tp",
                    help="sharding strategies when --mesh is given")
    ap.add_argument("--chunk", type=int, default=64,
                    help="decode steps per device program (chunked sampler)")
    args = ap.parse_args()

    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()

    from progen_tpu.core.precision import make_policy
    from progen_tpu.decode import (
        ProGenDecodeStep,
        init_caches,
        make_chunked_sampler,
        make_prefiller,
        pad_prime_length,
    )
    from progen_tpu.models import ProGen
    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.parallel import unbox

    cfg = CONFIGS[args.config]
    length = min(args.length, cfg.seq_len)
    policy = make_policy(True)
    model = ProGen(config=cfg, policy=policy)
    toks = jnp.zeros((1, cfg.seq_len), jnp.int32)
    if args.mesh is not None:
        from progen_tpu.core.mesh import MeshConfig, make_mesh
        from progen_tpu.parallel.sharding import param_shardings

        strategies = tuple(args.strategies.split(","))
        mesh = make_mesh(MeshConfig.parse(args.mesh))
        shardings = param_shardings(model, toks, mesh, strategies)["params"]
        params = jax.jit(
            lambda k: unbox(model.init(k, toks))["params"],
            out_shardings=shardings,
        )(jax.random.key(0))
        sampler = make_chunked_sampler(
            cfg, policy, mesh=mesh, strategies=strategies,
            params_shardings=shardings, chunk_size=args.chunk)
        prefiller = make_prefiller(cfg, policy, mesh=mesh,
                                   strategies=strategies)
        ndev = len(mesh.devices.reshape(-1))
        print(f"mesh {args.mesh} ({ndev} devices), strategies {strategies}",
              flush=True)
    else:
        params = unbox(jax.jit(model.init)(jax.random.key(0), toks))["params"]
        sampler = make_chunked_sampler(cfg, policy, chunk_size=args.chunk)
        prefiller = make_prefiller(cfg, policy)

    # sequential prefill reference: the prime teacher-forced through the
    # single-token decode scan — what the sampler did before prefill.py
    step_model = ProGenDecodeStep(config=cfg, policy=policy)

    @jax.jit
    def seq_prefill(params, tokens):
        b, p = tokens.shape
        caches = init_caches(cfg, b, policy, decode_len=length)

        def body(carry, t):
            logits, caches = step_model.apply(
                params, jax.lax.dynamic_index_in_dim(
                    tokens, t, axis=1, keepdims=False), t, carry)
            return caches, None

        caches, _ = jax.lax.scan(body, caches, jnp.arange(p))
        return caches

    def timed(fn, *fn_args):
        fn(*fn_args)  # compile + warm
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            fn(*fn_args)
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    rng = np.random.default_rng(0)
    for b in args.batches:
        prime = jnp.asarray(
            rng.integers(1, cfg.num_tokens, (b, args.prime)), jnp.int32)
        p = args.prime + 1  # + BOS, matching the sampler's add_bos path
        p_pad = pad_prime_length(p, cfg.window_size, cfg.seq_len)
        tokens = jnp.zeros((b, p_pad), jnp.int32).at[:, 1:p].set(prime)
        lengths = jnp.full((b,), p, jnp.int32)

        # --- prefill phase: one-pass parallel vs sequential scan ---
        t_par = timed(lambda: jax.block_until_ready(prefiller(
            {"params": params}, tokens, lengths, length)))
        t_seq = timed(lambda: jax.block_until_ready(seq_prefill(
            {"params": params}, tokens[:, :p])))
        print(
            f"config={args.config} batch={b} prime={p}: "
            f"prefill one-pass {b * p / t_par:,.0f} tokens/sec "
            f"({t_par * 1e3:.1f} ms), sequential "
            f"{b * p / t_seq:,.0f} tokens/sec ({t_seq * 1e3:.1f} ms), "
            f"speedup {t_seq / t_par:.1f}x",
            flush=True,
        )

        # --- decode phase: chunked sampler minus its prefill ---
        run = lambda k: np.asarray(sampler(
            {"params": params}, k, prime, length=length, top_k=25,
            add_bos=True))
        med = timed(run, jax.random.key(1))
        new_tokens = b * (length - p)
        t_dec = max(med - t_par, 1e-9)
        print(
            f"config={args.config} batch={b} length={length} "
            f"prime={args.prime}: {med:.3f}s/seq-batch, "
            f"decode {new_tokens / t_dec:,.0f} tokens/sec "
            f"({t_dec / (length - p) * 1e3:.2f} ms/token), "
            f"end-to-end {(new_tokens + b * p) / med:,.0f} tokens/sec",
            flush=True,
        )
        print(json.dumps(stamp_record({
            "bench": "decode",
            "config": args.config,
            "batch": b, "length": length, "prime": args.prime,
            "chunk": args.chunk, "mesh": args.mesh,
            "platform": jax.default_backend(),
            "prefill_onepass_tok_per_s": round(b * p / t_par, 1),
            "prefill_sequential_tok_per_s": round(b * p / t_seq, 1),
            "prefill_speedup": round(t_seq / t_par, 2),
            "decode_tok_per_s": round(new_tokens / t_dec, 1),
            "decode_ms_per_token": round(
                t_dec / (length - p) * 1e3, 3),
            "end_to_end_tok_per_s": round(
                (new_tokens + b * p) / med, 1),
        })), flush=True)


if __name__ == "__main__":
    main()
