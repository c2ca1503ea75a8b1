"""Sampling CLI — reference ``sample.py`` equivalent
(``/root/reference/sample.py:23-76``): load the last checkpoint, rebuild the
model from its stored config, decode with a prime, print.  Decoding runs
the cached scan sampler instead of O(L) full forwards.
"""

import click


@click.command()
@click.option("--seed", default=42)
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--prime", default="")
@click.option("--top_k", default=25)
@click.option("--temperature", default=1.0)
@click.option("--num_samples", default=1, help="decode N sequences in one batch")
@click.option("--seq_len", default=None, type=int,
              help="decode length (reference sample.py flag); defaults to "
                   "the model's trained seq_len, capped there (the learned "
                   "gMLP weights have no rows past it). Short decodes are "
                   "cheap: caches and the scan are sized to this length.")
@click.option("--mesh", "mesh_spec", default=None,
              help="mesh axis sizes data,fsdp,tensor,seq (-1 = remaining); "
                   "restores the params SHARDED over the mesh and decodes "
                   "SPMD — required when the model does not fit one chip")
@click.option("--strategies", default="fsdp",
              help="comma list of sharding strategies for --mesh restores")
@click.option("--serve", is_flag=True,
              help="decode through the continuous-batching engine instead of "
                   "the batch-synchronous sampler: primes (split --prime on "
                   "'|', or --num_samples copies) become queued requests, "
                   "prefilled in one parallel forward and decoded in early-"
                   "exit chunks (docs/SERVING.md)")
@click.option("--embed", "embed_mode", is_flag=True,
              help="with --serve: embeddings workload — one-pass prefill "
                   "forward per prime, mean-pooled final-layer hidden "
                   "state; prints the (D,) vector stats instead of decoded "
                   "tokens (docs/SERVING.md §8)")
@click.option("--infill", default=None, metavar="TEMPLATE",
              help="with --serve: constrained span-infilling — plain "
                   "characters are frozen scaffold positions, '?' a free "
                   "design position, '[ILV]' a position restricted to that "
                   "set; the engine decodes under the scaffold's per-"
                   "position logit mask so constrained positions can ONLY "
                   "emit allowed tokens (docs/SERVING.md §8)")
@click.option("--slots", default=8, help="engine: max concurrent requests")
@click.option("--chunk", default=32, help="engine: decode steps per device "
                                          "program between refill points")
@click.option("--paged", is_flag=True,
              help="engine: paged SGU gate cache — global page pool + "
                   "per-request page tables instead of per-slot max_len "
                   "slabs (docs/SERVING.md); greedy outputs are "
                   "bit-identical to the fixed-slot engine")
@click.option("--page_size", default=16, help="engine: token rows per page "
                                              "(with --paged)")
@click.option("--quantize", "quantize_mode", default=None,
              type=click.Choice(["weights", "weights+pages"]),
              help="engine: opt-in int8 serving — 'weights' re-types dense "
                   "kernels and SGU spatial weights to int8 (f32 per-channel "
                   "scales); 'weights+pages' additionally stores the paged "
                   "SGU gate cache as 8-bit pages (requires --paged).  Full "
                   "precision stays the default; accuracy is gated by "
                   "bench_serving --verify (docs/SERVING.md §12)")
@click.option("--serve_attempts", default=3,
              help="engine: total tries of the serve loop — a transient "
                   "failure snapshots the host-side request state, rebuilds "
                   "the engine and REPLAYS the in-flight requests (per-"
                   "request seed determinism makes the replay token-"
                   "identical; 1 = fail fast)")
@click.option("--snapshot_path", default=None, metavar="FILE",
              help="engine: where crash snapshots are persisted (JSON, "
                   "host-side request state only; default: not written "
                   "to disk)")
@click.option("--aot_warmup", is_flag=True,
              help="engine: AOT-compile every (prefill bucket, decode "
                   "chunk) program via jit(...).lower().compile() before "
                   "accepting traffic, so no request pays a JIT pause")
@click.option("--disagg", is_flag=True,
              help="engine: disaggregated serving — prefill runs in a "
                   "separate worker program whose cache handles are merged "
                   "into decode slots via a bounded handoff queue, so long "
                   "prefills no longer stall in-flight decode "
                   "(docs/SERVING.md)")
@click.option("--serve_procs", is_flag=True,
              help="with --serve: multi-process serving — spawn prefill "
                   "worker and decode replica SUBPROCESSES (each its own "
                   "JAX runtime) behind a router; cache handles cross "
                   "processes as CRC-framed zero-copy frames "
                   "(docs/SERVING.md §7). Workers rebuild the model from "
                   "this checkpoint, so output is token-identical to the "
                   "in-process engine")
@click.option("--prefill_procs", default=1,
              help="prefill worker processes (with --serve_procs)")
@click.option("--replicas", default=1,
              help="decode replica processes (with --serve_procs)")
@click.option("--autoscale", is_flag=True,
              help="with --serve_procs: run the elastic control plane — "
                   "scale the fleet between the min/max bounds on SLO "
                   "burn rate and queue depth; decisions are journaled "
                   "and printed (docs/SERVING.md §9)")
@click.option("--min_prefill", default=None, type=int,
              help="autoscale floor for prefill workers "
                   "(default: --prefill_procs)")
@click.option("--max_prefill", default=None, type=int,
              help="autoscale ceiling for prefill workers "
                   "(default: --prefill_procs + 2)")
@click.option("--min_replicas", default=None, type=int,
              help="autoscale floor for decode replicas "
                   "(default: --replicas)")
@click.option("--max_replicas", default=None, type=int,
              help="autoscale ceiling for decode replicas "
                   "(default: --replicas + 2)")
@click.option("--swap_at", default=None, type=int,
              help="with --serve_procs: after N completions, hot-swap "
                   "weights with a zero-downtime rolling worker upgrade "
                   "(new generation of the same checkpoint) — no request "
                   "is dropped; completions report their generation")
@click.option("--watchdog_timeout", default=None, type=float,
              help="engine: seconds without a completed serve step before "
                   "the watchdog dumps all-thread stacks to CWD and exits "
                   "nonzero (unset = off); compiles are exempt")
@click.option("--statusz", is_flag=True,
              help="with --serve_procs: serve live /healthz /statusz "
                   "/metricsz in every process (driver + workers) on "
                   "ephemeral loopback ports, printed at startup; "
                   "zero-perturbation (docs/OBSERVABILITY.md)")
@click.option("--trace", is_flag=True,
              help="record request spans in every serving process and "
                   "merge them into one Perfetto trace.json under "
                   "--trace_out (docs/OBSERVABILITY.md)")
@click.option("--trace_out", default="trace_out", metavar="DIR",
              help="directory for per-process trace dumps and the merged "
                   "trace.json (with --trace)")
@click.option("--xprof_dir", default=None, metavar="DIR",
              help="record an xprof/TensorBoard profile of the decode "
                   "into this directory (view with tensorboard)")
def main(seed, checkpoint_path, prime, top_k, temperature, num_samples,
         seq_len, mesh_spec, strategies, serve, embed_mode, infill, slots,
         chunk, paged, page_size, quantize_mode, serve_attempts,
         snapshot_path, aot_warmup, disagg, serve_procs, prefill_procs, replicas,
         autoscale, min_prefill, max_prefill, min_replicas, max_replicas,
         swap_at, watchdog_timeout, statusz, trace, trace_out, xprof_dir):
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()  # a second invocation reads its programs

    from progen_tpu.checkpoint import CheckpointStore, abstract_params_like
    from progen_tpu.core.precision import make_policy
    from progen_tpu.core.rng import KeySeq
    from progen_tpu.data import decode_tokens, encode_tokens
    from progen_tpu.decode import make_sampler
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.observe import profile_trace
    from progen_tpu.observe.trace import (
        configure_tracing,
        get_tracer,
        merge_trace_dir,
        trace_dump_path,
    )

    if trace:
        os.makedirs(trace_out, exist_ok=True)
        configure_tracing(enabled=True, process="driver")

    store = CheckpointStore(checkpoint_path)
    meta = store.restore_meta()
    if meta is None:
        raise SystemExit(f"no checkpoints found at {checkpoint_path}")

    model_config = ProGenConfig.from_dict(meta["model_config"])
    policy = make_policy(True)
    model = ProGen(config=model_config, policy=policy)
    sample_tokens = jnp.zeros((1, model_config.seq_len), jnp.int32)

    mesh = None
    strategy_list = tuple(strategies.split(","))
    param_sh = None
    if mesh_spec is not None:
        from progen_tpu.core.mesh import MeshConfig, make_mesh
        from progen_tpu.parallel.sharding import param_shardings

        try:
            mesh = make_mesh(MeshConfig.parse(mesh_spec))
        except ValueError as e:
            raise click.BadParameter(str(e), param_hint="--mesh")
        # restore each shard straight to its device — no host ever holds
        # the full state (the whole point for >1-chip models)
        param_sh = param_shardings(
            model, sample_tokens, mesh, strategy_list)["params"]
    params = store.restore_params(
        abstract_params_like(model, sample_tokens, shardings=param_sh))
    store.close()

    num_params = sum(x.size for x in jax.tree.leaves(params))
    if seq_len is None:
        seq_len = model_config.seq_len
    elif seq_len > model_config.seq_len:
        print(f"capping --seq_len {seq_len} to the model's trained "
              f"seq_len {model_config.seq_len}")
        seq_len = model_config.seq_len
    print(f"params: {num_params:,}")
    print(f"sequence length: {seq_len}")
    print(f"trained for {max(meta['next_seq_index'], 0)} sequences")

    if (embed_mode or infill) and not serve:
        raise click.BadParameter(
            "--embed/--infill are serving workloads; add --serve",
            param_hint="--serve")
    if embed_mode and infill:
        raise click.BadParameter("pick ONE of --embed / --infill",
                                 param_hint="--embed")

    if serve:
        from progen_tpu.decode import Request, ServingEngine, run_with_restarts
        from progen_tpu.resilience import Watchdog

        primes = prime.split("|") if "|" in prime else [prime] * num_samples
        requests = []
        if infill is not None:
            from progen_tpu.workloads import ScaffoldSpec

            def template_entry(seg):
                if seg == "?":
                    return None
                if len(seg) > 1:  # bracket set [ILV]
                    return tuple(encode_tokens(c)[0] for c in seg)
                return encode_tokens(seg)[0]

            segs, i = [], 0
            while i < len(infill):
                if infill[i] == "[":
                    j = infill.index("]", i)
                    segs.append(infill[i + 1:j])
                    i = j + 1
                else:
                    segs.append(infill[i])
                    i += 1
            scaffold = ScaffoldSpec(
                template=[0] + [template_entry(s) for s in segs],
                vocab=model_config.num_tokens)
            primes = [infill] * num_samples
            kw = scaffold.request_kwargs()
            requests = [Request(uid=i, top_k=top_k, temperature=temperature,
                                seed=seed + i, workload="infill", **kw)
                        for i in range(num_samples)]
        else:
            for i, p in enumerate(primes):
                toks = [0] + encode_tokens(p)  # BOS-prefixed, like add_bos
                requests.append(Request(
                    uid=i, tokens=toks, max_new_tokens=seq_len - len(toks),
                    top_k=top_k, temperature=temperature, seed=seed + i,
                    workload="embed" if embed_mode else "generate"))

        def print_embedding(comp):
            v = np.asarray(comp.embedding)
            print(f"\n {primes[comp.uid]} \n", "*" * 40,
                  f"[embed, dim={v.shape[0]}, "
                  f"norm={float(np.linalg.norm(v)):.4f}, "
                  f"{comp.latency:.2f}s]\n", np.round(v[:8], 4).tolist())

        if serve_procs:
            if mesh_spec is not None:
                raise click.BadParameter(
                    "--mesh shards ONE process's decode over devices; "
                    "--serve_procs spawns single-device worker processes — "
                    "pick one", param_hint="--serve_procs")
            from progen_tpu.serve import ServeCluster, make_spec

            # workers rebuild bit-identical params by restoring this same
            # checkpoint, so cluster output matches the in-process engine
            wspec = make_spec(
                model_config, mixed_precision=True,
                checkpoint_path=os.path.abspath(checkpoint_path),
                engine=dict(num_slots=slots, chunk_size=chunk,
                            max_len=seq_len, paged=paged,
                            page_size=page_size, quantize=quantize_mode),
                trace=({"dir": os.path.abspath(trace_out)}
                       if trace else None),
                statusz=statusz)
            cluster = ServeCluster(wspec, prefill_procs=prefill_procs,
                                   replicas=replicas)
            control = None
            if autoscale or swap_at is not None:
                from progen_tpu.serve import BurnRatePolicy, ControlPlane

                control = ControlPlane(cluster, BurnRatePolicy(
                    min_prefill=min_prefill or prefill_procs,
                    max_prefill=max_prefill or prefill_procs + 2,
                    min_replicas=min_replicas or replicas,
                    max_replicas=max_replicas or replicas + 2))
            if statusz:
                ports = cluster.stats().get("statusz_ports", {})
                for who, p in sorted(ports.items()):
                    print(f"statusz[{who}]: http://127.0.0.1:{p}")
            try:
                with profile_trace(xprof_dir):
                    for r in requests:
                        if embed_mode:
                            cluster.submit_embed(r)
                        else:
                            cluster.submit(r)
                    if control is None:
                        completions = cluster.drain()
                    else:
                        # drive loop with control ticks between polls:
                        # the autoscaler acts on live burn/queue signals
                        # and --swap_at rolls the fleet mid-stream
                        completions = []
                        swapped = False
                        while cluster.pending:
                            completions.extend(cluster.poll(timeout=0.2))
                            if (swap_at is not None and not swapped
                                    and len(completions) >= swap_at):
                                swapped = True
                                gen = control.swap_weights()
                                print(f"swap: rolled fleet to "
                                      f"generation {gen}")
                            if autoscale:
                                control.tick()
            finally:
                if control is not None:
                    for e in control.journal:
                        if e["event"] in ("scale_up", "scale_down"):
                            print(f"autoscale: {e['event']} {e['role']} "
                                  f"(cause={e['cause']}, "
                                  f"observed={e['observed']})")
                cluster.shutdown()
            if trace:
                merged = merge_trace_dir(trace_out)
                if merged:
                    print(f"trace: {merged}")
            for comp in sorted(completions, key=lambda c: c.uid):
                if comp.embedding is not None:
                    print_embedding(comp)
                    continue
                print(f"\n {primes[comp.uid]} \n", "*" * 40,
                      f"[{comp.finish_reason}, {len(comp.tokens)} tokens, "
                      f"{comp.latency:.2f}s]\n", decode_tokens(comp.tokens))
            return

        watchdog = None
        if watchdog_timeout:
            watchdog = Watchdog(watchdog_timeout, out_dir=".",
                                label="serve")
            watchdog.start()

        def engine_factory():
            eng = ServingEngine(
                model_config, {"params": params}, policy=policy,
                num_slots=slots, chunk_size=chunk, max_len=seq_len,
                paged=paged, page_size=page_size, quantize=quantize_mode,
                disagg=disagg,
                mesh=mesh, strategies=strategy_list,
                params_shardings=param_sh, watchdog=watchdog)
            if aot_warmup:
                stats = eng.aot_warmup(embed=embed_mode)
                print(f"aot warmup: {stats['programs']} programs in "
                      f"{stats['seconds']:.1f}s")
            return eng

        try:
            with profile_trace(xprof_dir):
                if embed_mode:
                    eng = engine_factory()
                    for r in requests:
                        eng.submit_embed(r)
                    completions = eng.run_until_idle()
                else:
                    completions = run_with_restarts(
                        engine_factory, requests, attempts=serve_attempts,
                        snapshot_path=snapshot_path)
        finally:
            if watchdog is not None:
                watchdog.stop()
        if trace:
            get_tracer().dump(trace_dump_path(trace_out, "driver"))
            merged = merge_trace_dir(trace_out)
            if merged:
                print(f"trace: {merged}")
        for comp in sorted(completions, key=lambda c: c.uid):
            if comp.embedding is not None:
                print_embedding(comp)
                continue
            print(f"\n {primes[comp.uid]} \n", "*" * 40,
                  f"[{comp.finish_reason}, {len(comp.tokens)} tokens, "
                  f"{comp.latency:.2f}s]\n", decode_tokens(comp.tokens))
        return

    prime_tokens = encode_tokens(prime)
    prime_length = len(prime_tokens) + 1  # + BOS
    batch = jnp.tile(jnp.asarray(prime_tokens, jnp.int32)[None, :]
                     if prime_tokens else jnp.zeros((1, 0), jnp.int32),
                     (num_samples, 1))

    sampler = make_sampler(model_config, policy, mesh=mesh,
                           strategies=strategy_list, params_shardings=param_sh)
    keys = KeySeq(seed)
    # add_bos handles empty primes too (a lone BOS column primes the model)
    with profile_trace(xprof_dir):
        if batch.shape[1] == 0:
            batch = jnp.zeros((num_samples, 1), jnp.int32)
            sampled = sampler({"params": params}, next(keys), batch,
                              length=seq_len, top_k=top_k,
                              temperature=temperature)
            prime_length = 1
        else:
            sampled = sampler({"params": params}, next(keys), batch,
                              length=seq_len, top_k=top_k, add_bos=True,
                              temperature=temperature)

    for row in np.asarray(sampled):
        print("\n", prime, "\n", "*" * 40, "\n",
              decode_tokens(row[prime_length:]))


if __name__ == "__main__":
    main()
