"""North-star scale proof: a REAL-SHAPE sharded train step on an 8-device
mesh, with sharded Adam state, cooperative orbax save, and a
different-topology restore.

Everything above ProGen-small had only ever run at toy shapes on the
virtual mesh (the single real chip OOMs at base/large full-state
training, ``benchmarks/configs.md``); this script executes the exact
configuration BASELINE.md's north star describes — ProGen-base (906M)
with fsdp x tp sharded f32 params+moments — end to end:

1. an 8-process ``jax.distributed`` CPU job (1 device per process, gloo
   collectives — the same multi-controller shape a real 8-host slice
   runs, and the only layout whose memory behaves: a single process
   hosting 8 virtual devices was OOM-killed at 130 GB because XLA:CPU
   schedules with no memory budget and holds every device's f32 weight
   all-gathers at once);
2. mesh ``data=1, fsdp=4, tensor=2``: init the full train state sharded,
   record per-device bytes of params and Adam moments (each device must
   hold ~1/8);
3. run >=1 jitted train step at the real batch/seq shapes to a finite
   loss;
4. orbax-save cooperatively (every process writes its own shards);
5. restore onto a DIFFERENT topology (``data=2, fsdp=2, tensor=2``) and
   take one more step there, proving checkpoints are topology-portable.

Compile staggering: process 0 AOT-compiles each program first into the
shared persistent XLA cache; the other 7 wait on a marker file, then
compile as cache hits — on this 1-core box an 8-way compile race would
multiply the (tens of minutes) compile time by 8.

Writes ``benchmarks/scale_proof_{config}.json`` (committed as the round's
evidence) with shard tables, losses and timings.

Usage: ``python tools/scale_proof.py [--config base] [--batch 8]``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PROC = 8


def _mesh1_seq_size(spec: str, n_devices: int) -> int:
    """Resolved seq-axis size of a ``--mesh1`` spec (data,fsdp,tensor,seq;
    one ``-1`` wildcard) — inline so the coordinator can validate without
    importing jax (MeshConfig lives next to jax imports)."""
    parts = spec.split(",")
    if len(parts) != 4:
        raise ValueError("need 4 comma-separated sizes (data,fsdp,tensor,seq)")
    sizes = [int(p) for p in parts]
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    if sizes[3] != -1:
        return sizes[3]
    fixed = sizes[0] * sizes[1] * sizes[2]
    if fixed <= 0 or n_devices % fixed:
        raise ValueError(
            f"{n_devices} devices not divisible by fixed axes product {fixed}")
    return n_devices // fixed


def _ckpt_identity(ckpt_dir: str) -> float:
    """Content identity of a checkpoint tree: mtime of the NEWEST numeric
    step directory.  The top-level dir's mtime only moves when a step dir
    is created or removed — orbax rewrites a re-run step INSIDE the
    existing tree (tmp dir + rename bumps the step dir, not its parent),
    so stamping the parent let a phase-1 rerun into the same path slip
    past the parity guard with an unchanged "identity"."""
    try:
        steps = [e.path for e in os.scandir(ckpt_dir)
                 if e.is_dir() and e.name.isdigit()]
    except OSError:
        steps = []
    if steps:
        return max(os.path.getmtime(p) for p in steps)
    return os.path.getmtime(ckpt_dir)


# --------------------------------------------------------------------------
# coordinator


def coordinate(args) -> int:
    if args.phase in ("3", "sp") and not args.ckpt:
        print(f"--phase {args.phase} needs --ckpt (the phase-1 run's saved "
              "checkpoint; its workdir is printed at launch)", file=sys.stderr)
        return 2
    if args.ckpt and args.phase not in ("3", "sp"):
        # phase 1 would save INTO --ckpt with keep_last_n=1, pruning a
        # user-supplied directory down to one step — refuse
        print("--ckpt is only valid with --phase 3 or sp", file=sys.stderr)
        return 2
    if args.skip_save and args.phase != "1":
        # phase 3/sp restore the phase-1 save; letting --phase all skip it
        # would burn the hours-long phase 1 and then die at restore
        print("--skip-save is only valid with --phase 1 (later phases "
              "restore that save)", file=sys.stderr)
        return 2
    try:
        mesh1_seq = _mesh1_seq_size(args.mesh1, N_PROC)
    except ValueError as e:
        print(f"--mesh1 {args.mesh1!r}: {e}", file=sys.stderr)
        return 2
    if mesh1_seq > 1:
        # phase 1 builds the model WITHOUT 'sp' in its strategies, so a seq
        # axis >1 never threads the shard_map CP ops — the axis would just
        # silently dilute fsdp/tp while claiming a seq mesh in the evidence
        print(f"--mesh1 {args.mesh1!r} resolves to seq={mesh1_seq}, but "
              "phase 1 never runs with the 'sp' strategy; use --phase sp "
              "for the seq-mesh proof", file=sys.stderr)
        return 2
    workdir = tempfile.mkdtemp(prefix=f"scale_proof_{args.config}_")
    print(f"[scale_proof] workdir {workdir} (phase-1 checkpoint lands in "
          f"{workdir}/ckpt)", flush=True)
    # fresh port per invocation: a lingering worker from a killed previous
    # run on the same port poisons the coordination service ("connected
    # with a different incarnation")
    port = 20000 + os.getpid() % 20000
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TPU_WORKER_HOSTNAMES", None)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append("--xla_force_host_platform_device_count=1")
    env["XLA_FLAGS"] = " ".join(flags)
    # the workers share the one compile cache (core/cache.py): reruns,
    # and the other 7 workers, hit it instead of repeating a ~30-minute
    # base compile

    workers = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--config", args.config, "--batch", str(args.batch),
             "--steps", str(args.steps), "--phase", args.phase,
             "--worker", str(pid), "--workdir", workdir,
             "--port", str(port)]
            + (["--ckpt", args.ckpt] if args.ckpt else [])
            + (["--skip-save"] if args.skip_save else [])
            + ["--mesh1", args.mesh1],
            env=env, cwd=REPO,
        )
        for pid in range(N_PROC)
    ]
    rcs = [w.wait() for w in workers]
    if any(rcs):
        print(f"[scale_proof] worker rcs: {rcs}", file=sys.stderr)
        # fall through: per-phase fragments flushed before a later crash
        # are still worth merging

    merged: dict = {}
    byte_tables: dict[str, dict] = {}
    for pid in range(N_PROC):
        for tag in ("p1init", "p1", "p3", "psp_restore", "psp"):
            frag = os.path.join(workdir, f"fragment_{tag}_{pid}.json")
            if not os.path.exists(frag):
                continue
            f = json.load(open(frag))
            merged.update(f.get("common", {}))
            for key, table in f.get("bytes", {}).items():
                byte_tables.setdefault(key, {}).update(table)
    if not merged:
        return 1
    merged.update(byte_tables)
    out_path = os.path.join(REPO, "benchmarks",
                            f"scale_proof_{args.config}.json")
    existing = json.load(open(out_path)) if os.path.exists(out_path) else {}
    if existing and existing.get("batch") not in (None, args.batch):
        # never silently mix runs at different shapes into one evidence
        # file; keep the old one visible instead
        existing = {"superseded_run": existing}
    existing.update(merged)
    # sp parity verdict: the fsdp-only restored step and the seq-mesh
    # restored step consumed the SAME checkpoint and the SAME batch, so
    # their losses must agree (CP halo exchange + row-sharded SGU vs plain
    # GSPMD).  bf16 matmuls under different reduction orders bound the
    # tolerance.
    # Guard against pairing losses from different runs: both phases must
    # have restored the SAME checkpoint directory with the SAME content
    # (mtime taken at restore — a phase-1 rerun into the same path
    # rewrites the step dir and bumps it), and this invocation must have
    # produced at least one side (merged), so a stale evidence file can
    # never manufacture a parity verdict on its own.  When the guard
    # declines, any previously written verdict is dropped rather than
    # left beside losses it no longer describes.
    # a restore-only fragment (run killed before its step) carries a fresh
    # mtime but no loss; the stale loss it displaces must go with it, or a
    # later run could pair losses from different checkpoint contents
    for mt_key, loss_key in (("restore_ckpt_mtime_sp", "loss_after_restore_sp"),
                             ("restore_ckpt_mtime_phase3", "loss_after_restore")):
        if mt_key in merged and loss_key not in merged:
            existing.pop(loss_key, None)
            existing.pop("sp_vs_fsdp_loss_abs_diff", None)
            existing.pop("sp_loss_parity_ok", None)
    same_ckpt = (
        existing.get("restore_ckpt_phase3")
        == existing.get("restore_ckpt_sp") is not None
        and existing.get("restore_ckpt_mtime_phase3")
        == existing.get("restore_ckpt_mtime_sp") is not None
    )
    if ("loss_after_restore" in existing
            and "loss_after_restore_sp" in existing
            and same_ckpt
            and ("loss_after_restore" in merged
                 or "loss_after_restore_sp" in merged)):
        diff = abs(existing["loss_after_restore"]
                   - existing["loss_after_restore_sp"])
        existing["sp_vs_fsdp_loss_abs_diff"] = diff
        existing["sp_loss_parity_ok"] = bool(diff < 5e-3)
    elif ("loss_after_restore" in merged
          or "loss_after_restore_sp" in merged) and not same_ckpt:
        existing.pop("sp_vs_fsdp_loss_abs_diff", None)
        existing.pop("sp_loss_parity_ok", None)
    with open(out_path, "w") as fh:
        json.dump(existing, fh, indent=1)
    print(f"[scale_proof] wrote {out_path}")
    return 0 if not any(rcs) else 1


# --------------------------------------------------------------------------
# worker


def _local_bytes(tree) -> dict[str, int]:
    out: dict[str, int] = {}
    for leaf in __import__("jax").tree.leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def _barrier(name: str, timeout_ms: int = 7_200_000) -> None:
    """Coordination-service barrier (gRPC, hours-scale timeout) — used
    between phases so every process ENTERS each executed program within
    seconds of the others.  Gloo creates a sub-communicator lazily at
    each collective's first use with a 30s peer timeout; staggered
    compiles would blow that without this."""
    from jax._src import distributed

    distributed.global_state.client.wait_at_barrier(name, timeout_in_ms=timeout_ms)


def _stagger(pid: int, workdir: str, tag: str, compile_fn) -> float:
    """P0 compiles into the shared persistent cache; others wait, then
    compile as cache hits.  Ends with a barrier so execution starts in
    lockstep.  Returns seconds spent."""
    marker = os.path.join(workdir, f"compiled_{tag}")
    t0 = time.time()
    if pid == 0:
        compile_fn()
        open(marker, "w").close()
    else:
        while not os.path.exists(marker):
            time.sleep(2.0)
        compile_fn()
    _barrier(f"compiled_{tag}")
    return time.time() - t0


def _warm_collectives(mesh) -> None:
    """Create every gloo communicator the sharded step will use, NOW,
    while all processes are barrier-synced.

    Gloo builds a context per device clique lazily at the clique's first
    collective, with a 30s peer-arrival window (a hardcoded
    GetKeyValue timeout).  Inside a minutes-long train step the 8
    timesharing processes drift far past 30s, so first-use there dies
    with DEADLINE_EXCEEDED; the client caches communicators per clique,
    so touching each clique with a tiny psum here makes the real step
    pure reuse."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    axis_sets = [
        ("fsdp",), ("tensor",), ("data",), ("seq",),
        ("data", "fsdp"), ("fsdp", "tensor"), tuple(names),
    ]
    for axes in axis_sets:
        f = shard_map(
            lambda x: jax.lax.psum(x, axes),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
        jax.block_until_ready(jax.jit(f)(jnp.ones((8,), jnp.float32)))


def worker(args) -> int:
    pid, workdir = args.worker, args.workdir
    sys.path.insert(0, REPO)

    import jax

    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()
    jax.distributed.initialize(
        coordinator_address=f"localhost:{args.port}",
        num_processes=N_PROC,
        process_id=pid,
    )
    assert jax.device_count() == N_PROC

    import jax.numpy as jnp
    import numpy as np

    from progen_tpu.checkpoint import CheckpointStore, abstract_state_like
    from progen_tpu.core.mesh import MeshConfig, make_mesh
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen
    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.parallel.sharding import batch_sharding
    from progen_tpu.train import make_optimizer, make_train_functions

    cfg = CONFIGS[args.config]
    strategies = ("fsdp", "tp")
    # per-phase keys (mesh_phase*, restore_ckpt_*) are stamped inside the
    # phase that actually executed, so a phase-1-only rerun cannot
    # advertise phases it never ran
    common: dict = {
        "config": args.config,
        "model": cfg.to_dict(),
        "batch": args.batch,
        "platform": "cpu (8-process jax.distributed, 1 device each)",
        "n_devices": N_PROC,
        "strategies": list(strategies),
        "remat": "full",
    }

    def build(mesh_cfg, phase_strategies=strategies):
        mesh = make_mesh(mesh_cfg)
        # a seq axis >1 needs the model built mesh-aware so the forward
        # routes through the shard_map CP ops (halo-exchange attention,
        # row-sharded SGU) — GSPMD alone cannot shard the window structure
        model = ProGen(config=cfg, policy=make_policy(mixed_precision=True),
                       remat=True, remat_policy="full",
                       mesh=mesh if "sp" in phase_strategies else None)
        sample = jnp.zeros((args.batch, cfg.seq_len), jnp.int32)
        fns = make_train_functions(
            model, make_optimizer(2e-4), sample, mesh=mesh,
            strategies=phase_strategies,
        )
        return mesh, fns

    def global_batch(mesh):
        rng = np.random.default_rng(0)
        host = np.concatenate(
            [np.zeros((args.batch, 1), np.int32),
             rng.integers(1, cfg.num_tokens, (args.batch, cfg.seq_len),
                          dtype=np.int32)], axis=1)
        sharding = batch_sharding(mesh)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    def log(msg):
        if pid == 0:
            print(f"[scale_proof] {msg}", flush=True)

    def flush_fragment(tag: str, bytes_tables: dict) -> None:
        # flushed per phase: a later OOM/crash cannot lose earlier evidence
        path = os.path.join(workdir, f"fragment_{tag}_{pid}.json")
        with open(path, "w") as fh:
            json.dump({"common": common if pid == 0 else {},
                       "bytes": bytes_tables}, fh)

    # strict tolerance at the real scales; toy smoke configs are dominated
    # by the SGU spatial weights (fsdp-sharded only, i.e. 4-way not 8) —
    # at base scale those are <1% of params
    tol = 1.06 if args.config in ("base", "large", "xl") else 3.0
    total_param_bytes = None
    batch_shape = jax.ShapeDtypeStruct(
        (args.batch, cfg.seq_len + 1), jnp.int32)
    ckpt_dir = args.ckpt or os.path.join(workdir, "ckpt")
    store = CheckpointStore(ckpt_dir, keep_last_n=1)

    # -- phase 1: fsdp=4 x tp=2 (or --mesh1; XL at batch 1 needs a layout
    # whose batch divisor data*fsdp is 1, i.e. pure tensor parallelism) ----
    if args.phase in ("all", "1"):
        mesh1_cfg = MeshConfig.parse(args.mesh1)
        sizes = mesh1_cfg.resolve(N_PROC)
        # labeled form matching the other mesh_* keys (seq omitted at 1,
        # as in the committed evidence files)
        names = ("data", "fsdp", "tensor", "seq")
        upto = 4 if sizes[3] > 1 else 3
        common["mesh_phase1"] = ",".join(
            f"{n}={s}" for n, s in zip(names[:upto], sizes[:upto]))
        mesh, fns = build(mesh1_cfg)
        key = jax.random.key(0)
        abstract = jax.eval_shape(fns.init_state, key)
        common["compile_init_seconds"] = round(_stagger(
            pid, workdir, "init1",
            lambda: fns.init_state.lower(key).compile()), 1)
        common["compile_step_seconds"] = round(_stagger(
            pid, workdir, "step1",
            lambda: fns.train_step.lower(abstract, batch_shape).compile()), 1)
        log(f"compiles done (init {common['compile_init_seconds']}s, "
            f"step {common['compile_step_seconds']}s)")
        _warm_collectives(mesh)
        log("collective cliques warmed")

        t0 = time.time()
        state = fns.init_state(key)
        jax.block_until_ready(state.params)
        common["init_seconds"] = round(time.time() - t0, 1)

        num_params = int(sum(x.size for x in jax.tree.leaves(state.params)))
        common["num_params"] = num_params
        param_bytes = _local_bytes(state.params)
        opt_bytes = _local_bytes(state.opt_state)
        # every device holds ~1/8 of the f32 params (4 bytes each).  Strict
        # tolerance at the real scales; toy smoke configs are dominated by
        # the SGU spatial weights (fsdp-sharded only, i.e. 4-way not 8) and
        # get a loose bound — at base scale those are <1% of params.
        total_param_bytes = 4 * num_params
        # evidence checkpoint BEFORE the audit assert and the (possibly
        # hours-long) step: the byte table is proof — or diagnosis —
        # even if the audit trips or a deadline cuts the step off
        flush_fragment("p1init", {
            "per_device_param_bytes": param_bytes,
            "per_device_opt_state_bytes": opt_bytes,
        })
        assert max(param_bytes.values()) < total_param_bytes / N_PROC * tol, (
            f"param sharding uneven on {pid}: {param_bytes} vs "
            f"{total_param_bytes}/{N_PROC}"
        )

        if pid == 0:
            leaves = [
                ("/".join(str(k.key) for k in path), leaf)
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(state.params)[0]
            ]
            leaves.sort(key=lambda kv: -kv[1].size)
            common["largest_param_shards"] = [
                {
                    "name": name,
                    "global_shape": list(leaf.shape),
                    "shard_shape": list(leaf.addressable_shards[0].data.shape),
                }
                for name, leaf in leaves[:5]
            ]

        batch = global_batch(mesh)
        t0 = time.time()
        for _ in range(args.steps):
            state, metrics = fns.train_step(state, batch)
        loss1 = float(metrics["loss"])
        common["step_seconds_fsdp4_tp2"] = round((time.time() - t0) / args.steps, 1)
        common["loss_fsdp4_tp2"] = loss1
        assert np.isfinite(loss1), f"non-finite loss {loss1}"
        log(f"fsdp=4,tp=2 step ok: loss={loss1:.4f} "
            f"({common['step_seconds_fsdp4_tp2']}s/step)")

        # -- phase 2: cooperative sharded save ----------------------------------
        if args.skip_save:
            # XL's f32 state is ~77 GB; this box has 43 GB of disk — the
            # executed-step evidence stands on its own, the save is
            # physically impossible here, and saying so beats crashing
            common["save_skipped"] = (
                "--skip-save: sharded f32 state exceeds available disk on "
                "this box; step evidence only")
            log("save skipped (--skip-save)")
        else:
            _barrier("pre_save")
            t0 = time.time()
            store.save(args.steps, state,
                       next_seq_index=args.batch * args.steps,
                       model_config=cfg.to_dict())
            store.wait_until_finished()
            common["save_seconds"] = round(time.time() - t0, 1)
            log(f"cooperative save done ({common['save_seconds']}s)")

        flush_fragment("p1", {
            "per_device_param_bytes": param_bytes,
            "per_device_opt_state_bytes": opt_bytes,
        })
        del state, metrics, batch

    # -- phase 3: restore onto a DIFFERENT topology, step again -------------
    if args.phase in ("all", "3"):
        common["mesh_phase3"] = "data=2,fsdp=2,tensor=2"
        common["restore_ckpt_phase3"] = os.path.abspath(ckpt_dir)
        common["restore_ckpt_mtime_phase3"] = _ckpt_identity(ckpt_dir)
        mesh2, fns2 = build(MeshConfig(data=2, fsdp=2, tensor=2))
        abstract2 = abstract_state_like(fns2)
        if total_param_bytes is None:
            total_param_bytes = 4 * int(sum(
                x.size for x in jax.tree.leaves(abstract2.params)))
        common["compile_step2_seconds"] = round(_stagger(
            pid, workdir, "step2",
            lambda: fns2.train_step.lower(abstract2, batch_shape).compile()),
            1)

        _barrier("pre_restore")
        _warm_collectives(mesh2)
        t0 = time.time()
        restored = store.restore_state(abstract2)
        assert restored is not None, f"no checkpoint found in {ckpt_dir}"
        jax.block_until_ready(restored.params)
        common["restore_seconds_data2_fsdp2_tp2"] = round(time.time() - t0, 1)
        # an external --ckpt may hold any step; the invariant is that the
        # restore landed on the step the STORE says is newest
        assert int(restored.step) == store.latest_step()

        param_bytes_resharded = _local_bytes(restored.params)
        # fsdp=2 x tp=2 -> each device holds ~1/4
        assert max(param_bytes_resharded.values()) < (
            total_param_bytes / 4 * tol)

        batch2 = global_batch(mesh2)
        t0 = time.time()
        restored, metrics2 = fns2.train_step(restored, batch2)
        loss2 = float(metrics2["loss"])
        common["step_seconds_data2_fsdp2_tp2"] = round(time.time() - t0, 1)
        common["loss_after_restore"] = loss2
        assert np.isfinite(loss2)
        log(f"data=2,fsdp=2,tp=2 restored step ok: loss={loss2:.4f}")

        flush_fragment("p3", {
            "per_device_param_bytes_after_reshard": param_bytes_resharded,
        })

    # -- phase sp: restore onto a SEQ mesh, step, record loss for parity ----
    # The CP halo exchange and row-sharded SGU (parallel/context.py) had
    # never run above seq 64; this executes them at the config's real
    # seq_len.  Loss parity with phase 3 (same checkpoint, same batch) is
    # asserted by the coordinator after the merge.
    if args.phase == "sp":
        common["mesh_phase_sp"] = "data=1,fsdp=4,tensor=1,seq=2"
        common["restore_ckpt_sp"] = os.path.abspath(ckpt_dir)
        common["restore_ckpt_mtime_sp"] = _ckpt_identity(ckpt_dir)
        mesh_sp, fns_sp = build(MeshConfig(data=1, fsdp=4, tensor=1, seq=2),
                                phase_strategies=("sp", "fsdp"))
        abstract_sp = abstract_state_like(fns_sp)
        if total_param_bytes is None:
            total_param_bytes = 4 * int(sum(
                x.size for x in jax.tree.leaves(abstract_sp.params)))
        common["compile_step_sp_seconds"] = round(_stagger(
            pid, workdir, "stepsp",
            lambda: fns_sp.train_step.lower(abstract_sp, batch_shape)
            .compile()), 1)

        _barrier("pre_restore_sp")
        _warm_collectives(mesh_sp)
        t0 = time.time()
        restored = store.restore_state(abstract_sp)
        assert restored is not None, f"no checkpoint found in {ckpt_dir}"
        jax.block_until_ready(restored.params)
        common["restore_seconds_sp"] = round(time.time() - t0, 1)
        assert int(restored.step) == store.latest_step()

        param_bytes_sp = _local_bytes(restored.params)
        # evidence checkpoint: the seq-mesh restore + byte audit are proof
        # on their own if a deadline cuts the (85-90 min on this box) step
        # off; on success the psp fragment adds the loss/timing keys
        log(f"seq-mesh restore done ({common['restore_seconds_sp']}s); "
            "stepping")
        flush_fragment("psp_restore", {
            "per_device_param_bytes_sp_mesh": param_bytes_sp,
        })
        # params shard over fsdp=4 only (replicated across seq) -> ~1/4 each
        assert max(param_bytes_sp.values()) < total_param_bytes / 4 * tol, (
            f"param sharding uneven on {pid} (sp mesh): {param_bytes_sp}"
        )

        batch_sp = global_batch(mesh_sp)
        t0 = time.time()
        restored, metrics_sp = fns_sp.train_step(restored, batch_sp)
        loss_sp = float(metrics_sp["loss"])
        common["step_seconds_sp"] = round(time.time() - t0, 1)
        common["loss_after_restore_sp"] = loss_sp
        assert np.isfinite(loss_sp)
        log(f"seq-mesh (fsdp=4,seq=2) restored step ok: loss={loss_sp:.4f}")

        flush_fragment("psp", {})  # byte table already in psp_restore

    store.close()
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="base",
                        help="any progen_tpu.models.configs name "
                             "(base = the north-star proof; default/tiny "
                             "are cheap plumbing smokes)")
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--steps", type=int, default=1,
                        help="train steps before the save")
    parser.add_argument("--phase", default="all",
                        choices=["all", "1", "3", "sp"],
                        help="run only the init+step+save phase (1), only "
                             "the restore+step phase (3, with --ckpt), or "
                             "the seq-mesh restore+step phase (sp, with "
                             "--ckpt; coordinator asserts loss parity with "
                             "phase 3); fragments flush per phase so a "
                             "crash in one never loses the other's evidence")
    parser.add_argument("--ckpt", default=None,
                        help="existing sharded checkpoint dir for "
                             "--phase 3/sp")
    parser.add_argument("--skip-save", action="store_true",
                        help="phase 1 without the cooperative save (XL's "
                             "state exceeds this box's disk)")
    parser.add_argument("--mesh1", default="1,4,2,1",
                        help="phase-1 mesh spec data,fsdp,tensor,seq; "
                             "batch must divide data*fsdp (XL at batch 1 "
                             "-> 1,1,8,1, pure tensor parallelism)")
    parser.add_argument("--worker", type=int, default=None)
    parser.add_argument("--workdir", default=None)
    parser.add_argument("--port", type=int, default=12123)
    args = parser.parse_args()
    if args.worker is None:
        return coordinate(args)
    return worker(args)


if __name__ == "__main__":
    sys.exit(main())
