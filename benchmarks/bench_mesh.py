#!/usr/bin/env python
"""Process-spanning mesh bench: multi-process training meshes and the
multi-process tensor-parallel decode group -> ``benchmarks/mesh.jsonl``.

Training leg (``training_mesh`` record): each mesh in the sweep —
``1x1x1`` (single process), ``2x1x2`` (data x tensor over 4 processes),
``1x2x2`` (fsdp x tensor over 4 processes) — runs the REAL Trainer as N
single-device ``jax.distributed`` CPU processes through
``tests/_multihost_worker.py``, then restores the cooperative checkpoint
next to a single-process reference run of the SAME mesh over N virtual
devices and compares params BIT-exactly.  ``mesh_ckpt_parity`` (1.0 =
every sweep entry bit-identical) is the benchdiff gate: process-spanning
an inner mesh axis must be invisible in the math, so the band is zero —
any break is a real partitioning regression, not noise.

Serving leg (``serving_tpgroup`` record): one decode replica as a
``--tp-group`` lockstep process group behind the real ServeCluster,
driven with the same request schedule as a single-process engine.  With
``--verify`` every completion must be token-identical to the in-process
engine's.  ``tp_group_decode_tok_s`` is the watched throughput.

``--smoke`` shrinks the sweep to the 2-process tensor-spanning mesh
(``1x1x2``) plus the tp-group serving leg — the tools/check.sh gate.

CPU-proof by design (tiny fixture configs); numbers are for trend-gating
via tools/benchdiff.py, not headlines.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402

from progen_tpu.observe.platform import stamp_record  # noqa: E402

# must match tests/_multihost_worker.py's fixed model config — the
# parity compare restores its checkpoints in this process
from progen_tpu.models import ProGenConfig  # noqa: E402

WORKER_MODEL = ProGenConfig(
    num_tokens=256, dim=64, seq_len=64, depth=2, window_size=32,
    global_mlp_depth=1, heads=2, dim_head=32, ff_mult=2,
)

# mesh name -> (processes, mesh_spec, per-shard batch, interleave ref data)
# Two batch shards (data*fsdp = 2) pair with per-shard batch 2 and the
# round-robin union order [4k, 4k+2, 4k+1, 4k+3] for the reference leg;
# one batch shard means both legs read the file in natural order.
SWEEP = {
    "1x1x1": (1, "1,1,1,1", 4, False),
    "1x1x2": (2, "1,1,2,1", 4, False),
    "2x1x2": (4, "2,1,2,1", 2, True),
    "1x2x2": (4, "1,2,2,1", 2, True),
}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _payloads():
    rng = np.random.default_rng(0)
    return {
        split: [
            b"# " + bytes(rng.integers(65, 91, size=40).tolist())
            for _ in range(n)
        ]
        for split, n in (("train", 48), ("valid", 8))
    }


def _write_data(root: str) -> tuple[str, str]:
    """Natural-order and round-robin-interleaved tfrecord dirs."""
    from progen_tpu.data.tfrecord import shard_filename, write_tfrecord

    payloads = _payloads()
    nat = os.path.join(root, "nat")
    ilv = os.path.join(root, "ilv")
    os.makedirs(nat, exist_ok=True)
    os.makedirs(ilv, exist_ok=True)
    for split, recs in payloads.items():
        write_tfrecord(
            os.path.join(nat, shard_filename(0, len(recs), split)), recs)
    train = payloads["train"]
    order = [i for k in range(len(train) // 4)
             for i in (4 * k, 4 * k + 2, 4 * k + 1, 4 * k + 3)]
    write_tfrecord(os.path.join(ilv, shard_filename(0, len(train), "train")),
                   [train[i] for i in order])
    write_tfrecord(os.path.join(ilv, shard_filename(0, 8, "valid")),
                   payloads["valid"])
    return nat, ilv


def _strategies_for(mesh_spec: str) -> str:
    _, fsdp, tensor, _ = (int(p) for p in mesh_spec.split(","))
    s = "dp"
    if fsdp > 1:
        s += "+fsdp"
    if tensor > 1:
        s += "+tp"
    return s


def _run_workers(data_dir, ckpt_dir, runs_dir, mesh_spec, *, num_processes,
                 total_devices, batch_size, timeout):
    port = _free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count="
                     f"{total_devices // num_processes}",
        "PYTHONPATH": _REPO,
    }
    workers = [
        subprocess.Popen(
            [sys.executable,
             os.path.join(_REPO, "tests", "_multihost_worker.py"),
             str(i), str(num_processes), str(port), str(data_dir),
             str(ckpt_dir), str(runs_dir),
             _strategies_for(mesh_spec), "1", str(batch_size), mesh_spec],
            env=env, cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(num_processes)
    ]
    outs = [w.communicate(timeout=timeout)[0] for w in workers]
    for i, (w, out) in enumerate(zip(workers, outs)):
        if w.returncode != 0:
            raise RuntimeError(
                f"mesh worker {i}/{num_processes} ({mesh_spec}) failed:\n"
                f"{out}")
    results = {}
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process_id"]] = r
    return results


def _restore_params(ckpt_dir: str, data_dir: str):
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    cfg = TrainerConfig(seed=7, batch_size=4, grad_accum_every=1,
                        mixed_precision=False, max_steps=3,
                        validate_every=100, sample_every=100,
                        checkpoint_every=100, log_every=1)
    t = Trainer(model_config=WORKER_MODEL, cfg=cfg, data_path=str(data_dir),
                checkpoint_path=str(ckpt_dir), use_mesh=False)
    try:
        state, _, _ = t.restore_or_init()
        import jax

        return jax.device_get(state.params)
    finally:
        t.store.close()


def run_training_sweep(meshes, workdir, *, timeout):
    import jax

    nat, ilv = _write_data(os.path.join(workdir, "data"))
    sweep = {}
    for name in meshes:
        procs, spec, shard_batch, interleave = SWEEP[name]
        base = os.path.join(workdir, name.replace("x", "_"))
        t0 = time.perf_counter()
        mh = _run_workers(
            nat, os.path.join(base, "ckpt_mh"), os.path.join(base, "runs_mh"),
            spec, num_processes=procs, total_devices=procs,
            batch_size=shard_batch, timeout=timeout)
        wall = time.perf_counter() - t0
        entry = {
            "processes": procs,
            "mesh_spec": spec,
            "wall_s": round(wall, 3),
            "final_loss": mh[0]["final_loss"],
            "data_shards": mh[0]["data_shard"][0],
        }
        if procs == 1:
            # this IS the single-process reference topology
            entry["ckpt_parity"] = 1.0
        else:
            ref_data = ilv if interleave else nat
            _run_workers(
                ref_data, os.path.join(base, "ckpt_sp"),
                os.path.join(base, "runs_sp"), spec,
                num_processes=1, total_devices=procs,
                batch_size=shard_batch * (2 if interleave else 1),
                timeout=timeout)
            mh_params = _restore_params(os.path.join(base, "ckpt_mh"), nat)
            sp_params = _restore_params(os.path.join(base, "ckpt_sp"), nat)
            a, b = jax.tree.leaves(mh_params), jax.tree.leaves(sp_params)
            identical = (len(a) == len(b) > 0 and all(
                np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(a, b)))
            entry["ckpt_parity"] = 1.0 if identical else 0.0
        sweep[name] = entry
        print(f"training_mesh {name}: procs={procs} wall={wall:.1f}s "
              f"parity={entry['ckpt_parity']}", file=sys.stderr)
    return sweep


def run_serving_tpgroup(args, workdir):
    from progen_tpu.decode.engine import Request
    from progen_tpu.serve.cluster import ServeCluster
    from progen_tpu.serve.worker import build_engine_from_spec, make_spec

    cfg = ProGenConfig(
        num_tokens=32, dim=16, seq_len=24, depth=2, window_size=4,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
    )
    spec = make_spec(cfg, mixed_precision=False, init_seed=7,
                     engine=dict(num_slots=4, chunk_size=4, max_len=24,
                                 prefill_batch=2, handoff_depth=2))

    def requests():
        return [Request(uid=i, tokens=[1 + i % 20, 2, 3],
                        max_new_tokens=args.max_new,
                        top_k=(None if i % 2 else 8),
                        temperature=(0.0 if i % 2 else 1.0), seed=100 + i)
                for i in range(args.requests)]

    # reference: the same engine in-process, single device
    eng = build_engine_from_spec(spec)
    for r in requests():
        eng.submit(r)
    t0 = time.perf_counter()
    ref_done = [c for c in eng.run_until_idle() if c.ok]
    ref_wall = time.perf_counter() - t0
    reference = {c.uid: [int(t) for t in c.tokens] for c in ref_done}
    ref_tok = int(sum(len(c.tokens) for c in ref_done))

    log_dir = os.path.join(workdir, "tpgroup_logs")
    os.makedirs(log_dir, exist_ok=True)
    cluster = ServeCluster(spec, prefill_procs=1, replicas=1,
                           tp_group=args.tp_group, log_dir=log_dir)
    try:
        t0 = time.perf_counter()
        for r in requests():
            cluster.submit(r)
        done = cluster.drain(timeout=600.0)
        wall = time.perf_counter() - t0
    finally:
        stats = cluster.shutdown()

    ok = [c for c in done if c.ok]
    gen = int(sum(len(c.tokens) for c in ok))
    if args.verify:
        got = {c.uid: [int(t) for t in c.tokens] for c in ok}
        assert len(ok) == args.requests, \
            f"only {len(ok)}/{args.requests} completions ok"
        assert got == reference, "tp-group tokens diverged from engine"
        tx = stats["transport_total"]
        assert tx["crc_failures"] == 0 and tx["desyncs"] == 0, tx
        print("verify: tp-group token identity OK", file=sys.stderr)

    return {
        "metric": "serving_tpgroup",
        "tp_group": args.tp_group,
        "requests": args.requests,
        "max_new_tokens": args.max_new,
        "wall_s": round(wall, 3),
        "generated_tokens": gen,
        "ok_requests": len(ok),
        "tp_group_decode_tok_s": round(gen / wall, 1) if wall else 0.0,
        # context, not gated: the same schedule on the in-process engine
        "single_engine_tok_s": round(ref_tok / ref_wall, 1)
        if ref_wall else 0.0,
        "transport": stats["transport_total"],
        "supervision": stats["supervision"],
        "verified": bool(args.verify),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--meshes", default="1x1x1,2x1x2,1x2x2",
                    help="comma-separated sweep, e.g. 1x1x1,2x1x2,1x2x2")
    ap.add_argument("--tp-group", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--verify", action="store_true",
                    help="assert tp-group token identity vs the engine")
    ap.add_argument("--smoke", action="store_true",
                    help="check.sh gate: 1x1x2 training parity + tp-group")
    ap.add_argument("--skip-training", action="store_true")
    ap.add_argument("--skip-serving", action="store_true")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per training leg (all workers together)")
    ap.add_argument("--out", default=None,
                    help="append records to this JSONL file")
    args = ap.parse_args()
    if args.smoke:
        args.meshes = "1x1x2"
        args.verify = True

    meshes = [m for m in args.meshes.split(",") if m]
    unknown = [m for m in meshes if m not in SWEEP]
    if unknown:
        ap.error(f"unknown meshes {unknown}; known: {sorted(SWEEP)}")

    import tempfile

    import jax

    records = []
    with tempfile.TemporaryDirectory(prefix="bench_mesh_") as workdir:
        if not args.skip_training:
            sweep = run_training_sweep(meshes, workdir,
                                       timeout=args.timeout)
            parities = [e["ckpt_parity"] for e in sweep.values()]
            records.append(stamp_record({
                "metric": "training_mesh",
                "meshes": meshes,
                # benchdiff gate: 1.0 only when EVERY sweep entry's
                # cooperative checkpoint is bit-identical to its
                # single-process same-mesh reference (zero noise band)
                "mesh_ckpt_parity": min(parities),
                "wall_s": round(sum(e["wall_s"] for e in sweep.values()), 3),
                "sweep": sweep,
                "platform": jax.devices()[0].platform,
            }))
        if not args.skip_serving:
            records.append(stamp_record({
                **run_serving_tpgroup(args, workdir),
                "platform": jax.devices()[0].platform,
            }))

    for record in records:
        line = json.dumps(record)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
