"""Multi-host smoke tests: two real ``jax.distributed`` CPU processes run
the actual Trainer and must agree with a single-process run.

Verifies, end to end:

* ``jax.distributed.initialize`` + a mesh spanning both processes;
* per-host data sharding (round-robin record split) feeds each host
  disjoint rows whose union is the single-process global batch;
* the jitted SPMD train step over process-spanning sharded arrays
  (``make_array_from_process_local_data``) — with params replicated
  (``dp``) and params/opt-state sharded ACROSS the processes (``fsdp``);
* in-training sampling as an SPMD program (broadcast prime, replicated
  key, globally-sharded params);
* single-writer tracker logs + a valid orbax checkpoint written
  cooperatively by both processes — and restorable on a DIFFERENT
  topology (single process);
* the loss trajectory matches a single-process run of the same global
  batch (the union is row-permuted, and batch_loss is a row mean, so the
  numbers agree to f32 tolerance);
* the fused superstep loop (cfg.superstep > 1) across two processes:
  each process stages only its own shard of the (K, accum, batch, seq)
  superbatch, spans land on the same hook boundaries as the per-step
  loop, and the resulting checkpoint params are BIT-identical to a
  single-process run fed the identical global row order.
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from progen_tpu.data.tfrecord import shard_filename, write_tfrecord
from progen_tpu.models import ProGenConfig

REPO = Path(__file__).resolve().parent.parent

MODEL_CONFIG = ProGenConfig(
    num_tokens=256, dim=64, seq_len=64, depth=2, window_size=32,
    global_mlp_depth=1, heads=2, dim_head=32, ff_mult=2,
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _mh_payloads():
    rng = np.random.default_rng(0)
    return {
        split: [
            b"# " + bytes(rng.integers(65, 91, size=40).tolist())
            for _ in range(n)
        ]
        for split, n in (("train", 48), ("valid", 8))
    }


@pytest.fixture(scope="module")
def mh_data(tmp_path_factory):
    data_dir = tmp_path_factory.mktemp("mh_data")
    for split, payloads in _mh_payloads().items():
        write_tfrecord(
            data_dir / shard_filename(0, len(payloads), split), payloads)
    return data_dir


@pytest.fixture(scope="module")
def mh_data_interleaved(tmp_path_factory):
    """``mh_data``'s train records reordered into the exact sequence the
    2-process round-robin split assembles global batches from: with a
    per-host batch of 2, global batch k is [4k, 4k+2] (host 0's rows)
    followed by [4k+1, 4k+3] (host 1's) — so ONE process reading this
    file in natural order sees row-IDENTICAL global batches, not merely
    row-permuted ones, and bit-exact comparison becomes meaningful."""
    data_dir = tmp_path_factory.mktemp("mh_data_ilv")
    payloads = _mh_payloads()
    train = payloads["train"]
    order = [i for k in range(len(train) // 4)
             for i in (4 * k, 4 * k + 2, 4 * k + 1, 4 * k + 3)]
    write_tfrecord(data_dir / shard_filename(0, len(train), "train"),
                   [train[i] for i in order])
    write_tfrecord(data_dir / shard_filename(0, 8, "valid"),
                   payloads["valid"])
    return data_dir


@pytest.fixture(scope="module")
def single_proc_losses(mh_data, tmp_path_factory):
    """Reference trajectory: one process, the same GLOBAL batch of 4."""
    from progen_tpu.observe import Tracker
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    out = tmp_path_factory.mktemp("sp")
    cfg = TrainerConfig(
        seed=7, batch_size=4, grad_accum_every=1, epochs=1,
        mixed_precision=False, log_every=1, validate_every=2,
        sample_every=10_000, checkpoint_every=3, max_steps=3,
    )
    tracker = Tracker(out_dir=str(out / "runs"), run_id="single",
                      use_wandb=False)
    trainer = Trainer(
        model_config=MODEL_CONFIG, cfg=cfg, data_path=str(mh_data),
        checkpoint_path=str(out / "ckpt"), tracker=tracker, use_mesh=False,
    )
    try:
        trainer.run()
    finally:
        tracker.finish()
        trainer.store.close()
    metrics = [json.loads(l) for l in
               (out / "runs" / "single" / "metrics.jsonl")
               .read_text().splitlines()]
    return {m["step"]: m["loss"] for m in metrics if "loss" in m}


def _run_workers(tmp_path, data_dir, strategy, *, num_processes=2,
                 superstep=1, batch_size=2, tag="mh", total_devices=2,
                 mesh=None, timeout=420):
    port = _free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # total_devices devices total either way: the mesh spans the
        # PROCESSES (total/num each) or one process exposing them all
        "XLA_FLAGS": "--xla_force_host_platform_device_count="
                     f"{total_devices // num_processes}",
        "PYTHONPATH": str(REPO),
    }
    argv_tail = [strategy, str(superstep), str(batch_size)]
    if mesh is not None:
        argv_tail.append(mesh)
    workers = [
        subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "_multihost_worker.py"),
             str(i), str(num_processes), str(port), str(data_dir),
             str(tmp_path / f"ckpt_{tag}"), str(tmp_path / f"runs_{tag}"),
             *argv_tail],
            env=env, cwd=str(REPO),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(num_processes)
    ]
    outs = [w.communicate(timeout=timeout)[0] for w in workers]
    for i, (w, out) in enumerate(zip(workers, outs)):
        assert w.returncode == 0, f"worker {i} failed:\n{out}"
    results = {}
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        r = json.loads(line)
        results[r["process_id"]] = r
    return results


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["dp", "fsdp"])
def test_two_process_trainer_matches_single(tmp_path, mh_data,
                                            single_proc_losses, strategy):
    results = _run_workers(tmp_path, mh_data, strategy)
    assert results[0]["step"] == results[1]["step"] == 3
    # the loss is computed on replicated outputs: both controllers agree
    assert results[0]["final_loss"] == pytest.approx(
        results[1]["final_loss"], rel=1e-6)

    # single-writer: exactly process 0's tracker wrote, and one run dir
    run_dirs = list((tmp_path / "runs_mh").iterdir())
    assert [d.name for d in run_dirs] == ["multihost"]
    metrics = [json.loads(l) for l in
               (run_dirs[0] / "metrics.jsonl").read_text().splitlines()]
    mh_losses = {m["step"]: m["loss"] for m in metrics if "loss" in m}
    assert set(mh_losses) == {1, 2, 3}
    # the in-training sample at step 3 ran SPMD and process 0 logged it
    assert (run_dirs[0] / "samples.html").exists()

    # per-host round-robin rows union to a row-permutation of the
    # single-process batch; the row-mean loss must agree step by step —
    # under fsdp this additionally proves the cross-process ZeRO-3
    # sharding computes the same math as one device
    for step in (1, 2, 3):
        assert mh_losses[step] == pytest.approx(
            single_proc_losses[step], rel=2e-4), (
            step, mh_losses, single_proc_losses)

    # the cooperatively-written checkpoint restores on a DIFFERENT
    # topology: this single pytest process (8 virtual devices, no mesh)
    from progen_tpu.checkpoint import CheckpointStore
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    store = CheckpointStore(str(tmp_path / "ckpt_mh"))
    meta = store.restore_meta()
    store.close()
    assert meta is not None and meta["train_step"] == 3
    assert meta["next_seq_index"] == 12  # global batch 4 x 3 steps

    cfg = TrainerConfig(seed=7, batch_size=4, grad_accum_every=1,
                        mixed_precision=False, max_steps=4,
                        validate_every=100, sample_every=100,
                        checkpoint_every=100, log_every=1)
    t = Trainer(model_config=MODEL_CONFIG, cfg=cfg, data_path=str(mh_data),
                checkpoint_path=str(tmp_path / "ckpt_mh"), use_mesh=False)
    state, start_seq, _ = t.restore_or_init()
    assert int(state.step) == 3 and start_seq == 12
    out = t.run()  # one more step from the restored state
    assert out["step"] == 4 and np.isfinite(out["loss"])
    t.store.close()


@pytest.mark.slow
def test_two_process_superstep_staging_bit_identical(
        tmp_path, mh_data, mh_data_interleaved):
    """ROADMAP 2(a): the fused K-step superstep loop across two processes.

    Each worker runs with cfg.superstep=2, so the SuperbatchStager stages
    a (K, 1, 2, 65) process-LOCAL block per span and ``_super_to_device``
    assembles the global superbatch via
    ``make_array_from_process_local_data`` — a host staging anything but
    exactly its own shard cannot produce the global shape.  max_steps=3
    exercises both program shapes: one fused K=2 dispatch (steps 1-2,
    landing exactly on the validate_every=2 boundary) and the K=1
    residual walk to the checkpoint/sample boundary at step 3.

    The reference leg is ONE process with two virtual devices, the same
    (data=2) mesh and superstep, fed ``mh_data_interleaved`` — the same
    records pre-arranged into the two-process round-robin union order.
    Global batches are then row-identical, every device holds the same
    rows, and both 2-term cross-device reductions add the same partials,
    so the checkpoints must agree BIT-exactly, not just to tolerance.
    """
    mh = _run_workers(tmp_path, mh_data, "dp", superstep=2)
    assert mh[0]["step"] == mh[1]["step"] == 3
    assert mh[0]["final_loss"] == pytest.approx(
        mh[1]["final_loss"], rel=1e-6)

    run_dirs = list((tmp_path / "runs_mh").iterdir())
    assert [d.name for d in run_dirs] == ["multihost"]
    metrics = [json.loads(l) for l in
               (run_dirs[0] / "metrics.jsonl").read_text().splitlines()]
    mh_losses = {m["step"]: m["loss"] for m in metrics if "loss" in m}
    # log_every == superstep: the fused span logs once at its boundary
    # (step 2); the residual step 3 is a hook boundary, not a log one —
    # identical span placement in both legs is what {2} asserts
    assert set(mh_losses) == {2}
    # the sample hook at step 3 fired as an SPMD program, on the boundary
    assert (run_dirs[0] / "samples.html").exists()

    sp = _run_workers(tmp_path, mh_data_interleaved, "dp", superstep=2,
                      num_processes=1, batch_size=4, tag="sp")
    assert sp[0]["step"] == 3
    sp_metrics = [json.loads(l) for l in
                  (tmp_path / "runs_sp" / "multihost" / "metrics.jsonl")
                  .read_text().splitlines()]
    sp_losses = {m["step"]: m["loss"] for m in sp_metrics
                 if "loss" in m}
    # identical step boundaries AND bit-identical logged loss values
    assert sp_losses == mh_losses

    # bit-identical params: restore both cooperative checkpoints in this
    # process (different topology again) and compare leaf by leaf
    import jax

    from progen_tpu.train.trainer import Trainer, TrainerConfig

    cfg = TrainerConfig(seed=7, batch_size=4, grad_accum_every=1,
                        mixed_precision=False, max_steps=3,
                        validate_every=100, sample_every=100,
                        checkpoint_every=100, log_every=1)
    params = {}
    for tag, data in (("mh", mh_data), ("sp", mh_data_interleaved)):
        t = Trainer(model_config=MODEL_CONFIG, cfg=cfg, data_path=str(data),
                    checkpoint_path=str(tmp_path / f"ckpt_{tag}"),
                    use_mesh=False)
        state, start_seq, _ = t.restore_or_init()
        assert int(state.step) == 3 and start_seq == 12
        params[tag] = jax.device_get(state.params)
        t.store.close()
    mh_leaves = jax.tree.leaves(params["mh"])
    sp_leaves = jax.tree.leaves(params["sp"])
    assert len(mh_leaves) == len(sp_leaves) > 0
    for x, y in zip(mh_leaves, sp_leaves):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.slow
def test_four_process_tensor_spanning_mesh_bit_identical(
        tmp_path, mh_data, mh_data_interleaved):
    """ROADMAP 1: a (data=2, tensor=2) mesh whose TENSOR axis spans
    processes — 4 single-device workers, processes (0,1) at data shard 0
    and (2,3) at shard 1, each tensor pair computing megatron-sharded
    matmuls across an OS process boundary, through the unmodified fused
    superstep loop.

    Data contract under test: ``process_batch_shards`` groups the 4
    processes into 2 batch shards, so processes 0 and 1 load IDENTICAL
    rows (round-robin shard 0) while 2 and 3 load shard 1 — the global
    batch assembled per step is [4k, 4k+2, 4k+1, 4k+3], exactly the
    2-process dp union order, so the ``mh_data_interleaved`` fixture is
    reusable as-is for the reference leg.

    The reference leg is ONE process exposing 4 virtual devices with the
    SAME (2,1,2,1) mesh and dp+tp strategies: the SPMD partitioning is
    identical, every cross-device reduction (tp psum over 2 shards, dp
    grad mean over 2 shards) adds the same 2 partials in the same order,
    so the cooperative checkpoints must agree BIT-exactly — the proof
    that spanning an inner mesh axis across processes changes nothing
    about the math."""
    mh = _run_workers(tmp_path, mh_data, "dp+tp", num_processes=4,
                      total_devices=4, superstep=2, batch_size=2,
                      mesh="2,1,2,1", timeout=600)
    assert all(mh[i]["step"] == 3 for i in range(4))
    # the batch-shard grouping the Trainer derived from the mesh
    assert [mh[i]["data_shard"] for i in range(4)] == [
        [2, 0], [2, 0], [2, 1], [2, 1]]
    assert mh[0]["final_loss"] == pytest.approx(mh[3]["final_loss"],
                                                rel=1e-6)

    run_dirs = list((tmp_path / "runs_mh").iterdir())
    assert [d.name for d in run_dirs] == ["multihost"]
    metrics = [json.loads(l) for l in
               (run_dirs[0] / "metrics.jsonl").read_text().splitlines()]
    mh_losses = {m["step"]: m["loss"] for m in metrics if "loss" in m}
    assert set(mh_losses) == {2}
    assert (run_dirs[0] / "samples.html").exists()

    sp = _run_workers(tmp_path, mh_data_interleaved, "dp+tp",
                      num_processes=1, total_devices=4, superstep=2,
                      batch_size=4, mesh="2,1,2,1", tag="sp", timeout=600)
    assert sp[0]["step"] == 3
    assert sp[0]["data_shard"] == [1, 0]
    sp_metrics = [json.loads(l) for l in
                  (tmp_path / "runs_sp" / "multihost" / "metrics.jsonl")
                  .read_text().splitlines()]
    sp_losses = {m["step"]: m["loss"] for m in sp_metrics if "loss" in m}
    # identical step boundaries AND bit-identical logged loss values
    assert sp_losses == mh_losses

    # bit-identical params: restore both cooperative checkpoints in this
    # process (different topology: no mesh at all) and compare leaf by
    # leaf — the 4-process tensor-spanning run and the 1-process run
    # wrote the same bits
    import jax

    from progen_tpu.train.trainer import Trainer, TrainerConfig

    cfg = TrainerConfig(seed=7, batch_size=4, grad_accum_every=1,
                        mixed_precision=False, max_steps=3,
                        validate_every=100, sample_every=100,
                        checkpoint_every=100, log_every=1)
    params = {}
    for tag, data in (("mh", mh_data), ("sp", mh_data_interleaved)):
        t = Trainer(model_config=MODEL_CONFIG, cfg=cfg, data_path=str(data),
                    checkpoint_path=str(tmp_path / f"ckpt_{tag}"),
                    use_mesh=False)
        state, start_seq, _ = t.restore_or_init()
        assert int(state.step) == 3 and start_seq == 12
        params[tag] = jax.device_get(state.params)
        t.store.close()
    mh_leaves = jax.tree.leaves(params["mh"])
    sp_leaves = jax.tree.leaves(params["sp"])
    assert len(mh_leaves) == len(sp_leaves) > 0
    for x, y in zip(mh_leaves, sp_leaves):
        assert np.array_equal(np.asarray(x), np.asarray(y))
