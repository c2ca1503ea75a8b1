"""End-to-end slice (SURVEY.md §7.3): tfrecords -> trainer -> checkpoint ->
resume -> sample, all through the real driver code."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.data import shard_filename, write_tfrecord
from progen_tpu.models import ProGenConfig
from progen_tpu.observe import Tracker
from progen_tpu.train.trainer import Trainer, TrainerConfig
from tests.parity import assert_same_steps

CFG = ProGenConfig(
    num_tokens=128, dim=16, seq_len=16, depth=2, window_size=8,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    rng = np.random.default_rng(0)
    mk = lambda: bytes(rng.integers(65, 90, rng.integers(6, 14)))
    write_tfrecord(d / shard_filename(0, 48, "train"), [mk() for _ in range(48)])
    write_tfrecord(d / shard_filename(0, 8, "valid"), [mk() for _ in range(8)])
    return d


def _trainer(data_dir, ckpt_dir, runs_dir, max_steps):
    cfg = TrainerConfig(
        batch_size=2, grad_accum_every=2, epochs=50, learning_rate=1e-3,
        validate_every=2, sample_every=4, checkpoint_every=4,
        prime_length=4, mixed_precision=False, log_every=1,
        max_steps=max_steps,
    )
    tracker = Tracker(out_dir=str(runs_dir))
    return Trainer(
        model_config=CFG, cfg=cfg, data_path=str(data_dir),
        checkpoint_path=str(ckpt_dir), tracker=tracker, use_mesh=False,
    )


def test_train_checkpoint_resume_sample(data_dir, tmp_path):
    ckpt = tmp_path / "ckpts"
    runs = tmp_path / "runs"

    t1 = _trainer(data_dir, ckpt, runs, max_steps=5)
    out1 = t1.run()
    assert out1["step"] == 5
    assert out1["loss"] is not None and np.isfinite(out1["loss"])
    t1.store.close()

    # metrics JSONL written
    metrics_files = list(runs.glob("*/metrics.jsonl"))
    assert metrics_files, "tracker wrote no metrics"
    rows = [json.loads(l) for l in metrics_files[0].read_text().splitlines()]
    assert any("loss" in r for r in rows)
    assert any("valid_loss" in r for r in rows)
    samples = list(runs.glob("*/samples.html"))
    assert samples and "step" in samples[0].read_text()

    # resume: picks up from the checkpoint (seq cursor > 0, step continues)
    t2 = _trainer(data_dir, ckpt, runs, max_steps=7)
    state, start_seq, run_id = t2.restore_or_init()
    assert start_seq > 0
    assert int(state.step) == 5 * 2  # 5 outer steps x grad_accum 2
    out2 = t2.run()
    assert out2["step"] == 7
    t2.store.close()


def test_ragged_corpus_through_sharded_trainer(tmp_path, devices8):
    """A corpus with N % batch != 0 must
    stream through the MESH-SHARDED trainer across epoch boundaries with
    no shape retrace (which would be a hard divisibility crash under the
    ('data','fsdp')-sharded batch)."""
    d = tmp_path / "ragged_data"
    d.mkdir()
    rng = np.random.default_rng(1)
    mk = lambda: bytes(rng.integers(65, 90, rng.integers(6, 14)))
    write_tfrecord(d / shard_filename(0, 18, "train"), [mk() for _ in range(18)])
    write_tfrecord(d / shard_filename(0, 3, "valid"), [mk() for _ in range(3)])

    cfg = TrainerConfig(
        batch_size=8, grad_accum_every=1, epochs=50, learning_rate=1e-3,
        validate_every=100, sample_every=100, checkpoint_every=100,
        mixed_precision=False, log_every=100,
        max_steps=5,  # 18 // 8 = 2 steps/epoch -> crosses 2 epoch boundaries
    )
    t = Trainer(model_config=CFG, cfg=cfg, data_path=str(d),
                checkpoint_path=str(tmp_path / "ragged_ckpt"))
    out = t.run()
    assert out["step"] == 5
    assert out["loss"] is None or np.isfinite(out["loss"])
    t.store.close()


def test_full_validation_eval_is_exact(tmp_path):
    """Trainer.evaluate must equal the per-record mean CE over the WHOLE
    valid split — including when the last batch is partial (3 % 2 != 0) —
    with pad rows masked out, not averaged in."""
    from progen_tpu.data import iterator_from_tfrecords_folder
    from progen_tpu.train.loss import cross_entropy

    d = tmp_path / "eval_data"
    d.mkdir()
    rng = np.random.default_rng(3)
    mk = lambda: bytes(rng.integers(65, 90, rng.integers(6, 14)))
    write_tfrecord(d / shard_filename(0, 4, "train"), [mk() for _ in range(4)])
    write_tfrecord(d / shard_filename(0, 3, "valid"), [mk() for _ in range(3)])

    cfg = TrainerConfig(batch_size=2, mixed_precision=False, max_steps=1)
    t = Trainer(model_config=CFG, cfg=cfg, data_path=str(d),
                checkpoint_path=str(tmp_path / "eval_ckpt"), use_mesh=False)
    state = t.fns.init_state(jax.random.key(0))
    got = t.evaluate(state)

    # oracle: per-row CE over each valid record individually
    _, it_fn = iterator_from_tfrecords_folder(str(d), "valid")
    rows = np.concatenate(list(it_fn(seq_len=CFG.seq_len, batch_size=1)))
    assert rows.shape[0] == 3
    per_row = []
    for r in rows:
        batch = jnp.asarray(r[None])
        logits = t.model.apply({"params": state.params}, batch[:, :-1])
        per_row.append(float(cross_entropy(logits, batch[:, 1:])[0]))
    assert got == pytest.approx(np.mean(per_row), rel=1e-5)
    t.store.close()


def test_trainer_rejects_config_mismatch(data_dir, tmp_path):
    ckpt = tmp_path / "ckpts2"
    t1 = _trainer(data_dir, ckpt, tmp_path / "runs2", max_steps=1)
    t1.run()
    t1.store.close()

    other_cfg = ProGenConfig(**{**CFG.to_dict(), "dim": 32})
    cfg = TrainerConfig(batch_size=2, mixed_precision=False, max_steps=1)
    t2 = Trainer(model_config=other_cfg, cfg=cfg, data_path=str(data_dir),
                 checkpoint_path=str(ckpt), use_mesh=False)
    with pytest.raises(ValueError, match="model config differs"):
        t2.restore_or_init()
    t2.store.close()


def test_preemption_checkpoints_and_resumes(data_dir, tmp_path):
    """A preemption notice (SIGTERM flag) makes the trainer checkpoint at
    the next step boundary and exit; a fresh trainer resumes from it."""
    ckpt = tmp_path / "preempt_ckpt"
    t = _trainer(data_dir, ckpt, tmp_path / "preempt_runs", max_steps=50)
    t._request_preempt_checkpoint()  # what the SIGTERM handler does
    out = t.run()
    assert out.get("preempted") is True
    assert out["step"] == 1  # stopped at the first boundary
    t.store.close()

    t2 = _trainer(data_dir, ckpt, tmp_path / "preempt_runs", max_steps=2)
    state, start_seq, _ = t2.restore_or_init()
    assert int(state.step) == 1 * 2  # grad_accum 2 micro-steps
    assert start_seq > 0
    out2 = t2.run()
    assert out2["step"] == 2 and not out2.get("preempted")
    t2.store.close()


@pytest.mark.parametrize("script", ["train.py", "sample.py"])
def test_cli_help_runs(script):
    repo = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(repo / script), "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "--checkpoint_path" in out.stdout


def test_background_checkpoint_skips_when_save_in_flight(data_dir, tmp_path):
    """Periodic saves must never queue behind a slow in-flight save (on
    slow host links the fetch can exceed the checkpoint cadence); only
    wait=True (exit/preemption) joins and always writes."""
    import threading
    import time as _time

    t = _trainer(data_dir, tmp_path / "ck", tmp_path / "runs", max_steps=1)
    state = t.fns.init_state(jax.random.key(0))
    calls = []
    release = threading.Event()

    def slow_save(step, snapshot, **kw):
        calls.append(step)
        release.wait(timeout=10)
        return True

    t.store.save = slow_save
    t._checkpoint(state, 10)                 # starts background save
    _time.sleep(0.1)
    t._checkpoint(state, 20)                 # in flight -> skipped
    assert calls == [0]
    release.set()
    t._checkpoint(state, 30, wait=True)      # joins, then writes
    assert calls == [0, 0]
    t.store.close()


def _flex_trainer(data_dir, ckpt_dir, max_steps, **cfg_kw):
    base = dict(
        batch_size=2, grad_accum_every=2, epochs=50, learning_rate=1e-3,
        validate_every=1000, sample_every=1000, checkpoint_every=1000,
        prime_length=4, mixed_precision=False, log_every=1,
        max_steps=max_steps,
    )
    base.update(cfg_kw)
    return Trainer(
        model_config=CFG, cfg=TrainerConfig(**base), data_path=str(data_dir),
        checkpoint_path=str(ckpt_dir), use_mesh=False,
    )


def test_multi_epoch_shuffled_resume_is_bit_exact(tmp_path):
    """A seeded shuffled stream orders every corpus pass differently, so a
    resume must skip the UN-WRAPPED cursor (the full output count of the
    interrupted stream), not the position within one epoch — the wrapped
    skip would replay epoch-1 record order.  16-sequence corpus, 4 seqs
    per step: interrupting at step 6 leaves the cursor at 24 > 16, well
    into epoch 2."""
    d = tmp_path / "tiny_corpus"
    d.mkdir()
    rng = np.random.default_rng(5)
    mk = lambda: bytes(rng.integers(65, 90, rng.integers(6, 14)))
    write_tfrecord(d / shard_filename(0, 16, "train"), [mk() for _ in range(16)])
    write_tfrecord(d / shard_filename(0, 4, "valid"), [mk() for _ in range(4)])

    shuf = dict(shuffle_buffer=8, seed=7)
    base = _flex_trainer(d, tmp_path / "ck_base", max_steps=10, **shuf)
    out_base = base.run()
    base.store.close()

    t1 = _flex_trainer(d, tmp_path / "ck_resume", max_steps=6, **shuf)
    t1.run()
    t1.store.close()

    t2 = _flex_trainer(d, tmp_path / "ck_resume", max_steps=10, **shuf)
    state, start_seq, _ = t2.restore_or_init()
    assert int(state.step) == 6 * 2
    assert start_seq == 6 * 4  # un-wrapped: 24 > 16-sequence corpus
    out2 = t2.run()
    t2.store.close()

    assert out2["step"] == 10
    for a, b in zip(jax.tree.leaves(out2["state"].params),
                    jax.tree.leaves(out_base["state"].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_superstep_run_crosses_hooks_and_resumes_bit_exact(data_dir, tmp_path):
    """--superstep 2 through validate (3, 6) and checkpoint (4)
    boundaries: the cadence mix forces BOTH fused program shapes (full
    K=2 spans at 0->2 and 4->6, residual K=1 walks at 2->3->4), a
    "crash" at the step-4 checkpoint, and a resume — which must land on
    the same seq cursor and the params of the unfused loop run straight
    through (within ``tests/parity.py``'s bound: the fused and the unfused
    step are two XLA programs)."""
    cadences = dict(validate_every=3, checkpoint_every=4, log_every=2,
                    sample_every=1000)

    ref = _flex_trainer(data_dir, tmp_path / "ck_ref", max_steps=6,
                        superstep=1, **cadences)
    out_ref = ref.run()
    assert out_ref["step"] == 6
    ref.store.close()

    t1 = _flex_trainer(data_dir, tmp_path / "ck_fused", max_steps=4,
                       superstep=2, **cadences)
    out1 = t1.run()
    assert out1["step"] == 4
    t1.store.close()

    t2 = _flex_trainer(data_dir, tmp_path / "ck_fused", max_steps=6,
                       superstep=2, **cadences)
    state, start_seq, _ = t2.restore_or_init()
    assert int(state.step) == 4 * 2       # micro-steps: grad_accum 2
    assert start_seq == 4 * 4             # same cursor the unfused loop keeps
    out2 = t2.run()
    assert out2["step"] == 6
    t2.store.close()

    assert_same_steps(out2["state"].params, out_ref["state"].params)


class _FakeSampler:
    """Records warm-execution and AOT-lower calls without any real decode."""

    def __init__(self):
        self.calls = []
        self.lowered = []

    def __call__(self, params, key, prime, **kw):
        self.calls.append(kw)
        return jnp.zeros((1, 4), jnp.int32)

    def lower(self, *a, **kw):
        self.lowered.append(kw)
        return self

    def compile(self):
        return self


def test_sampler_warmup_gated_by_flag(data_dir, tmp_path):
    """warm_sampler=False must skip the sampler's minutes-long decode
    compile entirely (preemption restarts that sample rarely)."""
    t = _flex_trainer(data_dir, tmp_path / "ck", max_steps=8,
                      sample_every=4, warm_sampler=False)
    fake = _FakeSampler()
    t.sampler = fake
    state, _, _ = t.restore_or_init()
    t._warm_compiles(state, global_step=0)
    t.store.close()
    assert fake.calls == [] and fake.lowered == []


def test_sampler_warmup_skipped_when_no_hook_due(data_dir, tmp_path):
    """Resuming at step 5 of a 6-step run with sample_every=4: the next
    sample hook (8) is past max_steps, so warming buys nothing."""
    t = _flex_trainer(data_dir, tmp_path / "ck", max_steps=6, sample_every=4)
    fake = _FakeSampler()
    t.sampler = fake
    state, _, _ = t.restore_or_init()
    t._warm_compiles(state, global_step=5)
    t.store.close()
    assert fake.calls == [] and fake.lowered == []


def test_sampler_warmup_runs_when_hook_ahead(data_dir, tmp_path):
    """Positive control: a reachable sample hook does warm-execute."""
    t = _flex_trainer(data_dir, tmp_path / "ck", max_steps=8, sample_every=4)
    fake = _FakeSampler()
    t.sampler = fake
    state, _, _ = t.restore_or_init()
    t._warm_compiles(state, global_step=0)
    t.store.close()
    assert len(fake.calls) == 1
