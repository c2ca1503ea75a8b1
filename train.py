"""Training CLI — flag-compatible with the reference ``train.py``
(``/root/reference/train.py:36-58``), plus TPU-native flags for mesh shape,
sharding strategies, rematerialization and profiling.

Multi-host: run the same command on every host with
``jax.distributed`` env vars set (or pass --distributed to autodetect).
"""

import sys
import tomllib
from pathlib import Path

import click


def _load_model_config(config_path: str, model_name: str) -> dict:
    path = Path(config_path) / f"{model_name}.toml"
    assert path.exists(), f"path to your model config {path} does not exist"
    return tomllib.loads(path.read_text())


@click.command()
@click.option("--seed", default=42)
@click.option("--batch_size", default=4)
@click.option("--grad_accum_every", default=4)
@click.option("--epochs", default=100)
@click.option("--learning_rate", default=2e-4)
@click.option("--lr_schedule", default="constant",
              help="lr shape (progen_tpu.train.SCHEDULES: constant, cosine, "
                   "linear); cosine/linear need --schedule_steps or "
                   "--max_steps as the decay horizon")
@click.option("--warmup_steps", default=0,
              help="linear lr warmup over this many optimizer steps")
@click.option("--schedule_steps", default=None, type=int,
              help="step at which cosine/linear decay bottoms out")
@click.option("--lr_min_ratio", default=0.1,
              help="decay floor as a fraction of --learning_rate")
@click.option("--weight_decay", default=1e-3)
@click.option("--max_grad_norm", default=0.5)
@click.option("--validate_every", default=100)
@click.option("--sample_every", default=500)
@click.option("--checkpoint_every", default=1000)
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--checkpoint_keep_n", default=500)
@click.option("--config_path", default="./configs/model")
@click.option("--model_name", default="default")
@click.option("--prime_length", default=25)
@click.option("--mixed_precision", default=False, is_flag=True)
@click.option("--data_path", default="./train_data")
@click.option("--shuffle_buffer", default=0,
              help="sliding-window record shuffle (0 = off, reference "
                   "behavior; data is already shuffled at prep). Resume is "
                   "deterministic: the seeded shuffle replays from the "
                   "stream start and the cursor skip applies to its OUTPUT, "
                   "so a resumed run consumes exactly the interrupted run's "
                   "record order")
@click.option("--wandb_off", default=False, is_flag=True)
@click.option("--wandb_project_name", default="progen-training")
@click.option("--new", default=False, is_flag=True)
# TPU-native flags (no reference counterpart)
@click.option("--strategies", default="dp",
              help="comma list of sharding strategies: dp,fsdp,tp,sp")
@click.option("--mesh", "mesh_spec", default="-1,1,1,1",
              help="mesh axis sizes data,fsdp,tensor,seq (-1 = remaining)")
@click.option("--remat", default=False, is_flag=True,
              help="rematerialize blocks in backward (saves HBM)")
@click.option("--remat_policy", default="full",
              type=click.Choice(["full", "dots", "attn"]),
              help="full: recompute everything; dots: save matmul outputs, "
                   "recompute only elementwise work; attn: save the "
                   "attention path (q/k/v + out), replay only the "
                   "feed-forward")
@click.option("--attn_impl", default="xla", type=click.Choice(["xla", "pallas"]),
              help="windowed attention implementation")
@click.option("--sgu_impl", default="xla", type=click.Choice(["xla", "pallas"]),
              help="SGU spatial-gate implementation (pallas = blocked-causal "
                   "fused kernel, skips upper-triangle blocks; falls back to "
                   "the context-parallel op under sp)")
@click.option("--prefetch_depth", default=2,
              help="device batches buffered ahead of the step consuming "
                   "them (0 = synchronous reference-style feed)")
@click.option("--superstep", default=1,
              help="fuse up to K optimizer steps per XLA dispatch "
                   "(lax.scan over a staged (K, accum, B, L) superbatch; "
                   "1 = per-step dispatch).  Spans shrink to land on hook "
                   "boundaries, so log/checkpoint/validate/sample cadences "
                   "are unchanged; costs ~2 superbatches of HBM "
                   "(docs/TRAINING.md)")
@click.option("--background_checkpoint/--no_background_checkpoint",
              default=True,
              help="checkpoint via an on-device state snapshot + background "
                   "device->host fetch (costs one state-sized HBM copy; "
                   "disable when HBM is tight)")
@click.option("--log_every", default=10)
@click.option("--max_steps", default=None, type=int)
@click.option("--profile_dir", default=None, type=str)
@click.option("--runs_dir", default="./runs")
@click.option("--distributed", default=False, is_flag=True,
              help="call jax.distributed.initialize() for multi-host "
                   "(retried with backoff; see docs/RESILIENCE.md)")
# resilience (docs/RESILIENCE.md)
@click.option("--run_attempts", default=3,
              help="total tries of the train loop: transient failures "
                   "re-restore from the latest checkpoint and continue "
                   "(1 = fail fast)")
@click.option("--watchdog_timeout", default=None, type=float,
              help="seconds without a completed step before the watchdog "
                   "dumps all-thread stacks + the flight recorder to the "
                   "run dir and exits nonzero (unset = off); size it to "
                   "several worst-case step times")
@click.option("--statusz", "statusz_port", default=None, type=int,
              flag_value=0, is_flag=False,
              help="serve live /healthz /statusz /metricsz /tracez "
                   "/flightz on this loopback port (bare --statusz = "
                   "ephemeral port, printed at startup); handlers read "
                   "host state only — zero perturbation "
                   "(docs/OBSERVABILITY.md)")
@click.option("--warm_sampler/--no_warm_sampler", default=True,
              help="pre-loop sampler warm execution (minutes of decode "
                   "compile); auto-skipped when no sample hook can fire, "
                   "e.g. on a preemption restart near max_steps")
@click.option("--inject-faults", "inject_faults", default=None, type=str,
              help="arm the deterministic fault-injection harness, e.g. "
                   "'ckpt.save:io_error:times=2;train.step:preempt:at=5' "
                   "(testing/drills only; see docs/RESILIENCE.md)")
# accepted for reference compatibility; the pmap flag is meaningless under
# pjit — dp over the mesh is the default
@click.option("--data_parallel", default=False, is_flag=True, hidden=True)
@click.option("--seq_len", default=None, type=int, hidden=True)
def main(**flags):
    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()  # restarts/resume hit the on-disk XLA cache
    if flags["inject_faults"]:
        from progen_tpu.resilience import faults

        faults.configure(flags["inject_faults"], seed=flags["seed"])
    if flags["distributed"]:
        from progen_tpu.core.mesh import initialize_distributed

        initialize_distributed()

    from progen_tpu.checkpoint import CheckpointStore
    from progen_tpu.core.mesh import MeshConfig
    from progen_tpu.models import ProGenConfig
    from progen_tpu.observe import Tracker
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    store = CheckpointStore(flags["checkpoint_path"], flags["checkpoint_keep_n"])
    if flags["new"]:
        if not click.confirm(
            "are you sure you want to clear all your checkpoints and restart "
            "training?"
        ):
            sys.exit()
        store.reset()

    # model config: checkpoint wins on resume (reference train.py:96-102)
    meta = store.restore_meta()
    if meta is None:
        model_kwargs = _load_model_config(flags["config_path"],
                                          flags["model_name"])
    else:
        model_kwargs = meta["model_config"]
    store.close()
    model_config = ProGenConfig.from_dict(model_kwargs)

    try:
        mesh_cfg = MeshConfig.parse(flags["mesh_spec"])
    except ValueError as e:
        raise click.BadParameter(str(e), param_hint="--mesh")

    cfg = TrainerConfig(
        seed=flags["seed"],
        batch_size=flags["batch_size"],
        grad_accum_every=flags["grad_accum_every"],
        epochs=flags["epochs"],
        learning_rate=flags["learning_rate"],
        lr_schedule=flags["lr_schedule"],
        warmup_steps=flags["warmup_steps"],
        schedule_steps=flags["schedule_steps"],
        lr_min_ratio=flags["lr_min_ratio"],
        weight_decay=flags["weight_decay"],
        max_grad_norm=flags["max_grad_norm"],
        validate_every=flags["validate_every"],
        sample_every=flags["sample_every"],
        checkpoint_every=flags["checkpoint_every"],
        checkpoint_keep_n=flags["checkpoint_keep_n"],
        prime_length=flags["prime_length"],
        mixed_precision=flags["mixed_precision"],
        shuffle_buffer=flags["shuffle_buffer"],
        strategies=tuple(flags["strategies"].split(",")),
        mesh=mesh_cfg,
        remat=flags["remat"],
        remat_policy=flags["remat_policy"],
        attn_impl=flags["attn_impl"],
        sgu_impl=flags["sgu_impl"],
        prefetch_depth=flags["prefetch_depth"],
        superstep=flags["superstep"],
        background_checkpoint=flags["background_checkpoint"],
        log_every=flags["log_every"],
        max_steps=flags["max_steps"],
        profile_dir=flags["profile_dir"],
        run_attempts=flags["run_attempts"],
        watchdog_timeout=flags["watchdog_timeout"],
        statusz_port=flags["statusz_port"],
        warm_sampler=flags["warm_sampler"],
    )

    tracker = Tracker(
        project=flags["wandb_project_name"],
        out_dir=flags["runs_dir"],
        run_id=(meta or {}).get("run_id"),
        use_wandb=not flags["wandb_off"],  # JSONL sink is always on
        config={**model_kwargs, **{k: v for k, v in flags.items()
                                   if k not in ("new",)}},
    )

    trainer = Trainer(
        model_config=model_config,
        cfg=cfg,
        data_path=flags["data_path"],
        checkpoint_path=flags["checkpoint_path"],
        tracker=tracker,
    )
    try:
        # click drops the value when run from the shell; a caller in this
        # process (chip_smoke.py) reads the final state from it
        return trainer.run()
    finally:
        tracker.finish()


if __name__ == "__main__":
    main()
