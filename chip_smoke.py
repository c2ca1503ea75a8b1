"""Chip smoke test: the main path, once, on the TPU, at ProGen-small's width.

    python chip_smoke.py            # one chip: kernels, train, resume, serve
    python chip_smoke.py --chips 4  # four chips: sharded training only

The quickest proof that the system still starts on the chip.  Nothing is
tiny and nothing is mocked: the model is ProGen-small at its published
width and depth (random weights from ``--seed``), the data are the
committed tfrecord shards under ``train_data_synth/``, and every phase goes
through the entry point a user would call — ``train.py`` and ``sample.py``
as click commands.

**One process.**  A chip belongs to one process at a time, so everything
runs in THIS process: the click commands are called in-process
(``main(args, standalone_mode=False)``), no child is started, and the
device line at the end is what this process's JAX reports.  That is also
how compile seconds are read: a ``jax.monitoring`` listener sums the
backend-compile durations (a persistent-cache retrieval on a hit, a real
compile on a miss) and counts cache hits and misses per phase.

Phases of the default run (one chip):

1. device    — fail unless JAX's first device is a TPU; print versions.
2. kernels   — windowed attention, blocked SGU (forward and backward),
               the paged gate-mix (bf16, q8) and the decode step's cache
               write (rings, gate cache; bit for bit): compiled kernel
               (``tpu_custom_call`` asserted in the compiled text) against
               its XLA twin on the same seeded inputs.
3. train     — ``train.py --model_name small --mixed_precision --attn_impl
               pallas --sgu_impl pallas``, batch 8, 6 optimizer steps with
               a validation hook (step 3) and a background checkpoint
               (step 4) inside them; every loss finite; step 1's loss
               against the same step with both XLA implementations.
4. resume    — the same command again for two more steps: restores step 6,
               and compiles from the persistent cache.
5. serve     — ``sample.py --serve`` on that checkpoint, five primes of
               different lengths, fixed-slot then ``--paged``; then one
               plain ``sample.py --prime`` through the one-shot sampler.

``--chips 4`` runs only the sharded path and what it is compared with:
ProGen-small for three steps on a (data 1, fsdp 2, tensor 2, seq 1) mesh
with both Pallas kernels under ``shard_map`` against the same three steps
on one chip (a mesh-less ``Trainer`` on the first device, same process:
``train.py`` has no flag for fewer devices than it sees), then ``train.py
--model_name large`` (published width and depth) ``--mesh 1,4,1,1
--strategies fsdp --remat`` for two optimizer steps over the four chips,
asserting from ``addressable_shards`` of the state the command returns
that parameters and optimizer state are spread.

Writes only under ``--out`` (default ``chip_smoke_out/`` beside this file,
wiped at start) and the compile cache (``JAX_COMPILATION_CACHE_DIR``, or
``.jax_cache/`` in the checkout).  Any failed phase raises: the exit code
is non-zero and the last line is not printed.  The last line of a passing
run is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import io
import json
import math
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG_PATH = os.path.join(REPO, "configs", "model")
DATA_PATH = os.path.join(REPO, "train_data_synth")

MODEL = "small"            # configs/model/small.toml == models.configs.SMALL
LARGE_MODEL = "large"      # configs/model/large.toml == models.configs.LARGE
BATCH = 8
TRAIN_STEPS, VALIDATE_AT, CHECKPOINT_AT, RESUME_STEPS = 6, 3, 4, 2
PRIMES = ["M", "MKV", "MKVLAAGIVG", "MSTNPKPQRKTKRNTNRRPQDVKFPGG",
          "MGSSHHHHHHSSGLVPRGSHMASMTGGQQMGRGSEFELRRQACGRTRAPPPPPLRSGC"]

# Tolerances, one per comparison; beside each, what the chip showed (my
# chip runs, PR 21).  A kernel against its XLA twin is measured as
# max|kernel - twin| / max|twin| over the forward output and every
# gradient; a wrong block, mask or scale is O(1) on that measure.
#
# Windowed attention and blocked SGU, forward + backward: both sides
# accumulate in f32 and round their outputs and gradients to bf16 (one ulp
# is 2^-8 = 3.9e-3 relative), in a different order of partial sums.
# Measured: 7.9e-3 (two ulps of the largest value) and 3.9e-3 (one).
ATTENTION_TOL = 2e-2
SGU_TOL = 1e-2
# Paged gate-mix, bf16 and q8 pools: f32 output; the kernel multiplies and
# accumulates in f32 on the VPU, and the twin's einsum (default precision)
# turned out f32-exact too — XLA does not round this batched mat-vec's
# operands to bf16 — so the two differ only in the order of a 1024-term
# sum.  Measured: 3.4e-7 (bf16), 2.8e-7 (q8).  A q8 scale applied to the
# wrong row, or a dequantization at bf16, is >= 1e-3.
PAGED_TOL = 1e-5
# Step-1 loss, Pallas kernels against the XLA implementations: an f32 mean
# over 8 x 1024 tokens of a bf16 forward from identical weights, so the
# kernels' rounding differences mostly average out.  Measured: 1.0e-3
# (6.054504 against 6.055518, the same on three machines).
LOSS_TOL_IMPL = 4e-3
# Per-step losses, (fsdp 2, tensor 2) mesh against one chip, three steps:
# same weights, data and global batch; tensor parallelism splits the
# contractions, so partial sums round differently, and the difference
# feeds through two optimizer updates.  Measured: 1.5e-4, 7.4e-4, 1.5e-3.
LOSS_TOL_SHARDED = 5e-3


# --------------------------------------------------------------- bookkeeping


class CompileClock:
    """Sums JAX's backend-compile durations and counts persistent-cache
    hits and misses, so each phase can report what it compiled."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


PHASES: list[dict] = []


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    """Time one phase; print its wall seconds, compile seconds, cache
    hits/misses and the device's peak memory so far.  Exceptions pass
    through: a failed phase ends the run."""
    import jax

    print(f"=== phase {name}: start", flush=True)
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    record = {"phase": name}
    yield record
    c1 = clock.snapshot()
    record.update(
        wall_s=round(time.perf_counter() - t0, 2),
        compile_s=round(c1[0] - c0[0], 2),
        cache_hits=c1[1] - c0[1],
        cache_misses=c1[2] - c0[2],
        peak_bytes_in_use=[
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()],
    )
    PHASES.append(record)
    print(f"=== phase {name}: ok {json.dumps(record)}", flush=True)
    gc.collect()


class _Tee(io.TextIOBase):
    def __init__(self, *sinks):
        self.sinks = sinks

    def write(self, s):
        for sink in self.sinks:
            sink.write(s)
        return len(s)

    def flush(self):
        for sink in self.sinks:
            sink.flush()


def run_cli(script: str, args: list[str]):
    """Call ``train.py``'s or ``sample.py``'s click command in this
    process; relay its stdout and return ``(stdout, the command's return
    value)``.  ``standalone_mode=False``: exceptions propagate, nothing
    calls exit.  ``train.py`` returns its run's result, final state
    included: a caller that only reads the text takes ``[0]``, so that
    the state leaves the device with the phase that made it."""
    print(f"$ python {script} " + " ".join(args), flush=True)
    command = importlib.import_module(script.removesuffix(".py")).main
    captured = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, captured)):
        value = command.main(args=args, standalone_mode=False)
    return captured.getvalue(), value


def read_metrics(runs_dir: str) -> list[dict]:
    """Rows of the one run's ``metrics.jsonl`` under ``runs_dir`` (a
    resumed run keeps its run id, so one directory, appended)."""
    (run,) = os.listdir(runs_dir)
    with open(os.path.join(runs_dir, run, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def losses_by_step(rows: list[dict]) -> dict[int, float]:
    return {r["step"]: r["loss"] for r in rows if "loss" in r}


def assert_finite(name: str, values) -> None:
    bad = [v for v in values if not math.isfinite(v)]
    if bad or not values:
        raise AssertionError(f"{name}: non-finite or missing: {values}")


# ------------------------------------------------------------------- device


def require_tpu():
    """Fail at once off-TPU (the repo's own check); print what is there."""
    import importlib.metadata as md

    import jax
    import jaxlib

    from progen_tpu.observe.platform import require_tpu as first_tpu_device

    device = first_tpu_device()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "unknown"
    stats = device.memory_stats() or {}
    print(json.dumps({
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "bytes_limit": stats.get("bytes_limit"),
        "disk_free_bytes": shutil.disk_usage(REPO).free,
        "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
    }), flush=True)
    return device


def device_line() -> str:
    import jax

    d = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}})


# ------------------------------------------------------------------ kernels


def _compare(name: str, kernel_fn, twin_fn, args, tol: float) -> dict:
    """Compile ``kernel_fn``, assert it holds a Mosaic kernel, run THAT
    executable and the XLA twin on ``args``; fail above ``tol``."""
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(kernel_fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the compiled "
                             "program — the kernel did not run on the chip")
    got = jax.tree.leaves(compiled(*args))
    want = jax.tree.leaves(jax.jit(twin_fn)(*args))
    worst_abs = worst_rel = 0.0
    for g, w in zip(got, want, strict=True):
        g, w = jnp.asarray(g, jnp.float32), jnp.asarray(w, jnp.float32)
        if not bool(jnp.all(jnp.isfinite(g))):
            raise AssertionError(f"{name}: kernel output is not finite")
        diff = float(jnp.max(jnp.abs(g - w)))
        scale = float(jnp.max(jnp.abs(w)))
        worst_abs = max(worst_abs, diff)
        worst_rel = max(worst_rel, diff / scale if scale else diff)
    out = {"kernel": name, "max_abs_diff": worst_abs,
           "max_rel_diff": worst_rel, "tolerance_rel": tol}
    print(json.dumps(out), flush=True)
    if not worst_rel <= tol:
        raise AssertionError(f"{name}: kernel and XLA twin differ by "
                             f"{worst_rel:.3g} of max|twin| > {tol}")
    return out


def _with_grads(fn, nargs: int, cot):
    """fn -> (forward, grads of sum(forward * cot) w.r.t. every arg)."""
    import jax
    import jax.numpy as jnp

    def both(*args):
        def scalar(*a):
            return jnp.sum(fn(*a).astype(jnp.float32) * cot)
        return fn(*args), jax.grad(scalar, argnums=tuple(range(nargs)))(*args)

    return both


def kernels_phase(cfg, seed: int) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from progen_tpu.decode.paging import NULL_PAGE
    from progen_tpu.ops import row_write
    from progen_tpu.ops.local_attention import local_attention
    from progen_tpu.ops.pallas_attention import pallas_local_attention
    from progen_tpu.ops.pallas_paged_attention import paged_gate_mix
    from progen_tpu.ops.pallas_sgu import pallas_spatial_gate
    from progen_tpu.ops.quant import quantize_rows, quantize_w
    from progen_tpu.ops.sgu import spatial_gate

    keys = iter(jax.random.split(jax.random.key(seed), 32))
    bf16 = jnp.bfloat16
    n, d = cfg.seq_len, cfg.dim * cfg.ff_mult // 2
    results = []

    # windowed attention, forward + backward
    shape = (BATCH, cfg.heads, n, cfg.dim_head)
    q, k, v = (jax.random.normal(next(keys), shape, bf16) for _ in range(3))
    cot = jax.random.normal(next(keys), shape, jnp.float32)
    results.append(_compare(
        "windowed_attention fwd+bwd",
        _with_grads(lambda q, k, v: pallas_local_attention(
            q, k, v, cfg.window_size, interpret=False), 3, cot),
        _with_grads(lambda q, k, v: local_attention(
            q, k, v, window_size=cfg.window_size), 3, cot),
        (q, k, v), ATTENTION_TOL))

    # blocked-causal SGU, forward + backward (weights at a scale that
    # makes the mixing matter; the trained scale is ~1e-6)
    res = jax.random.normal(next(keys), (BATCH, n, d), bf16)
    gate = jax.random.normal(next(keys), (BATCH, n, d), bf16)
    w = (jax.random.normal(next(keys), (n, n), jnp.float32) * n ** -0.5
         ).astype(bf16)
    b = jax.random.normal(next(keys), (n, 1), bf16)
    cot = jax.random.normal(next(keys), (BATCH, n, d), jnp.float32)
    results.append(_compare(
        "blocked_sgu fwd+bwd",
        _with_grads(lambda r, g, w, b: pallas_spatial_gate(
            r, g, w, b, interpret=False), 4, cot),
        _with_grads(lambda r, g, w, b: r * spatial_gate(g, w, b), 4, cot),
        (res, gate, w, b), SGU_TOL))

    # paged gate-mix: ragged positions, partially-NULL tables, the
    # engine's default page size
    ps = 16
    ppr = n // ps
    num_pages = 2 + BATCH * ppr
    pos = jnp.asarray([0, 15, 16, 100, 511, 512, 1000, n - 1][:BATCH],
                      jnp.int32)
    owned = jnp.arange(ppr)[None, :] <= (pos // ps)[:, None]
    table = jnp.where(
        owned, 2 + jnp.arange(BATCH)[:, None] * ppr + jnp.arange(ppr)[None],
        NULL_PAGE).astype(jnp.int32)
    weights = jax.random.normal(next(keys), (n, n), jnp.float32) * n ** -0.5
    biases = jax.random.normal(next(keys), (n, 1), jnp.float32)
    rows = jax.random.normal(next(keys), (num_pages, ps, d), jnp.float32)
    rows = rows.at[NULL_PAGE].set(0.0)
    pool = rows.astype(bf16)

    def mix(impl):
        return lambda w, b, pool, table, pos: paged_gate_mix(
            w, b, pool, table, pos, n_rows=n, impl=impl,
            interpret=False if impl == "pallas" else None)

    results.append(_compare("paged_gate_mix bf16", mix("pallas"), mix("xla"),
                            (weights, biases, pool, table, pos), PAGED_TOL))

    qw, w_scale = quantize_w(weights, channel_axis=0)
    pool_q, pool_scale = quantize_rows(rows)

    def mix_q8(impl):
        return lambda w, b, pool, table, pos, ws, ps_: paged_gate_mix(
            w, b, pool, table, pos, n_rows=n, impl=impl,
            interpret=False if impl == "pallas" else None,
            w_scale=ws, pool_scale=ps_)

    results.append(_compare(
        "paged_gate_mix q8", mix_q8("pallas"), mix_q8("xla"),
        (qw, biases, pool_q, table, pos, w_scale, pool_scale), PAGED_TOL))

    # the decode step's cache write against the scatter it replaces: a
    # layer's k and v rings in one call, then the gate cache; a copy, so
    # the tolerance is zero
    ring = (BATCH, cfg.heads, 2 * cfg.window_size, cfg.dim_head)
    k_ring, v_ring = (jax.random.normal(next(keys), ring, bf16)
                      for _ in range(2))
    k_row, v_row = (jax.random.normal(next(keys), ring[:2] + ring[3:], bf16)
                    for _ in range(2))
    slot = pos % ring[2]
    results.append(_compare(
        "row_write rings",
        lambda k, v, a, b, i: row_write.pallas_write_rows(
            (k, v), (a, b), i, interpret=False),
        lambda k, v, a, b, i: (row_write._scatter_rows(k, a, i, 1),
                               row_write._scatter_rows(v, b, i, 1)),
        (k_ring, v_ring, k_row, v_row, slot), 0.0))
    results.append(_compare(
        "row_write gate cache",
        lambda c, u, i: row_write.pallas_write_rows(
            (c,), (u,), i, interpret=False),
        lambda c, u, i: (row_write._scatter_rows(c, u, i, 0),),
        (gate, gate[:, 0], pos), 0.0))
    return results


# -------------------------------------------------------------------- train


def train_args(out: str, tag: str, *, model: str, impl: str, max_steps: int,
               seed: int, validate_every: int, checkpoint_every: int,
               batch_size: int = BATCH, extra: tuple = ()) -> list[str]:
    return [
        "--model_name", model, "--mixed_precision",
        "--attn_impl", impl, "--sgu_impl", impl,
        "--config_path", CONFIG_PATH, "--data_path", DATA_PATH,
        "--batch_size", str(batch_size), "--grad_accum_every", "1",
        "--wandb_off", "--seed", str(seed),
        "--max_steps", str(max_steps), "--log_every", "1",
        "--validate_every", str(validate_every),
        "--checkpoint_every", str(checkpoint_every),
        "--checkpoint_path", os.path.join(out, f"ckpt_{tag}"),
        "--runs_dir", os.path.join(out, f"runs_{tag}"),
        "--run_attempts", "1", *extra,
    ]


def one_chip_run(out: str, seed: int, clock: CompileClock) -> None:
    from progen_tpu.checkpoint import CheckpointStore
    from progen_tpu.models.configs import CONFIGS

    with phase("kernels", clock) as rec:
        rec["kernels"] = kernels_phase(CONFIGS[MODEL], seed)

    common = dict(model=MODEL, seed=seed, validate_every=VALIDATE_AT,
                  checkpoint_every=CHECKPOINT_AT)
    with phase("train", clock) as rec:
        text = run_cli("train.py", train_args(
            out, "main", impl="pallas", max_steps=TRAIN_STEPS, **common))[0]
        rows = read_metrics(os.path.join(out, "runs_main"))
        losses = losses_by_step(rows)
        if sorted(losses) != list(range(1, TRAIN_STEPS + 1)):
            raise AssertionError(f"train: logged steps {sorted(losses)}")
        valid = [r["valid_loss"] for r in rows if "valid_loss" in r]
        assert_finite("train losses", list(losses.values()))
        assert_finite("validation losses", valid)
        if "checkpoint to start at sequence index of "\
                f"{CHECKPOINT_AT * BATCH}" not in text:
            raise AssertionError("train: no checkpoint inside the steps")
        rec.update(losses=losses, valid_losses=valid)

    with phase("train_xla_step1", clock) as rec:
        # the same first step with both XLA implementations (hooks off)
        run_cli("train.py", train_args(
            out, "xla", impl="xla", max_steps=1, model=MODEL, seed=seed,
            validate_every=10**6, checkpoint_every=10**6))
        xla = losses_by_step(read_metrics(os.path.join(out, "runs_xla")))
        diff = abs(losses[1] - xla[1])
        rec.update(loss_pallas=losses[1], loss_xla=xla[1], abs_diff=diff,
                   tolerance_abs=LOSS_TOL_IMPL)
        if not diff <= LOSS_TOL_IMPL:
            raise AssertionError(
                f"step-1 loss: pallas {losses[1]} vs xla {xla[1]}")
        shutil.rmtree(os.path.join(out, "ckpt_xla"))

    store = CheckpointStore(os.path.join(out, "ckpt_main"))
    saved_step, meta = store.latest_step(), store.restore_meta()
    store.close()
    if saved_step != TRAIN_STEPS \
            or meta["next_seq_index"] != TRAIN_STEPS * BATCH:
        raise AssertionError(f"checkpoint: step {saved_step}, meta {meta}")

    with phase("resume", clock) as rec:
        text = run_cli("train.py", train_args(
            out, "main", impl="pallas",
            max_steps=TRAIN_STEPS + RESUME_STEPS, **common))[0]
        if f"starting from sequence {TRAIN_STEPS * BATCH}" not in text:
            raise AssertionError("resume: did not restore the saved step")
        resumed = losses_by_step(
            read_metrics(os.path.join(out, "runs_main")))
        new = sorted(set(resumed) - set(losses))
        want = list(range(TRAIN_STEPS + 1, TRAIN_STEPS + RESUME_STEPS + 1))
        if new != want:
            raise AssertionError(f"resume: new steps {new}, wanted {want}")
        assert_finite("resumed losses", [resumed[s] for s in new])
        rec.update(restored_step=saved_step,
                   losses={s: resumed[s] for s in new})
    first, again = next(p for p in PHASES if p["phase"] == "train"), PHASES[-1]
    print(json.dumps({"compile_s_first_run": first["compile_s"],
                      "first_run_cache_misses": first["cache_misses"],
                      "compile_s_resumed_run": again["compile_s"],
                      "resumed_cache_hits": again["cache_hits"],
                      "resumed_cache_misses": again["cache_misses"]}),
          flush=True)
    # (a "miss" is a program that was compiled and worth writing, >= 1 s.)
    # The resumed run must read from the cache, and where the first run
    # started cold its compile seconds must dwarf the resumed run's; where
    # the machine came with a warm cache, both are retrievals.
    if again["cache_hits"] == 0 or (
            first["cache_misses"] > 0
            and not again["compile_s"] < 0.5 * first["compile_s"]):
        raise AssertionError("resume: the compile cache did not hit")

    serve_phases(os.path.join(out, "ckpt_main"), seed, clock)


# -------------------------------------------------------------------- serve


_BLOCK = re.compile(r"\*{40} \[(\w+), (\d+) tokens, [\d.]+s\]\n")


def assert_in_vocabulary(text: str) -> None:
    """``decode_tokens`` prints token id t as chr(t - 1); the vocabulary
    is 256 ids, so no printed character may reach 255."""
    if any(ord(c) >= 255 for c in text):
        raise AssertionError("decoded a token outside the vocabulary")


def check_completions(text: str, n_requests: int) -> list[dict]:
    """Every request printed one completion block with a finish reason
    and at least one token, all inside the vocabulary."""
    blocks = _BLOCK.findall(text)
    if len(blocks) != n_requests:
        raise AssertionError(
            f"serve: {len(blocks)} completions for {n_requests} requests")
    for reason, count in blocks:
        if reason not in ("eos", "length") or int(count) < 1:
            raise AssertionError(f"serve: finish {reason!r}, {count} tokens")
    assert_in_vocabulary(text)
    return [{"finish_reason": r, "tokens": int(c)} for r, c in blocks]


def serve_phases(ckpt: str, seed: int, clock: CompileClock) -> None:
    base = ["--checkpoint_path", ckpt, "--seed", str(seed)]
    for name, extra in (("serve_fixed_slot", []), ("serve_paged", ["--paged"])):
        with phase(name, clock) as rec:
            text = run_cli("sample.py", base + [
                "--serve", "--prime", "|".join(PRIMES), *extra])[0]
            rec["completions"] = check_completions(text, len(PRIMES))
    with phase("sample_one_shot", clock) as rec:
        text = run_cli("sample.py", base + ["--prime", PRIMES[2]])[0]
        decoded = text.split("*" * 40)[-1].strip()
        if not decoded:
            raise AssertionError("sample: nothing was decoded")
        assert_in_vocabulary(decoded)
        rec["sampled_chars"] = len(decoded)


# --------------------------------------------------------------- four chips


def spread_report(name: str, tree) -> dict:
    """Share of ``tree``'s bytes that each device holds, from
    ``addressable_shards``; fail unless every device holds some and none
    holds more than 30% (an even spread over four is 25%)."""
    import jax

    held = {d.id: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    total = sum(held.values())
    shares = {k: round(v / total, 4) for k, v in held.items()}
    print(json.dumps({"spread": name, "bytes_total": total,
                      "share_per_device": shares}), flush=True)
    if len(held) != 4 or min(shares.values()) <= 0 \
            or max(shares.values()) > 0.30:
        raise AssertionError(f"{name} is not spread over four devices: "
                             f"{shares}")
    return shares


def four_chip_run(out: str, seed: int, clock: CompileClock) -> None:
    import jax

    from progen_tpu.models.configs import CONFIGS
    from progen_tpu.observe import Tracker
    from progen_tpu.train.trainer import Trainer, TrainerConfig

    if len(jax.devices()) != 4:
        raise SystemExit(f"--chips 4 needs four chips, JAX found "
                         f"{len(jax.devices())}")
    steps = 3
    off = 10**6  # hook cadences beyond the run: the exit save still runs

    with phase("small_sharded_fsdp2_tp2", clock) as rec:
        run_cli("train.py", train_args(
            out, "sharded", model=MODEL, impl="pallas", max_steps=steps,
            seed=seed, validate_every=off, checkpoint_every=off,
            extra=("--mesh", "1,2,2,1", "--strategies", "fsdp,tp")))
        sharded = losses_by_step(
            read_metrics(os.path.join(out, "runs_sharded")))
        assert_finite("sharded losses", list(sharded.values()))
        rec["losses"] = sharded

    with phase("small_one_chip_reference", clock) as rec:
        # train.py builds its mesh over every visible device and has no
        # flag for fewer, so the one-chip side is a mesh-less Trainer on
        # the first device, configured as train.py would
        tracker = Tracker(out_dir=os.path.join(out, "runs_one"),
                          use_wandb=False)
        try:
            Trainer(
                model_config=CONFIGS[MODEL], data_path=DATA_PATH,
                checkpoint_path=os.path.join(out, "ckpt_one"),
                tracker=tracker, use_mesh=False,
                cfg=TrainerConfig(
                    seed=seed, batch_size=BATCH, grad_accum_every=1,
                    mixed_precision=True, attn_impl="pallas",
                    sgu_impl="pallas", max_steps=steps, log_every=1,
                    validate_every=off, checkpoint_every=off,
                    sample_every=off)).run()
        finally:
            tracker.finish()
        one = losses_by_step(read_metrics(os.path.join(out, "runs_one")))
        diffs = {s: abs(sharded[s] - one[s]) for s in range(1, steps + 1)}
        rec.update(losses=one, abs_diff_vs_sharded=diffs,
                   tolerance_abs=LOSS_TOL_SHARDED)
        if not max(diffs.values()) <= LOSS_TOL_SHARDED:
            raise AssertionError(f"sharded vs one chip: {diffs}")
    for tag in ("sharded", "one"):
        shutil.rmtree(os.path.join(out, f"ckpt_{tag}"))

    with phase("large_fsdp4_remat", clock) as rec:
        _, result = run_cli("train.py", train_args(
            out, "large", model=LARGE_MODEL, impl="xla", max_steps=2,
            seed=seed, validate_every=off, checkpoint_every=off,
            batch_size=4, extra=("--mesh", "1,4,1,1", "--strategies",
                                 "fsdp", "--remat")))
        large = losses_by_step(read_metrics(os.path.join(out, "runs_large")))
        if sorted(large) != [1, 2]:
            raise AssertionError(f"large: logged steps {sorted(large)}")
        assert_finite("large losses", list(large.values()))
        state = result["state"]
        rec.update(
            losses=large,
            params=sum(x.size for x in jax.tree.leaves(state.params)),
            params_share=spread_report("large params", state.params),
            opt_state_share=spread_report("large optimizer state",
                                          state.opt_state))
        del state, result


# --------------------------------------------------------------------- main


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-training path and what it is "
                         "compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, kernel inputs and sampling derive from it")
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                    help="the one directory this writes under (wiped first)")
    args = ap.parse_args()

    t0 = time.perf_counter()
    from progen_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache()
    clock = CompileClock()
    require_tpu()
    out = os.path.abspath(args.out)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    if args.chips == 4:
        four_chip_run(out, args.seed, clock)
    else:
        one_chip_run(out, args.seed, clock)

    print(json.dumps({"phases": [
        {k: p[k] for k in ("phase", "wall_s", "compile_s", "cache_hits",
                           "cache_misses", "peak_bytes_in_use")}
        for p in PHASES],
        "total_wall_s": round(time.perf_counter() - t0, 1)}), flush=True)
    print(device_line(), flush=True)


if __name__ == "__main__":
    main()
