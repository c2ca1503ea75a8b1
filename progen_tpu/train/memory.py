"""Per-chip HBM planner for the training step.

The reference never had to think about memory (single GPU, toy config);
at this framework's target scales the first question is "does this
(config, mesh, strategies, remat, batch) fit the chip?", and the answer
used to be "compile it and see" (``benchmarks/configs.md`` records the
measured OOM boundaries).  This module predicts the answer analytically.

The peak model (calibrated against XLA's ``compiled.memory_analysis()``
on a v5e across six configurations, all within ~2% — see
``tools/memory_check.py`` and ``benchmarks/memory_plan.md``):

* **resident state** — f32 params + Adam moments (= the jit ARGUMENTS,
  12 bytes/param, +4 with a MultiSteps grad accumulator), divided by the
  axes that shard them (fsdp, tensor).  Gradients do NOT plateau: with
  donated buffers XLA streams each grad into its param/moment update, so
  4 bytes/param of grads never shows up in the measured peak;
* **activation plateau** — an explicit enumeration of the tensors kept
  live between forward and backward for THIS model's blocks (windowed
  attention + GEGLU / SGU feed-forward) per remat policy, times a
  measured scheduling efficiency (XLA's own rematerializer trims the
  naive set: x0.82 no-remat, x0.91 dots, x1.0 full);
* the peak temp is ``max(activation plateau, bf16 param-cast set)`` —
  when remat shrinks activations below the bf16 weight copies (2
  bytes/param), the casts become the floor (measured at large/batch-1) —
  plus the f32 logits+softmax pair.

``Trainer`` calls :func:`check_fits` to fail fast with the predicted
breakdown and actionable knobs instead of a 20-minute compile ending in
RESOURCE_EXHAUSTED.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

GiB = 1024**3


# XLA scheduling efficiency on the naive saved-tensor enumeration,
# fitted to v5e memory_analysis measurements (benchmarks/memory_plan.md)
ACT_EFFICIENCY = {"none": 0.82, "dots": 0.91, "full": 1.0, "attn": 1.0}

# device kinds the peak model was actually validated on (8 calibration
# points incl. the OOM boundaries, benchmarks/memory_plan.md); on other
# generations XLA's scheduler may assign buffers differently, so the fit
# gate must not hard-block runs it has never been checked against
CALIBRATED_DEVICE_KINDS = frozenset({"TPU v5e", "TPU v5 lite"})


@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    """Predicted per-chip HBM for one training-step configuration."""

    params_bytes: int
    moments_bytes: int
    accumulator_bytes: int
    activation_bytes: int
    cast_bytes: int
    logits_bytes: int
    num_params: int
    detail: dict
    snapshot_bytes: int = 0
    superbatch_bytes: int = 0

    @property
    def state_bytes(self) -> int:
        return self.params_bytes + self.moments_bytes + self.accumulator_bytes

    @property
    def temp_bytes(self) -> int:
        return max(self.activation_bytes, self.cast_bytes) + self.logits_bytes

    @property
    def total_bytes(self) -> int:
        return (self.state_bytes + self.temp_bytes + self.snapshot_bytes
                + self.superbatch_bytes)

    def report(self) -> str:
        rows = [
            ("params (f32)", self.params_bytes),
            ("adam moments (f32)", self.moments_bytes),
            ("grad accumulator (f32)", self.accumulator_bytes),
            ("activation plateau", self.activation_bytes),
            ("bf16 param casts", self.cast_bytes),
            ("f32 logits + softmax bwd", self.logits_bytes),
            ("background-checkpoint snapshot", self.snapshot_bytes),
            ("staged superbatches (int32)", self.superbatch_bytes),
            ("peak = state + max(act, cast) + logits + snapshot + stage",
             self.total_bytes),
        ]
        out = "\n".join(f"  {name:<48} {b / GiB:7.2f} GiB"
                        for name, b in rows)
        axes = self.detail.get("axis_shards")
        if axes:
            # per-axis pricing: which mesh axis pays for which shard —
            # on a process-spanning mesh this is the row that says "your
            # weights are split fsdp x tensor WAYS, across THESE axes"
            for kind, shards in axes.items():
                spec = " x ".join(f"{a}={v}" for a, v in shards.items())
                ways = 1
                for v in shards.values():
                    ways *= v
                out += f"\n  {kind + ' sharded over':<48} {spec} ({ways}x)"
        return out


def count_params(cfg) -> int:
    """Exact parameter count of the flax model (closed form; matches
    ``jax.eval_shape`` — asserted in tests)."""
    d, inner = cfg.dim, cfg.heads * cfg.dim_head
    n = cfg.num_tokens * d  # embed
    for i in range(cfg.depth):
        gmlp = cfg.layer_uses_gmlp(i)
        # attention: norm scale, qkv (no bias), out (+bias)
        n += d + d * 3 * inner + inner * d + d
        hidden = d * cfg.ff_mult * (1 if gmlp or not cfg.ff_glu else 2)
        # ff: norm scale, proj_in (+bias)
        n += d + d * hidden + hidden
        if gmlp:
            half = (d * cfg.ff_mult) // 2
            # sgu: norm scale, spatial weights/biases, proj_out (+bias)
            n += half + cfg.seq_len * cfg.seq_len + cfg.seq_len
            n += half * half + half
            n += half * d + d  # ff proj_out from half
        else:
            n += (hidden // (2 if cfg.ff_glu else 1)) * d + d  # ff proj_out
    n += d + d * cfg.num_tokens + cfg.num_tokens  # head norm + linear
    return n


def _layer_saved_bytes(cfg, tokens: int, policy: str, attn_impl: str,
                       gmlp: bool, act: int, tensor: int = 1,
                       sgu_impl: str = "xla") -> int:
    """Bytes of forward tensors kept for the backward of ONE layer
    (attention block + feed-forward block), per remat policy.

    ``act`` is the activation element size (2 for bf16 compute).
    ``tensor``: megatron tp degree — the qkv/hidden/heads activations are
    column-sharded over it; the residual-stream (dim-wide) tensors
    replicate.
    """
    d = cfg.dim
    inner = cfg.heads * cfg.dim_head // tensor
    t = tokens
    hidden = d * cfg.ff_mult * (1 if gmlp or not cfg.ff_glu else 2) // tensor
    half = (d * cfg.ff_mult) // 2 // tensor

    # residual-stream block inputs are always live (checkpoint args)
    saved = 2 * t * d * act

    if policy == "full":
        # jax.checkpoint(block): nothing else saved; backward recomputes
        return saved

    if policy == "attn":
        # save_only_these_names: post-rotary q/k/v + attention output
        return saved + 4 * t * inner * act

    # matmul ("dot") outputs, saved by the dots policy and by no-remat
    saved += t * 3 * inner * act          # qkv projection
    saved += t * d * act                  # attention out projection
    saved += t * hidden * act             # ff proj_in
    saved += t * d * act                  # ff proj_out
    if gmlp:
        if sgu_impl != "pallas":
            # the fused pallas kernel's VJP keeps only its inputs (already
            # counted below/as block args) and recomputes mixed blockwise —
            # the (t, half) mixed tensor never exists outside VMEM
            saved += t * half * act       # sgu spatial matmul output
        saved += t * half * act           # sgu proj_out
    if policy == "dots":
        return saved

    # no remat: every intermediate XLA keeps live
    saved += 2 * t * d * act              # the two LayerNorm outputs
    saved += 3 * t * inner * act          # post-rotary q, k, v
    if attn_impl == "pallas":
        # flash-style backward recomputes probs from q/k/v; keeps out+lse
        saved += t * inner * act + t * (cfg.heads // tensor) * 4
    else:
        saved += t * (cfg.heads // tensor) * 2 * cfg.window_size * act  # probs
        saved += t * inner * act          # attention output
    if gmlp:
        saved += t * half * act           # gelu output (gate half)
        saved += t * half * act           # normed gate
        saved += t * half * act           # x * gate
    else:
        saved += t * (hidden // (2 if cfg.ff_glu else 1)) * act  # (ge)glu out
    return saved


def plan(
    cfg,
    *,
    batch_size: int,
    mesh_shape: dict | None = None,
    strategies: Sequence[str] = ("dp",),
    remat: bool = False,
    remat_policy: str = "full",
    attn_impl: str = "pallas",
    sgu_impl: str = "xla",
    mixed_precision: bool = True,
    grad_accum_every: int = 1,
    checkpoint_snapshot: bool = False,
    superstep_k: int = 1,
) -> MemoryPlan:
    """Predict per-chip HBM for one jitted train step.

    ``batch_size`` is the GLOBAL micro-batch fed to ``train_step``;
    ``mesh_shape`` like ``{"data": 1, "fsdp": 8, "tensor": 1, "seq": 1}``
    (None = single chip).  ``superstep_k > 1`` adds the fused loop's
    staged ``(K, accum, B, L)`` superbatch buffers — two live at steady
    state, the one being scanned plus the next one in async transfer.
    """
    mesh_shape = mesh_shape or {}
    data = mesh_shape.get("data", 1)
    fsdp = mesh_shape.get("fsdp", 1)
    tensor = mesh_shape.get("tensor", 1) if "tp" in strategies else 1
    seq = mesh_shape.get("seq", 1) if "sp" in strategies else 1

    n = count_params(cfg)
    # fsdp shards every matrix param; tp shards qkv/mlp matrices.  Model
    # both as dividing the full count (norm scales that replicate are
    # O(depth*dim), noise at these scales).
    state_shard = (fsdp if "fsdp" in strategies else 1) * tensor
    params_b = 4 * n // state_shard
    moments_b = 8 * n // state_shard
    accum_b = (4 * n // state_shard) if grad_accum_every > 1 else 0

    act = 2 if mixed_precision else 4
    # per-chip tokens: batch sharded over (data, fsdp), sequence over seq
    tokens = batch_size * cfg.seq_len // (data * max(fsdp, 1) * seq)

    policy = remat_policy if remat else "none"
    act_b = 0
    peak_layer = 0
    for i in range(cfg.depth):
        gmlp = cfg.layer_uses_gmlp(i)
        act_b += _layer_saved_bytes(cfg, tokens, policy, attn_impl, gmlp, act,
                                    tensor, sgu_impl)
        peak_layer = max(
            peak_layer,
            _layer_saved_bytes(cfg, tokens, "none", attn_impl, gmlp, act,
                               tensor, sgu_impl),
        )
    if policy in ("full", "attn"):
        # the backward replays one block at a time: its full live set
        # rides on top of the saved block inputs
        act_b += peak_layer
    act_b = int(act_b * ACT_EFFICIENCY[policy])

    cast_b = (2 * n // state_shard) if mixed_precision else 0
    # f32 logits + softmax backward copy
    logits_b = 2 * tokens * cfg.num_tokens * 4

    detail = {
        "tokens_per_chip": tokens,
        "state_shard_ways": state_shard,
        "remat": policy,
        "attn_impl": attn_impl,
        "sgu_impl": sgu_impl,
        # per-axis shard pricing (report() renders these as plan rows):
        # weights divide over (fsdp, tensor); batch tokens over
        # (data, fsdp, seq); the tp-sharded activations (heads/mlp)
        # additionally divide over tensor (_layer_saved_bytes)
        "axis_shards": {
            "weights": {
                "fsdp": fsdp if "fsdp" in strategies else 1,
                "tensor": tensor,
            },
            "activations": {
                "data": data,
                "fsdp": max(fsdp, 1),
                "seq": seq,
                "tensor": tensor,
            },
        },
    }
    # Trainer's background checkpointing keeps one extra on-device copy of
    # the full state while the save's device->host fetch runs
    snapshot_b = (params_b + moments_b + accum_b) if checkpoint_snapshot else 0

    # fused superstep staging: the (K, accum, B, L+1) int32 superbatch
    # being scanned (donated, but alive until the scan consumes it) plus
    # the next one already streaming in; batch dim sharded like the batch
    superbatch_b = 0
    if superstep_k > 1:
        rows = batch_size // (data * max(fsdp, 1))
        superbatch_b = (2 * superstep_k * max(1, grad_accum_every) * rows
                        * (cfg.seq_len + 1) * 4)
        detail["superstep_k"] = superstep_k

    return MemoryPlan(
        params_bytes=params_b,
        moments_bytes=moments_b,
        accumulator_bytes=accum_b,
        activation_bytes=act_b,
        cast_bytes=cast_b,
        logits_bytes=logits_b,
        num_params=n,
        detail=detail,
        snapshot_bytes=snapshot_b,
        superbatch_bytes=superbatch_b,
    )


def device_hbm_bytes(device=None) -> int | None:
    """Usable HBM of the local accelerator, or None when unknown.

    Defaults to ``jax.local_devices()[0]``: in a multi-process run
    ``jax.devices()[0]`` is the globally-first device, which is
    non-addressable on every host but process 0 — ``memory_stats()`` would
    raise there and the fit gate would silently pass on those hosts while
    process 0 alone raised, leaving the fleet hung in collective init
    instead of failing together."""
    import jax

    device = device or jax.local_devices()[0]
    if device.platform != "tpu":
        return None
    try:
        stats = device.memory_stats()
        return int(stats["bytes_limit"])
    except Exception:
        return None


def check_fits(plan_: MemoryPlan, hbm_bytes: int | None,
               headroom: float = 0.02,
               device_kind: str | None = None) -> str | None:
    """None when the plan fits; otherwise a multi-line error message with
    the breakdown and the knobs most likely to make it fit.

    When ``device_kind`` is given and is NOT in
    :data:`CALIBRATED_DEVICE_KINDS`, an over-budget prediction degrades to
    a warning instead of an error: the peak model has only been validated
    against v5e buffer assignment, and hard-blocking a run on an
    uncalibrated generation would turn a model-fit question into a bad
    first-run experience on new hardware."""
    if hbm_bytes is None:
        return None
    budget = hbm_bytes * (1 - headroom)
    if plan_.total_bytes <= budget:
        return None
    if device_kind is not None and device_kind not in CALIBRATED_DEVICE_KINDS:
        import warnings

        warnings.warn(
            f"memory plan predicts {plan_.total_bytes / GiB:.2f} GiB > "
            f"{hbm_bytes / GiB:.2f} GiB HBM, but the planner is calibrated "
            f"only on {sorted(CALIBRATED_DEVICE_KINDS)} "
            f"(benchmarks/memory_plan.md), not {device_kind!r} — "
            "proceeding; if the compile ends in RESOURCE_EXHAUSTED, apply "
            "the plan's suggestions or set PROGEN_SKIP_MEMORY_CHECK=1",
            RuntimeWarning,
            stacklevel=2,
        )
        return None
    suggestions = []
    if (plan_.snapshot_bytes
            and plan_.total_bytes - plan_.snapshot_bytes <= budget):
        suggestions.append(
            "disable background checkpointing (--no_background_checkpoint): "
            "its on-device state snapshot is what does not fit"
        )
    if plan_.activation_bytes > plan_.cast_bytes:
        # escalation order measured in benchmarks/configs.md: 'attn' keeps
        # the most throughput per byte saved; 'full' saves the most bytes
        if plan_.detail["remat"] == "none":
            suggestions.append("enable remat (--remat; policy 'attn' first)")
        elif plan_.detail["remat"] == "dots":
            suggestions.append(
                "try --remat_policy attn (slimmer saved set) or full")
        elif plan_.detail["remat"] == "attn":
            suggestions.append("use --remat_policy full (recompute more)")
        suggestions.append("reduce --batch_size (activations scale with it)")
    if plan_.state_bytes > 0.7 * budget:
        # the f32 state is the blocker: it must shrink to leave room for
        # the step's working set -> shard it harder
        total_state = plan_.state_bytes * plan_.detail["state_shard_ways"]
        ways = max(2, -(-total_state // int(budget * 0.6)))
        suggestions.append(
            f"the f32 optimizer state dominates HBM: shard it (fsdp={ways} "
            "in --mesh, with 'fsdp' in --strategies)"
        )
    return (
        f"predicted per-chip HBM {plan_.total_bytes / GiB:.2f} GiB exceeds "
        f"the chip's {hbm_bytes / GiB:.2f} GiB (planner calibrated on "
        f"{sorted(CALIBRATED_DEVICE_KINDS)}, benchmarks/memory_plan.md; "
        "PROGEN_SKIP_MEMORY_CHECK=1 overrides):\n"
        f"{plan_.report()}\n"
        "try: " + "; ".join(suggestions or ["a bigger mesh"])
    )


# --------------------------------------------------------------- serving side


@dataclasses.dataclass(frozen=True)
class ServingMemoryPlan:
    """Predicted HBM for the ServingEngine's per-request decode state.

    The pageable resource in this architecture is the SGU gate cache —
    the one buffer that scales with ``max_len`` per slot (the attention
    k/v ring is a fixed O(2·window) and the carries are O(dim)).  The
    fixed-slot engine allocates ``gate_bytes_per_slot`` for every slot up
    front; paged mode replaces ``num_slots * gate_bytes_per_slot`` with
    ``pool_bytes`` (+ a tiny int32 page table), so the paged-vs-dense
    comparison at equal budget is ``pool_bytes`` vs
    ``num_slots * gate_bytes_per_slot``.
    """

    ring_bytes_per_slot: int
    carry_bytes_per_slot: int
    seq_bytes_per_slot: int
    gate_bytes_per_slot: int  # dense mode only (0 when paged)
    pool_bytes: int           # paged mode only (0 when dense)
    table_bytes: int
    num_slots: int
    # disaggregated serving: the bounded handoff queue can hold up to
    # ``handoff_depth`` full (num_slots, ...)-shaped handles in flight
    handoff_bytes: int = 0
    # constrained infilling: the slot-resident (max_len, vocab) bool logit
    # mask — allocated for every slot regardless of workload mix, since the
    # engine keeps the mask in state unconditionally (all-pass when unused)
    lmask_bytes_per_slot: int = 0
    # multi-tenant LoRA: the stacked (T, din, r)/(T, r, dout) adapter bank,
    # one copy shared by all slots
    adapter_bytes: int = 0
    # resident weight bytes, both sides of the quantization decision:
    # the f32 serving tree as-is, and the int8 re-typing (kernels 1 B +
    # f32 per-channel scales; embed/norms/biases/logit head stay f32).
    # Informational — NOT part of total_bytes, which has always counted
    # only per-request decode state.
    weight_bytes_full: int = 0
    weight_bytes_int8: int = 0

    @property
    def fixed_bytes_per_slot(self) -> int:
        return (self.ring_bytes_per_slot + self.carry_bytes_per_slot
                + self.seq_bytes_per_slot + self.lmask_bytes_per_slot)

    @property
    def pageable_bytes(self) -> int:
        """The budgeted resource: dense per-slot gate slabs or the pool."""
        return self.num_slots * self.gate_bytes_per_slot + self.pool_bytes

    @property
    def total_bytes(self) -> int:
        return (self.num_slots * (self.fixed_bytes_per_slot
                                  + self.gate_bytes_per_slot)
                + self.pool_bytes + self.table_bytes
                + self.handoff_bytes + self.adapter_bytes)


def gate_row_bytes(cfg, mixed_precision: bool = True,
                   gate_dtype: str = "bf16") -> int:
    """Bytes of ONE token row of SGU gate state across all gMLP layers —
    the per-token unit both the dense slab and the page pool are made of.

    ``gate_dtype="int8"`` prices the 8-bit page format: 1 byte per
    channel plus one f32 absmax scale per (row, layer) — ~2x smaller than
    bf16 for any non-trivial ``half``."""
    gmlp_layers = sum(1 for i in range(cfg.depth) if cfg.layer_uses_gmlp(i))
    half = (cfg.dim * cfg.ff_mult) // 2
    if gate_dtype == "int8":
        return gmlp_layers * (half + 4)
    if gate_dtype != "bf16":
        raise ValueError(f"gate_dtype {gate_dtype!r}: want 'bf16' or 'int8'")
    act = 2 if mixed_precision else 4
    return gmlp_layers * half * act


def weight_hbm_bytes(cfg, *, quantize: bool = False) -> int:
    """Resident weight bytes for a serving replica: the f32 tree as-is,
    or the int8 re-typing under ``quantize`` — dense kernels and the SGU
    spatial weights drop to 1 byte/element plus f32 per-channel (per-row
    for spatial) scales; embed, norms, biases and the logit head stay
    full precision, the same skip set as ``ops/quant.quantize_params``."""
    if not quantize:
        return count_params(cfg) * 4
    d, inner = cfg.dim, cfg.heads * cfg.dim_head
    n = cfg.num_tokens * d * 4  # embed stays f32
    for i in range(cfg.depth):
        gmlp = cfg.layer_uses_gmlp(i)
        hidden = d * cfg.ff_mult * (1 if gmlp or not cfg.ff_glu else 2)
        # attention: norm f32; qkv + out kernels int8 with f32 scales
        n += d * 4
        n += d * 3 * inner + 3 * inner * 4
        n += inner * d + d * 4 + d * 4  # out kernel + scale + bias
        # ff: norm f32; proj_in int8 + scale, f32 bias
        n += d * 4
        n += d * hidden + hidden * 4 + hidden * 4
        if gmlp:
            half = (d * cfg.ff_mult) // 2
            L = cfg.seq_len
            n += half * 4  # sgu norm
            n += L * L + L * 4 + L * 4  # spatial int8 + row scale + bias
            n += half * half + half * 4 + half * 4  # sgu proj_out
            n += half * d + d * 4 + d * 4  # ff proj_out from half
        else:
            dout = hidden // (2 if cfg.ff_glu else 1)
            n += dout * d + d * 4 + d * 4  # ff proj_out
    n += d * 4 + d * cfg.num_tokens * 4 + cfg.num_tokens * 4  # logit head
    return n


def serving_plan(cfg, *, num_slots: int, max_len: int | None = None,
                 mixed_precision: bool = True, paged: bool = False,
                 page_size: int = 16, num_pages: int | None = None,
                 disagg: bool = False,
                 handoff_depth: int = 2, lora_tenants: int = 0,
                 lora_rank: int = 0,
                 gate_dtype: str = "bf16") -> ServingMemoryPlan:
    """HBM accounting for a ServingEngine configuration (dense or paged).

    Mirrors ``decode/engine.py``'s state layout: k/v rings + carries +
    seq per slot always; per-slot ``(max_len, half)`` gate slabs in dense
    mode, the global ``(num_pages, page_size, half)`` pool (per gMLP
    layer) in paged mode.  ``num_pages`` defaults like the engine's
    (full budget: every slot can reach ``max_len``).

    ``disagg`` adds the handoff queue's worst case: ``handoff_depth``
    handles, each a full ``(num_slots, ...)`` state copy with dense gate
    slabs (even in paged mode — the worker hands off dense rows and the
    merge scatters them into the pool).

    The per-slot ``(max_len, vocab)`` bool logit mask (constrained
    infilling) is counted unconditionally — the engine allocates it for
    every configuration.  ``lora_tenants``/``lora_rank`` add the stacked
    adapter bank (one copy, all slots share it).

    ``gate_dtype="int8"`` prices 8-bit gate pages: the POOL shrinks ~2x
    while dense slabs and handoff slabs stay in compute
    dtype (quantization happens at the page-pool boundary).  Requires
    ``paged=True``, mirroring the engine."""
    act = 2 if mixed_precision else 4
    L = min(max_len or cfg.seq_len, cfg.seq_len)
    ring = 2 * cfg.window_size
    ring_b = cfg.depth * 2 * cfg.heads * ring * cfg.dim_head * act
    carry_b = cfg.depth * 2 * cfg.dim * act
    seq_b = L * 4
    lmask_b = L * cfg.num_tokens  # bool, 1 byte per (position, vocab) cell
    if gate_dtype != "bf16" and not paged:
        raise ValueError("gate_dtype='int8' requires paged=True — the "
                         "8-bit gate format is a page format")
    row_b = gate_row_bytes(cfg, mixed_precision)
    pages_per_row = -(-L // page_size)
    if paged:
        if num_pages is None:
            num_pages = 2 + num_slots * pages_per_row
        pool_b = num_pages * page_size * gate_row_bytes(
            cfg, mixed_precision, gate_dtype=gate_dtype)
        gate_b = 0
        table_b = num_slots * pages_per_row * 4
    else:
        pool_b = 0
        gate_b = L * row_b
        table_b = 0
    handoff_b = 0
    if disagg:
        # a handle row always carries the DENSE gate slab and the logit
        # mask; ~40 B of per-row scalars (pos/start/stop/done/keys/knobs)
        # ride along
        per_row = ring_b + carry_b + seq_b + lmask_b + L * row_b + 40
        handoff_b = handoff_depth * num_slots * per_row
    adapter_b = 0
    if lora_tenants:
        from progen_tpu.workloads.lora import adapter_bank_bytes
        adapter_b = adapter_bank_bytes(cfg, lora_tenants, lora_rank)
    return ServingMemoryPlan(
        ring_bytes_per_slot=ring_b,
        carry_bytes_per_slot=carry_b,
        seq_bytes_per_slot=seq_b,
        gate_bytes_per_slot=gate_b,
        pool_bytes=pool_b,
        table_bytes=table_b,
        num_slots=num_slots,
        handoff_bytes=handoff_b,
        lmask_bytes_per_slot=lmask_b,
        adapter_bytes=adapter_b,
        weight_bytes_full=weight_hbm_bytes(cfg),
        weight_bytes_int8=weight_hbm_bytes(cfg, quantize=True),
    )


def equal_budget_pages(cfg, *, dense_slots: int, max_len: int,
                       page_size: int = 16,
                       gate_dtype: str = "bf16") -> int:
    """Pool size (total pages, incl. the 2 reserved) whose gate-row bytes
    match what ``dense_slots`` fixed slots would pin: the equal-modeled-
    HBM-budget comparison from the serving benchmark.  At ``bf16`` the
    row byte size cancels and this is just ``dense_slots * max_len``
    token rows worth of pages; at ``int8`` the same byte budget buys
    ~2x the pages (dense slabs are always bf16 — that is the point of
    the comparison)."""
    budget = dense_slots * max_len * gate_row_bytes(cfg)
    pool_row = gate_row_bytes(cfg, gate_dtype=gate_dtype)
    return max(3, budget // (page_size * pool_row))
