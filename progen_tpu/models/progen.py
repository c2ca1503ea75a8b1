"""ProGen model core — flax linen, natively batched, sharding-annotated.

Behavior parity with the reference Haiku model
(``/root/reference/progen_transformer/progen.py``), re-designed TPU-first:

* natively batched ``(B, L) -> (B, L, num_tokens)`` (the reference is
  unbatched ``(L,)`` and relies on an outer ``vmap``, ``progen.py:224-233``;
  we keep its logits semantics, drop the shape contract);
* explicit precision policy (bf16 MXU compute / f32 params+output) instead
  of a class-wide jmp monkeypatch (``progen.py:235-241``);
* every parameter and key activation carries a LOGICAL axis name
  (t5x/maxtext convention) so DP/FSDP/TP/SP are pure rule tables over one
  mesh — see ``progen_tpu/parallel/sharding.py``;
* rotary tables are computed once per forward and shared by all layers
  (same as reference ``progen.py:227``).

Numerics contract implemented here (SURVEY.md §2.a):
scale-only LayerNorm (eps 1e-5, Haiku default); rotary on q, k AND v;
token-shift at the top of both blocks; windowed attention with
previous-window visibility; GEGLU feed-forward; the LAST
``global_mlp_depth`` layers swap GLU for the SGU/gMLP spatial gate; bare
residual adds; LN+Linear head, no weight tying.

The reference accepts dead kwargs ``clamp_gate``/``attn_dim``
(``progen.py:201-202`` — never used); ``ProGenConfig.from_dict`` accepts and
drops them for TOML/checkpoint config compatibility.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.ops.local_attention import local_attention
from progen_tpu.ops.quant import QuantDense
from progen_tpu.ops.rotary import apply_rotary_pos_emb, fixed_pos_embedding
from progen_tpu.ops.sgu import spatial_gate
from progen_tpu.ops.shift import shift_tokens


def _cp_active(mesh: Mesh | None, axis: str = "seq") -> bool:
    """True when the model should route sequence mixing through the explicit
    halo-exchange / all-gather context-parallel ops
    (``progen_tpu/parallel/context.py``) instead of the single-device ops."""
    return mesh is not None and mesh.shape.get(axis, 1) > 1

# kwargs the reference accepts but never reads (progen.py:201-202) plus
# driver-level kwargs that are not model architecture.
_IGNORED_CONFIG_KEYS = ("clamp_gate", "attn_dim", "mixed_precision")


@dataclasses.dataclass(frozen=True)
class ProGenConfig:
    num_tokens: int = 256
    dim: int = 512
    seq_len: int = 1024
    depth: int = 12
    window_size: int = 256
    global_mlp_depth: int = 2
    heads: int = 8
    dim_head: int = 64
    ff_mult: int = 4
    ff_glu: bool = True
    shift_tokens: bool = True

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ProGenConfig":
        clean = {k: v for k, v in d.items() if k not in _IGNORED_CONFIG_KEYS}
        return cls(**clean)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def layer_uses_gmlp(self, i: int) -> bool:
        """Layer i (0-based) uses the SGU/gMLP feed-forward iff it is among
        the last ``global_mlp_depth`` layers (reference ``progen.py:211``)."""
        return (self.depth - i) <= self.global_mlp_depth


def lora_delta(x, site, tenant):
    """Batched multi-tenant LoRA delta for one adapter site.

    ``x``: the dense layer's input ``(B, ..., Din)``; ``site``: stacked
    per-tenant factors ``{"a": (T, Din, r), "b": (T, r, Dout)}`` (any
    scale/alpha already folded into ``b`` by the converter); ``tenant``:
    ``(B,)`` int32 tenant ids.  Each batch row gathers ITS tenant's
    factors, so one decode step serves every tenant in the batch — the
    einsum contracts over the rank dim per row, no per-tenant dispatch.
    """
    a = jnp.take(site["a"], tenant, axis=0).astype(x.dtype)
    b = jnp.take(site["b"], tenant, axis=0).astype(x.dtype)
    h = jnp.einsum("b...d,bdr->b...r", x, a)
    return jnp.einsum("b...r,bro->b...o", h, b)


def apply_lora(base, x, site, tenant):
    """``base + lora_delta`` for rows with ``tenant > 0``; rows with
    tenant 0 return ``base`` BIT-identically.  The guard is a ``where`` on
    the output, not a zero delta: ``base + 0.0`` flips ``-0.0`` outputs to
    ``+0.0``, which would break the zero-adapter == base-model identity."""
    delta = lora_delta(x, site, tenant)
    live = (tenant > 0).reshape((-1,) + (1,) * (base.ndim - 1))
    return jnp.where(live, base + delta, base)


def _norm(policy: Policy, name: str | None = None) -> nn.LayerNorm:
    # Scale-only LayerNorm, eps matching Haiku's default (reference
    # ``progen.py:22``: create_scale=True, create_offset=False).
    return nn.LayerNorm(
        use_scale=True,
        use_bias=False,
        epsilon=1e-5,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
        name=name,
    )


def _dense(features: int, *, use_bias: bool, axes: tuple[str, str],
           policy: Policy, name: str | None = None,
           weights: str = "bf16") -> nn.Module:
    # weights="int8": the serving-only quantized path — an int8 kernel
    # with the SAME param names ("kernel"/"bias") and its f32 scale in a
    # parallel "qscale" collection (ops/quant.py).  "bf16" (the default)
    # is the unchanged full-precision layer.
    if weights == "int8":
        return QuantDense(features, use_bias=use_bias, axes=axes,
                          policy=policy, name=name)
    if weights != "bf16":
        raise ValueError(f"unknown weights mode {weights!r}; "
                         "use 'bf16' or 'int8'")
    bias_axes = (axes[-1],)
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), axes
        ),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, bias_axes),
        name=name,
    )


class LocalAttention(nn.Module):
    """Pre-LN windowed attention block (reference ``progen.py:50-103``).

    QKV fused into one bias-free projection (reference ``progen.py:70``),
    output projection with bias (``progen.py:71``).
    """

    dim: int
    window_size: int
    heads: int
    dim_head: int
    shift: bool
    policy: Policy
    attn_impl: str = "xla"  # "xla" | "pallas"
    mesh: Mesh | None = None  # seq axis >1 -> context-parallel halo path
    sow_caches: bool = True  # False: skip decode-carry sows (embeddings path)
    weights: str = "bf16"  # "int8": quantized projections (ops/quant.py)

    @nn.compact
    def __call__(self, x, sin, cos, adapters=None, tenant=None):
        b, n, _ = x.shape
        h, d = self.heads, self.dim_head
        inner = h * d

        with jax.named_scope("norm.layer"):
            x = _norm(self.policy, name="norm")(x)
        # post-norm PRE-shift activations: the decode token-shift carry
        # (harvested by decode/prefill.py when the "cache" collection is
        # mutable; a no-op otherwise, and skipped at init so the variable
        # tree stays params-only)
        if self.sow_caches and not self.is_initializing():
            self.sow("cache", "prev", x)
        with jax.named_scope("attn.project"):
            if self.shift:
                x = shift_tokens(x)

            qkv = _dense(inner * 3, use_bias=False, axes=("embed", "qkv"),
                         policy=self.policy, name="to_qkv",
                         weights=self.weights)(x)
            if adapters is not None:
                qkv = apply_lora(qkv, x, adapters["qkv"], tenant)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            # (B, L, H*D) -> (B, H, L, D)
            q, k, v = (
                t.reshape(b, n, h, d).transpose(0, 2, 1, 3)
                for t in (q, k, v)
            )
            # rotary on q, k AND v — reference progen.py:87
            q, k, v = (apply_rotary_pos_emb(t, sin, cos) for t in (q, k, v))
        # names for the 'attn' remat policy (save_only_these_names): the
        # post-rotary q/k/v feed the attention backward directly, so
        # saving them skips the norm->qkv->rotary replay
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        q = nn.with_logical_constraint(q, ("act_batch", "act_heads", "act_seq", None))
        k = nn.with_logical_constraint(k, ("act_batch", "act_heads", "act_seq", None))
        v = nn.with_logical_constraint(v, ("act_batch", "act_heads", "act_seq", None))
        # post-rotary k/v per position: exactly what the decode ring buffers
        # hold (decode/incremental.py) — prefill harvests these
        if self.sow_caches and not self.is_initializing():
            self.sow("cache", "k", k)
            self.sow("cache", "v", v)

        with jax.named_scope("attn.local"):
            if self.mesh is not None and self.attn_impl == "pallas":
                # pallas_call has no GSPMD rule — run it full-manual over the
                # mesh (halo exchange included); covers dp/fsdp/tp/sp meshes.
                from progen_tpu.parallel.context import (
                    sharded_pallas_local_attention,
                )

                out = sharded_pallas_local_attention(
                    q, k, v, mesh=self.mesh, window_size=self.window_size,
                    scale=d ** -0.5,
                )
            elif _cp_active(self.mesh):
                from progen_tpu.parallel.context import cp_local_attention

                out = cp_local_attention(
                    q, k, v, mesh=self.mesh, window_size=self.window_size,
                    scale=d ** -0.5,
                )
            elif self.attn_impl == "pallas":
                from progen_tpu.ops.pallas_attention import pallas_local_attention

                out = pallas_local_attention(q, k, v, self.window_size, d ** -0.5)
            elif self.attn_impl == "xla":
                out = local_attention(q, k, v, window_size=self.window_size,
                                      scale=d ** -0.5)
            else:
                raise ValueError(
                    f"unknown attn_impl {self.attn_impl!r}; use 'xla' or 'pallas'"
                )
            out = out.transpose(0, 2, 1, 3).reshape(b, n, inner)
        out = checkpoint_name(out, "attn_out")
        with jax.named_scope("attn.out"):
            y = _dense(self.dim, use_bias=True, axes=("qkv", "embed"),
                       policy=self.policy, name="to_out",
                       weights=self.weights)(out)
            if adapters is not None:
                y = apply_lora(y, out, adapters["out"], tenant)
        return y


class SGU(nn.Module):
    """gMLP spatial gating unit (reference ``progen.py:151-185``).

    Learned causal ``(n, n)`` token-mixing weights init U(±eps/n) with
    eps=1e-3, biases init to ones; gate half LayerNormed; output projected
    to ``dim_out = hidden // 2``.
    """

    seq_len: int
    dim_out: int
    policy: Policy
    eps: float = 1e-3
    sgu_impl: str = "xla"  # "xla" | "pallas" (blocked-causal fused kernel)
    mesh: Mesh | None = None  # seq axis >1 -> sharded spatial matmul
    sow_caches: bool = True
    weights: str = "bf16"  # "int8": quantized spatial weights + proj_out

    @nn.compact
    def __call__(self, x, adapters=None, tenant=None):
        n = self.seq_len
        with jax.named_scope("sgu.gate"):
            x, gate = jnp.split(x, 2, axis=-1)
            gate = _norm(self.policy, name="norm")(gate)
        # normed gate activations per position: the decode SGU gate cache
        # rows (decode/incremental.py SGUDecode) — prefill harvests these
        if self.sow_caches and not self.is_initializing():
            self.sow("cache", "gate", gate)

        init_scale = self.eps / n

        def symmetric_uniform(key, shape, dtype):
            return jax.random.uniform(
                key, shape, dtype, minval=-init_scale, maxval=init_scale
            )

        if self.weights == "int8":
            # int8 per-row spatial weights: same leaf name, re-typed; the
            # f32 row scale rides in "qscale" and is folded back here in
            # f32 (the mix contracts over COLUMNS, so one scale per row
            # is exact up to quantization rounding)
            weights_q = self.param(
                "spatial_weights",
                nn.with_logical_partitioning(
                    nn.initializers.zeros, ("spatial_row", "spatial_col")
                ),
                (n, n),
                jnp.int8,
            )
            w_scale = self.variable(
                "qscale", "spatial_weights_scale",
                lambda: jnp.ones((n,), jnp.float32)).value
            with jax.named_scope("sgu.spatial"):
                weights = weights_q.astype(jnp.float32) * w_scale[:, None]
        else:
            weights = self.param(
                "spatial_weights",
                nn.with_logical_partitioning(
                    symmetric_uniform, ("spatial_row", "spatial_col")
                ),
                (n, n),
                self.policy.param_dtype,
            )
        biases = self.param(
            "spatial_biases",
            nn.with_logical_partitioning(nn.initializers.ones, ("spatial_row", None)),
            (n, 1),
            self.policy.param_dtype,
        )

        if self.sgu_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown sgu_impl {self.sgu_impl!r}; use 'xla' or 'pallas'"
            )

        # inputs shorter than seq_len (one-pass prefill of a prime) use the
        # leading L rows/cols of the learned causal weights — exact, since
        # row m only ever reads columns <= m < L
        with jax.named_scope("sgu.spatial"):
            L = gate.shape[-2]
            if _cp_active(self.mesh):
                # cp_spatial_gate owns the op under sequence parallelism (the
                # all-gather + row-sharded matmul IS the sp decomposition);
                # sgu_impl="pallas" deliberately falls back here rather than
                # mis-sharding the blocked kernel across the seq axis.
                from progen_tpu.parallel.context import cp_spatial_gate

                if L != n:
                    raise ValueError(
                        f"context-parallel SGU requires the full seq_len {n}, "
                        f"got length {L}"
                    )
                gate = cp_spatial_gate(
                    gate,
                    weights.astype(self.policy.compute_dtype),
                    biases.astype(self.policy.compute_dtype),
                    mesh=self.mesh,
                )
                with jax.named_scope("sgu.gate"):
                    x = x * gate
            else:
                w = weights[:L, :L] if L != n else weights
                b = biases[:L] if L != n else biases
                w = w.astype(self.policy.compute_dtype)
                b = b.astype(self.policy.compute_dtype)
                if self.sgu_impl == "pallas" and self.mesh is not None:
                    # pallas_call has no GSPMD rule — run the fused kernel
                    # full-manual over the mesh (weights replicated per device)
                    from progen_tpu.parallel.context import (
                        sharded_pallas_spatial_gate,
                    )

                    x = sharded_pallas_spatial_gate(x, gate, w, b, mesh=self.mesh)
                elif self.sgu_impl == "pallas":
                    # fused res * (tril(W) @ gate + b): the mixed tensor never
                    # round-trips HBM and upper-triangle blocks are skipped
                    from progen_tpu.ops.pallas_sgu import pallas_spatial_gate

                    x = pallas_spatial_gate(x, gate, w, b)
                else:
                    gate = spatial_gate(gate, w, b)
                    with jax.named_scope("sgu.gate"):
                        x = x * gate
        with jax.named_scope("sgu.proj"):
            y = _dense(self.dim_out, use_bias=True, axes=("mlp_in", "mlp"),
                       policy=self.policy, name="proj_out",
                       weights=self.weights)(x)
            if adapters is not None:
                y = apply_lora(y, x, adapters, tenant)
        return y


class FeedForward(nn.Module):
    """Pre-LN MLP with GEGLU or SGU variant (reference ``progen.py:105-149``).

    ``glu`` and ``spatial_gate`` are mutually exclusive (``progen.py:118``);
    the hidden dim doubles under GLU so the gated half matches ``dim*ff_mult``.
    """

    dim: int
    seq_len: int
    ff_mult: int
    glu: bool
    use_sgu: bool
    shift: bool
    policy: Policy
    sgu_impl: str = "xla"
    mesh: Mesh | None = None
    sow_caches: bool = True
    weights: str = "bf16"  # "int8": quantized channel projections

    @nn.compact
    def __call__(self, x, adapters=None, tenant=None):
        assert not (self.glu and self.use_sgu)
        hidden = self.dim * self.ff_mult * (2 if self.glu else 1)

        with jax.named_scope("norm.layer"):
            x = _norm(self.policy, name="norm")(x)
        if self.sow_caches and not self.is_initializing():
            self.sow("cache", "prev", x)
        with jax.named_scope("ffn.dense"):
            if self.shift:
                x = shift_tokens(x)

            x = _dense(hidden, use_bias=True, axes=("embed", "mlp"),
                       policy=self.policy, name="proj_in",
                       weights=self.weights)(x)
            x = nn.with_logical_constraint(
                x, ("act_batch", "act_seq", "act_mlp"))

            if self.glu:
                x, gate = jnp.split(x, 2, axis=-1)
                x = x * nn.gelu(gate)
            else:
                x = nn.gelu(x)

        if self.use_sgu:
            x = SGU(seq_len=self.seq_len, dim_out=hidden // 2,
                    policy=self.policy, sgu_impl=self.sgu_impl,
                    mesh=self.mesh, sow_caches=self.sow_caches,
                    weights=self.weights, name="sgu")(
                        x,
                        None if adapters is None else adapters["sgu"],
                        tenant)

        with jax.named_scope("ffn.dense"):
            return _dense(self.dim, use_bias=True, axes=("mlp", "embed"),
                          policy=self.policy, name="proj_out",
                          weights=self.weights)(x)


class ProGen(nn.Module):
    """Full model: embed -> depth x [LocalAttention, FeedForward] -> head.

    ``remat=True`` rematerializes each block in the backward pass
    (``jax.checkpoint`` per layer) — trades ~30% more FLOPs for O(depth)
    less activation memory, the standard TPU HBM trade for the larger
    configs.  ``remat_policy`` refines the trade:

    * ``"full"`` (default) — save only block boundaries; recompute
      EVERYTHING in the backward, including the attention and all matmuls;
    * ``"dots"`` — ``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``:
      matmul outputs are saved, only the cheap elementwise/norm/softmax work
      is recomputed — most of full-remat's memory win at a fraction of its
      recompute FLOPs (the right setting when HBM is tight but not critical);
    * ``"attn"`` — save only the attention path (post-rotary q/k/v and the
      attention output, via ``checkpoint_name``/``save_only_these_names``):
      the backward replays the feed-forward matmuls but never the
      norm->qkv->rotary->windowed-attention chain.  Sits between ``full``
      (save 2 tensors/layer) and ``dots`` (save the fat ff hidden too):
      ~4x ``full``'s saved bytes, ~none of the attention recompute.
    """

    config: ProGenConfig
    policy: Policy = dataclasses.field(default_factory=make_policy)
    remat: bool = False
    remat_policy: str = "full"  # "full" | "dots"
    attn_impl: str = "xla"  # "xla" | "pallas" (TPU windowed flash kernel)
    sgu_impl: str = "xla"  # "xla" | "pallas" (blocked-causal fused SGU kernel)
    # With a mesh whose 'seq' axis is >1, sequence mixing (attention windows,
    # SGU spatial matmul) runs through the explicit context-parallel ops
    # (shard_map + ppermute/all_gather) instead of relying on GSPMD to invent
    # collectives for the window structure.
    mesh: Mesh | None = None
    # Embeddings-endpoint switch: sow ONLY the final post-norm hidden states
    # (collection "cache", name "final_hidden") and skip every per-layer
    # decode-carry sow, so the embed program materializes one (B, L, D)
    # tensor instead of full decode caches.  False (the default) is
    # byte-identical to the pre-switch model for all existing callers.
    sow_final_hidden: bool = False
    # "int8": serve quantized weights (ops/quant.py) — every block dense
    # and the SGU spatial weights re-typed int8 with f32 scales in the
    # "qscale" collection.  Embedding, norms and to_logits stay full
    # precision.  "bf16" (the default) is the unchanged model.
    weights: str = "bf16"

    @nn.compact
    def __call__(self, tokens, adapters=None, tenant=None):
        cfg = self.config
        if adapters is not None and tenant is None:
            raise ValueError("adapters require a (B,) tenant-id array")
        if tokens.ndim != 2:
            raise ValueError(
                f"ProGen takes batched (B, L) int tokens, got shape {tokens.shape}; "
                "the reference's unbatched (L,) contract was dropped — add a "
                "leading batch dim"
            )
        b, n = tokens.shape
        if cfg.global_mlp_depth > 0 and n > cfg.seq_len:
            raise ValueError(
                f"input length {n} > config.seq_len {cfg.seq_len}: the gMLP "
                "layers' learned (seq_len, seq_len) spatial weights have no "
                "rows past seq_len"
            )

        with jax.named_scope("embed.tokens"):
            x = nn.Embed(
                cfg.num_tokens,
                cfg.dim,
                dtype=self.policy.compute_dtype,
                param_dtype=self.policy.param_dtype,
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.variance_scaling(1.0, "fan_in", "normal", out_axis=0),
                    ("vocab", "embed"),
                ),
                name="embed",
            )(tokens)
        x = nn.with_logical_constraint(x, ("act_batch", "act_seq", "act_embed"))

        # rotary tables computed once, shared by all layers (progen.py:227);
        # kept f32, cast inside apply.
        with jax.named_scope("attn.rotary"):
            sin, cos = fixed_pos_embedding(n, cfg.dim_head)

        if self.remat:
            if self.remat_policy == "full":
                ckpt_policy = None
            elif self.remat_policy == "dots":
                ckpt_policy = (
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                )
            elif self.remat_policy == "attn":
                ckpt_policy = jax.checkpoint_policies.save_only_these_names(
                    "attn_q", "attn_k", "attn_v", "attn_out"
                )
            else:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    "use 'full', 'dots' or 'attn'"
                )
            attn_cls = nn.remat(LocalAttention, policy=ckpt_policy)
            ff_cls = nn.remat(FeedForward, policy=ckpt_policy)
        else:
            attn_cls = LocalAttention
            ff_cls = FeedForward

        sow_caches = not self.sow_final_hidden
        for i in range(cfg.depth):
            use_gmlp = cfg.layer_uses_gmlp(i)
            attn_ad = None if adapters is None else adapters.get(f"attn{i}")
            ff_ad = None if adapters is None else adapters.get(f"ff{i}")
            attn_out = attn_cls(
                dim=cfg.dim,
                window_size=cfg.window_size,
                heads=cfg.heads,
                dim_head=cfg.dim_head,
                shift=cfg.shift_tokens,
                policy=self.policy,
                attn_impl=self.attn_impl,
                mesh=self.mesh,
                sow_caches=sow_caches,
                weights=self.weights,
                name=f"attn{i}",
            )(x, sin, cos, attn_ad, tenant)
            with jax.named_scope("attn.out"):
                x = x + attn_out
            ff_out = ff_cls(
                dim=cfg.dim,
                seq_len=cfg.seq_len,
                ff_mult=cfg.ff_mult,
                glu=(not use_gmlp) and cfg.ff_glu,
                use_sgu=use_gmlp,
                shift=cfg.shift_tokens,
                policy=self.policy,
                sgu_impl=self.sgu_impl,
                mesh=self.mesh,
                sow_caches=sow_caches,
                weights=self.weights,
                name=f"ff{i}",
            )(x, ff_ad, tenant)
            with jax.named_scope("ffn.dense"):
                x = x + ff_out
            x = nn.with_logical_constraint(x, ("act_batch", "act_seq", "act_embed"))

        with jax.named_scope("head.logits"):
            x = _norm(self.policy, name="norm_out")(x)
            if self.sow_final_hidden and not self.is_initializing():
                self.sow("cache", "final_hidden", x)
            logits = _dense(cfg.num_tokens, use_bias=True,
                            axes=("embed", "vocab"),
                            policy=self.policy, name="to_logits")(x)
            return self.policy.cast_to_output(logits)
