"""One-pass parallel prefill: prime the decode caches with ONE forward.

The sampler historically teacher-forced the prime through O(P) sequential
single-token decode steps — P latency-bound dispatches of tiny matmuls.
Serving throughput on TPU is won by splitting prefill from decode (the
Ragged Paged Attention lesson, PAPERS.md): the prime is processed by the
existing batched PARALLEL ProGen forward ONCE — MXU-shaped matmuls over
all P positions — and the per-layer state the incremental decoder needs
is harvested from sown intermediates into the decode caches:

* **k/v rings** — the parallel forward sows post-rotary k/v ``(B, H, P,
  Dh)`` per layer (``models/progen.py``); ring slot ``s`` receives the
  LAST prime position congruent to ``s`` mod ``2w`` (exactly what a
  sequential scan would have left there), slots with no such position
  stay zero (the phantom zero-pad window before position 0);
* **token-shift carries** — each block sows its post-norm (pre-shift)
  activations; the carry is row ``P-1``;
* **SGU gate caches** — the gMLP layers sow the normed gate activations;
  rows ``[0, P)`` are copied in, later rows stay zero (they are written
  by decode before they are causally readable).

Ragged primes: ``lengths`` is a per-row vector, so one padded ``(B,
P_pad)`` prefill call harvests caches for rows of different prime
lengths — the continuous-batching engine admits a mixed batch of queued
requests in one forward.  Exactness vs the sequential path is asserted
by ``tests/test_serving.py`` (cache parity + logits parity against
``teacher_forced_logits``).

``P_pad`` must be a multiple of ``window_size`` (the parallel attention's
window layout) and ≤ ``seq_len``; right-padding with any token is safe —
causality keeps positions ``< lengths[b]`` independent of the pad tail,
and every harvested value is masked to real positions.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.models.progen import ProGen, ProGenConfig


def pad_prime_length(p: int, window_size: int, seq_len: int,
                     bucket: bool = False) -> int:
    """Padded prefill length for a ``p``-token prime.

    Always a multiple of ``window_size`` and capped at ``seq_len``.  With
    ``bucket=True`` the length additionally rounds up to ``window_size *
    2^k`` so the serving engine compiles O(log(seq_len/window)) prefill
    programs instead of one per distinct prime length.
    """
    if not (0 < p <= seq_len):
        raise ValueError(f"prime length {p} must be in (0, {seq_len}]")
    windows = -(-p // window_size)
    if bucket:
        b = 1
        while b < windows:
            b *= 2
        windows = b
    return min(windows * window_size, seq_len)


def prime_buckets(window_size: int, seq_len: int,
                  max_prime: int | None = None) -> list[int]:
    """Every bucketed prefill length a serving engine can dispatch:
    ``window_size * 2^k`` capped at ``seq_len``, for primes up to
    ``max_prime`` (default ``seq_len``).  This is the admission program
    grid an AOT warmup must compile — O(log(seq_len/window)) shapes.
    """
    cap = min(max_prime or seq_len, seq_len)
    out: list[int] = []
    p = 1
    while p <= cap:
        b = pad_prime_length(p, window_size, seq_len, bucket=True)
        if not out or b != out[-1]:
            out.append(b)
        if b >= cap:
            break
        p = b + 1
    return out


def mesh_trace_ctx(mesh: Mesh | None, strategies: Sequence[str]):
    """A factory of the context a model is TRACED under: the mesh and its
    logical-axis rules (both must be active while flax traces, the same
    pattern as ``train/step.py``'s ``apply_model``), or nothing without a
    mesh."""
    if mesh is None:
        return contextlib.ExitStack
    from progen_tpu.parallel.sharding import logical_rules

    rules = logical_rules(strategies)

    def trace_ctx():
        stack = contextlib.ExitStack()
        stack.enter_context(mesh)
        stack.enter_context(nn.logical_axis_rules(rules))
        return stack

    return trace_ctx


def _replicated_out(mesh: Mesh | None) -> dict:
    """``jax.jit`` keywords that replicate a program's outputs over the
    mesh."""
    if mesh is None:
        return {}
    return {"out_shardings": NamedSharding(mesh, PartitionSpec())}


def _constrain_caches(caches, mesh: Mesh | None, strategies: Sequence[str]):
    """Pin the decode caches' layouts over the mesh (none: as they are).

    Only tensor parallelism shards real decode state: the k/v rings split
    on heads and the SGU gate cache on its channel half, matching the tp
    rule table (``parallel/sharding.py``) so the per-step attention and
    gate contractions stay local to each tensor shard.  Everything else
    (tiny per-block carries) replicates — decode batches are small and
    fsdp's win is the PARAMS staying sharded, which they do via
    ``params_shardings``.
    """
    if (mesh is None or "tp" not in strategies
            or mesh.shape.get("tensor", 1) <= 1):
        return caches
    wsc = jax.lax.with_sharding_constraint
    kv = NamedSharding(mesh, PartitionSpec(None, "tensor", None, None))
    gate = NamedSharding(mesh, PartitionSpec(None, None, "tensor"))
    out = {
        **caches,
        "k": [wsc(x, kv) for x in caches["k"]],
        "v": [wsc(x, kv) for x in caches["v"]],
    }
    if caches.get("sgu_gate"):
        out["sgu_gate"] = {k: wsc(v, gate) for k, v in
                           caches["sgu_gate"].items()}
    if caches.get("sgu_pool"):
        # pooled gate rows shard on the channel half like the dense cache
        out["sgu_pool"] = {k: wsc(v, gate) for k, v in
                           caches["sgu_pool"].items()}
    return out


def _take_row(x, idx):
    """``x (B, L, ...)``, ``idx (B,)`` -> ``x[b, idx[b]] (B, ...)``."""
    return jax.vmap(lambda row, i: jax.lax.dynamic_index_in_dim(
        row, i, axis=0, keepdims=False))(x, idx)


def harvest_caches(config: ProGenConfig, sown: dict, lengths, policy: Policy,
                   decode_len: int) -> dict:
    """Build decode caches from the parallel forward's sown "cache"
    collection, per-row masked to ``lengths``.  The gate rows are dense
    ``(B, n_rows, half)`` slabs whatever the engine's cache layout: a
    paged engine scatters them into its page pool when it merges them
    (:func:`scatter_gate_rows`).
    """
    c = config
    pol = policy
    ring = 2 * c.window_size
    n_rows = min(decode_len, c.seq_len)
    last = lengths - 1  # (B,)

    caches = {"attn_prev": [], "ff_prev": [], "k": [], "v": [], "sgu_gate": {}}
    for i in range(c.depth):
        attn = sown[f"attn{i}"]
        k_all = attn["k"][0]   # (B, H, P_pad, Dh) post-rotary
        v_all = attn["v"][0]
        prev_a = attn["prev"][0]  # (B, P_pad, dim) post-norm
        ff = sown[f"ff{i}"]
        prev_f = ff["prev"][0]

        caches["attn_prev"].append(_take_row(prev_a, last))
        caches["ff_prev"].append(_take_row(prev_f, last))

        # ring slot s <- last prime position congruent to s (mod ring);
        # no such position (short primes) -> the slot stays zero, the
        # phantom zero-pad window the sequential path also leaves there
        s = jnp.arange(ring)[None, :]
        q_s = last[:, None] - jnp.mod(last[:, None] - s, ring)  # (B, ring)
        live = q_s >= 0
        idx = jnp.clip(q_s, 0)[:, None, :, None]  # (B, 1, ring, 1)
        k_ring = jnp.take_along_axis(k_all, idx, axis=2)
        v_ring = jnp.take_along_axis(v_all, idx, axis=2)
        m = live[:, None, :, None]
        caches["k"].append(jnp.where(m, k_ring, 0).astype(pol.compute_dtype))
        caches["v"].append(jnp.where(m, v_ring, 0).astype(pol.compute_dtype))

        if c.layer_uses_gmlp(i):
            gate = ff["sgu"]["gate"][0]  # (B, P_pad, hidden/2) normed
            b, p_pad, half = gate.shape
            rows = jnp.zeros((b, n_rows, half), pol.compute_dtype)
            upto = min(p_pad, n_rows)
            keep = (jnp.arange(upto)[None, :, None] < lengths[:, None, None])
            rows = rows.at[:, :upto].set(
                jnp.where(keep, gate[:, :upto], 0).astype(pol.compute_dtype))
            caches["sgu_gate"][str(i)] = rows
    return caches


def scatter_gate_rows(config: ProGenConfig, gate_rows: dict, lengths,
                      pool: dict, wtable, pool_scale: dict | None = None):
    """Scatter DENSE per-row gate slabs into the page pool.

    How a paged engine admits, inline and disaggregated alike: the
    prefill hands over ``(B, n_rows, half)`` gate slabs per gMLP layer
    (keyed ``str(i)`` like the dense cache), and the merge scatters each
    handle row ``i < lengths[b]`` to page ``wtable[b, i // page_size]`` at
    offset ``i % page_size``.  ``wtable`` is the WRITE table: it names the
    request's freshly allocated private pages and holds ``DUMP_PAGE`` for
    what must not be written — prefix-cache hits (read-only, filled by
    the first request that computed them), unadmitted handle rows and
    unowned tail entries.  Pad rows (``i >= lengths[b]``) are dumped too,
    so the scatter stays dense.

    Handle slabs come in the COMPUTE dtype regardless of the pool's
    format (a prefill worker cannot know the decode pool's page layout);
    with ``pool_scale`` (the f32 twin of an int8 pool, see
    ``init_gate_scale``) the rows are quantized per row here, at the
    merge, and the call returns ``(new_pool, new_scale)``.
    """
    from progen_tpu.decode.paging import DUMP_PAGE
    from progen_tpu.ops.quant import quantize_rows

    new_pool = dict(pool)
    new_scale = dict(pool_scale) if pool_scale is not None else None
    for i in range(config.depth):
        if not config.layer_uses_gmlp(i):
            continue
        gate = gate_rows[str(i)]  # (B, n_rows, half)
        b, n_rows, half = gate.shape
        layer_pool = pool[str(i)]  # (num_pages, page_size, half)
        page_size = layer_pool.shape[1]
        pages_per_row = wtable.shape[1]
        rows = jnp.arange(n_rows)
        # the slab can overshoot the table span; clamp the page index —
        # every overshooting row is >= lengths and dumped
        page_idx = jnp.minimum(rows // page_size, pages_per_row - 1)
        tgt = wtable[:, page_idx]  # (B, n_rows)
        tgt = jnp.where(rows[None, :] < lengths[:, None], tgt, DUMP_PAGE)
        off = jnp.broadcast_to((rows % page_size)[None, :], (b, n_rows))
        at = (tgt.reshape(-1), off.reshape(-1))
        if new_scale is not None:
            gate, scale = quantize_rows(gate)   # (B, n_rows, half) int8
            new_scale[str(i)] = pool_scale[str(i)].at[at].set(
                scale.reshape(-1))
        new_pool[str(i)] = layer_pool.at[at].set(
            gate.astype(layer_pool.dtype).reshape(-1, half))
    if new_scale is not None:
        return new_pool, new_scale
    return new_pool


def make_embedder(config: ProGenConfig, policy: Policy | None = None,
                  mesh: Mesh | None = None,
                  strategies: Sequence[str] = ("dp",),
                  weights: str = "bf16"):
    """Build ``embed(params, tokens, lengths) -> (B, dim) f32``: the
    embeddings-endpoint program.

    Reuses the one-pass prefill forward with ``sow_final_hidden=True`` —
    the model sows ONLY the final post-norm hidden states (no per-layer
    decode carries are materialized; the unused logits head is dead code
    XLA eliminates) — then mean-pools over each row's real positions
    (``< lengths[b]``; the window-aligned pad tail never contributes).
    Same ragged ``(B, P_pad)`` + ``lengths`` contract as
    :func:`make_prefiller`, so the serving engine warms one embed program
    per prime bucket.
    """
    policy = policy or make_policy()
    model = ProGen(config=config, policy=policy, mesh=None,
                   sow_final_hidden=True, weights=weights)

    trace_ctx = mesh_trace_ctx(mesh, strategies)

    @partial(jax.jit, **_replicated_out(mesh))
    def embed(params, tokens, lengths):
        b, p_pad = tokens.shape
        if p_pad % config.window_size != 0 or p_pad > config.seq_len:
            raise ValueError(
                f"padded prime length {p_pad} must be a multiple of "
                f"window_size {config.window_size} and <= seq_len "
                f"{config.seq_len}"
            )
        lengths = jnp.asarray(lengths, jnp.int32)
        with trace_ctx():
            _, varz = model.apply(params, tokens, mutable=["cache"])
        h = varz["cache"]["final_hidden"][0].astype(jnp.float32)
        keep = (jnp.arange(p_pad)[None, :] < lengths[:, None])
        pooled = jnp.sum(h * keep[:, :, None].astype(jnp.float32), axis=1)
        return pooled / jnp.maximum(lengths, 1).astype(jnp.float32)[:, None]

    return embed


def make_prefiller(config: ProGenConfig, policy: Policy | None = None,
                   mesh: Mesh | None = None,
                   strategies: Sequence[str] = ("dp",),
                   weights: str = "bf16"):
    """Build ``prefill(params, tokens, lengths, decode_len)``.

    ``tokens``: ``(B, P_pad)`` int prime tokens, right-padded; ``P_pad``
    must be a multiple of ``window_size`` and ≤ ``seq_len`` (see
    :func:`pad_prime_length`).  ``lengths``: ``(B,)`` actual prime lengths
    (1 ≤ length ≤ P_pad), may differ per row.  ``decode_len``: static —
    positions the subsequent decode will visit (sizes the SGU caches,
    matching ``init_caches(..., decode_len=...)``).

    Returns ``(last_logits (B, V) f32, caches)``: the logits at each
    row's LAST prime position (sample the first new token from these) and
    decode caches identical to sequentially teacher-forcing the prime.
    """
    policy = policy or make_policy()
    model = ProGen(config=config, policy=policy, mesh=None, weights=weights)

    trace_ctx = mesh_trace_ctx(mesh, strategies)

    @partial(jax.jit, static_argnames=("decode_len",),
             **_replicated_out(mesh))
    def prefill(params, tokens, lengths, decode_len):
        b, p_pad = tokens.shape
        if p_pad % config.window_size != 0 or p_pad > config.seq_len:
            raise ValueError(
                f"padded prime length {p_pad} must be a multiple of "
                f"window_size {config.window_size} and <= seq_len "
                f"{config.seq_len}"
            )
        lengths = jnp.asarray(lengths, jnp.int32)
        with trace_ctx():
            logits, varz = model.apply(params, tokens, mutable=["cache"])
            caches = harvest_caches(config, varz["cache"], lengths, policy,
                                    decode_len)
            caches = _constrain_caches(caches, mesh, strategies)
        last_logits = _take_row(logits, lengths - 1).astype(jnp.float32)
        return last_logits, caches

    return prefill
