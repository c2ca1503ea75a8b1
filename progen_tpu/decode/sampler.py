"""Autoregressive sampler: one ``lax.scan`` over positions, cached decode.

Capability parity with the reference sampler (``/root/reference/
progen_transformer/utils.py:97-135`` and call sites ``train.py:219-228``,
``sample.py:64-73``): prime teacher-forcing, optional prepended BOS, top-k
gumbel-max sampling, truncation after the second zero (position 0's
BOS/pad counts as the first).  Structural differences, both conscious:

* the reference runs a host-driven Python loop of FULL forwards (O(L) model
  applies over the whole padded sequence); this is a single jitted scan of
  cached single-token steps — same trajectory semantics, O(L·window)
  attention instead of O(L²·window);
* the reference zeroes non-top-k logits and multiplies the gumbel noise by
  the mask (``utils.py:97-100,121-123``), which can leak a masked token
  when every top-k entry is negative; here masked entries are ``-inf``
  (standard top-k gumbel-max).  Temperature generalizes the reference's
  implicit temperature=1 (pass ``temperature=0`` for greedy).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from progen_tpu.core.precision import Policy, make_policy
from progen_tpu.decode.incremental import ProGenDecodeStep, init_caches
from progen_tpu.decode.prefill import (
    _constrain_caches,
    _replicated_out,
    make_prefiller,
    mesh_trace_ctx,
    pad_prime_length,
)
from progen_tpu.models.progen import ProGenConfig
from progen_tpu.ops.kth import kth_largest_by_counting


def apply_logit_mask(logits, mask):
    """The one ``-inf`` masking idiom: keep ``logits`` where ``mask`` is
    true, ``-inf`` elsewhere.  Both the top-k cut and the infilling
    alphabet constraints route through here, so "never emits a masked
    token" is a property of a single expression.  An all-true mask
    returns ``logits`` bit-identically (``jnp.where`` selects, never
    recomputes)."""
    return jnp.where(mask, logits, -jnp.inf)


def gumbel_topk_sample(key, logits, top_k: int | None, temperature: float = 1.0,
                       mask=None):
    """Sample token ids ``(B,)`` from logits ``(B, V)``.

    Runs in f32 regardless of the logits dtype: bf16 logits under a tiny
    temperature overflow to inf (and the ``-inf`` top-k mask then yields
    ``inf - inf = NaN`` rows), so the division, masking and gumbel noise
    all happen after an f32 cast.

    ``mask`` (optional, broadcastable to ``logits``, bool): tokens with a
    false entry can never be emitted — applied before the greedy branch so
    ``temperature=0`` respects it too.  Masked entries survive the top-k
    cut as ``-inf`` (``-inf >= kth`` only when ``kth`` is itself ``-inf``,
    which keeps them ``-inf``), so top-k and constraints compose.
    """
    with jax.named_scope("sample.draw"):
        logits = logits.astype(jnp.float32)
        if mask is not None:
            logits = apply_logit_mask(logits, mask)
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = apply_logit_mask(logits, logits >= kth)
        noise = jax.random.gumbel(key, logits.shape, dtype=logits.dtype)
        return jnp.argmax(logits + noise, axis=-1)


def gumbel_topk_sample_batched(keys, logits, top_k, temperature, mask=None):
    """Per-row sampling for the serving engine: each row has its own key,
    top-k and temperature.

    ``keys``: ``(B,)`` typed PRNG keys; ``logits``: ``(B, V)``; ``top_k``:
    ``(B,)`` int32, ``0`` disables top-k for that row; ``temperature``:
    ``(B,)`` f32, ``0.0`` means greedy for that row.

    ``top_k`` is an array, so ``lax.top_k`` (whose k is static) cannot cut
    the rows.  What the cut needs of a row is ONE number, its k-th largest
    scaled logit.  Sorting every row to read one element of it was, on a
    sliced or whole chat vocabulary (16,384 to 100,352 columns), the largest
    single operation of a decode step after the model's own, so the number
    is found by counting (``ops/kth.py:kth_largest_by_counting``), at every
    width: on ProGen's 256 columns the 32 rounds cost a few microseconds
    more than the tiny sort did and no cell can tell (PERF.md §6, PR 39).
    While the rows of a draw fit on the chip together (up to 32 x 100,352)
    the rounds run from the chip's memory by themselves; a block step's
    256 x 151,936 do not, and there the same rounds run over one group of
    rows at a time, so that HBM is read once and not 32 times (PERF.md §6,
    PR 43) — the same algorithm and the same value either way.  The value
    is the one a full ascending sort of the row hands out at ``[v - k]``,
    so the mask, and under the same keys the tokens, are that form's:

    * ties at the k-th value all survive the cut (``>=``), so a row may
      keep more than ``k`` entries, exactly as many as under the sort;
    * ``-inf`` entries (the ``mask``, or a row's own) are ordinary lowest
      values; where more than ``V - k`` of a row are ``-inf`` the k-th is
      ``-inf``, the cut keeps everything and ``-inf`` still loses every
      argmax;
    * ``-0.0`` and ``+0.0`` compare equal in the cut whichever of the two
      is handed out as the k-th (a sort hands out the one its stable order
      left there, the counting ``-0.0`` unless ``k`` falls among the
      ``+0.0`` s: the only case in which the two values differ in a bit);
    * a NaN logit ranks above ``+inf``, as in ``jnp.sort``, whatever its
      sign bit.  With fewer than ``k`` NaNs in a row they take places among
      the ``k`` but fail the ``>=`` and are cut; with ``k`` or more the
      k-th is NaN, every comparison fails, the whole row is cut and the
      draw returns token 0.  A row's NaNs are the model's fault and are not
      repaired here.

    ``mask`` (optional ``(B, V)`` bool): per-row allowed-token constraint,
    applied before the greedy argmax so greedy rows respect it too.  A
    ``-inf``-masked entry divides to ``-inf``, survives the per-row k cut
    as ``-inf`` and loses every argmax, so constraints compose with
    per-row top-k exactly as in :func:`gumbel_topk_sample`.
    """
    return _cut_and_draw(keys, logits, top_k, temperature, mask)[0]


def _cut_and_draw(keys, logits, top_k, temperature, mask):
    """:func:`gumbel_topk_sample_batched`'s draw, and what it drew from:
    ``(tokens (B,), the float32 logits under the mask (B, V), the kept
    entries (B, V) bool)``."""
    with jax.named_scope("sample.draw"):
        logits = logits.astype(jnp.float32)
        if mask is not None:
            logits = apply_logit_mask(logits, mask)
        v = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1)
        scaled = logits / jnp.maximum(temperature, 1e-8)[:, None]
        k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
        kth = kth_largest_by_counting(scaled, k_eff, "sample_kth")
        kept = scaled >= kth
        masked = apply_logit_mask(scaled, kept)
        noise = jax.vmap(
            lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
        sampled = jnp.argmax(masked + noise, axis=-1)
        return jnp.where(temperature == 0.0, greedy, sampled), logits, kept


def gumbel_topk_sample_with_confidence(keys, logits, top_k, temperature,
                                       mask=None):
    """:func:`gumbel_topk_sample_batched`'s draw — the same tokens, bit for
    bit, under the same keys — and each drawn token's CONFIDENCE: its
    probability under the distribution it was drawn from, the softmax over
    the entries the mask and the top-k cut kept of ``logits /
    temperature`` (float32).  A greedy row (``temperature == 0``) takes the
    argmax and reads its probability at temperature 1: at temperature 0
    every draw would be certain and no position of a block more confident
    than another.  ``(tokens (B,) int, confidence (B,) float32)``; what a
    block-diffusion step keeps of a draw is decided from the second
    (:func:`confident_positions`)."""
    with jax.named_scope("sample.confidence"):
        tokens, logits, kept = _cut_and_draw(keys, logits, top_k,
                                             temperature, mask)
        scale = jnp.where(temperature == 0.0, 1.0,
                          jnp.maximum(temperature, 1e-8))[:, None]
        scaled = apply_logit_mask(logits / scale, kept)
        top = jnp.max(scaled, axis=-1, keepdims=True)
        drawn = jnp.take_along_axis(scaled, tokens[:, None], axis=-1)
        total = jnp.sum(jnp.exp(scaled - top), axis=-1)
        return tokens, jnp.exp(drawn - top)[:, 0] / total


def transfer_counts(block_length: int, steps: int):
    """Positions the STATIC rule fills at each of ``steps`` denoise
    forwards of a block, as a tuple: ``block_length // steps`` each, the
    first ``block_length % steps`` forwards one more."""
    base, extra = divmod(block_length, steps)
    return tuple(base + (i < extra) for i in range(steps))


def confident_positions(confidence, masked, count, threshold=None):
    """Which masked positions of each block take their draw: ``confidence
    (S, B)`` float32, ``masked (S, B)`` bool, ``count (S,)`` the static
    rule's number for the row's denoise step -> ``(S, B)`` bool.

    *static* (``threshold`` None): the ``count`` masked positions of highest
    confidence, ties to the lower index; a row with fewer masked positions
    (a first block that holds prompt tokens) takes them all.  *dynamic*:
    every masked position whose confidence is OVER ``threshold``, and at
    least the static rule's.  A position that holds a token already is
    never taken."""
    conf = jnp.where(masked, confidence, -jnp.inf)
    # rank 0 = the most confident; a stable descending order by comparison
    # (B is a handful: no sort)
    at = jnp.arange(conf.shape[-1])
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)
    take = rank < count[:, None]
    if threshold is not None:
        take = take | (conf > threshold)
    return take & masked


def split_keys_batched(key_data):
    """Advance a batch of raw uint32 key data one split: returns
    ``(next_key_data, subkeys)``.  The serving engine's per-slot key
    chains live as RAW key data (``jax.random.key_data``) so they can
    ride through jitted state dicts; every consumer of the chain (the
    decode chunk bodies) derives subkeys this one way."""
    keys = jax.random.wrap_key_data(key_data)
    split = jax.vmap(jax.random.split)(keys)  # (B, 2) keys
    return jax.random.key_data(split[:, 0]), split[:, 1]


def truncate_after_eos(seq, pad_id: int = 0):
    """Zero everything after the SECOND zero (reference ``utils.py:131-134``:
    the BOS/pad at position 0 is the first; the next zero is the learned
    EOS, which is kept)."""
    after = jnp.cumsum(seq == pad_id, axis=-1) > 1
    return seq * (~after)


# _constrain_caches moved to decode/prefill.py (shared by the prefill
# harvest, the chunked sampler and the serving engine); re-exported here
# for back-compat.


def make_sampler(config: ProGenConfig, policy: Policy | None = None,
                 mesh: Mesh | None = None,
                 strategies: Sequence[str] = ("dp",),
                 params_shardings=None):
    """Build ``sample(params, key, prime, length, ...)``.

    ``prime``: ``(B, P)`` int tokens (already encoded).  ``length`` must be
    ≤ ``config.seq_len`` (the learned (seq_len, seq_len) gMLP weights have
    no rows past that — true of the reference too).  Short decodes are
    cheap: every cache and the scan are sized to ``length``, not seq_len.
    Returns ``(B, length)`` sequences, EOS-truncated.

    Mesh-aware decode (BASELINE.md's XL row is "fully-sharded params +
    generation"): pass ``mesh`` (+ ``strategies`` and the params'
    ``params_shardings``, e.g. ``TrainFunctions.state_shardings.params``)
    and the decode runs as one SPMD program — params STAY in their
    training shardings (never gathered to one chip), tp shards the per-
    step contractions and caches, and the sampled tokens come out
    replicated so every host can fetch them.
    """
    policy = policy or make_policy()
    step_model = ProGenDecodeStep(config=config, policy=policy)

    trace_ctx = mesh_trace_ctx(mesh, strategies)
    # params shardings are applied via an explicit device_put in the
    # wrapper below (a no-op when the caller's params already live
    # there) — jit's in_shardings would reject the static kwargs
    jit_kwargs = _replicated_out(mesh)

    @partial(jax.jit, static_argnames=("length", "top_k", "add_bos", "temperature"),
             **jit_kwargs)
    def sample(params, key, prime, length, top_k=None, add_bos=False,
               temperature=1.0):
        if prime.ndim != 2:
            raise ValueError(f"prime must be (B, P), got {prime.shape}")
        b, p = prime.shape
        if add_bos:
            prime = jnp.concatenate(
                [jnp.zeros((b, 1), prime.dtype), prime[:, : length - 1]], axis=1
            )
            p = min(p + 1, length)
        start_pos = p
        if not (0 < start_pos <= length <= config.seq_len):
            raise ValueError(
                f"need 0 < prime length {start_pos} <= length {length} <= "
                f"seq_len {config.seq_len}"
            )

        seq = jnp.zeros((b, length), jnp.int32)
        seq = jax.lax.dynamic_update_slice(seq, prime.astype(jnp.int32), (0, 0))

        with trace_ctx():
            caches = init_caches(config, b, policy, decode_len=length)
            caches = _constrain_caches(caches, mesh, strategies)

            def body(carry, pos):
                seq, caches, key = carry
                tok = jax.lax.dynamic_index_in_dim(seq, pos, axis=1,
                                                   keepdims=False)
                logits, caches = step_model.apply(params, tok, pos, caches)
                key, sub = jax.random.split(key)
                nxt = gumbel_topk_sample(sub, logits.astype(jnp.float32), top_k,
                                         temperature).astype(jnp.int32)
                write = (pos + 1 >= start_pos) & (pos + 1 < length)
                cur = jax.lax.dynamic_index_in_dim(
                    seq, jnp.minimum(pos + 1, length - 1), axis=1,
                    keepdims=False)
                val = jnp.where(write, nxt, cur)
                seq = jax.lax.dynamic_update_index_in_dim(
                    seq, val, jnp.minimum(pos + 1, length - 1), axis=1
                )
                return (seq, caches, key), None

            (seq, _, _), _ = jax.lax.scan(
                body, (seq, caches, key), jnp.arange(length)
            )
        return truncate_after_eos(seq)

    if params_shardings is None:
        return sample

    def sharded_sample(params, key, prime, length, top_k=None, add_bos=False,
                       temperature=1.0):
        params = jax.device_put(params, {"params": params_shardings})
        return sample(params, key, prime, length, top_k=top_k,
                      add_bos=add_bos, temperature=temperature)

    sharded_sample.lower = sample.lower  # AOT warm-compile hook
    return sharded_sample


def make_chunked_sampler(config: ProGenConfig, policy: Policy | None = None,
                         mesh: Mesh | None = None,
                         strategies: Sequence[str] = ("dp",),
                         params_shardings=None, chunk_size: int = 64):
    """Build the serving-grade sampler: one-pass prefill + early-exit
    chunked decode.  Same signature and trajectory semantics as
    :func:`make_sampler` — same key ⇒ same sampled tokens — but:

    * the prime is processed by ONE batched parallel forward
      (``decode/prefill.py``) instead of P sequential decode steps;
    * decode runs in fixed-size chunks (static shapes — exactly one
      compiled chunk program, position passed dynamically); between
      chunks the HOST checks a per-row done-mask and stops as soon as
      every row has emitted EOS, so cost tracks emitted tokens, not
      ``length``.

    The done bookkeeping mirrors ``truncate_after_eos``: a row is done
    once it holds two zeros (BOS/pad + learned EOS); later steps for that
    row write pad.  The returned function exposes ``last_num_chunks``
    (chunks executed by the most recent call) for tests/benchmarks.
    """
    policy = policy or make_policy()
    step_model = ProGenDecodeStep(config=config, policy=policy)
    prefiller = make_prefiller(config, policy, mesh=mesh, strategies=strategies)

    trace_ctx = mesh_trace_ctx(mesh, strategies)

    @partial(jax.jit,
             static_argnames=("length", "start_pos", "top_k", "temperature"))
    def start_state(key, prime, last_logits, length, start_pos, top_k,
                    temperature, first_mask=None):
        b = prime.shape[0]
        seq = jnp.zeros((b, length), jnp.int32)
        seq = jax.lax.dynamic_update_slice(seq, prime.astype(jnp.int32), (0, 0))
        # burn the key splits the sequential sampler spends on the prime
        # positions so the trajectory is bit-identical to make_sampler
        if start_pos > 1:
            def burn(k, _):
                return jax.random.split(k)[0], None
            key, _ = jax.lax.scan(burn, key, None, length=start_pos - 1)
        key, sub = jax.random.split(key)
        first = gumbel_topk_sample(sub, last_logits, top_k,
                                   temperature, mask=first_mask).astype(
                                       jnp.int32)
        zcount = jnp.sum(prime == 0, axis=1).astype(jnp.int32)
        if start_pos < length:
            val = jnp.where(zcount > 1, 0, first)
            seq = seq.at[:, start_pos].set(val)
            zcount = zcount + (val == 0)
        return seq, key, zcount

    @partial(jax.jit,
             static_argnames=("length", "start_pos", "top_k", "temperature"))
    def decode_chunk(params, seq, caches, key, zcount, pos0, length,
                     start_pos, top_k, temperature, logit_mask=None):
        with trace_ctx():
            caches = _constrain_caches(caches, mesh, strategies)

            def body(carry, i):
                seq, caches, key, zcount = carry
                pos = jnp.minimum(pos0 + i, length - 1)
                tok = jax.lax.dynamic_index_in_dim(seq, pos, axis=1,
                                                   keepdims=False)
                logits, caches = step_model.apply(params, tok, pos, caches)
                key, sub = jax.random.split(key)
                raw = pos0 + i + 1
                write = (raw >= start_pos) & (raw < length)
                idx = jnp.minimum(raw, length - 1)
                # the mask row for the position being WRITTEN (absolute
                # index), same gather the serving engine does per slot
                mrow = None
                if logit_mask is not None:
                    mrow = jax.lax.dynamic_index_in_dim(
                        logit_mask, idx, axis=1, keepdims=False)
                nxt = gumbel_topk_sample(sub, logits, top_k,
                                         temperature, mask=mrow).astype(
                                             jnp.int32)
                val = jnp.where(zcount > 1, 0, nxt)
                cur = jax.lax.dynamic_index_in_dim(seq, idx, axis=1,
                                                   keepdims=False)
                out = jnp.where(write, val, cur)
                seq = jax.lax.dynamic_update_index_in_dim(seq, out, idx,
                                                          axis=1)
                zcount = zcount + jnp.where(write, (out == 0).astype(
                    jnp.int32), 0)
                return (seq, caches, key, zcount), None

            (seq, caches, key, zcount), _ = jax.lax.scan(
                body, (seq, caches, key, zcount), jnp.arange(chunk_size))
        return seq, caches, key, zcount, jnp.all(zcount > 1)

    def sample(params, key, prime, length, top_k=None, add_bos=False,
               temperature=1.0, logit_mask=None):
        if prime.ndim != 2:
            raise ValueError(f"prime must be (B, P), got {prime.shape}")
        if params_shardings is not None:
            params = jax.device_put(params, {"params": params_shardings})
        b, p = prime.shape
        prime = jnp.asarray(prime, jnp.int32)
        if add_bos:
            prime = jnp.concatenate(
                [jnp.zeros((b, 1), prime.dtype), prime[:, : length - 1]],
                axis=1)
            p = min(p + 1, length)
        start_pos = p
        if not (0 < start_pos <= length <= config.seq_len):
            raise ValueError(
                f"need 0 < prime length {start_pos} <= length {length} <= "
                f"seq_len {config.seq_len}"
            )
        if logit_mask is not None:
            logit_mask = jnp.asarray(logit_mask, bool)
            if logit_mask.shape != (b, length, config.num_tokens):
                raise ValueError(
                    f"logit_mask must be (B={b}, length={length}, "
                    f"V={config.num_tokens}), got {logit_mask.shape}"
                )

        p_pad = pad_prime_length(start_pos, config.window_size, config.seq_len)
        tokens = jnp.pad(prime, ((0, 0), (0, p_pad - start_pos)))
        lengths = jnp.full((b,), start_pos, jnp.int32)
        last_logits, caches = prefiller(params, tokens, lengths,
                                        decode_len=length)
        first_mask = None
        if logit_mask is not None and start_pos < length:
            first_mask = logit_mask[:, start_pos]
        seq, key, zcount = start_state(
            key, prime, last_logits, length, start_pos, top_k, temperature,
            first_mask)

        n_chunks = 0
        pos = start_pos
        while pos < length:
            seq, caches, key, zcount, done = decode_chunk(
                params, seq, caches, key, zcount, pos, length, start_pos,
                top_k, temperature, logit_mask)
            n_chunks += 1
            pos += chunk_size
            if bool(done):
                break
        sample.last_num_chunks = n_chunks
        return truncate_after_eos(seq)

    sample.last_num_chunks = 0
    sample.chunk_size = chunk_size
    return sample


def teacher_forced_logits(config: ProGenConfig, params, tokens,
                          policy: Policy | None = None):
    """Run the cached decode step over a FIXED token sequence and return all
    logits ``(B, L, V)`` — the decode-vs-parallel parity oracle (tests) and
    a scoring utility."""
    policy = policy or make_policy()
    step_model = ProGenDecodeStep(config=config, policy=policy)
    b, n = tokens.shape
    caches = init_caches(config, b, policy, decode_len=n)

    def body(caches, pos):
        tok = jax.lax.dynamic_index_in_dim(tokens, pos, axis=1, keepdims=False)
        logits, caches = step_model.apply(params, tok, pos, caches)
        return caches, logits

    _, logits = jax.lax.scan(body, caches, jnp.arange(n))
    return jnp.transpose(logits, (1, 0, 2))
