"""Speculative decoding: draft-propose / target-verify / draft-commit.

One speculative ROUND replaces up to ``k + 1`` sequential target-model
dispatches with three fused scans inside a single device program:

1. **draft propose** — a tiny draft ProGen (``models/configs
   .draft_config_for``) runs ``k`` cached single-token steps on a
   THROWAWAY copy of its caches and proposes ``d_1..d_k``.  Each
   proposal is sampled with the SAME subkey the target would consume for
   that step (the per-slot key chain is re-derived, not committed), so
   sampled requests accept exactly when draft and target sampling agree
   bit-for-bit — determinism never depends on the draft;
2. **target verify** — ``k + 1`` target steps over ``(tok, d_1, ..,
   d_k)`` reuse the chunked-sampler machinery from the engine's chunk
   body (live-masked scan with early exit): step ``j`` samples ``s_j``
   from the TRUE target logits with the slot's authoritative key chain
   and emits it iff the slot is still live; the slot stays live for step
   ``j + 1`` iff ``s_j == d_{j+1}`` and the stop rule did not fire.  All
   cache/ring/carry writes merge under the live mask, so a rejected
   step's writes roll back for free — the ``j = k`` step is the bonus
   token a fully-accepted round gets on top;
3. **draft commit** — the draft's REAL caches re-consume the verified
   inputs under the recorded live masks, so draft and target state stay
   position-aligned for the next round.

Because every emitted token is sampled from the target's own logits with
the target's own key chain, the output is TOKEN-IDENTICAL to non-
speculative decoding — greedy and sampled alike, for ANY draft.  The
draft only decides how many of the ``k + 1`` verify steps are usable
(``accepted-tokens/round``).  :func:`spec_acceptance` is the pure
acceptance rule, unit-testable against a hand-computed oracle.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from progen_tpu.decode.sampler import (
    gumbel_topk_sample_batched,
    split_keys_batched,
)
from progen_tpu.models.progen import ProGenConfig
from progen_tpu.ops.row_write import write_rows


def check_draft_config(target: ProGenConfig, draft: ProGenConfig) -> None:
    """The draft must agree with the target on everything that gives
    tokens and positions their meaning; capacity knobs are free."""
    for field in ("num_tokens", "window_size", "seq_len"):
        t, d = getattr(target, field), getattr(draft, field)
        if t != d:
            raise ValueError(
                f"draft config {field}={d} != target {field}={t}: the "
                f"draft proposes tokens in the target's vocabulary at "
                f"the target's positions (see draft_config_for)")


def spec_acceptance(sampled, proposed, done):
    """Pure acceptance rule for one speculative round.

    ``sampled (.., k+1)``: the target's verified tokens ``s_0..s_k``;
    ``proposed (.., k)``: the draft's ``d_1..d_k`` (``proposed[j]`` is
    the guess for ``sampled[j]``); ``done (.., k+1)``: whether step
    ``j``'s stop rule fired (EOS or length).  Returns ``(live, emitted)``
    where ``live[.., j]`` says step ``j``'s token was emitted and
    ``emitted`` counts them: step 0 is always live (for a live slot);
    step ``j + 1`` is live iff step ``j`` was, matched its proposal, and
    did not finish.  The final step never has a proposal to match — it is
    the bonus token of a fully-accepted round.
    """
    sampled = np.asarray(sampled)
    proposed = np.asarray(proposed)
    done = np.asarray(done)
    k1 = sampled.shape[-1]
    if proposed.shape[-1] != k1 - 1 or done.shape[-1] != k1:
        raise ValueError("want sampled (.., k+1), proposed (.., k), "
                         "done (.., k+1)")
    live = np.ones(sampled.shape[:-1], bool)
    lives = []
    for j in range(k1):
        lives.append(live)
        match = (sampled[..., j] == proposed[..., j]) if j < k1 - 1 \
            else np.zeros_like(live)
        live = live & match & ~done[..., j]
    live_mat = np.stack(lives, axis=-1)
    return live_mat, live_mat.sum(axis=-1)


def spec_round(state: dict, *, spec_k: int, max_len: int, eos_id: int,
               target_step: Callable, draft_step: Callable,
               merge_caches: Callable, live0) -> tuple[dict, jnp.ndarray]:
    """One speculative round over the engine's slot state (traced inside
    the spec decode-chunk program).

    ``target_step(tok, pos, caches, live) -> (logits, caches)`` and
    ``draft_step(tok, pos, draft_caches) -> (logits, draft_caches)`` are
    the engine's step closures (``live`` feeds the paged pool's
    ``write_ok``); ``merge_caches(live, new, old)`` is the engine's
    live-mask cache merge (ring keys only in paged mode — pool writes
    are already masked inside the step).  ``live0`` is the slots allowed
    to advance this round (active, not done, not paused).

    Returns ``(state, emitted)`` with ``emitted (S,)`` the tokens each
    slot produced (0 for slots dead at round start, up to ``spec_k + 1``
    for a fully-accepted round).
    """
    s = state["pos"].shape[0]
    pos0 = state["pos"]
    tok0 = jnp.take_along_axis(state["seq"], pos0[:, None], axis=1)[:, 0]
    # infilling logit masks ride the slot state as (S, max_len, V) rows
    # indexed by WRITE position; absent for direct callers (None = all-pass)
    lmask = state.get("lmask")

    def mask_rows(writepos):
        if lmask is None:
            return None
        return jnp.take_along_axis(
            lmask, writepos[:, None, None], axis=1)[:, 0]

    # -- draft propose: throwaway cache copy, re-derived key chain.  The
    # chain advances unconditionally (dead slots' proposals are garbage
    # and never consumed); positions clamp so a slot racing past its stop
    # mid-round cannot index past the gMLP weight rows.  The mask row for
    # the position the TARGET would write this step applies to the draft
    # sample too — draft and target see identical constrained logits, so
    # acceptance (and therefore token-identity) is preserved under masks.
    def propose_body(carry, _):
        dc, kd, tok, dpos = carry
        logits, dc = draft_step(tok, dpos, dc)
        kd, sub = split_keys_batched(kd)
        d = gumbel_topk_sample_batched(
            sub, logits, state["top_k"], state["temp"],
            mask=mask_rows(jnp.clip(dpos + 1, 0, max_len - 1))).astype(
                jnp.int32)
        return (dc, kd, d, jnp.minimum(dpos + 1, max_len - 1)), d

    (_, _, _, _), proposed = jax.lax.scan(
        propose_body, (state["draft_caches"], state["keys"], tok0, pos0),
        None, length=spec_k)  # proposed[j] (S,) = d_{j+1}, guess for s_j

    # -- target verify: k+1 live-masked steps; input j is the current
    # token at j=0, the draft's d_j after; guess j is d_{j+1} (none for
    # the final bonus step, so it always ends the round)
    inputs = jnp.concatenate([tok0[None], proposed], axis=0)
    guesses = jnp.concatenate(
        [proposed, jnp.full((1, s), -1, jnp.int32)], axis=0)
    verify_state = {k: v for k, v in state.items() if k != "draft_caches"}

    def verify_body(carry, xs):
        st, live = carry
        inp, guess = xs
        pos = st["pos"]
        logits, caches = target_step(inp, pos, st["caches"], live)
        caches = merge_caches(live, caches, st["caches"])
        kd, sub = split_keys_batched(st["keys"])
        writepos = jnp.clip(pos + 1, 0, max_len - 1)
        nxt = gumbel_topk_sample_batched(
            sub, logits, st["top_k"], st["temp"],
            mask=mask_rows(writepos)).astype(jnp.int32)
        cur = jnp.take_along_axis(st["seq"], writepos[:, None],
                                  axis=1)[:, 0]
        val = jnp.where(live, nxt, cur)
        seq = write_rows(st["seq"], val, writepos, axis=0)
        new_pos = jnp.where(live, pos + 1, pos)
        done_now = live & ((val == eos_id) | (new_pos + 1 >= st["stop"]))
        new_keys = jnp.where(live[:, None], kd, st["keys"])
        st = {**st, "seq": seq, "caches": caches, "pos": new_pos,
              "done": st["done"] | done_now, "keys": new_keys}
        return (st, live & (nxt == guess) & ~done_now), live

    (verified, _), lives = jax.lax.scan(
        verify_body, (verify_state, live0), (inputs, guesses))

    # -- draft commit: the real draft caches consume the same inputs
    # under the recorded live masks, staying aligned with the target
    def commit_body(carry, xs):
        dc, dpos = carry
        inp, live = xs
        _, dc_new = draft_step(inp, dpos, dc)

        def mrg(n, o):
            m = live.reshape((-1,) + (1,) * (o.ndim - 1))
            return jnp.where(m, n, o)

        dc = jax.tree.map(mrg, dc_new, dc)
        return (dc, jnp.where(live, jnp.minimum(dpos + 1, max_len - 1),
                              dpos)), None

    (draft_caches, _), _ = jax.lax.scan(
        commit_body, (state["draft_caches"], pos0), (inputs, lives))

    emitted = jnp.sum(lives.astype(jnp.int32), axis=0)
    return {**verified, "draft_caches": draft_caches}, emitted
