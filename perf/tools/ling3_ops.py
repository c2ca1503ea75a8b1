"""The channel-decay delta rule's two forms timed ALONE on the chip, at the
shapes of ``serve-ling3-longdoc-backlog``: the first rows of the trace a
later ``perf_opt`` starts from.  Not part of a run of the cell.

* the chunked form (``ops/gdn.py:kda_scan``) over 1 x 16,384, 2 x 16,384 and
  4 x 16,384 tokens (a row at a time, as ``ChannelDeltaBlock.prefill`` calls
  it: ``lax.map`` over the rows), chunks of 64 in blocks of 16, with the
  DIAGONAL blocks' decayed products BOTH ways — the ``16 x 16 x 128`` sum
  taken directly on the vector unit (every exponent non-positive), and a
  reference row inside the block (its first: float32 factors with exponents
  up to 16 x 5 = 80, two float32 products on the matrix unit), planted here
  for the comparison — in milliseconds a layer, beside ``gdn_scan``'s XLA
  form (a decay a head) at the same token count;
* how far the two forms' carries lie apart;
* the decode step (``kda_step``) at 64 slots inside a ``fori_loop`` over the
  carried state (as it is a carry in the engine's scan), in microseconds a
  layer and GB/s of carry read and written.

    python3 perf/tools/ling3_ops.py [--repeats 3]
"""

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

H, DK, DV, CHUNK, BLOCK, SLOTS, STEPS = 32, 128, 128, 64, 16, 64, 64
TOKENS = 16384


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--rows", type=int, nargs="+", default=[1, 2, 4])
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from progen_tpu.ops import gdn

    f32, bf16 = jnp.float32, jnp.bfloat16
    kind = jax.devices()[0].device_kind

    def by_reference_row(qb, kb, gb):
        """The diagonal blocks against their own first row, on the matrix
        unit: ``(K exp(gam - gam_first)) (K exp(gam_first - gam))^T``."""
        b = kb.shape[-2]
        inside = jnp.tril(jnp.ones((b, b), bool))
        first = gb[..., :1, :]
        down, up = jnp.exp(gb - first), kb * jnp.exp(first - gb)

        def product(x):
            return jnp.where(inside, jnp.einsum(
                "...id,...jd->...ij", x * down, up, precision=gdn.HIGHEST,
                preferred_element_type=f32), 0.0)

        return product(kb), product(qb)

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        best = float("inf")
        for _ in range(args.repeats):
            t = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            best = min(best, time.perf_counter() - t)
        return best

    def inputs(rows, tokens, dtype):
        ks = jax.random.split(jax.random.key(0), 5)
        q = jax.random.normal(ks[0], (rows, tokens, H, DK), f32)
        k = jax.random.normal(ks[1], (rows, tokens, H, DK), f32)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (rows, tokens, H, DV), f32)
        # the seeded weights' spread: a bias in [-6, 2] a channel
        bias = jax.random.uniform(ks[3], (H, DK), f32, -6.0, 2.0)
        g = -5.0 * jax.nn.sigmoid(
            jax.random.normal(ks[3], (rows, tokens, H, DK)) + bias)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, tokens, H)))
        return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta

    def a_row_at_a_time(scan):
        def rows(q, k, v, g, beta, lengths):
            return jax.lax.map(
                lambda x: scan(*(a[None] for a in x)),
                (q, k, v, g, beta, lengths))

        return jax.jit(rows)

    forms = {"direct": gdn._diagonal_products,
             "reference-row": by_reference_row}
    for rows in args.rows:
        xs = inputs(rows, TOKENS, bf16)
        lengths = jnp.full((rows,), TOKENS, jnp.int32)
        outs = {}
        for name, diagonal in forms.items():
            # ``lax.scan`` keeps the trace of its body by the function: the
            # planted form is traced only into an empty cache
            jax.clear_caches()
            with mock.patch.object(gdn, "_diagonal_products", diagonal):
                scan = a_row_at_a_time(
                    lambda *a: gdn.kda_scan(*a, CHUNK, BLOCK))
                secs = timed(scan, *xs, lengths)
                outs[name] = scan(*xs, lengths)
            print(json.dumps({"op": "kda_scan", "rows": rows,
                              "tokens": TOKENS, "chunk": CHUNK,
                              "diagonal": name, "ms_a_layer": 1e3 * secs,
                              "device": kind}), flush=True)
        print(json.dumps({"op": "kda_scan", "rows": rows,
                          "carry_off_direct": float(jnp.abs(
                              outs["reference-row"][1]
                              - outs["direct"][1]).max()),
                          "o_off_direct": float(jnp.abs(
                              outs["reference-row"][0].astype(f32)
                              - outs["direct"][0].astype(f32)).max()),
                          "carry_max": float(jnp.abs(
                              outs["direct"][1]).max())}), flush=True)
        del outs
        jax.clear_caches()
        head = jax.jit(lambda q, k, v, g, beta, n: gdn.xla_gdn_scan(
            q, k, v, g[..., 0], beta, n, CHUNK))
        print(json.dumps({"op": "gdn_scan (xla, a head's decay)",
                          "rows": rows, "tokens": TOKENS,
                          "ms_a_layer": 1e3 * timed(head, *xs, lengths),
                          "device": kind}), flush=True)
        del xs

    q, k, v, g, beta = (x[:, 0] for x in inputs(SLOTS, 1, bf16))
    state = jax.random.normal(jax.random.key(2), (SLOTS, H, DK, DV), f32)

    @jax.jit
    def steps(state):
        def body(_, carry):
            state, acc = carry
            o, state = gdn.kda_step(state, q, k, v, g, beta)
            return state, acc + o

        return jax.lax.fori_loop(0, STEPS, body,
                                 (state, jnp.zeros((SLOTS, H, DV), f32)))

    secs = timed(steps, state) / STEPS
    moved = 2 * state.size * 4
    print(json.dumps({"op": "kda_step", "slots": SLOTS,
                      "us_a_layer": 1e6 * secs,
                      "carry_gb_per_s": moved / secs / 1e9,
                      "device": kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
