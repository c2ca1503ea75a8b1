"""The gated delta rule's two forms timed ALONE on the chip, at the shapes
of ``serve-qwen3next-longdoc-backlog``: the first rows of the trace a later
``perf_opt`` starts from.  Not part of a run of the cell.

* the chunked form (``ops/gdn.py:gdn_scan``) over 1 x 16,384 and 2 x 16,384
  tokens, chunks of 64, with ``T = (I - A)^-1`` BOTH ways — forward
  substitution (``solve_triangular``), which the op keeps, and the products
  ``(I + A)(I + A^2)...(I + A^32)``, planted here for the comparison — in
  milliseconds a layer;
* the inverse alone, both ways, over one segment's triangles ``(32, 2, 16,
  2, 64, 64)``, and how far the products lie from substitution on a chunk of
  one repeated token (equal keys, ``beta`` 0.5);
* the decode step (``gdn_step``) at 32 slots inside a ``fori_loop`` over the
  carried state (as it is a carry in the engine's scan), in microseconds a
  layer and GB/s of carry read and written.

    python3 perf/tools/qwen3next_ops.py [--repeats 5]
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

HK, HV, DK, DV, CHUNK, SLOTS, STEPS = 16, 32, 128, 128, 64, 32, 64
TOKENS = 16384


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--repeats", type=int, default=5)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from progen_tpu.ops import gdn

    f32, bf16 = jnp.float32, jnp.bfloat16
    kind = jax.devices()[0].device_kind

    def by_products(a):
        t = a + jnp.eye(a.shape[-1], dtype=f32)
        power = a
        for _ in range(max(a.shape[-1] - 1, 1).bit_length() - 1):
            power = jnp.matmul(power, power, precision=gdn.HIGHEST)
            t = t + jnp.matmul(t, power, precision=gdn.HIGHEST)
        return t

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
        q = jax.random.normal(ks[0], (rows, tokens, HK, DK), f32)
        k = jax.random.normal(ks[1], (rows, tokens, HK, DK), f32)
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (rows, tokens, HV, DV), f32)
        g = -0.1 * jax.nn.softplus(jax.random.normal(ks[3],
                                                     (rows, tokens, HV)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, tokens, HV)))
        return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta

    by_substitution = gdn.unit_lower_inverse
    forms = {"substitution": by_substitution, "products": by_products}
    for rows in (1, 2):
        xs = inputs(rows, TOKENS, bf16)
        lengths = jnp.full((rows,), TOKENS, jnp.int32)
        outs = {}
        for name, inverse in forms.items():
            # ``lax.scan`` keeps the trace of its body by the function: the
            # planted form is traced only into an empty cache
            jax.clear_caches()
            with mock.patch.object(gdn, "unit_lower_inverse", inverse):
                scan = jax.jit(lambda *a: gdn.gdn_scan(*a, CHUNK))
                secs = timed(scan, *xs, lengths)
                outs[name] = scan(*xs, lengths)
            print(json.dumps({"op": "gdn_scan", "rows": rows,
                              "tokens": TOKENS, "chunk": CHUNK, "T": name,
                              "ms_a_layer": 1e3 * secs, "device": kind}),
                  flush=True)
        print(json.dumps({"op": "gdn_scan", "rows": rows,
                          "carry_off_substitution": {
                              name: float(jnp.abs(
                                  out[1] - outs["substitution"][1]).max())
                              for name, out in outs.items()},
                          "carry_max": float(jnp.abs(
                              outs["substitution"][1]).max())}), flush=True)

    a = jnp.tril(jax.random.normal(jax.random.key(1),
                                   (32, 2, HK, HV // HK, CHUNK, CHUNK), f32), -1)
    a = a * 0.05
    rank = jnp.arange(CHUNK)
    repeated = -0.5 * jnp.tril(jnp.ones((1, CHUNK, CHUNK), f32), -1) * (
        0.999 ** (rank[:, None] - rank[None, :]))
    for name, inverse in forms.items():
        print(json.dumps({
            "op": "inverse", "T": name, "triangles": a.size // CHUNK ** 2,
            "ms": 1e3 * timed(jax.jit(inverse), a),
            "repeated_token_off_substitution": float(jnp.abs(
                jax.jit(inverse)(repeated)
                - by_substitution(repeated)).max()),
            "device": kind}), flush=True)

    q, k, v, g, beta = (x[:, 0] for x in inputs(SLOTS, 1, bf16))
    state = jax.random.normal(jax.random.key(2), (SLOTS, HV, DK, DV), f32)

    @jax.jit
    def steps(state):
        def body(_, carry):
            state, acc = carry
            o, state = gdn.gdn_step(state, q, k, v, g, beta)
            return state, acc + o

        return jax.lax.fori_loop(0, STEPS, body,
                                 (state, jnp.zeros((SLOTS, HV, DV), f32)))

    secs = timed(steps, state) / STEPS
    moved = 2 * state.size * 4
    print(json.dumps({"op": "gdn_step", "slots": SLOTS,
                      "us_a_layer": 1e6 * secs,
                      "carry_gb_per_s": moved / secs / 1e9,
                      "device": kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
