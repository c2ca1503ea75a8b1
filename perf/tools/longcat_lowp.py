"""The control readings behind ``serve-longcat-backlog``'s three limits:
the reference's own equations computed AT and BELOW the precision the
configuration states, held against the float32 reference by the cell's own
measures.  Four variants, made here by wrapping the reference's three named
operations (``product``, ``softmax``, ``rms_norm``) — the reference itself
stays one float32 path.  In all of them matrix products take bfloat16
operands and activations are bfloat16, as the configuration states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the norms'
    statistics, logits) stay float32: what the program computes, so it has
    to read as the program does (the tool's own check)
``attention-softmax-bf16``
    and the attention's softmax alone in bfloat16
``islands-bf16``
    and every island in bfloat16
``one-notch-below``
    that, and both operands of every matrix product the configuration
    states in bfloat16 rounded to float8_e4m3fn first

For each it prints the direct check's two numbers over the same positions
of the same seeded row (the largest difference of any logit; the share of
(token, layer) routings whose chosen set differs) and the probe rule's gaps
for a server that computes in the variant: over the probes' primes and
``probe_new_tokens`` seeded continuation tokens each, the float32
reference's best (greedy) or ``top_k``-th best (sampled) allowed logit less
its logit of the token such a server serves — its best allowed token, and
the member of its top ``top_k`` the float32 reference likes least.
``as-stated`` has to pass every limit, ``islands-bf16`` has to be refused by
the routing limit, ``one-notch-below`` by every limit.  Run once, on the
chip; not part of a run of the cell.

    python3 perf/tools/longcat_lowp.py --seed <n> [<n> ...]
"""

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ISLANDS = ("router", "softmax", "norms", "logits")
# name -> (type the products' operands are rounded to, islands lowered)
VARIANTS = {
    "as-stated": (None, ()),
    "attention-softmax-bf16": (None, ("softmax",)),
    "islands-bf16": (None, ISLANDS),
    "one-notch-below": ("float8_e4m3fn", ISLANDS),
}
HEAD = "td,dv->tv"      # the reference's product that makes the logits


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS):
    """``perf.lib.reference_longcat`` with bfloat16 activations and products
    while this is open (trace inside it), and each of ``islands`` in
    bfloat16 too; the others stay float32.  ``operands``: a narrower type
    both operands of every product but the router's are rounded to first
    (the router is an island: its notch below float32 is bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_longcat as ref

    low, f32 = jnp.bfloat16, jnp.float32
    plain = {name: getattr(ref, name) for name in ("product", "softmax",
                                                   "route")}

    def stat(island):
        return low if island in islands else f32

    def narrow(x, to):
        x = x.astype(low)
        return x if to is None else x.astype(to).astype(low)

    def product(spec, a, b, to=operands):
        out = jnp.einsum(spec, narrow(a, to), narrow(b, to),
                         preferred_element_type=f32)
        return out.astype(stat("logits") if spec == HEAD else low)

    def softmax(x):
        return jax.nn.softmax(x.astype(stat("softmax")), axis=-1).astype(low)

    def rms_norm(x, scale, eps):
        xs = x.astype(stat("norms"))
        var = jnp.mean(xs * xs, axis=-1, keepdims=True)
        return (xs * jax.lax.rsqrt(var + eps) * scale.astype(xs.dtype)
                ).astype(low)

    def route(u, p, cfg):
        """The router is float32 over the bfloat16 activations (the
        reference's own operations), or bfloat16 throughout."""
        inner = ({"product": lambda s, a, b: product(s, a, b, None),
                  "softmax": lambda x: jax.nn.softmax(x.astype(low), -1)}
                 if "router" in islands else
                 {k: plain[k] for k in ("product", "softmax")})
        with mock.patch.multiple(ref, **inner):
            return plain["route"](u, p, cfg)

    with mock.patch.multiple(ref, product=product, softmax=softmax,
                             rms_norm=rms_norm, route=route):
        yield


def probe_rows(workload: dict, seed: int, vocab: int):
    """The probe rule's rows as the runner draws their primes (one from
    each quarter of the cell's range), each followed by ``probe_new_tokens``
    seeded tokens: ``(rows (2n, width), prime lengths)``."""
    import numpy as np

    from perf.lib import traffic

    check, primes = workload["correct"], workload["traffic"]["prime_tokens"]
    n, new = check["probes"], check["probe_new_tokens"]
    rng = traffic.rng_for(seed, "probe")
    edges = np.linspace(primes["min"], primes["max"] + 1, 2 * n + 1)
    rows = np.zeros((2 * n, primes["max"] + new), np.int32)
    lengths = []
    for i in range(2 * n):
        p = int(rng.integers(int(edges[i]), int(edges[i + 1])))
        rows[i, :p + new] = rng.integers(1, vocab, p + new)
        lengths.append(p)
    return rows, lengths


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[28])
    parser.add_argument("--workload", default="serve-longcat-backlog")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness, reference_longcat
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.models import longcat

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    direct = check["direct"]
    model_config = longcat.LongCatConfig.from_dict(config)
    runner = harness.load_module(workload["runner"])
    new, top_k = check["probe_new_tokens"], workload["traffic"]["sampling"][
        "top_k"]
    # one program per variant and shape, traced inside the variant once
    forwards = {name: jax.jit(lambda p, t, k: reference_longcat.forward_row(
        p, t, config, logit_positions=k)) for name in (None, *VARIANTS)}

    def run(variant, params, row, at, rows, primes):
        """``(direct logits, direct choices, [probe logits (new, V - 1)])``
        of the reference, plain (None) or in a variant."""
        if variant is None:
            ctx = contextlib.nullcontext()
        else:
            narrower, islands = VARIANTS[variant]
            ctx = lowered(narrower and getattr(jax.numpy, narrower), islands)
        fwd = forwards[variant]
        with ctx, jax.default_matmul_precision("highest"):
            logits, chosen = fwd(params, row, at)
            probes = [np.asarray(fwd(params, rows[i], np.arange(
                p - 1, p - 1 + new))[0])[:, 1:]     # token 0 is masked out
                for i, p in enumerate(primes)]
        return np.asarray(logits), np.asarray(chosen), probes

    for seed in args.seed:
        params = longcat.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            longcat.bf16_policy())
        n, row, at = runner.direct_row(direct, seed, model_config.vocab_size)
        inputs = (params, row, at,
                  *probe_rows(workload, seed, model_config.vocab_size))
        primes = inputs[-1]
        want, want_sets, want_probes = run(None, *inputs)
        for name in VARIANTS:
            got, got_sets, got_probes = run(name, *inputs)
            diff = np.abs(got - want)
            differ = np.any(np.sort(got_sets, -1) != np.sort(want_sets, -1),
                            axis=-1)[:, :n + 1]
            greedy = least = 0.0
            steps = np.arange(new)
            for ref_at, low_at in zip(want_probes, got_probes):
                greedy = max(greedy, float((
                    ref_at.max(-1) - ref_at[steps, low_at.argmax(-1)]).max()))
                served = np.argsort(low_at, axis=-1)[:, -top_k:]
                kth = np.sort(ref_at, axis=-1)[:, -top_k]
                least = max(least, float((
                    kth - ref_at[steps[:, None], served].min(-1)).max()))
            worst, share = float(diff.max()), float(differ.mean())
            print(json.dumps({
                "variant": name, "seed": seed, "prime": n,
                "positions": len(at), "worst": worst,
                "rms": float(np.sqrt((diff ** 2).mean())),
                "routings_differ_share": share,
                "probe_gap_greedy": greedy, "probe_gap_sampled_least": least,
                "probe_primes": primes,
                "refused_by": [k for k, over in {
                    "direct.tolerance": worst > direct["tolerance"],
                    "direct.routings_limit": share > direct["routings_limit"],
                    "tolerance": max(greedy, least) > check["tolerance"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params, inputs      # 10 GB: gone before the next seed's are made
    return 0


if __name__ == "__main__":
    sys.exit(main())
