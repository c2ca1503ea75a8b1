"""The control readings behind ``serve-granite-chat-backlog``'s limits: the
reference's own equations computed AT and BELOW the precision the
configuration states, held against the float32 reference by the cell's own
measures.  Four variants, made here by wrapping the reference's five named
operations (``product``, ``softmax``, ``rms_norm``, ``island``, ``carry``)
— the reference itself stays one float32 path.  In all of them matrix
products take bfloat16 operands and hand on bfloat16 activations, as the
configuration states:

``as-stated``
    and the configuration's float32 islands (the recurrence's step, decay
    and input, the convolution's sum, softmax, the norms' statistics,
    logits) stay float32 and the carry is float32: what the program
    computes, so it has to read as the program does (the tool's own check)
``carry-bf16``
    and the carry handed from token to token in bfloat16: the whole state
    re-rounded every token
``islands-bf16``
    that, and every island in bfloat16
``one-notch-below``
    that, and both operands of every matrix product rounded to
    float8_e4m3fn first

For each it prints the direct check's two numbers over the same positions
of the same seeded rows (the largest difference of any logit and the root
mean square of all of them) and the probe rule's reading for a server that
computes in the variant: over the probes' primes and ``probe_new_tokens``
seeded continuation tokens each, the share of positions at which the
float32 reference's best (greedy) or ``top_k``-th best (sampled) allowed
logit exceeds its logit of the token such a server serves by more than the
tolerance — its best allowed token, and the member of its top ``top_k`` the
float32 reference likes least.  ``as-stated`` has to pass every limit;
``one-notch-below`` has to be refused by at least one.  Run once, on the
chip; not part of a run of the cell.

    python3 perf/tools/granite_lowp.py --seed <n> [<n> ...]
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

ISLANDS = ("recurrence", "softmax", "norms", "logits")
# name -> (type the products' operands are rounded to, islands lowered,
# the carry's type)
VARIANTS = {
    "as-stated": (None, (), "float32"),
    "carry-bf16": (None, (), "bfloat16"),
    "islands-bf16": (None, ISLANDS, "bfloat16"),
    "one-notch-below": ("float8_e4m3fn", ISLANDS, "bfloat16"),
}
HEAD = "td,vd->tv"      # the reference's product that makes the logits
SCORES = "->kgqt"       # its product that makes the attention scores


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS, state="bfloat16"):
    """``perf.lib.reference_granite`` with bfloat16 activations and products
    while this is open (trace inside it), each of ``islands`` in bfloat16
    too (the others stay float32) and the carry in ``state``.  ``operands``:
    a narrower type both operands of every product are rounded to first."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_granite as ref

    low, f32 = jnp.bfloat16, jnp.float32

    def stat(island):
        return low if island in islands else f32

    def narrow(x):
        x = x.astype(low)
        if operands is None:
            return x
        top = float(jnp.finfo(operands).max)     # saturate: e4m3fn has no inf
        return jnp.clip(x, -top, top).astype(operands).astype(low)

    def product(spec, a, b):
        out = jnp.einsum(spec, narrow(a), narrow(b),
                         preferred_element_type=f32)
        if spec == HEAD:
            return out.astype(stat("logits"))
        # the scores stay as wide as the softmax that takes them (the
        # program accumulates and keeps them in float32)
        return out.astype(stat("softmax") if spec.endswith(SCORES) else low)

    def softmax(x):
        return jax.nn.softmax(x.astype(stat("softmax")), axis=-1).astype(low)

    def rms_norm(x, scale, eps):
        xs = x.astype(stat("norms"))
        var = jnp.mean(xs * xs, axis=-1, keepdims=True)
        return (xs * jax.lax.rsqrt(var + eps) * scale.astype(xs.dtype)
                ).astype(low)

    def island(x):
        return x.astype(stat("recurrence"))

    def carry(x):
        return x.astype(state)

    with mock.patch.multiple(ref, product=product, softmax=softmax,
                             rms_norm=rms_norm, island=island, carry=carry):
        yield


def probe_rows(sibling, workload: dict, seed: int, vocab: int):
    """The probe rule's rows as the runner draws their primes, each
    followed by ``probe_new_tokens`` seeded tokens: ``(rows [2n arrays],
    prime lengths)``."""
    import numpy as np

    from perf.lib import traffic

    new = workload["correct"]["probe_new_tokens"]
    reqs, _ = sibling.probe_requests(workload, seed, vocab, 0)
    rng = traffic.rng_for(seed, "probe-tail")
    rows = [np.asarray(list(r["prime"]) + rng.integers(1, vocab,
                                                       new).tolist(),
                       np.int32) for r in reqs]
    return rows, [len(r["prime"]) for r in reqs]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[38])
    parser.add_argument("--workload", default="serve-granite-chat-backlog")
    parser.add_argument("--variant", nargs="+", default=list(VARIANTS),
                        choices=list(VARIANTS))
    parser.add_argument("--probe-new-tokens", type=int, default=None,
                        help="probes of another length than the cell's")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf.lib import harness
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.models import granite_hybrid

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    if args.probe_new_tokens:
        check["probe_new_tokens"] = args.probe_new_tokens
    direct = check["direct"]
    steps = direct["decode_steps"]
    model_config = granite_hybrid.GraniteHybridConfig.from_dict(config)
    runner = harness.load_module(workload["runner"])
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    admit_rows = max(1, workload["engine"]["num_slots"] // SLOTS_PER_ADMIT_ROW)
    new, top_k = check["probe_new_tokens"], workload["traffic"]["sampling"][
        "top_k"]
    # one program per variant, traced inside the variant once, padded as
    # the runner's ``reference_for`` pads
    forwards = {name: runner.reference_for(config, workload, admit_rows)
                for name in (None, *args.variant)}

    def run(variant, params, lengths, tokens, at, rows, primes):
        """``([direct logits], [probe logits (new, V - 1)])`` of the
        reference, plain (None) or in a variant."""
        if variant is None:
            ctx = contextlib.nullcontext()
        else:
            narrower, islands, state = VARIANTS[variant]
            ctx = lowered(narrower and getattr(jnp, narrower), islands,
                          getattr(jnp, state))
        fwd = forwards[variant]
        with ctx, jax.default_matmul_precision("highest"):
            logits = [np.asarray(fwd(params, tokens[i, :n + steps], at[i]),
                                 np.float32)
                      for i, n in enumerate(lengths)]
            probes = [np.asarray(fwd(params, rows[i], np.arange(
                p - 1, p - 1 + new)), np.float32)[:, 1:]   # token 0 masked
                for i, p in enumerate(primes)]
        return logits, probes

    for seed in args.seed:
        params = granite_hybrid.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            granite_hybrid.bf16_policy())
        vocab = model_config.vocab_size
        inputs = (params,
                  *runner.direct_rows(direct, seed, vocab, admit_rows,
                                      model_config.mamba_chunk_size,
                                      model_config.prefill_bucket),
                  *probe_rows(sibling, workload, seed, vocab))
        want, want_probes = run(None, *inputs)
        for name in args.variant:
            got, got_probes = run(name, *inputs)
            diffs = [np.abs(g - w) for g, w in zip(got, want)]
            worst = {"prefill": max(float(d[:-steps].max()) for d in diffs),
                     "decode": max(float(d[-steps:].max()) for d in diffs)}
            rms = float(np.sqrt(np.mean(np.concatenate(diffs) ** 2)))
            # a server computing in the variant: its best allowed token, and
            # the member of its top ``top_k`` the float32 reference likes least
            greedy, least = [], []
            for ref_at, low_at in zip(want_probes, got_probes):
                greedy.append(sibling.probe_gaps(ref_at, low_at.argmax(-1),
                                                 None))
                served = np.argsort(low_at, axis=-1)[:, -top_k:]
                kth = np.sort(ref_at, axis=-1)[:, -top_k]
                least.append(np.maximum(kth - np.take_along_axis(
                    ref_at, served, -1).min(-1), 0.0))
            probes = {k: sibling.gap_reading(np.concatenate(v),
                                             check["tolerance"])
                      for k, v in (("greedy", greedy),
                                   ("sampled_least", least))}
            print(json.dumps({
                "variant": name, "seed": seed,
                "primes": inputs[1].tolist(), "worst": worst, "rms": rms,
                "probes": probes, "probe_primes": inputs[-1],
                "refused_by": [k for k, over in {
                    "direct.tolerance": max(worst.values())
                    > direct["tolerance"],
                    "direct.rms_limit": rms > direct["rms_limit"],
                    "over_share_limit": max(
                        r["over_share"] for r in probes.values())
                    > check["over_share_limit"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params, inputs
    return 0


if __name__ == "__main__":
    sys.exit(main())
