"""The control readings behind ``serve-trinity-mixedlen-backlog``'s three
limits: the reference's own equations computed AT and BELOW the precision
the configuration states, held against the float32 reference by the cell's
own measures.  Three variants, made here by wrapping the reference's four
named operations (``product``, ``softmax``, ``rms_norm``, ``sigmoid``) and
its router — the reference itself stays one float32 path.  In all of them
matrix products take bfloat16 operands and activations (the attention's
gate among them) are bfloat16, as the configuration states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the norms'
    statistics, logits) stay float32: what the program computes, so it has
    to read as the program does (the tool's own check)
``islands-bf16``
    and every island in bfloat16 (bfloat16 routing among them)
``one-notch-below``
    that, and both operands of every matrix product the configuration
    states in bfloat16 rounded to float8_e4m3fn first

For each it prints the direct check's three numbers over the same positions
of the same seeded rows (the largest difference of any logit where the
token's routing agreed; the share of compared positions where it did, in
every expert layer; the share of (token, expert layer) routings whose
chosen set differs — the sibling runner's ``compare_row``) and the probe
rule's reading for a server that computes in the variant: over the probes'
primes and ``probe_new_tokens`` seeded continuation tokens each, the share
of positions at which the float32 reference's best (greedy) or ``top_k``-th
best (sampled) allowed logit exceeds its logit of the token such a server
serves by more than the tolerance — its best allowed token, and the member
of its top ``top_k`` the float32 reference likes least.  ``as-stated`` has
to pass every limit; ``one-notch-below`` has to be refused by at least one.
Run once, on the chip; not part of a run of the cell.

    python3 perf/tools/trinity_lowp.py --seed <n> [<n> ...]
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
    "islands-bf16": (None, ISLANDS),
    "one-notch-below": ("float8_e4m3fn", ISLANDS),
}
HEAD = "td,dv->tv"      # the reference's product that makes the logits
SCORES = "->kgqt"       # its product that makes the attention scores


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS):
    """``perf.lib.reference_trinity`` with bfloat16 activations and products
    while this is open (trace inside it), and each of ``islands`` in
    bfloat16 too; the others stay float32.  ``operands``: a narrower type
    both operands of every product but the router's are rounded to first
    (the router is an island: its notch below float32 is bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_trinity as ref

    low, f32 = jnp.bfloat16, jnp.float32
    plain = {name: getattr(ref, name) for name in ("product", "sigmoid",
                                                   "route")}

    def stat(island):
        return low if island in islands else f32

    def narrow(x, to):
        x = x.astype(low)
        return x if to is None else x.astype(to).astype(low)

    def product(spec, a, b, to=operands):
        out = jnp.einsum(spec, narrow(a, to), narrow(b, to),
                         preferred_element_type=f32)
        if spec == HEAD:
            return out.astype(stat("logits"))
        # the scores stay as wide as the softmax that takes them (the
        # program accumulates and keeps them in float32)
        return out.astype(stat("softmax") if spec.endswith(SCORES) else low)

    def softmax(x):
        return jax.nn.softmax(x.astype(stat("softmax")), axis=-1).astype(low)

    def sigmoid(x):             # the attention's gate: an activation
        return jax.nn.sigmoid(x.astype(low))

    def rms_norm(x, scale, eps):
        xs = x.astype(stat("norms"))
        var = jnp.mean(xs * xs, axis=-1, keepdims=True)
        return (xs * jax.lax.rsqrt(var + eps) * scale.astype(xs.dtype)
                ).astype(low)

    def route(u, p, cfg):
        """The router is float32 over the bfloat16 activations (the
        reference's own operations), or bfloat16 throughout."""
        inner = ({"product": lambda s, a, b: product(s, a, b, None),
                  "sigmoid": lambda x: jax.nn.sigmoid(x.astype(low))}
                 if "router" in islands else
                 {k: plain[k] for k in ("product", "sigmoid")})
        with mock.patch.multiple(ref, **inner):
            return plain["route"](u, p, cfg)

    with mock.patch.multiple(ref, product=product, softmax=softmax,
                             sigmoid=sigmoid, rms_norm=rms_norm, route=route):
        yield


def probe_rows(sibling, workload: dict, seed: int, vocab: int):
    """The probe rule's rows as the runner draws their primes, each
    followed by ``probe_new_tokens`` seeded tokens: ``(rows (2n, width),
    prime lengths)``."""
    import numpy as np

    from perf.lib import traffic

    new = workload["correct"]["probe_new_tokens"]
    reqs, _ = sibling.probe_requests(workload, seed, vocab, 0)
    rng = traffic.rng_for(seed, "probe-tail")
    width = workload["traffic"]["prime_tokens"]["max"] + new
    rows = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        p = len(r["prime"])
        rows[i, :p] = r["prime"]
        rows[i, p:p + new] = rng.integers(1, vocab, new)
    return rows, [len(r["prime"]) for r in reqs]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[34])
    parser.add_argument("--workload",
                        default="serve-trinity-mixedlen-backlog")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness, reference_trinity
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.models import trinity

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    direct = check["direct"]
    steps = direct["decode_steps"]
    model_config = trinity.TrinityConfig.from_dict(config)
    runner = harness.load_module(workload["runner"])
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    admit_rows = max(1, workload["engine"]["num_slots"] // SLOTS_PER_ADMIT_ROW)
    new, top_k = check["probe_new_tokens"], workload["traffic"]["sampling"][
        "top_k"]
    # one program per variant, traced inside the variant once: every row
    # padded to one length and every list of positions to one count, as
    # the runner's ``reference_for`` pads them
    width = workload["traffic"]["prime_tokens"]["max"] + new
    count = runner.reference_positions(check)
    forwards = {name: jax.jit(
        lambda p, t, k: reference_trinity.forward_row(
            p, t, config, q_block=runner.QUERY_BLOCK, logit_positions=k))
        for name in (None, *VARIANTS)}

    def padded(fwd, params, tokens, positions):
        k = len(positions)
        logits, chosen = fwd(
            params, np.pad(tokens, (0, width - len(tokens))),
            np.pad(positions, (0, count - k), mode="edge"))
        return logits[:k], chosen

    def run(variant, params, lengths, tokens, at, rows, primes):
        """``([direct logits], [direct choices], [probe logits (new, V -
        1)])`` of the reference, plain (None) or in a variant."""
        if variant is None:
            ctx = contextlib.nullcontext()
        else:
            narrower, islands = VARIANTS[variant]
            ctx = lowered(narrower and getattr(jax.numpy, narrower), islands)
        fwd = forwards[variant]
        logits, chosen = [], []
        with ctx, jax.default_matmul_precision("highest"):
            for i, n in enumerate(lengths):
                out, sets = padded(fwd, params, tokens[i], at[i])
                logits.append(np.asarray(out))
                chosen.append(np.asarray(sets)[:, :n + steps])
            probes = [np.asarray(padded(fwd, params, rows[i], np.arange(
                p - 1, p - 1 + new))[0])[:, 1:]     # token 0 is masked out
                for i, p in enumerate(primes)]
        return logits, chosen, probes

    for seed in args.seed:
        params = trinity.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            trinity.bf16_policy())
        vocab = model_config.vocab_size
        inputs = (params,
                  *runner.direct_rows(direct, seed, vocab, admit_rows,
                                      model_config.sliding_window),
                  *probe_rows(sibling, workload, seed, vocab))
        want, want_sets, want_probes = run(None, *inputs)
        at = inputs[3]
        for name in VARIANTS:
            got, got_sets, got_probes = run(name, *inputs)
            rows = [sibling.compare_row(g, gs, w, ws, a) for g, gs, w, ws, a
                    in zip(got, got_sets, want, want_sets, at)]
            worst = {k: max(r["worst"][k] for r in rows)
                     for k in ("agreed", "all")}
            share = (sum(r["differ"] for r in rows)
                     / sum(r["routings"] for r in rows))
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
            agreed = sum(r["agreed_positions"] for r in rows) / at.size
            print(json.dumps({
                "variant": name, "seed": seed,
                "primes": inputs[1].tolist(), "worst": worst,
                "agreed_share": agreed,
                "routings_differ_share": share, "probes": probes,
                "probe_primes": inputs[-1],
                "refused_by": [k for k, over in {
                    "direct.tolerance": worst["agreed"] > direct["tolerance"],
                    "direct.routings_limit": share > direct["routings_limit"],
                    "direct.agreed_floor": agreed < direct["agreed_floor"],
                    "over_share_limit": max(
                        r["over_share"] for r in probes.values())
                    > check["over_share_limit"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params, inputs
    return 0


if __name__ == "__main__":
    sys.exit(main())
