"""The control readings behind ``serve-sdar-blockdiff-backlog``'s limits: the
reference's own equations computed AT and BELOW the precision the
configuration states, and under the WRONG MASK, held against the float32
reference by the cell's own measures.  Four variants, made here by wrapping
the reference's three named operations (``product``, ``softmax``,
``rms_norm``) and its router, or by handing it another mask — the reference
itself stays one float32 path.  In the first three, matrix products take
bfloat16 operands and activations are bfloat16, as the configuration states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the norms'
    statistics, logits) stay float32: what the program computes, so it has
    to read as the program does (the tool's own check)
``islands-bf16``
    and every island in bfloat16 (bfloat16 routing among them)
``one-notch-below``
    that, and both operands of every matrix product the configuration
    states in bfloat16 rounded to float8_e4m3fn first
``causal-in-block``
    float32 throughout, but a position sees only the keys at or before it
    INSIDE its block too: the error a block-diffusion server can make
    silently (every shape, every count and every token stream stays
    plausible)

For each it prints the direct check's numbers over the same replay rows of
the same seeded trajectories (the largest difference of any logit where the
token's routing agreed; the share of compared positions where it did; the
share of (token, layer) routings whose chosen set differs; the largest
difference of a committed key or value at agreed positions —
``serve_sdar.row_reading``) and the probe rules' readings for a server that
computes in the variant, over the probes' primes and ``probe_new_tokens``
seeded tokens filled in a seeded order: the share of kept positions at which
the float32 reference's best (greedy) or ``top_k``-th best (sampled) allowed
logit, at the forward that kept the position, exceeds its logit of the token
such a server serves there by more than the tolerance — its best allowed
token, and the member of its top ``top_k`` the float32 reference likes
least —, and the share of denoise forwards with a choice at which such a
server keeps other positions than the float32 reference's most confident
(where those lie more than ``order_margin`` apart).  ``as-stated`` has to
pass every limit; each of the others has to be refused by at least one.  Run
once, on the chip; not part of a run of the cell.

    python3 perf/tools/sdar_lowp.py --seed <n> [<n> ...]
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
# name -> (type the products' operands are rounded to, islands lowered);
# None: float32 as it stands (what differs is the mask, in ``replay``)
VARIANTS = {
    "as-stated": (None, ()),
    "islands-bf16": (None, ISLANDS),
    "one-notch-below": ("float8_e4m3fn", ISLANDS),
    "causal-in-block": None,
}
HEAD = "td,dv->tv"      # the reference's product that makes the logits
SCORES = "->kgqt"       # its product that makes the attention scores


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS):
    """``perf.lib.reference_sdar`` with bfloat16 activations and products
    while this is open (trace inside it), and each of ``islands`` in
    bfloat16 too; the others stay float32.  ``operands``: a narrower type
    both operands of every product but the router's are rounded to first
    (the router is an island: its notch below float32 is bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_sdar as ref

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

    def route(t, p, cfg):
        """The router is float32 over the bfloat16 activations (the
        reference's own operations), or bfloat16 throughout."""
        inner = ({"product": lambda s, a, b: product(s, a, b, None),
                  "softmax": lambda x: jax.nn.softmax(x.astype(low), axis=-1)}
                 if "router" in islands else
                 {k: plain[k] for k in ("product", "softmax")})
        with mock.patch.multiple(ref, **inner):
            return plain["route"](t, p, cfg)

    with mock.patch.multiple(ref, product=product, softmax=softmax,
                             rms_norm=rms_norm, route=route):
        yield


def probe_paths(sibling, workload: dict, seed: int, model_config):
    """The probe rules' trajectories: the primes the runner draws, each
    followed by ``probe_new_tokens`` seeded tokens, whole blocks filled in a
    seeded order at the static rule's counts: ``[(prime, tokens, fills)]``."""
    import numpy as np

    from perf.lib import reference_sdar, traffic

    b, steps = model_config.block_length, model_config.denoising_steps
    counts = reference_sdar.transfer_counts(b, steps)
    new = workload["correct"]["probe_new_tokens"]
    reqs, _ = sibling.probe_requests(workload, seed,
                                     model_config.mask_token_id, 0)
    rng = traffic.rng_for(seed, "probe-tail")
    out = []
    for r in reqs:
        p = len(r["prime"])
        tokens = rng.integers(1, model_config.mask_token_id, new)
        fills = np.zeros(new, np.int64)
        for p0 in range(p // b * b, p + new, b):
            order = rng.permutation(
                [q for q in range(max(p0, p), min(p0 + b, p + new))])
            step = 0
            while len(order):
                fills[order[:counts[step]] - p] = step
                order, step = order[counts[step]:], step + 1
        out.append((r["prime"], tokens, fills))
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[41])
    parser.add_argument("--workload", default="serve-sdar-blockdiff-backlog")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness, reference_sdar
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.models import sdar

    enable_compilation_cache()
    bench = harness.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(entry["traffic"])
    config = harness.load_config(entry["config"])
    check = workload["correct"]
    direct = check["direct"]
    runner = harness.load_module(workload["runner"])
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    model_config = runner.model_config_of(config, workload)
    ref_config = runner.reference_config(config, model_config)
    b, steps = model_config.block_length, model_config.denoising_steps
    counts = reference_sdar.transfer_counts(b, steps)
    admit_rows = max(1, workload["engine"]["num_slots"] // SLOTS_PER_ADMIT_ROW)
    top_k = workload["traffic"]["sampling"]["top_k"]
    banned = [0, model_config.mask_token_id]
    # one program per variant, traced inside the variant at its first call
    forwards = {name: runner.reference_for(ref_config, workload, model_config)
                for name in (None, *VARIANTS)}
    width = forwards[None].width

    def replay(variant, prime, tokens, fills):
        row = reference_sdar.replay_row(prime, tokens, fills, ref_config,
                                        steps, width)
        if variant == "causal-in-block":
            # within a copy of the row positions ascend, and a noisy block
            # stands after every clean block it sees
            tokens, positions, allowed, index = row
            row = (tokens, positions, allowed & (
                positions[None, :] <= positions[:, None]), index)
        return row

    def run(variant, params, rows, paths):
        """The reference, plain (None) or in a variant: per direct row
        ``(logits at its indices, choices, keys of its committed blocks,
        the indices)``, per probe path its logits at every masked position
        of every forward with the banned tokens at ``-inf``."""
        spec = VARIANTS.get(variant)
        ctx = (contextlib.nullcontext() if spec is None
               else lowered(spec[0] and getattr(jax.numpy, spec[0]), spec[1]))
        fwd = forwards[variant]
        direct_out, probe_out = [], []
        with ctx:
            for n, tokens, fills in rows:
                w, end = n // b * b, len(tokens)
                span = end - w
                row = replay(variant, tokens[:n], tokens[n:], fills[n:])
                k = direct["positions"] // len(rows)
                index = np.concatenate(
                    [np.linspace(0, w - 1, k).astype(np.int32)]
                    + [end + s * span + j * b + np.arange(b)
                       for j in range(direct["blocks"])
                       for s in range(steps)])
                logits, sets, keys = fwd(params, row[:3], index,
                                         np.arange(w, end))
                direct_out.append((
                    np.asarray(logits),
                    np.asarray(sets)[:, :end + steps * span],
                    np.asarray(keys)[:, :, :end - w], index, w, end))
            for prime, tokens, fills in paths:
                p, new = len(prime), len(tokens)
                w, end = p // b * b, (p + new) // b * b
                span = end - w
                row = replay(variant, prime, tokens, fills)
                read = [(q, s) for q in range(p, end)
                        for s in range(fills[q - p] + 1)]
                at = np.asarray([end + s * span + q - w for q, s in read])
                logits = np.array(fwd(params, row[:3], at)[0])
                logits[:, banned] = -np.inf
                probe_out.append((logits, read))
        return direct_out, probe_out

    def confidence(logits):
        cut = np.partition(logits, -top_k, axis=-1)[:, -top_k][:, None]
        e = np.where(logits >= cut, np.exp(
            logits - logits.max(-1, keepdims=True)), 0.0)
        return e.max(-1) / e.sum(-1)

    for seed in args.seed:
        params = sdar.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            sdar.bf16_policy())
        rows = runner.direct_rows(direct, seed, model_config, admit_rows)
        paths = probe_paths(sibling, workload, seed, model_config)
        want_direct, want_probes = run(None, params, rows, paths)
        for name in VARIANTS:
            got_direct, got_probes = run(name, params, rows, paths)
            readings = [runner.row_reading(
                sibling.compare_row, g[0], g[1], g[2], w[0], w[1], w[2],
                g[3], np.arange(g[4], g[5]))
                for g, w in zip(got_direct, want_direct)]
            worst = {k: max(r["worst"][k] for r in readings)
                     for k in ("agreed", "all")}
            worst["keys"] = max(r["keys"] for r in readings)
            share = (sum(r["differ"] for r in readings)
                     / sum(r["routings"] for r in readings))
            compared = sum(len(g[3]) for g in got_direct)
            agreed = sum(r["agreed_positions"] for r in readings) / compared
            # a server computing in the variant: at each kept position its
            # best allowed token, and the member of its top ``top_k`` the
            # float32 reference likes least; at each forward with a choice
            # the positions of ITS highest confidence
            greedy, least, orders = [], [], []
            for (prime, tokens, fills), (ref_at, read), (low_at, _) in zip(
                    paths, want_probes, got_probes):
                p = len(prime)
                at = {qs: j for j, qs in enumerate(read)}
                kept = np.asarray([at[q, fills[q - p]]
                                   for q in sorted({q for q, _ in read})])
                ref_kept, low_kept = ref_at[kept], low_at[kept]
                greedy.append(np.maximum(ref_kept.max(-1) - np.take_along_axis(
                    ref_kept, low_kept.argmax(-1)[:, None], -1)[:, 0], 0.0))
                served = np.argpartition(low_kept, -top_k, axis=-1)[:, -top_k:]
                kth = np.partition(ref_kept, -top_k, axis=-1)[:, -top_k]
                least.append(np.maximum(kth - np.take_along_axis(
                    ref_kept, served, -1).min(-1), 0.0))
                ref_conf, low_conf = confidence(ref_at), confidence(low_at)
                ends = (p + len(tokens)) // b * b
                for p0 in range(p // b * b, ends, b):
                    for s in range(steps):
                        here = [q for q in range(max(p0, p), p0 + b)
                                if fills[q - p] >= s]
                        mine = np.asarray([low_conf[at[q, s]] for q in here])
                        top = np.argsort(-mine, kind="stable")[:counts[s]]
                        got = runner.order_reading(
                            np.asarray([ref_conf[at[q, s]] for q in here]),
                            np.isin(np.arange(len(here)), top), counts[s])
                        if got is not None:
                            orders.append(got)
            probes = {k: sibling.gap_reading(np.concatenate(v),
                                             check["tolerance"])
                      for k, v in (("greedy", greedy),
                                   ("sampled_least", least))}
            order = runner.order_share(orders, check["order_margin"])
            print(json.dumps({
                "variant": name, "seed": seed,
                "primes": [n for n, _, _ in rows], "worst": worst,
                "agreed_share": agreed,
                "routings_differ_share": share, "probes": probes,
                "order": order,
                "order_by_margin": {str(m): runner.order_share(orders, m)
                                    for m in runner.MARGINS},
                "probe_primes": [len(prime) for prime, _, _ in paths],
                "refused_by": [k for k, over in {
                    "direct.tolerance": worst["agreed"] > direct["tolerance"],
                    "direct.routings_limit": share > direct["routings_limit"],
                    "direct.agreed_floor": agreed < direct["agreed_floor"],
                    "direct.keys_tolerance":
                        worst["keys"] > direct["keys_tolerance"],
                    "over_share_limit": max(
                        r["over_share"] for r in probes.values())
                    > check["over_share_limit"],
                    "order_wrong_limit":
                        order["share"] > check["order_wrong_limit"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
