"""The control readings behind ``serve-nemotron3-longgen-backlog``'s limits:
the reference's own equations computed AT and BELOW the precision the
configuration states, held against the float32 reference by the cell's own
measures.  Four variants, made here by wrapping the reference's six named
operations (``product``, ``softmax``, ``sigmoid``, ``rms_norm``, ``island``,
``carry``) and its router — the reference itself stays one float32 path.  In
all of them matrix products take bfloat16 operands and activations are
bfloat16, as the configuration states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the norms'
    statistics, the recurrence's step, decay and CARRY, logits) stay
    float32: what the program computes, so it has to read as the program
    does (the tool's own check)
``carry-bf16``
    that, with the recurrent state alone handed from token to token in
    bfloat16: the whole state re-rounded every token
``islands-bf16``
    every island in bfloat16 (bfloat16 routing and the carry among them)
``one-notch-below``
    that, and both operands of every matrix product the configuration
    states in bfloat16 rounded to float8_e4m3fn first

For each it prints the direct check's numbers (``runners/serve_nemotron3.py:
direct_reading``: the largest RMS logit difference of any compared row, by
group, the RMS over all of them, and the share of (token, expert layer,
chosen expert) assignments whose expert is not among the reference's) over
rows as long as the check's own — the same seeded primes of the same
compared slots, and seeded tokens where the engine's rows have generated
ones, read at the same two positions — and the probe rule's reading for a
server that computes in the variant: over the probes' primes and
``probe_new_tokens`` seeded continuation tokens each, the share of positions
at which the float32 reference's best allowed logit exceeds its logit of the
token such a server serves greedily by more than the tolerance.
``as-stated`` has to pass every limit; ``one-notch-below`` has to be refused
by at least one, and so should ``carry-bf16`` (PERF.md section 7 says what
was found).  Each seed's first line is ``unrelated_row_rms``: the least RMS
difference between the float32 reference's logits of two DIFFERENT compared
rows — what a slot reads whose carry, tail and keys are another request's,
the reading ``direct.row_rms_limit`` has to refuse.  Run once, on the chip;
not part of a run of the cell.

    python3 perf/tools/nemotron3_lowp.py --seed <n> [<n> ...]
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

ISLANDS = ("router", "softmax", "norms", "logits", "recurrence", "carry")
# name -> (type the products' operands are rounded to, islands lowered)
VARIANTS = {
    "as-stated": (None, ()),
    "carry-bf16": (None, ("carry",)),
    "islands-bf16": (None, ISLANDS),
    "one-notch-below": ("float8_e4m3fn", ISLANDS),
}
HEAD = "td,dv->tv"      # the reference's product that makes the logits
SCORES = "->kgqt"       # its product that makes the attention scores


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS):
    """``perf.lib.reference_nemotron3`` with bfloat16 activations and
    products while this is open (trace inside it), and each of ``islands``
    in bfloat16 too; the others stay float32.  ``operands``: a narrower
    type both operands of every product but the router's are rounded to
    first (the router is an island: its notch below float32 is
    bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_nemotron3 as ref

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

    def island(x):
        """A value of the recurrence's elementwise arithmetic: float32 as
        stated, or rounded to bfloat16 in a float32 container."""
        return x.astype(stat("recurrence")).astype(f32)

    def carry(state):
        return state.astype(stat("carry")).astype(f32)

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
                             island=island, carry=carry, rms_norm=rms_norm,
                             route=route):
        yield


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[49])
    parser.add_argument("--workload",
                        default="serve-nemotron3-longgen-backlog")
    parser.add_argument("--variants", nargs="+", default=list(VARIANTS))
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness, reference_nemotron3
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW
    from progen_tpu.models import nemotron_h

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    direct = check["direct"]
    model_config = nemotron_h.NemotronHConfig.from_dict(config)
    runner = harness.load_module(workload["runner"])
    lfm2_runner = harness.load_module("perf/runners/serve_lfm2.py")
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    # the probe rule's rows are drawn as the sibling tool draws them
    probe_rows = harness.load_module("perf/tools/trinity_lowp.py").probe_rows
    slots = workload["engine"]["num_slots"]
    admit_rows = max(1, slots // SLOTS_PER_ADMIT_ROW)
    new = check["probe_new_tokens"]
    at = lfm2_runner.compared_slots(direct, admit_rows, slots)
    groups = lfm2_runner.direct_groups(admit_rows, len(at))
    later = direct["chunks"] * workload["engine"]["chunk_size"]
    # one program per variant, traced inside the variant once: rows and
    # positions padded as the runner's ``reference_for`` pads them
    forwards = {name: jax.jit(
        lambda p, t, k: reference_nemotron3.forward_row(
            p, t, config, q_block=runner.QUERY_BLOCK, logit_positions=k))
        for name in (None, *args.variants)}
    width = max(workload["traffic"]["prime_tokens"]["max"] + new,
                lfm2_runner.direct_width(workload))

    def padded(fwd, params, tokens, positions):
        k = len(positions)
        logits, chosen = fwd(
            params, np.pad(tokens, (0, width - len(tokens))),
            np.pad(positions, (0, new - k), mode="edge"))
        return logits[:k], chosen

    def run(variant, params, direct_rows, rows, primes):
        """``(direct logits (2 N, V), direct choices (2 N, layers, k),
        [probe logits (new, V - 1)])`` of the reference, plain (None) or in
        a variant: the earlier step's rows, then the later one's."""
        if variant is None:
            ctx = contextlib.nullcontext()
        else:
            narrower, islands = VARIANTS[variant]
            ctx = lowered(narrower and getattr(jax.numpy, narrower), islands)
        fwd = forwards[variant]
        logits, chosen = [], []
        with ctx, jax.default_matmul_precision("highest"):
            for tokens in direct_rows:
                where = np.asarray([len(tokens) - 1 - later,
                                    len(tokens) - 1])
                out, sets = padded(fwd, params, tokens, where)
                logits.append(np.asarray(out))
                chosen.append(np.asarray(sets)[:, where].swapaxes(0, 1))
            probes = [np.asarray(padded(fwd, params, rows[i], np.arange(
                p - 1, p - 1 + new))[0])[:, 1:]     # token 0 is masked out
                for i, p in enumerate(primes)]
        logits = np.stack(logits).swapaxes(0, 1)
        chosen = np.stack(chosen).swapaxes(0, 1)
        return (logits.reshape((-1,) + logits.shape[2:]),
                chosen.reshape((-1,) + chosen.shape[2:]), probes)

    for seed in args.seed:
        params = nemotron_h.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            nemotron_h.bf16_policy())
        vocab = model_config.vocab_size
        _, primes = runner.direct_primes(lfm2_runner, direct, seed, vocab,
                                         admit_rows, slots)
        rng = np.random.default_rng(seed)
        direct_rows = [np.concatenate([primes[i], rng.integers(
            1, vocab, 1 + later).astype(np.int32)]) for i in at]
        inputs = (params, direct_rows,
                  *probe_rows(sibling, workload, seed, vocab))
        want, want_sets, want_probes = run(None, *inputs)
        apart = np.sqrt(((want[:, None] - want[None]) ** 2).mean(-1))
        print(json.dumps({
            "seed": seed, "unrelated_row_rms": float(
                apart[~np.eye(len(want), dtype=bool)].min()),
            "logit_std": float(want.std(axis=-1).mean())}), flush=True)
        for name in args.variants:
            got, got_sets, got_probes = run(name, *inputs)
            reading = runner.direct_reading(got, want, got_sets, want_sets,
                                            groups, direct)
            # a server computing in the variant serves its best allowed token
            greedy = [sibling.probe_gaps(ref_at, low_at.argmax(-1), None)
                      for ref_at, low_at in zip(want_probes, got_probes)]
            probes = sibling.gap_reading(np.concatenate(greedy),
                                         check["tolerance"])
            print(json.dumps({
                "variant": name, "seed": seed,
                "primes": [len(primes[i]) for i in at], "direct": reading,
                "probes": {"greedy": probes}, "probe_primes": inputs[-1],
                "refused_by": [k for k, over in {
                    "direct.row_rms_limit": max(
                        reading["row_rms_max"].values())
                    > direct["row_rms_limit"],
                    "direct.rms_limit": reading["rms"] > direct["rms_limit"],
                    "direct.assignments_limit": reading[
                        "assignments_differ_share"]
                    > direct["assignments_limit"],
                    "over_share_limit": probes["over_share"]
                    > check["over_share_limit"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params, inputs
    return 0


if __name__ == "__main__":
    sys.exit(main())
