"""The control readings behind ``serve-mimo-longdoc-backlog``'s limits: the
reference's own equations computed AT and BELOW the precision the
configuration states, and with each of the family's own choices LEFT OUT OR
MOVED, held against the float32 reference by the cell's own measures.  Nine
variants, made here by wrapping the reference's four named operations
(``product``, ``softmax``, ``sigmoid``, ``rms_norm``) and its router, and by
changing the keys of the configuration that the reference reads — the
reference itself stays one float32 path.  In all of them matrix products
take bfloat16 operands and activations are bfloat16, as the configuration
states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the norms'
    statistics, logits) stay float32: what the program computes, so it has
    to read as the program does (the tool's own check)
``islands-bf16``
    every island in bfloat16 (bfloat16 routing among them)
``fp8-operands``
    the islands float32, and both operands of every matrix product the
    configuration states in bfloat16 rounded to float8_e4m3fn first
``no-sink``
    as stated, the sliding layers' softmax without its sink
``sink-on-full``
    as stated, the full layers' softmax WITH a sink (each full layer reads
    the next sliding layer's seeded values)
``no-value-scale``
    as stated, ``attention_value_scale`` 1.0
``window-129``
    as stated, a window one token wider
``sliding-at-full-base``
    as stated, the sliding layers rotated at the full layers' base (1e7)
``one-notch-below``
    ``islands-bf16`` and ``fp8-operands`` together

For each it prints the direct check's numbers
(``runners/serve_mimo.py:direct_reading``) over rows as long as the check's
own — the same seeded primes of the same compared slots, and seeded tokens
where the engine's rows have generated ones, read at the same two positions
— and the probe rule's reading for a server that computes in the variant:
over the probes' primes and ``probe_new_tokens`` seeded continuation tokens
each, the share of positions at which the float32 reference's best allowed
logit exceeds its logit of the token such a server serves greedily by more
than the tolerance.  ``as-stated`` has to pass every limit; every other
variant but ``islands-bf16`` has to be refused by at least one on every
seed (PERF.md section 7 says what was found for ``islands-bf16``).  Each
seed's first line is ``unrelated_row_rms``: the least RMS difference between
the float32 reference's logits of two DIFFERENT compared rows — what a slot
reads whose keys are another request's, the reading ``direct.row_rms_limit``
has to refuse.  Run once, on the chip; not part of a run of the cell.

    python3 perf/tools/mimo_lowp.py --seed <n> [<n> ...]
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
FP8 = "float8_e4m3fn"
# name -> (type the products' operands are rounded to, islands lowered,
#          configuration keys changed, whether the full layers get a sink)
VARIANTS = {
    "as-stated": (None, (), {}, False),
    "islands-bf16": (None, ISLANDS, {}, False),
    "fp8-operands": (FP8, (), {}, False),
    "no-sink": (None, (), {"add_swa_attention_sink_bias": False}, False),
    "sink-on-full": (None, (), {"add_full_attention_sink_bias": True}, True),
    "no-value-scale": (None, (), {"attention_value_scale": 1.0}, False),
    "window-129": (None, (), {"sliding_window": 129}, False),
    "sliding-at-full-base": (None, (), {"swa_rope_theta": 10000000.0}, False),
    "one-notch-below": (FP8, ISLANDS, {}, False),
}
HEAD = "td,dv->tv"      # the reference's product that makes the logits
SCORES = "->kgqt"       # its product that makes the attention scores


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS):
    """``perf.lib.reference_mimo`` with bfloat16 activations and products
    while this is open (trace inside it), and each of ``islands`` in
    bfloat16 too; the others stay float32.  ``operands``: a narrower type
    both operands of every product but the router's are rounded to first
    (the router is an island: its notch below float32 is bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_mimo as ref

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
                             rms_norm=rms_norm, route=route):
        yield


def with_full_sinks(params):
    """The weights with a sink in the full layers too: each reads the
    seeded values of the next sliding layer (the last full layer the
    previous one's)."""
    layers = params["layers"]
    sinks = [layer["attn"].get("sink") for layer in layers]
    donors = [s for s in sinks if s is not None]
    out = []
    for i, layer in enumerate(layers):
        if sinks[i] is None:
            later = [s for s in sinks[i + 1:] if s is not None]
            layer = {**layer, "attn": {
                **layer["attn"], "sink": (later or donors[::-1])[0]}}
        out.append(layer)
    return {**params, "layers": out}


def variant_forward(name: str, config: dict):
    """``(forward_row of the variant, context to trace and call it in,
    weights -> the variant's weights)``; ``name`` None: the float32
    reference itself."""
    import jax

    from perf.lib import reference_mimo

    if name is None:
        return (reference_mimo.forward_row, contextlib.nullcontext,
                lambda p: p)
    narrower, islands, changed, full_sinks = VARIANTS[name]
    cfg = {**config, **changed}

    def forward_row(params, tokens, _, **kwargs):
        return reference_mimo.forward_row(params, tokens, cfg, **kwargs)

    return (forward_row,
            lambda: lowered(narrower and getattr(jax.numpy, narrower),
                            islands),
            with_full_sinks if full_sinks else (lambda p: p))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[54])
    parser.add_argument("--workload", default="serve-mimo-longdoc-backlog")
    parser.add_argument("--variants", nargs="+", default=list(VARIANTS))
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.models import mimo_v2

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    direct = check["direct"]
    model_config = mimo_v2.MiMoV2Config.from_dict(config)
    runner = harness.load_module(workload["runner"])
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    # the probe rule's rows are drawn as the sibling tool draws them
    probe_rows = harness.load_module("perf/tools/trinity_lowp.py").probe_rows
    slots = workload["engine"]["num_slots"]
    new = check["probe_new_tokens"]
    at = runner.compared_slots(direct, slots)
    groups = runner.direct_groups(direct, len(at))
    later = direct["chunks"] * workload["engine"]["chunk_size"]

    def run(variant, params, direct_rows, rows, primes):
        """``(direct logits (2 N, V), direct choices (2 N, layers, k),
        [probe logits (new, V - 1)])`` of the reference, plain (None) or in
        a variant: the earlier step's rows, then the later one's."""
        forward_row, ctx, weights = variant_forward(variant, config)
        # one pair of programs a variant, traced inside the variant, padded
        # as the runner's ``reference_for`` pads them
        fwd = runner.reference_for(config, workload, forward_row)
        params = weights(params)
        logits, chosen = [], []
        with ctx(), jax.default_matmul_precision("highest"):
            for tokens in direct_rows:
                where = np.asarray([len(tokens) - 1 - later,
                                    len(tokens) - 1])
                out, sets = fwd(params, tokens, where)
                logits.append(np.asarray(out, np.float32))
                chosen.append(np.asarray(sets)[:, where].swapaxes(0, 1))
            probes = [np.asarray(fwd(params, rows[i], np.arange(
                p - 1, p - 1 + new))[0], np.float32)[:, 1:]  # token 0 masked
                for i, p in enumerate(primes)]
        logits = np.stack(logits).swapaxes(0, 1)
        chosen = np.stack(chosen).swapaxes(0, 1)
        return (logits.reshape((-1,) + logits.shape[2:]),
                chosen.reshape((-1,) + chosen.shape[2:]), probes)

    for seed in args.seed:
        params = mimo_v2.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            mimo_v2.bf16_policy())
        vocab = model_config.vocab_size
        _, primes = runner.direct_primes(direct, workload, seed, vocab,
                                         slots)
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
                    "direct.tolerance": reading["worst_agreed"]
                    > direct["tolerance"],
                    "direct.agreed_floor": reading["agreed_share"]
                    < direct["agreed_floor"],
                    "direct.routings_limit": reading[
                        "routings_differ_share"] > direct["routings_limit"],
                    "over_share_limit": probes["over_share"]
                    > check["over_share_limit"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params, inputs
    return 0


if __name__ == "__main__":
    sys.exit(main())
