"""The control readings behind ``serve-glm52-longdoc-backlog``'s limits: the
reference's own equations computed AT and BELOW the precision the
configuration states, and with each of the family's own choices LEFT OUT OR
MOVED, held against the float32 reference by the cell's own measures.
Twelve variants, made here by wrapping the reference's four named operations
(``product``, ``softmax``, ``sigmoid``, ``rms_norm``) and its router, and by
changing the keys of the configuration that the reference reads — the
reference itself stays one float32 path.  In all of them matrix products
take bfloat16 operands and activations are bfloat16, as the configuration
states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the
    indexer's scores and top-k, the norms' statistics, logits) stay float32:
    what the program computes, so it has to read as the program does (the
    tool's own check)
``islands-bf16``
    every island in bfloat16 (bfloat16 routing and bfloat16 indexer scores
    among them)
``fp8-operands``
    the islands float32, and both operands of every matrix product the
    configuration states in bfloat16 rounded to float8_e4m3fn first
``no-selection``
    as stated, every visible key attended (``index_topk`` past any length)
``top-1024``
    as stated, half the keys kept
``no-relu``
    as stated, the indexer's score without its ReLU
``unweighted-heads``
    as stated, the indexer's heads summed with equal weights
``shared-attends-all``
    as stated, a SHARED layer attends every visible key (layers 1-3 without
    the selection they should borrow)
``shared-selects-itself``
    as stated, a shared layer SELECTS FOR ITSELF: layers 1-3 run layer 0's
    indexer weights on their own input instead of borrowing layer 0's result
``full-borrows``
    as stated, a full layer past the first drops its own selection for the
    one before it (layer 4 reads layer 0's)
``half-split-rope``
    as stated, the attention's rotations pair ``(i, i + 32)`` where pairs
    ``(2i, 2i + 1)`` are stated
``half-split-indexer-rope``
    as stated, the indexer's rotations half-split

For each it prints the direct check's numbers
(``runners/serve_mimo.py:direct_reading`` and
``runners/serve_dots3.py:selection_reading``) over rows as long as the
check's own — the same seeded primes of the same compared slots, and seeded
tokens where the engine's rows have generated ones, read at the same two
positions — and the probe rule's reading for a server that computes in the
variant.  ``as-stated`` has to pass every limit; every other variant but
``islands-bf16`` has to be refused by at least one on every seed (PERF.md
section 7 says what was found for ``islands-bf16``).  Each seed's first line
is ``unrelated_row_rms``: the least RMS difference between the float32
reference's logits of two DIFFERENT compared rows — what a slot reads whose
rows are another request's.  Run once, on the chip; not part of a run of the
cell.

    python3 perf/tools/glm52_lowp.py --seed <n> [<n> ...]
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
#          configuration keys changed)
VARIANTS = {
    "as-stated": (None, (), {}),
    "islands-bf16": (None, ISLANDS, {}),
    "fp8-operands": (FP8, (), {}),
    "no-selection": (None, (), {"index_topk": 2 ** 30}),
    "top-1024": (None, (), {"index_topk": 1024}),
    "no-relu": (None, (), {"index_relu": False}),
    "unweighted-heads": (None, (), {"index_head_weights": False}),
    "shared-attends-all": (None, (), {"shared_selection": "none"}),
    "shared-selects-itself": (None, (), {"shared_selection": "own"}),
    "full-borrows": (None, (), {"full_selection": "borrow"}),
    "half-split-rope": (None, (), {"rope_interleave": False}),
    "half-split-indexer-rope": (None, (), {"indexer_rope_interleave": False}),
}
HEAD = "td,dv->tv"              # the reference's product that makes the logits
SCORES = ("->hqt", "->jqt")     # its attention scores and its indexer's


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS):
    """``perf.lib.reference_glm52`` with bfloat16 activations and products
    while this is open (trace inside it), and each of ``islands`` in
    bfloat16 too; the others stay float32.  ``operands``: a narrower type
    both operands of every product but the router's are rounded to first
    (the router is an island: its notch below float32 is bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_glm52 as ref

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
        # the scores stay as wide as the softmax (or the selection) that
        # takes them: the program accumulates and keeps them in float32
        return out.astype(stat("softmax") if spec.endswith(SCORES) else low)

    def softmax(x):
        return jax.nn.softmax(x.astype(stat("softmax")), axis=-1).astype(low)

    def sigmoid(x):         # float32 inside, bfloat16 out
        return jax.nn.sigmoid(x.astype(stat("softmax"))).astype(low)

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


def variant_forward(name: str, config: dict):
    """``(forward_row of the variant, context to trace and call it in)``;
    ``name`` None: the float32 reference itself."""
    import jax

    from perf.lib import reference_glm52

    if name is None:
        return reference_glm52.forward_row, contextlib.nullcontext
    narrower, islands, changed = VARIANTS[name]
    cfg = {**config, **changed}

    def forward_row(params, tokens, _, **kwargs):
        return reference_glm52.forward_row(params, tokens, cfg, **kwargs)

    return forward_row, lambda: lowered(
        narrower and getattr(jax.numpy, narrower), islands)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[60])
    parser.add_argument("--workload", default="serve-glm52-longdoc-backlog")
    parser.add_argument("--variants", nargs="+", default=list(VARIANTS))
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.models import glm_dsa

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    direct = check["direct"]
    model_config = glm_dsa.GLMDSAConfig.from_dict(config)
    runner = harness.load_module(workload["runner"])
    mimo = harness.load_module("perf/runners/serve_mimo.py")
    dots3 = harness.load_module("perf/runners/serve_dots3.py")
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    # the probe rule's rows are drawn as the sibling tool draws them
    probe_rows = harness.load_module("perf/tools/trinity_lowp.py").probe_rows
    slots = workload["engine"]["num_slots"]
    new = check["probe_new_tokens"]
    at = mimo.compared_slots(direct, slots)
    groups = mimo.direct_groups(direct, len(at))
    later = direct["chunks"] * workload["engine"]["chunk_size"]

    def run(variant, params, direct_rows, rows, primes):
        """``(direct logits (2 N, V), direct choices (2 N, layers, k),
        selections [(set, context)] in the runner's order, [probe logits
        (new, V - 1)])`` of the reference, plain (None) or in a variant."""
        forward_row, ctx = variant_forward(variant, config)
        # one pair of programs a variant, traced inside the variant, padded
        # as the runner's ``reference_for`` pads them
        fwd = runner.reference_for(config, workload, forward_row)
        logits, chosen, picked = [], [], []
        with ctx(), jax.default_matmul_precision("highest"):
            for tokens in direct_rows:
                where = np.asarray([len(tokens) - 1 - later,
                                    len(tokens) - 1])
                out, sets, selected = fwd(params, tokens, where)
                logits.append(np.asarray(out, np.float32))
                chosen.append(np.asarray(sets)[:, where].swapaxes(0, 1))
                selected = np.asarray(selected)
                picked += [(set(np.flatnonzero(selected[layer, step])
                                .tolist()), int(where[step]) + 1)
                           for step in range(2)
                           for layer in range(selected.shape[0])]
            probes = [np.asarray(fwd(params, rows[i], np.arange(
                p - 1, p - 1 + new))[0], np.float32)[:, 1:]  # token 0 masked
                for i, p in enumerate(primes)]
        logits = np.stack(logits).swapaxes(0, 1)
        chosen = np.stack(chosen).swapaxes(0, 1)
        return (logits.reshape((-1,) + logits.shape[2:]),
                chosen.reshape((-1,) + chosen.shape[2:]), picked, probes)

    for seed in args.seed:
        params = glm_dsa.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            glm_dsa.bf16_policy())
        vocab = model_config.vocab_size
        _, primes = mimo.direct_primes(direct, workload, seed, vocab, slots)
        rng = np.random.default_rng(seed)
        direct_rows = [np.concatenate([primes[i], rng.integers(
            1, vocab, 1 + later).astype(np.int32)]) for i in at]
        inputs = (params, direct_rows,
                  *probe_rows(sibling, workload, seed, vocab))
        want, want_sets, want_picked, want_probes = run(None, *inputs)
        apart = np.sqrt(((want[:, None] - want[None]) ** 2).mean(-1))
        print(json.dumps({
            "seed": seed, "unrelated_row_rms": float(
                apart[~np.eye(len(want), dtype=bool)].min()),
            "logit_std": float(want.std(axis=-1).mean())}), flush=True)
        for name in args.variants:
            got, got_sets, got_picked, got_probes = run(name, *inputs)
            reading = mimo.direct_reading(got, want, got_sets, want_sets,
                                          groups, direct)
            selection = dots3.selection_reading(
                got_picked, [s for s, _ in want_picked],
                direct["selected_keys_limit"])
            # a server computing in the variant serves its best allowed token
            greedy = [sibling.probe_gaps(ref_at, low_at.argmax(-1), None)
                      for ref_at, low_at in zip(want_probes, got_probes)]
            probes = sibling.gap_reading(np.concatenate(greedy),
                                         check["tolerance"])
            print(json.dumps({
                "variant": name, "seed": seed,
                "primes": [len(primes[i]) for i in at], "direct": reading,
                "selection": selection,
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
                    "direct.selected_keys_limit": not selection["ok"],
                    "over_share_limit": probes["over_share"]
                    > check["over_share_limit"],
                }.items() if over],
                "device": jax.devices()[0].device_kind}), flush=True)
        del params, inputs
    return 0


if __name__ == "__main__":
    sys.exit(main())
