"""The control readings behind ``serve-ling3-longdoc-backlog``'s limits: the
reference's own equations computed AT and BELOW the precision the
configuration states, and with ONE of the family's own choices left out or
moved, held against the float32 reference by the cell's own measures.  The
variants are made here by wrapping the reference's named operations
(``product``, ``softmax``, ``sigmoid``, ``rms_norm``, ``island``, ``carry``)
and choices (``delta_token``, ``recurrence``, ``log_decay``, ``unit``,
``delta_gate``, ``latent_gate``, ``kept_groups``, ``clipped``) — the
reference itself stays one float32 path.  In all of them matrix products
take bfloat16 operands and activations are bfloat16, as the configuration
states:

``as-stated``
    and the configuration's float32 islands (router, softmaxes, the norms'
    statistics, the decay, the write strength, the l2 norms, the CARRY,
    logits) stay float32: what the program computes, so it has to read as
    the program does (the tool's own check)
``chunked``
    that, with the recurrence in a chunked form of the tool's own (chunks of
    64 in blocks of 16 rows, a diagonal block's products against a reference
    row INSIDE the block — positive exponents up to 16 x 5 = 80 —, ``T`` by
    forward substitution, float32): the tool's check of its own chunked
    form, which ``exponents-bf16`` plants its fault in
``one-notch-below``
    every island in bfloat16 and both operands of every matrix product
    rounded to float8_e4m3fn first
``head-decay``
    the decay a HEAD — the mean of its channels' log decays — in place of a
    channel: what ``ops/gdn.py:gdn_scan`` computes
``no-erase``
    the erase term left out: ``S <- diag(alpha) S + k (x) beta v``
``no-bound``
    the gate without its bound: ``g = -exp(A_log) softplus(f + dt_bias)``
``no-l2norm``
    q and k of the delta layers not normalised (q keeps its ``Dk^-1/2``)
``no-delta-gate``
    the delta layers' output not gated
``no-latent-gate``
    the latent layer's output not gated
``no-group-limit``
    the router's plain top-8 of 512, every group kept
``no-clip``
    the SwiGLU's two products not clipped
``carry-bf16``
    the recurrent state handed from token to token in bfloat16
``exponents-bf16``
    the chunked form with the diagonal blocks' positive-exponent factors
    formed in bfloat16

For each it prints the direct check's numbers (``runners/serve_nemotron3.py:
direct_reading``) over rows as long as the check's own — the same seeded
primes of the same compared slots, and seeded tokens where the engine's rows
have generated ones, read at the same two positions — and the probe rule's
reading for a server that computes in the variant.  ``as-stated`` and
``chunked`` have to pass every limit; every other variant has to be refused
by at least one (PERF.md section 7 says what was found).  Each seed's first
line is ``unrelated_row_rms``: the least RMS difference between the float32
reference's logits of two DIFFERENT compared rows — what a slot reads whose
carry, tail and latent rows are another request's, the reading
``direct.row_rms_limit`` has to refuse.  Run once, on the chip; not part of
a run of the cell.

    python3 perf/tools/ling3_lowp.py --seed <n> [<n> ...]
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
CHUNK, BLOCK = 64, 16
# name -> (type the products' operands are rounded to, islands lowered, the
# choice planted: a key of :func:`planted`)
VARIANTS = {
    "as-stated": (None, (), None),
    "chunked": (None, (), "chunked"),
    "one-notch-below": ("float8_e4m3fn", ISLANDS, None),
    "head-decay": (None, (), "head-decay"),
    "no-erase": (None, (), "no-erase"),
    "no-bound": (None, (), "no-bound"),
    "no-l2norm": (None, (), "no-l2norm"),
    "no-delta-gate": (None, (), "no-delta-gate"),
    "no-latent-gate": (None, (), "no-latent-gate"),
    "no-group-limit": (None, (), "no-group-limit"),
    "no-clip": (None, (), "no-clip"),
    "carry-bf16": (None, ("carry",), None),
    "exponents-bf16": (None, (), "exponents-bf16"),
}
PASSES = ("as-stated", "chunked")
HEAD = "td,dv->tv"      # the reference's product that makes the logits
SCORES = "->hqt"        # its product that makes the attention scores


def chunked_recurrence(e_dtype, chunk: int, block: int):
    """``reference.recurrence`` in a chunked form, plain ``jax.numpy`` over
    one row: the decayed products by blocks of ``block`` rows — a block
    under the diagonal against the last row of its column block, both
    exponents non-positive; a DIAGONAL block against its own first row, whose
    column factor ``K exp(gam_first - gam)`` takes a positive exponent up to
    ``block`` times the bound and is formed in ``e_dtype`` (bfloat16: the
    fault ``exponents-bf16`` plants) —, ``T = (I - A)^-1`` by forward
    substitution, everything else float32."""
    import jax
    import jax.numpy as jnp

    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def inverse(a):
        eye = jnp.eye(a.shape[-1], dtype=f32)
        return jax.scipy.linalg.solve_triangular(
            eye - a, jnp.broadcast_to(eye, a.shape), lower=True,
            unit_diagonal=True)

    def recurrence(q, k, v, alpha, beta):
        n, h, dk = k.shape
        dv = v.shape[-1]
        pad = -n % chunk
        q, k, v = (jnp.pad(x.astype(f32), ((0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        g = jnp.pad(jnp.log(alpha.astype(f32)), ((0, pad), (0, 0), (0, 0)))
        beta = jnp.pad(beta.astype(f32), ((0, pad), (0, 0)))
        m, nb = (n + pad) // chunk, chunk // block
        q, k, v, g = (x.reshape(m, chunk, h, -1).transpose(0, 2, 1, 3)
                      for x in (q, k, v, g))            # (m, h, c, d)
        beta = beta.reshape(m, chunk, h).transpose(0, 2, 1)
        gam = jnp.cumsum(g, axis=-2)
        cut = (m, h, nb, block, dk)
        gb, kb, qb = gam.reshape(cut), k.reshape(cut), q.reshape(cut)
        first, last = gb[..., :1, :], gb[..., -1, :]
        up = (kb * jnp.exp(first - gb)).astype(e_dtype).astype(f32)
        down = jnp.exp(gb - first)
        cols = kb * jnp.exp(last[..., None, :] - gb)
        rows = jnp.exp(jnp.minimum(
            gam[:, :, None] - last[..., None, :], 0.0))  # (m, h, J, c, dk)
        same = jnp.eye(nb, dtype=f32)[:, None, :, None]
        under = (jnp.arange(chunk)[:, None] // block
                 > jnp.arange(chunk)[None, :] // block)
        inside = jnp.tril(jnp.ones((block, block), bool))

        def decayed(x, xb):
            diagonal = jnp.where(inside, jnp.einsum(
                "mhbid,mhbjd->mhbij", xb * down, up, precision=hi), 0.0)
            below = jnp.einsum("mhjid,mhjbd->mhijb", x[:, :, None] * rows,
                               cols, precision=hi).reshape(
                                   m, h, chunk, chunk)
            return (diagonal[..., None, :] * same).reshape(
                m, h, chunk, chunk) + jnp.where(under, below, 0.0)

        kk, qk = decayed(k, kb), decayed(q, qb)
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        t = inverse(-jnp.where(strict, beta[..., None] * kk, 0.0))
        grown, end = jnp.exp(gam), gam[..., -1:, :]
        u = jnp.einsum("mhij,mhjd->mhid", t, beta[..., None] * v,
                       precision=hi)
        w = jnp.einsum("mhij,mhjd->mhid", t, beta[..., None] * k * grown,
                       precision=hi)
        q_in, k_out = q * grown, k * jnp.exp(end - gam)
        whole = jnp.exp(end[..., 0, :])

        def chunk_of(s, xs):
            u, w, within, q_in, k_out, whole = xs
            fresh = u - jnp.einsum("hik,hkv->hiv", w, s, precision=hi)
            o = (jnp.einsum("hik,hkv->hiv", q_in, s, precision=hi)
                 + jnp.einsum("hij,hjv->hiv", within, fresh, precision=hi))
            s = s * whole[..., None] + jnp.einsum(
                "hik,hiv->hkv", k_out, fresh, precision=hi)
            return s, o

        _, o = jax.lax.scan(chunk_of, jnp.zeros((h, dk, dv), f32),
                            (u, w, qk, q_in, k_out, whole))
        return o.transpose(0, 2, 1, 3).reshape(m * chunk, h, dv)[:n]

    return recurrence


def planted(choice):
    """``{name: replacement}`` for ``mock.patch.multiple`` over the
    reference, and the keys it adds to the configuration: ONE of the
    family's own choices left out or moved, in the reference's own terms."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_ling3 as ref

    if choice is None:
        return {}, {}
    if choice in ("chunked", "exponents-bf16"):
        e_dtype = jnp.float32 if choice == "chunked" else jnp.bfloat16
        return {"recurrence": chunked_recurrence(e_dtype, CHUNK, BLOCK)}, {}
    if choice == "head-decay":
        plain = ref.log_decay

        def log_decay(f, a_log, dt_bias, bound):
            g = plain(f, a_log, dt_bias, bound)
            return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True),
                                    g.shape)

        return {"log_decay": log_decay}, {}
    if choice == "no-erase":
        def delta_token(state, q, k, v, alpha, beta):
            state = ref.carry(state) * alpha[:, :, None]
            write = beta[:, None] * v
            state = ref.carry(state + k[:, :, None] * write[:, None, :])
            return state, jnp.sum(state * q[:, :, None], axis=1)

        return {"delta_token": delta_token}, {}
    if choice == "no-bound":
        def log_decay(f, a_log, dt_bias, bound):
            return -jnp.exp(ref.island(a_log))[:, None] * jax.nn.softplus(
                ref.island(f) + ref.island(dt_bias))

        return {"log_decay": log_decay}, {}
    if choice == "no-l2norm":
        return {"unit": lambda x: ref.island(x)}, {}
    if choice == "no-delta-gate":
        return {"delta_gate": lambda o, gate: o}, {}
    if choice == "no-latent-gate":
        return {"latent_gate": lambda o, gate: o}, {}
    if choice == "no-group-limit":
        return {"kept_groups": lambda c, cfg: jnp.ones(
            (c.shape[0], cfg["n_group"]), bool)}, {}
    if choice == "no-clip":
        return {"clipped": lambda a, b, limit: (a, b)}, {}
    raise ValueError(f"unknown choice {choice!r}")


@contextlib.contextmanager
def lowered(operands=None, islands=ISLANDS, choice=None):
    """``perf.lib.reference_ling3`` with bfloat16 activations and
    products while this is open (trace inside it), each of ``islands`` in
    bfloat16 too (the others stay float32) and ``choice`` planted.
    ``operands``: a narrower type both operands of every product but the
    router's are rounded to first (the router is an island: its notch below
    float32 is bfloat16)."""
    import jax
    import jax.numpy as jnp

    from perf.lib import reference_ling3 as ref

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

    replaced, _ = planted(choice)
    with mock.patch.multiple(ref, **{
            "product": product, "softmax": softmax, "island": island,
            "carry": carry, "rms_norm": rms_norm, "route": route,
            **replaced}):
        yield


def config_for(config: dict, choice) -> dict:
    """``config`` with what ``choice`` moves in it."""
    _, keys = planted(choice)
    return {**config, **{k: config[v] for k, v in keys.items()}}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, nargs="+", default=[63])
    parser.add_argument("--workload",
                        default="serve-ling3-longdoc-backlog")
    parser.add_argument("--variants", nargs="+", default=list(VARIANTS))
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from perf.lib import harness, reference_ling3
    from progen_tpu.core.cache import enable_compilation_cache
    from progen_tpu.models import bailing_hybrid

    enable_compilation_cache()
    workload = harness.load_workload(args.workload)
    workload["traffic"] = harness.load_traffic(workload["traffic"])
    config = harness.load_config(workload["config"])
    check = workload["correct"]
    direct = check["direct"]
    model_config = bailing_hybrid.BailingHybridConfig.from_dict(config)
    runner = harness.load_module(workload["runner"])
    mimo_runner = harness.load_module("perf/runners/serve_mimo.py")
    reading_of = harness.load_module(
        "perf/runners/serve_nemotron3.py").direct_reading
    sibling = harness.load_module("perf/runners/serve_deepseek_v2.py")
    # the probe rule's rows are drawn as the sibling tool draws them
    probe_rows = harness.load_module("perf/tools/trinity_lowp.py").probe_rows
    slots = workload["engine"]["num_slots"]
    new = check["probe_new_tokens"]
    at = mimo_runner.compared_slots(direct, slots)
    groups = mimo_runner.direct_groups(direct, len(at))
    later = direct["chunks"] * workload["engine"]["chunk_size"]
    # the runner's two reference programs a variant, traced inside the
    # variant once
    forwards = {name: runner.reference_for(
        config_for(config, VARIANTS[name][2] if name else None), workload,
        reference_ling3.forward_row) for name in (None, *args.variants)}

    widths = (runner.SHORT_WIDTH,
              workload["traffic"]["prime_tokens"]["max"] + new)

    def filled(tokens):
        """``tokens`` continued with seeded tokens up to the width of the
        reference program that takes it (causality keeps them out of what
        is read).  The runner pads with token 0: a thousand equal tokens are
        a thousand equal keys, chunks whose ``T`` a variant that rounds it
        cannot hold, and one ``nan`` there reaches every row through the
        latent layer's ``0 * nan``."""
        width = next(w for w in widths if len(tokens) <= w)
        fill = np.random.default_rng(len(tokens)).integers(
            1, model_config.vocab_size, width - len(tokens))
        return np.concatenate([tokens, fill.astype(np.int32)])

    def run(variant, params, direct_rows, rows, primes):
        """``(direct logits (2 N, V), direct choices (2 N, layers, k),
        [probe logits (new, V - 1)])`` of the reference, plain (None) or in
        a variant: the earlier step's rows, then the later one's."""
        if variant is None:
            ctx = contextlib.nullcontext()
        else:
            narrower, islands, choice = VARIANTS[variant]
            ctx = lowered(narrower and getattr(jax.numpy, narrower), islands,
                          choice)
        fwd = forwards[variant]
        logits, chosen = [], []
        with ctx, jax.default_matmul_precision("highest"):
            for tokens in direct_rows:
                where = np.asarray([len(tokens) - 1 - later,
                                    len(tokens) - 1])
                out, sets = fwd(params, filled(tokens), where)
                logits.append(np.asarray(out))
                chosen.append(np.asarray(sets)[:, where].swapaxes(0, 1))
            probes = [np.asarray(fwd(params, filled(rows[i][:p + new]),
                                     np.arange(p - 1, p - 1 + new))[0])[:, 1:]
                for i, p in enumerate(primes)]
        logits = np.stack(logits).swapaxes(0, 1)
        chosen = np.stack(chosen).swapaxes(0, 1)
        return (logits.reshape((-1,) + logits.shape[2:]),
                chosen.reshape((-1,) + chosen.shape[2:]), probes)

    for seed in args.seed:
        params = bailing_hybrid.init_params(
            model_config, jax.random.key(seed & 0xFFFFFFFF),
            bailing_hybrid.bf16_policy())
        vocab = model_config.vocab_size
        _, primes = mimo_runner.direct_primes(direct, workload, seed, vocab,
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
            reading = reading_of(got, want, got_sets, want_sets, groups,
                                 direct)
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
