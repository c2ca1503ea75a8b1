"""The text of the engine's programs, hashed: one head per family and program.

For each of the thirteen model families at its tiny test configuration
(``tests/*_tiny.py``; ProGen's is ``tests/test_serving.py``'s ``CFG``) this
builds ``ServingEngine`` as the family's engine test does, takes
``jax.make_jaxpr`` of the admission program at the smallest prefill bucket
and of the chunk program at the shapes ``aot_warmup`` compiles them for, and
prints ``family.program -> sha256 head`` of the jaxpr's text with object
addresses struck out.  The weights are shapes: nothing runs and nothing
compiles.

A PR that says "the other families' programs are the parent's letter for
letter" shows it with this: ``tests/test_program_identity.py`` holds each
head to ``tests/golden/programs.json``.  The configurations are tiny and the
trace is the CPU's, so a Pallas lowering that only a TPU takes is not in the
text (``tests/test_chip_compile.py`` compiles those).

Usage: ``python tools/program_hash.py`` prints the heads and says which
differ from the golden file; ``--write`` rewrites the golden file.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

GOLDEN = os.path.join(REPO, "tests", "golden", "programs.json")
FAMILIES = ("progen", "longcat", "deepseek_v2", "trinity", "granite", "sdar",
            "lfm2", "nemotron_h", "mimo_v2", "dots3", "glm_dsa", "qwen3_next",
            "bailing_hybrid")
PROGRAMS = ("admit", "chunk")
HEAD = 16
# what the families' engine tests build, over two admission rows of slots
# (``max_len`` is capped at the model's longest sequence: ProGen's 24)
ENGINE = dict(chunk_size=4, max_len=32)
_ADDRESS = re.compile(r"0x[0-9a-fA-F]+")


def build_engine(family: str):
    """The family's engine at its tiny configuration, float32, over weights
    that are SHAPES (``jax.eval_shape`` of what the family's tests
    initialise): a program's text needs no weight, and nothing compiles."""
    import importlib

    import jax
    import jax.numpy as jnp

    from progen_tpu.decode import ServingEngine
    from progen_tpu.decode.engine import SLOTS_PER_ADMIT_ROW

    if family == "progen":
        from progen_tpu.core.precision import make_policy
        from progen_tpu.models import ProGen
        from progen_tpu.parallel import unbox
        from tests.test_serving import CFG as config

        policy = make_policy(False)
        tokens = jnp.zeros((2, config.seq_len), jnp.int32)
        params = jax.eval_shape(lambda: unbox(ProGen(
            config=config, policy=policy).init(jax.random.key(7), tokens)))
    else:
        tiny = importlib.import_module(f"tests.{family}_tiny")
        config = tiny.TINY
        made = {}

        def weights():
            # past the module's cache: a traced call must not fill it
            params, made["policy"] = getattr(
                tiny.make, "__wrapped__", tiny.make)()
            return params

        params, policy = jax.eval_shape(weights), made["policy"]
    return ServingEngine(config, params, policy=policy,
                         num_slots=2 * SLOTS_PER_ADMIT_ROW, **ENGINE)


def program_shapes(engine) -> dict:
    """``program -> (body, argument shapes)``: the admission program at the
    smallest bucket and the chunk program, at what ``aot_warmup`` lowers."""
    import jax
    import jax.numpy as jnp

    as_shape = partial(jax.tree.map,
                       lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype))
    sd = jax.ShapeDtypeStruct
    s, rows, lay = engine.num_slots, engine.admit_rows, engine._layout
    p_pad = engine.family.buckets(engine.max_len - 1, engine.max_len)[0]
    params, state = as_shape(engine._params), as_shape(engine.state)
    prefill = [sd((rows, p_pad), jnp.int32), sd((rows,), jnp.int32),
               sd((rows,), jnp.int32), sd((rows,), jnp.uint32),
               sd((rows,), jnp.int32), sd((rows,), jnp.float32),
               sd(engine._lmask_shape(rows), jnp.bool_)]
    return {
        "admit": (engine._admit_impl, (
            params, state, sd((s,), jnp.int32), sd((s,), jnp.bool_),
            *prefill, *as_shape(lay.write_tables(rows)))),
        "chunk": (engine._chunk_impl(), (
            params, state, *as_shape(lay.chunk_operands()))),
    }


def program_head(body, shapes) -> str:
    """The first HEAD hex digits of the sha256 of ``body``'s jaxpr at
    ``shapes``, with what is not text of the program (objects' addresses in
    a primitive's parameters) struck out."""
    import jax

    text = _ADDRESS.sub("0x", str(jax.make_jaxpr(body)(*shapes)))
    return hashlib.sha256(text.encode()).hexdigest()[:HEAD]


def heads() -> dict:
    """``"family.program" -> head``."""
    return {f"{family}.{program}": program_head(body, shapes)
            for family in FAMILIES
            for program, (body, shapes) in program_shapes(
                build_engine(family)).items()}


def read_golden() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--write", action="store_true",
                    help="rewrite tests/golden/programs.json")
    args = ap.parse_args(argv)
    found = heads()
    golden = {} if args.write else read_golden()
    for name, head in found.items():
        mark = "" if args.write or golden.get(name) == head else "  CHANGED"
        print(f"{name:24s} {head}{mark}")
    if args.write:
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump(found, f, indent=1, sort_keys=True)
            f.write("\n")
        return 0
    return int(found != golden)


if __name__ == "__main__":
    sys.exit(main())
