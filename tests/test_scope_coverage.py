"""What the program traces lies under the program's own names: the admission
program and the chunk program of each of the thirteen families at its tiny
configuration (``tools/program_hash.py`` builds them) and ProGen's train
step, walked equation by equation — sub-jaxprs of ``scan`` / ``while`` /
``cond`` / ``pjit`` / ``custom_vjp`` included — with each equation's name
stack read as ``perf/lib/xplane.py:scope_of`` reads a device operation's
``tf_op``.  (a) every product, kernel, sort, running sum, differentiation
rule and every gather or scatter over a cache leaf has a scope; (b) every
group found is read by a ``per_layer`` entry of ``BENCHMARK.json`` whose
reader is ``perf/readers/scope_share.py``.  Nothing compiles.  That the
names change no program is ``tests/test_program_identity.py``'s."""

import functools
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from perf.lib.xplane import UNSCOPED, group_of, scope_of

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "program_hash", ROOT / "tools" / "program_hash.py")
program_hash = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(program_hash)

pytestmark = pytest.mark.serving

READER = "perf/readers/scope_share.py"
# what must lie under a name wherever it stands ...
NAMED = {"dot_general", "conv_general_dilated", "pallas_call", "sort",
         "cumsum", "custom_jvp_call", "custom_vjp_call"}
NAMED_PREFIXES = ("ragged_dot",)
# ... and what must where its operand is a leaf of the slots' caches
OVER_A_LEAF = ("scatter", "gather")


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            v = getattr(v, "jaxpr", v)
            if hasattr(v, "eqns"):
                yield v


def walk(jaxpr, path=""):
    """``(equation, its name path)`` of every equation: a sub-jaxpr's name
    stacks start anew, so the path of an equation inside one is the path
    of the equation that holds it and then its own."""
    for eqn in jaxpr.eqns:
        here = "/".join(p for p in (path, str(eqn.source_info.name_stack))
                        if p)
        yield eqn, here
        for sub in _subjaxprs(eqn):
            yield from walk(sub, here)


def _must_be_named(eqn, leaves) -> bool:
    name = eqn.primitive.name
    if name in NAMED or name.startswith(NAMED_PREFIXES):
        return True
    if name.startswith(OVER_A_LEAF) and eqn.invars:
        aval = eqn.invars[0].aval
        return (tuple(aval.shape[1:]), aval.dtype) in leaves
    return False


def check(jaxpr, leaves=frozenset()):
    """``(the equations of (a) that no scope names, the groups found)``."""
    bare, groups = [], set()
    for eqn, path in walk(jaxpr):
        scope = scope_of(f"{path}:{eqn.primitive.name}")
        if scope != UNSCOPED:
            groups.add(group_of(scope))
        elif _must_be_named(eqn, leaves):
            bare.append(f"{eqn.primitive.name} under {path or '(nothing)'!r}")
    return bare, groups


@functools.cache
def groups_read() -> frozenset:
    """The groups in the ``args.groups`` of the benchmark's entries that
    ``perf/readers/scope_share.py`` reads."""
    with open(ROOT / "BENCHMARK.json") as f:
        entries = json.load(f)["per_layer"]
    out = set()
    for entry in entries:
        with open(ROOT / "perf" / "metrics" / f"{entry['name']}.json") as f:
            metric = json.load(f)
        if metric.get("reader") == READER:
            out.update(metric["args"]["groups"])
    return frozenset(out)


@functools.cache
def _programs(family):
    """One engine a family for its two cases, and its cache leaves as
    ``(a row's shape, dtype)``."""
    engine = program_hash.build_engine(family)
    leaves = frozenset((tuple(a.shape[1:]), a.dtype)
                       for a in jax.tree.leaves(engine.state["caches"]))
    return program_hash.program_shapes(engine), leaves


def _assert_covered(jaxpr, leaves=frozenset()) -> set:
    """The groups found, once (a) and (b) hold."""
    bare, groups = check(jaxpr, leaves)
    assert not bare, (
        "no jax.named_scope('group.part') around:\n  " + "\n  ".join(
            sorted(set(bare))))
    assert groups, "the walk found no scope at all"
    unread = groups - groups_read()
    assert not unread, (
        f"scopes in the groups {sorted(unread)} that no per_layer entry "
        f"reads through {READER}: add an entry (perf/metrics/, "
        "BENCHMARK.json) or use a group that has one")
    return groups


@pytest.mark.parametrize("program", program_hash.PROGRAMS)
@pytest.mark.parametrize("family", program_hash.FAMILIES)
def test_the_engines_program_is_named(family, program):
    programs, leaves = _programs(family)
    body, shapes = programs[program]
    _assert_covered(jax.make_jaxpr(body)(*shapes).jaxpr, leaves)


def test_progens_train_step_is_named():
    """Forward, backward and update, through the Pallas kernels the train
    cell runs (``perf/workloads/train-small-uniref.json``)."""
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.train.optimizer import make_optimizer
    from progen_tpu.train.step import make_train_functions

    config = ProGenConfig(num_tokens=32, dim=32, seq_len=16, depth=3,
                          window_size=8, global_mlp_depth=1, heads=2,
                          dim_head=16, ff_mult=2)
    model = ProGen(config=config, policy=make_policy(True),
                   attn_impl="pallas", sgu_impl="pallas")
    fns = make_train_functions(model, make_optimizer(),
                               jnp.zeros((2, config.seq_len), jnp.int32))
    state = jax.eval_shape(fns.init_state, jax.random.key(0))
    batch = jax.ShapeDtypeStruct((2, config.seq_len + 1), jnp.int32)
    groups = _assert_covered(
        jax.make_jaxpr(fns.train_step)(state, batch).jaxpr)
    assert {"attn", "ffn", "sgu", "norm", "embed", "head", "loss",
            "optim"} <= groups


_SCOPE_SITE = re.compile(
    r"named_scope\((?P<call>[^)]*)\)|\bscope\s*=\s*(?P<kw>[^\n]*)")
_LITERAL = re.compile(r"\"([a-z0-9_]+\.[a-z0-9_.]+)\"")


def test_no_scope_in_the_package_is_in_a_group_nothing_reads():
    """The names as they are WRITTEN, so that a path the tiny programs do
    not trace (a paged gate, a mesh) is held to the rule too."""
    found = {}
    for path in sorted((ROOT / "progen_tpu").rglob("*.py")):
        for site in _SCOPE_SITE.finditer(path.read_text()):
            for scope in _LITERAL.findall(site["call"] or site["kw"]):
                found.setdefault(group_of(scope), set()).add(
                    f"{scope} ({path.relative_to(ROOT)})")
    assert len(found) >= 10, sorted(found)
    unread = {g: sorted(s) for g, s in found.items()
              if g not in groups_read()}
    assert not unread, f"no per_layer entry reads: {unread}"
