"""graftcheck unit tests: one true-positive and one true-negative per rule,
suppression + baseline mechanics, JSON output schema, CLI exit codes, and
the repo-wide zero-findings gate that makes the analyzer a tier-1 check."""

import ast
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from progen_tpu import analysis
from progen_tpu.analysis import cfg as cfg_mod
from progen_tpu.analysis import engine

pytestmark = pytest.mark.analysis

REPO_ROOT = Path(__file__).resolve().parent.parent

analysis.load_rules()


def check(source, path="progen_tpu/some/module.py", rules=None):
    return engine.check_source(textwrap.dedent(source), path=path, rules=rules)


def rule_names(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# trace-safety
# ---------------------------------------------------------------------------


def test_trace_safety_flags_print_in_jitted():
    findings = check(
        """
        import jax

        @jax.jit
        def step(x):
            print("inside trace")
            return x * 2
        """,
        rules=["trace-safety"],
    )
    assert rule_names(findings) == ["trace-safety"]
    assert "jax.debug.print" in findings[0].message


def test_trace_safety_flags_time_reachable_from_scan():
    findings = check(
        """
        import time
        from jax import lax

        def body(carry, x):
            t = time.perf_counter()
            return carry + x + t, x

        def run(xs):
            return lax.scan(body, 0.0, xs)
        """,
        rules=["trace-safety"],
    )
    assert rule_names(findings) == ["trace-safety"]


def test_trace_safety_flags_np_random_via_callee():
    # reachability must propagate through same-module calls
    findings = check(
        """
        import jax
        import numpy as np

        def helper(x):
            return x + np.random.rand()

        @jax.jit
        def step(x):
            return helper(x)
        """,
        rules=["trace-safety"],
    )
    assert rule_names(findings) == ["trace-safety"]


def test_trace_safety_ignores_host_driver_code():
    findings = check(
        """
        import time

        def train_loop(n):
            t0 = time.perf_counter()
            for i in range(n):
                print("host-side logging is fine", i)
            return time.perf_counter() - t0
        """,
        rules=["trace-safety"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# rng-reuse / rng-split-dropped
# ---------------------------------------------------------------------------


def test_rng_reuse_flags_double_consumption():
    findings = check(
        """
        import jax

        def sample(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
        """,
        rules=["rng-reuse"],
    )
    assert rule_names(findings) == ["rng-reuse"]
    assert "'key'" in findings[0].message


def test_rng_reuse_flags_loop_without_resplit():
    findings = check(
        """
        import jax

        def sample(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(key, (4,)))
            return out
        """,
        rules=["rng-reuse"],
    )
    assert rule_names(findings) == ["rng-reuse"]


def test_rng_reuse_accepts_split_discipline():
    findings = check(
        """
        import jax

        def sample(key, n):
            out = []
            for _ in range(n):
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, (4,)))
            a, b = jax.random.split(key)
            return out, jax.random.uniform(a), jax.random.uniform(b)
        """,
        rules=["rng-reuse"],
    )
    assert findings == []


def test_rng_reuse_accepts_branches():
    # either branch runs, not both: one consumption each is fine
    findings = check(
        """
        import jax

        def sample(key, greedy):
            if greedy:
                return jax.random.categorical(key, None)
            else:
                return jax.random.normal(key, (4,))
        """,
        rules=["rng-reuse"],
    )
    assert findings == []


def test_rng_split_dropped_flags_bare_statement():
    findings = check(
        """
        import jax

        def warmup(key):
            jax.random.split(key)
            return key
        """,
        rules=["rng-split-dropped"],
    )
    assert rule_names(findings) == ["rng-split-dropped"]


def test_rng_split_dropped_flags_underscore_assignment():
    findings = check(
        """
        import jax

        def warmup(key):
            _ = jax.random.split(key)
            return key
        """,
        rules=["rng-split-dropped"],
    )
    assert rule_names(findings) == ["rng-split-dropped"]


def test_rng_split_used_is_clean():
    findings = check(
        """
        import jax

        def warmup(key):
            key, sub = jax.random.split(key)
            return key, sub
        """,
        rules=["rng-split-dropped"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# dtype-pet / dtype-f32-literal
# ---------------------------------------------------------------------------


def test_dtype_pet_flags_bare_einsum_in_ops():
    findings = check(
        """
        import jax.numpy as jnp

        def attend(q, k):
            return jnp.einsum("bhid,bhjd->bhij", q, k)
        """,
        path="progen_tpu/ops/attention.py",
        rules=["dtype-pet"],
    )
    assert rule_names(findings) == ["dtype-pet"]
    assert "preferred_element_type" in findings[0].message


def test_dtype_pet_accepts_pinned_einsum():
    findings = check(
        """
        import jax.numpy as jnp

        def attend(q, k):
            return jnp.einsum("bhid,bhjd->bhij", q, k,
                              preferred_element_type=jnp.float32)
        """,
        path="progen_tpu/ops/attention.py",
        rules=["dtype-pet"],
    )
    assert findings == []


def test_dtype_pet_scoped_to_numeric_core():
    # the same bare einsum outside ops/ and decode/ is not this rule's business
    findings = check(
        """
        import jax.numpy as jnp

        def attend(q, k):
            return jnp.einsum("bhid,bhjd->bhij", q, k)
        """,
        path="progen_tpu/observe/flops.py",
        rules=["dtype-pet"],
    )
    assert findings == []


def test_dtype_literal_flags_inexact_bf16_mix():
    findings = check(
        """
        import jax.numpy as jnp

        def norm(x):
            return x.astype(jnp.bfloat16) + 1e-6
        """,
        rules=["dtype-f32-literal"],
    )
    assert rule_names(findings) == ["dtype-f32-literal"]


def test_dtype_literal_accepts_exact_and_f32():
    findings = check(
        """
        import jax.numpy as jnp

        def scale(x):
            a = x.astype(jnp.bfloat16) * 0.5
            b = x.astype(jnp.float32) * 0.1
            return a, b
        """,
        rules=["dtype-f32-literal"],
    )
    assert findings == []


def test_bf16_exact_helper():
    from progen_tpu.analysis.rules_dtype import bf16_exact

    assert bf16_exact(0.5) and bf16_exact(2.0) and bf16_exact(-1.0)
    assert not bf16_exact(0.1) and not bf16_exact(1e-6)


# ---------------------------------------------------------------------------
# mesh-axis
# ---------------------------------------------------------------------------


def test_mesh_axis_flags_unknown_axis():
    findings = check(
        """
        from jax.sharding import PartitionSpec as P

        SPEC = P("model", None)
        """,
        rules=["mesh-axis"],
    )
    assert rule_names(findings) == ["mesh-axis"]
    assert "'model'" in findings[0].message


def test_mesh_axis_accepts_declared_axes_and_tuples():
    findings = check(
        """
        from jax.sharding import PartitionSpec as P

        A = P(("data", "fsdp"), None)
        B = P(None, "seq", "tensor")
        """,
        rules=["mesh-axis"],
    )
    assert findings == []


def test_mesh_axis_vocabulary_comes_from_mesh_py():
    # the live repo declares MESH_AXES in core/mesh.py; discovery must find it
    ctx = engine.build_context(REPO_ROOT)
    assert ctx.mesh_axes == frozenset({"data", "fsdp", "tensor", "seq"})


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

_TRAINER_PATH = "progen_tpu/train/trainer.py"


def test_host_sync_flags_float_in_run_loop():
    findings = check(
        """
        class Trainer:
            def _run_loop(self, metrics):
                loss = float(metrics["loss"])
                return loss
        """,
        path=_TRAINER_PATH,
        rules=["host-sync"],
    )
    assert rule_names(findings) == ["host-sync"]
    assert "device sync" in findings[0].message


def test_host_sync_flags_asarray_in_engine_step():
    findings = check(
        """
        import numpy as np

        class ServingEngine:
            def step(self):
                done = np.asarray(self.state["done"])
                return done
        """,
        path="progen_tpu/decode/engine.py",
        rules=["host-sync"],
    )
    assert rule_names(findings) == ["host-sync"]


def test_host_sync_accepts_device_get_consolidation():
    # the sanctioned idiom: one explicit, suppressed device_get; everything
    # derived from it is host-side and free to float()/np.asarray()
    findings = check(
        """
        import jax
        import numpy as np

        class Trainer:
            def _run_loop(self, metrics):
                host = jax.device_get(metrics)  # graftcheck: disable=host-sync
                loss = float(host["loss"])
                grad = np.asarray(host["grad_norm"])
                return loss, grad
        """,
        path=_TRAINER_PATH,
        rules=["host-sync"],
    )
    assert findings == []


def test_host_sync_ignores_functions_outside_zones():
    findings = check(
        """
        class Trainer:
            def _checkpoint(self, state):
                return float(state.step)
        """,
        path=_TRAINER_PATH,
        rules=["host-sync"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def test_donation_flags_read_after_donating_call():
    findings = check(
        """
        import jax

        def make(step_impl):
            step = jax.jit(step_impl, donate_argnums=(0,))

            def run(state, batch):
                new_state = step(state, batch)
                stale = state.params
                return new_state, stale

            return run
        """,
        rules=["donation"],
    )
    assert rule_names(findings) == ["donation"]
    assert "'state'" in findings[0].message


def test_donation_accepts_rebinding():
    findings = check(
        """
        import jax

        def make(step_impl):
            step = jax.jit(step_impl, donate_argnums=(0,))

            def run(state, batch):
                state = step(state, batch)
                return state.params

            return run
        """,
        rules=["donation"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# recompile
# ---------------------------------------------------------------------------


def test_recompile_flags_config_arg_without_static():
    findings = check(
        """
        import jax

        def step_impl(params, config):
            return params

        step = jax.jit(step_impl)
        """,
        rules=["recompile"],
    )
    assert rule_names(findings) == ["recompile"]
    assert "'config'" in findings[0].message


def test_recompile_accepts_static_argnames():
    findings = check(
        """
        import jax

        def step_impl(params, config):
            return params

        step = jax.jit(step_impl, static_argnames=("config",))
        """,
        rules=["recompile"],
    )
    assert findings == []


def test_recompile_flags_string_leaf_literal_at_call_site():
    findings = check(
        """
        import jax

        def f_impl(x, opts):
            return x

        f = jax.jit(f_impl)

        def run(x):
            return f(x, {"mode": "fast"})
        """,
        rules=["recompile"],
    )
    assert rule_names(findings) == ["recompile"]


def test_recompile_accepts_array_pytree_literals():
    # dicts of arrays are legitimate traced pytrees (batches!)
    findings = check(
        """
        import jax

        def f_impl(x, batch):
            return x

        f = jax.jit(f_impl)

        def run(x, tokens, mask):
            return f(x, {"tokens": tokens, "mask": mask})
        """,
        rules=["recompile"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# pallas-indexmap / pallas-ref-write
# ---------------------------------------------------------------------------


def test_pallas_indexmap_flags_traced_closure():
    findings = check(
        """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x, idx):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((128,), lambda i: (idx[i], 0))],
            )(x)
        """,
        rules=["pallas-indexmap"],
    )
    assert rule_names(findings) == ["pallas-indexmap"]
    assert "'idx'" in findings[0].message


def test_pallas_indexmap_accepts_shape_derived_ints():
    findings = check(
        """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch(x, block: int):
            n = x.shape[0]
            nb = n // block
            return pl.pallas_call(
                kernel,
                grid=(nb,),
                in_specs=[pl.BlockSpec((block,), lambda i: (i % nb, 0))],
            )(x)
        """,
        rules=["pallas-indexmap"],
    )
    assert findings == []


def test_pallas_indexmap_accepts_helper_returned_ints():
    # one level of interprocedural staticness: tuple-unpack from a module
    # helper whose return elements are shape-derived ints
    findings = check(
        """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def _prep(x, block: int):
            n = x.shape[0]
            nbr = -(-n // block)
            return x, nbr

        def launch(x, block: int):
            x, nbr = _prep(x, block)
            return pl.pallas_call(
                kernel,
                grid=(nbr,),
                in_specs=[pl.BlockSpec((block,), lambda i: (i % nbr, 0))],
            )(x)
        """,
        rules=["pallas-indexmap"],
    )
    assert findings == []


def test_pallas_ref_write_flags_plain_store_in_loop():
    findings = check(
        """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            for i in range(4):
                o_ref[...] = x_ref[i]

        def launch(x):
            return pl.pallas_call(kernel)(x)
        """,
        rules=["pallas-ref-write"],
    )
    assert rule_names(findings) == ["pallas-ref-write"]
    assert "'o_ref'" in findings[0].message


def test_pallas_ref_write_accepts_accumulation():
    findings = check(
        """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref, acc_ref):
            for i in range(4):
                acc_ref[...] += x_ref[i]
            o_ref[...] = acc_ref[...]

        def launch(x):
            return pl.pallas_call(kernel)(x)
        """,
        rules=["pallas-ref-write"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

_BARE_EINSUM = """
import jax.numpy as jnp

def attend(q, k):
    return jnp.einsum("bhid,bhjd->bhij", q, k){comment}
"""


def test_suppression_on_finding_line():
    src = _BARE_EINSUM.format(comment="  # graftcheck: disable=dtype-pet")
    assert check(src, path="progen_tpu/ops/x.py", rules=["dtype-pet"]) == []


def test_suppression_on_preceding_comment_line():
    src = textwrap.dedent(
        """
        import jax.numpy as jnp

        def attend(q, k):
            # graftcheck: disable=dtype-pet
            return jnp.einsum("bhid,bhjd->bhij", q, k)
        """
    )
    assert check(src, path="progen_tpu/ops/x.py", rules=["dtype-pet"]) == []


def test_suppression_file_wide():
    src = textwrap.dedent(
        """
        # graftcheck: disable-file=dtype-pet
        import jax.numpy as jnp

        def attend(q, k):
            return jnp.einsum("bhid,bhjd->bhij", q, k)
        """
    )
    assert check(src, path="progen_tpu/ops/x.py", rules=["dtype-pet"]) == []


def test_suppression_of_other_rule_does_not_hide():
    src = _BARE_EINSUM.format(comment="  # graftcheck: disable=host-sync")
    findings = check(src, path="progen_tpu/ops/x.py", rules=["dtype-pet"])
    assert rule_names(findings) == ["dtype-pet"]


def test_trailing_comment_on_previous_code_line_does_not_leak():
    src = textwrap.dedent(
        """
        import jax.numpy as jnp

        def attend(q, k):
            q = q * 2  # graftcheck: disable=dtype-pet
            return jnp.einsum("bhid,bhjd->bhij", q, k)
        """
    )
    findings = check(src, path="progen_tpu/ops/x.py", rules=["dtype-pet"])
    assert rule_names(findings) == ["dtype-pet"]


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_roundtrip_and_matching(tmp_path):
    findings = check(
        _BARE_EINSUM.format(comment=""),
        path="progen_tpu/ops/x.py",
        rules=["dtype-pet"],
    )
    assert len(findings) == 1
    baseline_file = tmp_path / "baseline.json"
    engine.save_baseline(baseline_file, findings)
    baseline = engine.load_baseline(baseline_file)

    new, old = engine.apply_baseline(findings, baseline)
    assert new == [] and len(old) == 1

    # baseline keys ignore line numbers: shifting the finding down a few
    # lines (unrelated edits above it) must not invalidate the entry
    shifted = check(
        "\n\n\n" + _BARE_EINSUM.format(comment=""),
        path="progen_tpu/ops/x.py",
        rules=["dtype-pet"],
    )
    new, old = engine.apply_baseline(shifted, baseline)
    assert new == [] and len(old) == 1

    # ...but a different rule/path/message is a new finding
    other = check(
        _BARE_EINSUM.format(comment=""),
        path="progen_tpu/decode/y.py",
        rules=["dtype-pet"],
    )
    new, old = engine.apply_baseline(other, baseline)
    assert len(new) == 1 and old == []


# ---------------------------------------------------------------------------
# output formats
# ---------------------------------------------------------------------------


def test_json_output_schema():
    findings = check(
        _BARE_EINSUM.format(comment=""),
        path="progen_tpu/ops/x.py",
        rules=["dtype-pet"],
    )
    payload = json.loads(engine.format_json(findings, baselined=2))
    assert payload["version"] == 1
    assert payload["count"] == 1
    assert payload["baselined"] == 2
    (f,) = payload["findings"]
    assert set(f) == {"rule", "path", "line", "col", "message"}
    assert f["rule"] == "dtype-pet"
    assert f["path"] == "progen_tpu/ops/x.py"
    assert isinstance(f["line"], int) and isinstance(f["col"], int)


def test_human_output_format():
    findings = check(
        _BARE_EINSUM.format(comment=""),
        path="progen_tpu/ops/x.py",
        rules=["dtype-pet"],
    )
    text = engine.format_human(findings)
    assert "progen_tpu/ops/x.py:" in text
    assert "[dtype-pet]" in text
    assert text.endswith("1 finding(s)")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "graftcheck.py"), *args],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        timeout=120,
    )


def test_cli_list_rules_covers_all_eight_hazard_classes():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    listed = set(proc.stdout.split())
    assert listed >= {
        "trace-safety",
        "rng-reuse",
        "rng-split-dropped",
        "dtype-pet",
        "dtype-f32-literal",
        "mesh-axis",
        "host-sync",
        "donation",
        "recompile",
        "pallas-indexmap",
        "pallas-ref-write",
    }


def test_cli_exit_codes(tmp_path):
    dirty = tmp_path / "dirty"
    dirty.mkdir()
    (dirty / "ops").mkdir()
    (dirty / "ops" / "bad.py").write_text(
        "import jax.numpy as jnp\n\n"
        "def f(q, k):\n"
        "    return jnp.einsum('id,jd->ij', q, k)\n"
    )
    proc = _run_cli(str(dirty), "--no-baseline")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "[dtype-pet]" in proc.stdout

    proc = _run_cli(str(tmp_path / "nope.py"))
    assert proc.returncode == 2

    proc = _run_cli("--rules", "not-a-rule", "progen_tpu")
    assert proc.returncode == 2


# ---------------------------------------------------------------------------
# the tier-1 gate: the repo itself must be clean
# ---------------------------------------------------------------------------


def test_repo_wide_zero_findings_gate():
    targets = [
        REPO_ROOT / "progen_tpu",
        REPO_ROOT / "tools",
        REPO_ROOT / "benchmarks",
        REPO_ROOT / "train.py",
        REPO_ROOT / "sample.py",
        REPO_ROOT / "bench.py",
        REPO_ROOT / "generate_data.py",
    ]
    findings = analysis.run(targets, root=REPO_ROOT, report_stale=True)
    baseline_path = REPO_ROOT / "tools" / "graftcheck_baseline.json"
    baseline = (
        engine.load_baseline(baseline_path) if baseline_path.is_file() else set()
    )
    new, _ = engine.apply_baseline(findings, baseline)
    assert not new, "\n" + engine.format_human(new)


# ---------------------------------------------------------------------------
# cfg: hand-drawn graph checks
# ---------------------------------------------------------------------------


def _cfg(source):
    tree = ast.parse(textwrap.dedent(source))
    return cfg_mod.build_cfg(tree.body[0])


def test_cfg_if_else_hand_drawn():
    g = _cfg(
        """
        def f(a):
            x = 1
            if a:
                y = 2
            else:
                y = 3
            return y
        """
    )
    (branch,) = [n for n in g.nodes if n.kind == "branch"]
    assert {lab for _, lab in g.successors(branch.idx)} == {"true", "false"}
    (ret,) = [n for n in g.nodes if n.kind == "return"]
    # both arms reconverge on the return, which reaches exit
    for dst, _ in g.successors(branch.idx):
        assert ret.idx in g.reachable_from(dst)
    assert g.exit in g.reachable_from(g.entry)


def test_cfg_while_loop_back_edge():
    g = _cfg(
        """
        def f(n):
            while n:
                n = step(n)
            return n
        """
    )
    (branch,) = [n for n in g.nodes if n.kind == "branch"]
    (body,) = [n for n in g.nodes if n.kind == "stmt" and n.line == 4]
    assert (body.idx, "true") in g.successors(branch.idx)
    assert (branch.idx, "norm") in g.successors(body.idx)  # the back edge
    (ret,) = [n for n in g.nodes if n.kind == "return"]
    assert (ret.idx, "false") in g.successors(branch.idx)


def test_cfg_early_return_skips_following_code():
    g = _cfg(
        """
        def f(a):
            if a:
                return 1
            tail(a)
            return 2
        """
    )
    (early,) = [n for n in g.nodes if n.kind == "return" and n.line == 4]
    (tail,) = [n for n in g.nodes if n.kind == "stmt" and n.line == 5]
    reach = g.reachable_from(early.idx)
    assert g.exit in reach
    assert tail.idx not in reach


def test_cfg_finally_runs_on_both_continuations():
    g = _cfg(
        """
        def f(a):
            try:
                work(a)
            finally:
                cleanup(a)
            return a
        """
    )
    # the finally body is instantiated once per continuation purpose:
    # fall-through and the exception path both execute cleanup
    copies = g.nodes_for_line(6)
    assert len(copies) >= 2
    (ret,) = [n for n in g.nodes if n.kind == "return"]
    assert any(ret.idx in g.reachable_from(c.idx) for c in copies)
    assert any(g.raise_exit in g.reachable_from(c.idx) for c in copies)


def test_cfg_exception_edge_reaches_handler():
    g = _cfg(
        """
        def f(a):
            try:
                risky(a)
            except ValueError:
                a = 0
            return a
        """
    )
    (body,) = [n for n in g.nodes if n.kind == "stmt" and n.line == 4]
    (handler,) = [n for n in g.nodes if n.kind == "except"]
    assert (handler.idx, "exc") in g.successors(body.idx)
    # ValueError is not a catch-all: the exception may also propagate
    assert (g.raise_exit, "exc") in g.successors(body.idx)


def test_forward_dataflow_reaches_fixpoint_on_loop():
    g = _cfg(
        """
        def f(a):
            x = 1
            while a:
                x = x + 1
            return x
        """
    )
    states = cfg_mod.forward_dataflow(
        g,
        init=frozenset(),
        transfer=lambda node, state, label: state | {node.kind},
        join=lambda a, b: a | b,
    )
    assert "entry" in states[g.exit]
    assert "branch" in states[g.exit]
    assert "return" in states[g.exit]


# ---------------------------------------------------------------------------
# resource-leak (path-sensitive lifecycle)
# ---------------------------------------------------------------------------


def test_resource_leak_flags_exception_path():
    findings = check(
        """
        def admit(pool, n, bad):
            pages = pool.allocate(n)
            if bad:
                raise ValueError("no capacity")
            pool.release(pages)
        """,
        rules=["resource-leak"],
    )
    assert rule_names(findings) == ["resource-leak"]
    assert "raise propagates" in findings[0].message


def test_resource_leak_flags_early_return():
    findings = check(
        """
        def admit(pool, n, ok):
            pages = pool.allocate(n)
            if not ok:
                return None
            pool.release(pages)
            return n
        """,
        rules=["resource-leak"],
    )
    assert rule_names(findings) == ["resource-leak"]
    assert "function exit" in findings[0].message


def test_resource_leak_accepts_ownership_transfer():
    findings = check(
        """
        def grab(pool, n):
            pages = pool.allocate(n)
            return pages
        """,
        rules=["resource-leak"],
    )
    assert findings == []


def test_resource_leak_accepts_release_in_finally():
    findings = check(
        """
        def hold(pool, n):
            pages = pool.allocate(n)
            try:
                pages.append(0)
            finally:
                pool.release(pages)
        """,
        rules=["resource-leak"],
    )
    assert findings == []


def test_resource_leak_accepts_failed_allocate_none_branch():
    findings = check(
        """
        def admit(pool, n):
            pages = pool.allocate(n)
            if pages is None:
                return None
            pool.release(pages)
            return n
        """,
        rules=["resource-leak"],
    )
    assert findings == []


def test_resource_leak_flags_discarded_acquire():
    findings = check(
        """
        def f(pool, n):
            pool.allocate(n)
        """,
        rules=["resource-leak"],
    )
    assert rule_names(findings) == ["resource-leak"]
    assert "discarded" in findings[0].message


def test_resource_leak_flags_unexited_span():
    findings = check(
        """
        def f(tracer, work):
            s = tracer.span("step")
            work()
            return 1
        """,
        rules=["resource-leak"],
    )
    assert rule_names(findings) == ["resource-leak"]


def test_resource_leak_accepts_span_context_manager():
    findings = check(
        """
        def f(tracer, x):
            with tracer.span("step"):
                return x + 1
        """,
        rules=["resource-leak"],
    )
    assert findings == []


def test_resource_leak_suppression_on_acquire_line():
    findings = check(
        """
        def f(pool, n):
            pages = pool.allocate(n)  # graftcheck: disable=resource-leak
            return 1
        """,
        rules=["resource-leak"],
    )
    assert findings == []


def test_resource_leak_reproduces_pr9_ack_credit_leak():
    fixture = REPO_ROOT / "tests" / "fixtures" / "ack_credit_leak.py"
    findings = engine.check_source(
        fixture.read_text(),
        path="tests/fixtures/ack_credit_leak.py",
        rules=["resource-leak"],
    )
    assert len(findings) == 1, engine.format_human(findings)
    (f,) = findings
    assert "ack credit" in f.message
    assert "batch_id" in f.message
    assert "leaky_on_handle" in f.message  # the shipped fix stays clean


# ---------------------------------------------------------------------------
# wire-schema consistency
# ---------------------------------------------------------------------------


def test_wire_dead_field_and_strict_read():
    findings = check(
        """
        def thing_to_wire(r):
            msg = {"uid": r.uid, "n": int(r.n), "ghost": 1}
            if r.pri != 0:
                msg["pri"] = r.pri
            return msg

        def thing_from_wire(d):
            return (d["uid"], d["n"], d["pri"])
        """,
        rules=["wire-dead-field", "wire-strict-read"],
    )
    names = rule_names(findings)
    assert names.count("wire-dead-field") == 1
    assert names.count("wire-strict-read") == 1
    (dead,) = [f for f in findings if f.rule == "wire-dead-field"]
    assert "'ghost'" in dead.message
    (strict,) = [f for f in findings if f.rule == "wire-strict-read"]
    assert "'pri'" in strict.message


def test_wire_pair_with_fallbacks_is_clean():
    findings = check(
        """
        def thing_to_wire(r):
            msg = {"uid": r.uid}
            if r.pri != 0:
                msg["pri"] = r.pri
            return msg

        def thing_from_wire(d):
            return (d["uid"], d.get("pri", 0))
        """,
        rules=["wire-dead-field", "wire-strict-read"],
    )
    assert findings == []


def test_wire_const_mismatch():
    findings = check(
        """
        import struct

        FRAME_VERSION = 1

        def pack_frame(b):
            return struct.pack("<4sI", b, FRAME_VERSION)

        def unpack_frame(buf):
            return struct.unpack("<4sH", buf)

        FRAME_VERSION = 2
        """,
        rules=["wire-const-mismatch"],
    )
    msgs = " | ".join(f.message for f in findings)
    assert "FRAME_VERSION" in msgs
    assert "<4sI" in msgs and "<4sH" in msgs


def test_wire_const_consistent_is_clean():
    findings = check(
        """
        import struct

        FRAME_VERSION = 1

        def pack_frame(b):
            return struct.pack("<4sI", b, FRAME_VERSION)

        def unpack_frame(buf):
            return struct.unpack("<4sI", buf)
        """,
        rules=["wire-const-mismatch"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# determinism zones
# ---------------------------------------------------------------------------


def test_det_set_iter_flags_qos_decision():
    findings = check(
        """
        def pick(queues):
            ready = {q for q in queues if q}
            for q in ready:
                return q
            return None
        """,
        path="progen_tpu/decode/qos.py",
        rules=["det-set-iter"],
    )
    assert rule_names(findings) == ["det-set-iter"]


def test_det_set_iter_accepts_sorted_and_out_of_zone():
    sorted_src = """
        def pick(queues):
            ready = {q for q in queues if q}
            for q in sorted(ready):
                return q
            return None
        """
    assert check(sorted_src, path="progen_tpu/decode/qos.py",
                 rules=["det-set-iter"]) == []
    unsorted_src = """
        def pick(queues):
            ready = {q for q in queues if q}
            for q in ready:
                return q
            return None
        """
    assert check(unsorted_src, path="progen_tpu/core/ops.py",
                 rules=["det-set-iter"]) == []


def test_det_wallclock_zone_and_sanctioned_clock():
    findings = check(
        """
        import time

        def order(q):
            return time.time()
        """,
        path="progen_tpu/decode/qos.py",
        rules=["det-wallclock"],
    )
    assert rule_names(findings) == ["det-wallclock"]
    # the engine scheduling zone sanctions its monotonic timebase
    findings = check(
        """
        import time

        def _maybe_preempt(self):
            return time.perf_counter()
        """,
        path="progen_tpu/decode/engine.py",
        rules=["det-wallclock"],
    )
    assert findings == []


def test_det_ambient_rng():
    findings = check(
        """
        import random

        def victim(xs):
            return xs[int(random.random() * len(xs))]
        """,
        path="progen_tpu/decode/paging.py",
        rules=["det-ambient-rng"],
    )
    assert rule_names(findings) == ["det-ambient-rng"]
    findings = check(
        """
        import random

        def victim(xs, seed):
            rng = random.Random(seed)
            return xs[rng.randrange(len(xs))]
        """,
        path="progen_tpu/decode/paging.py",
        rules=["det-ambient-rng"],
    )
    assert findings == []


def test_det_hash_order_dependence():
    findings = check(
        """
        def key(x):
            return hash(x)
        """,
        path="progen_tpu/decode/qos.py",
        rules=["det-ambient-rng"],
    )
    assert rule_names(findings) == ["det-ambient-rng"]
    assert "PYTHONHASHSEED" in findings[0].message


# ---------------------------------------------------------------------------
# stale suppressions
# ---------------------------------------------------------------------------


def test_stale_suppression_reported_live_one_kept():
    src = """
        import jax.numpy as jnp

        def f(q, k):
            return jnp.einsum('id,jd->ij', q, k)  # graftcheck: disable=dtype-pet

        def g(x):
            return x  # graftcheck: disable=dtype-pet
        """
    findings = engine.check_source(
        textwrap.dedent(src), path="progen_tpu/ops/x.py", report_stale=True
    )
    stale = [f for f in findings if f.rule == "stale-suppression"]
    assert len(stale) == 1
    assert stale[0].line == 8  # g's comment — f's matched a real finding
    # report_stale off (the --allow-stale path): nothing reported
    assert engine.check_source(
        textwrap.dedent(src), path="progen_tpu/ops/x.py"
    ) == []


def test_suppression_example_in_docstring_is_inert():
    src = '''
        """Module docs showing the grammar:

            x = risky()  # graftcheck: disable=dtype-pet
        """

        def g(x):
            return x
        '''
    findings = engine.check_source(
        textwrap.dedent(src), path="progen_tpu/ops/x.py", report_stale=True
    )
    assert findings == []


def test_cli_allow_stale(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(
        "def g(x):\n    return x  # graftcheck: disable=dtype-pet\n"
    )
    proc = _run_cli(str(mod), "--no-baseline")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "stale-suppression" in proc.stdout
    proc = _run_cli(str(mod), "--no-baseline", "--allow-stale")
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# --changed
# ---------------------------------------------------------------------------


def _load_cli_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graftcheck_cli", REPO_ROOT / "tools" / "graftcheck.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_changed_files_vs_ref_and_fallback(tmp_path):
    cli = _load_cli_module()
    # outside a git checkout: None means "fall back to a full scan"
    plain = tmp_path / "plain"
    plain.mkdir()
    assert cli.changed_files(plain, "HEAD") is None

    try:
        has_git = (
            subprocess.run(["git", "--version"], capture_output=True)
            .returncode
            == 0
        )
    except OSError:
        has_git = False
    if not has_git:
        pytest.skip("no git binary")

    repo = tmp_path / "repo"
    repo.mkdir()

    def git(*args):
        return subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=repo, capture_output=True, text=True,
        )

    assert git("init", "-q").returncode == 0
    (repo / "a.py").write_text("A = 1\n")
    git("add", "a.py")
    if git("commit", "-qm", "seed").returncode != 0:
        pytest.skip("git commit unavailable in sandbox")
    git("branch", "-M", "main")
    (repo / "a.py").write_text("A = 2\n")       # modified
    (repo / "b.py").write_text("B = 1\n")       # untracked
    (repo / "c.txt").write_text("not python\n")  # not .py: ignored

    changed = cli.changed_files(repo, "HEAD")
    assert sorted(p.name for p in changed) == ["a.py", "b.py"]
    # bare --changed resolves the merge-base with main
    changed = cli.changed_files(repo, cli._MERGE_BASE)
    assert sorted(p.name for p in changed) == ["a.py", "b.py"]


# ---------------------------------------------------------------------------
# SARIF
# ---------------------------------------------------------------------------


def test_sarif_output_schema():
    findings = check(
        _BARE_EINSUM.format(comment=""),
        path="progen_tpu/ops/x.py",
        rules=["dtype-pet"],
    )
    doc = json.loads(engine.format_sarif(findings, baselined=1))
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    (sarif_run,) = doc["runs"]
    driver = sarif_run["tool"]["driver"]
    assert driver["name"] == "graftcheck"
    assert [r["id"] for r in driver["rules"]] == ["dtype-pet"]
    (res,) = sarif_run["results"]
    assert res["ruleId"] == "dtype-pet"
    assert res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "progen_tpu/ops/x.py"
    assert loc["region"]["startLine"] >= 1
    assert loc["region"]["startColumn"] >= 1  # SARIF columns are 1-based
    assert sarif_run["properties"]["baselined"] == 1


def test_cli_format_sarif(tmp_path):
    (tmp_path / "ops").mkdir()
    bad = tmp_path / "ops" / "bad.py"
    bad.write_text(
        "import jax.numpy as jnp\n\n"
        "def f(q, k):\n"
        "    return jnp.einsum('id,jd->ij', q, k)\n"
    )
    proc = _run_cli("--format", "sarif", "--no-baseline", str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"]


def test_cli_list_rules_includes_v2_passes():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    listed = set(proc.stdout.split())
    assert listed >= {
        "resource-leak",
        "wire-dead-field",
        "wire-strict-read",
        "wire-const-mismatch",
        "det-set-iter",
        "det-wallclock",
        "det-ambient-rng",
    }
