"""jaxlint analyzer tests — one positive + one negative fixture per rule,
plus suppression-comment, JSON-report, and CLI exit-code coverage.

Pure-AST tests: nothing here touches jax at runtime, so the suite is
milliseconds and platform-independent.
"""

import ast
import json
import subprocess
import sys
import textwrap

import pytest

from deeplearning4j_tpu.analysis import (ALL_RULES, Finding, analyze_paths,
                                         analyze_source, build_program,
                                         fingerprints, load_baseline,
                                         new_findings, render_json,
                                         rules_by_name, to_sarif,
                                         write_baseline)
from deeplearning4j_tpu.analysis.__main__ import main as cli_main
from deeplearning4j_tpu.analysis.dataflow import ReachingDefs
from deeplearning4j_tpu.analysis.engine import _check_file


def lint(src, rule=None, path="pkg/mod.py"):
    rules = [rules_by_name()[rule]] if rule else None
    return analyze_source(textwrap.dedent(src), path, rules)


def lint_program(files, rule=None):
    """Analyze {path: source} as ONE whole program (the v2 model)."""
    rules = [rules_by_name()[rule]] if rule else ALL_RULES
    srcs = [(p, textwrap.dedent(s)) for p, s in files.items()]
    program = build_program(srcs)
    out = []
    for p, s in srcs:
        out.extend(_check_file(p, s, program, rules))
    return out


def names(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------- host-sync
class TestHostSync:
    def test_item_inside_jit_flagged(self):
        fs = lint("""
            import jax

            @jax.jit
            def step(x):
                return x.sum().item()
            """, "host-sync")
        assert names(fs) == ["host-sync"]

    def test_float_on_array_in_jit_reachable_helper_flagged(self):
        # helper is not decorated, but is called from a jitted function
        fs = lint("""
            import jax

            def helper(x):
                return float(x)

            @jax.jit
            def step(x):
                return helper(x)
            """, "host-sync")
        assert len(fs) == 1 and fs[0].line == 5

    def test_np_asarray_in_kernel_module_flagged(self):
        fs = lint("""
            import numpy as np

            def kernel(x):
                return np.asarray(x)
            """, "host-sync", path="pkg/ops/k.py")
        assert names(fs) == ["host-sync"]

    def test_outside_jit_not_flagged(self):
        fs = lint("""
            def host_code(x):
                return float(x)
            """, "host-sync")
        assert fs == []

    def test_static_shape_args_not_flagged(self):
        fs = lint("""
            import jax

            @jax.jit
            def step(x):
                n = float(x.shape[0]) * int(x.ndim) * float(len(x))
                return x * n
            """, "host-sync")
        assert fs == []


# ------------------------------------------------------- prng-constant-key
class TestPrngConstantKey:
    def test_literal_key_flagged(self):
        fs = lint("""
            import jax

            def f(rng=None):
                return rng if rng is not None else jax.random.PRNGKey(0)
            """, "prng-constant-key")
        assert names(fs) == ["prng-constant-key"]

    def test_aliased_import_flagged(self):
        fs = lint("""
            from jax import random

            def f():
                return random.PRNGKey(42)
            """, "prng-constant-key")
        assert names(fs) == ["prng-constant-key"]

    def test_seed_variable_not_flagged(self):
        fs = lint("""
            import jax

            def f(seed: int):
                return jax.random.PRNGKey(seed)
            """, "prng-constant-key")
        assert fs == []


# ---------------------------------------------------------- prng-key-reuse
class TestPrngKeyReuse:
    def test_double_draw_flagged(self):
        fs = lint("""
            import jax

            def f(key):
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
            """, "prng-key-reuse")
        assert names(fs) == ["prng-key-reuse"]

    def test_split_between_draws_not_flagged(self):
        fs = lint("""
            import jax

            def f(key):
                a = jax.random.normal(key, (2,))
                key, sub = jax.random.split(key)
                b = jax.random.uniform(sub, (2,))
                return a + b
            """, "prng-key-reuse")
        assert fs == []

    def test_exclusive_early_return_branches_not_flagged(self):
        # the initializers.py pattern: each call path draws exactly once
        fs = lint("""
            import jax

            def f(key, dist):
                if dist == "normal":
                    return jax.random.normal(key, (2,))
                return jax.random.uniform(key, (2,))
            """, "prng-key-reuse")
        assert fs == []

    def test_if_else_branches_not_flagged(self):
        fs = lint("""
            import jax

            def f(key, flag):
                if flag:
                    out = jax.random.normal(key, (2,))
                else:
                    out = jax.random.uniform(key, (2,))
                return out
            """, "prng-key-reuse")
        assert fs == []


# ---------------------------------------------------------- jit-side-effect
class TestJitSideEffect:
    def test_print_under_jit_flagged(self):
        fs = lint("""
            import jax

            @jax.jit
            def step(x):
                print("loss", x)
                return x
            """, "jit-side-effect")
        assert names(fs) == ["jit-side-effect"]

    def test_stdlib_random_and_global_flagged(self):
        fs = lint("""
            import jax
            import random

            @jax.jit
            def step(x):
                global COUNTER
                return x * random.random()
            """, "jit-side-effect")
        assert sorted(names(fs)) == ["jit-side-effect", "jit-side-effect"]

    def test_jax_random_not_confused_with_stdlib(self):
        fs = lint("""
            import jax
            from jax import random

            @jax.jit
            def step(x, key):
                return x * random.normal(key, x.shape)
            """, "jit-side-effect")
        assert fs == []

    def test_print_outside_jit_not_flagged(self):
        fs = lint("""
            def train_loop(x):
                print("epoch done")
            """, "jit-side-effect")
        assert fs == []


# ----------------------------------------------------------- missing-donate
class TestMissingDonate:
    def test_step_without_donation_flagged(self):
        fs = lint("""
            import jax

            @jax.jit
            def train_step(params, opt_state, batch):
                return params, opt_state
            """, "missing-donate")
        assert names(fs) == ["missing-donate"]

    def test_wrap_call_without_donation_flagged(self):
        fs = lint("""
            import jax

            def update(params, grads):
                return params

            update_fn = jax.jit(update)
            """, "missing-donate")
        assert names(fs) == ["missing-donate"]

    def test_donated_step_not_flagged(self):
        fs = lint("""
            import jax
            from functools import partial

            @partial(jax.jit, donate_argnums=(0, 1))
            def train_step(params, opt_state, batch):
                return params, opt_state
            """, "missing-donate")
        assert fs == []

    def test_non_step_function_not_flagged(self):
        fs = lint("""
            import jax

            @jax.jit
            def infer(params, x):
                return x
            """, "missing-donate")
        assert fs == []


# ------------------------------------------------------------ float64-dtype
class TestFloat64Dtype:
    def test_float64_in_kernel_module_flagged(self):
        fs = lint("""
            import jax.numpy as jnp

            def kernel(x):
                return jnp.asarray(x, jnp.float64)
            """, "float64-dtype", path="pkg/ops/k.py")
        assert names(fs) == ["float64-dtype"]

    def test_dtype_string_and_builtin_float_flagged(self):
        fs = lint("""
            import jax.numpy as jnp

            def kernel(x):
                a = x.astype("float64")
                return jnp.zeros((2,), dtype=float) + a
            """, "float64-dtype", path="pkg/ops/k.py")
        assert len(fs) == 2

    def test_outside_kernel_module_not_flagged(self):
        fs = lint("""
            import numpy as np

            def io_path(x):
                return np.float64(x)
            """, "float64-dtype", path="pkg/data/io.py")
        assert fs == []

    def test_f32_kernel_not_flagged(self):
        fs = lint("""
            import jax.numpy as jnp

            def kernel(x):
                return jnp.asarray(x, jnp.float32)
            """, "float64-dtype", path="pkg/ops/k.py")
        assert fs == []


# ------------------------------------------------------------- broad-except
class TestBroadExcept:
    def test_swallowing_handler_flagged(self):
        fs = lint("""
            def f():
                try:
                    work()
                except Exception:
                    pass
            """, "broad-except")
        assert names(fs) == ["broad-except"]

    def test_bare_except_flagged(self):
        fs = lint("""
            def f():
                try:
                    work()
                except:
                    log()
            """, "broad-except")
        assert names(fs) == ["broad-except"]

    def test_reraise_and_narrow_not_flagged(self):
        fs = lint("""
            def f():
                try:
                    work()
                except ValueError:
                    pass
                except Exception as e:
                    cleanup()
                    raise
            """, "broad-except")
        assert fs == []

    def test_raise_from_not_flagged(self):
        fs = lint("""
            def f():
                try:
                    work()
                except Exception as e:
                    raise RuntimeError("context") from e
            """, "broad-except")
        assert fs == []


# ------------------------------------------------- suppression + reporting
class TestSuppression:
    SRC = """
        import jax

        @jax.jit
        def fwd(x):
            return x.sum().item(){tail}
        """

    def test_inline_disable(self):
        fs = lint(self.SRC.format(tail="  # jaxlint: disable=host-sync"))
        assert fs == []

    def test_disable_wrong_rule_keeps_finding(self):
        fs = lint(self.SRC.format(tail="  # jaxlint: disable=broad-except"))
        assert names(fs) == ["host-sync"]

    def test_disable_all(self):
        fs = lint(self.SRC.format(tail="  # jaxlint: disable=all"))
        assert fs == []

    def test_disable_next_line(self):
        fs = lint("""
            import jax

            @jax.jit
            def fwd(x):
                # jaxlint: disable-next=host-sync
                return x.sum().item()
            """)
        assert fs == []

    def test_disable_file(self):
        fs = lint("""
            # jaxlint: disable-file=host-sync
            import jax

            @jax.jit
            def fwd(x):
                return x.sum().item()
            """)
        assert fs == []


class TestReporting:
    def test_json_report_shape(self):
        fs = lint(TestSuppression.SRC.format(tail=""))
        doc = json.loads(render_json(fs))
        assert doc["count"] == 1
        (f,) = doc["findings"]
        assert f["rule"] == "host-sync"
        assert f["path"] == "pkg/mod.py"
        assert f["line"] > 0 and "message" in f

    def test_parse_error_is_a_finding(self):
        fs = lint("def broken(:\n")
        assert names(fs) == ["parse-error"]

    def test_all_rules_have_docs(self):
        assert len(ALL_RULES) >= 6
        for r in ALL_RULES:
            assert r.name and r.description and r.__doc__


class TestCliAndTree:
    def test_analyze_paths_walks_files(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n\n@jax.jit\ndef fwd(x):\n"
                       "    return x.sum().item()\n")
        (tmp_path / "__pycache__").mkdir()
        (tmp_path / "__pycache__" / "junk.py").write_text("def broken(:\n")
        fs = analyze_paths([str(tmp_path)])
        assert names(fs) == ["host-sync"]

    @pytest.mark.slow
    def test_cli_exit_codes(self, tmp_path):
        clean = tmp_path / "clean.py"
        clean.write_text("def f():\n    return 1\n")
        r = subprocess.run([sys.executable, "-m", "deeplearning4j_tpu.analysis",
                            str(clean)], capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr
        dirty = tmp_path / "dirty.py"
        dirty.write_text("import jax\nk = jax.random.PRNGKey(0)\n")
        r = subprocess.run([sys.executable, "-m", "deeplearning4j_tpu.analysis",
                            "--json", str(dirty)], capture_output=True, text=True)
        assert r.returncode == 1
        assert json.loads(r.stdout)["count"] == 1

    def test_repo_tree_is_clean(self):
        import os
        pkg = os.path.join(os.path.dirname(__file__), "..", "deeplearning4j_tpu")
        fs = analyze_paths([pkg])
        assert fs == [], "\n".join(f.render() for f in fs)


# ===================================================== whole-program (v2)
class TestCrossModuleJit:
    HELPER = """
        def helper(x):
            return float(x)
        """
    CALLER = """
        import jax
        from pkg import a

        @jax.jit
        def step(x):
            return a.helper(x)
        """

    def test_cross_module_jit_propagation(self):
        # the helper lives in a module with no jit anywhere — only the
        # cross-module call edge from b.step makes it jit context
        fs = lint_program({"pkg/a.py": self.HELPER, "pkg/b.py": self.CALLER},
                          "host-sync")
        assert [(f.rule, f.path) for f in fs] == [("host-sync", "pkg/a.py")]

    def test_v1_single_module_cannot_produce_it(self):
        # regression guard: analyzed alone (the v1 model), the helper module
        # is clean — the finding above is strictly interprocedural
        assert lint(self.HELPER, "host-sync", path="pkg/a.py") == []

    def test_relative_import_edge(self):
        caller = """
            import jax
            from .a import helper

            @jax.jit
            def step(x):
                return helper(x)
            """
        fs = lint_program({"pkg/a.py": self.HELPER, "pkg/b.py": caller},
                          "host-sync")
        assert names(fs) == ["host-sync"]

    def test_init_reexport_edge(self):
        # from pkg import helper, re-exported by pkg/__init__.py
        init = "from .a import helper\n"
        caller = """
            import jax
            import pkg

            @jax.jit
            def step(x):
                return pkg.helper(x)
            """
        fs = lint_program({"pkg/__init__.py": init, "pkg/a.py": self.HELPER,
                           "other/b.py": caller}, "host-sync")
        assert [(f.rule, f.path) for f in fs] == [("host-sync", "pkg/a.py")]

    def test_uncalled_helper_stays_clean(self):
        caller = """
            import jax
            from pkg import a

            @jax.jit
            def step(x):
                return x
            """
        fs = lint_program({"pkg/a.py": self.HELPER, "pkg/b.py": caller},
                          "host-sync")
        assert fs == []


# --------------------------------------------------------- prng-key-escape
class TestPrngKeyEscape:
    NOISE = """
        import jax

        def noise(key, shape):
            return jax.random.normal(key, shape)
        """

    def test_callee_then_local_draw_flagged(self):
        # each function alone is innocent; together the key is consumed twice
        use = """
            import jax
            from pkg import noisemod

            def f(key):
                n = noisemod.noise(key, (3,))
                return n + jax.random.uniform(key, (3,))
            """
        fs = lint_program({"pkg/noisemod.py": self.NOISE, "pkg/use.py": use},
                          "prng-key-escape")
        assert [(f.rule, f.path) for f in fs] == [
            ("prng-key-escape", "pkg/use.py")]

    def test_split_before_sharing_not_flagged(self):
        use = """
            import jax
            from pkg import noisemod

            def f(key):
                k1, k2 = jax.random.split(key)
                n = noisemod.noise(k1, (3,))
                return n + jax.random.uniform(k2, (3,))
            """
        fs = lint_program({"pkg/noisemod.py": self.NOISE, "pkg/use.py": use},
                          "prng-key-escape")
        assert fs == []

    def test_pure_local_reuse_is_not_double_reported(self):
        # same-function double draw belongs to prng-key-reuse only
        src = """
            import jax

            def f(key):
                a = jax.random.normal(key, (2,))
                return a + jax.random.uniform(key, (2,))
            """
        assert lint(src, "prng-key-escape") == []
        assert names(lint(src, "prng-key-reuse")) == ["prng-key-reuse"]

    def test_callee_that_draws_twice_flagged_at_call_site(self):
        double = """
            import jax

            def double(key):
                a = jax.random.normal(key, (2,))
                return a + jax.random.uniform(key, (2,))
            """
        use = """
            from pkg import m

            def g(key):
                return m.double(key)
            """
        fs = lint_program({"pkg/m.py": double, "pkg/use.py": use},
                          "prng-key-escape")
        assert [(f.rule, f.path) for f in fs] == [
            ("prng-key-escape", "pkg/use.py")]

    def test_exclusive_branch_callee_not_flagged(self):
        # initializer dispatch: callee draws once on every path
        init = """
            import jax

            def init(key, dist):
                if dist == "normal":
                    return jax.random.normal(key, (2,))
                return jax.random.uniform(key, (2,))
            """
        use = """
            from pkg import initmod

            def g(key, dist):
                return initmod.init(key, dist)
            """
        fs = lint_program({"pkg/initmod.py": init, "pkg/use.py": use},
                          "prng-key-escape")
        assert fs == []


# ---------------------------------------------------------- donation-alias
class TestDonationAlias:
    def test_read_after_donation_flagged(self):
        src = """
            import jax

            def _step(params, x):
                return params * x

            step = jax.jit(_step, donate_argnums=(0,))

            def train(params, xs):
                out = step(params, xs)
                return params + out
            """
        fs = lint(src, "donation-alias")
        assert names(fs) == ["donation-alias"]

    def test_rebinding_idiom_not_flagged(self):
        src = """
            import jax
            from functools import partial

            @partial(jax.jit, donate_argnums=(0, 1))
            def train_step(params, opt, batch):
                return params, opt

            def fit(params, opt, batches):
                for b in batches:
                    params, opt = train_step(params, opt, b)
                return params, opt
            """
        assert lint(src, "donation-alias") == []

    def test_self_attribute_jit_wrap(self):
        src = """
            import jax

            class Averager:
                def __init__(self):
                    def avg(p):
                        return p
                    self._avg = jax.jit(avg, donate_argnums=(0,))

                def run(self, params):
                    out = self._avg(params)
                    return params
            """
        fs = lint(src, "donation-alias")
        assert names(fs) == ["donation-alias"]

    def test_cross_module_donating_callee(self):
        stepmod = """
            import jax
            from functools import partial

            @partial(jax.jit, donate_argnums=(0,))
            def update(params, grads):
                return params
            """
        caller = """
            from pkg import stepmod

            def fit(params, grads):
                new = stepmod.update(params, grads)
                return params
            """
        fs = lint_program({"pkg/stepmod.py": stepmod, "pkg/fit.py": caller},
                          "donation-alias")
        assert [(f.rule, f.path) for f in fs] == [
            ("donation-alias", "pkg/fit.py")]


# ----------------------------------------------------- sharding-consistency
class TestShardingConsistency:
    def test_unknown_axis_flagged(self):
        src = """
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            mesh = Mesh(np.arange(4), ("data", "model"))
            SPEC = P("data", "modle")
            """
        fs = lint(src, "sharding-consistency", path="pkg/parallel/s.py")
        assert names(fs) == ["sharding-consistency"]
        assert "modle" in fs[0].message

    def test_duplicate_axis_flagged(self):
        src = """
            from jax.sharding import PartitionSpec as P

            DATA_AXIS = "data"
            SPEC = P("data", "data")
            """
        fs = lint(src, "sharding-consistency", path="pkg/parallel/s.py")
        assert names(fs) == ["sharding-consistency"]
        assert "twice" in fs[0].message

    def test_axis_constants_resolved_across_modules(self):
        meshmod = """
            MODEL_AXIS = "model"
            DATA_AXIS = "data"
            """
        spec = """
            from jax.sharding import PartitionSpec as P
            from pkg.parallel import meshmod

            GOOD = P(None, meshmod.MODEL_AXIS)
            DUP = P(meshmod.MODEL_AXIS, meshmod.MODEL_AXIS)
            """
        fs = lint_program({"pkg/parallel/meshmod.py": meshmod,
                           "pkg/parallel/spec.py": spec},
                          "sharding-consistency")
        assert names(fs) == ["sharding-consistency"]
        assert "twice" in fs[0].message

    def test_rank_sanity(self):
        src = """
            from jax.sharding import PartitionSpec as P

            SPEC = P(None, None, None, None, None, None)
            """
        fs = lint(src, "sharding-consistency", path="pkg/parallel/s.py")
        assert names(fs) == ["sharding-consistency"]
        assert "rank" in fs[0].message

    def test_outside_parallel_and_nn_not_checked(self):
        src = """
            from jax.sharding import PartitionSpec as P

            SPEC = P("data", "data")
            """
        assert lint(src, "sharding-consistency", path="pkg/data/io.py") == []


# --------------------------------------------------- unlocked-shared-state
class TestUnlockedSharedState:
    def test_thread_target_mutation_flagged(self):
        src = """
            import threading

            EVENTS = []

            def worker():
                EVENTS.append(1)

            t = threading.Thread(target=worker)
            """
        fs = lint(src, "unlocked-shared-state")
        assert names(fs) == ["unlocked-shared-state"]

    def test_handler_method_self_container_flagged(self):
        src = """
            class Handler:
                def __init__(self):
                    self.events = []

                def do_GET(self):
                    self.events.append(1)
            """
        fs = lint(src, "unlocked-shared-state")
        assert names(fs) == ["unlocked-shared-state"]

    def test_lock_held_not_flagged(self):
        src = """
            import threading

            class Handler:
                def __init__(self):
                    self.events = []
                    self._lock = threading.Lock()

                def do_GET(self):
                    with self._lock:
                        self.events.append(1)
            """
        assert lint(src, "unlocked-shared-state") == []

    def test_unreachable_function_not_flagged(self):
        src = """
            EVENTS = []

            def helper():
                EVENTS.append(1)
            """
        assert lint(src, "unlocked-shared-state") == []

    def test_cross_module_reachability(self):
        shared = """
            STATS = {}

            def bump(k):
                STATS[k] = STATS.get(k, 0) + 1
            """
        server = """
            import threading
            from pkg import shared

            def serve():
                shared.bump("req")

            t = threading.Thread(target=serve)
            """
        fs = lint_program({"pkg/shared.py": shared, "pkg/server.py": server},
                          "unlocked-shared-state")
        assert [(f.rule, f.path) for f in fs] == [
            ("unlocked-shared-state", "pkg/shared.py")]


# -------------------------------------------------------- broad-except v2
class TestBroadExceptV2:
    def test_tuple_containing_exception_flagged(self):
        fs = lint("""
            def f():
                try:
                    work()
                except (ValueError, Exception):
                    pass
            """, "broad-except")
        assert names(fs) == ["broad-except"]

    def test_contextlib_suppress_exception_flagged(self):
        fs = lint("""
            import contextlib

            def f():
                with contextlib.suppress(Exception):
                    work()
            """, "broad-except")
        assert names(fs) == ["broad-except"]

    def test_from_imported_suppress_flagged(self):
        fs = lint("""
            from contextlib import suppress

            def f():
                with suppress(BaseException):
                    work()
            """, "broad-except")
        assert names(fs) == ["broad-except"]

    def test_narrow_suppress_not_flagged(self):
        fs = lint("""
            import contextlib

            def f():
                with contextlib.suppress(KeyError):
                    work()
            """, "broad-except")
        assert fs == []


# ------------------------------------------------------------------ SARIF
class TestSarif:
    def test_sarif_schema_shape(self):
        fs = lint("import jax\nk = jax.random.PRNGKey(0)\n")
        doc = to_sarif(fs)
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        (run,) = doc["runs"]
        driver = run["tool"]["driver"]
        assert driver["name"] == "jaxlint"
        assert [r["id"] for r in driver["rules"]] == ["prng-constant-key"]
        (res,) = run["results"]
        assert res["ruleId"] == "prng-constant-key"
        assert res["ruleIndex"] == 0
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "pkg/mod.py"
        assert loc["region"]["startLine"] == 2
        assert loc["region"]["startColumn"] >= 1

    def test_empty_findings_is_valid_sarif(self):
        doc = to_sarif([])
        assert doc["runs"][0]["results"] == []
        assert doc["runs"][0]["tool"]["driver"]["rules"] == []

    def test_cli_writes_sarif(self, tmp_path, capsys):
        dirty = tmp_path / "dirty.py"
        dirty.write_text("import jax\nk = jax.random.PRNGKey(0)\n")
        out = tmp_path / "report.sarif"
        rc = cli_main([str(dirty), "--sarif", str(out)])
        capsys.readouterr()
        assert rc == 1
        doc = json.loads(out.read_text())
        assert doc["version"] == "2.1.0"
        assert len(doc["runs"][0]["results"]) == 1


# --------------------------------------------------------------- baseline
class TestBaseline:
    def test_fingerprints_are_line_number_free_but_occurrence_aware(self):
        a = Finding("r", "p.py", 3, 0, "msg")
        b = Finding("r", "p.py", 90, 4, "msg")
        fa, fb = fingerprints([a, b])
        assert fa.split(":")[0] == fb.split(":")[0]  # same hash
        assert fa != fb  # distinct occurrences

    def test_roundtrip(self, tmp_path):
        fs = lint("import jax\nk = jax.random.PRNGKey(0)\n")
        bl = tmp_path / "bl.json"
        write_baseline(str(bl), fs)
        assert new_findings(fs, load_baseline(str(bl))) == []
        extra = fs + [Finding("host-sync", "pkg/mod.py", 9, 0, "new one")]
        assert names(new_findings(extra, load_baseline(str(bl)))) == ["host-sync"]

    def test_cli_record_then_ratchet(self, tmp_path, capsys):
        dirty = tmp_path / "dirty.py"
        dirty.write_text("import jax\nk = jax.random.PRNGKey(0)\n")
        bl = tmp_path / "baseline.json"
        # first run records and exits 0
        assert cli_main([str(dirty), "--baseline", str(bl)]) == 0
        assert bl.exists()
        # re-run: nothing new
        assert cli_main([str(dirty), "--baseline", str(bl)]) == 0
        # inject a new finding: only it fails the run
        dirty.write_text("import jax\nk = jax.random.PRNGKey(0)\n"
                         "j = jax.random.PRNGKey(1)\n")
        assert cli_main([str(dirty), "--baseline", str(bl)]) == 1
        capsys.readouterr()


# ---------------------------------------------------------------- exclude
class TestExclude:
    def test_analyze_paths_exclude_glob(self, tmp_path):
        (tmp_path / "good.py").write_text(
            "import jax\nk = jax.random.PRNGKey(0)\n")
        gen = tmp_path / "generated"
        gen.mkdir()
        (gen / "bad.py").write_text("import jax\nk = jax.random.PRNGKey(0)\n")
        fs = analyze_paths([str(tmp_path)], exclude=["generated"])
        assert len(fs) == 1 and "good.py" in fs[0].path

    def test_cli_default_excludes_tests_dir(self, tmp_path, capsys):
        t = tmp_path / "tests"
        t.mkdir()
        (t / "bad.py").write_text("import jax\nk = jax.random.PRNGKey(0)\n")
        (tmp_path / "ok.py").write_text("x = 1\n")
        assert cli_main([str(tmp_path)]) == 0
        capsys.readouterr()

    def test_cli_exclude_flag_adds_to_defaults(self, tmp_path, capsys):
        v = tmp_path / "vendored"
        v.mkdir()
        (v / "bad.py").write_text("import jax\nk = jax.random.PRNGKey(0)\n")
        assert cli_main([str(tmp_path)]) == 1
        assert cli_main([str(tmp_path), "--exclude", "vendored"]) == 0
        capsys.readouterr()


# --------------------------------------------------------------- dataflow
class TestDataflow:
    def test_reaching_defs_branch_join(self):
        src = ("def f(a):\n"
               "    x = 1\n"
               "    if a:\n"
               "        x = 2\n"
               "    return x\n")
        fn = ast.parse(src).body[0]
        rd = ReachingDefs(fn)
        ((_, defs),) = rd.uses_of("x")
        assert defs == frozenset({2, 4})

    def test_reaching_defs_kill(self):
        src = ("def f():\n"
               "    x = 1\n"
               "    x = 2\n"
               "    return x\n")
        fn = ast.parse(src).body[0]
        rd = ReachingDefs(fn)
        ((_, defs),) = rd.uses_of("x")
        assert defs == frozenset({3})

    def test_params_count_as_defs(self):
        src = ("def f(a):\n"
               "    return a\n")
        fn = ast.parse(src).body[0]
        rd = ReachingDefs(fn)
        ((_, defs),) = rd.uses_of("a")
        assert defs == frozenset({1})


# ----------------------------------------------- metric-label-cardinality
class TestMetricLabelCardinality:
    def test_fstring_of_request_path_flagged(self):
        fs = lint("""
            def handle(metrics, self):
                metrics.counter("http_requests_total",
                                {"endpoint": f"{self.path}"}).inc()
            """, "metric-label-cardinality")
        assert names(fs) == ["metric-label-cardinality"]

    def test_str_of_id_and_bare_attribute_flagged(self):
        fs = lint("""
            def handle(metrics, req):
                metrics.gauge("inflight", {"req": str(req.request_id)}).set(1)
                metrics.histogram("latency_seconds",
                                  {"trace": req.trace_id}).observe(0.1)
            """, "metric-label-cardinality")
        assert names(fs) == ["metric-label-cardinality"] * 2

    def test_labels_dict_passed_by_name_resolved(self):
        fs = lint("""
            def handle(metrics, verb, path):
                labels = {"method": verb, "endpoint": path}
                metrics.counter("http_requests_total", labels).inc()
            """, "metric-label-cardinality")
        assert names(fs) == ["metric-label-cardinality"]

    def test_bounded_mapper_and_enum_labels_not_flagged(self):
        fs = lint("""
            def handle(metrics, server, path, code, tenant):
                # a collapsing helper is the sanctioned fix: its output is
                # assumed bounded even though its *input* is the raw path
                metrics.counter("http_requests_total",
                                {"endpoint": server._metric_route(path),
                                 "code": str(code),
                                 "tenant": tenant}).inc()
            """, "metric-label-cardinality")
        assert fs == []

    def test_numpy_histogram_lookalike_not_flagged(self):
        fs = lint("""
            import numpy as np

            def stats(data, request_id):
                counts, edges = np.histogram(data, bins=16)
                return counts
            """, "metric-label-cardinality")
        assert fs == []

    def test_suppression_comment_honored(self):
        fs = lint("""
            def skew(reg, sh):
                reg.gauge("replica_step_seconds",
                          # jaxlint: disable-next=metric-label-cardinality
                          {"replica": str(sh.device.id)}).set(0.0)
            """, "metric-label-cardinality")
        assert fs == []


# ===================================================== concurrency (v3)
class TestLockOrderCycle:
    # Two modules, each taking its OWN lock then calling into the other,
    # which takes ITS lock: a.A._lock -> b.B._lock and b.B._lock ->
    # a.A._lock. Neither file is suspicious alone — only the
    # whole-program order graph sees the ABBA cycle.
    MOD_A = """
        import threading
        from pkg import b

        class A:
            def __init__(self, peer):
                self._lock = threading.Lock()
                self.peer: b.B = peer

            def push(self):
                with self._lock:
                    self.peer.poke()

            def ping(self):
                with self._lock:
                    return 1
        """
    MOD_B = """
        import threading
        from pkg import a

        class B:
            def __init__(self, back):
                self._lock = threading.Lock()
                self.back: a.A = back

            def poke(self):
                with self._lock:
                    return 2

            def pull(self):
                with self._lock:
                    self.back.ping()
        """

    def test_two_module_abba_flagged_once(self):
        fs = lint_program({"pkg/a.py": self.MOD_A, "pkg/b.py": self.MOD_B},
                          "lock-order-cycle")
        assert names(fs) == ["lock-order-cycle"]
        assert "pkg.a.A._lock" in fs[0].message
        assert "pkg.b.B._lock" in fs[0].message

    def test_consistent_order_not_flagged(self):
        # both call paths take A then B: a DAG, no cycle
        mod_b = """
            import threading

            class B:
                def __init__(self):
                    self._lock = threading.Lock()

                def poke(self):
                    with self._lock:
                        return 2
            """
        mod_a = """
            import threading
            from pkg import b

            class A:
                def __init__(self, peer):
                    self._lock = threading.Lock()
                    self.peer: b.B = peer

                def push(self):
                    with self._lock:
                        self.peer.poke()

                def also_push(self):
                    with self._lock:
                        self.peer.poke()
            """
        fs = lint_program({"pkg/a.py": mod_a, "pkg/b.py": mod_b},
                          "lock-order-cycle")
        assert fs == []

    def test_same_lock_reentry_not_flagged(self):
        # one nominal identity (RLock re-enter / two instances of one
        # class) is deliberately not reported as a cycle
        fs = lint("""
            import threading

            class C:
                def __init__(self, other):
                    self._lock = threading.RLock()
                    self.other: "C" = other

                def f(self):
                    with self._lock:
                        self.other.g()

                def g(self):
                    with self._lock:
                        return 1
            """, "lock-order-cycle")
        assert fs == []


class TestBlockingUnderLock:
    def test_direct_sleep_under_lock_flagged(self):
        fs = lint("""
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        time.sleep(1.0)
            """, "blocking-call-under-lock")
        assert names(fs) == ["blocking-call-under-lock"]
        assert "time.sleep" in fs[0].message

    def test_sleep_outside_lock_not_flagged(self):
        fs = lint("""
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        n = 1
                    time.sleep(n)
            """, "blocking-call-under-lock")
        assert fs == []

    def test_transitive_block_through_callee_flagged(self):
        # the lock holder never blocks directly — its helper does
        fs = lint("""
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def helper(self):
                    time.sleep(0.1)

                def f(self):
                    with self._lock:
                        self.helper()
            """, "blocking-call-under-lock")
        assert len(fs) == 1
        assert "C.helper" in fs[0].message and "time.sleep" in fs[0].message

    def test_sanctioned_helper_not_flagged(self):
        fs = lint("""
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def helper(self):  # jaxlint: sanction=blocking-call-under-lock
                    time.sleep(0.1)

                def f(self):
                    with self._lock:
                        self.helper()
            """, "blocking-call-under-lock")
        assert fs == []

    def test_condition_wait_on_held_condition_exempt(self):
        # the wait-loop idiom: waiting RELEASES the held condition
        fs = lint("""
            import threading

            class C:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.ready = False

                def f(self):
                    with self._cond:
                        while not self.ready:
                            self._cond.wait()
            """, "blocking-call-under-lock")
        assert fs == []

    def test_event_wait_under_lock_flagged(self):
        # an Event.wait does NOT release anything — real stall
        fs = lint("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._go = threading.Event()

                def f(self):
                    with self._lock:
                        self._go.wait()
            """, "blocking-call-under-lock")
        assert names(fs) == ["blocking-call-under-lock"]
        assert "Event.wait" in fs[0].message


class TestAcquireRelease:
    ALLOCATOR = """
        import threading

        class BlockAllocator:
            def alloc(self, n):
                return list(range(n))

            def release(self, blocks):
                pass
        """

    def test_exception_path_leak_flagged(self):
        # the ISSUE's exception-path lease-leak shape: released on the
        # straight line, leaked when the call in between raises
        fs = lint(self.ALLOCATOR + """
            class Pages:
                def __init__(self):
                    self.alloc = BlockAllocator()

                def compute(self, n):
                    return n * 2

                def use(self, n):
                    blocks = self.alloc.alloc(n)
                    self.compute(n)
                    self.alloc.release(blocks)
            """, "acquire-release")
        assert names(fs) == ["acquire-release"]
        assert "leaks if" in fs[0].message and "blocks" in fs[0].message

    def test_try_finally_release_not_flagged(self):
        fs = lint(self.ALLOCATOR + """
            class Pages:
                def __init__(self):
                    self.alloc = BlockAllocator()

                def compute(self, n):
                    return n * 2

                def use(self, n):
                    blocks = self.alloc.alloc(n)
                    try:
                        self.compute(n)
                    finally:
                        self.alloc.release(blocks)
            """, "acquire-release")
        assert fs == []

    def test_never_released_flagged(self):
        fs = lint(self.ALLOCATOR + """
            class Pages:
                def __init__(self):
                    self.alloc = BlockAllocator()

                def use(self, n):
                    blocks = self.alloc.alloc(n)
                    return n
            """, "acquire-release")
        assert len(fs) == 1 and "never released" in fs[0].message

    def test_ownership_transfer_not_flagged(self):
        # returning or storing the allocation hands ownership off
        fs = lint(self.ALLOCATOR + """
            class Pages:
                def __init__(self):
                    self.alloc = BlockAllocator()
                    self.ids = []

                def grow(self, n):
                    new = self.alloc.alloc(n)
                    self.ids.extend(new)
                    return new
            """, "acquire-release")
        assert fs == []

    def test_contextmanager_bare_call_flagged(self):
        fs = lint("""
            import contextlib

            class Reg:
                @contextlib.contextmanager
                def lease(self):
                    yield 1

            class S:
                def __init__(self):
                    self.reg = Reg()

                def bad(self):
                    self.reg.lease()

                def good(self):
                    with self.reg.lease() as snap:
                        return snap
            """, "acquire-release")
        assert len(fs) == 1 and "bare statement" in fs[0].message

    def test_must_use_spend_discarded_flagged(self):
        fs = lint("""
            class RetryBudget:
                def spend(self):
                    return True

            class R:
                def __init__(self):
                    self.budget = RetryBudget()

                def bad(self):
                    self.budget.spend()

                def good(self):
                    if self.budget.spend():
                        return 1
                    return 0
            """, "acquire-release")
        assert len(fs) == 1 and "discarded" in fs[0].message


class TestPropertyVsCall:
    def test_property_called_flagged(self):
        # the PR 12 drain-bug shape: entry.resident() where resident is
        # a @property — TypeError at runtime, 400 on every drain
        fs = lint("""
            class Entry:
                @property
                def resident(self):
                    return True

            class Fleet:
                def get(self) -> Entry:
                    return Entry()

                def drain(self):
                    entry = self.get()
                    if entry.resident():
                        return "draining"
                    return "cold"
            """, "property-vs-call")
        assert names(fs) == ["property-vs-call"]
        assert "resident" in fs[0].message and "@property" in fs[0].message

    def test_property_read_not_flagged(self):
        fs = lint("""
            class Entry:
                @property
                def resident(self):
                    return True

            class Fleet:
                def get(self) -> Entry:
                    return Entry()

                def drain(self):
                    entry = self.get()
                    if entry.resident:
                        return "draining"
                    return "cold"
            """, "property-vs-call")
        assert fs == []

    def test_method_truth_tested_flagged(self):
        # the mirror bug: a bound method is always truthy
        fs = lint("""
            class Gauge:
                def ready(self):
                    return True

            class W:
                def __init__(self):
                    self.g = Gauge()

                def poll(self):
                    if self.g.ready:
                        return 1
                    return 0
            """, "property-vs-call")
        assert names(fs) == ["property-vs-call"]
        assert "always truthy" in fs[0].message

    def test_method_called_not_flagged(self):
        fs = lint("""
            class Gauge:
                def ready(self):
                    return True

            class W:
                def __init__(self):
                    self.g = Gauge()

                def poll(self):
                    if self.g.ready():
                        return 1
                    return 0
            """, "property-vs-call")
        assert fs == []

    def test_same_name_property_and_method_distinguished(self):
        # `resident` is a property on Entry but a METHOD on Pager —
        # nominal receivers keep the two apart (name-based matching
        # could not)
        fs = lint("""
            class Entry:
                @property
                def resident(self):
                    return True

            class Pager:
                def resident(self):
                    return ["m"]

            class Host:
                def __init__(self):
                    self.pager = Pager()

                def names(self):
                    return self.pager.resident()
            """, "property-vs-call")
        assert fs == []


class TestMetricDocsDrift:
    def test_labelset_fork_flagged_at_minority_site(self):
        fs = lint("""
            class M:
                def __init__(self, metrics):
                    self.metrics = metrics

                def a(self):
                    self.metrics.counter("x_total", {"model": "m"}).inc()

                def b(self):
                    self.metrics.counter(
                        "x_total", {"model": "m", "replica": "r"}).inc()

                def c(self):
                    self.metrics.counter("x_total", {"model": "m2"}).inc()
            """, "metric-docs-drift")
        assert names(fs) == ["metric-docs-drift"]
        assert "replica" in fs[0].message  # the minority site is flagged

    def test_consistent_labels_not_flagged(self):
        fs = lint("""
            class M:
                def __init__(self, metrics):
                    self.metrics = metrics

                def a(self):
                    self.metrics.counter("x_total", {"model": "m"}).inc()

                def b(self):
                    self.metrics.counter("x_total", {"model": "n"}).inc()
            """, "metric-docs-drift")
        assert fs == []

    def test_dynamic_labels_skipped(self):
        # a mutated labels dict cannot be proven either way: no finding
        fs = lint("""
            class M:
                def __init__(self, metrics):
                    self.metrics = metrics

                def a(self, extra):
                    labels = {"model": "m"}
                    if extra:
                        labels["tenant"] = extra
                    self.metrics.counter("x_total", labels).inc()

                def b(self):
                    self.metrics.counter("x_total", {"model": "m"}).inc()
            """, "metric-docs-drift")
        assert fs == []

    def test_undocumented_family_flagged_against_readme(self, tmp_path):
        obs = tmp_path / "obs"
        obs.mkdir()
        (obs / "README.md").write_text("- `y_total` — documented family\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(textwrap.dedent("""
            class M:
                def __init__(self, metrics):
                    self.metrics = metrics

                def a(self):
                    self.metrics.counter("x_total", {"m": "1"}).inc()

                def b(self):
                    self.metrics.counter("y_total", {"m": "1"}).inc()
            """))
        rules = [rules_by_name()["metric-docs-drift"]]
        fs = analyze_paths([str(tmp_path)], rules)
        assert len(fs) == 1
        assert "x_total" in fs[0].message
        assert "not documented" in fs[0].message


# ================================================= v4: shape interpreter
class TestShapeTransfer:
    """Broadcast/promotion transfer-function unit table: evaluate one
    expression in a fixed environment and check the inferred
    (shape, dtype) — the interpreter's contract for the ops the
    serving tree leans on."""

    ENV = """
        import jax.numpy as jnp
        import numpy as np

        def f():
            a = jnp.zeros((3, 4))
            b = jnp.ones((4,))
            i = jnp.zeros((2,), jnp.int32)
            return {expr}
        """

    TABLE = [
        ("a + b", "(3, 4)", "f32"),            # rank-broadcast
        ("a * 2", "(3, 4)", "f32"),            # weak int never promotes
        ("a + 1.5", "(3, 4)", "f32"),
        ("i + 1", "(2)", "i32"),               # weak int keeps i32
        ("i + 1.5", "(2)", "f32"),             # weak float flips kind only
        ("a.T", "(4, 3)", "f32"),
        ("a.sum(axis=0)", "(4)", "f32"),
        ("a.sum()", "()", "f32"),
        ("jnp.sum(a, axis=1, keepdims=True)", "(3, 1)", "f32"),
        ("jnp.concatenate([a, a], axis=1)", "(3, 8)", "f32"),
        ("jnp.stack([a, a])", "(2, 3, 4)", "f32"),
        ("a @ jnp.zeros((4, 7))", "(3, 7)", "f32"),
        ("jnp.expand_dims(b, 0)", "(1, 4)", "f32"),
        ("a.reshape(2, 6)", "(2, 6)", "f32"),
        ("jnp.where(a > 0, a, 0.0)", "(3, 4)", "f32"),
        ("a.astype(jnp.bfloat16)", "(3, 4)", "bf16"),
        ("jnp.pad(a, ((1, 1), (0, 2)))", "(5, 6)", "f32"),
    ]

    def _infer(self, expr):
        import textwrap

        from deeplearning4j_tpu.analysis import function_shapes
        from deeplearning4j_tpu.analysis.shapes import ArrayVal, render_shape
        program = build_program(
            [("pkg/t.py", textwrap.dedent(self.ENV.format(expr=expr)))])
        mi = program.lookup_module("pkg.t")
        fs = function_shapes(program, mi.functions["f"])
        av = fs.return_value
        assert isinstance(av, ArrayVal), f"{expr!r} -> {av!r}"
        return render_shape(av.shape), av.dtype

    @pytest.mark.parametrize("expr,shape,dtype", TABLE,
                             ids=[t[0] for t in TABLE])
    def test_transfer(self, expr, shape, dtype):
        assert self._infer(expr) == (shape, dtype)


class TestShapeMismatchRule:
    def test_provable_broadcast_mismatch_flagged_with_shapes(self):
        fs = lint("""
            import jax.numpy as jnp

            def f():
                a = jnp.zeros((3, 4))
                b = jnp.ones((5, 4))
                return a + b
            """, "shape-mismatch")
        assert names(fs) == ["shape-mismatch"]
        assert "(3, 4)" in fs[0].message and "(5, 4)" in fs[0].message

    def test_matmul_contraction_mismatch_flagged(self):
        fs = lint("""
            import jax.numpy as jnp

            def f():
                return jnp.zeros((3, 4)) @ jnp.ones((5, 6))
            """, "shape-mismatch")
        assert names(fs) == ["shape-mismatch"]
        assert "4" in fs[0].message and "5" in fs[0].message

    def test_concat_nonaxis_mismatch_flagged(self):
        fs = lint("""
            import jax.numpy as jnp

            def f():
                a = jnp.zeros((3, 4))
                b = jnp.zeros((3, 9))
                return jnp.concatenate([a, b], axis=0)
            """, "shape-mismatch")
        assert names(fs) == ["shape-mismatch"]

    def test_broadcastable_and_symbolic_shapes_clean(self):
        fs = lint("""
            import jax.numpy as jnp

            def f(x):
                a = jnp.zeros((3, 4))
                return a + jnp.ones((1, 4)) + jnp.ones((4,)) + x
            """, "shape-mismatch")
        assert fs == []


class TestUnboundedCompileSignature:
    def test_payload_dim_reaching_jit_flagged(self):
        fs = lint("""
            import json

            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(x):
                return x * 2

            def handle(payload):
                req = json.loads(payload)
                n = req["n"]
                x = jnp.zeros((n, 4))
                return step(x)
            """, "unbounded-compile-signature")
        assert names(fs) == ["unbounded-compile-signature"]
        assert "step" in fs[0].message and "unbounded" in fs[0].message

    def test_bucketed_dim_clean(self):
        fs = lint("""
            import json

            import jax
            import jax.numpy as jnp

            BUCKETS = (8, 16, 32)

            @jax.jit
            def step(x):
                return x * 2

            def handle(payload):
                n = len(json.loads(payload))
                b = next((k for k in BUCKETS if k >= n), BUCKETS[-1])
                x = jnp.zeros((b, 4))
                return step(x)
            """, "unbounded-compile-signature")
        assert fs == []

    def test_teaching_annotation_bounds_a_dim(self):
        fs = lint("""
            import json

            import jax
            import jax.numpy as jnp

            CHUNKS = (16, 32)

            @jax.jit
            def step(x):
                return x * 2

            def handle(job):
                b = job.next_chunk()  # jaxlint: dim=b:bucket(CHUNKS)
                x = jnp.zeros((1, b))
                return step(x)
            """, "unbounded-compile-signature")
        assert fs == []


class TestStaticArgnumUnbounded:
    def test_env_value_into_static_argnums_flagged(self):
        fs = lint("""
            import functools
            import os

            import jax

            @functools.partial(jax.jit, static_argnums=(1,))
            def step(x, width):
                return x[:width]

            def handle(x):
                w = int(os.environ["W"])
                return step(x, w)
            """, "static-argnum-unbounded")
        assert names(fs) == ["static-argnum-unbounded"]
        assert "width" in fs[0].message

    def test_config_value_into_static_argnums_clean(self):
        fs = lint("""
            import functools

            import jax

            @functools.partial(jax.jit, static_argnums=(1,))
            def step(x, width):
                return x[:width]

            class Server:
                def __init__(self, width=64):
                    self.width = int(width)

                def run(self, x):
                    return step(x, self.width)
            """, "static-argnum-unbounded")
        assert fs == []


class TestWeakTypePromotion:
    def test_int_float_mix_across_callsites_flagged(self):
        fs = lint("""
            import jax

            @jax.jit
            def scale(x, alpha):
                return x * alpha

            def warmup(x):
                return scale(x, 1)

            def serve(x):
                return scale(x, 0.5)
            """, "weak-type-promotion")
        assert names(fs) == ["weak-type-promotion"]
        assert "alpha" in fs[0].message

    def test_payload_scalar_flagged(self):
        fs = lint("""
            import json

            import jax

            @jax.jit
            def scale(x, alpha):
                return x * alpha

            def handle(payload, x):
                t = json.loads(payload)["temperature"]
                return scale(x, t)
            """, "weak-type-promotion")
        assert names(fs) == ["weak-type-promotion"]

    def test_consistent_kind_and_pinned_dtype_clean(self):
        fs = lint("""
            import json

            import jax
            import numpy as np

            @jax.jit
            def scale(x, alpha):
                return x * alpha

            def warmup(x):
                return scale(x, 1.0)

            def serve(x):
                return scale(x, 0.5)

            def handle(payload, x):
                t = np.float32(json.loads(payload)["temperature"])
                return scale(x, t)
            """, "weak-type-promotion")
        assert fs == []


class TestDonatedShapeDrift:
    def test_two_literal_donated_shapes_flagged(self):
        fs = lint("""
            import functools

            import jax
            import jax.numpy as jnp

            @functools.partial(jax.jit, donate_argnums=(0,))
            def update(buf, x):
                return buf + x

            def warm():
                return update(jnp.zeros((4, 4)), jnp.ones((4, 4)))

            def serve():
                return update(jnp.zeros((8, 4)), jnp.ones((8, 4)))
            """, "donated-shape-drift")
        assert names(fs) == ["donated-shape-drift"]
        assert "buf" in fs[0].message

    def test_unbounded_donated_shape_flagged(self):
        fs = lint("""
            import functools
            import json

            import jax
            import jax.numpy as jnp

            @functools.partial(jax.jit, donate_argnums=(0,))
            def update(buf, x):
                return buf + x

            def handle(payload, x):
                n = json.loads(payload)["n"]
                return update(jnp.zeros((n, 4)), x)
            """, "donated-shape-drift")
        assert names(fs) == ["donated-shape-drift"]
        assert "request-derived" in fs[0].message

    def test_invariant_donated_shape_clean(self):
        fs = lint("""
            import functools

            import jax
            import jax.numpy as jnp

            @functools.partial(jax.jit, donate_argnums=(0,))
            def update(buf, x):
                return buf + x

            def warm():
                return update(jnp.zeros((4, 4)), jnp.ones((4, 4)))

            def serve():
                return update(jnp.zeros((4, 4)), jnp.ones((1, 4)))
            """, "donated-shape-drift")
        assert fs == []


class TestCrossModuleBucket:
    """A traced dim that is only provably bounded because the bucketing
    helper lives in ANOTHER module — a per-file pass sees an opaque
    call and could only report unknown; the program-wide interpreter
    follows the call into the helper's summary."""

    FILES = {
        "pkg/buckets.py": """
            PROMPT_BUCKETS = (16, 32, 64)

            def pick(n):
                for b in PROMPT_BUCKETS:
                    if b >= n:
                        return b
                return PROMPT_BUCKETS[-1]
            """,
        "pkg/srv.py": """
            import json

            import jax
            import jax.numpy as jnp

            from pkg.buckets import pick

            @jax.jit
            def prefill(ids):
                return ids * 2

            def handle(payload):
                n = len(json.loads(payload))
                ids = jnp.zeros((1, pick(n)))
                return prefill(ids)
            """,
    }

    def test_cross_module_bucket_propagation_clean(self):
        fs = lint_program(self.FILES, "unbounded-compile-signature")
        assert fs == []

    def test_compile_surface_bound_is_bucket_cardinality(self):
        import textwrap

        from deeplearning4j_tpu.analysis import compute_surface, site_bound
        program = build_program(
            [(p, textwrap.dedent(s)) for p, s in self.FILES.items()])
        sites = compute_surface(program)
        (site,) = [s for s in sites if s.site_id.endswith(":prefill")]
        bound, numeric, _ = site_bound(site)
        assert bound == "|PROMPT_BUCKETS|"
        assert numeric == 3   # the table is a source literal

    def test_unbounded_without_the_bucket_helper(self):
        # the same server module with the helper bypassed IS flagged —
        # proving the clean result above comes from the propagation
        files = dict(self.FILES)
        files["pkg/srv.py"] = files["pkg/srv.py"].replace(
            "pick(n)", "n", 1).replace("jnp.zeros((1, n))",
                                       "jnp.zeros((1, n))")
        fs = lint_program(files, "unbounded-compile-signature")
        assert names(fs) == ["unbounded-compile-signature"]


class TestCompileBudget:
    """Round-trip through the real CLI: a fixture tree within budget
    exits 0; widening the compile surface past the committed budget
    (the regression CI must catch) exits 1."""

    SRC = """
        import jax
        import jax.numpy as jnp

        BUCKETS = (8, 16, 32)

        @jax.jit
        def step(x):
            return x * 2

        def handle(n):
            b = next((k for k in BUCKETS if k >= n), BUCKETS[-1])
            return step(jnp.zeros((b, 4)))
        """

    def _write_tree(self, tmp_path, src):
        pkg = tmp_path / "svc"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "srv.py").write_text(textwrap.dedent(src))
        return pkg

    def _budget(self, tmp_path, bound):
        b = tmp_path / "compile_budget.json"
        b.write_text(json.dumps(
            {"sites": {"svc.srv:step": {"bound": bound, "why": "test"}}}))
        return b

    def test_within_budget_exits_zero(self, tmp_path, capsys,
                                      monkeypatch):
        monkeypatch.chdir(tmp_path)
        pkg = self._write_tree(tmp_path, self.SRC)
        out = tmp_path / "compile_surface.json"
        budget = self._budget(tmp_path, "|BUCKETS|")
        rc = cli_main(["svc", "--compile-surface", str(out),
                       "--budget", str(budget)])
        assert rc == 0
        assert "compile budget: ok" in capsys.readouterr().out
        report = json.loads(out.read_text())
        (site,) = report["sites"]
        assert site["site"] == "svc.srv:step"
        assert site["bound"] == "|BUCKETS|"
        assert site["numeric"] == 3

    def test_cardinality_regression_exits_nonzero(self, tmp_path, capsys,
                                                  monkeypatch):
        monkeypatch.chdir(tmp_path)
        # the bucketing is bypassed: the traced dim is now unbounded,
        # so the surface widens past the committed |BUCKETS| budget
        regressed = self.SRC.replace(
            "b = next((k for k in BUCKETS if k >= n), BUCKETS[-1])",
            "b = n")
        pkg = self._write_tree(tmp_path, regressed)
        out = tmp_path / "compile_surface.json"
        budget = self._budget(tmp_path, "|BUCKETS|")
        rc = cli_main(["svc", "--compile-surface", str(out),
                       "--budget", str(budget)])
        assert rc == 1
        assert "compile-budget:" in capsys.readouterr().out

    def test_new_site_without_budget_entry_fails(self, tmp_path, capsys,
                                                 monkeypatch):
        monkeypatch.chdir(tmp_path)
        extra = self.SRC + """

        @jax.jit
        def extra_step(x):
            return x + 1

        def more(x):
            return extra_step(x)
        """
        pkg = self._write_tree(tmp_path, extra)
        out = tmp_path / "compile_surface.json"
        budget = self._budget(tmp_path, "|BUCKETS|")
        rc = cli_main(["svc", "--compile-surface", str(out),
                       "--budget", str(budget)])
        assert rc == 1
        assert "extra_step" in capsys.readouterr().out

    def test_tightening_is_always_allowed(self, tmp_path, capsys,
                                          monkeypatch):
        monkeypatch.chdir(tmp_path)
        # actual bound 1 (literal shape) under a |BUCKETS| budget: ok
        tightened = self.SRC.replace(
            "b = next((k for k in BUCKETS if k >= n), BUCKETS[-1])",
            "b = 8")
        pkg = self._write_tree(tmp_path, tightened)
        out = tmp_path / "compile_surface.json"
        budget = self._budget(tmp_path, "|BUCKETS|")
        rc = cli_main(["svc", "--compile-surface", str(out),
                       "--budget", str(budget)])
        assert rc == 0

    def test_stale_budget_entry_fails(self, tmp_path, capsys,
                                      monkeypatch):
        # a budget entry naming a jit site that no longer exists in the
        # tree is drift, not slack: the entry would silently re-admit the
        # site (at its old bound) if anyone recreated it. Deleting the
        # entry is the fix — and is always allowed (tightening).
        monkeypatch.chdir(tmp_path)
        pkg = self._write_tree(tmp_path, self.SRC)
        out = tmp_path / "compile_surface.json"
        b = tmp_path / "compile_budget.json"
        b.write_text(json.dumps({"sites": {
            "svc.srv:step": {"bound": "|BUCKETS|", "why": "test"},
            "svc.srv:removed_step": {"bound": "|BUCKETS|", "why": "gone"},
        }}))
        rc = cli_main(["svc", "--compile-surface", str(out),
                       "--budget", str(b)])
        assert rc == 1
        got = capsys.readouterr().out
        assert "svc.srv:removed_step" in got
        assert "stale budget entry" in got

    def test_budget_requires_surface_flag(self, tmp_path):
        with pytest.raises(SystemExit):
            cli_main([".", "--budget",
                      str(self._budget(tmp_path, "|BUCKETS|"))])

# ------------------------------------------------------------ error flow (v5)
# Fixtures carry their own typed hierarchy: the model roots on any program
# class *named* ServeError/ShedError, so the fixtures stay self-contained.
ERRORS_MOD = """
class ServeError(RuntimeError):
    cause = "internal"
    http_status = 500


class ShedError(ServeError):
    cause = "queue_full"
    http_status = 503


class QuotaError(ShedError):
    cause = "quota"
    http_status = 429
"""


def eflint(files, rule):
    """lint_program with the fixture error hierarchy alongside."""
    merged = {"pkg/errors.py": ERRORS_MOD}
    merged.update(files)
    return lint_program(merged, rule)


class TestErrorFlowModel:
    def test_cross_module_chain_and_hierarchy(self):
        from deeplearning4j_tpu.analysis.errorflow import get_error_model
        files = {
            "pkg/errors.py": ERRORS_MOD,
            "pkg/deep.py": """
                def inner():
                    raise KeyError("k")

                def mid():
                    return inner()
            """,
            "pkg/top.py": """
                from . import deep

                def outer():
                    return deep.mid()
            """,
        }
        srcs = [(p, textwrap.dedent(s)) for p, s in files.items()]
        program = build_program(srcs)
        model = get_error_model(program)
        mi = program.lookup_module("pkg.top")
        fi = next(f for f in mi.all_funcs if f.name == "outer")
        esc = model.escapes[fi]["KeyError"]
        # three-hop witness chain, origin pinned at the raise site
        assert len(esc.chain) == 3
        assert esc.chain[0].startswith("outer calls mid")
        assert "inner raises KeyError" in esc.chain[-1]
        assert esc.origin.name == "inner"
        # nominal hierarchy: program classes + builtins, attr inheritance
        assert model.is_serve_error("pkg.errors.QuotaError")
        assert model.is_shed_error("pkg.errors.QuotaError")
        assert not model.is_serve_error("RuntimeError")
        assert model.class_attr("pkg.errors.QuotaError", "http_status") == 429
        assert model.class_attr("pkg.errors.ShedError", "cause") == "queue_full"


class TestUntypedEscapeToHttp:
    def test_cross_module_escape_flagged(self):
        fs = eflint({
            "pkg/work.py": """
                def fetch(d):
                    raise KeyError("missing")
            """,
            "pkg/httpd.py": """
                from . import work

                class Handler:
                    def do_POST(self):
                        work.fetch({})
            """,
        }, rule="untyped-escape-to-http")
        assert names(fs) == ["untyped-escape-to-http"]
        assert "ESCAPES" in fs[0].message
        assert "KeyError" in fs[0].message
        assert "fetch raises KeyError" in fs[0].message  # witness chain

    def test_generic_catchall_flagged(self):
        fs = eflint({
            "pkg/work.py": """
                def fetch(d):
                    raise KeyError("missing")
            """,
            "pkg/httpd.py": """
                from . import work

                class Handler:
                    def do_POST(self):
                        try:
                            work.fetch({})
                        except Exception:  # jaxlint: disable=broad-except
                            self.send_response(500)
            """,
        }, rule="untyped-escape-to-http")
        assert names(fs) == ["untyped-escape-to-http"]
        assert "catch-all" in fs[0].message

    def test_specific_clause_is_deliberate_mapping(self):
        fs = eflint({
            "pkg/work.py": """
                def fetch(d):
                    raise KeyError("missing")
            """,
            "pkg/httpd.py": """
                from . import work

                class Handler:
                    def do_POST(self):
                        try:
                            work.fetch({})
                        except KeyError:
                            self.send_response(400)
            """,
        }, rule="untyped-escape-to-http")
        assert fs == []

    def test_module_tuple_clause_resolves(self):
        # the _BAD_REQUEST idiom: a module-level tuple constant in the
        # except clause is a specific mapping, not an unresolvable "?"
        fs = eflint({
            "pkg/httpd.py": """
                _BAD_REQUEST = (KeyError, ValueError)

                class Handler:
                    def do_POST(self):
                        try:
                            self._parse()
                        except _BAD_REQUEST:
                            self.send_response(400)

                    def _parse(self):
                        raise ValueError("bad json")
            """,
        }, rule="untyped-escape-to-http")
        assert fs == []

    def test_typed_serve_error_not_flagged(self):
        fs = eflint({
            "pkg/httpd.py": """
                from .errors import ShedError

                class Handler:
                    def do_POST(self):
                        self._admit()

                    def _admit(self):
                        raise ShedError("full")
            """,
        }, rule="untyped-escape-to-http")
        assert fs == []

    def test_sanction_on_boundary_mutes(self):
        fs = eflint({
            "pkg/httpd.py": """
                class Handler:
                    # debug-only endpoint: programming errors 500 on purpose
                    def do_POST(self):  # jaxlint: sanction=untyped-escape-to-http
                        raise KeyError("missing")
            """,
        }, rule="untyped-escape-to-http")
        assert fs == []


class TestSwallowedTypedError:
    def test_wrap_into_untyped_flagged(self):
        fs = eflint({
            "pkg/disp.py": """
                from .errors import ShedError

                def submit(q):
                    raise ShedError("full")

                def dispatch(q):
                    try:
                        submit(q)
                    except ShedError as e:
                        raise RuntimeError("dispatch failed")
            """,
        }, rule="swallowed-typed-error")
        assert names(fs) == ["swallowed-typed-error"]
        assert "ShedError" in fs[0].message
        assert "RuntimeError" in fs[0].message

    def test_reraise_and_typed_wrap_clean(self):
        fs = eflint({
            "pkg/disp.py": """
                from .errors import QuotaError, ShedError

                def submit(q):
                    raise ShedError("full")

                def reraises(q):
                    try:
                        submit(q)
                    except ShedError as e:
                        raise e

                def wraps_typed(q):
                    try:
                        submit(q)
                    except ShedError as e:
                        raise QuotaError("over") from e
            """,
        }, rule="swallowed-typed-error")
        assert fs == []


class TestErrorStatusDrift:
    def test_literal_contradicts_http_status(self):
        fs = eflint({
            "pkg/worker.py": """
                from .errors import ShedError

                class Worker:
                    def run(self):
                        try:
                            self.admit()
                        except ShedError as e:
                            self._err(500, str(e))

                    def admit(self):
                        raise ShedError("full")

                    def _err(self, code, body):
                        pass
            """,
        }, rule="error-status-drift")
        assert names(fs) == ["error-status-drift"]
        assert "http_status=503" in fs[0].message

    def test_503_without_retry_after_flagged(self):
        fs = eflint({
            "pkg/httpd.py": """
                from .errors import ShedError

                class Handler:
                    def do_POST(self):
                        try:
                            self._admit()
                        except ShedError as e:
                            self.send_response(503)

                    def _admit(self):
                        raise ShedError("full")
            """,
        }, rule="error-status-drift")
        assert names(fs) == ["error-status-drift"]
        assert "Retry-After" in fs[0].message

    def test_503_with_retry_after_clean(self):
        fs = eflint({
            "pkg/httpd.py": """
                from .errors import ShedError

                class Handler:
                    def do_POST(self):
                        try:
                            self._admit()
                        except ShedError as e:
                            self.send_response(503)
                            self.send_header("Retry-After", "3")

                    def _admit(self):
                        raise ShedError("full")
            """,
        }, rule="error-status-drift")
        assert fs == []


class TestUncountedShed:
    def test_uncounted_raise_flagged(self):
        fs = eflint({
            "pkg/q.py": """
                from .errors import ShedError

                class Q:
                    def admit(self, n):
                        if n > 8:
                            raise ShedError("queue full")
            """,
        }, rule="uncounted-shed")
        assert names(fs) == ["uncounted-shed"]
        assert "ShedError" in fs[0].message

    def test_self_count_clean(self):
        fs = eflint({
            "pkg/q.py": """
                from .errors import ShedError

                class Q:
                    def admit(self, n):
                        if n > 8:
                            self.metrics.counter(
                                "serve_shed_total", cause="queue_full").inc()
                            raise ShedError("queue full")
            """,
        }, rule="uncounted-shed")
        assert fs == []

    def test_direct_caller_count_clean(self):
        # the count-then-raise split: the caller owns the counter
        fs = eflint({
            "pkg/q.py": """
                from .errors import ShedError

                class Q:
                    def admit(self, n):
                        if n > 8:
                            raise ShedError("queue full")

                    def offer(self, n):
                        self.metrics.counter(
                            "fleet_shed_total", cause="q").inc()
                        self.admit(n)
            """,
        }, rule="uncounted-shed")
        assert fs == []

    def test_sanction_mutes(self):
        fs = eflint({
            "pkg/q.py": """
                from .errors import ShedError

                class Q:
                    # internal retry signal, counted at the boundary
                    def admit(self, n):  # jaxlint: sanction=uncounted-shed
                        if n > 8:
                            raise ShedError("queue full")
            """,
        }, rule="uncounted-shed")
        assert fs == []


class TestSsePostCommitError:
    def test_escape_after_commit_flagged(self):
        fs = eflint({
            "pkg/stream.py": """
                class Streamer:
                    def step(self):
                        raise ValueError("bad chunk")

                    def pump(self, handler):
                        handler.send_response(200)
                        self.step()
            """,
        }, rule="sse-post-commit-error")
        assert names(fs) == ["sse-post-commit-error"]
        assert "commit" in fs[0].message
        assert "ValueError" in fs[0].message

    def test_caught_locally_clean(self):
        fs = eflint({
            "pkg/stream.py": """
                class Streamer:
                    def step(self):
                        raise ValueError("bad chunk")

                    def pump(self, handler):
                        handler.send_response(200)
                        try:
                            self.step()
                        except ValueError:
                            pass  # in-band error event
            """,
        }, rule="sse-post-commit-error")
        assert fs == []

    def test_client_gone_may_escape(self):
        fs = eflint({
            "pkg/stream.py": """
                class Streamer:
                    def pump(self, handler):
                        handler.send_response(200)
                        raise BrokenPipeError()
            """,
        }, rule="sse-post-commit-error")
        assert fs == []

    def test_isinstance_narrowed_reraise_clean(self):
        # the router's client-gone idiom: the bare raise under the
        # isinstance guard re-raises ONLY the narrowed family, not the
        # whole clause tuple
        fs = eflint({
            "pkg/stream.py": """
                class Streamer:
                    def step(self):
                        raise ValueError("bad chunk")

                    def pump(self, handler):
                        handler.send_response(200)
                        try:
                            self.step()
                        except (ValueError, OSError) as e:
                            if isinstance(e, BrokenPipeError):
                                raise
            """,
        }, rule="sse-post-commit-error")
        assert fs == []


# ------------------------------------------------- error-surface budget (v5)
class TestErrorSurfaceCli:
    SRC_HTTP = """
    from .errors import ServeError, ShedError


    class Handler:
        def do_POST(self):
            try:
                self._work()
            except ServeError as e:
                self.send_response(e.http_status)

        def do_GET(self):
            self._parse()

        def _work(self):
            raise ShedError("full")

        def _parse(self):
            raise ValueError("bad query")
    """

    def _write_tree(self, tmp_path):
        pkg = tmp_path / "svc"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "errors.py").write_text(textwrap.dedent(ERRORS_MOD))
        (pkg / "httpd.py").write_text(textwrap.dedent(self.SRC_HTTP))
        return pkg

    def _gen(self, tmp_path, monkeypatch):
        """Generate the surface once; derive a budget that matches it."""
        monkeypatch.chdir(tmp_path)
        self._write_tree(tmp_path)
        out = tmp_path / "error_surface.json"
        rc = cli_main(["svc", "--error-surface", str(out)])
        assert rc == 0
        report = json.loads(out.read_text())
        budget = {"endpoints": {
            ep["endpoint"]: {
                "why": "test",
                "errors": {e["exception"]: {
                    "status": e["status"],
                    "retry_after": e["retry_after"],
                    "counted": e["counted"],
                } for e in ep["errors"]},
            } for ep in report["endpoints"]}}
        return out, report, budget

    def test_surface_contents(self, tmp_path, monkeypatch):
        _, report, _ = self._gen(tmp_path, monkeypatch)
        eps = {ep["endpoint"]: ep for ep in report["endpoints"]}
        assert set(eps) == {"svc.httpd:Handler.do_GET",
                            "svc.httpd:Handler.do_POST"}
        post = eps["svc.httpd:Handler.do_POST"]["errors"]
        # typed ShedError keeps its class http_status through the
        # explicitly-typed except ServeError entry
        assert [(r["class"], r["typed"], r["status"]) for r in post] \
            == [("ShedError", True, 503)]
        get = eps["svc.httpd:Handler.do_GET"]["errors"]
        assert [(r["class"], r["typed"], r["status"]) for r in get] \
            == [("ValueError", False, "escape")]

    def test_within_budget_passes(self, tmp_path, capsys, monkeypatch):
        out, _, budget = self._gen(tmp_path, monkeypatch)
        b = tmp_path / "error_budget.json"
        b.write_text(json.dumps(budget))
        rc = cli_main(["svc", "--error-surface", str(out),
                       "--error-budget", str(b)])
        assert rc == 0
        assert "error budget: ok" in capsys.readouterr().out

    def test_new_untyped_escape_fails(self, tmp_path, capsys, monkeypatch):
        out, _, budget = self._gen(tmp_path, monkeypatch)
        del budget["endpoints"]["svc.httpd:Handler.do_GET"][
            "errors"]["ValueError"]
        b = tmp_path / "error_budget.json"
        b.write_text(json.dumps(budget))
        rc = cli_main(["svc", "--error-surface", str(out),
                       "--error-budget", str(b)])
        assert rc == 1
        assert "new untyped escape" in capsys.readouterr().out

    def test_tightening_passes(self, tmp_path, capsys, monkeypatch):
        # an error class the budget allows but the tree no longer raises
        out, _, budget = self._gen(tmp_path, monkeypatch)
        budget["endpoints"]["svc.httpd:Handler.do_POST"]["errors"][
            "svc.errors.QuotaError"] = {
                "status": 429, "retry_after": False, "counted": []}
        b = tmp_path / "error_budget.json"
        b.write_text(json.dumps(budget))
        rc = cli_main(["svc", "--error-surface", str(out),
                       "--error-budget", str(b)])
        assert rc == 0

    def test_stale_endpoint_fails(self, tmp_path, capsys, monkeypatch):
        out, _, budget = self._gen(tmp_path, monkeypatch)
        budget["endpoints"]["svc.httpd:Handler.do_DELETE"] = {
            "why": "gone", "errors": {}}
        b = tmp_path / "error_budget.json"
        b.write_text(json.dumps(budget))
        rc = cli_main(["svc", "--error-surface", str(out),
                       "--error-budget", str(b)])
        assert rc == 1
        got = capsys.readouterr().out
        assert "stale budget endpoint" in got
        assert "do_DELETE" in got

    def test_status_drift_fails(self, tmp_path, capsys, monkeypatch):
        out, _, budget = self._gen(tmp_path, monkeypatch)
        budget["endpoints"]["svc.httpd:Handler.do_POST"]["errors"][
            "svc.errors.ShedError"]["status"] = 429
        b = tmp_path / "error_budget.json"
        b.write_text(json.dumps(budget))
        rc = cli_main(["svc", "--error-surface", str(out),
                       "--error-budget", str(b)])
        assert rc == 1
        assert "status mapping drifted" in capsys.readouterr().out

    def test_error_budget_requires_surface_flag(self, tmp_path):
        with pytest.raises(SystemExit):
            cli_main([".", "--error-budget", "nope.json"])
