"""zoolint: engine unit tests + the tier-1 full-package gate.

Three layers, all fast (pure AST, no device work):

1. **Fixture tests per checker family** -- each rule gets at least one
   known-true-positive and one known-false-positive snippet, so a rule
   that stops firing OR starts over-firing breaks CI, not a code
   review.
2. **CLI contract** -- ``scripts/zoolint.py`` exits non-zero when a
   violation from each of the four ISSUE-4 checker families is
   deliberately introduced, supports ``--json`` and the baseline
   workflow.
3. **The gate** -- the full suite over ``analytics_zoo_tpu/`` must
   produce no findings beyond ``zoolint_baseline.json``. This is the
   test that makes every future PR lint-clean by construction.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from analytics_zoo_tpu.analysis import run_zoolint
from analytics_zoo_tpu.analysis.baseline import (
    load_baseline, new_findings)
from analytics_zoo_tpu.analysis.concurrency import ConcurrencyChecker
from analytics_zoo_tpu.analysis.config_keys import ConfigKeyChecker
from analytics_zoo_tpu.analysis.core import all_rules
from analytics_zoo_tpu.analysis.hygiene import HygieneChecker
from analytics_zoo_tpu.analysis.mesh_rules import MeshCollectiveChecker
from analytics_zoo_tpu.analysis.protocol import ProtocolChecker
from analytics_zoo_tpu.analysis.trace_hazards import TraceHazardChecker
from analytics_zoo_tpu.analysis.vocabulary import VocabularyChecker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "analytics_zoo_tpu")
BASELINE = os.path.join(REPO, "zoolint_baseline.json")
CLI = os.path.join(REPO, "scripts", "zoolint.py")


def lint(tmp_path, code, checkers, name="snippet.py"):
    """Write one snippet and run the given checkers over it."""
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return run_zoolint([str(tmp_path)], checkers=checkers,
                       repo_root=str(tmp_path))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ===================================================================== #
# family 1: jit/trace hazards                                           #
# ===================================================================== #
class TestTraceHazards:
    def test_tracer_branch_fires(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                if x > 0:
                    return x
                while x:
                    x = x - 1
                return x
            """, [TraceHazardChecker()])
        assert rules_of(fs) == ["jit-tracer-branch"]
        assert len(fs) == 2  # the if AND the while

    def test_wrapped_by_name_fires(self, tmp_path):
        """The repo idiom: ``self._step = jax.jit(step)`` marks the
        def even without a decorator."""
        fs = lint(tmp_path, """
            import jax

            def step(x):
                if x > 0:
                    return x
                return -x

            compiled = jax.jit(step)
            """, [TraceHazardChecker()])
        assert rules_of(fs) == ["jit-tracer-branch"]

    def test_numpy_and_concretize_fire(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                a = np.sum(x)
                b = float(x)
                c = x.item()
                return a, b, c
            """, [TraceHazardChecker()])
        assert rules_of(fs) == ["jit-concretize", "jit-numpy-call"]
        assert sum(f.rule == "jit-concretize" for f in fs) == 2

    def test_static_conditions_do_not_fire(self, tmp_path):
        """Shape/None/len/isinstance branches are trace-static --
        the bucketing idiom all over the repo must stay clean."""
        fs = lint(tmp_path, """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(x, y):
                if x.shape[0] > 2:
                    x = x * 2
                if y is None:
                    return x
                if len(x) > 4 and x.ndim == 2:
                    x = x + 1
                return x + y
            """, [TraceHazardChecker()])
        assert fs == []

    def test_static_argnames_params_do_not_fire(self, tmp_path):
        """A param routed through static_argnums/static_argnames is a
        concrete value -- branching on it is the intended pattern."""
        fs = lint(tmp_path, """
            import jax

            def step(x, mode):
                if mode:
                    return x * 2
                return x

            fast = jax.jit(step, static_argnames=("mode",))
            """, [TraceHazardChecker()])
        assert fs == []

    def test_unjitted_function_free_to_use_numpy(self, tmp_path):
        """Host-side code (warm_up walking a bucket ladder, decode
        loops) uses numpy and data-dependent branches freely."""
        fs = lint(tmp_path, """
            import numpy as np

            def warm_up(model, batch_sizes):
                for b in batch_sizes:
                    x = np.zeros((b, 4), np.float32)
                    if x.sum() > 0:
                        raise AssertionError
                    model(x)
            """, [TraceHazardChecker()])
        assert fs == []

    def test_static_argnums_list_fires_tuple_ok(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def f(x, n):
                return x * n

            bad = jax.jit(f, static_argnums=[1])
            good = jax.jit(f, static_argnums=(1,))
            """, [TraceHazardChecker()])
        assert rules_of(fs) == ["jit-static-argnums"]
        assert len(fs) == 1

    def test_shard_map_body_checked(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def body(x):
                if x > 0:
                    return x
                return -x

            out = jax.shard_map(body, mesh=None, in_specs=None,
                                out_specs=None)
            """, [TraceHazardChecker()])
        assert rules_of(fs) == ["jit-tracer-branch"]

    def test_suppression_comment(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                if x > 0:  # zoolint: disable=jit-tracer-branch
                    return x
                return -x
            """, [TraceHazardChecker()])
        assert fs == []


# ===================================================================== #
# family 2: concurrency                                                 #
# ===================================================================== #
class TestConcurrency:
    CHECKER = [ConcurrencyChecker(restrict_dirs=None)]

    def test_lock_guard_fires(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Batcher:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.pending = 0

                def add(self):
                    with self._lock:
                        self.pending += 1

                def reset(self):
                    self.pending = 0
            """, self.CHECKER)
        assert rules_of(fs) == ["lock-guard"]
        assert "Batcher.pending" in fs[0].message

    def test_init_and_lock_free_counter_do_not_fire(self, tmp_path):
        """__init__ writes are happens-before; a class that never
        guards an attr (lock-free atomic counter idiom: int += under
        the GIL) states a policy, not a contradiction."""
        fs = lint(tmp_path, """
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                    self.peak = 0

                def inc(self):
                    self.n += 1

                def observe(self):
                    self.peak = max(self.peak, self.n)

                def guarded_other(self):
                    with self._lock:
                        self.other = 1
            """, self.CHECKER)
        assert fs == []

    def test_lock_order_fires(self, tmp_path):
        fs = lint(tmp_path, """
            class Router:
                def a_then_b(self):
                    with self._queue_lock:
                        with self._state_lock:
                            pass

                def b_then_a(self):
                    with self._state_lock:
                        with self._queue_lock:
                            pass
            """, self.CHECKER)
        assert rules_of(fs) == ["lock-order"]

    def test_consistent_order_does_not_fire(self, tmp_path):
        fs = lint(tmp_path, """
            class Router:
                def one(self):
                    with self._queue_lock:
                        with self._state_lock:
                            pass

                def two(self):
                    with self._queue_lock:
                        with self._state_lock:
                            pass
            """, self.CHECKER)
        assert fs == []

    def test_thread_join_fires(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self.run)
                    self._t.start()
            """, self.CHECKER)
        assert rules_of(fs) == ["thread-join"]

    def test_daemon_or_joined_do_not_fire(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self.run,
                                               daemon=True)
                    self._t.start()
                    self._u = threading.Thread(target=self.run)
                    self._u.start()

                def stop(self):
                    self._u.join()
            """, self.CHECKER)
        assert fs == []

    def test_scope_restricted_to_serving_and_obs(self, tmp_path):
        """Default scope skips non-threaded layers entirely."""
        code = """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self.run)
        """
        fs = lint(tmp_path, code, [ConcurrencyChecker()],
                  name="models/w.py")
        assert fs == []
        fs = lint(tmp_path, code, [ConcurrencyChecker()],
                  name="serving/w.py")
        assert rules_of(fs) == ["thread-join"]


# ===================================================================== #
# family 3: config-key drift                                            #
# ===================================================================== #
CONFIG_FIXTURE = """
_DEFAULTS = {
    "zoo.a.used": 1,
    "zoo.a.dead": 2,
    "zoo.mesh.axis.model": "model",
}
"""


class TestConfigKeys:
    CHECKER = [ConfigKeyChecker()]

    def _project(self, tmp_path, user_code):
        (tmp_path / "common").mkdir(parents=True, exist_ok=True)
        (tmp_path / "common" / "config.py").write_text(CONFIG_FIXTURE)
        (tmp_path / "user.py").write_text(textwrap.dedent(user_code))
        return run_zoolint([str(tmp_path)], checkers=self.CHECKER,
                           repo_root=str(tmp_path))

    def test_undeclared_key_fires(self, tmp_path):
        fs = self._project(tmp_path, """
            def f(cfg):
                return cfg.get("zoo.a.typo", 1)
            """)
        assert "config-undeclared" in rules_of(fs)
        assert any("zoo.a.typo" in f.message for f in fs)

    def test_unused_key_fires_used_does_not(self, tmp_path):
        fs = self._project(tmp_path, """
            def f(cfg):
                return cfg.get("zoo.a.used")
            """)
        unused = [f for f in fs if f.rule == "config-unused"]
        assert {m for f in unused for m in [f.message]
                if "zoo.a.used" in m} == set()
        assert any("zoo.a.dead" in f.message for f in unused)

    def test_prefix_wrapper_resolves_indirect_access(self, tmp_path):
        """The helper-wrapper idiom naive grep misses: building the
        key from a 'zoo.mesh.axis.' prefix marks the whole family
        used."""
        fs = self._project(tmp_path, """
            def config_axis(cfg, role):
                return cfg.get("zoo.mesh.axis." + role, role)
            """)
        assert not any("zoo.mesh.axis.model" in f.message
                       for f in fs if f.rule == "config-unused")

    def test_fstring_prefix_also_resolves(self, tmp_path):
        fs = self._project(tmp_path, """
            def config_axis(cfg, role):
                return cfg.get(f"zoo.mesh.axis.{role}")
            """)
        assert not any("zoo.mesh.axis.model" in f.message
                       for f in fs if f.rule == "config-unused")

    def test_docstring_mention_is_not_a_use(self, tmp_path):
        fs = self._project(tmp_path, '''
            def f():
                """Reads ``zoo.a.dead`` -- in prose only."""
                return None
            ''')
        assert any("zoo.a.dead" in f.message for f in fs
                   if f.rule == "config-unused")

    def test_undocumented_fires_with_docs_tree(self, tmp_path):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "conf.md").write_text(
            "`zoo.a.used` and `zoo.a.dead` and the `zoo.mesh.axis.model` axis")
        fs = self._project(tmp_path, """
            def f(cfg):
                return cfg.get("zoo.a.used")
            """)
        # all three keys are in docs -> no undocumented findings
        assert "config-undocumented" not in rules_of(fs)
        (tmp_path / "docs" / "conf.md").write_text("`zoo.a.used`")
        fs = self._project(tmp_path, """
            def f(cfg):
                return cfg.get("zoo.a.used")
            """)
        assert any(f.rule == "config-undocumented"
                   and "zoo.a.dead" in f.message for f in fs)


# ===================================================================== #
# family 4: vocabulary                                                  #
# ===================================================================== #
class TestVocabulary:
    CHECKER = [VocabularyChecker()]

    def test_bad_metric_name_fires(self, tmp_path):
        fs = lint(tmp_path, """
            _REG = object()
            _M = _REG.counter("serving_requests", "no prefix, no unit")
            """, self.CHECKER)
        assert "metric-name" in rules_of(fs)

    def test_good_metric_name_does_not_fire(self, tmp_path):
        fs = lint(tmp_path, """
            _REG = object()
            _M = _REG.counter("zoo_serving_requests_total", "ok")
            """, self.CHECKER)
        assert fs == []

    def test_timer_gauge_is_not_a_registration(self, tmp_path):
        """Per-instance Timer stats are not registry families -- the
        receiver heuristic must keep them out of scope."""
        fs = lint(tmp_path, """
            class W:
                def tick(self):
                    self.timer.gauge("queue_depth", 3)
            """, self.CHECKER)
        assert fs == []

    def test_cross_module_collision_fires(self, tmp_path):
        (tmp_path / "a.py").write_text(
            '_REG = object()\n'
            '_M = _REG.counter("zoo_serving_requests_total", "x")\n')
        (tmp_path / "b.py").write_text(
            '_REG = object()\n'
            '_M = _REG.counter("zoo_serving_requests_total", "x")\n')
        fs = run_zoolint([str(tmp_path)], checkers=self.CHECKER,
                         repo_root=str(tmp_path))
        assert rules_of(fs) == ["metric-collision"]

    def test_unregistered_event_type_fires(self, tmp_path):
        fs = lint(tmp_path, """
            from analytics_zoo_tpu.obs.events import emit
            emit("totally_new_event", "serving")
            """, self.CHECKER)
        assert "event-type" in rules_of(fs)

    def test_registered_event_type_does_not_fire(self, tmp_path):
        fs = lint(tmp_path, """
            from analytics_zoo_tpu.obs.events import emit
            emit("worker_start", "serving")
            """, self.CHECKER)
        assert fs == []

    def test_second_vocab_module_fires(self, tmp_path):
        fs = lint(tmp_path, """
            EVENT_TYPES = {"rogue": "a second vocabulary"}
            """, self.CHECKER)
        assert "event-vocab-module" in rules_of(fs)


# ===================================================================== #
# family 5: hygiene                                                     #
# ===================================================================== #
class TestHygiene:
    CHECKER = [HygieneChecker()]

    def test_silent_broad_except_fires(self, tmp_path):
        fs = lint(tmp_path, """
            def f():
                try:
                    g()
                except Exception:
                    pass
                try:
                    g()
                except:
                    pass
            """, self.CHECKER)
        assert rules_of(fs) == ["silent-except"]
        assert len(fs) == 2

    def test_narrow_or_logged_do_not_fire(self, tmp_path):
        fs = lint(tmp_path, """
            def f(logger):
                try:
                    g()
                except ValueError:
                    pass
                try:
                    g()
                except Exception as e:
                    logger.debug("g failed: %s", e)
            """, self.CHECKER)
        assert fs == []

    def test_rationale_suppression(self, tmp_path):
        fs = lint(tmp_path, """
            def f():
                try:
                    g()
                # teardown: nothing left to log to
                except Exception:  # zoolint: disable=silent-except
                    pass
            """, self.CHECKER)
        assert fs == []


# ===================================================================== #
# dataflow layer (reaching definitions + constant propagation)          #
# ===================================================================== #
class TestDataflow:
    def _chain_for_fn(self, code, fn_name):
        import ast

        from analytics_zoo_tpu.analysis.dataflow import walk_with_scopes
        tree = ast.parse(textwrap.dedent(code))
        for node, chain in walk_with_scopes(tree):
            if (isinstance(node, ast.FunctionDef)
                    and node.name == fn_name):
                return chain
        raise AssertionError(f"no def {fn_name}")

    @staticmethod
    def _name(n):
        import ast

        return ast.Name(id=n, ctx=ast.Load())

    def test_constant_propagation_through_locals_and_module(self):
        chain = self._chain_for_fn("""
            BASE = "zoo."
            KEY = BASE + "mesh"

            def f(flag):
                axis = KEY
                other = "a" if flag else "b"
                return axis, other
            """, "f")
        assert chain.resolve(self._name("axis")) == frozenset(
            ["zoo.mesh"])
        assert chain.resolve(self._name("other")) == frozenset(
            ["a", "b"])

    def test_config_axis_indirection_resolves(self):
        """THE acceptance case: ``axis = config_axis("tp")`` resolves
        to a symbolic ConfigAxis('tp') at the use site."""
        from analytics_zoo_tpu.analysis.dataflow import ConfigAxis
        chain = self._chain_for_fn("""
            def f(x):
                axis = config_axis("tp")
                return axis
            """, "f")
        assert chain.resolve(self._name("axis")) == frozenset(
            [ConfigAxis("tp")])

    def test_unknowns_stay_unknown(self):
        """Params, loop targets, rebinding taints, and calls must all
        resolve to None (the conservative contract every rule relies
        on to avoid false positives)."""
        chain = self._chain_for_fn("""
            def f(param, items):
                computed = len(items)
                for loop_var in items:
                    pass
                multi = "a"
                multi = compute()
                return param
            """, "f")
        for name in ("param", "loop_var", "computed", "multi",
                     "free_name"):
            assert chain.resolve(self._name(name)) is None, name

    def test_conflicting_reassignment_is_unknown(self):
        """No statement ordering in the walk, so a name reassigned to
        a DIFFERENT value must be unknown -- a union would let a later
        unrelated string indict an earlier correct collective axis."""
        chain = self._chain_for_fn("""
            def f(x):
                name = "model"
                use(name)
                name = "stage_done"
                agreed = "a"
                agreed = "a"
                return name
            """, "f")
        assert chain.resolve(self._name("name")) is None
        assert chain.resolve(self._name("agreed")) == frozenset(["a"])

    def test_match_case_bindings_visible(self):
        """match-case bodies belong to the enclosing scope: a dynamic
        rebinding inside a case must make the name unknown, not let a
        module constant shadow it (python 3.10+)."""
        chain = self._chain_for_fn("""
            axis = "data"

            def f(mode):
                match mode:
                    case "a" as captured:
                        axis = compute_axis()
                    case _:
                        pass
                return axis
            """, "f")
        assert chain.resolve(self._name("axis")) is None
        assert chain.resolve(self._name("captured")) is None

    def test_fstring_folds_when_constant(self):
        chain = self._chain_for_fn("""
            ROLE = "model"

            def f():
                key = f"zoo.mesh.axis.{ROLE}"
                return key
            """, "f")
        assert chain.resolve(self._name("key")) == frozenset(
            ["zoo.mesh.axis.model"])


# ===================================================================== #
# family 6: mesh/collective correctness                                 #
# ===================================================================== #
MESH_CONFIG_FIXTURE = """
_DEFAULTS = {
    "zoo.mesh.axis.data": "data",
    "zoo.mesh.axis.model": "model",
}
"""


class TestMeshRules:
    CHECKER = [MeshCollectiveChecker()]

    def _project(self, tmp_path, code, name="par.py"):
        (tmp_path / "common").mkdir(parents=True, exist_ok=True)
        (tmp_path / "common" / "config.py").write_text(
            MESH_CONFIG_FIXTURE)
        (tmp_path / name).write_text(textwrap.dedent(code))
        return run_zoolint([str(tmp_path)], checkers=self.CHECKER,
                           repo_root=str(tmp_path))

    def test_typod_axis_through_indirection_fires(self, tmp_path):
        """Acceptance case: a typo'd axis name reaches the collective
        through ONE level of variable indirection and still fires."""
        fs = self._project(tmp_path, """
            from jax import lax
            import jax

            def body(x):
                name = "modle"
                return lax.psum(x, name)

            f = jax.shard_map(body, mesh=None, in_specs=(None,),
                              out_specs=None)
            """)
        assert rules_of(fs) == ["mesh-axis-unbound"]
        assert "modle" in fs[0].message

    def test_declared_axis_and_unresolvable_do_not_fire(self, tmp_path):
        """Declared axes pass; an axis held in a function parameter is
        unresolvable and must never fire (collectives.py wrappers)."""
        fs = self._project(tmp_path, """
            from jax import lax

            def all_reduce(x, axis_name):
                return lax.psum(x, axis_name)

            def body(x):
                return lax.pmean(x, "model")
            """)
        assert fs == []

    def test_reused_variable_after_collective_does_not_fire(
            self, tmp_path):
        """A name holding a valid axis at the psum and reused for an
        unrelated string LATER must not fire: multi-assignment with
        differing values resolves to unknown, never a union."""
        fs = self._project(tmp_path, """
            from jax import lax

            def body(x, log):
                name = "model"
                r = lax.psum(x, name)
                name = "stage_done"
                log(name)
                return r
            """)
        assert fs == []

    def test_undeclared_config_axis_role_fires(self, tmp_path):
        fs = self._project(tmp_path, """
            from jax import lax

            def body(x):
                axis = config_axis("tensor")
                return lax.psum(x, axis)
            """)
        assert rules_of(fs) == ["mesh-axis-unbound"]
        assert "tensor" in fs[0].message

    def test_declared_config_axis_role_does_not_fire(self, tmp_path):
        fs = self._project(tmp_path, """
            from jax import lax

            def body(x):
                axis = config_axis("model")
                return lax.psum(x, axis)
            """)
        assert fs == []

    def test_quantized_collective_typo_axis_fires(self, tmp_path):
        """ISSUE-7 TP fixture: the EQuARX-idiom quantized collectives
        carry the same axis-name contract as lax collectives -- a
        typo'd axis reaching one must fail lint."""
        fs = self._project(tmp_path, """
            from analytics_zoo_tpu.parallel.collectives import (
                quantized_psum)

            def body(x):
                return quantized_psum(x, "modle")
            """)
        assert rules_of(fs) == ["mesh-axis-unbound"]
        assert "modle" in fs[0].message

    def test_quantized_collective_declared_or_param_axis_clean(
            self, tmp_path):
        """ISSUE-7 FP fixture: config_axis roles and pass-through
        parameters (the sharded serving layer's own idioms) stay
        clean."""
        fs = self._project(tmp_path, """
            from analytics_zoo_tpu.parallel.collectives import (
                quantized_all_gather, quantized_psum)

            def reassemble(leaf, axis_name):
                return quantized_all_gather(leaf, axis_name, axis=0)

            def body(x):
                axis = config_axis("model")
                return quantized_psum(x, axis)
            """)
        assert fs == []

    def test_quantized_psum_over_unsharded_axis_warns(self, tmp_path):
        """A quantized psum over an axis the enclosing shard_map never
        shards is the same replicated-operand bug as the exact one."""
        fs = self._project(tmp_path, """
            import jax

            def body(x):
                return quantized_psum(x, "model")

            f = jax.shard_map(body, mesh=None, in_specs=(P("data"),),
                              out_specs=P("data"))
            """)
        assert rules_of(fs) == ["mesh-unsharded-axis"]

    def test_spec_arity_mismatch_fires_match_does_not(self, tmp_path):
        fs = self._project(tmp_path, """
            import jax
            from jax.sharding import PartitionSpec as P

            def two_args(a, b):
                return a + b

            bad = jax.shard_map(two_args, mesh=None,
                                in_specs=(P("data"),),
                                out_specs=P())
            good = jax.shard_map(two_args, mesh=None,
                                 in_specs=(P("data"), P()),
                                 out_specs=P())
            """)
        assert rules_of(fs) == ["mesh-spec-arity"]
        assert len(fs) == 1 and "two_args" in fs[0].message

    def test_partial_wrapped_fn_is_skipped(self, tmp_path):
        """``shard_map(partial(fn, ...), ...)`` has an unknowable
        effective signature -- never a finding (zouwu/ring idiom)."""
        fs = self._project(tmp_path, """
            import jax
            from functools import partial
            from jax.sharding import PartitionSpec as P

            def fn(a, b, c):
                return a

            f = jax.shard_map(partial(fn, c=1), mesh=None,
                              in_specs=(P(),), out_specs=P())
            """)
        assert fs == []

    def test_unsharded_axis_fires_sharded_does_not(self, tmp_path):
        fs = self._project(tmp_path, """
            import jax
            from jax import lax
            from jax.sharding import PartitionSpec as P

            def body(x):
                return lax.psum(x, "model")

            bad = jax.shard_map(body, mesh=None,
                                in_specs=(P("data", None),),
                                out_specs=P("data", None))

            def body2(x):
                return lax.psum(x, "model")

            good = jax.shard_map(body2, mesh=None,
                                 in_specs=(P("model", None),),
                                 out_specs=P())
            """)
        unsharded = [f for f in fs if f.rule == "mesh-unsharded-axis"]
        assert len(unsharded) == 1
        assert "'body'" not in unsharded[0].message  # message names axis
        assert unsharded[0].line and "model" in unsharded[0].message

    def test_incomplete_specs_skip_unsharded_rule(self, tmp_path):
        """Specs holding a Name (espec, computed axis) make the
        sharded-axes set unknowable -- no unsharded claim (moe.py)."""
        fs = self._project(tmp_path, """
            import jax
            from jax import lax
            from jax.sharding import PartitionSpec as P

            espec = P("data")

            def body(x):
                return lax.psum(x, "model")

            f = jax.shard_map(body, mesh=None, in_specs=(espec,),
                              out_specs=P())
            """)
        assert [f for f in fs if f.rule == "mesh-unsharded-axis"] == []

    def test_nested_collective_fires_distinct_axes_do_not(
            self, tmp_path):
        fs = self._project(tmp_path, """
            from jax import lax

            def bad(x):
                return lax.psum(lax.psum(x, "model"), "model")

            def fine(x):
                return lax.psum(lax.psum(x, "data"), "model")
            """)
        assert rules_of(fs) == ["mesh-nested-collective"]
        assert len(fs) == 1

    def test_multiline_shard_map_suppression_span(self, tmp_path):
        """The core bugfix: ``# zoolint: disable=`` on ANY line of a
        multi-line shard_map statement suppresses its finding (the
        finding anchors to the in_specs line, the comment may sit on
        the closing line)."""
        fs = self._project(tmp_path, """
            import jax
            from jax.sharding import PartitionSpec as P

            def two_args(a, b):
                return a + b

            bad = jax.shard_map(
                two_args,
                mesh=None,
                in_specs=(P("data"),),
                out_specs=P(),
            )  # zoolint: disable=mesh-spec-arity
            """)
        assert fs == []


# ===================================================================== #
# family 7: wire-protocol contracts                                     #
# ===================================================================== #
PROTOCOL_HOME = """
URI_KEY = "__uri__"
TRACE_KEY = "__trace__"
WIRE_KEYS = (URI_KEY, TRACE_KEY)

DEADLINE_PREFIX = "deadline_exceeded"
CIRCUIT_PREFIX = "circuit_open"
ERROR_PREFIXES = {DEADLINE_PREFIX: 504, CIRCUIT_PREFIX: 503}
"""


class TestProtocol:
    CHECKER = [ProtocolChecker()]

    REFS = ("\nfrom .protocol import DEADLINE_PREFIX, CIRCUIT_PREFIX\n"
            "_USED = (DEADLINE_PREFIX, CIRCUIT_PREFIX)\n")

    def _project(self, tmp_path, code, name="serving/front.py",
                 home=PROTOCOL_HOME, refs=True):
        """Write the declaring module + one user file; ``refs`` adds a
        worker-side file referencing both prefixes so unrelated
        unused-prefix warnings stay out of the assertion under test."""
        (tmp_path / "serving").mkdir(parents=True, exist_ok=True)
        (tmp_path / "serving" / "protocol.py").write_text(home)
        if refs:
            (tmp_path / "serving" / "uses.py").write_text(self.REFS)
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(code))
        return run_zoolint([str(tmp_path)], checkers=self.CHECKER,
                           repo_root=str(tmp_path))

    def test_typod_wire_key_fires(self, tmp_path):
        fs = self._project(tmp_path, """
            def decode(z):
                return z["__deadlin__"]
            """)
        assert rules_of(fs) == ["wire-key-literal"]
        assert "__deadlin__" in fs[0].message

    def test_hand_typed_copy_of_declared_key_fires(self, tmp_path):
        fs = self._project(tmp_path, """
            def decode(z):
                return z["__trace__"]
            """)
        assert rules_of(fs) == ["wire-key-literal"]
        assert "import the constant" in fs[0].message

    def test_python_dunders_and_out_of_scope_do_not_fire(
            self, tmp_path):
        fs = self._project(tmp_path, """
            if __name__ == "__main__":
                print("__trace__ lives in serving only")
            """, name="models/tool.py")
        # models/ is outside the serving scope entirely
        assert fs == []
        fs = self._project(tmp_path, """
            MODE = "__main__"
            """)
        assert fs == []

    def test_inline_error_prefix_fires_constant_does_not(
            self, tmp_path):
        fs = self._project(tmp_path, """
            from .protocol import DEADLINE_PREFIX, CIRCUIT_PREFIX

            def reject(uri):
                return "deadline_exceeded: request " + uri

            def ok(uri):
                return f"{DEADLINE_PREFIX}: request {uri}"

            _USED = CIRCUIT_PREFIX
            """, refs=False)
        assert rules_of(fs) == ["error-prefix-literal"]
        assert len(fs) == 1

    def test_event_emission_is_not_a_prefix_copy(self, tmp_path):
        """emit("deadline_exceeded", ...) is the EVENT vocabulary --
        a different namespace, owned by the vocabulary family."""
        fs = self._project(tmp_path, """
            def on_expire(emit):
                emit("deadline_exceeded", "serving", uri="u")
            """)
        assert fs == []

    def test_frontend_unmapped_prefix_fires_via_indirection(
            self, tmp_path):
        """Satellite fixture: the frontend maps a prefix no worker
        declares -- through one level of variable indirection, so the
        dataflow layer (not a literal grep) must catch it."""
        fs = self._project(tmp_path, """
            _PREFIX = "deadline_exceded"

            def to_http(msg):
                if msg.startswith(_PREFIX):
                    return 504
                return 500
            """)
        assert "error-prefix-unknown" in rules_of(fs)
        assert any("deadline_exceded" in f.message for f in fs)

    def test_declared_prefix_startswith_does_not_fire(self, tmp_path):
        fs = self._project(tmp_path, """
            from .protocol import DEADLINE_PREFIX, CIRCUIT_PREFIX

            def to_http(msg):
                if msg.startswith(DEADLINE_PREFIX):
                    return 504
                if msg.startswith("tcp://"):
                    return 0
                return 500

            _USED = CIRCUIT_PREFIX
            """, refs=False)
        assert fs == []

    def test_scheme_sniffing_startswith_does_not_fire(self, tmp_path):
        """Snake-case startswith literals that are NOT near a declared
        prefix are ordinary string tests (backend scheme sniffing) --
        the unknown-prefix rule targets typos, not every word."""
        fs = self._project(tmp_path, """
            def pick(backend):
                if backend.startswith("redis"):
                    return "redis"
                if backend.startswith("unix"):
                    return "unix"
                return "memory"
            """)
        assert fs == []

    def test_multiline_suppression_does_not_leak_across_match(
            self, tmp_path):
        """A disable comment inside one match case must not silence a
        finding in a sibling case (Match is a compound statement)."""
        fs = self._project(tmp_path, """
            def decode(z, mode):
                match mode:
                    case "a":
                        x = "fine"  # zoolint: disable=wire-key-literal
                    case _:
                        x = z["__deadlin__"]
                return x
            """)
        assert rules_of(fs) == ["wire-key-literal"]

    def test_prefix_missing_from_error_prefixes_fires(self, tmp_path):
        fs = self._project(tmp_path, "X = 1\n", home="""
URI_KEY = "__uri__"
WIRE_KEYS = (URI_KEY,)
DEADLINE_PREFIX = "deadline_exceeded"
CIRCUIT_PREFIX = "circuit_open"
OOM_PREFIX = "oom_killed"
ERROR_PREFIXES = {DEADLINE_PREFIX: 504, CIRCUIT_PREFIX: 503}
""" + "_OOM_USED_ELSEWHERE = None\n")
        # OOM_PREFIX: no HTTP mapping AND never referenced outside
        unmapped = [f for f in fs if f.rule == "error-prefix-unmapped"]
        assert len(unmapped) == 2
        assert all("OOM_PREFIX" in f.message for f in unmapped)

    def test_second_vocab_module_fires(self, tmp_path):
        fs = self._project(tmp_path, """
            ROGUE_PREFIX = "shed_overload"
            """)
        assert "protocol-vocab-module" in rules_of(fs)


# ===================================================================== #
# config-type (family 3 extension)                                      #
# ===================================================================== #
CONFIG_TYPED_FIXTURE = """
_DEFAULTS = {
    "zoo.a.count": 4,
    "zoo.a.rate": 0.5,
    "zoo.a.mode": "auto",
}
_SPECS = {
    "zoo.a.count": ("int", 1, 64),
    "zoo.a.rate": ("float", 0, None),
    "zoo.a.mode": ("enum", "auto", "fast"),
}
"""


class TestConfigTypes:
    CHECKER = [ConfigKeyChecker()]

    def _project(self, tmp_path, user_code,
                 fixture=CONFIG_TYPED_FIXTURE):
        (tmp_path / "common").mkdir(parents=True, exist_ok=True)
        (tmp_path / "common" / "config.py").write_text(fixture)
        (tmp_path / "user.py").write_text(textwrap.dedent(user_code))
        fs = run_zoolint([str(tmp_path)], checkers=self.CHECKER,
                         repo_root=str(tmp_path))
        return [f for f in fs if f.rule == "config-type"]

    def test_contradicting_default_and_range_fire(self, tmp_path):
        fs = self._project(tmp_path, """
            def f(cfg):
                a = cfg.get("zoo.a.count", "lots")
                b = cfg.get("zoo.a.count", 128)
                c = cfg.get("zoo.a.mode", "turbo")
                return a, b, c
            """)
        msgs = [f.message for f in fs]
        assert len(fs) == 3
        assert any("'lots'" in m for m in msgs)
        assert any("<= 64" in m for m in msgs)
        assert any("'turbo'" in m for m in msgs)

    def test_contradicting_cast_fires(self, tmp_path):
        fs = self._project(tmp_path, """
            def f(cfg):
                return int(cfg.get("zoo.a.mode", "auto"))
            """)
        assert len(fs) == 1 and "int() cast" in fs[0].message

    def test_compatible_sites_do_not_fire(self, tmp_path):
        """int default for a float key, get(key, None) sentinel, and a
        matching cast are all fine."""
        fs = self._project(tmp_path, """
            def f(cfg):
                a = float(cfg.get("zoo.a.rate", 1))
                b = cfg.get("zoo.a.count", None)
                c = int(cfg.get("zoo.a.count", 8))
                return a, b, c
            """)
        assert fs == []

    def test_spec_defaults_self_check_fires(self, tmp_path):
        fs = self._project(tmp_path, "X = 1\n", fixture="""
_DEFAULTS = {
    "zoo.a.count": 0,
}
_SPECS = {
    "zoo.a.count": ("int", 1, 64),
    "zoo.a.ghost": ("bool",),
}
""")
        msgs = [f.message for f in fs]
        assert len(fs) == 2
        assert any("violates its own _SPECS" in m for m in msgs)
        assert any("ghost" in m for m in msgs)

    def test_runtime_validators_agree_with_specs(self):
        """The shipped _DEFAULTS must satisfy the shipped _SPECS (the
        lint self-check, exercised at runtime too)."""
        from analytics_zoo_tpu.common import config as cfg_mod
        for key, default in cfg_mod._DEFAULTS.items():
            cfg_mod.validate_config_value(key, default)
        with pytest.raises(ValueError):
            cfg_mod.validate_config_value(
                "zoo.serving.pipeline.depth", 0)
        with pytest.raises(ValueError):
            cfg_mod.validate_config_value(
                "zoo.ops.ring_schedule", "turbo")


# ===================================================================== #
# CLI contract                                                          #
# ===================================================================== #
VIOLATIONS = {
    # one deliberate violation per checker family (ISSUE-4 + the
    # ISSUE-6 shardcheck families)
    "trace": ("pkg/step.py", """
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
        """),
    "concurrency": ("pkg/serving/w.py", """
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self.run)
                self._t.start()
        """),
    "config": ("pkg/common/config.py", """
        _DEFAULTS = {"zoo.dead.key": 1}
        _SPECS = {"zoo.dead.key": ("bool",)}
        """),
    "vocabulary": ("pkg/metrics_owner.py", """
        _REG = object()
        _M = _REG.counter("not_a_zoo_metric", "bad name")
        """),
    "mesh": ("pkg/par.py", """
        import jax

        def body(x):
            return x

        f = jax.shard_map(body, mesh=None, in_specs=(None, None),
                          out_specs=None)
        """),
    "protocol": ("pkg/serving/fe.py", """
        from pkg.serving.proto import DEADLINE_PREFIX

        def decode(z, msg):
            _USED = DEADLINE_PREFIX
            return z["__deadlin__"]
        """),
    "protocol_home": ("pkg/serving/proto.py", """
        URI_KEY = "__uri__"
        WIRE_KEYS = (URI_KEY,)
        DEADLINE_PREFIX = "deadline_exceeded"
        ERROR_PREFIXES = {DEADLINE_PREFIX: 504}
        """),
}


def _run_cli(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, CLI] + args, cwd=cwd, env=env,
        capture_output=True, text=True, timeout=180)


class TestCLI:
    @pytest.fixture(scope="class")
    def violation_tree(self, tmp_path_factory):
        root = tmp_path_factory.mktemp("zoolint_cli")
        for _family, (rel, code) in VIOLATIONS.items():
            path = root / rel
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(textwrap.dedent(code))
        return root

    def test_nonzero_exit_and_all_families_reported(
            self, violation_tree):
        """One subprocess run covers the acceptance criterion for all
        families: deliberate violations -> exit 1, each family's rule
        named in the output."""
        proc = _run_cli(["--no-baseline", "--json", "pkg"],
                        cwd=str(violation_tree))
        assert proc.returncode == 1, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        fired = {f["rule"] for f in payload["new"]}
        assert "jit-tracer-branch" in fired          # family 1
        assert "thread-join" in fired                # family 2
        assert "config-unused" in fired              # family 3
        assert "metric-name" in fired                # family 4
        assert "config-type" in fired                # ISSUE-6 family 3
        assert "mesh-spec-arity" in fired            # ISSUE-6 family 1
        assert "wire-key-literal" in fired           # ISSUE-6 family 2

    def test_baseline_workflow_grandfathers_findings(
            self, violation_tree):
        baseline = str(violation_tree / "bl.json")
        up = _run_cli(["--baseline", baseline, "--update-baseline",
                       "pkg"], cwd=str(violation_tree))
        assert up.returncode == 0, up.stdout + up.stderr
        again = _run_cli(["--baseline", baseline, "pkg"],
                         cwd=str(violation_tree))
        assert again.returncode == 0, again.stdout + again.stderr
        assert "0 new" in again.stdout

    def test_list_rules(self, violation_tree):
        proc = _run_cli(["--list-rules"], cwd=str(violation_tree))
        assert proc.returncode == 0
        for rule in ("jit-tracer-branch", "lock-order",
                     "config-undeclared", "event-type",
                     "silent-except"):
            assert rule in proc.stdout

    def test_unknown_rule_is_a_usage_error(self, violation_tree):
        proc = _run_cli(["--rules", "no-such-rule", "pkg"],
                        cwd=str(violation_tree))
        assert proc.returncode == 2

    def test_update_baseline_refuses_rule_subset(self, violation_tree):
        """A filtered run must not rewrite the baseline -- it would
        silently drop every grandfathered entry outside the slice."""
        proc = _run_cli(["--rules", "silent-except",
                         "--update-baseline", "pkg"],
                        cwd=str(violation_tree))
        assert proc.returncode == 2
        assert "full-rule run" in proc.stderr

    def test_rules_subset_skips_other_families(self, violation_tree):
        """--rules restricts which checkers RUN, not just which
        findings print: the violation tree has trace/concurrency/
        config/vocabulary hits, but a thread-join-only run reports
        nothing else."""
        proc = _run_cli(["--no-baseline", "--json", "--rules",
                         "thread-join", "pkg"],
                        cwd=str(violation_tree))
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert {f["rule"] for f in payload["new"]} == {"thread-join"}


class TestChangedMode:
    """--changed lints only files changed vs a git ref. These tests
    run the CLI against THIS repository (the CLI anchors --changed to
    its own repo root), so they assert contracts that hold for any
    working-tree state: a bogus ref falls back to a full run, and the
    no-op fast path prints the 0-findings line without importing the
    checker stack."""

    def test_bad_ref_falls_back_to_full_run(self, tmp_path):
        proc = _run_cli(["--changed", "no-such-ref-xyz",
                         "--no-baseline"], cwd=str(tmp_path))
        assert "falling back to a full run" in proc.stderr

    def test_changed_refuses_update_baseline(self, tmp_path):
        proc = _run_cli(["--changed", "--update-baseline"],
                        cwd=str(tmp_path))
        assert proc.returncode == 2
        assert "full run" in proc.stderr

    def test_changed_scopes_to_lint_paths(self, tmp_path):
        """Changed files OUTSIDE the lint paths are not linted: point
        the path filter at an empty dir -> the fast no-op path."""
        empty = tmp_path / "nothing_here"
        empty.mkdir()
        proc = _run_cli(["--changed", "HEAD", str(empty)],
                        cwd=str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 finding(s), 0 new" in proc.stdout

    def test_changed_json_fast_path_emits_json(self, tmp_path):
        """--changed --json must produce the documented object shape
        even on the nothing-changed fast path (jq consumers)."""
        empty = tmp_path / "nothing_here"
        empty.mkdir()
        proc = _run_cli(["--changed", "HEAD", "--json", str(empty)],
                        cwd=str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["counts"]["total"] == 0
        assert payload["new"] == []

    def test_changed_reports_only_changed_files(self, tmp_path,
                                                monkeypatch):
        """End-to-end in a scratch git repo: two files violate, one is
        committed clean history, only the CHANGED one is reported."""
        import shutil

        repo = tmp_path / "repo"
        pkg = repo / "pkg"
        pkg.mkdir(parents=True)
        clean = textwrap.dedent("""
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self.run)
                    self._t.start()
        """)
        (pkg / "serving").mkdir()
        (pkg / "serving" / "old.py").write_text(clean)
        (pkg / "serving" / "new.py").write_text("X = 1\n")
        # the CLI anchors its repo root two levels above itself, so
        # install it as <repo>/scripts/zoolint.py in the scratch repo
        (repo / "scripts").mkdir()
        cli_copy = repo / "scripts" / "zoolint.py"
        shutil.copy(CLI, cli_copy)

        def git(*args):
            return subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                 *args], cwd=str(repo), capture_output=True,
                text=True, timeout=60)

        assert git("init", "-q").returncode == 0
        assert git("add", "-A").returncode == 0
        assert git("commit", "-qm", "seed").returncode == 0
        # old.py's violation is committed history; new.py gains one
        (pkg / "serving" / "new.py").write_text(clean)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, str(cli_copy),
             "--changed", "HEAD", "--no-baseline", "--json", "pkg"],
            cwd=str(repo), env=env, capture_output=True, text=True,
            timeout=180)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        paths = {f["path"] for f in payload["new"]}
        assert paths == {"pkg/serving/new.py"}


# ===================================================================== #
# the tier-1 gate                                                       #
# ===================================================================== #
class TestPackageGate:
    def test_rule_catalog_covers_four_families_plus_hygiene(self):
        rules = all_rules()
        families = {r.split("-")[0] for r in rules}
        assert {"jit", "lock", "thread", "config", "metric",
                "event", "silent"} <= families

    def test_package_is_lint_clean_modulo_baseline(self):
        """THE gate: the full checker suite over analytics_zoo_tpu/
        yields no findings beyond the checked-in baseline. When this
        fails: fix the finding, suppress inline with
        ``# zoolint: disable=<rule>`` + a comment, or (last resort)
        ``python scripts/zoolint.py --update-baseline`` and add a
        rationale to the new entry."""
        findings = run_zoolint([PACKAGE], repo_root=REPO)
        baseline = load_baseline(BASELINE)
        fresh = new_findings(findings, baseline)
        assert not fresh, (
            "new zoolint findings (fix, suppress with rationale, or "
            "baseline with rationale):\n"
            + "\n".join(f.render() for f in fresh))

    def test_baseline_entries_carry_rationales(self):
        """A grandfathered finding without a written reason is just a
        hidden finding."""
        baseline = load_baseline(BASELINE)
        missing = [k for k, e in baseline.items()
                   if not e.get("rationale", "").strip()]
        assert not missing, (
            f"baseline entries missing a rationale: {missing}")


# ===================================================================== #
# deepcheck (ISSUE-8): call graph + interprocedural families            #
# ===================================================================== #
def _graph_of(tmp_path, files):
    """Write {rel: code} and build the call graph over the tree."""
    from analytics_zoo_tpu.analysis.callgraph import build_call_graph
    from analytics_zoo_tpu.analysis.core import Project, collect_files

    for rel, code in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(code))
    parsed, root = collect_files([str(tmp_path)],
                                 repo_root=str(tmp_path))
    return build_call_graph(Project(parsed, repo_root=root))


def _node(graph, suffix):
    hits = [n for n in graph.nodes if n.qname.endswith(suffix)]
    assert len(hits) == 1, f"{suffix}: {[n.qname for n in hits]}"
    return hits[0]


class TestCallGraph:
    def test_cross_module_import_edge_and_context(self, tmp_path):
        """A helper imported from another module inherits jit context
        and per-parameter tracer taint through the edge."""
        g = _graph_of(tmp_path, {
            "main.py": """
                import jax
                from pkg.helpers import helper

                @jax.jit
                def step(x):
                    return helper(x * 2)
                """,
            "pkg/helpers.py": """
                def helper(z):
                    return z + 1
                """,
        })
        helper = _node(g, "pkg/helpers.py::helper")
        assert "jit" in helper.contexts
        assert helper.tracer_params == {"z"}
        assert not helper.jit_direct

    def test_module_alias_import_resolves(self, tmp_path):
        g = _graph_of(tmp_path, {
            "main.py": """
                import jax
                from pkg import helpers

                @jax.jit
                def step(x):
                    return helpers.helper(x)
                """,
            "pkg/helpers.py": """
                def helper(z):
                    return z
                """,
        })
        assert "jit" in _node(g, "pkg/helpers.py::helper").contexts

    def test_self_method_resolution_including_nested_step(self, tmp_path):
        """The repo's jitted-step idiom: a def nested inside a method
        calls ``self._math`` -- the nested def's owning class resolves
        through the enclosing chain."""
        g = _graph_of(tmp_path, {
            "est.py": """
                import jax

                class Est:
                    def _math(self, v, x):
                        return v + x

                    def build(self):
                        def step(v, x):
                            return self._math(v, x)
                        return jax.jit(step)
                """,
        })
        math = _node(g, "est.py::Est._math")
        assert "jit" in math.contexts
        assert math.tracer_params == {"v", "x"}

    def test_alias_indirection_one_level(self, tmp_path):
        """``self._step = jax.jit(step)`` then ``self._step(...)``
        resolves through the self-attribute alias + jit unwrap."""
        g = _graph_of(tmp_path, {
            "w.py": """
                import jax

                def step(x):
                    return x

                class Runner:
                    def __init__(self):
                        self._step = jax.jit(step)

                    def run(self, batch):
                        return self._step(batch)
                """,
        })
        runner = _node(g, "w.py::Runner.run")
        assert [e.callee.name for e in runner.edges_out] == ["step"]

    def test_unresolvable_calls_are_conservative(self, tmp_path):
        """Dict dispatch / attribute calls on unknown objects make NO
        edges (and no contexts leak), they are only counted."""
        g = _graph_of(tmp_path, {
            "d.py": """
                import jax

                def helper(z):
                    return z

                HANDLERS = {"h": helper}

                @jax.jit
                def step(x, obj):
                    HANDLERS["h"](x)
                    obj.method(x)
                    return x
                """,
        })
        helper = _node(g, "d.py::helper")
        assert helper.contexts == set()
        assert sum(g.unresolved.values()) >= 2

    def test_hot_path_roots_and_finalize_barrier(self, tmp_path):
        g = _graph_of(tmp_path, {
            "w.py": """
                class ServingWorker:
                    def _dispatch_group(self, group):
                        shared(group)
                        self._finalize_record(group)

                    def _finalize_record(self, rec):
                        sink(rec)

                def shared(g):
                    return g

                def sink(r):
                    return r
                """,
        })
        assert "hotpath" in _node(g, "w.py::shared").contexts
        seam = _node(g, "w.py::ServingWorker._finalize_record")
        assert "hotpath" not in seam.contexts
        assert "hotpath" not in _node(g, "w.py::sink").contexts

    def test_declared_hot_path_roots(self, tmp_path):
        g = _graph_of(tmp_path, {
            "svc.py": """
                ZOOLINT_HOT_PATH = ("serve_one", "Engine.tick")

                def serve_one(req):
                    return req

                class Engine:
                    def tick(self):
                        return 1
                """,
        })
        assert "hotpath" in _node(g, "svc.py::serve_one").contexts
        assert "hotpath" in _node(g, "svc.py::Engine.tick").contexts

    def test_graph_dump_shape(self, tmp_path):
        g = _graph_of(tmp_path, {
            "m.py": """
                import jax

                def helper(z):
                    return z

                @jax.jit
                def step(x):
                    return helper(x)
                """,
        })
        d = g.to_dict()
        assert d["counts"]["functions"] == 2
        assert d["counts"]["edges"] == 1
        helper = [f for f in d["functions"]
                  if f["qname"].endswith("::helper")][0]
        assert helper["contexts"] == ["jit"]
        assert helper["tracer_params"] == ["z"]

    def test_partial_wrapped_body_marked_collective(self, tmp_path):
        """The pipeline idiom: a plain module function traced through
        ``shard_map(partial(body, ...), ...)`` via an alias -- the
        resolution gap that hid the real lax.axis_size crashes. The
        partial's kw-bound params must NOT carry tracer taint."""
        g = _graph_of(tmp_path, {
            "pipe.py": """
                import jax
                from functools import partial

                def _local(params, batch, stage_fn, axis_name):
                    return stage_fn(params, batch)

                def apply(params, batch, mesh, sf):
                    body = partial(_local, stage_fn=sf,
                                   axis_name="stage")
                    fn = jax.shard_map(body, mesh=mesh,
                                       in_specs=None, out_specs=None)
                    return fn(params, batch)
                """,
        })
        local = _node(g, "pipe.py::_local")
        assert {"jit", "collective"} <= local.contexts
        assert local.tracer_params == {"params", "batch"}
        assert not local.jit_direct  # PR 4 cannot see this form

    def test_param_wrapped_body_resolves_at_call_site(self, tmp_path):
        """One higher-order level: ``_shard_call`` wraps its own
        PARAMETER; the wrapped function is whatever its resolved call
        sites pass (the ring-attention idiom)."""
        g = _graph_of(tmp_path, {
            "ring.py": """
                import jax
                from functools import partial

                def _attn_local(q, k, v, axis_name):
                    return q

                def _shard_call(local_fn, q, k, v, mesh):
                    fn = jax.shard_map(
                        partial(local_fn, axis_name="seq"),
                        mesh=mesh, in_specs=None, out_specs=None)
                    return fn(q, k, v)

                def ring_attention(q, k, v, mesh):
                    return _shard_call(_attn_local, q, k, v, mesh)
                """,
        })
        local = _node(g, "ring.py::_attn_local")
        assert "collective" in local.contexts
        assert local.tracer_params == {"q", "k", "v"}

    def test_splat_partial_propagates_context_not_taint(self, tmp_path):
        """A **kwargs splat in the partial can bind ANY parameter --
        binding is unknowable, so context propagates but no parameter
        may claim tracer taint (conservatism over coverage)."""
        g = _graph_of(tmp_path, {
            "m.py": """
                import jax
                from functools import partial

                def _local(x, causal):
                    return x if causal else -x

                def call(x, mesh, **kw):
                    fn = jax.shard_map(partial(_local, **kw),
                                       mesh=mesh, in_specs=None,
                                       out_specs=None)
                    return fn(x)
                """,
        })
        local = _node(g, "m.py::_local")
        assert "collective" in local.contexts
        assert local.tracer_params == set()


class TestDeepRules:
    def deep(self):
        from analytics_zoo_tpu.analysis.deep_rules import DeepChecker

        return [DeepChecker()]

    # ---- family 1: transitive trace hazards ------------------------- --
    def test_transitive_numpy_call_fires_one_call_deep(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import numpy as np

            def helper(z):
                return np.clip(z, 0, 1)

            @jax.jit
            def step(x):
                return helper(x * 2)
            """, self.deep())
        assert rules_of(fs) == ["jit-numpy-call"]
        assert "reached from jit-traced 'step'" in fs[0].message

    def test_same_helper_unreached_from_jit_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import numpy as np

            def helper(z):
                return np.clip(z, 0, 1)

            def host_loop(x):
                return helper(x)
            """, self.deep())
        assert fs == []

    def test_transitive_concretize_and_branch(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import jax.numpy as jnp

            def helper(z):
                total = jnp.sum(z)
                if total > 0:
                    return float(total)
                return 0.0

            @jax.jit
            def step(x):
                return helper(x)
            """, self.deep())
        assert rules_of(fs) == ["jit-concretize", "jit-tracer-branch"]

    def test_untainted_param_does_not_fire(self, tmp_path):
        """The jit caller passes a STATIC value -- the helper's numpy
        call is host math on a constant, not a trace hazard."""
        fs = lint(tmp_path, """
            import jax
            import numpy as np

            def helper(k):
                return np.log2(k)

            @jax.jit
            def step(x):
                return x * helper(x.shape[0])
            """, self.deep())
        assert fs == []

    def test_np_metadata_probe_is_static(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import numpy as np

            def spec_for(z):
                return np.ndim(z)

            @jax.jit
            def step(x):
                return x * spec_for(x)
            """, self.deep())
        assert fs == []

    def test_no_double_report_with_old_engine(self, tmp_path):
        """A hazard in a DIRECTLY jitted body belongs to the PR-4
        family; running both checkers reports it exactly once."""
        code = """
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                return np.sum(x)
            """
        both = lint(tmp_path, code,
                    [TraceHazardChecker()] + self.deep())
        assert len(both) == 1

    def test_host_callback_fires_and_suppresses(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                return jax.pure_callback(lambda a: a, x, x)
            """, self.deep())
        assert rules_of(fs) == ["jit-host-callback-undeclared"]
        fs = lint(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                # host metric hook, once per epoch by construction
                return jax.pure_callback(lambda a: a, x, x)  # zoolint: disable=jit-host-callback-undeclared
            """, self.deep())
        assert fs == []

    # ---- family 2: hot-path host syncs ------------------------------ --
    HOT_TP = """
        import jax.numpy as jnp
        import numpy as np

        class ServingWorker:
            def _dispatch_group(self, group):
                preds, n = self.model.predict_async(group)
                return fetch_rows(preds, n)

        def fetch_rows(preds, n):
            return np.asarray(preds)[:n]
        """

    def test_hotpath_sync_fires_one_call_deep(self, tmp_path):
        fs = lint(tmp_path, self.HOT_TP, self.deep())
        assert rules_of(fs) == ["hotpath-block-on-device"]
        assert "np.asarray" in fs[0].message

    def test_same_sync_outside_hot_path_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import numpy as np

            class Trainer:
                def evaluate(self, model, group):
                    preds, n = model.predict_async(group)
                    return fetch_rows(preds, n)

            def fetch_rows(preds, n):
                return np.asarray(preds)[:n]
            """, self.deep())
        assert fs == []

    def test_finalize_seam_is_exempt(self, tmp_path):
        fs = lint(tmp_path, """
            import numpy as np

            class ServingWorker:
                def _run_pipelined(self, q):
                    self._finalize_record(q)

                def _finalize_record(self, rec):
                    return np.asarray(rec[3]).tolist()
            """, self.deep())
        assert fs == []

    def test_host_data_asarray_in_stage_is_clean(self, tmp_path):
        """np.asarray over DECODED REQUEST tensors (host data) in the
        decode stage is the engine's bread and butter -- only proven
        device values fire."""
        fs = lint(tmp_path, """
            import numpy as np

            class ServingWorker:
                def _decode_stage(self, blobs):
                    return [np.asarray(b) for b in blobs]
            """, self.deep())
        assert fs == []

    def test_block_until_ready_always_fires_in_hot_context(
            self, tmp_path):
        fs = lint(tmp_path, """
            class ServingWorker:
                def _dispatch_group(self, group):
                    return drain(group)

            def drain(batch):
                batch.block_until_ready()
                return batch
            """, self.deep())
        assert rules_of(fs) == ["hotpath-block-on-device"]

    # ---- family 3: dtype drift -------------------------------------- --
    def test_f32_into_bf16_param_fires(self, tmp_path):
        fs = lint(tmp_path, """
            import jax.numpy as jnp
            import numpy as np

            def bn_stat(x, scale=jnp.bfloat16(1.0)):
                return x * scale

            def caller(x):
                return bn_stat(x, np.float32(0.5))
            """, self.deep())
        assert rules_of(fs) == ["dtype-upcast-f32"]

    def test_weak_python_float_does_not_fire(self, tmp_path):
        fs = lint(tmp_path, """
            import jax.numpy as jnp

            def bn_stat(x, scale=jnp.bfloat16(1.0)):
                return x * scale

            def caller(x):
                return bn_stat(x, 0.5)
            """, self.deep())
        assert fs == []

    def test_f32_array_through_local_alias_fires(self, tmp_path):
        fs = lint(tmp_path, """
            import jax.numpy as jnp
            import numpy as np

            def kern(x, eps=jnp.bfloat16(1e-3)):
                return x + eps

            def caller(x):
                e = np.zeros((), np.float32)
                return kern(x, e)
            """, self.deep())
        assert rules_of(fs) == ["dtype-upcast-f32"]

    def test_mixed_collective_fires_single_dtype_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import jax.numpy as jnp
            from jax import lax

            def mixed(x, y):
                return lax.psum(x.astype(jnp.bfloat16)
                                + y.astype(jnp.float32), "data")

            def uniform(x, y):
                return lax.psum(x.astype(jnp.bfloat16)
                                + y.astype(jnp.bfloat16), "data")
            """, self.deep())
        assert rules_of(fs) == ["dtype-mixed-collective"]
        assert len(fs) == 1

    # ---- family 4: version-fragile collective API ------------------- --
    def test_axis_size_in_propagated_collective_context(self, tmp_path):
        """THE interprocedural case from the real tree: a plain local
        body only provably collective through shard_map(partial(...))
        resolution calls the jax>=0.5-only lax.axis_size."""
        fs = lint(tmp_path, """
            import jax
            from functools import partial
            from jax import lax

            def _local(params, batch, axis_name):
                n = lax.axis_size(axis_name)
                return params, batch, n

            def apply(params, batch, mesh):
                body = partial(_local, axis_name="stage")
                fn = jax.shard_map(body, mesh=mesh, in_specs=None,
                                   out_specs=None)
                return fn(params, batch)
            """, self.deep())
        rules = rules_of(fs)
        assert "collective-version-api" in rules
        api = [f for f in fs if f.rule == "collective-version-api"]
        assert len(api) == 1
        assert "traced via 'apply'" in api[0].message

    def test_axis_size_unreached_from_collective_is_clean(self,
                                                          tmp_path):
        """Same call in a function no shard_map ever traces: not this
        rule's business (it would be a plain runtime error anyway)."""
        fs = lint(tmp_path, """
            from jax import lax

            def host_side(axis_name):
                return lax.axis_size(axis_name)
            """, self.deep())
        assert fs == []

    def test_shard_map_direct_fires_compat_module_exempt(self,
                                                         tmp_path):
        """Direct jax.shard_map use (call or import-from) fires
        anywhere except the one compat wrapper, parallel/mesh.py."""
        from analytics_zoo_tpu.analysis.core import (
            Project, collect_files)
        from analytics_zoo_tpu.analysis.deep_rules import DeepChecker

        files = {
            "model.py": """
                import jax

                def run(f, mesh):
                    return jax.shard_map(f, mesh=mesh, in_specs=None,
                                         out_specs=None)
                """,
            "legacy.py": """
                from jax.experimental.shard_map import shard_map
                """,
            "parallel/mesh.py": """
                import jax

                def shard_map(f, mesh, in_specs, out_specs):
                    sm = getattr(jax, "shard_map", None)
                    if sm is not None:
                        return sm(f, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs)
                    from jax.experimental.shard_map import \\
                        shard_map as esm
                    return esm(f, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs)
                """,
        }
        for rel, code in files.items():
            path = tmp_path / rel
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(textwrap.dedent(code))
        parsed, root = collect_files([str(tmp_path)],
                                     repo_root=str(tmp_path))
        fs = [f for f in DeepChecker().check_project(
            Project(parsed, repo_root=root))
            if f.rule == "shard-map-direct"]
        assert sorted(f.path for f in fs) == ["legacy.py", "model.py"]

    def test_compat_shard_map_wrapper_use_is_clean(self, tmp_path):
        """Routing through the compat wrapper -- the fixed form of
        every real finding -- is exactly what the rule wants."""
        fs = lint(tmp_path, """
            from analytics_zoo_tpu.parallel.mesh import shard_map

            def run(f, mesh):
                return shard_map(f, mesh, in_specs=None,
                                 out_specs=None)
            """, self.deep())
        assert fs == []

    # ---- conservatism / robustness regressions ---------------------- --
    def test_self_referential_assign_does_not_recurse(self, tmp_path):
        """``acc = acc + jnp...`` in a hot-path stage: the device walk
        must terminate (regression: RecursionError killed the whole
        run) and the accumulated jnp value still counts as device."""
        fs = lint(tmp_path, """
            import jax.numpy as jnp
            import numpy as np

            class ServingWorker:
                def _dispatch_group(self, group):
                    acc = jnp.zeros(3)
                    acc = acc + jnp.ones(3)
                    buf = group
                    buf = buf[1:]
                    np.asarray(buf)  # host value: clean
                    return np.asarray(acc)
            """, self.deep())
        assert rules_of(fs) == ["hotpath-block-on-device"]
        assert len(fs) == 1

    def test_partial_alias_call_claims_no_bindings(self, tmp_path):
        """``body = partial(helper, cfg); body(x)`` inside jit: the
        pre-bound positional shifts the param map, so the edge must
        claim NO argument bindings (regression: x was bound to the
        static first param, a false-positive jit-numpy-call)."""
        fs = lint(tmp_path, """
            import jax
            import numpy as np
            from functools import partial

            def helper(cfg, z):
                return np.log2(cfg["levels"]) + z

            @jax.jit
            def step(x):
                body = partial(helper, {"levels": 4})
                return body(x)
            """, self.deep())
        assert fs == []

    def test_shape_metadata_on_device_value_is_clean(self, tmp_path):
        """``int(preds.shape[0])`` in a stage reads host metadata --
        no d2h sync, no finding (regression: the device walk recursed
        through .shape and flagged it)."""
        fs = lint(tmp_path, """
            class ServingWorker:
                def _dispatch_group(self, group):
                    preds, n = self.model.predict_async(group)
                    k = int(preds.shape[0])
                    return k
            """, self.deep())
        assert fs == []

    def test_explicit_dtype_selector_kwarg_is_clean(self, tmp_path):
        """``dtype=np.float32`` into a ``dtype=jnp.bfloat16``-defaulted
        param is the caller CHOOSING f32 (master weights idiom), not a
        silent upcast (regression: flagged as dtype-upcast-f32)."""
        fs = lint(tmp_path, """
            import jax.numpy as jnp
            import numpy as np

            def init_buf(shape, dtype=jnp.bfloat16):
                return jnp.zeros(shape, dtype)

            def master_weights(shape):
                return init_buf(shape, dtype=np.float32)
            """, self.deep())
        assert fs == []

    def test_nested_def_findings_fire_once(self, tmp_path):
        """A hazard inside a def nested in a jitted function must be
        reported exactly once (regression: the parent's walk descended
        into the nested body and double-reported)."""
        fs = lint(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                def inner(y):
                    return jax.pure_callback(abs, y, y)
                return inner(x)
            """, self.deep())
        assert rules_of(fs) == ["jit-host-callback-undeclared"]
        assert len(fs) == 1


class TestOldEngineMisses:
    """THE ISSUE-8 acceptance test: hazards one call deep that the
    PR-4/PR-6 intraprocedural engine cannot see -- each fixture is the
    minimal form of a pattern from this repo's own history (the
    pre-pipelining dispatch-stage fetch PR 1 moved into the finalize
    seam, a helper extracted from a jitted step, an f32 constant
    flowing into a bf16 kernel, and the pipeline/ring-attention local
    body whose jax>=0.5-only lax.axis_size -- invisible without
    shard_map(partial(...)) resolution -- this PR found at 3 real
    sites and fixed, along with 7 direct jax.shard_map uses)."""

    FIXTURE = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        # 1. the pre-PR-1 serving engine: dispatch stage fetched its
        #    results synchronously (stalling the decode/dispatch
        #    overlap); one helper-extraction deep, invisible to a
        #    per-function scan
        class ServingWorker:
            def _dispatch_group(self, group):
                preds, n = self.model.predict_async(group)
                return rows_of(preds, n)

        def rows_of(preds, n):
            return np.asarray(preds)[:n]

        # 2. a numpy helper extracted from a jitted step: the PR-4
        #    scan checks step's own body only
        def normalize(z):
            return np.clip(z, 0.0, 1.0)

        @jax.jit
        def step(x):
            return normalize(x * 2)

        # 3. the BN-profile upcast: an f32 constant flowing into a
        #    bf16-defaulted kernel helper (BENCH_NOTES r4: 31% of
        #    ResNet-50 step time in f32 BN convert fusions)
        def bn_kernel(x, eps=jnp.bfloat16(1e-3)):
            return x + eps

        def model_forward(x):
            return bn_kernel(x, np.float32(1e-3))

        # 4. the pre-deepcheck parallel/ layer, verbatim idiom: a
        #    plain local body traced through shard_map(partial(...))
        #    calls the jax>=0.5-only lax.axis_size -- a crash on the
        #    0.4.x rigs that no per-function scan can connect to the
        #    collective wrap two hops away (pipeline.py:39 and
        #    ring_attention.py:83/256 before this PR), plus the direct
        #    jax.shard_map call itself (absent on 0.4.x)
        def _pipeline_local(params, batch, stage_fn, axis_name):
            n_stages = jax.lax.axis_size(axis_name)
            return stage_fn(params, batch) / n_stages

        def pipeline_apply(params, batch, mesh, stage_fn):
            from functools import partial
            body = partial(_pipeline_local, stage_fn=stage_fn,
                           axis_name="stage")
            fn = jax.shard_map(body, mesh=mesh, in_specs=None,
                               out_specs=None)
            return fn(params, batch)
        """

    def old_engine(self):
        return [TraceHazardChecker(), ConcurrencyChecker(),
                ConfigKeyChecker(), VocabularyChecker(),
                HygieneChecker(), MeshCollectiveChecker(),
                ProtocolChecker()]

    def test_old_engine_misses_all_of_them(self, tmp_path):
        fs = lint(tmp_path, self.FIXTURE, self.old_engine())
        assert fs == [], [f.render() for f in fs]

    def test_deepcheck_finds_all_of_them(self, tmp_path):
        from analytics_zoo_tpu.analysis.deep_rules import DeepChecker

        fs = lint(tmp_path, self.FIXTURE, [DeepChecker()])
        assert rules_of(fs) == ["collective-version-api",
                                "dtype-upcast-f32",
                                "hotpath-block-on-device",
                                "jit-numpy-call",
                                "shard-map-direct"]
        assert len(fs) == 5


class TestLintBudget:
    def test_full_tree_lint_under_30s(self):
        """The whole-package run -- call-graph construction AND the
        lifecycle engine's per-function CFG product walk included --
        must stay a usable gate. 30 s is ~3x the current cost; if this
        fails, run ``scripts/zoolint.py --profile`` and attack the
        biggest family (historically callgraph._propagate or the
        lifecycle walk's state count) before reaching for caching."""
        import time

        timings = {}
        t0 = time.monotonic()
        run_zoolint([PACKAGE], repo_root=REPO, timings=timings)
        elapsed = time.monotonic() - t0
        # the budget is only meaningful if the CFG engine actually ran
        # inside the measured pass (a registry regression dropping the
        # lifecycle family would make this gate vacuously green)
        assert timings.get("lifecycle", 0.0) > 0.0, sorted(timings)
        assert elapsed < 30.0, f"full-tree lint took {elapsed:.1f}s"
