"""flowlint rule tests: each rule against known-good / known-bad fixture
snippets, plus the regression gate that the repo itself lints clean
(what `make lint` / CI enforce)."""

# flowlint: skip-file
# (the fixture strings below deliberately contain findings)

import os
import sys
import textwrap

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from tools.flowlint.runner import run_lint  # noqa: E402


def _lint(tmp_path, source: str, name: str = "fix.py", rules=None):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run_lint(str(tmp_path), [name], rules)


def _rules(findings):
    return [f.rule for f in findings]


class TestJitPurity:
    def test_direct_impurity_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            import time, jax

            @jax.jit
            def step(x):
                print("tracing")
                return x + time.time()
        """)
        msgs = [f.message for f in out]
        assert any("print" in m for m in msgs)
        assert any("time.time" in m for m in msgs)

    def test_transitive_reachability(self, tmp_path):
        out = _lint(tmp_path, """
            import jax

            def helper(y):
                import random
                return random.random() + y

            @jax.jit
            def step(y):
                return helper(y)
        """)
        assert any("random.random" in f.message for f in out)

    def test_partial_decorator_and_shard_map_forms(self, tmp_path):
        out = _lint(tmp_path, """
            import jax
            from functools import partial
            from jax.experimental.shard_map import shard_map

            @partial(jax.jit, static_argnames=("k",))
            def step(x, *, k):
                open("/tmp/x")
                return x

            def per_chip(x):
                import time
                return x + time.time()

            fn = jax.jit(shard_map(per_chip, mesh=None, in_specs=None,
                                   out_specs=None))
        """)
        msgs = " ".join(f.message for f in out)
        assert "open" in msgs and "time.time" in msgs

    def test_metric_mutation_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            import jax
            from flow_pipeline_tpu.obs import REGISTRY

            m = REGISTRY.counter("c", "help")

            @jax.jit
            def step(x):
                m.inc()
                return x
        """)
        assert any(".inc" in f.message for f in out)

    def test_global_write_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            import jax
            _CACHE = None

            @jax.jit
            def step(x):
                global _CACHE
                _CACHE = x
                return x
        """)
        assert any("module-global write" in f.message for f in out)

    def test_pure_jit_and_host_side_effects_clean(self, tmp_path):
        out = _lint(tmp_path, """
            import jax
            import jax.numpy as jnp
            from flow_pipeline_tpu.obs import REGISTRY

            m = REGISTRY.counter("c", "help")

            @jax.jit
            def step(x):
                return jnp.sum(x) * 2

            def host_loop(x):
                m.inc()          # fine: NOT reachable from a jit body
                print("host")
                return step(x)
        """)
        assert _rules(out) == []


class TestUint64Discipline:
    def test_unmarked_module_not_checked(self, tmp_path):
        out = _lint(tmp_path, """
            import numpy as np
            def f(x):
                return x.astype(np.int64) + np.array([1])
        """)
        assert _rules(out) == []

    def test_marked_module_flags_casts_and_dtypeless(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np
            import jax.numpy as jnp

            def f(x):
                a = x.astype(np.int64)
                b = jnp.asarray(x).astype(jnp.int32)
                c = np.array([1, 2])
                d = np.zeros(4)
                e = np.int64(7) + x
                ok = np.asarray(x)            # dtype-preserving: allowed
                ok2 = np.zeros(4, np.uint64)  # explicit dtype: allowed
                return a, b, c, d, e, ok, ok2
        """)
        assert _rules(out) == ["uint64-discipline"] * 5

    def test_suppression_with_reason(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(x):
                # flowlint: disable=uint64-discipline -- indices < 2^31, not counters
                return x.astype(np.int32)
        """)
        assert _rules(out) == []

    def test_suppression_without_reason_is_a_finding(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(x):
                return x.astype(np.int32)  # flowlint: disable=uint64-discipline
        """)
        assert "suppression" in _rules(out)

    def test_trailing_suppression_does_not_mask_next_line(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(x):
                a = x.astype(np.int32)  # flowlint: disable=uint64-discipline -- bounded
                b = x.astype(np.int64)
                return a, b
        """)
        assert _rules(out) == ["uint64-discipline"]  # only line b


class TestLockDiscipline:
    def test_guarded_write_enforced(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._lock = threading.Lock()
                    self._n = 0  # guarded-by: _lock

                def good(self):
                    with self._lock:
                        self._n += 1

                def bad(self):
                    self._n += 1
        """)
        assert _rules(out) == ["lock-discipline"]
        assert "outside" in out[0].message

    def test_guarded_write_in_match_case_enforced(self, tmp_path):
        # `match` case bodies are walked like `if` branches
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._lock = threading.Lock()
                    self._n = 0  # guarded-by: _lock

                def bad(self, mode):
                    match mode:
                        case "bump":
                            self._n += 1
        """)
        assert _rules(out) == ["lock-discipline"]
        assert "outside" in out[0].message

    def test_undeclared_attribute_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            class Box:
                def __init__(self):
                    self._m = 0

                def touch(self):
                    self._m = 5
        """)
        assert any("undeclared attribute" in f.message for f in out)

    def test_tuple_unpack_write_seen(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._cv = threading.Condition()
                    self._err = None  # guarded-by: _cv

                def take(self):
                    err, self._err = self._err, None
                    return err
        """)
        assert _rules(out) == ["lock-discipline"]

    def test_blocking_call_under_lock(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading, time

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._lock = threading.Lock()
                    self._n = 0  # guarded-by: _lock

                def slow(self):
                    with self._lock:
                        self._n += 1
                        time.sleep(1)
        """)
        assert any("blocking" in f.message for f in out)

    def test_cv_wait_on_held_lock_allowed(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._cv = threading.Condition()
                    self._n = 0  # guarded-by: _cv

                def drain(self):
                    with self._cv:
                        self._cv.wait_for(lambda: self._n == 0, 5)
        """)
        assert _rules(out) == []

    def test_module_global_guard(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            _LOCK = threading.Lock()
            _POOL = None  # guarded-by: _LOCK

            def good():
                global _POOL
                with _LOCK:
                    if _POOL is None:
                        _POOL = object()
                return _POOL

            def bad():
                global _POOL
                _POOL = None
        """)
        assert _rules(out) == ["lock-discipline"]
        assert "_POOL" in out[0].message


class TestLockRuleExprScan:
    def test_no_duplicate_findings_in_nested_statements(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading, time

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._lock = threading.Lock()
                    self._n = 0  # guarded-by: _lock

                def slow(self):
                    with self._lock:
                        if self._n > 0:
                            time.sleep(1)
        """)
        assert len([f for f in out if "blocking" in f.message]) == 1

    def test_nested_cv_wait_under_outer_lock_allowed(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._lock = threading.Lock()
                    # flowlint: unguarded -- the lock itself
                    self._cv = threading.Condition()
                    self._n = 0  # guarded-by: _cv

                def drain(self):
                    with self._lock:
                        with self._cv:
                            self._cv.wait_for(lambda: self._n == 0, 5)
        """)
        assert _rules(out) == []


class TestSuppressionHygiene:
    def test_unknown_rule_in_disable_reported(self, tmp_path):
        out = _lint(tmp_path, """
            def f():
                # flowlint: disable=lock-dicipline -- typo'd rule name
                return 1
        """)
        assert any("unknown rule" in f.message for f in out)

    def test_unused_suppression_reported_on_full_run(self, tmp_path):
        out = _lint(tmp_path, """
            def f():
                # flowlint: disable=jit-purity -- nothing here triggers it
                return 1
        """)
        assert any("no longer matches" in f.message for f in out)

    def test_unused_not_reported_when_rules_narrowed(self, tmp_path):
        out = _lint(tmp_path, """
            def f():
                # flowlint: disable=jit-purity -- nothing here triggers it
                return 1
        """, rules=("uint64-discipline",))
        assert _rules(out) == []


class TestNativeLoaderOverride:
    def test_missing_override_raises_every_call(self, monkeypatch):
        import importlib

        import flow_pipeline_tpu.native as native

        monkeypatch.setenv("FLOWDECODE_LIB", "/nonexistent/libx.so")
        importlib.reload(native)
        import pytest as _pytest
        with _pytest.raises(RuntimeError, match="FLOWDECODE_LIB"):
            native.available()
        # the strict override must NOT latch: a caller that swallowed the
        # first error must not silently get the no-native fallback
        with _pytest.raises(RuntimeError, match="FLOWDECODE_LIB"):
            native.available()
        monkeypatch.delenv("FLOWDECODE_LIB")
        importlib.reload(native)  # restore normal loader state


class TestFlagRegistry:
    def _write_registry(self, tmp_path, names):
        util = tmp_path / "utils"
        util.mkdir()
        (util / "flags.py").write_text(
            "KNOWN_FLAGS = frozenset({" +
            ", ".join(repr(n) for n in names) + "})\n")
        return "utils/flags.py"

    def test_undeclared_token_and_declaration(self, tmp_path):
        reg = self._write_registry(tmp_path, ["kafka.topic"])
        (tmp_path / "README.md").write_text("uses -kafka.topic\n")
        (tmp_path / "app.py").write_text(textwrap.dedent("""
            def build(fs):
                fs.string("kafka.topic", "flows", "topic")
                fs.string("kafka.brokerz", "x", "typo'd declaration")
                argv = ["-kafka.topic", "t", "-no.such.flag=1"]
                return argv
        """))
        out = run_lint(str(tmp_path), [reg, "app.py"])
        msgs = " ".join(f.message for f in out)
        assert "kafka.brokerz" in msgs
        assert "-no.such.flag=1" in msgs
        assert "kafka.topic" not in " ".join(
            m for m in msgs.splitlines() if "not mentioned" in m)

    def test_undocumented_flag_flagged(self, tmp_path):
        reg = self._write_registry(tmp_path, ["secret.knob"])
        (tmp_path / "README.md").write_text("no flags here\n")
        out = run_lint(str(tmp_path), [reg])
        assert any("secret.knob" in f.message and "not mentioned" in f.message
                   for f in out)


class TestDtypeFlow:
    """v2 uint64-discipline: the flow-sensitive dtype interpreter."""

    def test_uint64_pyint_promotion_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                c = np.zeros(4, np.uint64)
                total = c.sum()
                return total + 1
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "promote to float64" in out[0].message
        assert "np.uint64(" in out[0].message
        # the finding carries the inferred dtype chain as evidence
        assert "dtype chain" in out[0].message
        assert "np.zeros" in out[0].message or "total" in out[0].message

    def test_wrapped_constant_clean(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                c = np.zeros(4, np.uint64)
                shifted = (c >> np.uint64(16)) | (c << np.uint64(48))
                return c.sum() + np.uint64(1) + shifted[0]
        """, rules=("uint64-discipline",))
        assert _rules(out) == []

    def test_true_division_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                c = np.zeros(4, np.uint64)
                return c / np.uint64(2)
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "division" in out[0].message

    def test_float_mixing_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                c = np.zeros(4, np.uint64)
                scale = np.float32(0.5)
                return c * scale
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "promotion out of the unsigned envelope" in out[0].message

    def test_uint32_pyint_leaves_wraparound_envelope(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                h = np.full(8, 7, np.uint32)
                return h * 5
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "wraparound envelope" in out[0].message

    def test_ops_scope_checked_without_marker(self, tmp_path):
        # ops/ and hostsketch/ modules get promotion checks even
        # unmarked — but NOT the strict dtype-less-constructor checks
        out = _lint(tmp_path, """
            import numpy as np

            def f():
                lax = np.zeros(4)          # dtype-less: ok here
                c = np.zeros(4, np.uint64)
                return c + 1, lax
        """, name="flow_pipeline_tpu/ops/fix.py",
            rules=("uint64-discipline",))
        assert len(out) == 1
        assert "promote to float64" in out[0].message

    def test_jnp_weak_typing_exempt(self, tmp_path):
        # JAX keeps the array dtype for python-int operands (weak
        # typing); only numpy's scalar rules promote
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import jax.numpy as jnp

            def f(x):
                h = x.astype(jnp.uint32)
                return h ^ (h >> 16)
        """, rules=("uint64-discipline",))
        assert _rules(out) == []

    def test_param_shadowing_module_global_not_guessed(self, tmp_path):
        # a parameter shadows a module-level uint64 constant: callers
        # may pass anything, so the interpreter must not inherit the
        # global's dtype — under-approximate, never guess
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            MASK = np.uint64(0xFF)

            def f(MASK):
                return MASK + 1
        """, rules=("uint64-discipline",))
        assert _rules(out) == []

    def test_class_level_dtypeless_constructor_flagged(self, tmp_path):
        # class-body statements execute at definition time; a platform-
        # default-dtype table at class scope is still a finding
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            class C:
                TABLE = np.array([1, 2, 3])
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "without an explicit dtype" in out[0].message

    def test_yield_fstring_and_subscript_index_scanned(self, tmp_path):
        # expressions the statement driver reaches only through yield,
        # f-strings, or an assignment target's index are still scanned
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def gen():
                yield np.zeros(3)

            def fmt():
                return f"{np.zeros(4)}"

            def store(d, v):
                d[np.int64(v)] = 0
        """, rules=("uint64-discipline",))
        assert len(out) == 3
        msgs = " ".join(f.message for f in out)
        assert "without an explicit dtype" in msgs
        assert "signed scalar constructor" in msgs

    def test_walrus_assignment_tracked(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                c = np.zeros(4, np.uint64)
                if (total := c.sum() + 1) > 0:
                    return total
                return None
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "uint64 +" in out[0].message

    def test_match_case_bodies_interpreted(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(mode):
                c = np.zeros(4, np.uint64)
                match mode:
                    case "bump":
                        return c + 1
                    case _:
                        return c
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "uint64 +" in out[0].message

    def test_decorator_expressions_scanned(self, tmp_path):
        # decorators evaluate at definition time in the enclosing scope
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def deco(table):
                def wrap(fn):
                    return fn
                return wrap

            @deco(np.zeros(3))
            def f():
                return 0
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "without an explicit dtype" in out[0].message

    def test_propagation_through_branches_and_calls(self, tmp_path):
        # dtype survives if/else when both branches agree; np.where and
        # astype propagate; the flag fires far from the construction
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(cond, raw):
                if cond:
                    c = np.asarray(raw, dtype=np.uint64)
                else:
                    c = np.zeros(3, np.uint64)
                picked = np.where(cond, c, np.uint64(0))
                return picked - 1
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert out[0].line == 11  # the `return picked - 1` line
        assert "np.asarray" in out[0].message  # chain reaches back

    def test_comprehension_lambda_and_default_bodies_scanned(self, tmp_path):
        # the v1 ast.walk checks must survive the move to an
        # interpreter: constructors inside comprehensions, lambdas, and
        # default-arg expressions are still findings
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(vals, fill=np.zeros(2)):
                planes = [np.zeros(4) for _ in range(3)]
                sig = [np.int64(v) for v in vals]
                g = lambda v: np.array([v])
                return planes, sig, g, fill
        """, rules=("uint64-discipline",))
        assert _rules(out) == ["uint64-discipline"] * 4

    def test_suppression_still_works(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                c = np.zeros(4, np.uint64)
                # flowlint: disable=uint64-discipline -- bounded by caller, exact below 2^53
                return c.sum() + 1
        """, rules=("uint64-discipline",))
        assert _rules(out) == []


class TestLockOrder:
    def test_two_lock_cycle_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, rules=("lock-order",))
        assert len(out) == 1
        assert "lock-order cycle" in out[0].message
        assert "Box._a" in out[0].message and "Box._b" in out[0].message

    def test_multi_item_with_cycle_flagged(self, tmp_path):
        # `with a, b:` acquires left to right — the same deadlock as
        # nested withs, and the same finding
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a, self._b:
                        pass

                def two(self):
                    with self._b, self._a:
                        pass
        """, rules=("lock-order",))
        assert len(out) == 1
        assert "lock-order cycle" in out[0].message

    def test_consistent_order_clean(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """, rules=("lock-order",))
        assert _rules(out) == []

    def test_nested_def_not_attributed_to_encloser(self, tmp_path):
        # defining a callback is not running it: schedule() never
        # sleeps, so calling it under a lock is not blocking-while-
        # holding (same for lambda bodies)
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import time
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()

                def schedule(self):
                    def cb():
                        time.sleep(1)
                    slow = lambda: time.sleep(2)
                    return cb, slow

                def outer(self):
                    with self._lock:
                        return self.schedule()
        """, rules=("lock-order", "lock-discipline"))
        assert _rules(out) == []

    def test_same_named_classes_not_unified(self, tmp_path):
        # two unrelated classes that happen to share a name must not
        # have their locks merged into a phantom deadlock cycle
        m1 = textwrap.dedent("""
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def go(self):
                    with self._a:
                        with self._b:
                            pass
        """)
        m2 = m1.replace("with self._a:", "with self._X:").replace(
            "with self._b:", "with self._a:").replace(
            "with self._X:", "with self._b:")
        (tmp_path / "m1.py").write_text(m1)
        (tmp_path / "m2.py").write_text(m2)
        out = run_lint(str(tmp_path), ["m1.py", "m2.py"],
                       rules=("lock-order",))
        assert out == []

    def test_cycle_witness_reports_only_real_edges(self, tmp_path):
        # a<->b and b<->c form one SCC, but there is NO c -> a edge:
        # the reported witness path must not fabricate one (it would
        # send the maintainer to reorder an acquisition no code does)
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._c = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass

                def bc(self):
                    with self._b:
                        with self._c:
                            pass

                def cb(self):
                    with self._c:
                        with self._b:
                            pass
        """, rules=("lock-order",))
        assert len(out) == 1
        assert "fix.Box._a -> fix.Box._b -> fix.Box._a" in out[0].message
        assert "_c -> fix.Box._a" not in out[0].message

    def test_match_case_bodies_walked(self, tmp_path):
        # acquisitions and blocking calls inside `match` case bodies
        # must be as visible as inside `if` branches
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import time
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self, mode):
                    with self._a:
                        match mode:
                            case "x":
                                with self._b:
                                    self.slow()

                def slow(self):
                    time.sleep(1)

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, rules=("lock-order",))
        msgs = " ".join(f.message for f in out)
        assert "lock-order cycle" in msgs
        assert "slow()" in msgs and "time.sleep" in msgs

    def test_interprocedural_cycle_through_calls(self, tmp_path):
        # the cycle only exists composed with the call graph: each
        # method nests ONE with, the second lock comes from the callee
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        self.grab_b()

                def grab_b(self):
                    with self._b:
                        pass

                def two(self):
                    with self._b:
                        self.grab_a()

                def grab_a(self):
                    with self._a:
                        pass
        """, rules=("lock-order",))
        assert any("lock-order cycle" in f.message for f in out)

    def test_interprocedural_blocking_while_holding(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading, time

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()

                def helper(self):
                    time.sleep(1)

                def outer(self):
                    with self._lock:
                        self.helper()
        """, rules=("lock-order",))
        assert len(out) == 1
        assert "eventually blocks" in out[0].message
        assert "time.sleep" in out[0].message

    def test_cv_wait_exemption_kept(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._cv = threading.Condition()

                def drain(self):
                    with self._cv:
                        self._cv.wait_for(lambda: True, 5)

                def caller(self):
                    self.drain()
        """, rules=("lock-order",))
        assert _rules(out) == []

    def test_plain_lock_self_deadlock_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._m = threading.Lock()

                def a(self):
                    with self._m:
                        self.b()

                def b(self):
                    with self._m:
                        pass
        """, rules=("lock-order",))
        assert len(out) == 1
        assert "fix.Box._m -> fix.Box._m" in out[0].message

    def test_reentrant_lock_self_reentry_allowed(self, tmp_path):
        # bus.InProcessBus.produce -> create_topic under the same RLock
        # is the sanctioned pattern; Condition wraps an RLock too
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._m = threading.RLock()

                def a(self):
                    with self._m:
                        self.b()

                def b(self):
                    with self._m:
                        pass
        """, rules=("lock-order",))
        assert _rules(out) == []

    def test_cross_class_edge_via_constructed_attr(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading, time

            class Inner:
                def __init__(self):
                    self._il = threading.Lock()

                def poke(self):
                    with self._il:
                        time.sleep(0.1)

            class Outer:
                def __init__(self):
                    self._ol = threading.Lock()
                    self._inner = Inner()

                def a(self):
                    with self._ol:
                        self._inner.poke()
        """, rules=("lock-order",))
        # no cycle — but the blocking call inside Inner.poke is seen
        # from Outer.a through the constructor-typed attribute
        assert len(out) == 1
        assert "eventually blocks" in out[0].message


class TestLockDisciplineSubscript:
    def test_subscript_store_needs_annotation(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            class Box:
                def __init__(self):
                    self._states = [None]

                def reset(self, i):
                    self._states[i] = object()
        """, rules=("lock-discipline",))
        assert len(out) == 1
        assert "undeclared attribute" in out[0].message

    def test_annotated_subscript_store_passes(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            class Box:
                def __init__(self):
                    # flowlint: unguarded -- worker thread only
                    self._states = [None]

                def reset(self, i):
                    self._states[i] = object()
        """, rules=("lock-discipline",))
        assert _rules(out) == []

    def test_guarded_subscript_store_enforced(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    # flowlint: unguarded -- the lock itself
                    self._lock = threading.Lock()
                    self._commits = {}  # guarded-by: _lock

                def good(self, k, v):
                    with self._lock:
                        self._commits[k] = v

                def bad(self, k, v):
                    self._commits[k] = v
        """, rules=("lock-discipline",))
        assert _rules(out) == ["lock-discipline"]
        assert "outside" in out[0].message


_ABI_CC = """
#include <stdint.h>

extern "C" {

// sums n uint32s, scaled
long long fd_sum(const uint32_t* data, long long n, int scale) {
  long long out = 0;
  for (long long i = 0; i < n; ++i) { out += data[i] * scale; }
  return out;
}

long long fd_scan(const uint8_t* buf, long long n, float* out) {
  if (n > 0) { out[0] = 1.0f; }
  return n;
}

}  // extern "C"
"""

_ABI_BINDER_OK = """
import ctypes
import numpy as np


def _c_arr(a):
    return a.ctypes.data_as(ctypes.c_void_p)


def _bind(lib):
    lib.fd_sum.restype = ctypes.c_longlong
    lib.fd_sum.argtypes = [
        ctypes.c_void_p,
        ctypes.c_longlong,
        ctypes.c_int,
    ]
    lib.fd_scan.restype = ctypes.c_longlong
    lib.fd_scan.argtypes = [
        ctypes.c_char_p,
        ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_float),
    ]
    return lib


def call(lib, xs):
    xs = np.ascontiguousarray(xs, dtype=np.uint32)
    return lib.fd_sum(_c_arr(xs), len(xs), 1)
"""


class TestAbiContract:
    def _setup(self, tmp_path, cc=_ABI_CC, binder=_ABI_BINDER_OK):
        (tmp_path / "native").mkdir(exist_ok=True)
        (tmp_path / "native" / "fake.cc").write_text(cc)
        (tmp_path / "binder.py").write_text(textwrap.dedent(binder))
        return run_lint(str(tmp_path), ["binder.py"],
                        rules=("abi-contract",))

    def test_matching_binder_clean(self, tmp_path):
        assert self._setup(tmp_path) == []

    def test_arity_mismatch_flagged(self, tmp_path):
        out = self._setup(tmp_path, binder=_ABI_BINDER_OK.replace(
            "        ctypes.c_int,\n", ""))
        assert len(out) == 1
        assert "declares 2 parameter(s)" in out[0].message
        assert "fd_sum" in out[0].message

    def test_ctype_mapping_mismatch_flagged(self, tmp_path):
        out = self._setup(tmp_path, binder=_ABI_BINDER_OK.replace(
            "        ctypes.c_longlong,\n        ctypes.c_int,",
            "        ctypes.c_int,\n        ctypes.c_int,"))
        assert len(out) == 1
        assert "argtypes[1]" in out[0].message
        assert "long long" in out[0].message

    def test_unbound_export_flagged_and_allowlisted(self, tmp_path):
        binder_partial = _ABI_BINDER_OK.replace(
            "    lib.fd_scan.restype = ctypes.c_longlong\n"
            "    lib.fd_scan.argtypes = [\n"
            "        ctypes.c_char_p,\n"
            "        ctypes.c_longlong,\n"
            "        ctypes.POINTER(ctypes.c_float),\n"
            "    ]\n", "")
        out = self._setup(tmp_path, binder=binder_partial)
        assert len(out) == 1
        assert "fd_scan" in out[0].message and "no ctypes binding" \
            in out[0].message
        assert out[0].path.endswith("fake.cc")
        # the explicit allowlist silences it
        out = self._setup(tmp_path, binder=binder_partial +
                          "\n# flowlint: abi-unbound: fd_scan -- "
                          "bound lazily by the stress driver only\n")
        assert out == []

    def test_binding_nonexistent_symbol_flagged(self, tmp_path):
        out = self._setup(tmp_path, binder=_ABI_BINDER_OK.replace(
            "fd_scan", "fd_scam"))
        msgs = " ".join(f.message for f in out)
        assert "fd_scam" in msgs and "no extern" in msgs
        # and fd_scan is now unbound on the C side
        assert "fd_scan" in msgs

    def test_missing_restype_flagged(self, tmp_path):
        out = self._setup(tmp_path, binder=_ABI_BINDER_OK.replace(
            "    lib.fd_scan.restype = ctypes.c_longlong\n", ""))
        assert len(out) == 1
        assert "no restype" in out[0].message

    def test_ctypes_alias_treated_as_unknown(self, tmp_path):
        # a local alias (`_LL = ctypes.c_longlong`) is opaque to the
        # parser: skip the comparison, don't report the alias's
        # spelling as an ABI mismatch
        binder = _ABI_BINDER_OK.replace(
            "import ctypes\n",
            "import ctypes\n\n_LL = ctypes.c_longlong\n").replace(
            "    lib.fd_sum.restype = ctypes.c_longlong\n",
            "    lib.fd_sum.restype = _LL\n").replace(
            "        ctypes.c_longlong,\n        ctypes.c_int,\n",
            "        _LL,\n        ctypes.c_int,\n")
        assert "_LL = ctypes.c_longlong" in binder
        out = self._setup(tmp_path, binder=binder)
        assert out == []

    def test_argtypes_via_shared_name_not_misreported(self, tmp_path):
        # argtypes assigned a module-level name is unparseable for the
        # rule: treat it as unknown and skip the arity/type checks —
        # never claim the argtypes assignment is missing
        binder = _ABI_BINDER_OK.replace(
            "    lib.fd_scan.argtypes = [\n"
            "        ctypes.c_char_p,\n"
            "        ctypes.c_longlong,\n"
            "        ctypes.POINTER(ctypes.c_float),\n"
            "    ]\n",
            "    lib.fd_scan.argtypes = _SCAN_ARGS\n")
        assert binder != _ABI_BINDER_OK
        out = self._setup(tmp_path, binder=binder)
        assert out == []

    def test_callsite_dtype_mismatch_flagged(self, tmp_path):
        out = self._setup(tmp_path, binder=_ABI_BINDER_OK.replace(
            "dtype=np.uint32", "dtype=np.float32"))
        assert len(out) == 1
        assert "float32 buffer" in out[0].message
        assert "uint32_t*" in out[0].message

    def test_callsite_dtype_via_assert_and_empty(self, tmp_path):
        binder = _ABI_BINDER_OK + textwrap.dedent("""
            def scan(lib, buf):
                assert buf.dtype == np.uint8
                out = np.empty(4, np.float64)
                return lib.fd_scan(buf, len(buf), _c_arr(out))
        """)
        out = self._setup(tmp_path, binder=binder)
        assert len(out) == 1
        assert "float64 buffer" in out[0].message
        assert "'out'" in out[0].message

    def test_rule_skipped_without_binder_in_scope(self, tmp_path):
        (tmp_path / "native").mkdir()
        (tmp_path / "native" / "fake.cc").write_text(_ABI_CC)
        (tmp_path / "other.py").write_text("x = 1\n")
        out = run_lint(str(tmp_path), ["other.py"],
                       rules=("abi-contract",))
        assert out == []

    def test_repo_abi_covers_all_native_symbols(self):
        # the acceptance criterion: the rule parses and checks every
        # bound symbol of the real library (17 as of r21 — decode/count/
        # encode/hash_group + the threaded hash_group_mt twin + the 4
        # hs_* sketch kernels + the hs_spread_update register scatter-max
        # + the 2 hs_inv_* invertible kernels + the 3 ff_* fused-dataplane
        # kernels + the 2 ff_build_* lane builders). The fused kernels'
        # cross-file calls INTO hs_* are declarations (semicolon-
        # terminated), which the parser must not double-count as exports.
        from tools.flowlint import rules_abi

        exports = [f.name for f in rules_abi.parse_exports(REPO)]
        assert sorted(exports) == sorted(set(exports)), \
            "extern-C declarations double-counted as exports"
        assert set(exports) == {
            "flow_decode_stream", "flow_count_frames",
            "flow_encode_stream", "flow_hash_group",
            "flow_hash_group_mt",
            "hs_cms_update", "hs_cms_query", "hs_hh_prefilter",
            "hs_topk_merge", "hs_spread_update",
            "hs_inv_update", "hs_inv_decode",
            "ff_group_sum", "ff_group_sum_mt", "ff_fused_update",
            "ff_build_lanes", "ff_build_planes",
        }
        bound = rules_abi.parse_bound_symbols(os.path.join(
            REPO, "flow_pipeline_tpu", "native", "__init__.py"))
        assert bound == set(exports)


class TestJsonOutput:
    def test_json_findings_machine_readable(self, tmp_path, capsys):
        import json

        from tools.flowlint.runner import main

        (tmp_path / "fix.py").write_text(textwrap.dedent("""
            # flowlint: uint64-exact
            import numpy as np

            def f():
                return np.zeros(3)
        """))
        rc = main(["--root", str(tmp_path), "--json", "fix.py"])
        assert rc == 1
        data = json.loads(capsys.readouterr().out)
        assert data["count"] == 1
        (f,) = data["findings"]
        assert f["file"] == "fix.py" and f["rule"] == "uint64-discipline"
        assert isinstance(f["line"], int) and f["message"]

    def test_json_clean_run(self, tmp_path, capsys):
        import json

        from tools.flowlint.runner import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--root", str(tmp_path), "--json", "ok.py"])
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        assert data["count"] == 0 and data["findings"] == []


class TestDtypeSignedMix:
    """unsigned op signed — the headline promotion, both dtypes inferred."""

    def test_uint64_int64_promotion_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                a = np.zeros(3, dtype=np.uint64)
                b = np.ones(3, dtype=np.int64)
                return a + b
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "promotes to float64" in out[0].message
        assert "dtype chain" in out[0].message

    def test_smaller_unsigned_signed_mix_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f():
                a = np.zeros(3, dtype=np.uint32)
                b = np.ones(3, dtype=np.int32)
                return a ^ b
        """, rules=("uint64-discipline",))
        assert len(out) == 1
        assert "wraparound envelope" in out[0].message

    def test_starred_unpack_clears_tracked_dtype(self, tmp_path):
        # `a, *rest = vals` rebinds rest to a plain list — a stale
        # tracked uint64 here was a false positive on correct code
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(vals):
                rest = np.zeros(3, dtype=np.uint64)
                a, *rest = vals
                return rest + [1]
        """, rules=("uint64-discipline",))
        assert _rules(out) == []

    def test_class_bases_and_keywords_scanned(self, tmp_path):
        # base/metaclass expressions run at class-definition time just
        # like decorators; v1 (ast.walk) saw them, so must v2
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            class C(make_base(np.zeros(3)), metaclass=pick(np.array([1]))):
                pass
        """, rules=("uint64-discipline",))
        assert len(out) == 2
        assert all("without an explicit dtype" in f.message for f in out)


class TestAsyncCoverage:
    """async def / async with / async for bodies get the same analysis
    as their sync twins in every rule."""

    def test_dtype_interpreter_enters_async_with(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            async def g(lock, it):
                async with lock:
                    bad = np.zeros(3)
                async for _ in it:
                    d = np.zeros(3, dtype=np.uint64)
                    return d / 2
        """, rules=("uint64-discipline",))
        assert len(out) == 2
        assert any("without an explicit dtype" in f.message for f in out)
        assert any("true division" in f.message for f in out)

    def test_lock_discipline_covers_async_methods(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading
            import time

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0  # guarded-by: _lock

                async def ok(self):
                    async with self._lock:
                        self.n = 1

                async def bad(self):
                    self.n = 2

                async def blocky(self):
                    async with self._lock:
                        time.sleep(1)
        """, rules=("lock-discipline",))
        msgs = sorted(f.message for f in out)
        assert len(out) == 2
        assert any("outside `with self._lock:`" in m for m in msgs)
        assert any("blocking call" in m for m in msgs)

    def test_lock_order_cycle_through_async_with(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: lock-checked
            import threading

            class Box:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                async def ab(self):
                    async with self._a:
                        async with self._b:
                            pass

                async def ba(self):
                    async with self._b:
                        async with self._a:
                            pass
        """, rules=("lock-order",))
        assert len(out) == 1
        assert "cycle" in out[0].message


class TestCrossFileLockCycle:
    def _write_pkg(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "a_mod.py").write_text(textwrap.dedent("""
            # flowlint: lock-checked
            import threading
            from pkg.z_mod import Worker

            class A:
                def __init__(self):
                    self.l1 = threading.Lock()
                    self.w = Worker()

                def go(self):
                    with self.l1:
                        self.w.go()

                def reenter(self):
                    with self.l1:
                        pass
        """))
        (pkg / "z_mod.py").write_text(textwrap.dedent("""
            # flowlint: lock-checked
            import threading
            from pkg.a_mod import A

            class Worker:
                def __init__(self):
                    self.l2 = threading.Lock()
                    self.back = A()

                def go(self):
                    with self.l2:
                        self.back.reenter()
        """))

    def test_cycle_found_in_both_file_orders(self, tmp_path):
        # constructor-typed attrs must resolve against classes indexed
        # LATER in the file list too — a one-pass index dropped
        # whichever direction of the cycle was scanned first
        self._write_pkg(tmp_path)
        for order in (["pkg/a_mod.py", "pkg/z_mod.py"],
                      ["pkg/z_mod.py", "pkg/a_mod.py"]):
            out = run_lint(str(tmp_path), order, ("lock-order",))
            assert any("pkg.a_mod.A.l1 -> pkg.z_mod.Worker.l2" in f.message
                       for f in out), order


class TestDtypePositionalCast:
    def test_asarray_positional_dtype_retypes(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def f(x):
                y = np.asarray(x, np.uint64)
                return y + 1
        """, rules=("uint64-discipline",))
        # the cast target (uint64), not the input's dtype, flows on
        assert len(out) == 1
        assert "uint64 + python int" in out[0].message

    def test_sort_positional_axis_not_a_dtype(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: uint64-exact
            import numpy as np

            def g():
                a = np.zeros(3, dtype=np.uint32)
                s = np.sort(a, 0)
                return s + np.uint32(1)
        """, rules=("uint64-discipline",))
        assert _rules(out) == []


class TestJsonRuleNarrowing:
    def test_json_rules_reflect_selection(self, tmp_path, capsys):
        import json

        from tools.flowlint.runner import main

        (tmp_path / "ok.py").write_text("x = 1\n")
        rc = main(["--root", str(tmp_path), "--json",
                   "--rule", "lock-order", "ok.py"])
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        # a narrowed run must not claim all six rules ran
        assert data["rules"] == ["lock-order"]


class TestNetTimeout:
    """r17 satellite: every urlopen/socket/requests call in net-checked
    modules must carry an explicit timeout (the r13 mesh trace fan-out
    bug was exactly this class)."""

    def test_unmarked_module_not_checked(self, tmp_path):
        out = _lint(tmp_path, """
            import urllib.request
            def f(url):
                return urllib.request.urlopen(url).read()
            """, rules=("net-timeout",))
        assert out == []

    def test_urlopen_without_timeout_flagged(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: net-checked
            import urllib.request
            def f(url):
                return urllib.request.urlopen(url).read()
            """, rules=("net-timeout",))
        assert _rules(out) == ["net-timeout"]
        assert "urlopen" in out[0].message

    def test_aliased_urlopen_still_matched(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: net-checked
            import urllib.request as _rq
            def f(url):
                return _rq.urlopen(url).read()
            """, rules=("net-timeout",))
        assert _rules(out) == ["net-timeout"]

    def test_timeout_kw_and_positional_accepted(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: net-checked
            import socket
            import urllib.request
            def f(url, addr):
                a = urllib.request.urlopen(url, timeout=5).read()
                b = urllib.request.urlopen(url, None, 5).read()
                c = socket.create_connection(addr, 2.0)
                d = socket.create_connection(addr, timeout=2.0)
                return a, b, c, d
            """, rules=("net-timeout",))
        assert out == []

    def test_http_connection_and_requests_checked(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: net-checked
            import http.client
            import requests
            import socket
            def f(host, addr, url):
                c1 = http.client.HTTPConnection(host, 80)
                c2 = http.client.HTTPConnection(host, 80, timeout=3)
                r1 = requests.get(url)
                r2 = requests.get(url, timeout=3)
                s1 = socket.create_connection(addr)
                return c1, c2, r1, r2, s1
            """, rules=("net-timeout",))
        assert _rules(out) == ["net-timeout"] * 3
        lines = sorted(f.line for f in out)
        assert lines == [7, 9, 11]

    def test_suppression_with_reason_accepted(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: net-checked
            import urllib.request
            def f(url):
                # deliberate: blocks until the endless stream closes
                return urllib.request.urlopen(url).read()  # flowlint: disable=net-timeout -- endless tail follow, bounded by caller's thread lifetime
            """, rules=("net-timeout",))
        assert out == []

    def test_repo_net_modules_are_marked(self):
        """The modules that actually open cross-process sockets must
        stay opted in — deleting a marker would silently de-fang the
        rule exactly where it matters."""
        from tools.flowlint.core import load_files

        rels = ["flow_pipeline_tpu/mesh/server.py",
                "flow_pipeline_tpu/serve/loadgen.py",
                "flow_pipeline_tpu/sink/clickhouse.py",
                "flow_pipeline_tpu/cli.py"]
        for sf in load_files(REPO, rels):
            assert "net-checked" in sf.markers, sf.rel


_FAM_HOOKS = """
def payload(state): return {}
def merge(payloads, config=None): return {}
def top_rows(merged, config, k, slot): return {}
def capture(m): return (None, 1, None)
def capture_merged(spec, slot, payloads): return None
def save(model): return {}
def restore(model, ms, name): return None
"""

_FAM_REGISTRY = """
register(SketchFamily(
    kind="hh",
    snapshot_kind="windowed_hh",
    checkpoint_kind="windowed_hh",
    payload_kinds=("hh",),
    merge_monoid="u64-sum",
    ranked=True,
    state_attr="state",
    payload="hooks:payload",
    merge="hooks:merge",
    top_rows="hooks:top_rows",
    serve_capture="hooks:capture",
    serve_capture_merged="hooks:capture_merged",
    checkpoint_save="hooks:save",
    checkpoint_restore="hooks:restore",
    flag_namespace="hh.",
    endpoint="/query/topk",
    parity_target="hh-parity",
    doc_token="`hh`",
    obs_token="hh_recall",
))
"""


class TestFamilyCitizenship:
    """family-citizenship fixture battery: the registry parser, the
    per-surface completeness checks, the reverse kind-literal check,
    and the suppression/skip-file behavior every other rule has."""

    def _run(self, tmp_path, registry=_FAM_REGISTRY, extra=()):
        files = {"families/registry.py": registry,
                 "hooks.py": _FAM_HOOKS}
        files.update(extra)
        for rel, src in files.items():
            path = tmp_path / rel
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(textwrap.dedent(src))
        return run_lint(str(tmp_path), sorted(files),
                        rules=("family-citizenship",))

    def test_complete_registry_clean(self, tmp_path):
        assert self._run(tmp_path) == []

    def test_rule_skipped_without_registry_in_scope(self, tmp_path):
        (tmp_path / "app.py").write_text('kind = x["kind"] == "mystery"\n')
        out = run_lint(str(tmp_path), ["app.py"],
                       rules=("family-citizenship",))
        assert out == []

    def test_missing_surface_named_exactly_once(self, tmp_path):
        out = self._run(tmp_path, registry=_FAM_REGISTRY.replace(
            '    merge="hooks:merge",\n', ""))
        assert len(out) == 1
        assert "family `hh` is missing surface `merge`" in out[0].message

    def test_ranked_surfaces_only_owed_when_ranked(self, tmp_path):
        dropped = _FAM_REGISTRY.replace(
            '    serve_capture="hooks:capture",\n', "")
        out = self._run(tmp_path, registry=dropped)
        assert len(out) == 1
        assert "missing surface `serve_capture`" in out[0].message
        # an unranked family (exact rows, wagg-style) legitimately
        # leaves the top-K capture surfaces unset
        unranked = dropped.replace("    ranked=True,", "    ranked=False,") \
            .replace('    serve_capture_merged="hooks:capture_merged",\n',
                     "").replace('    snapshot_kind="windowed_hh",\n', "") \
            .replace('    state_attr="state",\n', "")
        assert self._run(tmp_path, registry=unranked) == []

    def test_unresolvable_hook_flagged(self, tmp_path):
        out = self._run(tmp_path, registry=_FAM_REGISTRY.replace(
            "hooks:merge", "hooks:no_such_fn"))
        assert len(out) == 1
        assert "does not resolve" in out[0].message
        assert "no_such_fn" in out[0].message

    def test_hook_module_outside_scope_flagged(self, tmp_path):
        out = self._run(tmp_path, registry=_FAM_REGISTRY.replace(
            "hooks:merge", "phantom_mod:merge"))
        assert len(out) == 1
        assert "phantom_mod" in out[0].message
        assert "not in the lint scope" in out[0].message

    def test_computed_field_is_a_finding(self, tmp_path):
        out = self._run(tmp_path, registry=_FAM_REGISTRY.replace(
            'merge="hooks:merge",', 'merge="hooks:" + MERGE_FN,'))
        assert any("must be a literal" in f.message for f in out)

    def test_unregistered_kind_literal_flagged(self, tmp_path):
        out = self._run(tmp_path, extra={"mesh/codec.py": """
            def capture(payload):
                if payload["kind"] == "mystery":
                    return None
                if payload["kind"] == "hh":
                    return payload
        """})
        assert len(out) == 1
        assert 'kind tag "mystery"' in out[0].message
        assert out[0].path == "mesh/codec.py"

    def test_snapshot_and_get_kind_forms_checked(self, tmp_path):
        out = self._run(tmp_path, extra={"serve/publisher.py": """
            def pick(m, payload):
                a = m.snapshot_kind == "windowed_hh"       # registered
                b = payload.get("kind") in ("hh", "rogue")
                return a, b
        """})
        assert len(out) == 1
        assert 'kind tag "rogue"' in out[0].message

    def test_bare_kind_local_not_a_signal(self, tmp_path):
        # journal records / delta ships reuse a local named `kind`;
        # those tagged unions are not family dispatch
        out = self._run(tmp_path, extra={"mesh/coordinator.py": """
            def replay(records):
                for kind, blob in records:
                    if kind == "chk":
                        return blob
        """})
        assert out == []

    def test_non_family_kind_allowed_then_stale_flagged(self, tmp_path):
        allow = "NON_FAMILY_KINDS = (\"ddos\",)\n" + _FAM_REGISTRY
        out = self._run(tmp_path, registry=allow, extra={
            "engine/worker.py": """
                def restore(ms):
                    if ms["kind"] == "ddos":
                        return None
            """})
        assert out == []
        # the same entry with no dispatch surface mentioning it is
        # itself a finding (stale allowlist discipline)
        out = self._run(tmp_path, registry=allow, extra={
            "engine/worker.py": """
                def restore(ms):
                    return ms
            """})
        assert len(out) == 1
        assert '"ddos" appears at no dispatch surface' in out[0].message

    def test_empty_registry_flagged(self, tmp_path):
        out = self._run(tmp_path, registry="FAMILIES = {}\n")
        assert len(out) == 1
        assert "registers no SketchFamily" in out[0].message

    def test_suppression_with_reason_accepted(self, tmp_path):
        out = self._run(tmp_path, registry=_FAM_REGISTRY.replace(
            "register(SketchFamily(",
            "register(SketchFamily(  # flowlint: disable=family-citizenship -- half-registered on purpose: fixture").replace(
            '    merge="hooks:merge",\n', ""))
        assert out == []

    def test_skip_file_opts_registry_out(self, tmp_path):
        out = self._run(
            tmp_path,
            registry="# flowlint: skip-file\n" + _FAM_REGISTRY.replace(
                '    merge="hooks:merge",\n', ""))
        assert out == []

    def test_repo_registry_parses_with_four_families(self):
        # the real registry must stay statically readable: the same
        # parser the lint uses sees all four families and both
        # NON_FAMILY_KINDS entries
        from tools.flowlint import rules_family
        from tools.flowlint.core import load_files

        (reg,) = load_files(
            REPO, ["flow_pipeline_tpu/families/registry.py"])
        fams, non_family, _line, findings = \
            rules_family._parse_registry(reg)
        assert findings == []
        assert [kw["kind"] for kw, _ in fams] == \
            ["hh", "wagg", "dense", "spread"]
        assert non_family == ["ddos", "flowguard"]


class TestAnnotate:
    def test_json_round_trips_to_error_lines(self, tmp_path, capsys):
        import json

        from tools.flowlint import annotate
        from tools.flowlint.runner import main

        (tmp_path / "fix.py").write_text(textwrap.dedent("""
            # flowlint: uint64-exact
            import numpy as np

            def f():
                return np.zeros(3)
        """))
        rc = main(["--root", str(tmp_path), "--json", "fix.py"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        json_path = tmp_path / "findings.json"
        json_path.write_text(json.dumps(doc))
        assert annotate.main([str(json_path)]) == 0
        lines = capsys.readouterr().out.splitlines()
        (f,) = doc["findings"]
        assert lines == [
            f"::error file=fix.py,line={f['line']},"
            f"title=flowlint uint64-discipline::{f['message']}",
            "flowlint: 1 finding(s)",
        ]

    def test_clean_document_emits_count_only(self, capsys):
        from tools.flowlint import annotate

        assert annotate.annotations({"findings": [], "count": 0}) == \
            ["flowlint: 0 finding(s)"]


# common fixture prologue — indented to match the fixture literals so
# textwrap.dedent in _lint sees one uniform block
_DUR = """
            # flowlint: durable-checked
            from flow_pipeline_tpu.utils import fsutil
"""


class TestDurabilityProtocol:
    """durability-protocol fixture battery: the per-function protocol
    model (open/write/fsync/replace/dir-fsync ordering), the raw-op and
    bare-open fences, the group-commit seam, and the verified
    `# durable:` annotation grammar."""

    def test_unmarked_module_not_checked(self, tmp_path):
        out = _lint(tmp_path, """
            def f(path):
                with open(path, "w") as fh:
                    fh.write("x")
        """, rules=("durability-protocol",))
        assert out == []

    def test_bare_write_open_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def f(path):
                with open(path, "w") as fh:
                    fh.write("x")
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "bare open" in out[0].message
        assert "open_durable" in out[0].message

    def test_nonliteral_mode_flagged_read_modes_ignored(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def f(path, m):
                a = open(path)            # default read: fine
                b = open(path, "r")
                c = open(path, "rb")
                d = open(path, m)         # unclassifiable
                return a, b, c, d
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "non-literal mode" in out[0].message

    def test_raw_os_ops_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            import os, shutil

            def f(a, b):
                os.replace(a, b)
                shutil.rmtree(a)
        """, rules=("durability-protocol",))
        msgs = " ".join(f.message for f in out)
        assert len(out) == 2
        assert "raw os.replace()" in msgs
        assert "raw shutil.rmtree()" in msgs
        assert "utils/fsutil" in msgs

    def test_raw_ops_exempt_in_core_fsutil(self, tmp_path):
        out = _lint(tmp_path, """
            # flowlint: durable-checked
            import os

            def fsync_file(f):
                f.flush()
                os.fsync(f.fileno())
        """, name="flow_pipeline_tpu/utils/fsutil.py",
            rules=("durability-protocol",))
        assert out == []

    def test_full_publish_protocol_clean(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def publish(path, data):
                tmp = path + ".tmp"
                with fsutil.open_durable(tmp, "wb") as f:
                    f.write(data)
                    fsutil.fsync_file(f)
                fsutil.replace(tmp, path)
                fsutil.fsync_dir(".")
        """, rules=("durability-protocol",))
        assert out == []

    def test_write_bytes_durable_is_the_whole_sentence(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def spill(path, data):
                fsutil.write_bytes_durable(path, data)
        """, rules=("durability-protocol",))
        assert out == []

    def test_unsynced_handle_write_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def f(path):
                fh = fsutil.open_durable(path, "ab")
                fh.write(b"rec")
                fh.close()
                fsutil.fsync_dir(".")
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "no later fsutil.fsync_file(fh)" in out[0].message

    @pytest.mark.parametrize("synced", [True, False])
    def test_a_handle_yielded_is_written_through(self, tmp_path, synced):
        """`yield f` hands f to a block that writes it: the handle owes
        the fsync that a literal f.write would
        (fsutil.staged_durable, which the checkpoint's streamed
        arrays.npz goes through)."""
        out = _lint(tmp_path, _DUR + f"""
            import contextlib

            @contextlib.contextmanager
            def staged(path):
                tmp = path + ".tmp"
                with fsutil.open_durable(tmp, "wb") as f:
                    yield f
                    {"fsutil.fsync_file(f)" if synced else "pass"}
                fsutil.replace(tmp, path)
                fsutil.fsync_dir(".")
        """, rules=("durability-protocol",))
        msgs = " ".join(f.message for f in out)
        if synced:
            assert out == []
        else:
            assert "no later fsutil.fsync_file(f)" in msgs
            assert "never fsynced" in msgs

    def test_staged_durable_is_the_whole_sentence(self, tmp_path):
        """A block under fsutil.staged_durable owes nothing more: the
        helper fsyncs, replaces and fsyncs the directory when it ends."""
        out = _lint(tmp_path, _DUR + """
            def f(path, fill):
                with fsutil.staged_durable(path) as f:
                    fill(f)
        """, rules=("durability-protocol",))
        assert out == []

    def test_replace_of_unsynced_temp_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def f(path):
                tmp = path + ".tmp"
                with fsutil.open_durable(tmp, "wb") as f:
                    f.write(b"payload")
                fsutil.replace(tmp, path)
                fsutil.fsync_file(f)   # too late: after the publish
                fsutil.fsync_dir(".")
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "never fsynced" in out[0].message
        assert "torn" in out[0].message

    def test_unpublished_staging_file_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def f(path):
                tmp = path + ".tmp"
                with fsutil.open_durable(tmp, "wb") as f:
                    f.write(b"x")
                    fsutil.fsync_file(f)
                fsutil.fsync_dir(".")
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "never" in out[0].message and "published" in out[0].message

    def test_missing_dir_fsync_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def f(a, b):
                fsutil.replace(a, b)
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "no later fsutil.fsync_dir" in out[0].message

    def test_unacked_seam_append_flagged(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            class Coord:
                def ok(self, rec):
                    self._j.append(rec)
                    self._j.sync()

                def bad(self, rec):
                    self._j.append(rec)

                def flush(self):
                    self._j.sync()
        """, rules=("durability-protocol",))
        assert len(out) == 1
        assert "self._j.append" in out[0].message
        assert "not durable when the caller acks" in out[0].message

    def test_plain_list_append_is_not_a_seam(self, tmp_path):
        # .append on an attr the module never .sync()s is a list, not a
        # buffered journal — and list-method names like .remove must
        # never be read as fsutil name ops
        out = _lint(tmp_path, _DUR + """
            class Box:
                def add(self, v):
                    self._items.append(v)

                def drop(self, v):
                    self._items.remove(v)
        """, rules=("durability-protocol",))
        assert out == []

    def test_group_commit_annotation_excuses_deferred_sync(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            class Coord:
                def deferred(self, rec):
                    # durable: group-commit=flush -- every public caller flushes before its ack
                    self._j.append(rec)

                def flush(self):
                    self._j.sync()
        """, rules=("durability-protocol",))
        assert out == []

    def test_annotation_without_reason_is_a_finding(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            class Coord:
                def deferred(self, rec):
                    # durable: group-commit=flush
                    self._j.append(rec)

                def flush(self):
                    self._j.sync()
        """, rules=("durability-protocol",))
        msgs = " ".join(f.message for f in out)
        assert "without a justification" in msgs
        # and the unexcused append is still reported
        assert "not durable when the caller acks" in msgs

    def test_annotation_naming_barrierless_method_is_a_finding(
            self, tmp_path):
        # the static half of the mutation gate: delete the fsync out of
        # the promised method and the annotation itself turns red
        out = _lint(tmp_path, _DUR + """
            def rotate(old, new):
                # durable: dir-fsync=commit -- commit fsyncs the dir before any ack
                fsutil.rename(old, new)

            def commit():
                pass
        """, rules=("durability-protocol",))
        msgs = " ".join(f.message for f in out)
        assert "does not contain the promised barrier" in msgs

    def test_dir_fsync_annotation_excuses_deferred_barrier(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            def rotate(old, new):
                # durable: dir-fsync=commit -- commit fsyncs the dir before any ack
                fsutil.rename(old, new)

            def commit():
                fsutil.fsync_dir(".")
        """, rules=("durability-protocol",))
        assert out == []

    def test_suppression_with_reason_accepted(self, tmp_path):
        out = _lint(tmp_path, _DUR + """
            import os

            def f(a, b):
                # flowlint: disable=durability-protocol -- migration shim, deleted with r22
                os.replace(a, b)
        """, rules=("durability-protocol",))
        assert out == []

    def test_repo_durable_modules_are_marked(self):
        """Every module that owns crash-critical state must stay opted
        in — deleting a marker would silently de-fang the rule exactly
        where it matters (same contract as the net-checked list)."""
        from tools.flowlint.core import load_files

        rels = ["flow_pipeline_tpu/mesh/journal.py",
                "flow_pipeline_tpu/mesh/coordinator.py",
                "flow_pipeline_tpu/sink/resilient.py",
                "flow_pipeline_tpu/history/archive.py",
                "flow_pipeline_tpu/engine/checkpoint.py",
                "flow_pipeline_tpu/utils/fsutil.py"]
        for sf in load_files(REPO, rels):
            assert "durable-checked" in sf.markers, sf.rel


class TestDurabilityMutationGate:
    """The static half of the two-prong durability mutation gate:
    deleting any single load-bearing fsync / dir-fsync / replace from a
    durable surface must produce a durability-protocol finding when the
    mutated module is linted standalone. (The dynamic half lives in
    tests/test_crashpoints.py::TestBarrierMutations, where the same
    deletions — via fsutil.suppressed — surface as crash-state
    invariant violations.)"""

    # (repo-relative module, line regex, 0-based occurrence). Barrier
    # lines NOT listed are excluded deliberately:
    # - journal.py compact's fsync of the OLD handle (occurrence 2 of
    #   fsync_file(self._f)) protects only never-acked buffered appends
    #   — not load-bearing for acked data;
    # - archive.py's rotation-time fsync of the outgoing segment
    #   (occurrence 0 of fsync_file(self._fh)) is an interprocedural
    #   barrier the lexical rule cannot see; the crash-point checker
    #   covers it (the archive scenario commits across a rotation);
    # - coordinator.py syncs other than fence/submit are per-caller
    #   copies of the annotated group-commit seam (deleting one leaves
    #   other callers' barriers intact — redundancy, not protocol).
    MUTATIONS = [
        ("flow_pipeline_tpu/mesh/journal.py",
         r"fsutil\.fsync_file\(self\._f\)", 0),
        ("flow_pipeline_tpu/mesh/journal.py",
         r"fsutil\.fsync_file\(self\._f\)", 1),
        ("flow_pipeline_tpu/mesh/journal.py",
         r"fsutil\.fsync_file\(f\)", 0),
        ("flow_pipeline_tpu/mesh/journal.py",
         r"fsutil\.fsync_dir\(dir_\)", 0),
        ("flow_pipeline_tpu/mesh/journal.py",
         r"fsutil\.fsync_dir\(self\.dir\)", 0),
        ("flow_pipeline_tpu/mesh/journal.py",
         r"fsutil\.replace\(tmp, self\.path\)", 0),
        ("flow_pipeline_tpu/history/archive.py",
         r"fsutil\.fsync_file\(self\._fh\)", 1),
        ("flow_pipeline_tpu/history/archive.py",
         r"fsutil\.fsync_dir\(self\.dir\)", 0),
        ("flow_pipeline_tpu/history/archive.py",
         r"fsutil\.fsync_dir\(self\.dir\)", 1),
        ("flow_pipeline_tpu/engine/checkpoint.py",
         r"fsutil\.fsync_dir\(parent\)", 0),
        ("flow_pipeline_tpu/mesh/coordinator.py",
         r"self\._journal\.sync\(\)", 3),   # fence()'s ack barrier
        ("flow_pipeline_tpu/mesh/coordinator.py",
         r"self\._journal\.sync\(\)", 5),   # submit()'s ack barrier
        # the dead-letter spill is one write_bytes_durable call and the
        # checkpoint's streamed arrays.npz one staged_durable block;
        # their three barriers live in fsutil's own protocol sentence
        ("flow_pipeline_tpu/utils/fsutil.py",
         r"^        fsync_file\(f\)", 0),
        ("flow_pipeline_tpu/utils/fsutil.py",
         r"^    replace\(tmp, path\)", 0),
        ("flow_pipeline_tpu/utils/fsutil.py",
         r"^    fsync_dir\(os\.path", 0),
    ]

    @staticmethod
    def _mutate(src: str, pattern: str, occurrence: int) -> str:
        import re
        lines = src.splitlines(keepends=True)
        hits = [i for i, ln in enumerate(lines) if re.search(pattern, ln)]
        assert len(hits) > occurrence, \
            f"{pattern!r}: {len(hits)} hit(s), wanted > {occurrence} — " \
            f"the mutation list is stale against the source"
        i = hits[occurrence]
        indent = lines[i][:len(lines[i]) - len(lines[i].lstrip())]
        lines[i] = indent + "pass  # mutated\n"
        return "".join(lines)

    def test_unmutated_modules_lint_clean_standalone(self, tmp_path):
        for rel in sorted({rel for rel, _p, _o in self.MUTATIONS}):
            with open(os.path.join(REPO, rel)) as fh:
                src = fh.read()
            dst = tmp_path / "base" / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            dst.write_text(src)
            out = run_lint(str(tmp_path / "base"), [rel],
                           rules=("durability-protocol",))
            assert out == [], (rel, [f.render() for f in out])

    def test_every_dropped_barrier_is_a_finding(self, tmp_path):
        for n, (rel, pattern, occ) in enumerate(self.MUTATIONS):
            with open(os.path.join(REPO, rel)) as fh:
                src = fh.read()
            root = tmp_path / str(n)
            dst = root / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            dst.write_text(self._mutate(src, pattern, occ))
            out = run_lint(str(root), [rel],
                           rules=("durability-protocol",))
            dur = [f for f in out if f.rule == "durability-protocol"]
            assert dur, (
                f"deleting {pattern!r} occurrence {occ} from {rel} "
                f"produced no durability-protocol finding — the static "
                f"mutation gate lost its teeth")


class TestAnnotateRobustness:
    def test_output_byte_identical_across_runs(self, tmp_path, capsys):
        import json

        from tools.flowlint import annotate
        from tools.flowlint.runner import main

        (tmp_path / "fix.py").write_text(textwrap.dedent("""
            # flowlint: uint64-exact
            import numpy as np

            def f():
                a = np.zeros(3)
                b = np.int64(1)
                return a, b
        """))
        rc = main(["--root", str(tmp_path), "--json", "fix.py"])
        assert rc == 1
        json_path = tmp_path / "findings.json"
        json_path.write_text(capsys.readouterr().out)
        assert annotate.main([str(json_path)]) == 0
        first = capsys.readouterr().out
        assert annotate.main([str(json_path)]) == 0
        second = capsys.readouterr().out
        assert first == second
        assert first.encode() == second.encode()

    def test_missing_keys_degrade_gracefully(self):
        # a hand-built or version-skewed document must never crash the
        # presenter — CI would lose the real findings behind a KeyError
        from tools.flowlint import annotate

        lines = annotate.annotations({"findings": [{}]})
        assert lines[0].startswith("::error file=<unknown>,line=1,")
        assert lines[-1] == "flowlint: 1 finding(s)"

    def test_count_falls_back_to_findings_length(self):
        from tools.flowlint import annotate

        lines = annotate.annotations(
            {"findings": [{"file": "a.py", "line": 3, "rule": "r",
                           "message": "m"}]})
        assert lines[-1] == "flowlint: 1 finding(s)"


class TestLintWallClock:
    def test_full_repo_run_within_budget(self):
        """make lint is a pre-commit gate: a rule that regresses the
        full-scope run past interactive latency is a bug even when its
        findings are right (observed ~3s on CI-class hardware; the
        ceiling leaves 20x headroom before failing)."""
        import time

        t0 = time.monotonic()
        run_lint(REPO)
        assert time.monotonic() - t0 < 60.0


class TestRepoRegression:
    def test_repo_lints_clean(self):
        findings = run_lint(REPO)
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_repo_has_jit_roots_covered(self):
        # the purity rule must actually be traversing this codebase: the
        # fused engine step and the hh update are jit roots, so a planted
        # impurity in models/ must be reachable (guards against the rule
        # silently finding zero roots after a refactor)
        import ast

        from tools.flowlint import rules_purity
        from tools.flowlint.core import discover, load_files

        files = load_files(REPO, discover(REPO, ("flow_pipeline_tpu",)))
        n_roots = 0
        for sf in files:
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.FunctionDef) \
                        and rules_purity._decorated_jit(node):
                    n_roots += 1
                elif isinstance(node, ast.Call) \
                        and rules_purity._wrapper_kind(node):
                    n_roots += 1
        assert n_roots >= 10, n_roots
