"""pslint framework tests (script/pslint/, doc/STATIC_ANALYSIS.md).

Each pass is proven LIVE with a bad fixture it must flag and a good
fixture it must not; the engine's suppression contract (reason
mandatory) is exercised both ways; and the tier-1 acceptance test runs
the full suite against this repo and requires zero unsuppressed
findings — the checked-in concurrency annotations, thread owners,
jit purity, donation decisions and metric catalog all stay enforced.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "script"))

from pslint.affinity import ThreadAffinityRule  # noqa: E402
from pslint.determinism import DeterminismRule  # noqa: E402
from pslint.donate_flow import UseAfterDonateRule  # noqa: E402
from pslint.engine import Engine, SourceFile, default_rules  # noqa: E402
from pslint.jitpure import JitPurityRule  # noqa: E402
from pslint.locks import LockDisciplineRule  # noqa: E402
from pslint.spans import SpanDisciplineRule  # noqa: E402
from pslint.threads import ThreadLifecycleRule  # noqa: E402


def write(tmp_path, rel, body):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return rel


def run_rule(tmp_path, rule, rel):
    rule = type(rule)(scope=(rel,))
    findings, suppressed = Engine(str(tmp_path), [rule]).run()
    return findings, suppressed


class TestEngine:
    def test_findings_format_is_editor_clickable(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def bad(self):
                    self._x = 1
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert len(findings) == 1
        line = findings[0].format()
        # path:line rule message — splittable by the first two fields
        loc, rule, msg = line.split(" ", 2)
        assert loc == "m.py:10"
        assert rule == "guarded-access"
        assert "_x" in msg and "_lock" in msg

    def test_suppression_with_reason_silences(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def stat(self):
                    # single writer: only the dispatch thread mutates it
                    return self._x  # pslint: disable=guarded-access — monotonic stat read, staleness is fine
            """,
        )
        findings, suppressed = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert findings == []
        assert suppressed == 1

    def test_suppression_without_reason_rejected(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def stat(self):
                    return self._x  # pslint: disable=guarded-access
            """,
        )
        findings, suppressed = run_rule(tmp_path, LockDisciplineRule(), rel)
        # the reasonless disable does NOT silence the guarded-access
        # finding, and is a finding of its own
        rules = sorted(f.rule for f in findings)
        assert rules == ["guarded-access", "suppression"]
        assert suppressed == 0

    def test_unknown_rule_name_rejected(self):
        with pytest.raises(ValueError, match="unknown rule"):
            default_rules(["no-such-pass"])


class TestLockDiscipline:
    def test_clean_class_passes(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def inc(self):
                    with self._lock:
                        self._x += 1
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert findings == []

    def test_unguarded_read_and_write_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def bad_write(self):
                    self._x = 1

                def bad_read(self):
                    return self._x + 1
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.line for f in findings] == [10, 13]
        assert "written" in findings[0].message
        assert "read" in findings[1].message

    def test_holds_lock_annotation_honored(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def _bump_locked(self):  # holds-lock: _lock
                    self._x += 1

                def bump(self):
                    with self._lock:
                        self._bump_locked()
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert findings == []

    def test_nested_def_does_not_inherit_lock(self, tmp_path):
        """A def created under a with-lock may run on another thread
        (Thread targets!) — it must NOT count as holding the lock."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def spawnish(self):
                    with self._lock:
                        def escapes():
                            self._x += 1
                        return escapes
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["guarded-access"]

    def test_condition_wait_for_lambda_inherits_lock(self, tmp_path):
        """The WorkloadPool idiom: Condition(self._lock) shares the
        lock, and a wait_for predicate lambda runs with it held."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._n = 0  # guarded-by: _lock
                    self._lock = threading.Lock()
                    self._done = threading.Condition(self._lock)

                def wait(self):
                    with self._done:
                        self._done.wait_for(lambda: self._n > 0)
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert findings == []

    def test_unknown_guard_lock_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._x = 0  # guarded-by: _mutex
                    self._lock = threading.Lock()
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["unknown-lock"]

    def test_classlevel_guard_with_cls_lock(self, tmp_path):
        """The Postoffice singleton shape: class attribute guarded by a
        class-level lock, accessed via cls in classmethods."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Single:
                _instance = None  # guarded-by: _lock
                _lock = threading.Lock()

                @classmethod
                def instance(cls):
                    with cls._lock:
                        if cls._instance is None:
                            cls._instance = cls()
                        return cls._instance

                @classmethod
                def bad_peek(cls):
                    return cls._instance
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["guarded-access"]
        assert findings[0].line == 17

    def test_seeded_lock_order_cycle_detected(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["lock-order"]
        assert "C._a" in findings[0].message and "C._b" in findings[0].message

    def test_cross_class_consistent_order_is_acyclic(self, tmp_path):
        """Holding A._l while calling a B method that takes B._l is an
        edge, not a cycle, while every path agrees on the order."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class B:
                def __init__(self):
                    self._l = threading.Lock()
                    self.peer = None

                def poke(self):
                    with self._l:
                        pass

                def crossed(self):
                    with self._l:
                        self.peer.poke()

            class A:
                def __init__(self):
                    self._l = threading.Lock()
                    self.b = B()

                def crossed(self):
                    with self._l:
                        self.b.crossed()
            """,
        )
        # consistent one-directional order (A._l -> B._l only): no cycle
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert findings == []

    def test_holds_lock_method_contributes_order_edges(self, tmp_path):
        """A lock acquired inside a `# holds-lock:` method is an edge
        from the annotated lock — the *_locked convention must not
        silence deadlock-cycle detection."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def inner_locked(self):  # holds-lock: _b
                    with self._a:
                        pass

                def ab(self):
                    with self._a:
                        with self._b:
                            pass
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["lock-order"]
        assert "C._a" in findings[0].message and "C._b" in findings[0].message

    def test_multi_item_with_orders_locks(self, tmp_path):
        """``with self._a, self._b:`` acquires in item order — the
        intra-statement a→b edge must cycle against a reversed nested
        acquisition elsewhere."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a, self._b:
                        pass

                def ba(self):
                    with self._b:
                        with self._a:
                            pass
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["lock-order"]

    def test_duplicate_class_names_both_checked(self, tmp_path):
        """Two scope files reusing a class name must BOTH stay under
        checking — a name-keyed model map silently dropped one."""
        body = """
            import threading

            class W:
                def __init__(self):
                    self._x = 0  # guarded-by: _lock
                    self._lock = threading.Lock()

                def bad(self):
                    self._x = 1
        """
        rel1 = write(tmp_path, "m1.py", body)
        rel2 = write(tmp_path, "m2.py", body)
        rule = LockDisciplineRule(scope=(rel1, rel2))
        findings, _ = Engine(str(tmp_path), [rule]).run()
        assert sorted(f.path for f in findings) == ["m1.py", "m2.py"]
        assert {f.rule for f in findings} == {"guarded-access"}

    def test_cycle_through_method_call_detected(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class A:
                def __init__(self):
                    self._l = threading.Lock()
                    self.b = B()

                def into_b(self):
                    with self._l:
                        self.b.into_a()

                def touch(self):
                    with self._l:
                        pass

            class B:
                def __init__(self):
                    self._l = threading.Lock()
                    self.a = A()

                def into_a(self):
                    with self._l:
                        self.a.touch()
            """,
        )
        findings, _ = run_rule(tmp_path, LockDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["lock-order"]
        assert "A._l" in findings[0].message and "B._l" in findings[0].message


class TestThreadLifecycle:
    def test_joined_thread_passes(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Owner:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def stop(self):
                    self._t.join()
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadLifecycleRule(), rel)
        assert findings == []

    def test_unjoined_thread_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            def fire_and_forget(fn):
                t = threading.Thread(target=fn, daemon=True)
                t.start()
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadLifecycleRule(), rel)
        assert [f.rule for f in findings] == ["thread-join"]
        assert findings[0].line == 5

    def test_unjoined_thread_suppressible_with_reason(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            def fire_and_forget(fn):
                # pslint: disable=thread-join — interpreter-lifetime watcher, joined by no one by design
                t = threading.Thread(target=fn, daemon=True)
                t.start()
            """,
        )
        findings, suppressed = run_rule(tmp_path, ThreadLifecycleRule(), rel)
        assert findings == []
        assert suppressed == 1

    def test_str_join_does_not_satisfy_rule(self, tmp_path):
        """A ``", ".join(parts)`` in the owning class is not a thread
        join — classes with string formatting (Dashboard!) must not get
        a free pass for unjoined threads."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Renderer:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def render(self, parts):
                    return ", ".join(str(p) for p in parts)
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadLifecycleRule(), rel)
        assert [f.rule for f in findings] == ["thread-join"]

    def test_function_level_join_owns_spawn(self, tmp_path):
        """The iter_on_thread shape: spawn + join in one function."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            def run_joined(fn):
                t = threading.Thread(target=fn)
                t.start()
                try:
                    yield
                finally:
                    t.join()
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadLifecycleRule(), rel)
        assert findings == []


class TestSpansPass:
    def test_with_statement_span_passes(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            from parameter_server_tpu.telemetry import span, flow_scope

            def timed(fid):
                with flow_scope(fid), span("stage.prep", phase="e2e"):
                    return 1
            """,
        )
        findings, _ = run_rule(tmp_path, SpanDisciplineRule(), rel)
        assert findings == []

    def test_bare_span_call_flagged(self, tmp_path):
        """The PR-1 span-leak hazard: a bare span(...) builds a
        generator that never runs — untimed block, and a stored ctx can
        die with its owner and corrupt the timeline."""
        rel = write(
            tmp_path,
            "m.py",
            """
            from parameter_server_tpu.telemetry import span

            def leaky():
                span("stage.prep")
                return 1
            """,
        )
        findings, _ = run_rule(tmp_path, SpanDisciplineRule(), rel)
        assert [f.rule for f in findings] == ["span-with"]
        assert findings[0].line == 5

    def test_module_alias_span_flagged_and_with_passes(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            from parameter_server_tpu.telemetry import spans as telemetry_spans

            def bad():
                ctx = telemetry_spans.span("x")
                with ctx:
                    pass

            def good():
                with telemetry_spans.span("x"):
                    pass
            """,
        )
        findings, _ = run_rule(tmp_path, SpanDisciplineRule(), rel)
        assert [(f.rule, f.line) for f in findings] == [("span-with", 5)]

    def test_enter_context_owns_the_span(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import contextlib
            from parameter_server_tpu.telemetry import span

            def stacked():
                with contextlib.ExitStack() as stack:
                    stack.enter_context(span("stage.prep"))
            """,
        )
        findings, _ = run_rule(tmp_path, SpanDisciplineRule(), rel)
        assert findings == []

    def test_regex_match_span_not_flagged(self, tmp_path):
        """``re.Match.span()`` and other unrelated .span attributes must
        never trip the rule."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import re

            def bounds(m: "re.Match"):
                return m.span(), m.span(1)
            """,
        )
        findings, _ = run_rule(tmp_path, SpanDisciplineRule(), rel)
        assert findings == []

    def test_suppressible_with_reason(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            from parameter_server_tpu.telemetry import span

            def deferred():
                # pslint: disable=span-with — handed to the reactor loop, which enters and closes it
                return span("stage.prep")
            """,
        )
        findings, suppressed = run_rule(tmp_path, SpanDisciplineRule(), rel)
        assert findings == []
        assert suppressed == 1


class TestJitPurity:
    def test_pure_jit_passes(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import functools
            import jax
            import jax.numpy as jnp
            import numpy as np

            @functools.partial(jax.jit, static_argnames=("k",))
            def pure(x, *, k):
                # np constants / shape math are trace-time legal
                scale = 1.0 / np.sqrt(x.shape[-1])
                return jnp.sum(x * np.float32(scale), axis=-1)[:k]
            """,
        )
        findings, _ = run_rule(tmp_path, JitPurityRule(), rel)
        assert findings == []

    def test_print_np_time_nonlocal_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import time
            import jax
            import numpy as np

            calls = []

            @jax.jit
            def impure(x):
                nonlocal_count = 0

                def bump():
                    nonlocal nonlocal_count
                    nonlocal_count += 1

                print("tracing", x.shape)
                t0 = time.perf_counter()
                host = np.asarray(x)
                bump()
                return x * host.size + t0
            """,
        )
        findings, _ = run_rule(tmp_path, JitPurityRule(), rel)
        kinds = sorted(f.message.split(" inside")[0] for f in findings)
        assert kinds == [
            "host numpy np.asarray()",
            "nonlocal mutation",
            "print()",
            "time.perf_counter() clock read",
        ]

    def test_telemetry_call_inside_jit_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import jax

            def _tel():
                return None

            @jax.jit
            def step(x):
                tel = _tel()
                tel["pushes"].inc()
                return x + 1
            """,
        )
        findings, _ = run_rule(tmp_path, JitPurityRule(), rel)
        assert [f.rule for f in findings] == ["jit-purity"]
        assert ".inc()" in findings[0].message

    def test_jit_by_reference_scanned(self, tmp_path):
        """kv_ops shape: partial(jax.jit, ...)(impl) marks impl."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import functools
            import jax

            def _impl(x):
                print("boom")
                return x

            pull = functools.partial(jax.jit, static_argnames=())(_impl)
            """,
        )
        findings, _ = run_rule(tmp_path, JitPurityRule(), rel)
        assert [f.line for f in findings] == [6]


class TestDonationPass:
    def _fake_root(self, tmp_path, kv_ops_body):
        """A mini-repo exposing donation_lint's full scope."""
        from pslint.donation import _load_sibling

        scope = _load_sibling("donation_lint").SCOPE
        for rel in scope:
            write(tmp_path, rel, "")
        write(tmp_path, "parameter_server_tpu/ops/kv_ops.py", kv_ops_body)
        return tmp_path

    def test_undeclared_jit_site_flagged(self, tmp_path):
        from pslint.donation import DonationRule

        self._fake_root(
            tmp_path,
            """
            import jax

            def update(table, grads):
                return jax.jit(lambda t, g: t + g)(table, grads)
            """,
        )
        findings, _ = Engine(str(tmp_path), [DonationRule()]).run()
        assert [f.rule for f in findings] == ["donation"]
        assert findings[0].path == "parameter_server_tpu/ops/kv_ops.py"

    def test_no_donate_reason_passes(self, tmp_path):
        from pslint.donation import DonationRule

        self._fake_root(
            tmp_path,
            """
            import jax

            def pull(table, idx):
                # no-donate: pull reads the table; the store keeps it
                return jax.jit(lambda t, i: t[i])(table, idx)
            """,
        )
        findings, _ = Engine(str(tmp_path), [DonationRule()]).run()
        assert findings == []


class TestMetricsPass:
    def test_catalog_problems_become_findings(self, monkeypatch):
        from pslint import metrics as metrics_pass

        seen_roots = []

        class FakeLint:
            @staticmethod
            def lint(root=None):
                seen_roots.append(root)
                return ["counter 'x' should end in '_total'"]

        monkeypatch.setattr(metrics_pass, "_load_sibling", lambda name: FakeLint)
        findings = metrics_pass.MetricsRule().check({}, REPO)
        assert [f.rule for f in findings] == ["metrics"]
        assert findings[0].path.endswith("instruments.py")
        # --root must flow through to the catalog import (wrong-checkout
        # validation was a silent fail-open)
        assert seen_roots == [REPO]

    def test_live_catalog_is_clean(self):
        from pslint.metrics import MetricsRule

        assert MetricsRule().check({}, REPO) == []


class TestUseAfterDonate:
    DONATING_PRELUDE = """
            import functools
            import jax

            step = functools.partial(jax.jit, donate_argnums=(0,))(lambda t, g: t + g)

            def slow(t, g):
                return t + g
    """

    def test_read_after_donating_call_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            self.DONATING_PRELUDE
            + """
            def train(table, grads):
                out = step(table, grads)
                return table.sum()
            """,
        )
        findings, _ = run_rule(tmp_path, UseAfterDonateRule(), rel)
        assert [f.rule for f in findings] == ["use-after-donate"]
        assert "donated to step()" in findings[0].message

    def test_reassignment_kills_the_donation(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            self.DONATING_PRELUDE
            + """
            def train(table, grads):
                table = step(table, grads)
                return table.sum()
            """,
        )
        findings, _ = run_rule(tmp_path, UseAfterDonateRule(), rel)
        assert findings == []

    def test_donation_in_returning_branch_does_not_leak(self, tmp_path):
        """Regression: a donate inside an ``if`` arm that *returns* must
        not poison the fall-through sibling (the async_sgd selector
        idiom was a false positive until branch termination landed)."""
        rel = write(
            tmp_path,
            "m.py",
            self.DONATING_PRELUDE
            + """
            def train(table, grads, fast):
                if fast:
                    return step(table, grads)
                return slow(table, grads)
            """,
        )
        findings, _ = run_rule(tmp_path, UseAfterDonateRule(), rel)
        assert findings == []

    def test_one_wrapper_level_propagation(self, tmp_path):
        """A module function that forwards its arg into a donating
        callee is itself donating — callers one level up are caught."""
        rel = write(
            tmp_path,
            "m.py",
            self.DONATING_PRELUDE
            + """
            def apply(t, g):
                return step(t, g)

            def train(table, grads):
                apply(table, grads)
                return table.sum()
            """,
        )
        findings, _ = run_rule(tmp_path, UseAfterDonateRule(), rel)
        assert [f.rule for f in findings] == ["use-after-donate"]
        assert "donated to apply()" in findings[0].message

    def test_local_donating_name_does_not_poison_other_functions(
        self, tmp_path
    ):
        """Regression: a function-LOCAL ``fn = jit(..., donate_argnums=...)``
        must donate inside its own function only — a global name-keyed
        map flagged every unrelated call named ``fn``."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import jax

            def donating_scope(table, grads):
                fn = jax.jit(lambda t, g: t + g, donate_argnums=(0,))
                fn(table, grads)
                return table.sum()

            def innocent_scope(x):
                fn = lambda v: v + 1
                fn(x)
                return x + 1
            """,
        )
        findings, _ = run_rule(tmp_path, UseAfterDonateRule(), rel)
        assert [(f.line, f.rule) for f in findings] == [
            (7, "use-after-donate")
        ]

    def test_donated_dead_escape_comment(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            self.DONATING_PRELUDE
            + """
            def train(table, grads):
                out = step(table, grads)
                return table  # donated-dead: error-path echo only, never dereferenced
            """,
        )
        findings, _ = run_rule(tmp_path, UseAfterDonateRule(), rel)
        assert findings == []

    def test_suppressible_with_reason(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            self.DONATING_PRELUDE
            + """
            def train(table, grads):
                out = step(table, grads)
                return table.sum()  # pslint: disable=use-after-donate — fixture: proving the disable path
            """,
        )
        findings, suppressed = run_rule(
            tmp_path, UseAfterDonateRule(), rel
        )
        assert findings == []
        assert suppressed == 1


class TestThreadAffinity:
    def test_two_entry_points_without_lock_flagged(self, tmp_path):
        """The seeded violation: an owner-thread method reachable from
        two distinct Thread entry points with no lock on the path."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Pump:  # owner-thread: scheduler
                def __init__(self):
                    self.q = []
                    self._lock = threading.Lock()
                    self._t1 = threading.Thread(target=self._run_a, name="ingest")
                    self._t2 = threading.Thread(target=self._run_b, name="drain")

                def _run_a(self):
                    self.push(1)

                def _run_b(self):
                    self.push(2)

                def push(self, x):
                    self.q.append(x)
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadAffinityRule(), rel)
        assert [f.rule for f in findings] == ["thread-affinity"]
        assert "Pump.push" in findings[0].message
        # entry names surface in the message for triage
        assert "ingest" in findings[0].message
        assert "drain" in findings[0].message

    def test_locked_method_is_exempt(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Pump:  # owner-thread: scheduler
                def __init__(self):
                    self.q = []
                    self._lock = threading.Lock()
                    self._t1 = threading.Thread(target=self._run_a, name="ingest")
                    self._t2 = threading.Thread(target=self._run_b, name="drain")

                def _run_a(self):
                    self.push(1)

                def _run_b(self):
                    self.push(2)

                def push(self, x):
                    with self._lock:
                        self.q.append(x)
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadAffinityRule(), rel)
        assert findings == []

    def test_single_entry_point_is_fine(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Pump:  # owner-thread: scheduler
                def __init__(self):
                    self.q = []
                    self._t1 = threading.Thread(target=self._run_a, name="ingest")

                def _run_a(self):
                    self.push(1)

                def push(self, x):
                    self.q.append(x)
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadAffinityRule(), rel)
        assert findings == []

    def test_owner_thread_any_exempts_a_method(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Pump:  # owner-thread: scheduler
                def __init__(self):
                    self.q = []
                    self._t1 = threading.Thread(target=self._run_a, name="ingest")
                    self._t2 = threading.Thread(target=self._run_b, name="drain")

                def _run_a(self):
                    self.push(1)

                def _run_b(self):
                    self.push(2)

                def push(self, x):  # owner-thread: any
                    self.q.append(x)
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadAffinityRule(), rel)
        assert findings == []

    def test_unannotated_class_not_checked(self, tmp_path):
        """No ``# owner-thread:`` declaration — the pass has no owner
        contract to enforce; the locks pass covers such classes."""
        rel = write(
            tmp_path,
            "m.py",
            """
            import threading

            class Pump:
                def __init__(self):
                    self.q = []
                    self._t1 = threading.Thread(target=self._run_a, name="ingest")
                    self._t2 = threading.Thread(target=self._run_b, name="drain")

                def _run_a(self):
                    self.push(1)

                def _run_b(self):
                    self.push(2)

                def push(self, x):
                    self.q.append(x)
            """,
        )
        findings, _ = run_rule(tmp_path, ThreadAffinityRule(), rel)
        assert findings == []


class TestDeterminism:
    def test_scoped_module_without_marker_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            '''
            """Module under the contract but missing its marker."""

            X = 1
            ''',
        )
        findings, _ = run_rule(tmp_path, DeterminismRule(), rel)
        assert [(f.line, f.rule) for f in findings] == [(1, "determinism")]
        assert "bit-identical" in findings[0].message

    def test_set_iteration_and_wall_clock_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            # bit-identical
            import time

            def pack(d):
                return [k for k in set(d)]

            def stamp():
                return time.time()
            """,
        )
        findings, _ = run_rule(tmp_path, DeterminismRule(), rel)
        assert [f.line for f in findings] == [6, 9]
        assert "order varies" in findings[0].message
        assert "wall-clock" in findings[1].message

    def test_sorted_set_and_perf_counter_pass(self, tmp_path):
        """sorted(...) launders set order; perf_counter is a sanctioned
        telemetry clock — neither is a finding."""
        rel = write(
            tmp_path,
            "m.py",
            """
            # bit-identical
            import time

            def pack(d):
                return sorted(set(d))

            def tick():
                return time.perf_counter()
            """,
        )
        findings, _ = run_rule(tmp_path, DeterminismRule(), rel)
        assert findings == []

    def test_unseeded_rng_and_unsorted_listdir_flagged(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            # bit-identical
            import os
            import random

            def sample():
                return random.random()

            def shards(path):
                return [p for p in os.listdir(path)]
            """,
        )
        findings, _ = run_rule(tmp_path, DeterminismRule(), rel)
        assert len(findings) == 2
        assert "unseeded" in findings[0].message or "RNG" in findings[0].message
        assert "sorted" in findings[1].message

    def test_suppressible_with_reason(self, tmp_path):
        rel = write(
            tmp_path,
            "m.py",
            """
            # bit-identical
            import time

            def stamp():
                # pslint: disable=determinism — telemetry birth timestamp, never replayed bytes
                return time.time()
            """,
        )
        findings, suppressed = run_rule(tmp_path, DeterminismRule(), rel)
        assert findings == []
        assert suppressed == 1


class TestCrossArtifact:
    """Each sub-check drives a mini-repo holding both sides of one
    artifact boundary, drifted on purpose."""

    def _mini_repo(self, tmp_path, **overrides):
        defaults = {
            "parameter_server_tpu/__init__.py": "",
            "parameter_server_tpu/system/__init__.py": "",
            "parameter_server_tpu/system/faults.py": """
                POINTS = ("push_drop", "pull_stall")
            """,
            "parameter_server_tpu/system/drill.py": """
                from . import faults

                def go():
                    faults.arm("pull_stall")
            """,
            "parameter_server_tpu/telemetry/__init__.py": "",
            "parameter_server_tpu/telemetry/instruments.py": """
                NAMES = ("ps_push_total", "ps_pull_latency")
            """,
        }
        defaults.update(overrides)
        for rel, body in defaults.items():
            write(tmp_path, rel, body)
        return tmp_path

    def _run(self, tmp_path):
        from pslint.artifacts import CrossArtifactRule

        return Engine(str(tmp_path), [CrossArtifactRule()]).run()

    def test_consistent_mini_repo_is_clean(self, tmp_path):
        self._mini_repo(tmp_path)
        findings, _ = self._run(tmp_path)
        assert findings == []

    def test_unknown_fault_point_flagged(self, tmp_path):
        self._mini_repo(
            tmp_path,
            **{
                "parameter_server_tpu/system/drill.py": """
                    from . import faults

                    def go():
                        faults.inject("push_dorp")
                """
            },
        )
        findings, _ = self._run(tmp_path)
        assert [f.rule for f in findings] == ["fault-point"]
        assert "push_dorp" in findings[0].message

    def test_unqualified_arm_call_not_matched(self, tmp_path):
        """``blackbox.arm()`` is a different arm — only ``faults.``-
        qualified calls are pinned to POINTS."""
        self._mini_repo(
            tmp_path,
            **{
                "parameter_server_tpu/system/drill.py": """
                    def go(blackbox):
                        blackbox.arm("not_a_point")
                """
            },
        )
        findings, _ = self._run(tmp_path)
        assert findings == []

    def test_alert_metric_drift_flagged(self, tmp_path):
        self._mini_repo(tmp_path)
        write(
            tmp_path,
            "configs/alerts/a.json",
            '{"rules": [{"metric": "ps_pull_latency", "den": "ps_gone_total"}]}\n',
        )
        findings, _ = self._run(tmp_path)
        assert [f.rule for f in findings] == ["alert-metric"]
        assert "ps_gone_total" in findings[0].message
        assert findings[0].path == "configs/alerts/a.json"


class TestIncrementalCache:
    """The content-hash cache contract: a warm run recomputes nothing,
    an edit recomputes exactly the edited file, and the cache can
    neither hide a fresh finding nor resurrect a fixed one."""

    def _engine(self, tmp_path, rels):
        return Engine(
            str(tmp_path),
            [DeterminismRule(scope=tuple(rels))],
            cache_path=str(tmp_path / "cache.json"),
        )

    def test_warm_run_is_fully_cached(self, tmp_path):
        rels = [
            write(tmp_path, "a.py", "# bit-identical\nX = 1\n"),
            write(tmp_path, "b.py", "# bit-identical\nY = 1\n"),
        ]
        e1 = self._engine(tmp_path, rels)
        assert e1.run() == ([], 0)
        assert e1.stats["determinism"] == {"analyzed": 2, "cached": 0}
        e2 = self._engine(tmp_path, rels)
        assert e2.run() == ([], 0)
        assert e2.stats["determinism"] == {"analyzed": 0, "cached": 2}

    def test_edit_recomputes_only_the_edited_file(self, tmp_path):
        rels = [
            write(tmp_path, "a.py", "# bit-identical\nX = 1\n"),
            write(tmp_path, "b.py", "# bit-identical\nY = 1\n"),
        ]
        self._engine(tmp_path, rels).run()
        # introduce a finding in b only: the stale cache entry must not
        # hide it, and a must stay served from cache
        (tmp_path / "b.py").write_text(
            "# bit-identical\nimport time\nT = time.time()\n"
        )
        e = self._engine(tmp_path, rels)
        findings, _ = e.run()
        assert e.stats["determinism"] == {"analyzed": 1, "cached": 1}
        assert [(f.path, f.line) for f in findings] == [("b.py", 3)]
        # revert: the finding disappears (the key is the content hash,
        # so the bad entry cannot be served for the fixed file); the
        # save-only-touched policy pruned the original entry, so b is
        # re-analyzed once while a stays a hit
        (tmp_path / "b.py").write_text("# bit-identical\nY = 1\n")
        e2 = self._engine(tmp_path, rels)
        assert e2.run() == ([], 0)
        assert e2.stats["determinism"] == {"analyzed": 1, "cached": 1}

    def test_cached_findings_still_pass_suppression_filter(self, tmp_path):
        """The cache stores PRE-suppression findings; the filter runs
        every time, so editing only a comment elsewhere cannot leak a
        suppressed finding."""
        rel = write(
            tmp_path,
            "c.py",
            "# bit-identical\nimport time\n"
            "T = time.time()  # pslint: disable=determinism — fixture timestamp\n",
        )
        e1 = self._engine(tmp_path, [rel])
        assert e1.run() == ([], 1)
        e2 = self._engine(tmp_path, [rel])
        assert e2.run() == ([], 1)
        assert e2.stats["determinism"] == {"analyzed": 0, "cached": 1}

    def test_rule_version_bump_invalidates(self, tmp_path):
        """The rule version is part of the cache key — a pass upgrade
        must never serve findings computed by its older self."""
        rel = write(tmp_path, "a.py", "# bit-identical\nX = 1\n")
        self._engine(tmp_path, [rel]).run()

        class Bumped(DeterminismRule):
            version = DeterminismRule.version + "-test"

        e = Engine(
            str(tmp_path),
            [Bumped(scope=(rel,))],
            cache_path=str(tmp_path / "cache.json"),
        )
        e.run()
        assert e.stats["determinism"] == {"analyzed": 1, "cached": 0}


class TestRepoIsClean:
    def test_full_suite_repo_clean(self):
        """Tier-1 acceptance: the repo lints clean under every pass —
        the concurrency annotations, thread owners, jitted data plane,
        donation decisions and metric catalog all hold."""
        findings, _ = Engine(REPO, default_rules()).run()
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_every_suppression_carries_reason(self):
        """Engine-wide hygiene: scan every package + script file for
        pslint disables; each must parse with a reason (the engine
        enforces this for scoped files; this test sweeps everything)."""
        import re

        bad = []
        # (tests/ excluded: this file's fixture strings deliberately
        # contain a reasonless disable to prove the engine rejects it)
        for base in ("parameter_server_tpu", "script"):
            for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, base)):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fn in filenames:
                    if not fn.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, fn)
                    with open(path, encoding="utf-8") as f:
                        for i, line in enumerate(f, 1):
                            m = re.search(r"#\s*pslint:\s*disable=(\S+)", line)
                            if m is None:
                                continue
                            if not re.search(r"(?:—|–|--| - )\s*\S", line[m.end():]):
                                bad.append(f"{path}:{i}")
        assert bad == [], f"reasonless pslint suppressions: {bad}"

    def test_doc_section_references_resolve(self):
        """Code and docs cite the design docs by section name
        (``doc/PERFORMANCE.md "Donation rules"``): every such name is a
        heading, or a bold lead-in, of the file it names, so a section
        that is renamed or deleted takes its citations with it."""
        import re

        ref = re.compile(
            r'doc/([A-Z_]+\.md)[,:]?\s*(?:#\s*)?\(?\s*(?:#\s*)?"([^"]{3,80})"'
        )
        cache = {}

        def titles(doc):
            if doc not in cache:
                with open(os.path.join(REPO, "doc", doc),
                          encoding="utf-8") as f:
                    text = f.read().replace("`", "")
                found = re.findall(r"^#+\s+(.*)$", text, re.M)
                found += re.findall(r"\*\*([^*]+)\*\*", text)
                cache[doc] = [re.sub(r"\s+", " ", t).strip() for t in found]
            return cache[doc]

        from conftest import repo_texts

        dangling, seen = [], 0
        for rel, text in repo_texts(
            ("parameter_server_tpu", "script", "doc", "configs",
             "chip_smoke.py", "README.md", "Makefile", "PERF.md",
             "ROADMAP.md"),
            (".py", ".md", ".json", "Makefile"),
        ):
            for m in ref.finditer(text):
                # a citation may wrap inside a comment
                title = re.sub(
                    r"\s*\n\s*(?:#\s*)?", " ", m.group(2)
                ).replace("`", "").strip().rstrip(".")
                if len(title) < 4:
                    continue  # a string literal's own quotes
                seen += 1
                if not os.path.exists(
                    os.path.join(REPO, "doc", m.group(1))
                ) or not any(
                    t.startswith(title) for t in titles(m.group(1))
                ):
                    dangling.append((rel, m.group(1), title))
        assert seen >= 40  # the pattern still finds the citations
        assert dangling == []

    def test_cli_exit_codes(self):
        """The make target contract: exit 0 + OK line on this repo."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "script", "pslint", "cli.py")],
            capture_output=True,
            text=True,
            timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "pslint: OK" in proc.stdout

    def test_cli_rules_filter_and_list(self):
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(REPO, "script", "pslint", "cli.py"),
                "--list",
            ],
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert proc.returncode == 0
        assert set(proc.stdout.split()) == {
            "locks", "threads", "jit-purity", "donation", "metrics",
            "spans", "use-after-donate", "thread-affinity",
            "determinism", "cross-artifact",
        }

    def test_cli_timings_and_budget(self, tmp_path):
        """--timings reports per-pass wall-clock; --budget turns a slow
        run into exit 2 (the make target keeps the suite honest)."""
        write(tmp_path, "parameter_server_tpu/__init__.py", "")
        cli = os.path.join(REPO, "script", "pslint", "cli.py")
        base = [
            sys.executable, cli, "--root", str(tmp_path),
            "--rules", "spans", "--no-cache",
        ]
        proc = subprocess.run(
            base + ["--timings"], capture_output=True, text=True, timeout=60
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "pslint: timing spans:" in proc.stderr
        assert "pslint: timing total:" in proc.stderr
        proc = subprocess.run(
            base + ["--budget", "0"], capture_output=True, text=True,
            timeout=60,
        )
        assert proc.returncode == 2
        assert "BUDGET EXCEEDED" in proc.stderr
