"""graftcheck fixture suite: known-violation snippets must flag, clean
snippets must pass, suppressions/annotations must behave per the policy
in docs/static-analysis.md. Pure AST analysis — no JAX import, no
device; this file stays in the tier-1 gate.
"""

import subprocess
import sys
import textwrap

import pytest

from tools.graftcheck import __main__ as cli
from tools.graftcheck.core import Config, run_paths

REPO_ROOT = __file__.rsplit("/tests/", 1)[0]


def check(tmp_path, source, name="mod.py", select=None, **cfg_kw):
    """Write one fixture file and run the selected analyzers on it."""
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    cfg = Config(root=str(tmp_path), **cfg_kw)
    return run_paths([str(path)], cfg, select)


def rules(findings):
    return [f.rule for f in findings]


# -- trace-safety ------------------------------------------------------------

class TestTraceSafety:
    def test_host_sync_in_jitted_function_flags(self, tmp_path):
        fs = check(tmp_path, """
            import jax, numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x) + 1
        """, select=["trace"])
        assert "trace-safety/host-sync" in rules(fs)

    def test_item_call_in_scan_body_flags(self, tmp_path):
        fs = check(tmp_path, """
            from jax import lax

            def body(carry, x):
                return carry, x.item()

            def run(xs):
                return lax.scan(body, 0, xs)
        """, select=["trace"])
        assert "trace-safety/host-sync" in rules(fs)

    def test_branch_on_traced_value_flags(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            @jax.jit
            def step(x):
                if x > 0:
                    return x
                return -x
        """, select=["trace"])
        assert "trace-safety/tracer-branch" in rules(fs)

    def test_branch_on_static_state_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import jax, jax.numpy as jnp

            @jax.jit
            def step(x, config):
                if config.deep:          # static param name
                    x = x * 2
                if x.shape[0] > 4:       # shape reads are static
                    x = x[:4]
                return jnp.sum(x)
        """, select=["trace"])
        assert fs == []

    def test_static_argname_branch_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import functools, jax

            @functools.partial(jax.jit, static_argnames=("mode",))
            def step(x, mode):
                if mode == "fast":
                    return x * 2
                return x
        """, select=["trace"])
        assert fs == []

    def test_reachability_through_helper_calls(self, tmp_path):
        # The sync hides one call down from the jitted entry point.
        fs = check(tmp_path, """
            import jax, numpy as np

            def helper(x):
                return np.asarray(x)

            @jax.jit
            def step(x):
                return helper(x)
        """, select=["trace"])
        assert "trace-safety/host-sync" in rules(fs)

    def test_unreachable_sync_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import numpy as np

            def host_only(x):
                return np.asarray(x)      # never traced
        """, select=["trace"])
        assert fs == []

    def test_jit_in_loop_flags(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def compile_all(fns):
                out = []
                for f in fns:
                    out.append(jax.jit(f))
                return out
        """, select=["trace"])
        assert "trace-safety/jit-in-loop" in rules(fs)

    def test_static_unhashable_default_flags(self, tmp_path):
        fs = check(tmp_path, """
            import functools, jax

            @functools.partial(jax.jit, static_argnames=("shapes",))
            def step(x, shapes=[1, 2]):
                return x
        """, select=["trace"])
        assert "trace-safety/static-unhashable" in rules(fs)

    def test_sync_ok_suppression_with_reason(self, tmp_path):
        fs = check(tmp_path, """
            import jax, numpy as np

            @jax.jit
            def step(x):
                # graftcheck: sync-ok fixture says this readback is intentional
                return np.asarray(x) + 1
        """, select=["trace"])
        assert fs == []

    def test_reasonless_suppression_is_its_own_finding(self, tmp_path):
        fs = check(tmp_path, """
            import jax, numpy as np

            @jax.jit
            def step(x):
                # graftcheck: sync-ok
                return np.asarray(x) + 1
        """, select=["trace"])
        assert "suppression/no-reason" in rules(fs)

    def test_hot_sync_covers_np_array_and_tolist(self, tmp_path):
        fs = check(tmp_path, """
            import numpy as np

            def snapshot(self, logits):
                live = np.array([1, 2], bool)
                return logits.tolist()
        """, name="serve/scheduler.py", select=["trace"])
        assert rules(fs).count("trace-safety/hot-sync") == 2

    def test_trailing_suppression_does_not_leak_to_next_statement(
            self, tmp_path):
        # A trailing sync-ok on one statement must not suppress the
        # separate statement on the next line.
        fs = check(tmp_path, """
            import numpy as np

            def drain(self):
                a = np.asarray(self.x)  # graftcheck: sync-ok first readback is intentional
                b = np.asarray(self.y)
                return a, b
        """, name="serve/scheduler.py", select=["trace"])
        assert [f.line for f in fs
                if f.rule == "trace-safety/hot-sync"] == [6]

    def test_trailing_suppression_inside_multiline_statement_applies(
            self, tmp_path):
        # ...but a trailing comment mid-way through ONE multi-line call
        # covers the call's later physical lines (the in-tree
        # scheduler/multihost annotations use this form).
        fs = check(tmp_path, """
            import numpy as np

            def build(self, ids):
                return self._build_j(
                    self._params,  # graftcheck: sync-ok upload of host ids, not a readback
                    np.asarray(ids))
        """, name="serve/scheduler.py", select=["trace"])
        assert fs == []

    def test_hot_path_sync_requires_annotation(self, tmp_path):
        src = """
            import numpy as np

            def drain(lengths):
                return np.asarray(lengths)
        """
        fs = check(tmp_path, src, name="serve/scheduler.py",
                   select=["trace"])
        assert "trace-safety/hot-sync" in rules(fs)
        # Same code outside the hot-path modules needs no annotation.
        assert check(tmp_path, src, name="serve/other.py",
                     select=["trace"]) == []


# -- lock-discipline ---------------------------------------------------------

class TestLockDiscipline:
    def test_unguarded_access_flags(self, tmp_path):
        fs = check(tmp_path, """
            import threading

            class Store:
                def __init__(self):
                    self._data = {}       # guarded-by: _mu
                    self._mu = threading.Lock()

                def get(self, k):
                    return self._data.get(k)
        """, select=["lock"])
        assert "lock-discipline/unguarded" in rules(fs)

    def test_guarded_access_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import threading

            class Store:
                def __init__(self):
                    self._data = {}       # guarded-by: _mu
                    self._mu = threading.Lock()

                def get(self, k):
                    with self._mu:
                        return self._data.get(k)
        """, select=["lock"])
        assert fs == []

    def test_nested_function_does_not_inherit_lock(self, tmp_path):
        # The closure runs later, on whatever thread calls it — holding
        # the lock at definition time protects nothing.
        fs = check(tmp_path, """
            import threading

            class Store:
                def __init__(self):
                    self._data = {}       # guarded-by: _mu
                    self._mu = threading.Lock()

                def deferred(self):
                    with self._mu:
                        def later():
                            return self._data.copy()
                    return later
        """, select=["lock"])
        assert "lock-discipline/unguarded" in rules(fs)

    def test_trailing_annotation_does_not_bleed_to_next_line(self, tmp_path):
        # Regression: the lock assignment on the line AFTER a trailing
        # `# guarded-by:` comment must not register as guarded by itself
        # (acquiring `with self._mu:` would then flag everywhere).
        fs = check(tmp_path, """
            import threading

            class Store:
                def __init__(self):
                    self._data = {}       # guarded-by: _mu
                    self._mu = threading.Lock()

                def swap(self):
                    with self._mu:
                        self._data = {}
        """, select=["lock"])
        assert fs == []

    def test_bad_lock_name_flags(self, tmp_path):
        fs = check(tmp_path, """
            class Store:
                def __init__(self):
                    self._data = {}       # guarded-by: _nonexistent
        """, select=["lock"])
        assert "lock-discipline/bad-lock" in rules(fs)

    def test_owned_by_off_thread_access_flags(self, tmp_path):
        fs = check(tmp_path, """
            class Sched:
                def __init__(self):
                    self._slots = []      # owned-by: _loop

                def _loop(self):
                    self._slots.append(1)

                def snapshot(self):
                    return len(self._slots)
        """, select=["lock"])
        assert "lock-discipline/off-thread" in rules(fs)

    def test_runs_on_annotation_clears_off_thread(self, tmp_path):
        fs = check(tmp_path, """
            class Sched:
                def __init__(self):
                    self._slots = []      # owned-by: _loop

                def _loop(self):
                    self._tick()

                def _tick(self):
                    self._slots.append(1)

                # graftcheck: runs-on _loop
                def _warm(self):
                    return len(self._slots)
        """, select=["lock"])
        assert fs == []

    def test_function_level_suppression_covers_body(self, tmp_path):
        fs = check(tmp_path, """
            class Sched:
                def __init__(self):
                    self._slots = []      # owned-by: _loop

                def _loop(self):
                    self._slots.append(1)

                # graftcheck: lock-ok fixture: drained after thread join
                def stop(self):
                    self._slots = []
        """, select=["lock"])
        assert fs == []


# -- lock-order --------------------------------------------------------------

class TestLockOrder:
    def test_two_class_cycle_flags_with_witness(self, tmp_path):
        # A holds its lock calling into B (A._mu -> B._mu) while B holds
        # its lock calling back into A (B._mu -> A._mu): the classic
        # cross-object deadlock the per-class grammar cannot see.
        fs = check(tmp_path, """
            import threading

            class A:
                def __init__(self):
                    self._mu = threading.Lock()
                    self.b = B(self)

                def m(self):
                    with self._mu:
                        self.b.poke()

                def poke2(self):
                    with self._mu:
                        pass

            class B:
                def __init__(self, a: "A"):
                    self._mu = threading.Lock()
                    self.a = a

                def poke(self):
                    with self._mu:
                        pass

                def n(self):
                    with self._mu:
                        self.a.poke2()
        """, select=["order"])
        cyc = [f for f in fs if f.rule == "lock-order/cycle"]
        assert cyc, rules(fs)
        assert "A._mu" in cyc[0].message and "B._mu" in cyc[0].message

    def test_nested_class_lock_does_not_bleed_into_outer(self, tmp_path):
        # Outer._pool is a plain context-managed resource; only the
        # nested helper class owns a Lock named _pool. Registering it
        # as Outer's lock fabricates an Outer._mu <-> Outer._pool cycle
        # on code with exactly one real lock.
        fs = check(tmp_path, """
            import threading

            class Outer:
                class _Helper:
                    def __init__(self):
                        self._pool = threading.Lock()

                def __init__(self):
                    self._mu = threading.Lock()
                    self._pool = ConnectionPool()

                def a(self):
                    with self._mu:
                        with self._pool:
                            pass

                def b(self):
                    with self._pool:
                        with self._mu:
                            pass
        """, select=["order"])
        assert fs == []

    def test_closure_acquires_do_not_attribute_to_definer(self, tmp_path):
        # start() only DEFINES worker; the closure runs later on its
        # own thread (the lock-discipline scoping rule). Attributing
        # _b to start() fabricates an _a -> _b edge and a false cycle
        # against the legitimate b-then-a path in n().
        fs = check(tmp_path, """
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def start(self):
                    def worker():
                        with self._b:
                            pass
                    return worker

                def m(self):
                    with self._a:
                        self.start()

                def n(self):
                    with self._b:
                        with self._a:
                            pass
        """, select=["order"])
        assert fs == []

    def test_condition_reentrancy_follows_wrapped_lock(self, tmp_path):
        # Condition() wraps an RLock: same-thread re-entry is legal and
        # must not read as a self-deadlock. Condition(Lock()) is the
        # opposite — re-entry really does deadlock.
        src = """
            import threading

            class S:
                def __init__(self):
                    self._cv = threading.Condition({arg})

                def m(self):
                    with self._cv:
                        self.n()

                def n(self):
                    with self._cv:
                        pass
        """
        assert check(tmp_path, src.format(arg=""), name="a.py",
                     select=["order"]) == []
        fs = check(tmp_path, src.format(arg="threading.Lock()"),
                   name="b.py", select=["order"])
        assert "lock-order/cycle" in rules(fs)

    def test_semaphore_initial_count_sets_reentrancy(self, tmp_path):
        # Semaphore(2): a second same-thread acquire takes another
        # permit. The default count of 1 blocks — a real self-deadlock.
        src = """
            import threading

            class S:
                def __init__(self):
                    self._sem = threading.Semaphore({arg})

                def m(self):
                    with self._sem:
                        self.n()

                def n(self):
                    with self._sem:
                        pass
        """
        assert check(tmp_path, src.format(arg="2"), name="a.py",
                     select=["order"]) == []
        fs = check(tmp_path, src.format(arg=""), name="b.py",
                   select=["order"])
        assert "lock-order/cycle" in rules(fs)

    def test_acyclic_nesting_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import threading

            class S:
                def __init__(self):
                    self._outer = threading.Lock()
                    self._inner = threading.Lock()

                def m(self):
                    with self._outer:
                        with self._inner:
                            pass
        """, select=["order"])
        assert fs == []

    def test_self_reacquire_of_plain_lock_flags(self, tmp_path):
        # m holds _mu and calls n, which takes _mu again: instant
        # self-deadlock on a non-reentrant Lock.
        src = """
            import threading

            class S:
                def __init__(self):
                    self._mu = threading.{cls}()

                def m(self):
                    with self._mu:
                        self.n()

                def n(self):
                    with self._mu:
                        pass
        """
        fs = check(tmp_path, src.format(cls="Lock"), select=["order"])
        assert "lock-order/cycle" in rules(fs)
        # The same shape on an RLock is reentrant and fine.
        fs = check(tmp_path, src.format(cls="RLock"), name="r.py",
                   select=["order"])
        assert fs == []

    def test_declared_order_contradicted_by_code_flags(self, tmp_path):
        fs = check(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    # lock-order: C._b < C._a
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def m(self):
                    with self._a:
                        with self._b:
                            pass
        """, select=["order"])
        assert "lock-order/cycle" in rules(fs)
        assert "declared" in [f for f in fs
                              if f.rule == "lock-order/cycle"][0].message

    def test_consistent_declaration_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    # lock-order: C._a < C._b
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def m(self):
                    with self._a:
                        with self._b:
                            pass
        """, select=["order"])
        assert fs == []

    def test_declaration_typo_flags_unknown_lock(self, tmp_path):
        fs = check(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    # lock-order: C._a < C._nope
                    self._a = threading.Lock()
        """, select=["order"])
        assert "lock-order/unknown-lock" in rules(fs)

    def test_multi_item_with_orders_items(self, tmp_path):
        # `with self._a, self._b:` acquires left to right — the same
        # a->b edge as the nested form, so against a method taking them
        # in the other order it is the textbook two-lock deadlock.
        fs = check(tmp_path, """
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def m(self):
                    with self._a, self._b:
                        pass

                def n(self):
                    with self._b:
                        with self._a:
                            pass
        """, select=["order"])
        assert "lock-order/cycle" in rules(fs)

    def test_nested_def_does_not_inherit_held_lock(self, tmp_path):
        # The closure runs later on another thread: no A->B edge, no
        # cycle even with the reverse declared.
        fs = check(tmp_path, """
            import threading

            class S:
                def __init__(self):
                    # lock-order: S._b < S._a
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def m(self):
                    with self._a:
                        def later(self=self):
                            with self._b:
                                pass
                    return later
        """, select=["order"])
        assert fs == []


# -- blocking-under-lock -----------------------------------------------------

class TestBlocking:
    def test_sleep_under_lock_flags(self, tmp_path):
        fs = check(tmp_path, """
            import threading, time

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self):
                    with self._mu:
                        time.sleep(1.0)
        """, name="serve/mod.py", select=["blocking"])
        assert "blocking/under-lock" in rules(fs)

    def test_nested_class_lock_does_not_bleed_into_outer(self, tmp_path):
        # Same defect class as lock-order's: a nested class's Lock named
        # _pool must not make Outer's plain `with self._pool:` count as
        # a held lock around the sleep.
        fs = check(tmp_path, """
            import threading, time

            class Outer:
                class _Helper:
                    def __init__(self):
                        self._pool = threading.Lock()

                def __init__(self):
                    self._pool = ConnectionPool()

                def m(self):
                    with self._pool:
                        time.sleep(1.0)
        """, name="serve/mod.py", select=["blocking"])
        assert fs == []

    def test_nested_function_in_module_function_flags_once(self, tmp_path):
        # `inner` is reached while visiting `outer`; starting it again
        # as its own top-level root would print the finding twice.
        fs = check(tmp_path, """
            import threading, time

            _mu = threading.Lock()

            def outer():
                def inner():
                    with _mu:
                        time.sleep(1.0)
                return inner
        """, name="serve/mod.py", select=["blocking"])
        assert rules(fs) == ["blocking/under-lock"]

    def test_http_under_lock_flags(self, tmp_path):
        fs = check(tmp_path, """
            import threading
            import urllib.request

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self, url):
                    with self._mu:
                        return urllib.request.urlopen(url)
        """, name="p2p/mod.py", select=["blocking"])
        assert "blocking/under-lock" in rules(fs)

    def test_queue_get_without_timeout_flags(self, tmp_path):
        src = """
            import queue, threading

            class S:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue()

                def m(self):
                    with self._mu:
                        return self._q.get({args})
        """
        fs = check(tmp_path, src.format(args=""), name="serve/a.py",
                   select=["blocking"])
        assert "blocking/under-lock" in rules(fs)
        # A timeout bounds the wait; block=False never waits.
        assert check(tmp_path, src.format(args="timeout=0.1"),
                     name="serve/b.py", select=["blocking"]) == []
        assert check(tmp_path, src.format(args="block=False"),
                     name="serve/c.py", select=["blocking"]) == []

    def test_dict_get_on_queue_named_mapping_is_clean(self, tmp_path):
        # Queue.get's signature is (block=True, timeout=None): a first
        # positional that isn't a literal bool is dict.get(key, default)
        # on a queue-NAMED mapping — a lock-free read, not a wait.
        fs = check(tmp_path, """
            import threading

            class S:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._by_queue = {}

                def m(self, req_id):
                    with self._mu:
                        return self._by_queue.get(req_id, None)
        """, name="serve/a.py", select=["blocking"])
        assert fs == []

    def test_timeout_none_is_still_unbounded(self, tmp_path):
        # Queue.get(timeout=None) is the documented INFINITE wait — the
        # most literal spelling of unbounded must not read as a bound.
        fs = check(tmp_path, """
            import queue, threading

            class S:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue()

                def m(self):
                    with self._mu:
                        return self._q.get(timeout=None)
        """, name="serve/a.py", select=["blocking"])
        assert "blocking/under-lock" in rules(fs)

    def test_truthy_positional_block_arg_is_a_queue_wait(self, tmp_path):
        # Queue.get(1) is block=1 — truthy, waits forever on an empty
        # queue. A numeric first positional must read as the block
        # flag, not demote the call to dict.get(key).
        fs = check(tmp_path, """
            import queue, threading

            class S:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._q = queue.Queue()

                def m(self):
                    with self._mu:
                        return self._q.get(1)
        """, name="serve/a.py", select=["blocking"])
        assert "blocking/under-lock" in rules(fs)

    def test_wait_timeout_none_is_still_unbounded(self, tmp_path):
        # Same rule as Queue.get: wait(timeout=None) IS the infinite
        # wait; a real timeout bounds it.
        src = """
            import threading

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self, ev):
                    with self._mu:
                        ev.wait({args})
        """
        for args in ("timeout=None", "None", ""):
            fs = check(tmp_path, src.format(args=args),
                       name=f"serve/w{len(args)}.py", select=["blocking"])
            assert "blocking/under-lock" in rules(fs), args
        assert check(tmp_path, src.format(args="0.5"),
                     name="serve/wb.py", select=["blocking"]) == []

    def test_cond_wait_on_the_held_lock_is_exempt(self, tmp_path):
        # The canonical CV pattern: cond.wait() RELEASES the held
        # condition while waiting — nothing stalls behind it. It is
        # still blocking when a DIFFERENT lock stays held across the
        # wait.
        fs = check(tmp_path, """
            import threading

            class S:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._mu = threading.Lock()

                def good(self):
                    with self._cond:
                        self._cond.wait()

                def bad(self):
                    with self._mu:
                        with self._cond:
                            self._cond.wait()
        """, name="serve/a.py", select=["blocking"])
        assert len(rules(fs)) == 1
        assert "blocking/under-lock" in rules(fs)

    def test_multi_item_with_holds_earlier_items(self, tmp_path):
        # Items acquire left to right: the urlopen in the second item
        # of `with self._mu, urlopen(url):` executes under _mu.
        fs = check(tmp_path, """
            import threading
            import urllib.request

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self, url):
                    with self._mu, urllib.request.urlopen(url) as r:
                        return r.read()
        """, name="serve/a.py", select=["blocking"])
        assert "blocking/under-lock" in rules(fs)

    def test_outside_hot_dirs_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import threading, time

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self):
                    with self._mu:
                        time.sleep(1.0)
        """, name="models/mod.py", select=["blocking"])
        assert fs == []

    def test_nested_def_does_not_inherit_lock(self, tmp_path):
        fs = check(tmp_path, """
            import threading, time

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self):
                    with self._mu:
                        def later():
                            time.sleep(1.0)
                    return later
        """, name="serve/mod.py", select=["blocking"])
        assert fs == []

    def test_block_ok_suppression_with_reason(self, tmp_path):
        fs = check(tmp_path, """
            import threading, time

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def m(self):
                    with self._mu:
                        # graftcheck: block-ok fixture: bounded settle wait by design
                        time.sleep(0.01)
        """, name="serve/mod.py", select=["blocking"])
        assert fs == []

    def test_sleep_without_lock_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import time

            def pace():
                time.sleep(1.0)
        """, name="serve/mod.py", select=["blocking"])
        assert fs == []


# -- metrics-contract --------------------------------------------------------

class TestMetricsContract:
    def test_consumed_but_unexported_flags(self, tmp_path):
        # The router-aggregation-table shape: a display of series names
        # with no registration site anywhere.
        fs = check(tmp_path, """
            TABLE = frozenset(("serve_ghost_total",))
        """, name="serve/agg.py", select=["metrics"])
        assert "metrics-contract/unexported" in rules(fs)

    def test_registered_consumer_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.metrics import Registry
            reg = Registry()
            c = reg.counter("serve_ghost_total")
            TABLE = frozenset(("serve_ghost_total",))
        """, name="serve/agg.py", select=["metrics"])
        assert fs == []

    def test_snapshot_key_counts_as_export(self, tmp_path):
        fs = check(tmp_path, """
            class S:
                def metrics_snapshot(self):
                    out = {"serve_ghost_total": 1}
                    return out

            TABLE = ("serve_ghost_total",)
        """, name="serve/agg.py", select=["metrics"])
        assert fs == []

    def test_test_grep_counts_as_consumer(self, tmp_path):
        fs = check(tmp_path, """
            def test_metrics():
                text = ""
                assert "serve_ghost_total" in text
        """, name="test_fixture.py", select=["metrics"])
        assert "metrics-contract/unexported" in rules(fs)

    def test_docs_catalog_counts_as_consumer(self, tmp_path):
        # fixture_ prefix on purpose: exact series literals in THIS file
        # would otherwise read as consumer references when graftcheck
        # scans the real tree (the analyzer covers tests/ by design).
        (tmp_path / "metrics.md").write_text(
            "prose `fixture_prose_total` is ignored\n"
            "<!-- metrics-contract:begin -->\n"
            "| `fixture_listed_total` | a documented series |\n"
            "| `fixture_{a,b}_total` | brace shorthand expands |\n"
            "<!-- metrics-contract:end -->\n")
        fs = check(tmp_path, "x = 1\n", name="serve/mod.py",
                   select=["metrics"], metrics_docs=("metrics.md",),
                   metric_prefixes=("fixture_",))
        names = {f.message.split("`")[1] for f in fs}
        assert names == {"fixture_listed_total", "fixture_a_total",
                         "fixture_b_total"}

    def test_docs_catalog_checks_prefix_only_names(self, tmp_path):
        # The marked region is a curated catalog: a prefix match alone
        # makes a token contract there — `serve_draining`-shaped names
        # (no grammar suffix) must not sit listed-but-unchecked. Tokens
        # without a series prefix (label keys like `replica`) stay out.
        (tmp_path / "metrics.md").write_text(
            "<!-- metrics-contract:begin -->\n"
            "| `fixture_draining` | gauge (`replica` label) |\n"
            "<!-- metrics-contract:end -->\n")
        fs = check(tmp_path, "x = 1\n", name="serve/mod.py",
                   select=["metrics"], metrics_docs=("metrics.md",),
                   metric_prefixes=("fixture_",))
        names = {f.message.split("`")[1] for f in fs}
        assert names == {"fixture_draining"}

    def test_duplicate_unlabeled_export_flags(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.metrics import Registry
            a = Registry().counter("serve_twice_total")
            b = Registry().counter("serve_twice_total")
        """, name="serve/agg.py", select=["metrics"])
        assert "metrics-contract/duplicate-export" in rules(fs)

    def test_partial_run_duplicate_export_stays_suppressible(self,
                                                             tmp_path):
        # Exports resolve tree-wide, so a duplicate's sites can sit in
        # a file whose metrics-ok suppressions were never loaded. The
        # finding must anchor in the analyzed set (where suppressions
        # apply) and vanish from partial runs that don't select any of
        # its sites — the full CI run still reports it.
        pkg = tmp_path / "pkg" / "serve"
        pkg.mkdir(parents=True)
        (tmp_path / "pkg" / "__init__.py").write_text("")
        (pkg / "__init__.py").write_text("")
        (pkg / "exp.py").write_text(textwrap.dedent("""
            a = reg.counter("serve_twice_total")  # graftcheck: metrics-ok fixture: legacy double registration
            b = reg.counter("serve_twice_total")
        """))
        other = pkg / "other.py"
        other.write_text("x = 1\n")
        cfg = Config(root=str(tmp_path), package_dirs=("pkg",))
        # Analyzed directly, exp.py's own suppression applies...
        assert run_paths([str(pkg / "exp.py")], cfg, ["metrics"]) == []
        # ...and a partial run of a sibling must not resurrect the
        # finding anchored where no suppression can be consulted.
        assert run_paths([str(other)], cfg, ["metrics"]) == []

    def test_package_tree_reloads_after_edit(self, tmp_path):
        # The resolution-tree cache must key on file state, not just
        # the root: in a long-lived process an export added after the
        # first run has to satisfy the consumer on the second.
        pkg = tmp_path / "pkg" / "serve"
        pkg.mkdir(parents=True)
        (tmp_path / "pkg" / "__init__.py").write_text("")
        (pkg / "__init__.py").write_text("")
        exp = pkg / "exp.py"
        exp.write_text("x = 1\n")
        cons = pkg / "agg.py"
        cons.write_text('TABLE = ("serve_ghost_total",)\n')
        cfg = Config(root=str(tmp_path), package_dirs=("pkg",))
        assert "metrics-contract/unexported" in rules(
            run_paths([str(cons)], cfg, ["metrics"]))
        exp.write_text('c = reg.counter("serve_ghost_total")\n')
        assert run_paths([str(cons)], cfg, ["metrics"]) == []

    def test_non_metric_shaped_literals_ignored(self, tmp_path):
        # Bench row keys / ledger keys share suffixes but lack the
        # series prefixes — out of scope by the name grammar.
        fs = check(tmp_path, """
            ROW = ("ttft_p50_ms", "wall_over_device")
            assert "p50_ttft_ms" not in ROW
        """, name="serve/agg.py", select=["metrics"])
        assert fs == []


# -- stream-close discipline -------------------------------------------------

class TestStreamClose:
    def test_yield_outside_finally_flags(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            def handler(req):
                def gen():
                    yield b"data"
                    yield b"more"
                return Response(200, stream=gen())
        """, select=["streams"])
        assert "stream-close/no-finally" in rules(fs)

    def test_self_method_stream_flags(self, tmp_path):
        # stream=self._stream(...) — the loadgen/stub.py shape — must
        # resolve against the enclosing class's methods, not silently
        # escape checking.
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            class H:
                def _stream(self, gauge):
                    gauge.add(1)
                    yield b"data"
                    gauge.add(-1)

                def handler(self, req, gauge):
                    return Response(200, stream=self._stream(gauge))
        """, select=["streams"])
        assert "stream-close/no-finally" in rules(fs)

    def test_self_method_stream_with_finally_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            class H:
                def _stream(self, gauge):
                    try:
                        yield b"data"
                    finally:
                        gauge.add(-1)

                def handler(self, req, gauge):
                    return Response(200, stream=self._stream(gauge))
        """, select=["streams"])
        assert fs == []

    def test_try_finally_wrapped_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            def handler(req, gauge):
                def gen():
                    try:
                        yield b"data"
                    finally:
                        gauge.add(-1)
                return Response(200, stream=gen())
        """, select=["streams"])
        assert fs == []

    def test_with_wrapped_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            def handler(req, resp):
                def gen():
                    with resp:
                        for line in resp:
                            yield line
                return Response(200, stream=gen())
        """, select=["streams"])
        assert fs == []

    def test_same_named_gens_resolve_per_handler(self, tmp_path):
        # Every in-tree handler nests a `def gen():` — resolution must
        # be the NEAREST enclosing scope, or only the first gen in the
        # file is ever checked and each later handler's leak escapes.
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            def handler_ok(req, gauge):
                def gen():
                    try:
                        yield b"data"
                    finally:
                        gauge.add(-1)
                return Response(200, stream=gen())

            def handler_leaky(req, gauge):
                def gen():
                    gauge.add(1)
                    yield b"data"
                    gauge.add(-1)
                return Response(200, stream=gen())
        """, select=["streams"])
        assert rules(fs) == ["stream-close/no-finally"]

    def test_plain_generator_not_streamed_is_ignored(self, tmp_path):
        fs = check(tmp_path, """
            def pairs(xs):
                for x in xs:
                    yield x, x
        """, select=["streams"])
        assert fs == []

    def test_stream_ok_suppression_with_reason(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.http import Response

            def handler(req):
                # graftcheck: stream-ok fixture: single constant yield, nothing held
                def gen():
                    yield b"{}"
                return Response(200, stream=gen())
        """, select=["streams"])
        assert fs == []


# -- runtime lockcheck (GRAFTCHECK_LOCKCHECK=1) ------------------------------

class TestLockcheck:
    def _load(self, tmp_path, source, name="guarded_fixture"):
        import importlib.util
        from tools.graftcheck import lockcheck
        path = tmp_path / f"{name}.py"
        path.write_text(textwrap.dedent(source))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        armed = lockcheck.instrument_module(mod, str(path))
        return mod, armed

    SRC = """
        import threading

        class Store:
            def __init__(self):
                self._mu = threading.Lock()
                self._data = {}       # guarded-by: _mu

            def put(self, k, v):
                with self._mu:
                    self._data[k] = v

            def unguarded(self, k):
                return self._data.get(k)
    """

    def test_unguarded_access_raises(self, tmp_path):
        from tools.graftcheck.lockcheck import LockcheckError
        mod, armed = self._load(tmp_path, self.SRC)
        assert armed == ["Store._data<-_mu"]
        s = mod.Store()          # init-time assignment is exempt
        s.put("a", 1)            # locked write passes
        with s._mu:
            assert s._data == {"a": 1}      # locked read passes
        with pytest.raises(LockcheckError):
            s.unguarded("a")

    def test_lock_held_by_another_thread_still_raises(self, tmp_path):
        import threading
        from tools.graftcheck.lockcheck import LockcheckError
        mod, _ = self._load(tmp_path, self.SRC, name="guarded_other")
        s = mod.Store()
        hold = threading.Event()
        release = threading.Event()

        def holder():
            with s._mu:
                hold.set()
                release.wait(5.0)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert hold.wait(5.0)
        try:
            # SOMEONE holds the lock — but not this thread: lock.locked()
            # alone would pass here; owner tracking must not.
            with pytest.raises(LockcheckError, match="another thread"):
                s.unguarded("a")
        finally:
            release.set()
            t.join(timeout=5.0)

    def test_runtime_honors_lockcheck_ok_suppression(self, tmp_path):
        mod, _ = self._load(tmp_path, """
            import threading

            class Store:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._n = 0           # guarded-by: _mu

                # graftcheck: lockcheck-ok fixture: advisory torn read is acceptable here
                def peek(self):
                    return self._n
        """, name="guarded_suppressed")
        s = mod.Store()
        assert s.peek() == 0     # suppressed site: no raise

    def test_condition_wait_does_not_corrupt_ownership(self, tmp_path):
        # Condition.wait() releases the raw primitive PAST the proxy; a
        # shared owner/depth pair would let the producer's enter/exit
        # strand stale state — a spurious raise for the woken consumer
        # and a free pass for the producer. Per-thread counts survive
        # the interleave: the consumer's post-wait guarded access
        # passes, and the producer's later unguarded read still raises.
        import threading
        from tools.graftcheck.lockcheck import LockcheckError
        mod, _ = self._load(tmp_path, """
            import threading

            class Box:
                def __init__(self):
                    self._cv = threading.Condition()
                    self._val = 0         # guarded-by: _cv

                def consume(self):
                    with self._cv:
                        while self._val == 0:
                            self._cv.wait(5.0)
                        got = self._val
                        self._val = 0
                        return got

                def produce(self, v):
                    with self._cv:
                        self._val = v
                        self._cv.notify()

                def unguarded(self):
                    return self._val
        """, name="guarded_condition")
        b = mod.Box()
        got: list = []
        t = threading.Thread(target=lambda: got.append(b.consume()),
                             daemon=True)
        t.start()
        b.produce(7)
        t.join(timeout=10.0)
        assert got == [7]
        with pytest.raises(LockcheckError):
            b.unguarded()

    def test_deliberately_unguarded_write_is_caught(self, tmp_path):
        # The acceptance-criteria leg: a seeded write that skips the
        # lock is caught by the rewritten class at runtime.
        from tools.graftcheck.lockcheck import LockcheckError
        mod, _ = self._load(tmp_path, """
            import threading

            class Sched:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._shed = 0        # guarded-by: _mu

                def seeded_violation(self):
                    self._shed += 1       # missing `with self._mu:`
        """, name="guarded_seeded")
        s = mod.Sched()
        with pytest.raises(LockcheckError, match="Sched._shed"):
            s.seeded_violation()


# -- env-hygiene -------------------------------------------------------------

class TestEnvHygiene:
    DOCS = "flags.md"

    def _cfg(self, tmp_path, docs_text="| `SERVE_ADDR` | documented |\n"):
        (tmp_path / self.DOCS).write_text(docs_text)
        return dict(docs_files=(self.DOCS,))

    def test_raw_environ_read_flags(self, tmp_path):
        fs = check(tmp_path, """
            import os
            addr = os.environ.get("SERVE_ADDR", "")
        """, select=["env"], **self._cfg(tmp_path))
        assert "env-hygiene/raw-read" in rules(fs)

    def test_getenv_and_subscript_reads_flag(self, tmp_path):
        fs = check(tmp_path, """
            import os
            a = os.getenv("SERVE_ADDR")
            b = os.environ["SERVE_SLOTS"]
        """, select=["env"], **self._cfg(tmp_path))
        assert rules(fs).count("env-hygiene/raw-read") == 2

    def test_typed_helper_read_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.env import env_or
            addr = env_or("SERVE_ADDR", "127.0.0.1:11434")
        """, select=["env"], **self._cfg(tmp_path))
        assert fs == []

    def test_undocumented_flag_flags(self, tmp_path):
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.env import env_int
            n = env_int("SERVE_SECRET_KNOB", 0)
        """, select=["env"], **self._cfg(tmp_path))
        assert "env-hygiene/undocumented" in rules(fs)

    def test_documented_match_is_exact_token_not_substring(self, tmp_path):
        # `SERVE_MAX` must not ride on a documented `SERVE_MAX_SEQ`.
        fs = check(tmp_path, """
            from p2p_llm_chat_tpu.utils.env import env_int
            n = env_int("SERVE_MAX", 0)
        """, select=["env"],
                   **self._cfg(tmp_path, "| `SERVE_MAX_SEQ` | documented |\n"))
        assert "env-hygiene/undocumented" in rules(fs)

    def test_env_module_itself_may_read_environ(self, tmp_path):
        fs = check(tmp_path, """
            import os

            def env_or(key, default):
                v = os.environ.get(key, "")
                return v if v != "" else default

            x = os.environ.get("SERVE_ADDR", "")
        """, name="utils/env.py", select=["env"], **self._cfg(tmp_path))
        assert fs == []

    def test_non_prefixed_vars_ignored(self, tmp_path):
        fs = check(tmp_path, """
            import os
            home = os.environ.get("HOME", "/")
        """, select=["env"], **self._cfg(tmp_path))
        assert fs == []

    def test_no_paged_variable_under_package_or_tools(self):
        # Decode attention chooses from the window, the pool's geometry
        # and the platform (ops/paged_attention.py): no PAGED_* variable
        # is read, named or scrubbed for under the package or the tools.
        import pathlib
        import re
        hits = [f"{path}:{n}"
                for top in ("p2p_llm_chat_tpu", "tools")
                for path in pathlib.Path(REPO_ROOT, top).rglob("*.py")
                for n, line in enumerate(path.read_text().splitlines(), 1)
                if re.search(r"PAGED_[A-Z*]", line)]
        assert hits == []


# -- pytest-marker hygiene ---------------------------------------------------

class TestMarkers:
    INI = "fixture_pytest.ini"

    def _cfg(self, tmp_path):
        (tmp_path / self.INI).write_text(
            "[pytest]\nmarkers =\n    slow: registered marker\n")
        return dict(pytest_ini=self.INI)

    def test_unregistered_marker_flags(self, tmp_path):
        fs = check(tmp_path, """
            import pytest

            @pytest.mark.sloow
            def test_x():
                pass
        """, name="test_fixture.py", select=["markers"],
                   **self._cfg(tmp_path))
        assert "markers/unregistered" in rules(fs)

    def test_registered_and_builtin_markers_clean(self, tmp_path):
        fs = check(tmp_path, """
            import pytest

            @pytest.mark.slow
            @pytest.mark.parametrize("x", [1, 2])
            def test_x(x):
                pass
        """, name="test_fixture.py", select=["markers"],
                   **self._cfg(tmp_path))
        assert fs == []

    def test_non_test_files_ignored(self, tmp_path):
        fs = check(tmp_path, """
            import pytest
            mark = pytest.mark.sloow
        """, name="helper.py", select=["markers"], **self._cfg(tmp_path))
        assert fs == []

    def test_repo_markers_are_registered(self):
        # The real pytest.ini must cover every marker the suite uses —
        # `-m 'not slow'` on a typo would silently select everything.
        from tools.graftcheck.markers import registered_markers
        regs = registered_markers(f"{REPO_ROOT}/pytest.ini")
        assert {"slow", "model"} <= regs


# -- buffer-donation safety ---------------------------------------------------

class TestDonation:
    def test_use_after_donate_flags_the_read(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def _step(params, tokens, cache):
                return tokens, cache

            def run(params, toks, cache):
                step_j = jax.jit(_step, donate_argnums=(2,))
                out, new_cache = step_j(params, toks, cache)
                return cache.k.sum()        # donated: invalid now
        """, select=["donation"])
        assert rules(fs) == ["donation/use-after-donate"]

    def test_rebind_in_dispatch_statement_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def _step(params, tokens, cache):
                return tokens, cache

            def run(params, toks, cache):
                step_j = jax.jit(_step, donate_argnums=(2,))
                for _ in range(8):
                    toks, cache = step_j(params, toks, cache)
                return toks
        """, select=["donation"])
        assert fs == []

    def test_loop_dispatch_without_rebind_flags(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def _step(params, tokens, cache):
                return tokens

            def run(params, toks, cache):
                step_j = jax.jit(_step, donate_argnums=(2,))
                out = []
                for _ in range(8):
                    out.append(step_j(params, toks, cache))
                return out
        """, select=["donation"])
        assert rules(fs) == ["donation/use-after-donate"]

    def test_donate_index_out_of_range_flags(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def _f(a, b):
                return a

            f_j = jax.jit(_f, donate_argnums=(5,))
        """, select=["donation"])
        assert rules(fs) == ["donation/bad-index"]

    def test_unknown_donate_argname_flags(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def _f(a, b):
                return a

            f_j = jax.jit(_f, donate_argnames=("cache",))
        """, select=["donation"])
        assert rules(fs) == ["donation/bad-index"]

    def test_partial_decorator_form_validates_indices(self, tmp_path):
        fs = check(tmp_path, """
            import functools
            import jax

            @functools.partial(jax.jit, donate_argnums=(3,))
            def _f(a, b):
                return a
        """, select=["donation"])
        assert rules(fs) == ["donation/bad-index"]

    def test_nodonate_advisory_fires_only_in_hot_modules(self, tmp_path):
        src = """
            import jax

            def _step(params, tokens, cache):
                return tokens

            step_j = jax.jit(_step)
        """
        hot = check(tmp_path, src, name="serve/engine.py",
                    select=["donation"])
        assert rules(hot) == ["donation/no-donate"]
        cold = check(tmp_path, src, name="cold.py", select=["donation"])
        assert cold == []

    def test_suppressions_clear_both_tags(self, tmp_path):
        fs = check(tmp_path, """
            import jax

            def _step(params, tokens, cache):
                return tokens

            # graftcheck: nodonate prefill must keep its input pages
            step_j = jax.jit(_step)

            def run(params, toks, cache):
                out = step_j(params, toks, cache)
                return cache  # graftcheck: donated-ok cache is dense-only here
        """, name="serve/engine.py", select=["donation"])
        assert fs == []


# -- failpoint-site contract --------------------------------------------------

class TestFailpointContract:
    REGISTRY = """
        KNOWN_SITES = (
            "serve.api.parse",
            "serve.kv_tier.export",
        )
    """

    def _root(self, tmp_path, registry=None, test_src=None, docs=None):
        reg = tmp_path / "p2p_llm_chat_tpu" / "utils" / "failpoints.py"
        reg.parent.mkdir(parents=True, exist_ok=True)
        reg.write_text(textwrap.dedent(registry or self.REGISTRY))
        if test_src is not None:
            t = tmp_path / "tests" / "test_chaos.py"
            t.parent.mkdir(parents=True, exist_ok=True)
            t.write_text(textwrap.dedent(test_src))
        if docs is not None:
            d = tmp_path / "docs" / "robustness.md"
            d.parent.mkdir(parents=True, exist_ok=True)
            d.write_text(textwrap.dedent(docs))
        return reg

    def _run(self, tmp_path, paths):
        cfg = Config(root=str(tmp_path))
        return run_paths([str(p) for p in paths], cfg, ["failpoints"])

    def test_unarmed_site_flags_at_registry(self, tmp_path):
        reg = self._root(tmp_path, test_src="""
            from p2p_llm_chat_tpu.utils import failpoints
            def test_parse():
                failpoints.arm("serve.api.parse", "raise")
        """)
        fs = self._run(tmp_path, [reg])
        assert rules(fs) == ["failpoints/unarmed-site"]
        assert "serve.kv_tier.export" in fs[0].message

    def test_spec_literal_arms_a_site(self, tmp_path):
        reg = self._root(tmp_path, test_src="""
            def test_chaos(monkeypatch):
                monkeypatch.setenv(
                    "FAIL_POINTS",
                    "serve.api.parse=raise*1, serve.kv_tier.export=delay:20@0.5")
        """)
        assert self._run(tmp_path, [reg]) == []

    def test_unknown_site_typo_flags_in_the_test(self, tmp_path):
        reg = self._root(tmp_path, test_src="""
            from p2p_llm_chat_tpu.utils import failpoints
            def test_all():
                failpoints.arm("serve.api.parse", "raise")
                failpoints.arm("serve.kv_tier.export", "raise")
                failpoints.arm("serve.api.prase", "raise")   # typo
        """)
        t = tmp_path / "tests" / "test_chaos.py"
        fs = self._run(tmp_path, [reg, t])
        assert rules(fs) == ["failpoints/unknown-site"]
        assert fs[0].path.endswith("test_chaos.py")

    def test_scratch_prefix_sites_are_exempt(self, tmp_path):
        reg = self._root(tmp_path, test_src="""
            from p2p_llm_chat_tpu.utils import failpoints
            def test_all():
                failpoints.arm("serve.api.parse", "raise")
                failpoints.arm("serve.kv_tier.export", "raise")
                failpoints.arm("t.scratch", "raise")
        """)
        t = tmp_path / "tests" / "test_chaos.py"
        assert self._run(tmp_path, [reg, t]) == []

    def test_unregistered_call_flags(self, tmp_path):
        reg = self._root(tmp_path, test_src="""
            from p2p_llm_chat_tpu.utils import failpoints
            def test_all():
                failpoints.arm("serve.api.parse", "raise")
                failpoints.arm("serve.kv_tier.export", "raise")
        """)
        mod = tmp_path / "p2p_llm_chat_tpu" / "serve" / "thing.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(textwrap.dedent("""
            from ..utils.failpoints import failpoint
            def work():
                failpoint("serve.thing.unlisted")
        """))
        fs = self._run(tmp_path, [reg, mod])
        assert rules(fs) == ["failpoints/unregistered-call"]

    def test_docs_catalog_undocumented_and_orphan(self, tmp_path):
        reg = self._root(tmp_path, test_src="""
            from p2p_llm_chat_tpu.utils import failpoints
            def test_all():
                failpoints.arm("serve.api.parse", "raise")
                failpoints.arm("serve.kv_tier.export", "raise")
        """, docs="""
            # Robustness

            <!-- failpoint-contract:begin -->
            | `serve.api.parse` | parse | contract |
            | `serve.api.ghost` | gone | contract |
            <!-- failpoint-contract:end -->
        """)
        fs = self._run(tmp_path, [reg])
        assert sorted(rules(fs)) == ["failpoints/orphan-site",
                                     "failpoints/undocumented-site"]

    def test_partial_run_without_registry_is_clean(self, tmp_path):
        self._root(tmp_path)    # registry in the tree, NOT analyzed
        mod = tmp_path / "p2p_llm_chat_tpu" / "serve" / "thing.py"
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text("x = 1\n")
        assert self._run(tmp_path, [mod]) == []


# -- HTTP wire contract -------------------------------------------------------

class TestHttpContract:
    def test_503_without_retry_after_flags(self, tmp_path):
        fs = check(tmp_path, """
            from .utils.http import Response

            def shed(req):
                return Response(503, {"error": "full"})
        """, name="serve/api.py", select=["http"])
        assert rules(fs) == ["http/503-no-retry-after"]

    def test_503_with_retry_after_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from .utils.http import Response

            def shed(req):
                return Response(503, {"error": "full"},
                                headers={"Retry-After": "2"})
        """, name="serve/api.py", select=["http"])
        assert fs == []

    def test_http_rules_skip_non_front_modules(self, tmp_path):
        fs = check(tmp_path, """
            from .utils.http import Response

            def shed(req):
                return Response(503, {"error": "full"})
        """, name="p2p/relay.py", select=["http"])
        assert fs == []

    def test_ndjson_stream_without_done_flags(self, tmp_path):
        fs = check(tmp_path, """
            import json
            from .utils.http import Response

            def handle(req):
                def gen():
                    for d in ("a", "b"):
                        yield (json.dumps({"delta": d}) + "\\n").encode()
                return Response(200, stream=gen(),
                                content_type="application/x-ndjson")
        """, name="serve/api.py", select=["http"])
        assert rules(fs) == ["http/stream-no-done"]

    def test_ndjson_terminal_done_on_both_paths_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            import json
            from .utils.http import Response

            def handle(req):
                def gen():
                    try:
                        for d in ("a", "b"):
                            yield (json.dumps({"delta": d}) + "\\n").encode()
                        yield (json.dumps({"done": True}) + "\\n").encode()
                    except Exception as e:
                        yield (json.dumps({"error": str(e),
                                           "done": True}) + "\\n").encode()
                return Response(200, stream=gen(),
                                content_type="application/x-ndjson")
        """, name="serve/api.py", select=["http"])
        assert fs == []

    def test_yielding_except_without_done_flags(self, tmp_path):
        fs = check(tmp_path, """
            import json
            from .utils.http import Response

            def handle(req):
                def gen():
                    try:
                        yield b'{"delta": "a"}'
                    except Exception:
                        yield b'{"error": "x"}'
                    yield b'{"done": true}'
                return Response(200, stream=gen(),
                                content_type="application/x-ndjson")
        """, name="serve/api.py", select=["http"])
        assert rules(fs) == ["http/stream-no-done"]

    def test_proxy_dropping_headers_flags_both(self, tmp_path):
        fs = check(tmp_path, """
            from .utils.http import http_json, Response

            def proxy(req):
                status, body = http_json("GET", "http://up/x")
                return Response(status, body)
        """, name="ui.py", select=["http"])
        assert sorted(rules(fs)) == ["http/proxy-no-session",
                                     "http/proxy-no-trace"]

    def test_proxy_forwarding_via_helper_is_clean(self, tmp_path):
        fs = check(tmp_path, """
            from .utils.http import http_json, Response

            def _fwd(req):
                out = {}
                tid = req.headers.get("x-graft-trace")
                if tid:
                    out["X-Graft-Trace"] = tid
                sid = req.headers.get("x-session-id")
                if sid:
                    out["X-Session-Id"] = sid
                return out

            def proxy(req):
                status, body = http_json("GET", "http://up/x",
                                         headers=_fwd(req))
                return Response(status, body)
        """, name="ui.py", select=["http"])
        assert fs == []

    def test_proxy_suppression_covers_both_rules(self, tmp_path):
        fs = check(tmp_path, """
            from .utils.http import http_json, Response

            # graftcheck: http-ok scrape fan-out, no wire context to forward
            def metrics(req):
                status, body = http_json("GET", "http://rep/metrics")
                return Response(status, body)
        """, name="serve/router.py", select=["http"])
        assert fs == []

    def test_endpoint_catalog_mismatch_flags(self, tmp_path):
        d = tmp_path / "docs" / "serving.md"
        d.parent.mkdir(parents=True, exist_ok=True)
        d.write_text(textwrap.dedent("""
            <!-- endpoint-contract:begin -->
            | `GET /healthz` | api | liveness |
            | `GET /ghost` | api | never registered |
            <!-- endpoint-contract:end -->
        """))
        fs = check(tmp_path, """
            class Front:
                def __init__(self):
                    self.router.add("GET", "/healthz", self._health)
                    for ep in ("/api/new", "/api/new2"):
                        self.router.add("POST", ep, self._gen)
        """, name="serve/api.py", select=["http"])
        assert sorted(rules(fs)) == ["http/orphan-endpoint",
                                     "http/undocumented-endpoint",
                                     "http/undocumented-endpoint"]

    def test_new_analyzers_clean_on_single_repo_files(self):
        for rel, sel in (("p2p_llm_chat_tpu/ui.py", "http"),
                         ("p2p_llm_chat_tpu/utils/failpoints.py",
                          "failpoints"),
                         ("p2p_llm_chat_tpu/serve/multihost.py",
                          "donation")):
            cfg = Config(root=REPO_ROOT)
            fs = run_paths([f"{REPO_ROOT}/{rel}"], cfg, [sel])
            assert fs == [], (rel, rules(fs))


# -- CLI exit-status contract ------------------------------------------------

class TestCLI:
    def _write(self, tmp_path, source):
        p = tmp_path / "fixture.py"
        p.write_text(textwrap.dedent(source))
        return str(p)

    def test_clean_file_exits_zero(self, tmp_path, capsys):
        p = self._write(tmp_path, "x = 1\n")
        assert cli.main([p, "--root", str(tmp_path)]) == 0
        assert "0 findings" in capsys.readouterr().out

    def test_violation_exits_one(self, tmp_path, capsys):
        p = self._write(tmp_path, """
            import jax, numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x)
        """)
        assert cli.main([p, "--root", str(tmp_path)]) == 1
        assert "trace-safety/host-sync" in capsys.readouterr().out

    def test_unknown_analyzer_exits_two(self, tmp_path):
        p = self._write(tmp_path, "x = 1\n")
        assert cli.main([p, "--select", "bogus"]) == 2

    def test_nonexistent_path_exits_two(self, tmp_path):
        # A typo'd target must be a loud usage error — a silent 0-file
        # "clean" run would neuter the CI gate.
        assert cli.main([str(tmp_path / "no_such_dir")]) == 2

    def test_partial_run_on_single_repo_file_is_clean(self):
        # A dev linting just the file they edited must not false-fail
        # on cross-file contracts: scheduler.py's lock-order declaration
        # names KVTier (defined in kv_tier.py) and the docs metrics
        # catalog must resolve against the whole package tree, not the
        # one selected file.
        for rel in ("p2p_llm_chat_tpu/serve/scheduler.py",
                    "p2p_llm_chat_tpu/p2p/udp.py"):
            assert cli.main([f"{REPO_ROOT}/{rel}",
                             "--root", REPO_ROOT]) == 0

    def test_select_runs_only_requested_analyzer(self, tmp_path):
        p = self._write(tmp_path, """
            import os
            a = os.environ.get("SERVE_ADDR", "")
        """)
        assert cli.main([p, "--select", "lock",
                         "--root", str(tmp_path)]) == 0
        assert cli.main([p, "--select", "env",
                         "--root", str(tmp_path)]) == 1

    def test_shipped_tree_is_clean(self):
        # The acceptance bar: `python -m tools.graftcheck p2p_llm_chat_tpu/`
        # exits 0 on the shipped tree (same invocation ci.sh runs).
        proc = subprocess.run(
            [sys.executable, "-m", "tools.graftcheck",
             "p2p_llm_chat_tpu", "start_all.py", "tests"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
