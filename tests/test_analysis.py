"""The compiled-decode + host-control-plane invariant checker
(analysis/): rule fixtures (positive + negative + suppressed per rule,
the lock-discipline / resource-lifecycle / thread-reachability families
included), DERIVED thread-aware reachability on the real package (the
superset-of-the-old-pin-list regression), the CLI exit contract with
seeded-violation fixtures for each control-plane rule, and the
compiled-artifact (HLO) assertions for solo and pp decode.

Selectable standalone: `pytest -m analysis`.
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from distributed_llm_inference_tpu.analysis import hlo
from distributed_llm_inference_tpu.analysis.callgraph import (
    build_index, decode_unreachable, thread_roots, traced_reachable,
)
from distributed_llm_inference_tpu.analysis.lint import run_lint

pytestmark = pytest.mark.analysis

PKG_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "distributed_llm_inference_tpu",
)

def make_pkg(tmp_path, files: dict) -> str:
    """Write a throwaway package tree and return its root."""
    root = tmp_path / "fixture_pkg"
    for rel, body in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(body))
    return str(root)


def lint(tmp_path, files, rules=None):
    return run_lint(make_pkg(tmp_path, files), rules=rules)


def rules_hit(diagnostics):
    return sorted({d.rule for d in diagnostics})


# -- host-sync: reachability-scoped sync detection ---------------------------

HOST_SYNC_PKG = {
    "engine/generate.py": """
        import functools
        import jax
        import jax.numpy as jnp
        from ..ops.helpers import traced_helper

        @functools.partial(jax.jit, donate_argnames=("cache",))
        def decode(tokens, cache):
            return traced_helper(tokens), cache

        def host_only(x):
            return x.item()  # NOT reachable from a jit root: no finding
    """,
    "ops/helpers.py": """
        import jax.numpy as jnp

        def traced_helper(x):
            return jnp.sum(x)
    """,
}


def test_host_sync_negative(tmp_path):
    diags, _ = lint(tmp_path, HOST_SYNC_PKG, rules=["host-sync"])
    assert diags == []


def test_host_sync_positive_through_call_graph(tmp_path):
    files = dict(HOST_SYNC_PKG)
    files["ops/helpers.py"] = """
        import jax.numpy as jnp

        def traced_helper(x):
            n = x.item()
            return jnp.sum(x) + n
    """
    diags, _ = lint(tmp_path, files, rules=["host-sync"])
    assert len(diags) == 1
    d = diags[0]
    assert d.rule == "host-sync"
    assert d.path.endswith("ops/helpers.py")
    assert d.line == 5
    assert ".item()" in d.message


@pytest.mark.parametrize("snippet,expect", [
    ("jnp.sum(x)", 0),                       # clean
    ("x.tolist()", 1),                       # explicit fetch
    ("float(x)", 1),                         # concretization
    ("float(x.shape[0])", 0),                # shape metadata is host-known
    ("int(len(x.shape))", 0),                # len() is host-known
    ("np.asarray(x)", 1),                    # numpy forces host
    ("print(x)", 1),                         # host side effect
    ("time.time()", 1),                      # timestamps in the trace
    ("jax.device_get(x)", 1),                # device->host
    ("jax.debug.print('{}', x)", 1),         # lowers to a callback
])
def test_host_sync_catalog(tmp_path, snippet, expect):
    files = {
        "engine/mod.py": f"""
            import time
            import functools
            import jax
            import jax.numpy as jnp
            import numpy as np

            @functools.partial(jax.jit, donate_argnames=("cache",))
            def decode(x, cache):
                y = {snippet}
                return y, cache
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["host-sync"])
    assert len(diags) == expect, (snippet, diags)


def test_host_sync_suppressed_with_reason(tmp_path):
    files = {
        "engine/mod.py": """
            import jax

            @jax.jit
            def decode(x):
                n = x.item()  # jaxlint: disable=host-sync -- fixture: known-safe here
                return n
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["host-sync"])
    assert diags == []
    assert suppressed == 1


def test_suppression_without_reason_is_reported(tmp_path):
    files = {
        "engine/mod.py": """
            import jax

            @jax.jit
            def decode(x):
                n = x.item()  # jaxlint: disable=host-sync
                return n
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["host-sync"])
    assert suppressed == 0
    assert rules_hit(diags) == ["bad-suppression", "host-sync"]


def test_standalone_suppression_covers_next_line(tmp_path):
    files = {
        "engine/mod.py": """
            import jax

            @jax.jit
            def decode(x):
                # jaxlint: disable=host-sync -- fixture: next-line form
                n = x.item()
                return n
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["host-sync"])
    assert diags == []
    assert suppressed == 1


# -- tracer-branch -----------------------------------------------------------

def test_tracer_branch_positive_and_negative(tmp_path):
    files = {
        "ops/kernels.py": """
            import jax.numpy as jnp

            def bad(x):
                if jnp.any(x > 0):
                    return x
                return -x

            def good(x):
                if x.shape[0] > 1:
                    return x
                if x is None:
                    return None
                return -x
        """,
        "serving/host.py": """
            import jax.numpy as jnp

            def fine_here(x):
                # serving/ is host code: data-dependent branching is normal
                if jnp.any(x > 0):
                    return x
                return -x
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["tracer-branch"])
    assert len(diags) == 1
    assert diags[0].path.endswith("ops/kernels.py")
    assert diags[0].line == 5


def test_tracer_branch_while_and_reduction_method(tmp_path):
    files = {
        "parallel/ring.py": """
            def spin(x):
                while x.sum() > 0:
                    x = x - 1
                return x
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["tracer-branch"])
    assert len(diags) == 1
    assert "while" in diags[0].message


# -- donate-cache ------------------------------------------------------------

def test_donation_positive_negative_argnums(tmp_path):
    files = {
        "engine/mod.py": """
            import functools
            import jax

            @functools.partial(jax.jit, donate_argnames=("cache",))
            def good_names(tokens, cache):
                return tokens, cache

            @functools.partial(jax.jit, static_argnames=("n",))
            def bad(tokens, cache, *, n):
                return tokens, cache

            @jax.jit
            def no_cache_arg(tokens):
                return tokens

            def build():
                def body(shared, tokens, cache):
                    return tokens, cache
                shmapped = wrap(body)
                return jax.jit(shmapped, donate_argnums=(2,))

            def build_bad():
                def body(shared, tokens, cache):
                    return tokens, cache
                shmapped = wrap(body)
                return jax.jit(shmapped, donate_argnums=(1,))

            def wrap(f):
                return f
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["donate-cache"])
    assert len(diags) == 2
    assert {d.line for d in diags} == {10, 27}  # `bad` def, build_bad's jit


def test_donation_shared_pool_exception(tmp_path):
    """Block-level prefix sharing: a `shared_pool` param is a READ-ONLY
    mapped pool — the rule inverts: leaving it undonated is correct, and
    donating it (which would let XLA recycle buffers other block tables
    still read) is the flagged defect."""
    files = {
        "engine/mod.py": """
            import functools
            import jax

            @jax.jit
            def good_gather(shared_pool, table_row):
                return shared_pool

            @functools.partial(jax.jit, donate_argnames=("shared_pool",))
            def bad_gather(shared_pool, table_row):
                return shared_pool

            @jax.jit
            def still_bad_plain_pool(pool, table_row):
                return pool
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["donate-cache"])
    assert len(diags) == 2
    by_line = {d.line: d.message for d in diags}
    assert 10 in by_line and "must not be donated" in by_line[10]
    assert 14 in by_line and "does not donate" in by_line[14]


def test_donation_shared_pool_reasoned_suppression(tmp_path):
    """A donated shared_pool under a REASONED suppression is accepted;
    dropping the reason downgrades to the bad-suppression diagnostic —
    same contract as every other rule's escape hatch."""
    files = {
        "engine/mod.py": """
            import functools
            import jax

            @functools.partial(jax.jit, donate_argnames=("shared_pool",))
            # jaxlint: disable=donate-cache -- single-tenant pool: no other table maps these blocks
            def gather_private(shared_pool, table_row):
                return shared_pool
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["donate-cache"])
    assert diags == []
    assert suppressed == 1
    files_bad = {
        "engine/mod.py": files["engine/mod.py"].replace(
            " -- single-tenant pool: no other table maps these blocks", ""
        ),
    }
    diags, _ = lint(tmp_path, files_bad, rules=["donate-cache"])
    assert any(d.rule == "bad-suppression" for d in diags)


# -- static-args -------------------------------------------------------------

def test_static_args_fstring_call_site(tmp_path):
    files = {
        "engine/mod.py": """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("mode",))
            def run(x, *, mode):
                return x

            def bad_caller(x, name):
                return run(x, mode=f"m-{name}")

            def good_caller(x):
                return run(x, mode="fixed")
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["static-args"])
    assert len(diags) == 1
    assert diags[0].line == 10


def test_static_args_computed_names(tmp_path):
    files = {
        "engine/mod.py": """
            import functools
            import jax

            NAMES = ("mode",)

            @functools.partial(jax.jit, static_argnames=NAMES)
            def run(x, *, mode):
                return x
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["static-args"])
    assert len(diags) == 1
    assert "literal" in diags[0].message


# -- metrics-labels ----------------------------------------------------------

def test_metrics_labels_literal_and_cap(tmp_path):
    files = {
        "serving/mod.py": """
            def setup(registry, names):
                ok = registry.counter(
                    "dli_good_total", "fine", ("route", "status"),
                )
                computed = registry.counter(
                    "dli_computed_total", "bad", tuple(names),
                )
                wide = registry.gauge(
                    "dli_wide", "bad",
                    ("a", "b", "c", "d", "e"),
                )
                unlabeled = registry.counter("dli_plain_total", "fine")
                not_a_metric = registry.counter("requests", "no dli_ prefix")
                return ok, computed, wide, unlabeled, not_a_metric
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["metrics-labels"])
    assert len(diags) == 2
    msgs = " / ".join(d.message for d in diags)
    assert "dli_computed_total" in msgs and "dli_wide" in msgs


# -- route-counter -----------------------------------------------------------

def test_route_counter_rule(tmp_path):
    files = {
        "serving/srv.py": """
            class Handler:
                def _send(self, code):
                    self._count(code)
                    self.send_response(code)

                def good_stream(self):
                    self._count(200)
                    self.send_response(200)

                def bad_stream(self):
                    self.send_response(200)
        """,
        "engine/not_serving.py": """
            class Other:
                def whatever(self):
                    self.send_response(200)  # not serving/: out of scope
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["route-counter"])
    assert len(diags) == 1
    assert diags[0].line == 12
    assert "bad_stream" in diags[0].message


# -- thread-reach: thread-aware reachability (fixtures) ----------------------

THREAD_PKG = {
    "engine/mod.py": """
        import threading
        import jax
        import jax.numpy as jnp

        def worker():
            return jnp.sum(jnp.ones(3))

        def spawn():
            t = threading.Thread(target=worker, daemon=True)
            t.start()
            return t
    """,
}


def test_thread_reach_negative(tmp_path):
    diags, _ = lint(tmp_path, THREAD_PKG, rules=["thread-reach"])
    assert diags == []


def test_thread_reach_positive_traced_thread_target(tmp_path):
    files = dict(THREAD_PKG)
    files["engine/mod.py"] += """
        @jax.jit
        def decode(x):
            return worker() + x
    """
    diags, _ = lint(tmp_path, files, rules=["thread-reach"])
    assert len(diags) == 1
    assert "thread entry point" in diags[0].message
    assert "worker" in diags[0].message


def test_thread_reach_suppressed_with_reason(tmp_path):
    files = dict(THREAD_PKG)
    files["engine/mod.py"] = files["engine/mod.py"].replace(
        "t = threading.Thread(target=worker, daemon=True)",
        "t = threading.Thread(target=worker, daemon=True)"
        "  # jaxlint: disable=thread-reach -- fixture: eager-only helper",
    ) + """
        @jax.jit
        def decode(x):
            return worker() + x
    """
    diags, suppressed = lint(tmp_path, files, rules=["thread-reach"])
    assert diags == []
    assert suppressed == 1


def test_thread_reach_annotated_but_traced(tmp_path):
    files = {
        "engine/mod.py": """
            import jax
            import jax.numpy as jnp

            # jaxlint: decode-unreachable -- fixture: believed host-only
            def helper(x):
                return jnp.sum(x)

            @jax.jit
            def decode(x):
                return helper(x)
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["thread-reach"])
    assert len(diags) == 1
    assert "annotated decode-unreachable but IS reachable" in diags[0].message


def test_thread_reach_annotation_needs_reason(tmp_path):
    files = {
        "engine/mod.py": """
            # jaxlint: decode-unreachable
            def host_helper(x):
                return x
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["thread-reach"])
    assert len(diags) == 1
    assert "without a reason" in diags[0].message


def test_derived_reachability_on_fixture(tmp_path):
    """decode_unreachable() proves thread-spawned loops and their
    callees host-only, and keeps traced helpers out."""
    root = make_pkg(tmp_path, {
        "engine/mod.py": """
            import threading
            import time
            import jax
            import jax.numpy as jnp

            def hot(x):
                return jnp.sum(x)

            @jax.jit
            def decode(x):
                return hot(x)

            def loop_body():
                helper()

            def helper():
                time.sleep(0.01)

            def spawn():
                threading.Thread(target=loop_body, daemon=True).start()
        """,
    })
    index = build_index(root)
    derived = decode_unreachable(index)
    assert ("engine.mod", "loop_body") in derived
    assert ("engine.mod", "helper") in derived
    assert ("engine.mod", "hot") not in derived
    assert ("engine.mod", "decode") not in derived


# -- lock-order: acquisition-order inversions (fixtures) ---------------------

LOCK_ORDER_BAD = {
    "engine/locky.py": """
        import threading

        class A:
            def __init__(self):
                self.l1 = threading.Lock()
                self.l2 = threading.Lock()

            def forward(self):
                with self.l1:
                    with self.l2:
                        return 1

            def backward(self):
                with self.l2:
                    with self.l1:
                        return 2
    """,
}


def test_lock_order_inversion_flagged(tmp_path):
    diags, _ = lint(tmp_path, LOCK_ORDER_BAD, rules=["lock-order"])
    assert len(diags) == 2, diags  # both edges of the cycle
    assert all("inversion" in d.message for d in diags)
    assert {d.line for d in diags} == {11, 16}


def test_lock_order_consistent_is_clean(tmp_path):
    files = {
        "engine/locky.py": LOCK_ORDER_BAD["engine/locky.py"].replace(
            "with self.l2:\n                    with self.l1:",
            "with self.l1:\n                    with self.l2:",
        ),
    }
    diags, _ = lint(tmp_path, files, rules=["lock-order"])
    assert diags == []


def test_lock_order_inversion_through_a_call(tmp_path):
    """The deadlock shape that spans functions: forward holds l1 and
    CALLS a helper that takes l2; backward nests them the other way."""
    files = {
        "engine/locky.py": """
            import threading

            class A:
                def __init__(self):
                    self.l1 = threading.Lock()
                    self.l2 = threading.Lock()

                def forward(self):
                    with self.l1:
                        return self.helper()

                def helper(self):
                    with self.l2:
                        return 1

                def backward(self):
                    with self.l2:
                        with self.l1:
                            return 2
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["lock-order"])
    assert len(diags) == 2, diags
    assert {d.line for d in diags} == {11, 19}


def test_lock_order_suppressed_with_reason(tmp_path):
    files = {
        "engine/locky.py": LOCK_ORDER_BAD["engine/locky.py"]
        .replace(
            "with self.l2:\n                        return 1",
            "with self.l2:"
            "  # jaxlint: disable=lock-order -- fixture: A-then-B is canon\n"
            "                        return 1",
        )
        .replace(
            "with self.l1:\n                        return 2",
            "with self.l1:"
            "  # jaxlint: disable=lock-order -- fixture: migration window\n"
            "                        return 2",
        ),
    }
    diags, suppressed = lint(tmp_path, files, rules=["lock-order"])
    assert diags == []
    assert suppressed == 2


# -- blocking-under-lock (fixtures) ------------------------------------------

BLOCKING_PKG = {
    "serving/q.py": """
        import threading
        import time
        import urllib.request

        class Q:
            def __init__(self):
                self._cv = threading.Condition()

            def bad_sleep(self):
                with self._cv:
                    time.sleep(0.1)

            def ok_sleep_outside(self):
                time.sleep(0.1)
                with self._cv:
                    return 1

            def ok_wait_on_held(self):
                with self._cv:
                    self._cv.wait(timeout=0.1)

            def fetch(self):
                return urllib.request.urlopen("http://peer/ready")

            def bad_transitive(self):
                with self._cv:
                    return self.fetch()
    """,
}


def test_blocking_under_lock_catalog(tmp_path):
    diags, _ = lint(tmp_path, BLOCKING_PKG, rules=["blocking-under-lock"])
    assert len(diags) == 2, diags
    by_line = {d.line: d.message for d in diags}
    assert 12 in by_line and "time.sleep" in by_line[12]
    assert 28 in by_line and "fetch" in by_line[28]  # transitive call


def test_blocking_under_lock_queue_put_and_join(tmp_path):
    files = {
        "serving/q.py": """
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = None
                    self._t = None

                def bad_put(self, x):
                    with self._lock:
                        self._q.put(x, block=True)

                def ok_put_nowait(self, x):
                    with self._lock:
                        self._q.put_nowait(x)

                def bad_join(self):
                    with self._lock:
                        self._t.join()
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["blocking-under-lock"])
    assert len(diags) == 2, diags
    msgs = " / ".join(d.message for d in diags)
    assert "block=True" in msgs and ".join()" in msgs


def test_blocking_under_lock_suppressed(tmp_path):
    files = {
        "serving/q.py": BLOCKING_PKG["serving/q.py"].replace(
            "time.sleep(0.1)\n\n            def ok_sleep_outside",
            "time.sleep(0.1)"
            "  # jaxlint: disable=blocking-under-lock -- fixture: test-only pacing\n"
            "\n            def ok_sleep_outside",
        ).replace(
            "return self.fetch()",
            "return self.fetch()"
            "  # jaxlint: disable=blocking-under-lock -- fixture: startup path, single-threaded",
        ),
    }
    diags, suppressed = lint(tmp_path, files, rules=["blocking-under-lock"])
    assert diags == []
    assert suppressed == 2


# -- guarded-by (fixtures) ---------------------------------------------------

GUARDED_PKG = {
    "engine/state.py": """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0  # guarded-by: _lock

            def good(self):
                with self._lock:
                    self.depth = 1

            def bad(self):
                self.depth = 2

            # guarded-by: _lock
            def _bump_locked(self):
                self.depth += 1

            def caller_bad(self):
                self._bump_locked()

            def caller_good(self):
                with self._lock:
                    self._bump_locked()
    """,
}


def test_guarded_by_write_and_call_violations(tmp_path):
    diags, _ = lint(tmp_path, GUARDED_PKG, rules=["guarded-by"])
    assert len(diags) == 2, diags
    by_line = {d.line: d.message for d in diags}
    assert 14 in by_line and "outside its declared lock" in by_line[14]
    assert 21 in by_line and "without holding" in by_line[21]


def test_guarded_by_init_exempt_and_subscript_write(tmp_path):
    files = {
        "engine/state.py": """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.table = {}  # guarded-by: _lock
                    self.table = {"seed": 1}  # __init__ is pre-sharing

                def good(self, k, v):
                    with self._lock:
                        self.table[k] = v

                def bad(self, k, v):
                    self.table[k] = v
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["guarded-by"])
    assert len(diags) == 1
    assert diags[0].line == 15


def test_guarded_by_suppressed_with_reason(tmp_path):
    files = {
        "engine/state.py": GUARDED_PKG["engine/state.py"].replace(
            "self.depth = 2",
            "self.depth = 2"
            "  # jaxlint: disable=guarded-by -- fixture: single-threaded setup phase",
        ).replace(
            "def caller_bad(self):\n                self._bump_locked()",
            "def caller_bad(self):\n                self._bump_locked()"
            "  # jaxlint: disable=guarded-by -- fixture: lock held by caller's caller",
        ),
    }
    diags, suppressed = lint(tmp_path, files, rules=["guarded-by"])
    assert diags == []
    assert suppressed == 2


# -- resource-lifecycle (fixtures) -------------------------------------------

PR4_LEAK_PKG = {
    "engine/admission.py": """
        _BLOCKED = object()

        class Admission:
            def __init__(self, alloc, ctable):
                self._alloc = alloc
                self._ctable = ctable

            def admit(self, req):
                blocks = self._alloc.alloc(req.need)
                if blocks is None:
                    return _BLOCKED
                off = self._ctable.acquire(req.cart)
                if off is None:
                    return _BLOCKED
                req.block_ids = blocks
                req.cart = (req.cart, off)
                return req
    """,
}


def test_lifecycle_catches_pr4_blocked_leak(tmp_path):
    """The exact PR-4 shape: blocks granted, a LATER acquisition
    backpressures, and the retry sentinel returns without decref'ing
    what is already held."""
    diags, _ = lint(tmp_path, PR4_LEAK_PKG, rules=["resource-lifecycle"])
    assert len(diags) == 1, diags
    assert diags[0].line == 15
    assert "blocks" in diags[0].message and "alloc" in diags[0].message


def test_lifecycle_release_on_every_path_is_clean(tmp_path):
    files = {
        "engine/admission.py": PR4_LEAK_PKG["engine/admission.py"].replace(
            "if off is None:\n                    return _BLOCKED",
            "if off is None:\n"
            "                    self._alloc.decref(blocks)\n"
            "                    return _BLOCKED",
        ),
    }
    diags, _ = lint(tmp_path, files, rules=["resource-lifecycle"])
    assert diags == []


def test_lifecycle_incref_and_finally_and_transfer(tmp_path):
    files = {
        "engine/admission.py": """
            class A:
                def leak_incref(self, shared, cond):
                    self._alloc.incref(shared)
                    if cond:
                        return None
                    self._alloc.decref(shared)
                    return 1

                def ok_finally(self, req):
                    blocks = self._alloc.alloc(req.need)
                    if blocks is None:
                        return None
                    try:
                        if req.bad:
                            return None
                        return blocks
                    finally:
                        self._alloc.decref(blocks)

                def ok_transfer(self, req):
                    blocks = self._alloc.alloc(req.need)
                    if blocks is None:
                        return None
                    req.block_ids = blocks
                    if req.fast:
                        return req
                    return req
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["resource-lifecycle"])
    assert len(diags) == 1, diags
    assert diags[0].line == 6
    assert "shared" in diags[0].message


def test_lifecycle_ownership_transfer_suppression(tmp_path):
    files = {
        "engine/admission.py": """
            class A:
                def handoff(self, pool):
                    blocks = pool.alloc(4)
                    if blocks is None:
                        return None
                    self.enqueue(blocks)
                    return True  # jaxlint: disable=resource-lifecycle -- ownership moved to the enqueue consumer
        """,
    }
    diags, suppressed = lint(
        tmp_path, files, rules=["resource-lifecycle"]
    )
    assert diags == []
    assert suppressed == 1


# -- join-hygiene (fixtures) -------------------------------------------------

def test_join_hygiene_non_daemon_without_join(tmp_path):
    files = {
        "serving/w.py": """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    pass
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["join-hygiene"])
    assert len(diags) == 1
    assert "no join(timeout=...)" in diags[0].message


def test_join_hygiene_bounded_join_or_daemon_is_clean(tmp_path):
    files = {
        "serving/w.py": """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
                    self._d = threading.Thread(target=self._run, daemon=True)
                    self._d.start()

                def close(self):
                    self._t.join(timeout=5)

                def _run(self):
                    pass
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["join-hygiene"])
    assert diags == []


def test_join_hygiene_unbounded_join_flagged(tmp_path):
    """The PR-9 follower-wedge shape: the drain path joins without a
    timeout, so one wedged thread holds shutdown hostage."""
    files = {
        "serving/w.py": """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def close(self):
                    self._t.join()

                def _run(self):
                    pass
        """,
    }
    diags, _ = lint(tmp_path, files, rules=["join-hygiene"])
    assert len(diags) == 2, diags
    msgs = " / ".join(d.message for d in diags)
    assert "UNBOUNDED" in msgs and "unbounded .join()" in msgs


def test_join_hygiene_suppressed(tmp_path):
    files = {
        "serving/w.py": """
            import threading

            class W:
                def start(self):
                    # jaxlint: disable=join-hygiene -- fixture: process-lifetime thread, reaped by exit
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    pass
        """,
    }
    diags, suppressed = lint(tmp_path, files, rules=["join-hygiene"])
    assert diags == []
    assert suppressed == 1


# -- call-graph units on the REAL package ------------------------------------

@pytest.fixture(scope="module")
def real_reachable():
    index = build_index(PKG_ROOT)
    return traced_reachable(index)


def test_real_traced_set_includes_hot_path(real_reachable):
    for key in [
        ("engine.generate", "decode"),
        ("engine.generate", "stop_mask"),
        ("engine.generate", "slot_step"),
        ("ops.sampling", "sample_token"),
        ("ops.sampling", "_sample_warped"),
        ("models.api", "forward_layers"),
        ("models.llama", "forward_layers"),
        ("models.gpt2", "forward_layers"),  # family-dispatch fan-out
        ("ops.attention", "attend"),
        ("engine.paged", "make_paged_hook.hook"),  # nested closure
    ]:
        assert key in real_reachable, key


def test_real_traced_set_excludes_host_code(real_reachable):
    for key in [
        ("engine.generate", "pick_bucket"),  # host-side bucket picker
        ("engine.engine", "InferenceEngine.generate"),
        ("serving.server", "main"),
        ("utils.metrics", "MetricsRegistry.render"),
    ]:
        assert key not in real_reachable, key


# -- DERIVED thread-aware reachability (replaces the per-PR manual pin
# fixtures that grew here in PRs 5-11) --------------------------------------

@pytest.fixture(scope="module")
def real_index():
    return build_index(PKG_ROOT)


@pytest.fixture(scope="module")
def real_derived(real_index, real_reachable):
    return decode_unreachable(real_index, real_reachable)


# What this file used to assert by hand, pin by pin, PR by PR. Whole
# modules are enumerated at test time (so functions ADDED to a pinned
# module stay covered); the explicit keys are the exact pins the old
# fixtures carried. The derivation (host roots -> closure, minus the
# traced set, plus the annotated escape hatch) must prove ALL of it.
OLD_PIN_MODULES = (
    "utils.faults", "engine.shadow", "engine.scheduler",
    "serving.router", "utils.retry", "serving.kv_fabric",
)
OLD_PIN_FUNCS = [
    ("engine.continuous", "ContinuousEngine._launch_chunk"),
    ("engine.continuous", "ContinuousEngine._process"),
    ("engine.continuous", "ContinuousEngine._admit_one"),
    ("engine.continuous", "ContinuousEngine._supervise"),
    ("engine.continuous", "ContinuousEngine._run_recovery"),
    ("engine.engine", "InferenceEngine._generate_locked"),
    ("engine.continuous", "ContinuousEngine._shadow_capture"),
    ("engine.continuous", "ContinuousEngine._restore_shadow"),
    ("engine.continuous", "ContinuousEngine._preempt_for"),
    ("engine.continuous", "ContinuousEngine._victim_for"),
    ("engine.continuous", "ContinuousEngine._alloc_with_pressure"),
    ("engine.continuous", "ContinuousEngine._prepare_resume"),
    ("engine.continuous", "ContinuousEngine._cancel_env"),
    ("engine.continuous", "ContinuousEngine._deadline_env"),
    ("engine.continuous", "ContinuousEngine._past_deadline"),
    ("engine.scheduler", "TokenBudgetScheduler.select_victim"),
    ("engine.scheduler", "TokenBudgetScheduler.victim_key"),
    ("engine.paged", "build_ragged_meta"),
    ("engine.continuous", "ContinuousEngine._ragged_ingest"),
    ("engine.continuous", "ContinuousEngine._ragged_launch_args"),
    ("engine.continuous", "ContinuousEngine._launch_mixed"),
    ("engine.continuous", "ContinuousEngine._process_mixed"),
    ("engine.continuous", "ContinuousEngine._start_job"),
    ("engine.continuous", "ContinuousEngine._sched_loop"),
    ("engine.continuous", "ContinuousEngine._fabric_prefetch"),
    ("engine.continuous", "ContinuousEngine._import_fabric_chain"),
    ("engine.continuous", "ContinuousEngine.fabric_chain"),
    ("engine.continuous", "ContinuousEngine.fabric_digests"),
]


def test_derived_reachability_supersets_old_pins(real_index, real_derived):
    """The thread-aware derivation proves (at least) everything the old
    manual pin list asserted — the acceptance criterion that let the
    pins be deleted. A miss here means a host root went undetected
    (new spawn idiom?) or a helper lost its last host-side caller:
    either derive it or annotate it `# jaxlint: decode-unreachable`."""
    missing = [k for k in OLD_PIN_FUNCS if k not in real_derived]
    assert not missing, missing
    for mod_name in OLD_PIN_MODULES:
        funcs = [
            f.key for f in real_index.modules[mod_name].functions.values()
        ]
        missing = [k for k in funcs if k not in real_derived]
        assert not missing, (mod_name, missing)


def test_derived_set_disjoint_from_traced(real_derived, real_reachable):
    """Soundness: nothing the derivation (or an annotation) calls
    host-only may be reachable from a jit root. The thread-reach rule
    enforces the annotated half in CI; this is the belt to that
    suspender, over the whole derived set."""
    overlap = sorted(real_derived & real_reachable)
    assert not overlap, overlap


def test_thread_roots_cover_the_control_plane_loops(real_index):
    """The spawn-edge detector sees every long-lived control-plane
    thread this repo starts — supervisor loop, shadow copier, queue
    dispatcher, router prober, deadline-abandonment runner."""
    roots = thread_roots(real_index)
    for key in [
        ("engine.continuous", "ContinuousEngine._loop"),
        ("engine.shadow", "ShadowStore._copier"),
        ("serving.queue", "BatchingQueue._dispatch_loop"),
        ("serving.router", "Router.start_prober._loop"),
        ("engine.engine", "InferenceEngine._with_deadline.run"),
        ("serving.multihost", "MirroredEngine.shutdown_followers._bcast"),
    ]:
        assert key in roots, key


def test_traced_halves_stay_reachable(real_reachable):
    """The derivation must not swallow the TRACED halves of the paged
    path: the ragged fill closure and the mixed epilogue execute inside
    compiled programs, and the host-sync rule audits them only while
    they stay in the traced set."""
    assert ("engine.paged", "make_ragged_fill_hook.hook") in real_reachable
    assert ("engine.paged", "mixed_epilogue") in real_reachable


def test_repo_is_clean():
    """The package itself lints clean — the same gate CI runs."""
    diags, _ = run_lint(PKG_ROOT)
    assert diags == [], "\n".join(d.format() for d in diags)


# -- CLI exit contract (acceptance criterion) --------------------------------

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "distributed_llm_inference_tpu.analysis",
         *args],
        capture_output=True, text=True,
        cwd=os.path.dirname(PKG_ROOT),
    )


def test_cli_clean_repo_exits_zero():
    r = _run_cli()
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_item_in_decode_reachable_function_exits_nonzero(tmp_path):
    """A `.item()` injected into a decode-reachable function must fail the
    CLI with a file:line diagnostic."""
    import shutil

    bad_root = str(tmp_path / "pkg_with_item")
    shutil.copytree(PKG_ROOT, bad_root, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc"
    ))
    gen = os.path.join(bad_root, "engine", "generate.py")
    with open(gen) as fh:
        src = fh.read()
    needle = "    m = tokens == jnp.int32(cfg.eos_token_id)"
    assert needle in src
    with open(gen, "w") as fh:
        fh.write(src.replace(
            needle, "    _bad = tokens.item()\n" + needle
        ))
    r = _run_cli("--root", bad_root)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "host-sync" in r.stdout
    # file:line diagnostics
    assert "generate.py:" in r.stdout and ".item()" in r.stdout


_SEEDED_VIOLATIONS = {
    "lock-order": """
        import threading

        class A:
            def __init__(self):
                self.l1 = threading.Lock()
                self.l2 = threading.Lock()

            def forward(self):
                with self.l1:
                    with self.l2:
                        return 1

            def backward(self):
                with self.l2:
                    with self.l1:
                        return 2
    """,
    "blocking-under-lock": """
        import threading
        import time

        class Q:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    time.sleep(0.5)
    """,
    "guarded-by": """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.depth = 0  # guarded-by: _lock

            def bump(self):
                self.depth += 1
    """,
    "resource-lifecycle": """
        _BLOCKED = object()

        class Admission:
            def admit(self, req):
                blocks = self._alloc.alloc(req.need)
                if blocks is None:
                    return _BLOCKED
                off = self._ctable.acquire(req.cart)
                if off is None:
                    return _BLOCKED
                req.block_ids = blocks
                req.cart = (req.cart, off)
                return req
    """,
}


@pytest.mark.parametrize("rule", sorted(_SEEDED_VIOLATIONS))
def test_cli_seeded_violation_fixtures_exit_nonzero(tmp_path, rule):
    """The acceptance contract for the host-control-plane rules: a
    seeded violation of each family (lock inversion, blocking call
    under a lock, guarded-by write, the PR-4 refcount leak) fails the
    CLI with a file:line diagnostic naming the rule."""
    root = make_pkg(tmp_path, {
        "engine/seeded.py": _SEEDED_VIOLATIONS[rule],
    })
    r = _run_cli("--root", root)
    assert r.returncode == 1, r.stdout + r.stderr
    assert rule in r.stdout
    assert "seeded.py:" in r.stdout


# -- compiled-artifact (HLO) assertions --------------------------------------

@pytest.fixture(scope="module")
def engine():
    return hlo.tiny_engine()


def test_solo_decode_artifact(engine):
    text = hlo.lower_solo_decode(engine)
    assert hlo.check_no_host_callbacks(text) == []
    assert hlo.check_while_compiled(text) == []
    cache = engine.backend.init_cache(1, engine.cfg.max_seq_len)
    n_leaves = hlo.count_cache_leaves(cache)
    assert hlo.check_donation(text, min_aliased=n_leaves) == []


def test_constrained_decode_artifact(engine):
    text = hlo.lower_solo_decode(engine, constrained=True)
    assert hlo.check_no_host_callbacks(text) == []
    assert hlo.check_while_compiled(text) == []


def test_donation_checker_catches_dropped_donation(engine):
    """check_donation must FAIL on a re-wrap that drops donate_argnames —
    the exact silent regression it exists to catch."""
    import jax as _jax
    import jax.numpy as jnp

    from distributed_llm_inference_tpu.engine import generate as G

    cfg = engine.cfg
    cache = engine.backend.init_cache(1, cfg.max_seq_len)
    undonated = _jax.jit(
        G.decode, static_argnames=("cfg", "max_steps"),
    ).lower(
        cfg, engine.backend.params, jnp.zeros((1,), jnp.int32), cache,
        jnp.int32(4), jnp.int32(8), _jax.random.PRNGKey(0),
        G.default_sampling(greedy=True), None, None, None, None, None,
        max_steps=16,
    ).as_text()
    assert hlo.check_donation(undonated, min_aliased=1) != []


def test_callback_checker_catches_injected_callback(engine):
    """check_no_host_callbacks must FAIL on a program that really does
    call back into Python per step."""
    import jax as _jax
    import jax.numpy as jnp

    def with_callback(x):
        _jax.debug.print("step {}", x)
        return x * 2

    text = _jax.jit(with_callback).lower(jnp.ones((4,))).as_text()
    assert hlo.check_no_host_callbacks(text) != []


def test_recompile_guard(engine):
    assert hlo.check_no_recompile(engine) == []


def test_run_hlo_checks_all_green():
    results = hlo.run_hlo_checks()
    bad = {k: v for k, v in results.items() if v}
    assert not bad, bad


def test_pp_decode_artifact(eight_devices):
    if not hlo.pp_available():
        pytest.skip("pp HLO check needs >= 2 devices")
    text = hlo.lower_pp_decode()
    assert hlo.check_no_host_callbacks(text) == []
    assert hlo.check_pp_ring(text) == []
