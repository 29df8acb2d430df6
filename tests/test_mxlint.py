"""mxlint (ISSUE 4): the TPU-invariant static analyzer.

Three layers, bottom-up:

  * fixture snippets per rule — positive hit (right rule id, right
    line), suppressed hit (`# mxlint: disable=`), baselined hit, clean
    code — all through ``lint_source`` with no filesystem;
  * the CLI contract (`python -m tools.mxlint`): exit 0 clean / 1 new
    violations / 2 usage error, ``--format json``, ``--write-baseline``
    round-trip, plus ``tools/gen_env_docs.py --check`` consistency;
  * the tier-1 gate: the SHIPPED tree lints clean against the checked-in
    baseline, and intentionally reintroducing the historical violations
    (an ``asnumpy()`` in ``Trainer._update``, a raw ``time.time()`` in
    the kvstore connect-retry loop) trips the right rule id — the
    acceptance criteria of the issue, verbatim.

Pure stdlib + pytest: no jax import, so this file costs milliseconds.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.mxlint import (lint_source, lint_sources, lint_paths,    # noqa: E402
                          load_baseline, load_baseline_whys,
                          write_baseline, collect_env_reads, RULES)
from tools.mxlint.core import apply_baseline                        # noqa: E402

BASELINE = os.path.join(REPO, "tools", "mxlint", "baseline.json")
RUNTIME_PATHS = [os.path.join(REPO, "mxnet_tpu"),
                 os.path.join(REPO, "tools", "launch.py")]


def rules_of(diags):
    return [d.rule for d in diags]


def src(text):
    return textwrap.dedent(text).lstrip("\n")


# ---------------------------------------------------------------------------
# host-sync-in-hot-path
# ---------------------------------------------------------------------------

HOT_PATH = "mxnet_tpu/gluon/trainer.py"

def test_host_sync_positive_direct_and_via_helper():
    code = src("""
    class Trainer:
        def step(self, batch_size):
            self._update()

        def _update(self):
            for p in self.params:
                self._drain(p)

        def _drain(self, p):
            return float(p.grad.asnumpy()[0])
    """)
    diags = lint_source(code, HOT_PATH)
    assert rules_of(diags) == ["host-sync-in-hot-path"]
    assert diags[0].line == 10
    # message names the reachable root, not just the containing helper
    assert "Trainer" in diags[0].message and "_drain" in diags[0].message


def test_host_sync_suppressed():
    code = src("""
    class Trainer:
        def _update(self):
            return self.g.asnumpy()  # mxlint: disable=host-sync-in-hot-path
    """)
    assert lint_source(code, HOT_PATH) == []


def test_host_sync_clean_and_out_of_hot_path():
    clean = src("""
    class Trainer:
        def _update(self):
            self.w = self.w - self.lr * self.g

    def offline_report(arrs):
        return [a.asnumpy() for a in arrs]
    """)
    assert lint_source(clean, HOT_PATH) == []
    # same sync outside any hot-path file: no rule applies
    sync = "def f(a):\n    return a.asnumpy()\n"
    assert lint_source(sync, "mxnet_tpu/visualization.py") == []


def test_host_sync_metric_update_root():
    code = src("""
    class Accuracy:
        def update(self, labels, preds):
            import numpy as np
            self.sum_metric += float(np.asarray(preds).sum())
    """)
    diags = lint_source(code, "mxnet_tpu/metric.py")
    assert rules_of(diags) == ["host-sync-in-hot-path"]


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------

def test_jit_purity_decorated():
    code = src("""
    import time
    import jax

    @jax.jit
    def kernel(x):
        print("tracing")
        t = time.time()
        if x > 0:
            return x
        return -x
    """)
    diags = lint_source(code, "mxnet_tpu/ops/extra.py")
    kinds = rules_of(diags)
    assert kinds == ["jit-purity"] * 3
    msgs = " | ".join(d.message for d in diags)
    assert "print()" in msgs and "wall-clock" in msgs and \
        "data-dependent" in msgs


def test_jit_purity_static_args_and_shape_branches_ok():
    code = src("""
    import jax
    from functools import partial

    @partial(jax.jit, static_argnames=("mode",))
    def kernel(x, mode, axis=0):
        if mode == "fast":      # static_argnames: fine
            return x
        if axis:                # defaulted param: static by contract
            return x.sum(axis)
        if x.ndim > 2:          # shape attr: static under trace
            return x.reshape(-1)
        if x is None:           # sentinel: fine
            return x
        return x
    """)
    assert lint_source(code, "mxnet_tpu/ops/extra.py") == []


def test_jit_purity_registered_op_and_env_read():
    code = src("""
    import os
    from .registry import register

    @register("myop")
    def _k(x):
        if os.environ.get("MX_DEBUG_FLAG"):
            return x
        return x + 1

    @register("dynop", no_jit=True)
    def _d(x):
        print(x)   # eager op: prints are legal
        return x
    """)
    diags = lint_source(code, "mxnet_tpu/ops/extra.py",
                        catalog={"MX_DEBUG_FLAG"})
    # the same read trips BOTH rules: ad-hoc env read (env-var-registry)
    # and trace-time env read (jit-purity)
    assert sorted(set(rules_of(diags))) == ["env-var-registry", "jit-purity"]
    jp = [d for d in diags if d.rule == "jit-purity"]
    assert "os.environ" in jp[0].message


def test_jit_purity_by_name_jit_call():
    code = src("""
    import jax
    import random

    def make(fn):
        def step(x):
            return x * random.random()
        return jax.jit(step)
    """)
    diags = lint_source(code, "mxnet_tpu/parallel/foo.py")
    assert rules_of(diags) == ["jit-purity"]
    assert "RNG" in diags[0].message


# ---------------------------------------------------------------------------
# wall-clock-in-fault-path
# ---------------------------------------------------------------------------

def test_wall_clock_positive_alias_and_from_import():
    code = src("""
    import time as _time
    from time import monotonic

    def retry_loop():
        deadline = _time.time() + 60
        while monotonic() < deadline:
            _time.sleep(0.2)
    """)
    diags = lint_source(code, "mxnet_tpu/kvstore/kvstore.py")
    assert rules_of(diags) == ["wall-clock-in-fault-path"] * 3
    assert "fault.now()" in diags[0].message
    assert "fault.sleep()" in diags[-1].message


def test_wall_clock_suppressed_and_clean_and_scoped():
    sup = src("""
    import time as _time

    class _RealClock:
        now = staticmethod(_time.monotonic)  # mxlint: disable=wall-clock-in-fault-path
    """)
    assert lint_source(sup, "mxnet_tpu/fault.py") == []
    clean = src("""
    from .. import fault as _fault

    def retry_loop():
        deadline = _fault.now() + 60
        _fault.sleep(0.2)
    """)
    assert lint_source(clean, "mxnet_tpu/kvstore/kvstore.py") == []
    # time.time is legal outside the fault-path files
    other = "import time\ndef f():\n    return time.time()\n"
    assert lint_source(other, "mxnet_tpu/callback.py") == []


# ---------------------------------------------------------------------------
# env-var-registry
# ---------------------------------------------------------------------------

def test_env_registry_adhoc_read_flagged():
    code = src("""
    import os

    def f():
        a = os.environ.get("MX_SOME_FLAG")
        b = os.getenv("MX_OTHER")
        c = os.environ["MX_THIRD"]
        return a, b, c
    """)
    diags = lint_source(code, "mxnet_tpu/foo.py",
                        catalog={"MX_SOME_FLAG", "MX_OTHER", "MX_THIRD"})
    assert rules_of(diags) == ["env-var-registry"] * 3
    assert all("get_env" in d.message for d in diags)


def test_env_registry_submodule_import_does_not_blind():
    # `import os.path` binds the name `os`; the alias map must not remap
    # it to "os.path" or every os.environ detector goes blind
    code = src("""
    import os.path

    def f():
        return os.environ.get("MX_SOME_FLAG")
    """)
    diags = lint_source(code, "mxnet_tpu/foo.py", catalog={"MX_SOME_FLAG"})
    assert rules_of(diags) == ["env-var-registry"]


def test_env_registry_unregistered_and_clean_and_writes_ok():
    code = src("""
    from .base import get_env

    def f():
        return get_env("MX_NOT_IN_CATALOG")
    """)
    diags = lint_source(code, "mxnet_tpu/foo.py", catalog={"MX_KNOWN"})
    assert rules_of(diags) == ["env-var-registry"]
    assert "ENV_CATALOG" in diags[0].message
    clean = src("""
    import os
    from .base import get_env

    def f():
        os.environ["MX_FORCE_CPU"] = "1"   # writes are fine
        return get_env("MX_KNOWN"), os.environ.get("PATH")
    """)
    assert lint_source(clean, "mxnet_tpu/foo.py", catalog={"MX_KNOWN",
                                                           "MX_FORCE_CPU"}) \
        == []
    # base.py itself is the accessor: exempt
    accessor = 'import os\nv = os.environ.get("MX_FORCE_CPU")\n'
    assert lint_source(accessor, "mxnet_tpu/base.py") == []


# ---------------------------------------------------------------------------
# donation-after-use
# ---------------------------------------------------------------------------

def test_donation_after_use_positive():
    code = src("""
    import jax

    def f(g, a, b):
        fn = jax.jit(g, donate_argnums=(0,))
        out = fn(a, b)
        return a + out
    """)
    diags = lint_source(code, "mxnet_tpu/parallel/foo.py")
    assert rules_of(diags) == ["donation-after-use"]
    assert "'a'" in diags[0].message


def test_donation_after_use_rebind_and_nondonated_ok():
    code = src("""
    import jax

    def f(g, a, b):
        fn = jax.jit(g, donate_argnums=(0,))
        a = fn(a, b)      # rebound: old buffer unreachable
        return a + b      # b was not donated
    """)
    assert lint_source(code, "mxnet_tpu/parallel/foo.py") == []


def test_donation_after_use_self_attr_and_conditional_donate():
    code = src("""
    import jax

    class Step:
        def __init__(self, fn, donate):
            self._step = jax.jit(fn, donate_argnums=(0, 1) if donate else ())

        def run(self, params, opt, batch):
            new_p, new_o = self._step(params, opt, batch)
            self.stale = params.copy()
            return new_p, new_o
    """)
    diags = lint_source(code, "mxnet_tpu/parallel/foo.py")
    assert rules_of(diags) == ["donation-after-use"]
    assert "'params'" in diags[0].message


# ---------------------------------------------------------------------------
# concurrency rules (ISSUE 6): whole-program pass fixtures
# ---------------------------------------------------------------------------

CONC = "mxnet_tpu/foo.py"

SHARED_HIT = src("""
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def bump(self):
        self._n += 1

    def _run(self):
        while True:
            x = self._n
""")


def test_unguarded_shared_write_hit():
    diags = lint_source(SHARED_HIT, CONC)
    assert rules_of(diags) == ["unguarded-shared-write"]
    d = diags[0]
    assert d.line == 11 and "Pump._n" in d.message
    # both thread roots named, and the peer read site carried separately
    assert "thread:Pump._run" in d.threads and "main" in d.threads
    assert d.peer == "mxnet_tpu/foo.py:15"


def test_unguarded_shared_write_suppressed_baselined_clean(tmp_path):
    sup = SHARED_HIT.replace(
        "self._n += 1",
        "self._n += 1  # mxlint: disable=unguarded-shared-write")
    assert lint_source(sup, CONC) == []
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), lint_source(SHARED_HIT, CONC))
    new, old, stale = apply_baseline(lint_source(SHARED_HIT, CONC),
                                     load_baseline(str(bl)))
    assert new == [] and len(old) == 1 and stale == []
    clean = SHARED_HIT.replace(
        "        self._n += 1",
        "        with self._lock:\n            self._n += 1").replace(
        "            x = self._n",
        "            with self._lock:\n                x = self._n")
    assert lint_source(clean, CONC) == []


def test_unguarded_shared_write_init_is_prepublication():
    # writes in __init__ (and private helpers only it calls) happen
    # before the thread starts: never a conflict
    code = src("""
    import threading

    class Pump:
        def __init__(self):
            self._setup()
            threading.Thread(target=self._run, daemon=True).start()

        def _setup(self):
            self._n = 0

        def _run(self):
            return self._n
    """)
    assert lint_source(code, CONC) == []


def test_unguarded_shared_write_handler_multi_instance():
    # one socketserver handler root is MANY threads: a shared object it
    # writes without a lock conflicts with itself
    code = src("""
    import socketserver

    class Store:
        def note(self, k):
            self._seen[k] = 1

    store = Store()

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            store.note(self.request)
    """)
    diags = lint_source(code, CONC)
    assert rules_of(diags) == ["unguarded-shared-write"]
    assert "handler:Handler" in diags[0].threads


def test_inconsistent_guard_quad(tmp_path):
    code = src("""
    import threading

    class Pump:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0
            threading.Thread(target=self._run, daemon=True).start()

        def bump(self):
            with self._lock:
                self._n += 1

        def _run(self):
            return self._n
    """)
    diags = lint_source(code, CONC)
    assert rules_of(diags) == ["inconsistent-guard"]
    # anchored on the UNGUARDED side, naming the guarded peer's lock
    assert diags[0].line == 14
    assert "Pump._lock" in diags[0].message
    sup = code.replace("return self._n",
                       "return self._n  # mxlint: disable=inconsistent-guard")
    assert lint_source(sup, CONC) == []
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), diags)
    new, old, _ = apply_baseline(lint_source(code, CONC),
                                 load_baseline(str(bl)))
    assert new == [] and len(old) == 1
    clean = code.replace("return self._n",
                         "with self._lock:\n            return self._n")
    assert lint_source(clean, CONC) == []


def test_guard_propagates_through_private_callee():
    # a helper called ONLY with the lock held inherits the guard — the
    # _try_release_barrier pattern must not false-positive
    code = src("""
    import threading

    class Pump:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0
            threading.Thread(target=self._run, daemon=True).start()

        def bump(self):
            with self._lock:
                self._bump_locked()

        def _bump_locked(self):
            self._n += 1

        def _run(self):
            with self._lock:
                return self._n
    """)
    assert lint_source(code, CONC) == []


def test_lock_order_cycle_quad(tmp_path):
    code = src("""
    import threading

    class AB:
        def __init__(self):
            self._a_lock = threading.Lock()
            self._b_lock = threading.Lock()
            threading.Thread(target=self._w, daemon=True).start()

        def fwd(self):
            with self._a_lock:
                with self._b_lock:
                    pass

        def _w(self):
            with self._b_lock:
                with self._a_lock:
                    pass
    """)
    diags = lint_source(code, CONC)
    assert rules_of(diags) == ["lock-order-cycle"]
    assert "AB._a_lock" in diags[0].message and \
        "AB._b_lock" in diags[0].message
    anchor = diags[0].line
    lines = code.splitlines()
    lines[anchor - 1] += "  # mxlint: disable=lock-order-cycle"
    assert lint_source("\n".join(lines) + "\n", CONC) == []
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), diags)
    new, old, _ = apply_baseline(lint_source(code, CONC),
                                 load_baseline(str(bl)))
    assert new == [] and len(old) == 1
    clean = code.replace(
        "with self._b_lock:\n            with self._a_lock:",
        "with self._a_lock:\n            with self._b_lock:")
    assert lint_source(clean, CONC) == []


def test_blocking_wait_unbounded_quad(tmp_path):
    code = src("""
    import threading

    class W:
        def __init__(self):
            self._ev = threading.Event()
            self._lk = threading.Lock()

        def park(self):
            self._ev.wait()

        def grab(self):
            self._lk.acquire()

        def park_ok(self):
            self._ev.wait(1.0)
            self._lk.acquire(timeout=2.0)
    """)
    path = "mxnet_tpu/kvstore/foo.py"
    diags = lint_source(code, path)
    assert rules_of(diags) == ["blocking-wait-unbounded"] * 2
    assert "Event.wait" in diags[0].message
    assert "acquire" in diags[1].message
    # out of the fault/kvstore/health/launch scope: not checked
    assert lint_source(code, "mxnet_tpu/callback.py") == []
    sup = code.replace(
        "self._ev.wait()",
        "self._ev.wait()  # mxlint: disable=blocking-wait-unbounded"
    ).replace(
        "self._lk.acquire()",
        "self._lk.acquire()  # mxlint: disable=blocking-wait-unbounded")
    assert lint_source(sup, path) == []
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), diags)
    new, old, _ = apply_baseline(lint_source(code, path),
                                 load_baseline(str(bl)))
    assert new == [] and len(old) == 2


def test_thread_leak_quad(tmp_path):
    hit = src("""
    import threading

    def work():
        pass

    def spawn():
        t = threading.Thread(target=work)
        t.start()
    """)
    diags = lint_source(hit, CONC)
    assert rules_of(diags) == ["thread-leak"]
    sup = hit.replace(
        "t = threading.Thread(target=work)",
        "t = threading.Thread(target=work)  # mxlint: disable=thread-leak")
    assert lint_source(sup, CONC) == []
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), diags)
    new, old, _ = apply_baseline(lint_source(hit, CONC),
                                 load_baseline(str(bl)))
    assert new == [] and len(old) == 1
    # clean: daemon=True, an (even bounded) join, or a stop-event loop
    assert lint_source(hit.replace("target=work", "target=work, daemon=True"),
                       CONC) == []
    joined = hit + "\n    t.join(timeout=5)\n"
    assert lint_source(joined, CONC) == []
    stop_ev = src("""
    import threading

    _stop = threading.Event()

    def work():
        while not _stop.wait(0.5):
            pass

    def spawn():
        threading.Thread(target=work).start()
    """)
    assert lint_source(stop_ev, CONC) == []


def test_grad_hook_callback_is_thread_root():
    # `X._grad_hook = partial(self._cb, ...)` marks _cb as an overlap
    # callback root (fires mid-backward) — unguarded state it shares
    # with the step path is flagged
    code = src("""
    import functools

    class Trainer:
        def arm(self, grads):
            self._sess = object()
            for i, g in enumerate(grads):
                g._grad_hook = functools.partial(self._on_ready, i)

        def _on_ready(self, i):
            s = self._sess
            return s
    """)
    diags = lint_source(code, "mxnet_tpu/gluon/trainer.py")
    assert "unguarded-shared-write" in rules_of(diags)
    assert any("hook:Trainer._on_ready" in d.threads for d in diags)


def test_pool_submit_target_is_thread_root():
    code = src("""
    from concurrent.futures import ThreadPoolExecutor

    class Loader:
        def __init__(self):
            self._pool = ThreadPoolExecutor(4)
            self._epoch = 0

        def reset(self):
            self._epoch += 1

        def fetch(self, keys):
            return list(self._pool.map(self._load, keys))

        def _load(self, k):
            return (k, self._epoch)
    """)
    diags = lint_source(code, CONC)
    assert rules_of(diags) == ["unguarded-shared-write"]
    assert any("pool:Loader._load" in d.threads for d in diags)


def test_lock_order_same_named_locals_do_not_collide():
    # same-named function-local locks in two files are DIFFERENT locks:
    # their tokens must not merge into one graph node and fabricate a
    # cross-file cycle
    a = src("""
    import threading
    my_lock = threading.Lock()
    my_sem = threading.Semaphore()

    def f():
        with my_lock:
            with my_sem:
                pass
    """)
    b = src("""
    import threading
    my_lock = threading.Lock()
    my_sem = threading.Semaphore()

    def g():
        with my_sem:
            with my_lock:
                pass
    """)
    assert lint_sources({"mxnet_tpu/x.py": a, "mxnet_tpu/y.py": b}) == []


def test_blocking_wait_per_method_timeout_semantics():
    # a positional arg is not always a timeout: wait_for's first arg is
    # the predicate, and acquire(blocking=True) is explicitly unbounded
    code = src("""
    import threading

    class W:
        def __init__(self):
            self._cv = threading.Condition()
            self._lk = threading.Lock()

        def bad(self):
            with self._cv:
                self._cv.wait_for(lambda: True)
            self._lk.acquire(blocking=True)

        def ok(self):
            with self._cv:
                self._cv.wait_for(lambda: True, 5.0)
            self._lk.acquire(False)
            self._lk.acquire(True, 5.0)
            self._lk.acquire(timeout=1.0)
    """)
    diags = lint_source(code, "mxnet_tpu/kvstore/foo.py")
    assert rules_of(diags) == ["blocking-wait-unbounded"] * 2
    assert [d.line for d in diags] == [10, 11]


def test_thread_leak_join_matching_is_file_scoped():
    # an unrelated `t.join()` in ANOTHER file must not silence a leak
    # bound to a bare local name; a class-qualified binding still
    # matches project-wide
    leak = src("""
    import threading

    def work():
        pass

    def spawn():
        t = threading.Thread(target=work)
        t.start()
    """)
    other = src("""
    class Other:
        def stop(self):
            t = self.worker
            t.join()
    """)
    out = lint_sources({"mxnet_tpu/m.py": leak, "mxnet_tpu/n.py": other})
    assert rules_of(out) == ["thread-leak"]


# ---------------------------------------------------------------------------
# cross-file anchoring (the two-site satellite): write site anchors the
# diagnostic, the peer read in ANOTHER file rides in message/peer only —
# so suppression and the baseline fingerprint stay stable under peer drift
# ---------------------------------------------------------------------------

XFILE_A = src("""
class Base:
    def set(self, v):
        self._n = v
""")

XFILE_B = src("""
import threading
from .a import Base

class Worker(Base):
    def __init__(self):
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        return self._n
""")


def test_cross_file_conflict_anchors_on_write_site():
    diags = lint_sources({"mxnet_tpu/a.py": XFILE_A,
                          "mxnet_tpu/b.py": XFILE_B})
    assert rules_of(diags) == ["unguarded-shared-write"]
    d = diags[0]
    assert d.path == "mxnet_tpu/a.py" and d.line == 3
    assert d.peer == "mxnet_tpu/b.py:9"
    assert "mxnet_tpu/b.py:9" in d.message


def test_cross_file_fingerprint_survives_peer_drift(tmp_path):
    diags = lint_sources({"mxnet_tpu/a.py": XFILE_A,
                          "mxnet_tpu/b.py": XFILE_B})
    # shift the PEER file by 5 lines: fingerprint (and thus a baseline
    # entry / suppression) must not change, only the peer pointer
    shifted = lint_sources({"mxnet_tpu/a.py": XFILE_A,
                            "mxnet_tpu/b.py": "\n" * 5 + XFILE_B})
    assert diags[0].fingerprint() == shifted[0].fingerprint()
    assert diags[0].fingerprint_id() == shifted[0].fingerprint_id()
    assert shifted[0].peer == "mxnet_tpu/b.py:14"
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), diags)
    new, old, stale = apply_baseline(shifted, load_baseline(str(bl)))
    assert new == [] and len(old) == 1 and stale == []


def test_cross_file_suppression_on_write_site():
    sup_a = XFILE_A.replace(
        "self._n = v",
        "self._n = v  # mxlint: disable=unguarded-shared-write")
    assert lint_sources({"mxnet_tpu/a.py": sup_a,
                         "mxnet_tpu/b.py": XFILE_B}) == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    code = src("""
    class Trainer:
        def _update(self):
            return self.g.asnumpy()
    """)
    diags = lint_source(code, HOT_PATH)
    assert len(diags) == 1
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), diags)
    new, old, stale = apply_baseline(lint_source(code, HOT_PATH),
                                     load_baseline(str(bl)))
    assert new == [] and len(old) == 1 and stale == []
    # a SECOND violation with a different line text is NOT absorbed
    code2 = code + "\n    def update(self):\n        return self.w.asnumpy()\n"
    new2, old2, _ = apply_baseline(lint_source(code2, HOT_PATH),
                                   load_baseline(str(bl)))
    assert len(new2) == 1 and len(old2) == 1
    # fixing the violation leaves the entry stale (reported, not fatal)
    fixed = "class Trainer:\n    def _update(self):\n        return 0\n"
    new3, old3, stale3 = apply_baseline(lint_source(fixed, HOT_PATH),
                                        load_baseline(str(bl)))
    assert new3 == [] and old3 == [] and len(stale3) == 1


def test_parse_error_is_a_diagnostic():
    diags = lint_source("def broken(:\n", "mxnet_tpu/foo.py")
    assert rules_of(diags) == ["mxlint-parse"]


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

def _fake_repo(tmp_path, bad=True):
    pkg = tmp_path / "mxnet_tpu"
    (pkg / "kvstore").mkdir(parents=True)
    (pkg / "base.py").write_text("ENV_CATALOG = {'MX_KNOWN': ('', 'd')}\n")
    body = "import time as _time\n\ndef retry():\n    return _time.time()\n" \
        if bad else "def retry():\n    return 0\n"
    (pkg / "kvstore" / "mod.py").write_text(body)
    return pkg


def _run_cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.mxlint"] + args,
                          cwd=cwd, capture_output=True, text=True)


def test_cli_exit_codes_and_json(tmp_path):
    pkg = _fake_repo(tmp_path, bad=True)
    r = _run_cli([str(pkg), "--no-baseline", "--format", "json"])
    assert r.returncode == 1, r.stderr
    payload = json.loads(r.stdout)
    assert [v["rule"] for v in payload["violations"]] == \
        ["wall-clock-in-fault-path"]
    assert payload["violations"][0]["path"] == "mxnet_tpu/kvstore/mod.py"

    clean = _fake_repo(tmp_path / "c", bad=False)
    r = _run_cli([str(clean), "--no-baseline"])
    assert r.returncode == 0, r.stdout + r.stderr

    assert _run_cli(["/nonexistent/path"]).returncode == 2
    assert _run_cli([str(pkg), "--select", "no-such-rule"]).returncode == 2
    assert _run_cli(["--list-rules"]).returncode == 0

    # a typo'd --baseline is a usage error (2), NOT "new violations" (1)
    r = _run_cli([str(pkg), "--baseline", str(tmp_path / "no_such.json")])
    assert r.returncode == 2, r.stdout + r.stderr
    assert "cannot read baseline" in r.stderr
    garbled = tmp_path / "garbled.json"
    garbled.write_text("{not json")
    r = _run_cli([str(pkg), "--baseline", str(garbled)])
    assert r.returncode == 2, r.stdout + r.stderr


def test_cli_write_baseline_roundtrip(tmp_path):
    pkg = _fake_repo(tmp_path, bad=True)
    bl = tmp_path / "bl.json"
    r = _run_cli([str(pkg), "--baseline", str(bl), "--write-baseline"])
    assert r.returncode == 0, r.stderr
    r = _run_cli([str(pkg), "--baseline", str(bl)])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_write_baseline_narrowed_scan_preserves_entries(tmp_path):
    # re-baselining one FILE must not erase grandfathered entries for the
    # rest of the tree; re-baselining with --select must refuse outright
    pkg = _fake_repo(tmp_path, bad=True)
    clean_file = pkg / "kvstore" / "other.py"
    clean_file.write_text("def ok():\n    return 0\n")
    bl = tmp_path / "bl.json"
    r = _run_cli([str(pkg), "--baseline", str(bl), "--write-baseline"])
    assert r.returncode == 0, r.stderr
    full = json.loads(bl.read_text())["entries"]
    assert len(full) == 1     # mod.py's wall-clock hit

    r = _run_cli([str(clean_file), "--baseline", str(bl),
                  "--write-baseline"])
    assert r.returncode == 0, r.stderr
    assert "preserved" in r.stdout
    assert json.loads(bl.read_text())["entries"] == full

    r = _run_cli([str(pkg), "--baseline", str(bl), "--write-baseline",
                  "--select", "jit-purity"])
    assert r.returncode == 2, r.stdout + r.stderr
    assert json.loads(bl.read_text())["entries"] == full


def test_cli_jobs_parallel_matches_serial(tmp_path):
    # --jobs N must produce byte-identical findings to the serial scan
    pkg = _fake_repo(tmp_path, bad=True)
    (pkg / "kvstore" / "waits.py").write_text(src("""
    import threading

    class W:
        def __init__(self):
            self._ev = threading.Event()

        def park(self):
            self._ev.wait()
    """))
    serial = _run_cli([str(pkg), "--no-baseline", "--format", "json"])
    par = _run_cli([str(pkg), "--no-baseline", "--format", "json",
                    "--jobs", "4"])
    assert serial.returncode == par.returncode == 1
    assert json.loads(serial.stdout)["violations"] == \
        json.loads(par.stdout)["violations"]


def test_cli_json_schema_stable(tmp_path):
    pkg = _fake_repo(tmp_path, bad=True)
    r = _run_cli([str(pkg), "--no-baseline", "--format", "json"])
    payload = json.loads(r.stdout)
    assert payload["schema"] == 2
    assert set(payload) >= {"schema", "violations", "baselined",
                            "stale_baseline", "lock_graph"}
    v = payload["violations"][0]
    # the machine contract: rule id, drift-stable fingerprint,
    # file:line, thread roots involved
    assert set(v) >= {"rule", "path", "line", "col", "message",
                      "snippet", "fingerprint", "threads"}
    assert isinstance(v["fingerprint"], str) and len(v["fingerprint"]) == 16
    assert payload["lock_graph"]["acyclic"] in (True, False)


def test_cli_select_accepts_concurrency_rules(tmp_path):
    pkg = _fake_repo(tmp_path, bad=True)
    # selecting ONLY a concurrency rule: the wall-clock hit disappears
    r = _run_cli([str(pkg), "--no-baseline",
                  "--select", "unguarded-shared-write,lock-order-cycle"])
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_cli(["--list-rules"])
    for rid in ("unguarded-shared-write", "inconsistent-guard",
                "lock-order-cycle", "blocking-wait-unbounded",
                "thread-leak"):
        assert rid in r.stdout


def test_write_baseline_preserves_why(tmp_path):
    # the baseline-justification policy: regenerating the baseline must
    # keep each surviving entry's reviewer-written `why`
    pkg = _fake_repo(tmp_path, bad=True)
    bl = tmp_path / "bl.json"
    r = _run_cli([str(pkg), "--baseline", str(bl), "--write-baseline"])
    assert r.returncode == 0, r.stderr
    data = json.loads(bl.read_text())
    assert len(data["entries"]) == 1
    data["entries"][0]["why"] = "virtual-clock exempt: test fixture"
    bl.write_text(json.dumps(data))
    r = _run_cli([str(pkg), "--baseline", str(bl), "--write-baseline"])
    assert r.returncode == 0, r.stderr
    entries = json.loads(bl.read_text())["entries"]
    assert entries[0]["why"] == "virtual-clock exempt: test fixture"
    assert load_baseline_whys(str(bl))


# ---------------------------------------------------------------------------
# env scanner + gen_env_docs --check
# ---------------------------------------------------------------------------

def test_collect_env_reads(tmp_path):
    f = tmp_path / "m.py"
    f.write_text(src("""
    import os
    from .base import get_env

    a = os.environ.get("MX_ALPHA")
    b = get_env("MXNET_BETA")
    c = os.environ["MX_GAMMA"]
    d = os.environ.get("HOME")        # not MX_*: ignored
    """))
    found = collect_env_reads([str(tmp_path)])
    assert set(found) == {"MX_ALPHA", "MXNET_BETA", "MX_GAMMA"}


@pytest.mark.slow
def test_gen_env_docs_check_passes_on_shipped_tree():
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "gen_env_docs.py"),
                        "--check"], capture_output=True, text=True,
                       cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# the tier-1 gate: shipped tree is clean; reinjected violations trip
# ---------------------------------------------------------------------------

_TREE_SCAN = []     # memo: the full-tree scan feeds three gate tests


def _scan_tree():
    if not _TREE_SCAN:
        _TREE_SCAN.append(lint_paths(RUNTIME_PATHS, root=REPO,
                                     return_project=True))
    return _TREE_SCAN[0]


def _lint_tree():
    diags, _project = _scan_tree()
    return apply_baseline(diags, load_baseline(BASELINE))


def test_shipped_tree_lints_clean():
    # the whole threaded runtime (mxnet_tpu + the supervisor), ALL rules
    # including the concurrency pass
    new, old, stale = _lint_tree()
    assert new == [], "\n".join(map(repr, new))
    assert stale == [], ("baseline entries no longer match the tree — "
                         "run `python -m tools.mxlint --write-baseline`"
                         ": %s" % (stale,))


def test_shipped_lock_graph_is_acyclic():
    # the acceptance criterion verbatim: the runtime's static
    # lock-acquisition graph must stay acyclic, and must actually SEE
    # the lock hierarchy the docs promise
    _diags, project = _scan_tree()
    cycles = project.lock_cycles()
    assert cycles == [], cycles
    edges = set(project.lock_graph())
    assert ("KVStoreServer._barrier_cv",
            "KVStoreServer._seen_lock") in edges
    assert ("KVStoreServer._snapshot_lock",
            "KVStoreServer._global_lock") in edges
    assert ("KVStoreDistAsync._lock",
            "KVStoreDistAsync._seq_lock") in edges


def test_shipped_thread_roots_discovered():
    # the pass must actually SEE the runtime's thread landscape: the
    # kvstore heartbeat, the socketserver handler, the watchdog, and
    # the overlap grad-hook callback
    _diags, project = _scan_tree()
    roots = {r.display for r in project.roots}
    assert any("handler:Handler" in r for r in roots), roots
    assert any("Watchdog._run" in r for r in roots), roots
    assert any("_start_heartbeat" in r and r.startswith("thread:")
               for r in roots), roots
    assert any(r.startswith("hook:") and "_on_grad_ready" in r
               for r in roots), roots
    # ISSUE 13: the async input pipeline's producer thread
    assert "thread:DevicePrefetcher._run" in roots, roots


def test_reinjected_asnumpy_in_trainer_update_trips():
    p = os.path.join(REPO, "mxnet_tpu", "gluon", "trainer.py")
    with open(p) as f:
        code = f.read()
    anchor = 'with _telemetry.phase("optimizer_apply"):'
    assert anchor in code, "Trainer._update moved; update this test"
    bad = code.replace(
        anchor,
        anchor + "\n            _dbg = [g.asnumpy() for g in gs]")
    diags = lint_source(bad, "mxnet_tpu/gluon/trainer.py")
    assert "host-sync-in-hot-path" in rules_of(diags)
    # and it is NOT absorbed by the shipped baseline
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "host-sync-in-hot-path" in rules_of(new)


def test_reinjected_asnumpy_in_compiled_step_body_trips():
    """ISSUE 7: the whole-step compiled trace is a jit-purity target — a
    float(asnumpy()) reintroduced INSIDE the traced step body must trip
    the linter (a host sync under trace either crashes on tracers or
    bakes a constant in; either way the single-program contract dies)."""
    p = os.path.join(REPO, "mxnet_tpu", "step.py")
    with open(p) as f:
        code = f.read()
    anchor = ("            carry = (t_vals, f_vals, opt_states, w32s, "
              "residuals, mstate)")
    assert anchor in code, "_traced_step_window moved; update this test"
    bad = code.replace(
        anchor,
        anchor + "\n            _dbg = float(t_vals[0].asnumpy())", 1)
    diags = lint_source(bad, "mxnet_tpu/step.py")
    assert "jit-purity" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "jit-purity" in rules_of(new)


def test_reinjected_asnumpy_in_compiled_step_host_path_trips():
    """The compiled lane's HOST side (CompiledStep._run and friends) is a
    hot-path root: a per-dispatch sync there stalls the one-program
    pipeline exactly like a per-op sync used to."""
    p = os.path.join(REPO, "mxnet_tpu", "step.py")
    with open(p) as f:
        code = f.read()
    anchor = "            state = self._gather_state(plan)"
    assert anchor in code, "CompiledStep._run moved; update this test"
    bad = code.replace(
        anchor, anchor + "\n            _dbg = state[0][0].asnumpy()", 1)
    diags = lint_source(bad, "mxnet_tpu/step.py")
    assert "host-sync-in-hot-path" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "host-sync-in-hot-path" in rules_of(new)


def test_compiled_step_is_hot_path_root():
    """The rule table names the compiled-step entry points (regression
    guard: removing the root entry would silently drop the coverage the
    two reinjection tests above rely on)."""
    from tools.mxlint.rules import HOT_PATH_ROOTS
    roots = dict(HOT_PATH_ROOTS)
    assert "mxnet_tpu/step.py" in roots
    assert any("CompiledStep.step" in q for q in roots["mxnet_tpu/step.py"])
    assert any("CompiledStep._run" in q for q in roots["mxnet_tpu/step.py"])


def test_reinjected_host_sync_in_serve_batcher_trips():
    """ISSUE 9: the serving batcher's dispatch loop is a hot-path root —
    a blocking ``float(...asnumpy())`` reintroduced between dequeue and
    dispatch (debug peeking at the batch output) serializes the whole
    fleet's latency and must trip the rule."""
    p = os.path.join(REPO, "mxnet_tpu", "serve", "batcher.py")
    with open(p) as f:
        code = f.read()
    anchor = "                outs = sv.dispatch(bucket, padded)"
    assert anchor in code, "Batcher._dispatch moved; update this test"
    bad = code.replace(
        anchor,
        anchor + "\n                _dbg = float(outs[0].asnumpy()[0])", 1)
    diags = lint_source(bad, "mxnet_tpu/serve/batcher.py")
    assert "host-sync-in-hot-path" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "host-sync-in-hot-path" in rules_of(new)


def test_serve_batcher_is_hot_path_root():
    """Regression guard for the root-table entries the reinjection test
    above relies on (batcher loop + the servable dispatch side of the
    cross-file hot edge)."""
    from tools.mxlint.rules import HOT_PATH_ROOTS
    roots = dict(HOT_PATH_ROOTS)
    assert "mxnet_tpu/serve/batcher.py" in roots
    assert any("Batcher._dispatch" in q
               for q in roots["mxnet_tpu/serve/batcher.py"])
    assert any("Batcher._collect" in q
               for q in roots["mxnet_tpu/serve/batcher.py"])
    assert "mxnet_tpu/serve/servable.py" in roots
    assert any("Servable.dispatch" in q
               for q in roots["mxnet_tpu/serve/servable.py"])


def test_serve_batcher_thread_is_a_discovered_root():
    """The concurrency pass must see the batcher's dispatch loop as a
    thread root (its shared state is then race-checked) — and the
    serving socket handler as a multi-instance root, like the kvstore
    server's.  Reuses the memoized full-tree scan."""
    _diags, proj = _scan_tree()
    displays = {r.display for r in proj.roots}
    assert "thread:Batcher._loop" in displays
    assert any("mxnet_tpu/serve/server.py" in e
               for r in proj.roots for e in r.entries
               if r.kind == "handler")


def test_reinjected_host_sync_in_decode_pump_trips():
    """ISSUE 15: the decode pump is a hot-path root — a blocking host
    read reintroduced between decode dispatches (debug peeking at the
    step's emitted tokens) stalls EVERY active generation's token
    cadence; the device→host read belongs only to the harvester
    thread."""
    p = os.path.join(REPO, "mxnet_tpu", "serve", "decode.py")
    with open(p) as f:
        code = f.read()
    anchor = "            out = self._sv.dispatch_step(ids)"
    assert anchor in code, "DecodeBatcher._step moved; update this test"
    bad = code.replace(
        anchor,
        anchor + "\n            _dbg = float(out.asnumpy()[0])", 1)
    diags = lint_source(bad, "mxnet_tpu/serve/decode.py")
    assert "host-sync-in-hot-path" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "host-sync-in-hot-path" in rules_of(new)


def test_decode_pump_is_hot_path_root():
    """Root-table regression guard for the decode engine (ISSUE 15):
    the pump loop, the slot allocator and the servable dispatch path
    must stay rooted so the reinjection test above keeps meaning
    something."""
    from tools.mxlint.rules import HOT_PATH_ROOTS
    roots = dict(HOT_PATH_ROOTS)
    assert "mxnet_tpu/serve/decode.py" in roots
    entries = roots["mxnet_tpu/serve/decode.py"]
    for qual in ("DecodeBatcher._tick", "DecodeBatcher._admit",
                 "DecodeBatcher._step",
                 "DecodeServable.dispatch_step"):
        assert any(qual in q for q in entries), (qual, entries)
    # the harvester is deliberately NOT rooted: it is the one place the
    # device→host token read is allowed to live
    assert not any("_harvest" in q for q in entries), entries


def test_decode_pump_threads_are_discovered_roots():
    """The concurrency pass must see BOTH decode threads — the dispatch
    pump and the token harvester — as thread roots so their shared
    state is race-checked.  Reuses the memoized full-tree scan."""
    _diags, proj = _scan_tree()
    displays = {r.display for r in proj.roots}
    assert "thread:DecodeBatcher._loop" in displays
    assert "thread:DecodeBatcher._harvest_loop" in displays


def test_reinjected_host_sync_in_page_allocator_trips():
    """ISSUE 18: the page allocator runs inside the pump's admission
    path every tick — a device sync smuggled into ``alloc()`` (debug
    peeking at the heap while handing out pages) stalls admission AND
    decode, since the pump alternates both on one thread."""
    p = os.path.join(REPO, "mxnet_tpu", "serve", "paging.py")
    with open(p) as f:
        code = f.read()
    anchor = "                self._refs[page] = 1"
    assert anchor in code, "PageAllocator.alloc moved; update this test"
    bad = code.replace(
        anchor,
        anchor + "\n                _dbg = float(heap.asnumpy()[page])",
        1)
    diags = lint_source(bad, "mxnet_tpu/serve/paging.py")
    assert "host-sync-in-hot-path" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "host-sync-in-hot-path" in rules_of(new)


def test_reinjected_host_sync_in_chunk_scheduler_trips():
    """The chunked-prefill scheduler is a hot-path root: a blocking
    read of the chunk's emitted token inside the pump (instead of the
    harvester) re-serializes every interleaved generation."""
    p = os.path.join(REPO, "mxnet_tpu", "serve", "decode.py")
    with open(p) as f:
        code = f.read()
    anchor = "        self._c_chunks.inc()"
    assert anchor in code, \
        "PagedDecodeBatcher._dispatch_chunk_for moved; update this test"
    bad = code.replace(
        anchor, anchor + "\n        _dbg = float(t0.asnumpy())", 1)
    diags = lint_source(bad, "mxnet_tpu/serve/decode.py")
    assert "host-sync-in-hot-path" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "host-sync-in-hot-path" in rules_of(new)


def test_paged_engine_is_hot_path_root():
    """Root-table regression guard for the paged engine (ISSUE 18):
    the chunk scheduler, the page planner, the allocator and the
    prefix-hash helpers must stay rooted so the reinjection tests
    above keep meaning something."""
    from tools.mxlint.rules import HOT_PATH_ROOTS
    roots = dict(HOT_PATH_ROOTS)
    entries = roots["mxnet_tpu/serve/decode.py"]
    for qual in ("PagedDecodeBatcher._tick", "PagedDecodeBatcher._plan",
                 "PagedDecodeBatcher._dispatch_chunk_for",
                 "PagedDecodeServable.dispatch_chunk",
                 "PagedDecodeServable.dispatch_step"):
        assert any(qual in q for q in entries), (qual, entries)
    assert "mxnet_tpu/serve/paging.py" in roots
    palloc = roots["mxnet_tpu/serve/paging.py"]
    for qual in ("PageAllocator.alloc", "PageAllocator.release",
                 "chain_hash", "page_hashes"):
        assert any(qual in q for q in palloc), (qual, palloc)


def test_reinjected_wall_clock_in_kvstore_retry_trips():
    p = os.path.join(REPO, "mxnet_tpu", "kvstore", "kvstore.py")
    with open(p) as f:
        code = f.read()
    anchor = "if deadline.expired():"
    assert anchor in code, "connect-retry loop moved; update this test"
    bad = code.replace(
        anchor,
        "import time\n                    "
        "if time.time() > _connect_t0 + 60:", 1)
    diags = lint_source(bad, "mxnet_tpu/kvstore/kvstore.py")
    assert "wall-clock-in-fault-path" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "wall-clock-in-fault-path" in rules_of(new)


def test_reinjected_unguarded_write_in_server_trips():
    # acceptance criterion: re-introduce the known-fixed race (the
    # liveness-table write losing its lock) into a test copy of
    # kvstore/server.py and the lint must fail
    p = os.path.join(REPO, "mxnet_tpu", "kvstore", "server.py")
    with open(p) as f:
        code = f.read()
    anchor = ("            with self._seen_lock:\n"
              "                self._last_seen[rank] = _fault.now()\n"
              "                self._seen_regime[rank] = "
              "_fault.is_virtual()")
    assert anchor in code, "touch() moved; update this test"
    bad = code.replace(anchor,
                       "            self._last_seen[rank] = _fault.now()\n"
                       "            self._seen_regime[rank] = "
                       "_fault.is_virtual()")
    diags = lint_source(bad, "mxnet_tpu/kvstore/server.py")
    assert "unguarded-shared-write" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "unguarded-shared-write" in rules_of(new)


def test_reinjected_unguarded_write_in_server_fails_cli(tmp_path):
    # same reinjection through the CLI exit-code contract, on a copied
    # tree (the shipped tree itself must stay clean)
    pkg = tmp_path / "mxnet_tpu"
    (pkg / "kvstore").mkdir(parents=True)
    (pkg / "base.py").write_text("ENV_CATALOG = {}\n")
    p = os.path.join(REPO, "mxnet_tpu", "kvstore", "server.py")
    with open(p) as f:
        code = f.read()
    bad = code.replace("            with self._seen_lock:\n"
                       "                self._last_seen[rank]",
                       "            if True:\n"
                       "                self._last_seen[rank]")
    assert bad != code
    (pkg / "kvstore" / "server.py").write_text(bad)
    r = _run_cli([str(pkg), "--select", "unguarded-shared-write"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "unguarded-shared-write" in r.stdout


def test_reinjected_hook_race_in_trainer_trips():
    # the overlap-session handoff (ISSUE 5) is lock-protected; dropping
    # the guard on the hook-side read must trip the concurrency pass
    p = os.path.join(REPO, "mxnet_tpu", "gluon", "trainer.py")
    with open(p) as f:
        code = f.read()
    anchor = ("    def _on_grad_ready(self, i, d):\n"
              "        with self._hook_lock:\n"
              "            sess = self._exchange_session")
    assert anchor in code, "Trainer._on_grad_ready moved; update this test"
    bad = code.replace(anchor,
                       "    def _on_grad_ready(self, i, d):\n"
                       "        if True:\n"
                       "            sess = self._exchange_session")
    diags = lint_source(bad, "mxnet_tpu/gluon/trainer.py")
    assert "inconsistent-guard" in rules_of(diags) or \
        "unguarded-shared-write" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert new != []


def test_rule_set_is_complete():
    assert {"host-sync-in-hot-path", "jit-purity",
            "wall-clock-in-fault-path", "env-var-registry",
            "donation-after-use",
            # ISSUE 6: the whole-program concurrency pass
            "unguarded-shared-write", "inconsistent-guard",
            "lock-order-cycle", "blocking-wait-unbounded",
            "thread-leak",
            # ISSUE 11: the program-contract PR's AST rules
            "retrace-hazard", "wire-verb-exhaustive"} <= set(RULES)


# ---------------------------------------------------------------------------
# retrace-hazard (ISSUE 11)
# ---------------------------------------------------------------------------

STEP_PATH = "mxnet_tpu/step.py"


def test_retrace_hazard_shape_branch_in_jitted_body():
    code = src("""
    import jax

    def body(x, k):
        if x.shape[0] > 4:
            return x * k
        return x

    f = jax.jit(body)
    """)
    diags = lint_source(code, STEP_PATH, select={"retrace-hazard"})
    assert rules_of(diags) == ["retrace-hazard"]
    assert "x.shape" in diags[0].message and "body" in diags[0].message


def test_retrace_hazard_scalar_literal_at_hot_call_site():
    code = src("""
    import jax

    def body(x, k):
        return x * k

    _F = jax.jit(body)

    class CompiledStep:
        def _run(self, x):
            return _F(x, 3.0)
    """)
    diags = lint_source(code, STEP_PATH, select={"retrace-hazard"})
    assert rules_of(diags) == ["retrace-hazard"]
    assert "3.0" in diags[0].message and "VALUE" in diags[0].message


def test_retrace_hazard_negative_and_keyword_scalars():
    # -1.0 parses as UnaryOp(USub, Constant) and k=3.0 arrives via
    # node.keywords — both are value-keyed retrace amplifiers; a
    # static_argnames-covered keyword is exempt
    code = src("""
    import jax

    def body(x, c, k=None, mode=None):
        return x * c + k

    _F = jax.jit(body, static_argnames=("mode",))

    class CompiledStep:
        def _run(self, x):
            return _F(x, -1.0, k=3.0, mode=2)
    """)
    diags = lint_source(code, STEP_PATH, select={"retrace-hazard"})
    assert rules_of(diags) == ["retrace-hazard"] * 2
    msgs = "\n".join(d.message for d in diags)
    assert "-1.0" in msgs and "3.0" in msgs and "2" not in msgs.split()


def test_retrace_hazard_register_program_site_and_static_exempt():
    # static_argnums covers both halves: the branch argument and the
    # scalar position are trace-static, so neither is a hazard
    code = src("""
    import jax
    from mxnet_tpu.programs import register_program

    def body(x, n):
        if x.shape[0] > n:
            return x
        return x + n

    _F = register_program("p", body, static_argnums=(1,))

    class CompiledStep:
        def _run(self, x):
            return _F(x, 3)
    """)
    diags = lint_source(code, STEP_PATH, select={"retrace-hazard"})
    # the shape branch still flags (x is traced); the scalar does not
    assert rules_of(diags) == ["retrace-hazard"]
    assert "x.shape" in diags[0].message

    clean = src("""
    import jax
    from mxnet_tpu.programs import register_program

    def body(x, n):
        if x.shape[0] > n:
            return x
        return x + n

    _F = register_program("p", body, static_argnums=(0, 1))
    """)
    assert lint_source(clean, STEP_PATH,
                       select={"retrace-hazard"}) == []


def test_retrace_hazard_suppressed_and_ops_exempt():
    code = src("""
    import jax

    def body(x):
        if x.shape[0] > 4:  # mxlint: disable=retrace-hazard
            return x
        return x

    f = jax.jit(body)
    """)
    assert lint_source(code, STEP_PATH, select={"retrace-hazard"}) == []
    # per-op eager kernels specialize by rank/shape by design — the
    # rule's path scope exempts mxnet_tpu/ops entirely
    unsuppressed = code.replace("  # mxlint: disable=retrace-hazard", "")
    assert lint_source(unsuppressed, "mxnet_tpu/ops/matrix.py",
                       select={"retrace-hazard"}) == []


def test_reinjected_shape_branch_in_step_body_trips():
    """ISSUE 11 reinjection: a per-shape python branch reintroduced into
    the traced step body must trip retrace-hazard (and not be absorbed
    by the shipped baseline)."""
    p = os.path.join(REPO, "mxnet_tpu", "step.py")
    with open(p) as f:
        code = f.read()
    anchor = ("            carry = (t_vals, f_vals, opt_states, w32s, "
              "residuals, mstate)")
    assert anchor in code, "_traced_step_window moved; update this test"
    bad = code.replace(
        anchor,
        "            if xs[0].shape[0] > 4:\n"
        "                pass\n" + anchor, 1)
    diags = lint_source(bad, "mxnet_tpu/step.py")
    assert "retrace-hazard" in rules_of(diags)
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert "retrace-hazard" in rules_of(new)


# ---------------------------------------------------------------------------
# wire-verb-exhaustive (ISSUE 11)
# ---------------------------------------------------------------------------

WIRE_SERVER = "mxnet_tpu/serve/xserver.py"
WIRE_CLIENT = "mxnet_tpu/serve/xclient.py"

CLEAN_SERVER = src("""
WIRE_VERBS = {
    "ROUTE": {"semantics": "replayable", "codec": "blob"},
    "DRAIN": {"semantics": "idempotent", "codec": None},
}
_CACHED = ("ROUTE",)

def encode_blob(x):
    return x

def decode_blob(x):
    return x

def handle(msg):
    cmd = msg[0]
    if cmd == "ROUTE":
        return True, "ok"
    if cmd == "DRAIN":
        return True, "ok"
    return False, "unknown"
""")

CLEAN_CLIENT = src("""
class C:
    def route(self, x):
        return self._rpc("ROUTE", x)

    def drain(self):
        return self._rpc("DRAIN")
""")


def test_wire_verbs_clean_pair():
    diags = lint_sources({WIRE_SERVER: CLEAN_SERVER,
                          WIRE_CLIENT: CLEAN_CLIENT},
                         select={"wire-verb-exhaustive"})
    assert diags == []


def test_wire_verb_undeclared_emission():
    client = CLEAN_CLIENT + src("""
    class D:
        def leave(self):
            return self._rpc("LEAVE", 0)
    """)
    diags = lint_sources({WIRE_SERVER: CLEAN_SERVER, WIRE_CLIENT: client},
                         select={"wire-verb-exhaustive"})
    assert rules_of(diags) == ["wire-verb-exhaustive"]
    assert "'LEAVE'" in diags[0].message and diags[0].path == WIRE_CLIENT


def test_wire_verb_unhandled_bad_semantics_replay_and_codec():
    server = src("""
    WIRE_VERBS = {
        "JOIN": {"semantics": "replayable", "codec": None},
        "ROUTE": {"semantics": "maybe", "codec": "blob"},
    }
    _CACHED = ("PREDICT",)

    def handle(msg):
        cmd = msg[0]
        if cmd == "ROUTE":
            return True, "ok"
    """)
    diags = lint_sources({WIRE_SERVER: server},
                         select={"wire-verb-exhaustive"})
    msgs = "\n".join(d.message for d in diags)
    assert "no handler comparison" in msgs          # JOIN unhandled
    assert "missing from this file's replay-cache" in msgs
    assert "semantics 'maybe'" in msgs              # ROUTE semantics
    assert "encode_blob" in msgs                    # codec pair absent


def test_wire_verb_handled_but_undeclared_and_idempotent_in_cache():
    server = src("""
    WIRE_VERBS = {
        "ROUTE": {"semantics": "idempotent", "codec": None},
    }
    _CACHED = ("ROUTE",)

    def handle(msg):
        cmd = msg[0]
        if cmd == "ROUTE":
            return True, "ok"
        if cmd == "EVICT":
            return True, "ok"
    """)
    diags = lint_sources({WIRE_SERVER: server},
                         select={"wire-verb-exhaustive"})
    msgs = "\n".join(d.message for d in diags)
    assert "does not declare it" in msgs            # EVICT handled only
    assert "declared idempotent but sits" in msgs   # ROUTE in _CACHED


def test_wire_verb_cross_protocol_declaration_does_not_mask():
    """A verb declared only by ANOTHER protocol's manifest (kvstore's
    STOP) must not satisfy a serve-client emission: declaration is
    scoped to the client's own package directory when it has a
    manifest."""
    kv_server = src("""
    WIRE_VERBS = {
        "STOP": {"semantics": "idempotent", "codec": None},
    }

    def handle(msg):
        cmd = msg[0]
        if cmd == "STOP":
            return True, "ok"
    """)
    # serve server manifest exists but does NOT declare STOP
    serve_server = CLEAN_SERVER
    serve_client = CLEAN_CLIENT + src("""
    class S:
        def stop(self):
            return self._rpc("STOP")
    """)
    diags = lint_sources({"mxnet_tpu/kvstore/xserver.py": kv_server,
                          WIRE_SERVER: serve_server,
                          WIRE_CLIENT: serve_client},
                         select={"wire-verb-exhaustive"})
    assert any("'STOP'" in d.message and d.path == WIRE_CLIENT
               for d in diags), "\n".join(map(repr, diags))
    assert any("this protocol's server module" in d.message
               for d in diags)
    # a manifest-less directory still falls back to any manifest
    tool_client = src("""
    def shutdown(sock):
        send_msg(sock, ("STOP", "rank0"))
    """)
    diags = lint_sources({"mxnet_tpu/kvstore/xserver.py": kv_server,
                          "tools/xlaunch.py": tool_client},
                         select={"wire-verb-exhaustive"})
    assert diags == [], "\n".join(map(repr, diags))


def test_wire_verb_suppressed_on_manifest_line():
    server = CLEAN_SERVER.replace(
        "WIRE_VERBS = {",
        "WIRE_VERBS = {  # mxlint: disable=wire-verb-exhaustive")
    server = server.replace(
        '    "DRAIN": {"semantics": "idempotent", "codec": None},\n', "")
    # DRAIN handled-but-undeclared anchors on the handler line; the
    # manifest-line suppression covers manifest-side findings only
    diags = lint_sources({WIRE_SERVER: server, WIRE_CLIENT: CLEAN_CLIENT},
                         select={"wire-verb-exhaustive"})
    assert {d.rule for d in diags} <= {"wire-verb-exhaustive"}
    assert all("DRAIN" in d.message for d in diags), \
        "\n".join(d.message for d in diags)


def test_reinjected_unpaired_route_verb_trips():
    """ISSUE 11 reinjection (acceptance criterion): a ROUTE verb added
    to the serve client without completing the server's WIRE_VERBS row
    ships half-wired and must fail lint."""
    p = os.path.join(REPO, "mxnet_tpu", "serve", "client.py")
    with open(p) as f:
        code = f.read()
    anchor = "    def stop(self) -> None:"
    assert anchor in code, "ServeClient moved; update this test"
    bad = code.replace(
        anchor,
        "    def route(self, payload):\n"
        "        return self._rpc(\"ROUTE\", payload)\n\n" + anchor, 1)
    sources = {"mxnet_tpu/serve/client.py": bad}
    for rel in ("mxnet_tpu/serve/server.py",
                "mxnet_tpu/kvstore/server.py",
                "mxnet_tpu/kvstore/wire_codec.py"):
        with open(os.path.join(REPO, rel)) as f:
            sources[rel] = f.read()
    diags = lint_sources(sources, select={"wire-verb-exhaustive"})
    assert any("'ROUTE'" in d.message for d in diags), \
        "\n".join(map(repr, diags))
    new, _, _ = apply_baseline(diags, load_baseline(BASELINE))
    assert any("'ROUTE'" in d.message for d in new)


def test_shipped_wire_surface_is_declared():
    """The shipped protocol surface: both server manifests parse, every
    client verb is declared, and the replay sets agree with semantics
    (the tree-level gate is test_shipped_tree_lints_clean; this pins
    the extraction actually SEEING the manifests)."""
    _diags, project = _scan_tree()
    manifests = {p: s.wire.manifest for p, s in project.summaries.items()
                 if getattr(s, "wire", None) is not None
                 and s.wire.manifest is not None}
    assert "mxnet_tpu/serve/server.py" in manifests
    assert "mxnet_tpu/kvstore/server.py" in manifests
    serve = manifests["mxnet_tpu/serve/server.py"]
    # ISSUE 17: DRAIN retires a replica (re-asserting keeps the FIRST
    # deadline, so a retried DRAIN is a no-op = idempotent)
    assert set(serve) == {"PREDICT", "GENERATE", "STREAM", "HEALTH",
                          "METRICS", "SWAP", "STOP", "DRAIN"}
    assert serve["PREDICT"]["semantics"] == "replayable"
    # ISSUE 15: a replayed COMPLETED generation answers from the cache;
    # STREAM is the server->client chunk frame (handled with an explicit
    # error if a client ever emits it as a request)
    assert serve["GENERATE"]["semantics"] == "replayable"
    assert serve["STREAM"]["semantics"] == "idempotent"
    assert serve["DRAIN"]["semantics"] == "idempotent"
    # ISSUE 17: the router speaks the same surface plus its own DRAIN;
    # forwarded verbs keep the replica's replay semantics (the envelope
    # crosses unmodified, so exactly-once stays with the replica cache)
    assert "mxnet_tpu/serve/router.py" in manifests
    rt = manifests["mxnet_tpu/serve/router.py"]
    assert set(rt) == {"PREDICT", "GENERATE", "STREAM", "HEALTH",
                       "METRICS", "SWAP", "STOP", "DRAIN"}
    assert rt["PREDICT"]["semantics"] == "replayable"
    assert rt["GENERATE"]["semantics"] == "replayable"
    assert rt["DRAIN"]["semantics"] == "idempotent"
    kv = manifests["mxnet_tpu/kvstore/server.py"]
    # ISSUE 16: PULLQ (quantized pull — a read, idempotent like PULL)
    # and the elastic membership verbs JOIN/LEAVE/MEMBERS (no-op
    # mutations never bump the epoch, so replays are safe = idempotent)
    assert {"INIT", "PUSH", "PULL", "PULLQ", "SET_OPT", "BARRIER",
            "PING", "METRICS", "JOIN", "LEAVE", "MEMBERS",
            "STOP"} == set(kv)
    assert kv["METRICS"]["semantics"] == "idempotent"
    assert kv["PULLQ"]["semantics"] == "idempotent"
    assert kv["JOIN"]["semantics"] == "idempotent"
    assert kv["LEAVE"]["semantics"] == "idempotent"
    # the fleet plane's surface (ISSUE 12)
    assert "mxnet_tpu/fleet.py" in manifests
    fl = manifests["mxnet_tpu/fleet.py"]
    assert set(fl) == {"FLEET", "METRICS"}
    assert fl["FLEET"]["codec"] == "json"
