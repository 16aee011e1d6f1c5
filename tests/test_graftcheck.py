"""graftcheck linter + instrumented-lock detector tests.

One positive and one negative fixture per rule GC001-GC006, suppression
coverage, CLI behavior, and the runtime lock-order/long-hold detectors.
"""
import json
import os
import threading
import time

import pytest

from ray_tpu.devtools import graftcheck
from ray_tpu.devtools import locks as lockmod


def rules_found(src: str):
    return sorted({f.rule for f in graftcheck.check_source(src, "fix.py")})


# ---------------------------------------------------------------------------
# GC001 — blocking get() inside remote bodies


def test_gc001_positive_nested_get():
    src = """
import ray_tpu

@ray_tpu.remote
def outer(ref):
    return ray_tpu.get(ref)
"""
    assert rules_found(src) == ["GC001"]


def test_gc001_positive_actor_method_and_bare_import():
    src = """
import ray_tpu
from ray_tpu import get

@ray_tpu.remote
class A:
    def m(self, ref):
        return get(ref)
"""
    assert rules_found(src) == ["GC001"]


def test_gc001_negative_driver_get_and_dict_get():
    src = """
import ray_tpu

def driver(ref):
    return ray_tpu.get(ref)          # not a remote scope

@ray_tpu.remote
def task(d):
    return d.get("key")              # dict.get, not runtime.get
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC002 — unserializable closure capture


def test_gc002_positive_module_lock_capture():
    src = """
import threading
import ray_tpu

_LOCK = threading.Lock()

@ray_tpu.remote
def task():
    with _LOCK:
        return 1
"""
    assert rules_found(src) == ["GC002"]


def test_gc002_negative_local_lock():
    src = """
import threading
import ray_tpu

_LOCK = threading.Lock()

@ray_tpu.remote
def task():
    _LOCK = threading.Lock()         # local shadow: created in the worker
    with _LOCK:
        return 1

def driver():
    with _LOCK:                      # non-remote scope: fine
        return 2
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC003 — module-global mutation from task bodies


def test_gc003_positive_global_write():
    src = """
import ray_tpu

COUNTER = 0

@ray_tpu.remote
def bump():
    global COUNTER
    COUNTER += 1
"""
    assert rules_found(src) == ["GC003"]


def test_gc003_negative_global_read_only():
    src = """
import ray_tpu

LIMIT = 10

@ray_tpu.remote
def check(x):
    global LIMIT
    return x < LIMIT
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC004 — time.sleep on the actor event loop


def test_gc004_positive_async_sleep():
    src = """
import time
import ray_tpu

@ray_tpu.remote
class A:
    async def tick(self):
        time.sleep(0.5)
"""
    assert rules_found(src) == ["GC004"]


def test_gc004_negative_sync_sleep_and_asyncio():
    src = """
import asyncio
import time
import ray_tpu

@ray_tpu.remote
class A:
    def sync_method(self):
        time.sleep(0.5)              # sync method: worker thread, fine

    async def tick(self):
        await asyncio.sleep(0.5)
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC005 — bare except swallowing framework errors


def test_gc005_positive_bare_except():
    src = """
import ray_tpu

def poll(ref):
    try:
        return ray_tpu.get(ref)
    except:
        return None
"""
    assert rules_found(src) == ["GC005"]


def test_gc005_negative_reraise_and_typed():
    src = """
import ray_tpu

def poll(ref):
    try:
        return ray_tpu.get(ref)
    except ray_tpu.exceptions.TaskError:
        return None

def cleanup(ref):
    try:
        return ray_tpu.get(ref)
    except:
        release_things()
        raise
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC006 — manual lock handling


def test_gc006_positive_unprotected_acquire():
    src = """
import threading

lock = threading.Lock()

def work():
    lock.acquire()
    do_stuff()
    lock.release()
"""
    assert rules_found(src) == ["GC006"]


def test_gc006_negative_timed_acquire_guard():
    src = """
import threading

lock = threading.Lock()

def timed():
    got = lock.acquire(timeout=5)
    if got:
        try:
            do_stuff()
        finally:
            lock.release()
"""
    assert rules_found(src) == []


def test_gc006_negative_with_and_try_finally():
    src = """
import threading

lock = threading.Lock()

def good_with():
    with lock:
        do_stuff()

def good_try():
    lock.acquire()
    try:
        do_stuff()
    finally:
        lock.release()
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC008 — dynamic calls inside compiled-graph-bound methods


def test_gc008_positive_remote_in_bound_method():
    src = """
import ray_tpu
from ray_tpu.cgraph import InputNode

@ray_tpu.remote
def helper(x):
    return x

@ray_tpu.remote
class Stage:
    def fwd(self, x):
        return helper.remote(x)      # dynamic submission in the loop

with InputNode() as inp:
    dag = stage.fwd.bind(inp)
"""
    assert rules_found(src) == ["GC008"]


def test_gc008_positive_blocking_get_in_bound_method():
    src = """
import ray_tpu

@ray_tpu.remote
class Stage:
    def fwd(self, ref):
        return ray_tpu.get(ref)

dag = stage.fwd.bind(inp)
"""
    # both rules fire: the method is a remote scope (GC001) AND bound
    # into a compiled graph (GC008)
    assert rules_found(src) == ["GC001", "GC008"]


def test_gc008_negative_unbound_method_and_plain_bind():
    src = """
import ray_tpu

@ray_tpu.remote
def helper(x):
    return x

@ray_tpu.remote
class Stage:
    def fwd(self, x):
        return x + 1                 # bound, but pure compute

    def dynamic(self, x):
        return helper.remote(x)      # dynamic, but never bound

dag = stage.fwd.bind(inp)
sock.bind(("127.0.0.1", 0))          # not a method-node bind
"""
    assert rules_found(src) == []


def test_gc008_negative_bind_on_non_actor_class():
    src = """
class Plain:
    def fwd(self, x):
        return helper.remote(x)      # not an actor method: GC008 n/a

dag = stage.fwd.bind(inp)
"""
    assert rules_found(src) == []


def test_gc008_negative_same_name_on_unrelated_class():
    src = """
import ray_tpu

@ray_tpu.remote
class Pipeline:
    def step(self, x):
        return x + 1                 # bound below via a Pipeline handle

@ray_tpu.remote
class Unrelated:
    def step(self, x):
        return helper.remote(x)      # same NAME, different class: clean

stage = Pipeline.remote()
dag = stage.step.bind(inp)
"""
    assert rules_found(src) == []


def test_gc008_positive_options_chain_handle():
    src = """
import ray_tpu

@ray_tpu.remote
class Pipeline:
    def step(self, x):
        return helper.remote(x)

stage = Pipeline.options(num_cpus=2).remote()
dag = stage.step.bind(inp)
"""
    assert rules_found(src) == ["GC008"]


def test_gc008_suppression():
    src = """
import ray_tpu

@ray_tpu.remote
class Stage:
    def fwd(self, x):
        return helper.remote(x)  # graftcheck: disable=GC008

dag = stage.fwd.bind(inp)
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC009 — blocking calls inside async serve deployment methods


def test_gc009_positive_blocking_get_in_async_method():
    src = """
import ray_tpu
from ray_tpu import serve

@serve.deployment
class Ingress:
    async def __call__(self, x):
        ref = self.downstream.remote(x)
        return ray_tpu.get(ref)
"""
    assert rules_found(src) == ["GC009"]


def test_gc009_positive_sync_handle_result():
    src = """
from ray_tpu import serve

@serve.deployment(num_replicas=2)
class Ingress:
    async def handler(self, x):
        return self.h.remote(x).result()
"""
    assert rules_found(src) == ["GC009"]


def test_gc009_positive_sync_helper_called_inline():
    # a nested def inside the async method inherits the event-loop
    # context — calling it inline still stalls the loop
    src = """
import ray_tpu
from ray_tpu import serve

@serve.deployment
class Ingress:
    async def __call__(self, x):
        def helper(ref):
            return ray_tpu.get(ref)
        return helper(self.h.remote(x))
"""
    assert rules_found(src) == ["GC009"]


def test_gc009_negative_sync_method_and_await():
    src = """
import ray_tpu
from ray_tpu import serve

@serve.deployment
class Ingress:
    def sync_call(self, x):
        return ray_tpu.get(self.h.remote(x))   # sync method: no loop

    async def good(self, x):
        return await self.h.remote(x)          # awaited: clean
"""
    assert rules_found(src) == []


def test_gc009_negative_async_method_outside_deployment():
    src = """
import ray_tpu

class NotADeployment:
    async def __call__(self, x):
        return ray_tpu.get(self.h.remote(x))
"""
    assert rules_found(src) == []


def test_gc009_options_chain_decorator():
    src = """
import ray_tpu
from ray_tpu import serve

@serve.deployment(num_replicas=2).options(max_ongoing_requests=4)
class Ingress:
    async def __call__(self, x):
        return ray_tpu.get(self.h.remote(x))
"""
    assert rules_found(src) == ["GC009"]


def test_gc009_suppression():
    src = """
import ray_tpu
from ray_tpu import serve

@serve.deployment
class Ingress:
    async def __call__(self, x):
        return ray_tpu.get(self.h.remote(x))  # graftcheck: disable=GC009
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# GC012 — unbounded bare retry loops


def test_gc012_positive_remote_retry_without_bound():
    src = """
def keep_calling(handle):
    while True:
        try:
            return_ref = handle.ping.remote()
        except Exception:
            continue
"""
    assert rules_found(src) == ["GC012"]


def test_gc012_positive_connect_with_constant_sleep():
    src = """
import time
from ray_tpu.core.rpc import connect

def join(addr):
    while True:
        try:
            return connect(addr)
        except OSError:
            time.sleep(0.5)
"""
    # a fixed sleep paces the hammering but never bounds it
    assert rules_found(src) == ["GC012"]


def test_gc012_negative_deadline_bound():
    src = """
import time
from ray_tpu.core.rpc import connect

def join(addr, timeout=30.0):
    deadline = time.monotonic() + timeout
    while True:
        try:
            return connect(addr)
        except OSError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
"""
    assert rules_found(src) == []


def test_gc012_negative_policy_and_growing_backoff():
    src_policy = """
from ray_tpu.util.retry import RetryPolicy
from ray_tpu.core.rpc import connect

def join(addr):
    for attempt in RetryPolicy(deadline_s=30).sleeps():
        try:
            return connect(addr)
        except OSError:
            continue
    raise TimeoutError(addr)
"""
    assert rules_found(src_policy) == []
    src_backoff = """
import time
from ray_tpu.core.rpc import connect

def join(addr):
    delay = 0.1
    while True:
        try:
            return connect(addr)
        except OSError:
            time.sleep(delay)
            delay = min(delay * 2, 5.0)
"""
    # variable sleep = a backoff the author grows; GC012 stays quiet
    assert rules_found(src_backoff) == []


def test_gc012_negative_handler_reraises_or_breaks():
    src = """
def drain(handle):
    while True:
        try:
            handle.step.remote()
        except Exception:
            raise
"""
    assert rules_found(src) == []
    src_break = """
def drain(handle):
    while True:
        try:
            handle.step.remote()
        except Exception:
            break
"""
    assert rules_found(src_break) == []


def test_gc012_negative_non_remote_loop_body():
    src = """
def pump(q):
    while True:
        try:
            q.put(1)
        except Exception:
            continue
"""
    assert rules_found(src) == []


def test_gc012_suppression():
    src = """
def keep_calling(handle):
    while True:
        try:  # graftcheck: disable=GC012
            handle.ping.remote()
        except Exception:
            continue
"""
    assert rules_found(src) == []


# ---------------------------------------------------------------------------
# suppressions + CLI


def test_suppression_same_line_and_file_wide():
    src = """
import ray_tpu

@ray_tpu.remote
def a(ref):
    return ray_tpu.get(ref)  # graftcheck: disable=GC001
"""
    assert rules_found(src) == []
    src_file_wide = """
# graftcheck: disable-file=GC001
import ray_tpu

@ray_tpu.remote
def a(ref):
    return ray_tpu.get(ref)

@ray_tpu.remote
def b(ref):
    return ray_tpu.get(ref)
"""
    assert rules_found(src_file_wide) == []


def test_suppression_with_trailing_justification():
    src = """
import ray_tpu

@ray_tpu.remote
def a(ref):
    return ray_tpu.get(ref)  # graftcheck: disable=GC001 bounded depth
"""
    assert rules_found(src) == []


def test_suppression_preceding_comment_line():
    src = """
import ray_tpu

@ray_tpu.remote
def a(ref):
    # graftcheck: disable=GC001
    return ray_tpu.get(ref)
"""
    assert rules_found(src) == []


def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import ray_tpu\n"
        "@ray_tpu.remote\n"
        "def f(r):\n"
        "    return ray_tpu.get(r)\n")
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")

    assert graftcheck.main([str(good)]) == 0
    assert graftcheck.main([str(bad)]) == 1
    capsys.readouterr()
    assert graftcheck.main(["--json", str(bad)]) == 1
    out = json.loads(capsys.readouterr().out)
    assert len(out) == 1 and out[0]["rule"] == "GC001" \
        and out[0]["line"] == 4


def test_cli_rule_filter(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import ray_tpu\n"
        "@ray_tpu.remote\n"
        "def f(r):\n"
        "    return ray_tpu.get(r)\n")
    assert graftcheck.main(["--rules", "GC006", str(bad)]) == 0
    assert graftcheck.main(["--rules", "GC001", str(bad)]) == 1


# ---------------------------------------------------------------------------
# instrumented locks


@pytest.fixture
def debug_locks(monkeypatch):
    monkeypatch.setenv("RAY_TPU_DEBUG_LOCKS", "1")
    lockmod.reset_lock_state()
    yield
    lockmod.reset_lock_state()


def test_factory_returns_plain_locks_when_disabled(monkeypatch):
    monkeypatch.delenv("RAY_TPU_DEBUG_LOCKS", raising=False)
    lk = lockmod.instrumented_lock("x")
    assert not isinstance(lk, lockmod.InstrumentedLock)
    with lk:
        pass
    rlk = lockmod.instrumented_lock("y", reentrant=True)
    with rlk:
        with rlk:
            pass


def test_lock_order_inversion_detected(debug_locks):
    """Two threads, opposite acquisition order -> inversion report."""
    a = lockmod.instrumented_lock("lock.a")
    b = lockmod.instrumented_lock("lock.b")
    assert isinstance(a, lockmod.InstrumentedLock)

    def order_ab():
        with a:
            with b:
                pass

    def order_ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=order_ab)
    t1.start()
    t1.join(timeout=30)
    assert lockmod.get_lock_reports() == []  # one order alone is fine

    t2 = threading.Thread(target=order_ba)
    t2.start()
    t2.join(timeout=30)
    reports = lockmod.get_lock_reports()
    assert any(r.kind == "lock-order-inversion" for r in reports)
    inv = next(r for r in reports if r.kind == "lock-order-inversion")
    assert set(inv.locks) == {"lock.a", "lock.b"}
    assert inv.stacks.get("this_acquisition")


def test_no_inversion_for_consistent_order(debug_locks):
    a = lockmod.instrumented_lock("ord.a")
    b = lockmod.instrumented_lock("ord.b")
    for _ in range(3):
        with a:
            with b:
                pass
    assert [r for r in lockmod.get_lock_reports()
            if r.kind == "lock-order-inversion"] == []


def test_reentrant_lock_no_self_report(debug_locks):
    r = lockmod.instrumented_lock("reent", reentrant=True)
    with r:
        with r:
            pass
    assert lockmod.get_lock_reports() == []


def test_long_hold_reported(debug_locks, monkeypatch):
    monkeypatch.setenv("RAY_TPU_LOCK_HOLD_WARN_S", "0.05")
    lk = lockmod.instrumented_lock("slow.lock")
    with lk:
        time.sleep(0.12)
    reports = lockmod.get_lock_reports()
    assert any(r.kind == "long-hold" and "slow.lock" in r.locks
               for r in reports)


def test_three_lock_cycle_detected(debug_locks):
    """Inversions across a chain (a->b, b->c, then c->a) are caught even
    though no single pair is ever taken in both orders."""
    a = lockmod.instrumented_lock("tri.a")
    b = lockmod.instrumented_lock("tri.b")
    c = lockmod.instrumented_lock("tri.c")

    def run(first, second):
        t = threading.Thread(target=lambda: _nest(first, second))
        t.start()
        t.join(timeout=30)

    def _nest(x, y):
        with x:
            with y:
                pass

    run(a, b)
    run(b, c)
    assert lockmod.get_lock_reports() == []
    run(c, a)
    reports = lockmod.get_lock_reports()
    assert any(r.kind == "lock-order-inversion" for r in reports)
