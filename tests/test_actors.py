"""Actor semantics (ref: python/ray/tests/test_actor*.py)."""
import time

import pytest

import ray_tpu
from ray_tpu import exceptions


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def incr(self, k=1):
        self.n += k
        return self.n

    def read(self):
        return self.n

    def fail(self):
        raise RuntimeError("actor method failure")


def test_actor_basic(ray_start_regular):
    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote(5), timeout=60) == 15
    assert ray_tpu.get(c.read.remote(), timeout=60) == 15


def test_actor_ordering(ray_start_regular):
    c = Counter.remote(0)
    refs = [c.incr.remote() for _ in range(30)]
    assert ray_tpu.get(refs, timeout=60) == list(range(1, 31))


def test_actor_method_error(ray_start_regular):
    c = Counter.remote(0)
    with pytest.raises(exceptions.TaskError):
        ray_tpu.get(c.fail.remote(), timeout=60)
    # actor survives method errors
    assert ray_tpu.get(c.read.remote(), timeout=60) == 0


def test_actor_init_error(ray_start_regular):
    @ray_tpu.remote
    class Broken:
        def __init__(self):
            raise ValueError("bad init")

        def f(self):
            return 1

    b = Broken.remote()
    with pytest.raises((exceptions.TaskError, exceptions.ActorDiedError)):
        ray_tpu.get(b.f.remote(), timeout=30)


def test_named_actor(ray_start_regular):
    Counter.options(name="counter1").remote(7)
    h = ray_tpu.get_actor("counter1")
    assert ray_tpu.get(h.read.remote(), timeout=60) == 7
    with pytest.raises(ValueError):
        ray_tpu.get_actor("no_such_actor")


def test_kill_actor(ray_start_regular):
    c = Counter.remote(0)
    ray_tpu.get(c.read.remote(), timeout=60)
    ray_tpu.kill(c)
    with pytest.raises(exceptions.ActorDiedError):
        ray_tpu.get(c.read.remote(), timeout=30)


def test_actor_handle_in_task(ray_start_regular):
    c = Counter.remote(0)

    @ray_tpu.remote
    def bump(h, k):
        return ray_tpu.get(h.incr.remote(k), timeout=60)  # graftcheck: disable=GC001

    assert ray_tpu.get(bump.remote(c, 42), timeout=60) == 42


def test_actor_creates_actor(ray_start_regular):
    @ray_tpu.remote
    class Parent:
        def spawn(self):
            child = Counter.remote(99)
            return ray_tpu.get(child.read.remote(), timeout=60)  # graftcheck: disable=GC001

    p = Parent.remote()
    assert ray_tpu.get(p.spawn.remote(), timeout=60) == 99


def test_threaded_actor(ray_start_regular):
    @ray_tpu.remote(max_concurrency=4)
    class Slow:
        def __init__(self):
            import threading

            self.lock = threading.Lock()
            self.inflight = self.peak = 0

        def work(self, t):
            with self.lock:
                self.inflight += 1
                self.peak = max(self.peak, self.inflight)
            time.sleep(t)
            with self.lock:
                self.inflight -= 1
            return t

        def peak_inflight(self):
            return self.peak

    s = Slow.remote()
    refs = [s.work.remote(0.5) for _ in range(4)]
    assert ray_tpu.get(refs, timeout=60) == [0.5] * 4
    # the calls overlapped inside the actor (an elapsed time says the
    # same only on an idle machine)
    assert 2 <= ray_tpu.get(s.peak_inflight.remote(), timeout=60) <= 4


def test_async_actor(ray_start_regular):
    @ray_tpu.remote(max_concurrency=8)
    class Async:
        def __init__(self):
            self.inflight = self.peak = 0

        async def aget(self, x):
            import asyncio

            self.inflight += 1
            self.peak = max(self.peak, self.inflight)
            await asyncio.sleep(0.2)
            self.inflight -= 1
            return x * 2

        async def peak_inflight(self):
            return self.peak

    a = Async.remote()
    out = ray_tpu.get([a.aget.remote(i) for i in range(5)], timeout=60)
    assert out == [0, 2, 4, 6, 8]
    # the five awaits overlapped on the actor's loop
    assert 2 <= ray_tpu.get(a.peak_inflight.remote(), timeout=60) <= 5


def test_get_if_exists(ray_start_regular):
    a = Counter.options(name="singleton", get_if_exists=True).remote(3)
    b = Counter.options(name="singleton", get_if_exists=True).remote(1000)
    ray_tpu.get(a.incr.remote(), timeout=60)
    # b is the same actor
    assert ray_tpu.get(b.read.remote(), timeout=60) == 4
