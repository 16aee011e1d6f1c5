"""Scale-envelope smoke tests (SURVEY §6: 10k+ concurrent tasks, 1k+
PGs, 1M queued — scaled to CI size). These exist to catch the envelope's
first casualties: polling loops, per-waiter wakeup storms, O(N^2) queue
scans (ref test model: release/benchmarks/ many_tasks / many_pgs)."""
import threading
import time

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def _routed_task_rate(n=2000):
    """Tasks/s of a small burst through the head, on this machine as busy
    as it is right now: the scale of every throughput bound below. An
    absolute rate holds on an idle box of one class only (the same code
    read 1.0-5.4k tasks/s across hosts and loads); what a regression of
    the envelope breaks is the ratio to a small burst: a polling loop, a
    wakeup storm or an O(queue^2) scan slows the deep queue, not this."""
    @ray_tpu.remote(num_cpus=0.001)
    def tiny(i):
        return i

    t0 = time.monotonic()
    out = ray_tpu.get([tiny.remote(i) for i in range(n)], timeout=240)
    assert out == list(range(n))
    return n / (time.monotonic() - t0)


def test_ten_thousand_tasks_complete(cluster):
    @ray_tpu.remote(num_cpus=0.001)
    def tiny(i):
        return i

    small = _routed_task_rate()
    t0 = time.monotonic()
    refs = [tiny.remote(i) for i in range(10000)]
    out = ray_tpu.get(refs, timeout=240)
    dt = time.monotonic() - t0
    assert out == list(range(10000))
    rate = 10000 / dt
    print(f"10k tasks at {rate:.0f}/s (2k burst: {small:.0f}/s)")
    # measured 0.7-1.1x the small burst, idle and under six test workers
    assert rate > small / 3, \
        f"10k tasks ran at {rate:.0f}/s, a 2k burst at {small:.0f}/s"


@pytest.mark.time_limit(450)  # 96 s alone, 150 s beside five workers
def test_hundred_thousand_queued_tasks(cluster):
    """The reference's envelope claims 1M+ queued (release/benchmarks);
    this pins a 100k burst: bucketed dispatch + lease reuse must hold
    throughput, not degrade O(queue^2)."""
    @ray_tpu.remote(num_cpus=0.001)
    def tiny(i):
        return i

    small = _routed_task_rate()
    t0 = time.monotonic()
    refs = [tiny.remote(i) for i in range(100000)]
    out = ray_tpu.get(refs, timeout=400)
    dt = time.monotonic() - t0
    assert out == list(range(100000))
    rate = 100000 / dt
    print(f"100k queued at {rate:.0f}/s (2k burst: {small:.0f}/s)")
    # throughput holds: measured 0.6-1.0x the small burst
    assert rate > small / 3, \
        f"100k queued ran at {rate:.0f}/s, a 2k burst at {small:.0f}/s"


def test_many_concurrent_waiters_wake_evently(cluster):
    """200 threads each parked in wait() on a distinct object: every one
    must wake when its object (and only then) completes — the
    event-driven wait path under fan-out (the old 2 ms polling loop
    burned a core per waiter here)."""
    @ray_tpu.remote(num_cpus=0.01)
    def produce(i):
        time.sleep(0.05)
        return i

    refs = [produce.remote(i) for i in range(200)]
    results = {}
    lock = threading.Lock()

    def waiter(i, ref):
        ready, pending = ray_tpu.wait([ref], timeout=120)
        with lock:
            results[i] = (len(ready), len(pending))

    threads = [threading.Thread(target=waiter, args=(i, r))
               for i, r in enumerate(refs)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(results.get(i) == (1, 0) for i in range(200)), \
        {i: results.get(i) for i in range(200)
         if results.get(i) != (1, 0)}
    assert time.monotonic() - t0 < 90


def test_many_placement_groups_lifecycle(cluster):
    from ray_tpu.core.placement_group import (placement_group,
                                              remove_placement_group)

    t0 = time.monotonic()
    pgs = [placement_group([{"CPU": 0.001}]) for _ in range(1000)]
    ready = sum(1 for pg in pgs if pg.ready(timeout=120))
    dt = time.monotonic() - t0
    assert ready == 1000
    # the single-placer design places a 1k burst in well under a second;
    # anything superlinear (per-commit rescan storms) blows this budget
    assert dt < 60, f"1000 PGs took {dt:.1f}s"
    for pg in pgs:
        remove_placement_group(pg)


def test_direct_actor_call_envelope(cluster):
    """ISSUE 6: steady-state actor calls ride the direct path (zero head
    submissions) and the pipelined rate pins the decentralized-dispatch
    win — ~3x the r5 routed actor-call rate on the same host class."""
    from ray_tpu.core.runtime import dispatch_counts

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    ray_tpu.get(c.inc.remote(), timeout=60)
    n = 3000
    ratios = []
    # the counters pin "all direct"; the rate pins that direct is worth
    # having: well above the head-routed path on the same machine at the
    # same moment (measured 2.5-5.2x it, idle and under load). The two
    # bursts are a second apart and six test workers share the cores (one
    # pair in eleven read 1.31x), so a pair that falls short is measured
    # again: a direct path no faster than the routed one fails all three
    for _ in range(3):
        routed = _routed_task_rate()
        first = ray_tpu.get(c.inc.remote(), timeout=60) + 1
        d0, r0 = dispatch_counts()
        t0 = time.monotonic()
        out = ray_tpu.get([c.inc.remote() for _ in range(n)], timeout=240)
        dt = time.monotonic() - t0
        assert out == list(range(first, first + n))
        d1, r1 = dispatch_counts()
        assert d1 - d0 == n and r1 - r0 == 0, \
            f"steady state must be all-direct (direct={d1-d0} routed={r1-r0})"
        ratios.append(n / dt / routed)
        print(f"direct actor calls at {n / dt:.0f}/s, {ratios[-1]:.2f}x routed "
              f"tasks ({routed:.0f}/s)")
        if ratios[-1] > 1.5:
            break
    assert max(ratios) > 1.5, \
        f"pipelined direct actor calls ran at {ratios} times the routed rate"
    ray_tpu.kill(c)


def test_deep_queue_drains_in_order_per_actor(cluster):
    """One actor, 5000 queued calls: seq-ordered execution survives a
    deep backlog."""
    @ray_tpu.remote
    class Seq:
        def __init__(self):
            self.n = 0

        def next(self):
            self.n += 1
            return self.n

    a = Seq.remote()
    refs = [a.next.remote() for _ in range(5000)]
    out = ray_tpu.get(refs, timeout=240)
    assert out == list(range(1, 5001))
    ray_tpu.kill(a)


def test_wait_num_returns_contract_at_scale(cluster):
    """wait() returns AT MOST num_returns ready entries even when many
    more are already complete (the ray.wait contract)."""
    @ray_tpu.remote(num_cpus=0.01)
    def now(i):
        return i

    refs = [now.remote(i) for i in range(64)]
    ray_tpu.get(refs, timeout=60)  # all complete
    ready, pending = ray_tpu.wait(refs, num_returns=5, timeout=10)
    assert len(ready) == 5 and len(pending) == 59
