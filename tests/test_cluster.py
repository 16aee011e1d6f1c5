"""Multi-node scheduling, resources, placement groups
(ref: python/ray/tests/test_scheduling.py, test_placement_group.py)."""
import time

import pytest

import ray_tpu
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy, PlacementGroupSchedulingStrategy)


def test_multi_node_spread(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)

    @ray_tpu.remote(scheduling_strategy="SPREAD", num_cpus=1)
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    nodes = set(ray_tpu.get([where.remote() for _ in range(12)], timeout=60))
    assert len(nodes) >= 2


def test_node_affinity(ray_start_cluster):
    cluster = ray_start_cluster
    n2 = cluster.add_node(num_cpus=2)

    @ray_tpu.remote(num_cpus=1)
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    strat = NodeAffinitySchedulingStrategy(n2.node_id)
    out = ray_tpu.get(where.options(scheduling_strategy=strat).remote(), timeout=60)
    assert out == n2.node_id.hex()


def test_custom_resource(ray_start_cluster):
    cluster = ray_start_cluster
    special = cluster.add_node(num_cpus=1, resources={"accel": 2})

    @ray_tpu.remote(resources={"accel": 1}, num_cpus=0)
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    assert ray_tpu.get(where.remote(), timeout=60) == special.node_id.hex()


def test_resource_gating(ray_start_regular):
    # 4 CPUs; 2-cpu tasks -> at most 2 concurrent
    @ray_tpu.remote(num_cpus=2)
    def hold():
        time.sleep(0.6)
        return time.monotonic()

    t0 = time.monotonic()
    refs = [hold.remote() for _ in range(4)]
    ray_tpu.get(refs, timeout=60)
    elapsed = time.monotonic() - t0
    assert elapsed >= 1.0  # two waves of 0.6s


def test_placement_group_strict_spread(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)

    pg = ray_tpu.placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
    assert pg.ready(timeout=15)

    @ray_tpu.remote(num_cpus=1)
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    outs = ray_tpu.get([
        where.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
            pg, placement_group_bundle_index=i)).remote()
        for i in range(3)
    ], timeout=60)
    assert len(set(outs)) == 3
    ray_tpu.remove_placement_group(pg)


def test_placement_group_strict_pack(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=4)
    pg = ray_tpu.placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_PACK")
    assert pg.ready(timeout=15)

    @ray_tpu.remote(num_cpus=1)
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    outs = ray_tpu.get([
        where.options(scheduling_strategy=PlacementGroupSchedulingStrategy(pg)).remote()
        for _ in range(2)
    ], timeout=60)
    assert len(set(outs)) == 1


def test_placement_group_unsatisfiable_waits(ray_start_cluster):
    pg = ray_tpu.placement_group([{"CPU": 100}], strategy="PACK")
    assert not pg.ready(timeout=1.0)


def test_pg_capacity_reserved(ray_start_cluster):
    cluster = ray_start_cluster  # head has 2 CPUs
    pg = ray_tpu.placement_group([{"CPU": 2}], strategy="PACK")
    assert pg.ready(timeout=15)
    # all CPU reserved by the PG: a non-PG task cannot run...
    @ray_tpu.remote(num_cpus=1)
    def f():
        return 1

    _, pending = ray_tpu.wait([f.remote()], timeout=1.5)
    assert pending  # blocked
    # ...until the PG is removed
    ray_tpu.remove_placement_group(pg)
    ready, _ = ray_tpu.wait(pending, timeout=30)
    assert ready


def test_add_node_unparks_tasks(ray_start_cluster):
    cluster = ray_start_cluster

    @ray_tpu.remote(resources={"special": 1})
    def f():
        return "ran"

    ref = f.remote()
    _, pending = ray_tpu.wait([ref], timeout=1.0)
    assert pending
    cluster.add_node(num_cpus=1, resources={"special": 1})
    assert ray_tpu.get(ref, timeout=30) == "ran"


def test_locality_aware_scheduling(ray_start_cluster):
    """A dependent task follows its (large, store-resident) argument to
    the node holding it (ref: lease_policy.cc LocalityAwareLeasePolicy)."""
    import numpy as np

    cluster = ray_start_cluster
    n2 = cluster.add_node(num_cpus=2)

    @ray_tpu.remote(num_cpus=1)
    def produce():
        return np.zeros(1_000_000, dtype=np.uint8)  # sealed on executor

    @ray_tpu.remote(num_cpus=1)
    def consume(arr):
        assert arr.nbytes == 1_000_000
        return ray_tpu.get_runtime_context().get_node_id()

    strat = NodeAffinitySchedulingStrategy(n2.node_id)
    big = produce.options(scheduling_strategy=strat).remote()
    ray_tpu.wait([big], timeout=60)
    # default-strategy consumer should land where the bytes are
    for _ in range(3):
        out = ray_tpu.get(consume.remote(big), timeout=60)
        assert out == n2.node_id.hex()


def test_locality_loses_to_saturation(ray_start_cluster):
    """Locality only wins when the holding node has capacity NOW."""
    import numpy as np

    cluster = ray_start_cluster
    n2 = cluster.add_node(num_cpus=1)

    @ray_tpu.remote(num_cpus=1)
    def produce():
        return np.zeros(1_000_000, dtype=np.uint8)

    @ray_tpu.remote(num_cpus=1)
    def blocker(sec):
        import time as _t

        _t.sleep(sec)
        return "done"

    @ray_tpu.remote(num_cpus=1)
    def consume(arr):
        return ray_tpu.get_runtime_context().get_node_id()

    strat = NodeAffinitySchedulingStrategy(n2.node_id)
    big = produce.options(scheduling_strategy=strat).remote()
    ray_tpu.wait([big], timeout=60)
    hold = blocker.options(scheduling_strategy=strat).remote(3.0)
    import time as _t

    _t.sleep(0.3)  # let the blocker take n2's only CPU
    out = ray_tpu.get(consume.remote(big), timeout=60)
    assert out != n2.node_id.hex()  # fell through to the head node
    assert ray_tpu.get(hold, timeout=30) == "done"


class TestLauncher:
    """`ray_tpu up/down/exec` with the local provider (ref test model:
    the reference exercises commands.py against fake_multi_node)."""

    def test_up_exec_down_local_cluster(self, tmp_path, monkeypatch):
        import json
        import os
        import subprocess
        import time

        from ray_tpu.autoscaler import launcher as L

        monkeypatch.setattr(L, "STATE_DIR", str(tmp_path / "state"))
        cfg = tmp_path / "cluster.yaml"
        cfg.write_text(
            "cluster_name: launchtest\n"
            "provider:\n  type: local\n"
            "head:\n  port: 0\n  num_cpus: 2\n"
            "workers:\n  count: 2\n  num_cpus: 1\n")
        # port 0 isn't supported by the blocking head CLI (we must know
        # the port to join); pick a free one explicitly
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        cfg.write_text(
            "cluster_name: launchtest\n"
            "provider:\n  type: local\n"
            f"head:\n  port: {port}\n  num_cpus: 2\n"
            "workers:\n  count: 2\n  num_cpus: 1\n")

        state = L.cluster_up(str(cfg), wait_workers_s=90)
        try:
            assert state["address"].endswith(str(port))
            assert len(state["worker_pids"]) == 2
            # all three nodes alive through the head's control channel
            nodes = L._alive_nodes(state["address"], state["authkey"])
            assert len(nodes) == 3
            # exec on head: runner works and sees the cluster env
            out = L.exec_on_head("launchtest", "echo -n $RTPU_ADDRESS")
            assert out == state["address"]
            # a remote driver (fresh process) can run work on the cluster
            import sys

            script = (
                "import os, ray_tpu\n"
                "ray_tpu.init(address=os.environ['RTPU_ADDR'],\n"
                "             authkey=os.environ['RTPU_TOKEN'])\n"
                "@ray_tpu.remote\n"
                "def f(x):\n"
                "    return x + 1\n"
                "print(ray_tpu.get(f.remote(41), timeout=60))\n")
            env = dict(os.environ)
            env["RTPU_ADDR"] = state["address"]
            env["RTPU_TOKEN"] = state["authkey"]
            env["JAX_PLATFORMS"] = "cpu"
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
            out = subprocess.run([sys.executable, "-c", script], env=env,
                                 capture_output=True, text=True, timeout=120)
            assert out.returncode == 0, out.stderr
            assert out.stdout.strip() == "42"
        finally:
            L.cluster_down("launchtest")
        # processes are gone
        time.sleep(1.0)
        for pid in [state["head_pid"], *state["worker_pids"]]:
            try:
                os.kill(int(pid), 0)
                alive = True
            except ProcessLookupError:
                alive = False
            assert not alive, f"pid {pid} survived cluster_down"
        # state file removed
        assert not os.path.exists(
            os.path.join(L.STATE_DIR, "launchtest.json"))


# ---- one process per chip (core/worker_env.py) ------------------------------

def test_only_a_tpu_lease_can_reach_the_chip(monkeypatch):
    """With nothing pinning the platform from outside, a worker whose
    lease holds no TPU is born pinned to the CPU; one whose lease holds
    TPU inherits the parent's (unpinned) choice and the compile cache."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        @ray_tpu.remote(num_cpus=1)
        def env_of():
            import os
            return (os.environ.get("JAX_PLATFORMS"),
                    os.environ.get("JAX_COMPILATION_CACHE_DIR"), os.getpid())

        host = ray_tpu.get(env_of.remote(), timeout=60)
        chip = ray_tpu.get(env_of.options(num_tpus=1).remote(), timeout=60)
        assert host[0] == "cpu"
        assert chip[0] is None
        assert chip[1] and chip[1].endswith(".jax_cache")
        assert host[2] != chip[2]
    finally:
        ray_tpu.shutdown()


def test_no_tpu_resource_no_chip_worker(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        class A:
            def platform(self):
                import os
                return os.environ.get("JAX_PLATFORMS")

        @ray_tpu.remote(num_cpus=1)
        def platform():
            import os
            return os.environ.get("JAX_PLATFORMS")

        assert ray_tpu.get(platform.remote(), timeout=60) == "cpu"
        assert ray_tpu.get(A.remote().platform.remote(), timeout=60) == "cpu"
    finally:
        ray_tpu.shutdown()


def test_two_tpu_actors_never_live_at_once():
    """num_tpus=1: the second actor's process is started only after the
    first one's process has exited, whatever order kill and create race in."""
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        @ray_tpu.remote(num_tpus=1, num_cpus=1)
        class Holder:
            def __init__(self, other_pid=None):
                import os
                self.other_alive = (other_pid is not None
                                    and os.path.exists(f"/proc/{other_pid}"))

            def pid(self):
                import os
                return os.getpid()

            def saw_other_alive(self):
                return self.other_alive

        a = Holder.remote()
        a_pid = ray_tpu.get(a.pid.remote(), timeout=60)
        b = Holder.remote(a_pid)
        ready, _ = ray_tpu.wait([b.pid.remote()], timeout=1.0)
        assert not ready  # the chip is taken: b waits
        ray_tpu.kill(a)
        b_pid = ray_tpu.get(b.pid.remote(), timeout=60)
        assert b_pid != a_pid
        assert ray_tpu.get(b.saw_other_alive.remote(), timeout=60) is False
    finally:
        ray_tpu.shutdown()
