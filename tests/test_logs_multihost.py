"""The forward_logs leg across a real process boundary: a remote node
agent's workers tee stdout/stderr up the TCP channel, the head indexes
them attributed, mirrors them onto the driver console, and the stack
fan-out reaches remote workers through the agent relay (satellite:
coverage for the `_StreamTee`/forward_logs path)."""
import re
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import state
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(head_resources={"CPU": 2.0})
    remote = c.add_remote_node(num_cpus=2.0)
    yield c, remote
    c.shutdown()


def _pin(node):
    return NodeAffinitySchedulingStrategy(node_id=node.node_id, soft=False)


def _wait_for(pred, timeout=20.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(interval)
    return pred()


def test_remote_worker_stdout_reaches_driver_intact(cluster, capsys):
    c, remote = cluster

    @ray_tpu.remote
    def remote_talker():
        for i in range(10):
            print(f"remote-intact-{i:02d}")
        import sys

        sys.stderr.write("remote-err-line\n")
        return ray_tpu.get_runtime_context().get_node_id()

    nid = ray_tpu.get(remote_talker.options(
        scheduling_strategy=_pin(remote)).remote(), timeout=60)
    assert nid == remote.node_id.hex()

    def stored():
        recs = [r for r in state.logs(node_id=nid, limit=2000)["records"]
                if r["line"].startswith("remote-intact-")]
        return recs if len(recs) == 10 else None

    recs = _wait_for(stored)
    assert recs, "remote lines never reached the head store"
    assert [r["line"] for r in recs] == \
        [f"remote-intact-{i:02d}" for i in range(10)]
    for r in recs:
        assert r["node_id"] == nid
        assert r["worker_id"] and r["task_id"]
        assert r["stream"] == "stdout"
    # seq numbers are monotonic per stream across the channel
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    errs = [r for r in state.logs(node_id=nid, stream="stderr",
                                  limit=500)["records"]
            if r["line"] == "remote-err-line"]
    assert errs and errs[0]["task_id"] == recs[0]["task_id"]
    # driver mirroring: the provenance-prefixed copy reached this
    # process's console (the log_to_driver surface)
    out = capsys.readouterr().out
    assert re.search(r"\(worker pid=\d+, node=[0-9a-f]{8}\).*"
                     r"remote-intact-00", out), out[-2000:]


def test_remote_concurrent_writers_no_shear(cluster):
    c, remote = cluster

    @ray_tpu.remote
    def storm():
        import threading as th

        def writer(i):
            for j in range(25):
                print(f"rs{i:02d}-{j:03d}-" + "q" * 16)

        ts = [th.Thread(target=writer, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        return 1

    assert ray_tpu.get(storm.options(
        scheduling_strategy=_pin(remote)).remote(), timeout=60) == 1

    def intact():
        lines = {r["line"] for r in state.logs(limit=10000)["records"]
                 if re.fullmatch(r"rs\d{2}-\d{3}-q{16}", r["line"])}
        return lines if len(lines) == 6 * 25 else None

    mine = _wait_for(intact)
    assert mine and len(mine) == 6 * 25, \
        f"expected 150 distinct intact lines, got {len(mine or ())}"


def test_stack_report_covers_remote_workers(cluster):
    c, remote = cluster

    @ray_tpu.remote
    def linger():
        time.sleep(3)
        return 1

    ref = linger.options(scheduling_strategy=_pin(remote)).remote()
    time.sleep(0.8)
    rep = state.stack_report(timeout=5.0)
    remote_rows = [w for w in rep["workers"]
                   if w.get("node_id") == remote.node_id.hex()]
    assert remote_rows, rep["workers"]
    ok = [w for w in remote_rows if not w.get("error")]
    assert ok, remote_rows
    joined = "\n".join(fr for w in ok for th in w.get("threads", [])
                       for fr in th["frames"])
    assert "linger" in joined or "sleep" in joined
    ray_tpu.get(ref, timeout=60)


def test_agent_keeps_local_log_ring(cluster):
    """The agent's bounded per-worker ring serves a local tail even
    independent of the head store (post-mortem / eviction triage)."""
    c, remote = cluster

    @ray_tpu.remote
    def ring_talker():
        print("ring-proof-line")
        return 1

    assert ray_tpu.get(ring_talker.options(
        scheduling_strategy=_pin(remote)).remote(), timeout=60) == 1

    def ring():
        rows = remote.channel.call("agent_logs", {"limit": 1000},
                                   timeout=10)
        mine = [r for r in rows
                if r["rec"][-1] == "ring-proof-line"]
        return mine or None

    rows = _wait_for(ring)
    assert rows and rows[0]["worker_id"]
