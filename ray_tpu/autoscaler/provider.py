"""Node providers — how the autoscaler actually adds/removes capacity.

ref: python/ray/autoscaler/node_provider.py NodeProvider interface;
_private/fake_multi_node/node_provider.py FakeMultiNodeProvider (spawns
real local raylets for tests — here: real node_agent processes).
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..core.ids import NodeId
from ..util.retry import RetryPolicy


class NodeProvider:
    """Launch/terminate slice agents. Implementations must be idempotent:
    the reconcile loop may retry either direction after failures."""

    def create_node(self) -> NodeId:
        raise NotImplementedError

    def terminate_node(self, node_id: NodeId) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> List[NodeId]:
        raise NotImplementedError

    def node_resources(self) -> Dict[str, float]:
        """Resources one launched node contributes (for demand planning)."""
        raise NotImplementedError

    def poll_preemptions(self) -> List[Tuple[NodeId, float]]:
        """Preemption notices since the last poll: ``(node_id,
        grace_s)`` pairs meaning the platform kills that node in
        ``grace_s`` seconds. Each notice is delivered AT MOST ONCE —
        the autoscaler's reconcile pass turns it into a
        ``NODE_PREEMPTING`` GCS event and starts the drain
        (docs/FAULT_TOLERANCE.md "Elasticity")."""
        return []


class FakeSliceProvider(NodeProvider):
    """Spawns local `ray_tpu.core.node_agent` processes as fake slices —
    scale-up/down logic runs for real in CI without cloud credentials
    (ref: fake_multi_node/node_provider.py)."""

    # join-wait poll cadence (util/retry.py): fixed fast polls with a
    # hard deadline rather than a hand-rolled while/sleep loop
    _JOIN_WAIT = RetryPolicy(initial_backoff_s=0.05, multiplier=1.0,
                             max_backoff_s=0.05, jitter=0.0,
                             deadline_s=30.0)

    def __init__(self, runtime, resources_per_node: Optional[Dict] = None):
        self.runtime = runtime
        self._resources = dict(resources_per_node or {"CPU": 2.0})
        self._procs: Dict[NodeId, subprocess.Popen] = {}
        self._lock = threading.Lock()
        self._addr = runtime.enable_remote_nodes()
        # scheduled preemptions: node_id -> (notice_at, grace_s,
        # delivered) — the fake platform's maintenance calendar
        self._preempt_sched: Dict[NodeId, list] = {}

    def node_resources(self) -> Dict[str, float]:
        return dict(self._resources)

    def create_node(self) -> NodeId:
        node_id = NodeId.from_random()
        res = dict(self._resources)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.node_agent",
             "--address", f"{self._addr[0]}:{self._addr[1]}",
             "--num-cpus", str(res.pop("CPU", 1.0)),
             "--resources", json.dumps(res),
             "--labels", json.dumps({"autoscaled": "1"}),
             "--node-id", node_id.hex()],
            env=env)
        with self._lock:
            self._procs[node_id] = proc
        for _attempt in self._JOIN_WAIT.sleeps():
            node = self.runtime.nodes.get(node_id)
            if node is not None:
                # chaos preempt schedules / Cluster.remove_node reach the
                # agent process through the node handle
                node._agent_proc = proc
                return node_id
            if proc.poll() is not None:
                with self._lock:
                    self._procs.pop(node_id, None)
                raise RuntimeError(
                    f"fake slice agent exited rc={proc.returncode}")
        proc.kill()
        with self._lock:
            self._procs.pop(node_id, None)
        raise TimeoutError("fake slice agent did not join")

    # -- the fake platform's maintenance calendar --------------------------

    def schedule_preemption(self, node_id: NodeId, notice_in_s: float = 0.0,
                            grace_s: float = 10.0) -> None:
        """Arm a scheduled preemption: the notice becomes visible to
        ``poll_preemptions()`` at ``now + notice_in_s``, and the AXE —
        an unconditional SIGKILL of the agent process, exactly what a
        spot platform does — falls at ``notice + grace_s`` whether or
        not anyone drained. A node that exited cleanly first makes the
        kill a no-op."""
        now = time.monotonic()
        with self._lock:
            self._preempt_sched[node_id] = [now + notice_in_s,
                                            float(grace_s), False]

        def _axe():
            time.sleep(max(0.0, notice_in_s + grace_s))
            with self._lock:
                proc = self._procs.get(node_id)
            if proc is not None and proc.poll() is None:
                try:
                    os.kill(proc.pid, signal.SIGKILL)
                except OSError:
                    pass

        threading.Thread(target=_axe, daemon=True,
                         name=f"fake-axe-{node_id.hex()[:8]}").start()

    def poll_preemptions(self) -> List[Tuple[NodeId, float]]:
        now = time.monotonic()
        due = []
        with self._lock:
            for nid, sched in self._preempt_sched.items():
                notice_at, grace, delivered = sched
                if not delivered and now >= notice_at:
                    sched[2] = True
                    due.append((nid, grace))
        return due

    def terminate_node(self, node_id: NodeId) -> None:
        node = self.runtime.nodes.get(node_id)
        if node is not None and node.alive:
            self.runtime._count_preempt_outcome(node)
            node.shutdown()
            self.runtime.on_remote_node_lost(node_id)
        with self._lock:
            self._preempt_sched.pop(node_id, None)
            proc = self._procs.pop(node_id, None)
        if proc is not None:
            try:
                proc.terminate()
                proc.wait(timeout=5)
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass

    def non_terminated_nodes(self) -> List[NodeId]:
        with self._lock:
            return [nid for nid, p in self._procs.items()
                    if p.poll() is None]

    def shutdown(self) -> None:
        for nid in list(self.non_terminated_nodes()):
            self.terminate_node(nid)


class TPUSliceProvider(NodeProvider):
    """TPU-VM slice autodiscovery behind the same interface.

    A multi-host TPU slice pre-provisions its workers: the GCE metadata
    server / env expose the peer hostnames (TPU_WORKER_HOSTNAMES, worker
    id in TPU_WORKER_ID — the same discovery jax.distributed uses). So
    "create" here means STARTING an agent on the next not-yet-joined
    slice worker over the admin channel configured by `launcher` —
    actual VM creation belongs to the platform (GKE/queued resources),
    exactly as the reference delegates VM lifecycle to cloud providers.
    """

    # GCE metadata-server preemption surface (the shape jax.distributed
    # and the reference's TPU pod-manager poll): `maintenance-event`
    # flips to TERMINATE_ON_HOST_MAINTENANCE and `preempted` to TRUE
    # shortly before a spot slice is reclaimed. Env override for tests /
    # non-GCE platforms that mimic the shape.
    METADATA_URL = ("http://metadata.google.internal/computeMetadata/v1/"
                    "instance/maintenance-event")
    PREEMPT_VALUES = ("TERMINATE_ON_HOST_MAINTENANCE", "TRUE", "PREEMPTED")

    def __init__(self, runtime, launcher=None,
                 resources_per_node: Optional[Dict] = None,
                 preempt_grace_s: float = 60.0):
        self.runtime = runtime
        self.launcher = launcher  # callable(hostname, join_addr) -> NodeId
        self._resources = dict(resources_per_node or {"CPU": 1.0, "TPU": 4})
        hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        self._hosts: List[str] = [h for h in hosts.split(",") if h]
        self._launched: Dict[str, NodeId] = {}
        self._lock = threading.Lock()
        self.preempt_grace_s = float(preempt_grace_s)
        self._preempt_delivered = False

    def discovered_hosts(self) -> List[str]:
        return list(self._hosts)

    def node_resources(self) -> Dict[str, float]:
        return dict(self._resources)

    def create_node(self) -> NodeId:
        with self._lock:
            pending = [h for h in self._hosts if h not in self._launched]
        if not pending:
            raise RuntimeError(
                "TPU slice exhausted: all discovered workers joined "
                f"({len(self._hosts)} hosts); provision a larger slice")
        if self.launcher is None:
            raise RuntimeError(
                "TPUSliceProvider needs a launcher callable "
                "(hostname, join_addr) -> NodeId; on GKE this is the pod "
                "exec hook, on TPU-VMs an ssh runner")
        host = pending[0]
        addr = self.runtime.enable_remote_nodes()
        node_id = self.launcher(host, addr)
        with self._lock:
            self._launched[host] = node_id
        return node_id

    def terminate_node(self, node_id: NodeId) -> None:
        node = self.runtime.nodes.get(node_id)
        if node is not None and node.alive:
            node.shutdown()
            self.runtime.on_remote_node_lost(node_id)
        with self._lock:
            for h, nid in list(self._launched.items()):
                if nid == node_id:
                    self._launched.pop(h)

    def non_terminated_nodes(self) -> List[NodeId]:
        with self._lock:
            return list(self._launched.values())

    def _metadata_value(self) -> Optional[str]:
        """One metadata poll; None on any failure (not on GCE, server
        slow, ...) — preemption polling must never wedge the reconcile
        loop. ``RTPU_TPU_METADATA_URL`` overrides the endpoint (tests,
        or platforms that mimic the GCE shape behind a local agent)."""
        import urllib.request

        url = os.environ.get("RTPU_TPU_METADATA_URL") or self.METADATA_URL
        try:
            req = urllib.request.Request(
                url, headers={"Metadata-Flavor": "Google"})
            with urllib.request.urlopen(req, timeout=1.0) as resp:
                return resp.read().decode("utf-8", "replace").strip()
        except Exception:
            return None

    def poll_preemptions(self) -> List[Tuple[NodeId, float]]:
        """A TPU slice is one scheduling unit: a maintenance event on the
        metadata server means the WHOLE slice goes away — every launched
        node gets the notice, once per event. The latch RE-ARMS when the
        metadata value clears (event over, slice relaunched), so the
        next maintenance event months later still delivers."""
        value = self._metadata_value()
        preempting = (value is not None
                      and value.upper() in self.PREEMPT_VALUES)
        if not preempting:
            if value is not None:
                self._preempt_delivered = False  # event cleared: re-arm
            return []
        if self._preempt_delivered:
            return []
        self._preempt_delivered = True
        with self._lock:
            nodes = list(self._launched.values())
        return [(nid, self.preempt_grace_s) for nid in nodes]
