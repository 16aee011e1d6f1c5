"""In-process multi-node cluster for tests.

Equivalent of the reference's ray.cluster_utils.Cluster
(ref: python/ray/cluster_utils.py:99; add_node :165, remove_node :238) — the
standard way fault-tolerance tests create and kill "nodes" without machines.
Each added node is a full Node (raylet-equivalent) with its own shared-memory
store and worker subprocesses.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

from .core import runtime as runtime_mod
from .core.config import Config
from .core.node import Node
from .core.runtime import DriverRuntime


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_resources: Optional[Dict[str, float]] = None,
                 system_config: Optional[dict] = None):
        if runtime_mod.maybe_runtime() is not None:
            raise RuntimeError("ray_tpu already initialized")
        res = head_resources or {"CPU": 2.0}
        self.runtime = DriverRuntime(resources=res, num_nodes=1 if initialize_head else 0,
                                     config=Config(system_config))
        runtime_mod.set_runtime(self.runtime)
        self.head_node = (next(iter(self.runtime.nodes.values()))
                          if initialize_head else None)

    def add_node(self, num_cpus: float = 2.0, num_tpus: float = 0.0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None) -> Node:
        res = dict(resources or {})
        res.setdefault("CPU", num_cpus)
        if num_tpus:
            res["TPU"] = num_tpus
        return self.runtime.add_node(res, labels)

    def add_remote_node(self, num_cpus: float = 2.0,
                        resources: Optional[Dict[str, float]] = None,
                        labels: Optional[Dict[str, str]] = None,
                        timeout: float = 30.0) -> Node:
        """Start a node agent in a SEPARATE OS process that joins over
        localhost TCP — the multi-host path (ref: cluster_utils.py
        add_node runs real raylets; here: ray_tpu.core.node_agent)."""
        from .core.ids import NodeId

        addr = self.runtime.enable_remote_nodes()
        node_id = NodeId.from_random()  # assigned here so the join is
        res = dict(resources or {})     # matched deterministically
        res.setdefault("CPU", num_cpus)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.node_agent",
             "--address", f"{addr[0]}:{addr[1]}",
             "--num-cpus", str(res.pop("CPU")),
             "--resources", json.dumps(res),
             "--labels", json.dumps(labels or {}),
             "--node-id", node_id.hex()],
            env=env)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            node = self.runtime.nodes.get(node_id)
            if node is not None:
                node._agent_proc = proc  # for remove_node(kill=True)
                return node
            if proc.poll() is not None:
                raise RuntimeError(
                    f"node agent exited rc={proc.returncode} before joining")
            time.sleep(0.05)
        proc.kill()
        raise TimeoutError("node agent did not join in time")

    def remove_node(self, node: Node, kill: bool = True) -> None:
        """kill=True simulates abrupt node failure (workers SIGKILLed, object
        store segments destroyed) — the chaos-test path. For a remote node
        with kill=True the agent process is SIGKILLed, exercising the
        channel-loss path."""
        proc = getattr(node, "_agent_proc", None)
        if proc is not None and kill:
            proc.kill()
            try:
                proc.wait(timeout=10)
            except Exception:
                pass
            self.runtime.on_remote_node_lost(node.node_id)
            return
        self.runtime.remove_node(node.node_id, kill=kill)
        if proc is not None:
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()

    def shutdown(self) -> None:
        self.runtime.shutdown()
        runtime_mod.set_runtime(None)
