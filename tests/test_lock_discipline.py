"""Targeted regressions for the GC050 concurrency-sweep fixes: the
worker-table and object-directory mutations the static sweep flagged
now run under their class lock.

Each test swaps the mutated container for a probing subclass that, at
every access, asks a second thread to try-acquire the owning lock —
the try-acquire failing proves the caller holds it at that instant.
Deterministic (no timing races): the probe thread runs to completion
inside the access itself.
"""
import threading
from collections import OrderedDict
from types import SimpleNamespace

from ray_tpu.core.ids import NodeId, WorkerId, ObjectId


def _held_by_someone(lock) -> bool:
    out = {}

    def probe():
        # graftcheck: disable=GC006 — try-acquire probe, released just below
        got = lock.acquire(blocking=False)
        if got:
            lock.release()
        out["free"] = got

    t = threading.Thread(target=probe)
    t.start()
    t.join(timeout=30)
    return not out["free"]


class _ProbedDict(dict):
    """dict recording whether `lock` was held at each mutation."""

    def __init__(self, lock):
        super().__init__()
        self.probe_lock = lock
        self.mutations = []  # (op, lock_was_held)

    def __setitem__(self, k, v):
        self.mutations.append(("set", _held_by_someone(self.probe_lock)))
        dict.__setitem__(self, k, v)

    def pop(self, k, *default):
        self.mutations.append(("pop", _held_by_someone(self.probe_lock)))
        return dict.pop(self, k, *default)


class _ProbedODict(OrderedDict):
    """OrderedDict recording lock state on reads too — the put paths
    must hold the lock across create -> entry read -> write -> seal."""

    probe_lock = None
    accesses = None

    def __setitem__(self, k, v):
        if self.accesses is not None:
            self.accesses.append(("set", _held_by_someone(self.probe_lock)))
        OrderedDict.__setitem__(self, k, v)

    def __getitem__(self, k):
        if self.accesses is not None:
            self.accesses.append(("get", _held_by_someone(self.probe_lock)))
        return OrderedDict.__getitem__(self, k)


def test_probe_detects_unlocked_mutation():
    lock = threading.RLock()
    d = _ProbedDict(lock)
    d["x"] = 1
    with lock:
        d["y"] = 2
    assert [h for _, h in d.mutations] == [False, True]


def test_node_start_worker_registers_under_lock(monkeypatch):
    from ray_tpu.core import node as node_mod

    class _DummyProc:
        pid = 4242

        def wait(self):
            raise RuntimeError("no real process")

    monkeypatch.setattr(node_mod.subprocess, "Popen",
                        lambda *a, **kw: _DummyProc())
    n = node_mod.Node.__new__(node_mod.Node)
    n._lock = threading.RLock()
    n._workers = _ProbedDict(n._lock)
    n._starting_count = 0
    n._sock_path = "/tmp/nowhere.sock"
    n.node_id = NodeId.from_random()
    h = n._start_worker()
    assert h.worker_id in n._workers
    assert n._workers.mutations == [("set", True)]


def test_node_terminate_worker_pops_under_lock():
    from ray_tpu.core.node import Node, WorkerHandle

    n = Node.__new__(Node)
    n._lock = threading.RLock()
    n._workers = _ProbedDict(n._lock)
    n.runtime = SimpleNamespace(refcount=SimpleNamespace(
        release_holder=lambda wid: None))
    w = WorkerHandle(worker_id=WorkerId.from_random(), proc=None)
    with n._lock:
        n._workers[w.worker_id] = w
    n._terminate_worker(w)
    assert w.state == "dead"
    assert w.worker_id not in n._workers
    assert n._workers.mutations == [("set", True), ("pop", True)]


def test_remote_node_lifecycle_mutates_under_lock():
    from ray_tpu.core.node import WorkerHandle
    from ray_tpu.core.remote_node import RemoteNode

    rn = RemoteNode.__new__(RemoteNode)
    rn._lock = threading.RLock()
    rn._workers = _ProbedDict(rn._lock)
    rn._starting_count = 0
    rn.channel = SimpleNamespace(notify=lambda *a, **kw: None,
                                 closed=False)
    rn.runtime = SimpleNamespace(refcount=SimpleNamespace(
        release_holder=lambda wid: None))
    h = rn._start_worker()
    assert isinstance(h, WorkerHandle)
    rn._terminate_worker(h)
    assert rn._workers.mutations == [("set", True), ("pop", True)]


def test_direct_peer_close_during_connect_does_not_deadlock(monkeypatch):
    """GC051 regression: chan.on_close() fires its callback SYNCHRONOUSLY
    when the channel already died, and the callback re-takes the actor
    record's non-reentrant lock. Registering the callback while holding
    rec.lock (as _submit_actor_direct once did) therefore self-deadlocks
    the moment a freshly-connected peer channel loses the race with the
    worker's death. The registration must happen after rec.lock drops."""
    from ray_tpu.core import rpc as rpc_mod
    from ray_tpu.core.runtime import DriverRuntime, _ActorRecord
    from ray_tpu.core.gcs import ActorInfo, ActorState
    from ray_tpu.core.node import WorkerHandle
    from ray_tpu.core.task_spec import TaskSpec, TaskType
    from ray_tpu.core.ids import ActorId, JobId, TaskId

    class _DeadChannel:
        """Peer channel that died before on_close registration: the real
        RpcChannel invokes late-registered callbacks immediately."""

        closed = True

        def __init__(self):
            self.notified = []

        def on_close(self, cb):
            cb()

        def notify(self, method, payload):
            self.notified.append(method)

    chan = _DeadChannel()
    monkeypatch.setattr(rpc_mod, "connect", lambda *a, **kw: chan)

    actor_id = ActorId.from_random()
    spec = TaskSpec(task_id=TaskId.from_random(), job_id=JobId.from_random(),
                    task_type=TaskType.ACTOR_TASK, func_id="f",
                    description="a.m", args=[], kwargs={}, actor_id=actor_id,
                    method_name="m")
    info = ActorInfo(actor_id=actor_id, name="", namespace="", job_id=spec.job_id,
                     state=ActorState.ALIVE, creation_spec=spec, max_restarts=0)
    worker = WorkerHandle(worker_id=WorkerId.from_random(), proc=None,
                          direct_addr="/tmp/peer.sock")
    rec = _ActorRecord(info=info, worker=worker,
                       node_id=NodeId.from_random())

    rt = DriverRuntime.__new__(DriverRuntime)
    rt._actors = {actor_id: rec}
    rt.gcs = SimpleNamespace(get_actor=lambda aid: info)
    rt.nodes = {rec.node_id: SimpleNamespace(alive=True, is_remote=True)}
    rt.worker_id = WorkerId.from_random()
    rt.refcount = SimpleNamespace(add_owned=lambda oid: None)
    rt.make_ref = lambda oid: oid
    rt._object_available = lambda oid: True  # short-circuit the resubmit

    done = {}

    def run():
        done["refs"] = rt._submit_actor_direct(spec)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=15)
    assert not t.is_alive(), \
        "submit deadlocked re-acquiring rec.lock from the close callback"
    assert done["refs"] is not None
    # the synchronous close callback ran and dropped the dead channel
    assert rec.direct_chan is None
    assert not rec.direct_inflight, "in-flight call recovered on close"


def test_plasma_put_paths_hold_directory_lock():
    from ray_tpu.core.object_store import PlasmaStore

    store = PlasmaStore(NodeId.from_random(), capacity_bytes=1 << 20)
    try:
        store._lock = threading.RLock()
        probed = _ProbedODict()
        probed.probe_lock = store._lock
        probed.accesses = []
        store._entries = probed
        oid = ObjectId.from_random()
        store.put_bytes(oid, b"payload", pin=False)
        assert probed.accesses, "expected directory accesses"
        unlocked = [(op, held) for op, held in probed.accesses if not held]
        assert unlocked == [], unlocked
    finally:
        store.destroy()
