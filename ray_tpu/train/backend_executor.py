"""BackendExecutor + WorkerGroup — the gang that runs the train loop.

Parity with the reference (ref: python/ray/train/_internal/
backend_executor.py:45 — start:104, start_training:342,
get_next_results:457; worker_group.py:100), re-based on the mesh layer:
instead of `_setup_torch_process_group` the backend forms a
jax.sharding.Mesh per worker (ray_tpu/parallel/mesh_group.py) and the
user loop reads it via `train.get_mesh()`.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

import ray_tpu
from ray_tpu.core.placement_group import placement_group, remove_placement_group
from ray_tpu.util.queue import Queue

from ..parallel.mesh import MeshSpec
from ..perf.recorder import get_recorder
from ..parallel.mesh_group import MeshWorkerMixin
from .config import ScalingConfig
from .session import (TrainContext, enter_loop, init_session,
                      shutdown_session)


class TrainWorkerError(RuntimeError):
    """A worker (or its node) died mid-training."""


class _TrainWorker(MeshWorkerMixin):
    """Actor hosting one rank of the gang."""

    def setup_session(self, rank: int, world: int, queue_actor,
                      dataset_shard_blob: Optional[bytes],
                      checkpoint, experiment_name: str) -> bool:
        from ray_tpu.util.queue import Queue as _Q

        q = _Q.__new__(_Q)
        q.actor = queue_actor
        shards = (cloudpickle.loads(dataset_shard_blob)
                  if dataset_shard_blob else {})
        init_session(
            TrainContext(world_rank=rank, world_size=world,
                         experiment_name=experiment_name),
            result_queue=q,
            mesh=getattr(self, "_mesh", None),
            dataset_shards=shards,
            checkpoint=checkpoint)
        return True

    def flight_ring(self) -> List[dict]:
        """This process's flight-recorder ring, left in place."""
        return get_recorder().snapshot(clear=False)

    def run_train_fn(self, fn_blob: bytes, config: Dict[str, Any]):
        fn = cloudpickle.loads(fn_blob)
        enter_loop()
        try:
            if config:
                return fn(config)
            try:
                return fn()
            except TypeError as e:
                if "positional argument" in str(e):
                    return fn({})
                raise
        finally:
            shutdown_session()


class BackendExecutor:
    def __init__(self, scaling: ScalingConfig, experiment_name: str = ""):
        self.scaling = scaling
        self.experiment_name = experiment_name
        self.queue: Optional[Queue] = None
        self.workers: List[Any] = []
        self._pg = None
        self._run_refs: List[Any] = []
        self._pending: Dict[int, dict] = {}

    # ---- lifecycle ---------------------------------------------------------

    def start(self, train_fn: Callable, train_config: Dict[str, Any],
              dataset_shards: Optional[List[dict]] = None,
              checkpoint=None) -> None:
        s = self.scaling
        self._pending = {}
        n = s.num_workers
        res = s.worker_resources()
        bundles = [dict(res) for _ in range(n)]
        # start-up spans, in the driver's ring (docs/OBSERVABILITY.md)
        rec = get_recorder()
        with rec.span("rtpu.train.pg_ready", pin=True):
            self._pg = placement_group(bundles,
                                       strategy=s.placement_strategy)
            if not self._pg.ready(timeout=60.0):
                raise TrainWorkerError(
                    "placement group for train workers not ready")
        self.queue = Queue()
        cls = ray_tpu.remote(_TrainWorker)
        self.workers = [
            cls.options(
                num_cpus=res.get("CPU", 1.0),
                resources={k: v for k, v in res.items() if k != "CPU"},
                placement_group=self._pg,
                placement_group_bundle_index=i,
            ).remote()
            for i in range(n)
        ]
        spec = s.mesh or MeshSpec()
        spec_kwargs = {"dp": spec.dp, "fsdp": spec.fsdp, "tp": spec.tp,
                       "sp": spec.sp, "ep": spec.ep, "pp": spec.pp}
        with rec.span("rtpu.train.setup_mesh", pin=True) as sp:
            # covers the lease, the worker process (rtpu.core.
            # worker_spawn) and the actor's construction too; the
            # workers say how much of it was jax and the mesh
            ready = ray_tpu.get([
                w.setup_mesh.remote(i, n, None, spec_kwargs,
                                    s.devices_per_worker)
                for i, w in enumerate(self.workers)])
            sp.data = {k: [getattr(r, k, None) for r in ready]
                       for k in ("entered", "jax_start_s", "mesh_s")}
        shard_blobs = []
        for i in range(n):
            shard = dataset_shards[i] if dataset_shards else None
            shard_blobs.append(cloudpickle.dumps(shard) if shard else None)
        with rec.span("rtpu.train.setup_session", pin=True):
            ray_tpu.get([
                w.setup_session.remote(i, n, self.queue.actor,
                                       shard_blobs[i], checkpoint,
                                       self.experiment_name)
                for i, w in enumerate(self.workers)])
        blob = cloudpickle.dumps(train_fn)
        self._run_refs = [w.run_train_fn.remote(blob, train_config)
                          for w in self.workers]

    # ---- result streaming --------------------------------------------------

    def _drain_queue(self) -> None:
        """Pull every queued report into the persistent per-iteration buffer.

        The buffer must live on `self`: a single drain can dequeue partial
        rows for several iterations at once, and any rows not returned by
        this call must survive until their iteration completes (round-1 bug:
        a call-local buffer silently dropped them)."""
        for p in self.queue.get_batch(256):
            self._pending.setdefault(p["iteration"], {})[p["rank"]] = p

    def _pop_complete(self) -> Optional[List[dict]]:
        for it in sorted(self._pending):
            if len(self._pending[it]) == len(self.workers):
                row = self._pending.pop(it)
                return [row[r] for r in sorted(row)]
        return None

    def next_results(self, timeout: float = 600.0) -> Optional[List[dict]]:
        """One result per rank for the next finished iteration, or None when
        training completed. Raises TrainWorkerError on a dead worker."""
        deadline = time.monotonic() + timeout
        while True:
            self._drain_queue()
            row = self._pop_complete()
            if row is not None:
                return row
            done, _ = ray_tpu.wait(self._run_refs,
                                   num_returns=len(self._run_refs), timeout=0.0)
            if len(done) == len(self._run_refs):
                # surface worker exceptions (if any), then drain stragglers
                try:
                    ray_tpu.get(self._run_refs)
                except ray_tpu.exceptions.RayTpuError as e:
                    raise TrainWorkerError(str(e)) from e
                self._drain_queue()
                return self._pop_complete()
            if time.monotonic() > deadline:
                raise TrainWorkerError(
                    f"timed out waiting for training results ({timeout}s)")
            time.sleep(0.01)

    def finish(self) -> List[Any]:
        return ray_tpu.get(self._run_refs)

    def ring_fetchers(self) -> Dict[str, Callable[[], List[dict]]]:
        """``dump_bundle``'s ``ring_fetchers`` for the gang as it stands:
        each worker's ring by one actor call of 5 s at most. An actor
        still inside its loop function does not answer in time."""
        return {f"train_worker:{i}":
                (lambda w=w: ray_tpu.get(w.flight_ring.remote(), timeout=5.0))
                for i, w in enumerate(self.workers)}

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
        if self.queue is not None:
            self.queue.shutdown()
            self.queue = None
        if self._pg is not None:
            try:
                remove_placement_group(self._pg)
            except Exception:
                pass
            self._pg = None
