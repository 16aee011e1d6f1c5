"""Gang-scheduled mesh formation.

The TPU analog of the reference's process-group bootstrap: TorchConfig's
`_setup_torch_process_group` (ref: python/ray/train/torch/config.py:69 —
rank-0 rendezvous address, dist.init_process_group :113) and the
WorkerGroup it runs on (ref: python/ray/train/_internal/worker_group.py:100).

A "task" on a TPU slice is N coordinated host processes entering the same
pjit program — a gang. `MeshGroup` owns that gang: it spawns one actor per
host (in a placement group so they land on distinct nodes), passes each its
process index + coordinator address, has each call `jax.distributed.
initialize` (multi-host) or just claim local devices (single host / CPU
tests), and then `run()` broadcasts a callable for SPMD execution.
"""
from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Sequence

import ray_tpu
from ray_tpu.core.placement_group import placement_group, remove_placement_group

from ..perf.chipwatch import start_chip_watch
from ..perf.jaxbuild import install_jax_spans
from ..perf.recorder import get_recorder
from .mesh import MeshSpec


class MeshReady(int):
    """What ``setup_mesh`` answers: the worker's device count (every
    caller that wants only that keeps working) with the seconds the
    worker spent bringing jax up and building the mesh and the wall-clock
    time at which the call reached it, so the driver's
    ``rtpu.train.setup_mesh`` span can say what it waited for (before
    ``entered``: the lease, the worker's process, the actor's
    construction) without another call."""

    def __new__(cls, devices: int, jax_start_s: float = 0.0,
                mesh_s: float = 0.0, entered: float = 0.0):
        self = super().__new__(cls, devices)
        self.jax_start_s = float(jax_start_s)
        self.mesh_s = float(mesh_s)
        self.entered = float(entered)
        return self

    def __reduce__(self):
        return (MeshReady, (int(self), self.jax_start_s, self.mesh_s,
                            self.entered))


class MeshWorkerMixin:
    """Mixin giving an actor the mesh-formation protocol. Train workers and
    RL learners inherit this; `setup_mesh` is invoked once by MeshGroup.

    Mesh construction/validation goes through the shared ownership layer
    (parallel.sharding.MeshOwner) — the same object the LLM engine's tp
    lowering and the pipeline stages' fsdp plane consume, so every stack
    agrees on axis names and sharding factories (docs/SHARDING.md)."""

    def setup_mesh(self, process_id: int, num_processes: int,
                   coordinator: Optional[str], spec_kwargs: dict,
                   devices_per_process: Optional[int] = None) -> int:
        """-> the number of devices of this worker's mesh, as a
        :class:`MeshReady`: an int that also says where the seconds of
        the call went (jax start-up, building the mesh)."""
        rec = get_recorder()
        entered = time.time()
        with rec.span("rtpu.train.jax_start") as jax_start:
            import jax

            devs = self._jax_devices(jax, process_id, num_processes,
                                     coordinator, devices_per_process)
        # from here every program this worker builds leaves rtpu.jax.*
        install_jax_spans()
        # and, where the devices are TPUs, the chip's counters and the
        # host's are sampled into the ring (rtpu.chip.sample, .stall)
        start_chip_watch(devs)
        with rec.span("rtpu.train.mesh") as mesh:
            from .sharding import MeshOwner

            self._mesh_devices = devs
            self._owner = MeshOwner(MeshSpec(**spec_kwargs), devices=devs,
                                    name=f"gang-p{process_id}")
            self._mesh = self._owner.mesh
        return MeshReady(len(devs), jax_start.dur, mesh.dur, entered)

    def _jax_devices(self, jax, process_id: int, num_processes: int,
                     coordinator: Optional[str],
                     devices_per_process: Optional[int]) -> list:
        """Imports done, this is jax's own start-up: the backend comes up
        (on a chip worker: the TPU runtime) at ``jax.devices()``."""
        self._process_id = process_id
        self._num_processes = num_processes
        if num_processes > 1 and coordinator:
            # Real multi-host path: one jax process per TPU host. Guarded so
            # CPU CI (everything in one OS process) skips the barrier.
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id)
        devs = jax.devices()
        if devices_per_process is not None:
            lo = process_id * devices_per_process
            devs = devs[lo:lo + devices_per_process]
        return devs

    @property
    def mesh(self):
        return self._mesh

    @property
    def mesh_owner(self):
        """The sharding-layer MeshOwner (NamedSharding factory, layout,
        per-device accounting) backing :attr:`mesh`."""
        return self._owner

    def mesh_run(self, fn_blob: bytes, *args, **kwargs):
        import cloudpickle

        fn = cloudpickle.loads(fn_blob)
        return fn(self, *args, **kwargs)


class MeshGroup:
    """Forms and drives a gang of mesh workers.

    worker_cls must mix in MeshWorkerMixin. On a v5e-256 this is 64 host
    actors each owning 4 chips; on CPU CI it is N actors sharing the
    virtual-device pool (partitioned via devices_per_process).
    """

    def __init__(self, num_workers: int,
                 spec: Optional[MeshSpec] = None,
                 worker_cls: Optional[type] = None,
                 devices_per_process: Optional[int] = None,
                 resources_per_worker: Optional[dict] = None,
                 coordinator: Optional[str] = None):
        self.num_workers = num_workers
        self.spec = spec or MeshSpec()
        cls = worker_cls or _DefaultMeshWorker
        res = dict(resources_per_worker or {"CPU": 1.0})
        bundles = [dict(res) for _ in range(num_workers)]
        self._pg = placement_group(bundles, strategy="SPREAD")
        if not self._pg.ready():
            raise TimeoutError("MeshGroup placement group not ready")
        remote_cls = ray_tpu.remote(cls)
        self.workers = [
            remote_cls.options(
                num_cpus=res.get("CPU", 1.0),
                resources={k: v for k, v in res.items() if k != "CPU"},
                placement_group=self._pg,
                placement_group_bundle_index=i,
            ).remote()
            for i in range(num_workers)
        ]
        counts = ray_tpu.get([
            w.setup_mesh.remote(i, num_workers, coordinator,
                                _spec_kwargs(self.spec), devices_per_process)
            for i, w in enumerate(self.workers)
        ])
        self.devices_per_worker = counts

    def run(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Gang-invoke fn(worker_self, *args) on every worker; returns all
        results. This is the gang-scheduling primitive the reference lacks
        (SURVEY.md §7 hard parts)."""
        import cloudpickle

        blob = cloudpickle.dumps(fn)
        return ray_tpu.get([
            w.mesh_run.remote(blob, *args, **kwargs) for w in self.workers])

    def run_async(self, fn: Callable, *args, **kwargs):
        import cloudpickle

        blob = cloudpickle.dumps(fn)
        return [w.mesh_run.remote(blob, *args, **kwargs) for w in self.workers]

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self._pg)
        except Exception:
            pass


class _DefaultMeshWorker(MeshWorkerMixin):
    pass


def _spec_kwargs(spec: MeshSpec) -> dict:
    return {"dp": spec.dp, "fsdp": spec.fsdp, "tp": spec.tp,
            "sp": spec.sp, "ep": spec.ep, "pp": spec.pp}
