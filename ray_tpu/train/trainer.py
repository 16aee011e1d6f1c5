"""Trainers.

Parity with the reference's Train API (ref: python/ray/train/
base_trainer.py:570 fit; data_parallel_trainer.py:432 training_loop
driving BackendExecutor over a WorkerGroup; torch/torch_trainer.py:16).
`JaxTrainer` is the native trainer (mesh backend); `DataParallelTrainer`
is the generic base; failure handling = gang restart from the latest
checkpoint (ref: FailureConfig semantics, tune/execution/experiment_state).
"""
from __future__ import annotations

import os
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

from ..perf.postmortem import dump_bundle, fetch_rings, spans_and_tail
from ..perf.recorder import get_recorder
from .backend_executor import BackendExecutor, TrainWorkerError
from .checkpoint import Checkpoint, prune_checkpoints
from .config import (CheckpointConfig, FailureConfig, Result, RunConfig,
                     ScalingConfig)


class DataParallelTrainer:
    """Runs `train_loop_per_worker` on a gang of workers, streams results,
    persists rank-0 checkpoints, restarts the gang on worker failure."""

    def __init__(self,
                 train_loop_per_worker: Callable,
                 *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.train_loop = train_loop_per_worker
        self.train_config = dict(train_loop_config or {})
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = dict(datasets or {})
        self.resume_checkpoint = resume_from_checkpoint

    # -- dataset sharding ----------------------------------------------------

    def _dataset_shards(self) -> Optional[List[dict]]:
        if not self.datasets:
            return None
        from ..data import DataShard, DatasetPipeline
        from ..data.iterator import Shardable

        n = self.scaling.num_workers
        shards: List[dict] = [{} for _ in range(n)]
        for name, ds in self.datasets.items():
            if isinstance(ds, Shardable):
                # the DataShard contract: exactly n shards, rows
                # disjoint and exhaustive (enforced here so a broken
                # implementer fails loudly, not with silently skewed
                # or duplicated per-rank data)
                parts = ds.split_shards(n)
                if len(parts) != n or not all(
                        isinstance(p, DataShard) for p in parts):
                    raise TypeError(
                        f"dataset {name!r}: split_shards({n}) must "
                        f"return exactly {n} DataShards (the Shardable "
                        f"contract); got {len(parts)} x "
                        f"{[type(p).__name__ for p in parts[:3]]}")
            elif isinstance(ds, DatasetPipeline):
                parts = ds.split(n)
            elif isinstance(ds, (list, tuple)):
                parts = [list(ds[i::n]) for i in range(n)]
            else:
                parts = [ds] * n
            for i in range(n):
                shards[i][name] = parts[i]
        return shards

    # -- the controller loop -------------------------------------------------

    def fit(self) -> Result:
        path = self.run_config.resolved_storage_path()
        os.makedirs(path, exist_ok=True)
        max_failures = self.run_config.failure_config.max_failures
        ckpt_cfg = self.run_config.checkpoint_config
        failures = 0
        latest_ckpt = self.resume_checkpoint
        history: List[Dict[str, Any]] = []
        last_metrics: Dict[str, Any] = {}
        error: Optional[BaseException] = None
        flight_path: Optional[str] = None

        while True:
            executor = BackendExecutor(
                self.scaling, experiment_name=self.run_config.name or "train")
            ended: Optional[BaseException] = None   # how this gang ended
            try:
                executor.start(self.train_loop, self.train_config,
                               dataset_shards=self._dataset_shards(),
                               checkpoint=latest_ckpt)
                while True:
                    results = executor.next_results()
                    if results is None:
                        break
                    rank0 = results[0]
                    last_metrics = dict(rank0["metrics"])
                    last_metrics["iteration"] = rank0["iteration"]
                    history.append(last_metrics)
                    if rank0.get("checkpoint") is not None:
                        latest_ckpt = rank0["checkpoint"]
                        ckpt_dir = os.path.join(
                            path, f"checkpoint_{rank0['iteration']:06d}")
                        latest_ckpt.to_directory(ckpt_dir)
                        latest_ckpt = Checkpoint.from_directory(ckpt_dir)
                        prune_checkpoints(path, ckpt_cfg.num_to_keep)
                break  # clean finish
            except TrainWorkerError as e:
                ended = e
                failures += 1
                if max_failures >= 0 and failures > max_failures:
                    error = e
                    break
                time.sleep(0.2)  # gang restart backoff
            except Exception as e:  # noqa: BLE001 — surface in Result
                ended = error = e
                traceback.print_exc()
                break
            finally:
                # the workers' rings die with them: fetch them first
                flight_path = _flight_record(
                    executor, path, ended, failures,
                    len(history)) or flight_path
                executor.shutdown()

        return Result(metrics=last_metrics, checkpoint=latest_ckpt,
                      path=path, error=error, metrics_history=history,
                      flight_path=flight_path)


def _flight_record(executor: BackendExecutor, path: str,
                   ended: Optional[BaseException], failures: int,
                   iterations: int) -> Optional[str]:
    """``<path>/flight.json``: the driver's ring (its spans and its last
    256 instant events: the rest is ``dispatch.*`` of ``next_results``'
    polling) and the ring of every worker of ``executor`` that still
    answers (5 s each), in ``dump_bundle``'s shape, so ``ray_tpu
    postmortem`` renders a run that ended well as it renders an abort.
    Written when a gang ends, however it ended and before it is killed; a
    restarted gang's record replaces its predecessor's (the driver's ring
    holds both). Where a worker's ring holds an ``rtpu.chip.stall`` (the
    chip watcher saw the process stand still, ``perf/chipwatch.py``) the
    same bundle is also left in ``bundle_dir()``, reason ``fit: stalled``,
    where the next run of the job does not replace it. Never raises, and
    writes nothing with the recorder off. What it cost is the driver's
    span ``rtpu.train.flight``."""
    rec = get_recorder()
    if not rec.enabled:
        return None
    try:
        with rec.span("rtpu.train.flight", pin=True):
            rings = fetch_rings(executor.ring_fetchers())
            bundle = dict(
                origin="driver", extra_rings=rings, throttle=False,
                origin_ring=spans_and_tail(rec.snapshot(clear=False)),
                meta={"error": ended and f"{type(ended).__name__}: {ended}",
                      "failures": failures, "iterations": iterations})
            if any(ev.get("kind") == "rtpu.chip.stall"
                   for ring in rings.values() for ev in ring):
                dump_bundle("fit: stalled", **bundle)
            return dump_bundle(
                "fit: " + ("ok" if ended is None else type(ended).__name__),
                path=os.path.join(path, "flight.json"), **bundle)
    except Exception:  # noqa: BLE001 - a record, never the run's failure
        traceback.print_exc()
        return None


class JaxTrainer(DataParallelTrainer):
    """The native trainer: gang of workers, each with a mesh slice
    (ScalingConfig.mesh), bf16 SPMD via pjit inside the user loop.
    North-star config: GPT-2 on a v5e pod (BASELINE.md)."""


class TorchTrainer(DataParallelTrainer):
    """Torch data-parallel trainer with a REAL gloo process group (ref:
    torch/torch_trainer.py:16 + torch/config.py _setup_torch_process_group).
    Every gang worker joins `dist.init_process_group("gloo")` over a
    rank-0 TCP rendezvous before the user loop runs, so
    `train.torch.prepare_model(model)` returns a genuine
    DistributedDataParallel whose gradients allreduce across workers.
    torch-cpu only by design — the TPU compute path is JaxTrainer."""

    def fit(self) -> Result:
        import uuid

        # route_host: where the cluster's control plane listens — rank 0
        # derives ITS OWN reachable interface toward it, then advertises
        # the TCPStore address through a named broker actor (the store
        # lives in the rank-0 worker process, not on the driver)
        route_host = "127.0.0.1"
        from ..core import runtime as runtime_mod

        rt = runtime_mod.maybe_runtime()
        srv = getattr(rt, "_remote_server", None)
        if srv is not None:
            route_host = srv.address[0]
        user_loop = self.train_loop
        user_config = self.train_config

        def wrapped(config):
            from . import get_context
            from .torch_backend import (rendezvous,
                                        setup_torch_process_group,
                                        teardown_torch_process_group)

            config = dict(config)
            rdzv_name = config.pop("_torch_rdzv_name")
            rhost = config.pop("_torch_route_host")
            ctx = get_context()
            init_method = rendezvous(rdzv_name, rhost,
                                     ctx.get_world_rank(),
                                     ctx.get_world_size())
            setup_torch_process_group(init_method, ctx.get_world_rank(),
                                      ctx.get_world_size())
            try:
                return user_loop(config)
            finally:
                teardown_torch_process_group()

        self.train_loop = wrapped
        self.train_config = {**self.train_config,
                             "_torch_rdzv_name":
                                 f"_torch_rdzv_{uuid.uuid4().hex[:12]}",
                             "_torch_route_host": route_host}
        try:
            return super().fit()
        finally:
            self.train_loop = user_loop
            self.train_config = user_config
