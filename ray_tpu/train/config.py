"""Train configuration dataclasses.

Parity with the reference's AIR configs (ref: python/ray/air/config.py —
ScalingConfig/RunConfig/FailureConfig/CheckpointConfig), with the TPU
twist: ScalingConfig carries a MeshSpec instead of GPU counts — the
backend hands each worker a mesh slice rather than a torch process group
(ref: train/torch/config.py:69)."""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ..parallel.mesh import MeshSpec


@dataclass
class ScalingConfig:
    num_workers: int = 1
    use_tpu: bool = False
    resources_per_worker: Dict[str, float] = field(default_factory=dict)
    mesh: Optional[MeshSpec] = None          # parallelism layout per worker gang
    devices_per_worker: Optional[int] = None  # CI: partition the host devices
    placement_strategy: str = "SPREAD"

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker)
        res.setdefault("CPU", 1.0)
        if self.use_tpu:
            res.setdefault("TPU", 1.0)
        return res


@dataclass
class PipelineConfig:
    """Knobs for the compiled-graph pipeline engine
    (train/pipeline_cgraph.py CompiledPipelineEngine). Carried as one
    object so trainers/benches/smokes configure the engine uniformly."""
    num_microbatches: int = 4
    virtual_stages: int = 1      # model chunks per actor (interleaving)
    dp: int = 1                  # data-parallel pipeline replicas
    # in-actor sharded param/opt-state axis (parallel.sharding
    # FsdpPlane): each stage's chunk params + moments live 1/fsdp per
    # chip; composes with dp and the stages into pp x dp x fsdp
    fsdp: int = 1
    zero_update: bool = True     # ZeRO-shard the dp optimizer update
    # slow-wire codecs (docs/COLLECTIVES.md): "int8"/"e4m3" block-scaled
    # quantization, None = full precision. grad_codec compresses the dp
    # gradient sync (ZeRO reduce-scatter/all-gather or the replicated
    # allreduce); wire_codec compresses the cgraph activation/cotangent
    # channel payloads between stages.
    grad_codec: Optional[str] = None
    wire_codec: Optional[str] = None
    remat: bool = False          # recompute fwd in bwd (activation remat)
    channel_bytes: int = 1 << 20  # per-slot channel capacity
    resources_per_stage: Dict[str, float] = field(default_factory=dict)
    # fault tolerance (docs/FAULT_TOLERANCE.md): non-empty dir enables
    # atomic rename-commit checkpoints; every > 0 snapshots after each
    # Nth step and engine.recover() resumes from the newest commit
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0

    def engine_kwargs(self) -> Dict[str, Any]:
        return {
            "num_microbatches": self.num_microbatches,
            "virtual_stages": self.virtual_stages,
            "dp": self.dp,
            "fsdp": self.fsdp,
            "zero_update": self.zero_update,
            "grad_codec": self.grad_codec,
            "wire_codec": self.wire_codec,
            "remat": self.remat,
            "channel_bytes": self.channel_bytes,
            "resources_per_stage": self.resources_per_stage or None,
            "checkpoint_dir": self.checkpoint_dir,
            "checkpoint_every": self.checkpoint_every,
        }


@dataclass
class FailureConfig:
    max_failures: int = 0    # 0 = fail fast; -1 = unlimited restarts


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_frequency: int = 0


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    verbose: int = 0
    # Tune stop criteria, e.g. {"training_iteration": 10} — a trial stops
    # when any key's reported value reaches the threshold (ref: air.RunConfig
    # stop / tune/stopper.py)
    stop: Optional[Dict[str, Any]] = None
    # remote-storage mirror of the experiment dir (ref: tune/syncer.py
    # SyncConfig(upload_dir)): any fsspec URI (gs://, s3://, file://,
    # memory://) or a plain path; experiment snapshots + checkpoints are
    # pushed there and Tuner.restore can resume from the mirror
    upload_dir: Optional[str] = None
    sync_period_s: float = 5.0

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.expanduser("~/ray_tpu_results")
        name = self.name or "train_run"
        return os.path.join(base, name)


@dataclass
class Result:
    """What fit() returns (ref: python/ray/air/result.py)."""
    metrics: Dict[str, Any]
    checkpoint: Optional[Any]            # train.Checkpoint
    path: str
    error: Optional[BaseException] = None
    metrics_history: list = field(default_factory=list)
    # the run's flight record: the driver's ring and every worker's, as
    # they stood when fit() ended (`ray_tpu postmortem <flight_path>`)
    flight_path: Optional[str] = None
