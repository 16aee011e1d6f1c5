"""ray_tpu.train — distributed training (the reference's Ray Train,
re-based on device meshes).

ref: python/ray/train — BaseTrainer.fit (base_trainer.py:570),
DataParallelTrainer (data_parallel_trainer.py:432), BackendExecutor
(backend_executor.py:45), WorkerGroup (worker_group.py:100),
session.report (session.py:429). The NCCL/process-group backend is
replaced by the mesh layer: workers form a jax Mesh and the user loop
does pjit/shard_map SPMD — collectives ride ICI, reporting/checkpoints
ride the runtime.
"""
from .checkpoint import Checkpoint
from .config import (CheckpointConfig, FailureConfig, PipelineConfig,
                     Result, RunConfig, ScalingConfig)
from .session import (get_checkpoint, get_context, get_dataset_shard,
                      get_mesh, report)
from .trainer import DataParallelTrainer, JaxTrainer, TorchTrainer
from .backend_executor import BackendExecutor, TrainWorkerError
from .pipeline_cgraph import (CompiledPipelineEngine,
                              reshard_checkpoint, run_reference_1f1b)

__all__ = [
    "Checkpoint", "CheckpointConfig", "FailureConfig", "Result", "RunConfig",
    "ScalingConfig", "PipelineConfig", "report", "get_context",
    "get_checkpoint", "get_mesh",
    "get_dataset_shard", "DataParallelTrainer", "JaxTrainer", "TorchTrainer",
    "BackendExecutor", "TrainWorkerError",
    "CompiledPipelineEngine", "reshard_checkpoint",
    "run_reference_1f1b",
]
