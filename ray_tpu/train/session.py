"""Worker-side training session.

Parity with the reference's `_TrainSession` / `ray.train.report`
(ref: python/ray/train/_internal/session.py:429 report — queue-based
result channel consumed by the trainable; :470 get_dataset_shard). Here
the channel is a ray_tpu Queue actor and the "process group" is the
worker's mesh slice."""
from __future__ import annotations

import gc
import resource
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from ..perf.recorder import get_recorder

_session_lock = threading.Lock()
_session: Optional["_Session"] = None
# ident of the thread inside the loop function (``enter_loop``), for the
# chip watcher's reading of that thread's CPU clock; None outside a loop
_loop_thread: Optional[int] = None


@dataclass
class TrainContext:
    world_rank: int
    world_size: int
    local_rank: int = 0
    node_rank: int = 0
    experiment_name: str = ""
    trial_name: str = ""

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_world_size(self) -> int:
        return self.world_size

    def get_local_rank(self) -> int:
        return self.local_rank


@dataclass
class _Session:
    context: TrainContext
    result_queue: Any                      # ray_tpu.util.queue.Queue handle
    mesh: Any = None
    dataset_shards: Dict[str, Any] = field(default_factory=dict)
    latest_checkpoint: Optional[Any] = None
    iteration: int = 0
    stop_requested: bool = False
    usage: Optional[Tuple[float, ...]] = None   # _usage() at the last report


def init_session(context: TrainContext, result_queue, mesh=None,
                 dataset_shards=None, checkpoint=None) -> None:
    global _session
    with _session_lock:
        _session = _Session(context=context, result_queue=result_queue,
                            mesh=mesh, dataset_shards=dict(dataset_shards or {}),
                            latest_checkpoint=checkpoint)


def shutdown_session() -> None:
    global _session, _loop_thread
    with _session_lock:
        _session = None
        _loop_thread = None


def enter_loop() -> None:
    """Called by the worker on the thread that is about to run the loop
    function: from here to ``shutdown_session`` that thread is "the loop's"
    (``loop_state``), and the first report's ``data`` counts from here."""
    global _loop_thread
    _loop_thread = threading.get_ident()
    s = _session
    if s is not None and get_recorder().enabled:
        s.usage = _usage()


def loop_state() -> Tuple[Optional[int], int]:
    """-> (ident of the thread inside the loop function or None, the
    ``iteration`` of the last ``report``): what the chip watcher
    (``perf/chipwatch.py``) reads at every sample, without a lock."""
    s = _session
    return _loop_thread, (s.iteration if s is not None else 0)


def _get_session() -> "_Session":
    if _session is None:
        raise RuntimeError(
            "No training session active; train.report/get_context only work "
            "inside a train_loop_per_worker launched by a Trainer.")
    return _session


def get_context() -> TrainContext:
    return _get_session().context


def get_mesh():
    """The jax.sharding.Mesh for this worker's gang — the TPU analog of
    `torch.distributed` process-group state."""
    return _get_session().mesh


_USAGE_KEYS = ("since_s", "cpu_s", "loop_cpu_s", "nvcsw", "nivcsw",
               "majflt", "gc")


def _usage() -> Tuple[float, ...]:
    """Cumulative, in ``_USAGE_KEYS``' order: the wall clock, the process's
    CPU seconds, the calling (loop) thread's, the process's voluntary and
    involuntary context switches and major faults, the garbage collector's
    collections. A few microseconds."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return (time.time(), time.process_time(), time.thread_time(),
            ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_majflt,
            sum(g["collections"] for g in gc.get_stats()))


def report(metrics: Dict[str, Any], checkpoint=None) -> None:
    """Report metrics (and optionally a checkpoint) for this iteration.
    Only rank 0's checkpoint is persisted (reference semantics)."""
    s = _get_session()
    rec = get_recorder()
    data = None
    if rec.enabled:
        # what lay between the last report's start (the loop's entry, for
        # the first) and this one's: a loop that syncs on a loss just
        # before it reports makes this a device-paced stamp
        now, last = _usage(), s.usage
        s.usage = now
        data = {"iteration": s.iteration + 1}
        if last is not None:
            data.update((k, b - a) for k, a, b in zip(_USAGE_KEYS, last, now))
    # in the chip's process, so a gap in a training trace has a name
    with rec.span("rtpu.train.report", data=data):
        s.iteration += 1
        payload = {
            "rank": s.context.world_rank,
            "iteration": s.iteration,
            "metrics": dict(metrics),
            "checkpoint": checkpoint if s.context.world_rank == 0 else None,
        }
        s.result_queue.put(payload)


def get_checkpoint():
    """Latest checkpoint to restore from (set on restart after failure)."""
    return _get_session().latest_checkpoint


def get_dataset_shard(name: str = "train"):
    return _get_session().dataset_shards.get(name)
