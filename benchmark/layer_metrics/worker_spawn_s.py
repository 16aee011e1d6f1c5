"""The chip worker's process, from the node asking for it to its
registration: the driver's span ``rtpu.core.worker_spawn`` of the worker
that was born able to open the chip (interpreter start, ``import ray_tpu``,
the worker's own imports, the socket). Part of ``worker_start_s``."""
from benchmark.layer_metrics._program import ring_spans

LAYER = "cluster runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    if view.get("trace") is None:
        return None
    chip = [ev for ev in ring_spans("rtpu.core.worker_spawn")
            if (ev.get("data") or {}).get("chip")]
    return chip[0]["dur"] if chip else None
