"""What the chip watcher costs: median ``dur`` of the chip worker's spans
``rtpu.chip.sample`` that began inside the window (one a quarter second:
the runtime's counters asked for on a thread of its own, the process's
clocks, ``getrusage``, ``/proc/self/task``), from the run's flight record."""
from benchmark.layer_metrics import _chipwatch
from benchmark.layer_metrics._common import median

LAYER = "cluster runtime"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(view):
    taken = [ev["dur"] for ev in _chipwatch.samples(view)]
    return 1e3 * median(taken) if taken else None
