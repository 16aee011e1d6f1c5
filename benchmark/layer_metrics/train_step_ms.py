"""Median device time of the train-step program."""
from benchmark.layer_metrics._common import TRAIN_STEP, complete_runs, median

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    tr = view.get("trace")
    if tr is None:
        return None
    d = [p[2] for p in complete_runs(tr, TRAIN_STEP)]
    return 1e3 * median(d) if d else None
