"""The rest of one train step: device time of operations under none of the
model's scopes (the optimizer, the scan's stacking copies, whatever has no
scope) and time inside the program in which no operation ran. With the
three scoped readers it sums to the mean train-step program."""
from benchmark.layer_metrics._program import UNSCOPED, scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by[UNSCOPED] if by else None
