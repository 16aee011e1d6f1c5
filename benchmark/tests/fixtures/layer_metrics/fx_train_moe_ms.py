"""Test fixture: device self time of one train step under the scope
``moe`` of the cell's family (``families/fx_moe.py``): a scope reader of
another family is these three lines."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by["moe"] if by else None
