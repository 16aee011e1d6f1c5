"""Device self time of one train step under the scope ``select`` of the
cell's family (the exact top ``topk`` index scores a query and whatever
turns them into the mask or the indices the attention reads)."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by.get("select") if by else None
