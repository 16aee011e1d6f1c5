"""Device self time of one train step under the scope ``indexer`` of the
cell's family (the three index projections, the index key's LayerNorm, the
rotation and the index scores of every layer), forward and recomputation
alike: the indexer has no backward."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by.get("indexer") if by else None
