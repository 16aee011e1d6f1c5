"""Device self time of one train step under the model's scope ``mlp``
(layer norm, the two feed-forward matmuls, gelu and residual of every
layer), forward, backward and recomputation alike."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by["mlp"] if by else None
