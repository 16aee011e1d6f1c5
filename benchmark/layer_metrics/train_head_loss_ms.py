"""Device self time of one train step under the model's scopes ``embed``,
``lm_head`` (final norm and the vocabulary matmul) and ``loss``, forward
and backward."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by["embed"] + by["lm_head"] + by["loss"] if by else None
