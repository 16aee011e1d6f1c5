"""Device self time of one train step under the scope ``latent_proj`` of the
cell's family (an expert layer's projections into and out of its experts'
latent), forward, backward and recomputation alike."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by.get("latent_proj") if by else None
