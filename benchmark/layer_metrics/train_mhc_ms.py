"""Device self time of one train step under the scope ``mhc`` of the
cell's family (the hyper-connections' coefficients, Sinkhorn iterations
and the two mixings of every sublayer, the streams' sum at the end),
forward, backward and recomputation alike."""
from benchmark.layer_metrics._program import scope_ms_per_step

LAYER = "models"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    by = scope_ms_per_step(view)
    return by.get("mhc") if by else None
