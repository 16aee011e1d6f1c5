"""Mean device-idle time between consecutive train-step programs."""
from benchmark.layer_metrics._common import T, TRAIN_STEP

LAYER = "trainer"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    tr = view.get("trace")
    if tr is None:
        return None
    gaps = T.gaps_between(tr, TRAIN_STEP)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
