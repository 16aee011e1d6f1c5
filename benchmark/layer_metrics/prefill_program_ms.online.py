"""Median device time of the prefill programs, all buckets together."""
from benchmark.layer_metrics._common import PREFILL, T, median

LAYER = "models"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "device_trace"


def read(view):
    tr = view.get("trace")
    if tr is None:
        return None
    d = T.program_durations(tr, PREFILL)
    return 1e3 * median(d) if d else None
