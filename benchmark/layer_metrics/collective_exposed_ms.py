"""Per decode step, the time a collective runs on the first device with
no other operation beside it."""
from benchmark.layer_metrics._common import DECODE, T

LAYER = "sharding"
UNIT = "ms"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(view):
    tr = view.get("trace")
    if tr is None:
        return None
    got = T.exposed_collective_s(tr, within=DECODE)
    if got is None or not got[1]:
        return None
    return 1e3 * got[0] / got[1]
