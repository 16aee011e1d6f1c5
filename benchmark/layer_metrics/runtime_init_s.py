"""``ray_tpu.init`` in the driver: GCS, the node and its socket, from the
driver's span ``rtpu.core.init``. Part of ``worker_start_s``."""
from benchmark.layer_metrics._program import ring_spans

LAYER = "cluster runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    if view.get("trace") is None:
        return None
    init = [ev for ev in ring_spans("rtpu.core.init")
            if ev["kind"] == "rtpu.core.init"]
    return init[0]["dur"] if init else None
