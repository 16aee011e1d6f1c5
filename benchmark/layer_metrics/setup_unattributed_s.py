"""What the tracing still cannot name of the set-up: process start to the
window less the seconds of the wall clock under any program span of
either ring of the run's flight record (``rtpu.core.*``, ``rtpu.train.*``,
``rtpu.jax.*``; the driver's and the chip worker's): imports before
``ray_tpu.init``, buffers made on the device, an executable's load, the
two warm-up steps. The number that says whether the set-up needs another
`tracing` issue."""
from benchmark.layer_metrics import _flight

LAYER = "trainer"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    whole = view["spans"].get("process_start_to_window")
    opened = _flight.t_window(view)
    named = [ev for ring in (_flight.DRIVER, _flight.WORKER)
             for ev in _flight.spans(view, ring, _flight.PROGRAM_KINDS)]
    if whole is None or opened is None or not named:
        return None
    inside = _flight.T.clip(map(_flight.interval, named),
                            opened - whole, opened)
    return whole - _flight.T.total(_flight.T.union(inside))
