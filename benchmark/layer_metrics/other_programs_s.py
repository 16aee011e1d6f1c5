"""Every program the chip worker builds before the window but the train
step: parameter and optimizer-state initialisation, ``device_put``, the
eager programs. Seconds of the wall clock under an ``rtpu.jax.*`` span of
the worker that ended before the window and is not the step's, from the
run's flight record (a union: overlapping spans count once, and what lies
under a span of the step counts there)."""
from benchmark.layer_metrics import _flight

LAYER = "trainer"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    step, others = _flight.built_before_window(view)
    if not others:
        return None
    return _flight.covered_s(step + others) - _flight.covered_s(step)
