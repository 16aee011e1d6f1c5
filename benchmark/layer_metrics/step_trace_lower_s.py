"""What the train step costs at EVERY start of a process, cached or not:
the Python that traces it to a jaxpr and the lowering of the jaxpr and of
each Pallas kernel to MLIR (the persistent cache is keyed by the lowered
module). The chip worker's spans ``rtpu.jax.trace`` + ``rtpu.jax.lower``
of the step's program, from the run's flight record."""
from benchmark.layer_metrics import _flight

LAYER = "models"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    step, _ = _flight.built_before_window(view)
    phases = [ev["dur"] for ev in step
              if ev["kind"] in ("rtpu.jax.trace", "rtpu.jax.lower")]
    return sum(phases) if phases else None
