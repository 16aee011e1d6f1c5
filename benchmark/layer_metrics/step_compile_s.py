"""The train step's backend compile, or the read of its executable from
the persistent compile cache (``core/worker_env.use_compile_cache``): the
chip worker's span ``rtpu.jax.compile`` of the step's program, from the
run's flight record; its ``data.cache`` says which (``hit`` in a warm
run, ``miss`` where the step compiled)."""
from benchmark.layer_metrics import _flight

LAYER = "cluster runtime"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    step, _ = _flight.built_before_window(view)
    compiles = [ev["dur"] for ev in step if ev["kind"] == "rtpu.jax.compile"]
    return sum(compiles) if compiles else None
