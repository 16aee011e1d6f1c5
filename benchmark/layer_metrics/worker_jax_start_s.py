"""jax start-up on the chip and the mesh, as the trainer's worker timed
them inside ``setup_mesh`` and reported on its reply (data of the
driver's span ``rtpu.train.setup_mesh``); the slowest worker. Part of
``worker_start_s``."""
from benchmark.layer_metrics._program import ring_spans

LAYER = "trainer"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(view):
    if view.get("trace") is None:
        return None
    for ev in ring_spans("rtpu.train.setup_mesh"):
        d = ev.get("data") or {}
        both = [j + m for j, m in zip(d.get("jax_start_s") or (),
                                      d.get("mesh_s") or ())
                if j is not None and m is not None]
        if both:
            return max(both)
    return None
