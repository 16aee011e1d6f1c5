"""Shared by the readers of what PR 54's tracing leaves in a run's flight
record: the chip watcher's samples (``ray_tpu/perf/chipwatch.py``: the
chip worker's spans ``rtpu.chip.sample`` that began inside the window; a
program without a watcher (a tree before PR 54, the recorder off, no TPU)
leaves none, and the readers say so with None) and the profiler's stretch,
which is the harness's own pause."""
from benchmark.layer_metrics import _flight


def profilers_stretch(view):
    """-> (start, stop) of the traced run's profiler on the rings' clock,
    (None, None) for an untraced run or a profiler that never stopped."""
    traced = (view.get("train") or {}).get("trace_span")
    return tuple(traced) if traced and None not in traced else (None, None)


def samples(view):
    t0 = _flight.t_window(view)
    if t0 is None:
        return []
    t1 = t0 + view["train"]["elapsed_s"]
    return [ev for ev in _flight.spans(view, _flight.WORKER,
                                       ("rtpu.chip.sample",))
            if t0 <= ev["ts"] < t1]
