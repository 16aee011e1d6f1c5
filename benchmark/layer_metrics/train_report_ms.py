"""``train.report``, the trainer's only code on the host's path between
two steps: median of the chip worker's spans ``rtpu.train.report`` that
began inside the window, from the run's flight record: every report of
the run, not the one that happens to fall into 3 s of trace."""
from benchmark.layer_metrics import _flight
from benchmark.layer_metrics._common import median

LAYER = "trainer"
UNIT = "ms"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(view):
    t0 = _flight.t_window(view)
    if t0 is None:
        return None
    t1 = t0 + view["train"]["elapsed_s"]
    reports = [ev["dur"] for ev in _flight.spans(
        view, _flight.WORKER, ("rtpu.train.report",))
        if t0 <= ev["ts"] < t1]
    return 1e3 * median(reports) if reports else None
