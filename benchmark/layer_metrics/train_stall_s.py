"""Seconds of the window in which the run stood still (ROADMAP A14): over
the chip worker's spans ``rtpu.train.report`` that began inside the window
(twenty steps apart, and a device-paced stamp: the loop syncs on a loss
just before it reports), the sum over consecutive pairs of the gap less
the MEDIAN gap, counted where a gap exceeds the median by more than 5 %.
(Not the least gap: the pair after a standstill lies CLOSER than a quiet
one, 3.315 s against 3.488 s in the run the chip showed, because the loop
comes out of it one step less ahead of the device; measured from the least
gap every quiet pair of that run would count as a stall of a step.) A pair
that overlaps the profiler's stretch (``trace_span``) is left out: starting
and stopping the profiler is the harness's own pause. 0 in a quiet run, the
seconds lost in a low one, from the run's flight record, whether a chip
counter is live or not. None with fewer than three reports in the window,
or where no pair is left."""
from benchmark.layer_metrics import _chipwatch, _flight
from benchmark.layer_metrics._common import median

LAYER = "trainer"
UNIT = "s"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"

TOLERANCE = 0.05


def read(view):
    t0 = _flight.t_window(view)
    if t0 is None:
        return None
    t1 = t0 + view["train"]["elapsed_s"]
    starts = sorted(ev["ts"] for ev in _flight.spans(
        view, _flight.WORKER, ("rtpu.train.report",)) if t0 <= ev["ts"] < t1)
    if len(starts) < 3:
        return None
    lo, hi = _chipwatch.profilers_stretch(view)
    gaps = [b - a for a, b in zip(starts, starts[1:])
            if lo is None or b <= lo or a >= hi]
    if not gaps:
        return None
    quiet = median(gaps)
    return sum(g - quiet for g in gaps if g > (1.0 + TOLERANCE) * quiet)
