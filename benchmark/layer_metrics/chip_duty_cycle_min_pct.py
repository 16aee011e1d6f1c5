"""The least duty cycle the runtime reported of the window: the minimum of
``data.chip.duty_pct`` (``libtpu.sdk.tpumonitoring``'s ``duty_cycle_pct``:
the share of the last sample period in which the chip executed) over the
chip watcher's samples, from the run's flight record. The runtime renews
the counter every five to six seconds and every sample until then carries
the reading before, so a sample speaks of up to ``LAG_S`` seconds before
its stamp (seen on the chip, PR 54: a pause that ended at 35.2 s still
read 59 % at 44.7 s): counted are the
samples from ``LAG_S`` into the window on, less those up to ``LAG_S`` after
the profiler's stretch (starting and stopping the profiler is the
harness's own pause, and the device waits through it). A device that
stood still for seconds reads here as a fall from 100; None where the
counter is not live."""
from benchmark.layer_metrics import _chipwatch, _flight

LAYER = "cluster runtime"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"

LAG_S = 12.0


def read(view):
    t0 = _flight.t_window(view)
    if t0 is None:
        return None
    lo, hi = _chipwatch.profilers_stretch(view)
    duty = [ev["data"]["chip"]["duty_pct"]
            for ev in _chipwatch.samples(view)
            if "duty_pct" in ((ev.get("data") or {}).get("chip") or {})
            and ev["ts"] >= t0 + LAG_S
            and (lo is None or not lo <= ev["ts"] <= hi + LAG_S)]
    return min(duty) if duty else None
