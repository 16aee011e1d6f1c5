"""The run's flight record: ``<out>/<cell>/train/flight.json``, which
``JaxTrainer.fit`` leaves beside every run (``Result.flight_path``): the
driver's flight-recorder ring and the chip worker's, fetched before the
worker is killed, in ``ray_tpu.perf``'s post-mortem shape. Every ring
stamps ``time.time()``, as ``train_loop``'s final report does
(``t_window``), so a span places itself against the window and the
profiler's stretch with no further work. A program that leaves no such
file (a tree before PR 38) gives the readers nothing, and they say so
with None."""
import os
import re

from benchmark.layer_metrics._common import T, TRAIN_STEP
from benchmark.lib import spec

DRIVER = "driver"
WORKER = "train_worker:0"       # rank 0: the benchmark's cells have one
PROGRAM_KINDS = ("rtpu.core.", "rtpu.train.", "rtpu.jax.")


def rings(view):
    """-> {ring name: [events]} of the run's flight record, or None."""
    try:
        from ray_tpu.perf import load_bundle

        return load_bundle(os.path.join(
            spec.OUT_DIR, view["cell"]["name"], "train",
            "flight.json"))["rings"]
    except (ImportError, OSError, ValueError, KeyError):
        return None


def spans(view, ring, prefixes):
    """Span events (``ts``, ``dur``) of one ring whose kind starts with
    one of ``prefixes``, oldest first; [] without a record."""
    found = rings(view)
    return [ev for ev in (found or {}).get(ring, ())
            if "dur" in ev and ev["kind"].startswith(prefixes)]


def interval(ev):
    return (ev["ts"], ev["ts"] + ev["dur"])


def is_step(ev):
    return re.search(TRAIN_STEP, ev.get("label") or "") is not None


def t_window(view):
    """When the measured window opened, on the rings' clock; None for a
    view without a training run's final report."""
    return (view.get("train") or {}).get("t_window")


def built_before_window(view):
    """The chip worker's ``rtpu.jax.*`` spans that ended before the
    window opened, split into (those of the train step, the others)."""
    opened = t_window(view)
    built = [ev for ev in spans(view, WORKER, ("rtpu.jax.",))
             if opened is not None and ev["ts"] + ev["dur"] <= opened]
    return ([ev for ev in built if is_step(ev)],
            [ev for ev in built if not is_step(ev)])


def covered_s(events):
    """Seconds of the wall clock under at least one of ``events``."""
    return T.total(T.union(interval(ev) for ev in events))
