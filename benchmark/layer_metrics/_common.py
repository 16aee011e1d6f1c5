"""Shared by the readers in this directory (files that start with an
underscore are not metrics). Program names are the ones jax gives the
jitted programs today; the `tracing` issue is asked to make them stable."""
from benchmark.lib import trace as T
from benchmark.lib.stats import median, percentile  # noqa: F401

DECODE = r"^jit__decode$"
PREFILL = r"^jit__(prefill|extend)$"
TRAIN_STEP = r"bench_train_step"


def decode_gap_ms(view):
    tr = view.get("trace")
    if tr is None:
        return None
    gaps = T.gaps_between(tr, DECODE, not_between=PREFILL)
    return 1e3 * median(gaps) if gaps else None


def decode_program_ms(view):
    tr = view.get("trace")
    if tr is None:
        return None
    d = T.program_durations(tr, DECODE)
    return 1e3 * median(d) if d else None


def window_samples(view):
    w = view.get("window")
    if not w:
        return []
    return [s for s in w["samples"] if "running" in s
            and w["t_window"] <= s["t"] <= w["t_window"] + w["seconds"]]


def complete_runs(tr, pattern):
    """Executions of the programs matching ``pattern`` that lie whole
    inside the trace: a trace that starts or stops in the middle of a
    program holds a shorter event for it."""
    import re

    runs = [p for p in T.programs(tr) if re.search(pattern, p[0])]
    if not runs:
        return []
    mid = median([p[2] for p in runs])
    return [p for p in runs if p[2] >= 0.9 * mid]
