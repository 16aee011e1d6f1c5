"""Shared by the readers in this directory (files that start with an
underscore are not metrics). Program names are the ones jax gives the
jitted programs today; the `tracing` issue is asked to make them stable."""
import re

from benchmark.lib import trace as T
from benchmark.lib.peaks import peak
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
    runs = [p for p in T.programs(tr) if re.search(pattern, p[0])]
    if not runs:
        return []
    mid = median([p[2] for p in runs])
    return [p for p in runs if p[2] >= 0.9 * mid]


def kernel_s_per_step(view, pattern):
    """Device seconds per train step of the operations whose name or
    detail matches ``pattern`` (a kernel's pinned name, "%<name>.<n>" in
    the trace), over the train steps that lie whole inside the trace; None
    outside a traced run, without a whole step, or where nothing
    matches."""
    tr = view.get("trace")
    if tr is None:
        return None
    steps = complete_runs(tr, TRAIN_STEP)
    if not steps:
        return None
    inside = T.union((p[1], p[1] + p[2]) for p in steps)
    kernel = T.union((o[1], o[1] + o[2]) for o in T.ops_matching(tr, pattern))
    busy = T.total(e for lo, hi in inside for e in T.clip(kernel, lo, hi))
    return busy / len(steps) if busy else None


def roofline_pct(view, seconds, flops, bytes):
    """Share (%) of its roofline at which work of ``flops`` operations and
    ``bytes`` bytes ran in ``seconds`` on the view's device: the least time
    the chip could take, max(operations / peak FLOP/s, bytes / peak
    bytes/s), over the time taken. None where there is no time."""
    if not seconds:
        return None
    pk = peak(view["device"]["kind"])
    return 100.0 * max(flops / pk["bf16_flops"],
                       bytes / pk["hbm_bytes_per_s"]) / seconds
