"""From a ``jax.profiler`` trace to numbers. Two steps, so the arithmetic
can be checked on a recorded trace without a chip:

1. ``load_xplane(path)`` reads an ``.xplane.pb`` with jax alone
   (``jax.profiler.ProfileData``) into a ``Trace``: per device plane the
   events of its program line ("XLA Modules": one event per executed jitted
   program) and of its operation line ("XLA Ops": one per HLO operation,
   control-flow operations enclosing their bodies), times in seconds.
   ``Trace.to_json``/``from_json`` keep a trimmed copy as a fixture.
2. the reductions below work on a ``Trace`` only.

Device planes are those whose name starts with ``/device:TPU:``; a trace
without one (a CPU run) has ``devices == {}`` and every reduction returns
None: a reader that finds nothing to read returns nothing.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PROGRAM_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
ASYNC_LINES = ("Async XLA Ops",)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)(?!.*-start)")
ANY_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")


def split_hlo(text: str) -> Tuple[str, str]:
    """On a TPU an operation event is named by its whole HLO line:
    "%fusion.7 = f32[8,128]{...} fusion(...), kind=kLoop, calls=...".
    -> ("%fusion.7", detail): the result's name, which is unique in the
    program, and a short description: the start of the right-hand side,
    plus "custom_call_target=<target>" where the operation is one (a Pallas
    kernel's target is "tpu_custom_call")."""
    name, sep, rhs = text.partition(" = ")
    if not sep:
        return text[:120], ""
    m = re.search(r'custom_call_target="([^"]+)"', rhs)
    detail = rhs[:90] + (f" custom_call_target={m.group(1)}" if m else "")
    return name, detail


def program_name(event_name: str) -> str:
    """"jit__decode(1234567)" -> "jit__decode": the run id is not part of
    a program's name."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


class Trace:
    """{"devices": {ordinal: {"programs": [[name, start, dur], ...],
    "ops": [[name, start, dur, detail], ...], "async_ops": [the same, from
    the line of asynchronous operations: copies, slices, collectives in
    flight]}}, "host": [[name, start, dur], ...]} with starts relative to
    the trace's first device event."""

    def __init__(self, devices: Dict[int, dict], host: List[list]):
        self.devices = devices
        self.host = host

    def to_json(self) -> dict:
        return {"devices": {str(k): v for k, v in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls({int(k): v for k, v in data["devices"].items()},
                   data.get("host", []))

    def window(self) -> Optional[Interval]:
        lo, hi = None, None
        for d in self.devices.values():
            for ev in d["ops"] or d["programs"]:
                lo = ev[1] if lo is None else min(lo, ev[1])
                hi = ev[1] + ev[2] if hi is None else max(hi, ev[1] + ev[2])
        return None if lo is None else (lo, hi)


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load_xplane(path: str, host_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[list] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"programs": [], "ops": []})
            for line in plane.lines:
                if line.name in PROGRAM_LINES:
                    for e in line.events:
                        dev["programs"].append(
                            [program_name(e.name), e.start_ns * 1e-9,
                             e.duration_ns * 1e-9])
                elif line.name in OP_LINES + ASYNC_LINES:
                    into = dev["ops"] if line.name in OP_LINES \
                        else dev.setdefault("async_ops", [])
                    for e in line.events:
                        name, detail = split_hlo(e.name)
                        into.append([name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9, detail])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host.append([e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9])
    t0 = min((ev[1] for d in devices.values()
              for ev in d["programs"] + d["ops"]), default=0.0)
    for d in devices.values():
        d.setdefault("async_ops", [])
        for ev in d["programs"] + d["ops"] + d["async_ops"]:
            ev[1] -= t0
        d["programs"].sort(key=lambda ev: ev[1])
        d["ops"].sort(key=lambda ev: (ev[1], -ev[2]))
        d["async_ops"].sort(key=lambda ev: ev[1])
    for ev in host:
        ev[1] -= t0
    host.sort(key=lambda ev: ev[1])
    return Trace(devices, host)


def save_fixture(trace: Trace, path: str, max_events: int = 4000) -> None:
    """A trimmed copy for tests: the first ``max_events`` operations of
    each device and the programs that ran in that stretch."""
    out: Dict[int, dict] = {}
    for k, d in trace.devices.items():
        ops = d["ops"][:max_events]
        end = max((o[1] + o[2] for o in ops), default=0.0)
        out[k] = {"ops": ops,
                  "programs": [p for p in d["programs"] if p[1] < end],
                  "async_ops": [a for a in d.get("async_ops", [])
                                if a[1] < end][:max_events]}
    with gzip.open(path, "wt") as f:
        json.dump(Trace(out, [h for h in trace.host]).to_json(), f)


def load_fixture(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by the
    (disjoint, sorted) intervals ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


# -- reductions ---------------------------------------------------------------

def _ivals(events: Sequence[list]) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in events]


def busy_and_window(trace: Trace) -> Optional[Tuple[float, float]]:
    """-> (busy_s, window_s): seconds in which an operation ran on a
    device (union of its operation events), averaged over the devices, and
    the length of the traced window (first to last device event)."""
    w = trace.window()
    if w is None:
        return None
    busy = [total(union(_ivals(d["ops"] or d["programs"])))
            for d in trace.devices.values()]
    return sum(busy) / len(busy), w[1] - w[0]


def programs(trace: Trace, device: Optional[int] = None) -> List[list]:
    if not trace.devices:
        return []
    dev = min(trace.devices) if device is None else device
    return trace.devices[dev]["programs"]


def program_durations(trace: Trace, pattern: str) -> List[float]:
    """Device seconds of every execution of the programs whose name
    matches ``pattern`` (a regular expression, searched), first device."""
    rx = re.compile(pattern)
    return [p[2] for p in programs(trace) if rx.search(p[0])]


def gaps_between(trace: Trace, pattern: str,
                 not_between: Optional[str] = None) -> List[float]:
    """Device-idle seconds between consecutive executions of programs
    matching ``pattern`` on the first device: from the end of one to the
    start of the next, less any time another program ran in between. A
    pair with a program matching ``not_between`` in between is skipped."""
    rx = re.compile(pattern)
    skip = re.compile(not_between) if not_between else None
    progs = programs(trace)
    out: List[float] = []
    last = None
    for i, p in enumerate(progs):
        if not rx.search(p[0]):
            continue
        if last is not None:
            between = progs[last + 1:i]
            if not (skip and any(skip.search(b[0]) for b in between)):
                gap = (p[1] - (progs[last][1] + progs[last][2])
                       - sum(b[2] for b in between))
                out.append(max(0.0, gap))
        last = i
    return out


def self_times(ops: Sequence[list]) -> Dict[str, float]:
    """Seconds by operation name, each operation's own time only: an
    enclosing operation (a while loop, a conditional, a fusion's wrapper)
    is charged its duration less its children's."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, child_time, dur]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto + 1e-12:
            name, _end, child, dur = stack.pop()
            out[name] = out.get(name, 0.0) + max(0.0, dur - child)
            if stack:
                stack[-1][2] += dur

    for name, start, dur, *_ in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start + dur, 0.0, dur])
    close(float("inf"))
    return out


def top_device_ops(trace: Trace, n: int = 10) -> List[list]:
    """The operations of the first device that took most of its time
    (self time), as [[name, seconds], ...]."""
    if not trace.devices:
        return []
    ops = trace.devices[min(trace.devices)]["ops"]
    st = self_times(ops)
    about = {o[0]: o[3] for o in ops if len(o) > 3}
    return [[_label(k, about.get(k, "")), v]
            for k, v in sorted(st.items(), key=lambda kv: -kv[1])[:n]]


def _label(name: str, detail: str) -> str:
    m = re.search(r"custom_call_target=(\S+)", detail)
    if m:
        return f"{name} custom-call {m.group(1)}"
    return (name + " = " + detail)[:100] if detail else name


def longest_idle_gaps(trace: Trace, n: int = 10) -> List[list]:
    """Idle stretches of the first device summed by what surrounded them:
    "<program before> -> <program after>", as [[name, seconds], ...]. With
    no span from inside the program, the neighbours are the only evidence
    of what the host was doing (sampling between two decode steps,
    scheduling before a prefill, feeding before a train step)."""
    w = trace.window()
    if w is None:
        return []
    dev = trace.devices[min(trace.devices)]
    busy = union(_ivals(dev["ops"] or dev["programs"]))
    idle = subtract([w], busy)
    progs = dev["programs"]
    out: Dict[str, float] = {}
    j = 0
    for lo, hi in idle:
        mid = (lo + hi) / 2
        while j < len(progs) and progs[j][1] + progs[j][2] < mid:
            j += 1
        if j < len(progs) and progs[j][1] <= mid:
            key = f"inside {progs[j][0]}"     # the program waits, e.g. on
            #                                   a transfer from the host
        else:
            before = progs[j - 1][0] if j > 0 else "(window start)"
            after = progs[j][0] if j < len(progs) else "(window end)"
            key = f"{before} -> {after}"
        out[key] = out.get(key, 0.0) + (hi - lo)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


def ops_matching(trace: Trace, pattern: str, device: Optional[int] = None
                 ) -> List[list]:
    """Operation events of one device whose name or detail matches."""
    if not trace.devices:
        return []
    rx = re.compile(pattern)
    dev = min(trace.devices) if device is None else device
    return [o for o in trace.devices[dev]["ops"]
            if rx.search(o[0]) or (len(o) > 3 and rx.search(o[3] or ""))]


def exposed_collective_s(trace: Trace, within: Optional[str] = None
                         ) -> Optional[Tuple[float, int]]:
    """-> (seconds, executions): time on the first device in which a
    collective operation ran and no other operation did, inside the
    executions of programs matching ``within`` (all programs if None), and
    how many such executions there were. None where the trace has no
    collective at all (one chip)."""
    if not trace.devices:
        return None
    dev = trace.devices[min(trace.devices)]
    rx = re.compile(within) if within else None
    runs = [p for p in dev["programs"] if rx is None or rx.search(p[0])]
    leaves_coll, leaves_other = [], []
    for name, start, dur, *_ in _leaf_ops(dev["ops"]):
        base = name.lstrip("%")
        if COLLECTIVE.match(base):
            leaves_coll.append((start, start + dur))
        elif not ANY_COLLECTIVE.match(base):      # a "-start" is neither
            leaves_other.append((start, start + dur))
    for name, start, dur, *_ in dev.get("async_ops", []):
        if ANY_COLLECTIVE.match(name.lstrip("%")):
            leaves_coll.append((start, start + dur))
    if not leaves_coll:
        return None
    exposed = subtract(union(leaves_coll), union(leaves_other))
    inside = union(_ivals(runs))
    sec = total(e for lo, hi in inside for e in clip(exposed, lo, hi))
    return sec, len(runs)


def _leaf_ops(ops: Sequence[list]) -> List[list]:
    """Operations that enclose no other operation."""
    s = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(s):
        nxt = s[i + 1] if i + 1 < len(s) else None
        if nxt is None or nxt[1] >= e[1] + e[2] - 1e-12:
            out.append(e)
    return out
