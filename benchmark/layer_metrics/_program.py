"""What the PROGRAM says about itself, for the readers of this directory
(files that start with an underscore are not metrics). The program times
its phases with one instrument, ``FlightRecorder.span`` of
``ray_tpu/perf/recorder.py``: every span is an event in the ring of the
process it ran in and, where jax is imported there, a
``jax.profiler.TraceAnnotation`` on the host plane of a profiler trace, on
the clock of the device planes. Five sources, each ``None``/empty where
the program (a parent commit) has no such span, counter or scope:

* ``program_spans(view)``: the ``rtpu.*`` spans of the chip's process in
  the traced stretch, from the xplane a traced run leaves under
  ``.bench_out/<cell>/trace/``;
* ``ring_spans(prefix)``: the span events of THIS process's ring. The
  readers run in the harness's process after ``ray_tpu.shutdown()``, so
  that is the driver's ring: start-up (``rtpu.core.*``, ``rtpu.train.*``);
* ``stats_delta(view)`` / ``wait_samples(view, who)``: the window's
  difference of two ``engine.stats()`` samples, and the lock waits that
  completed between consecutive 100 ms samples;
* ``scope_ms_per_step(view)``: device self time of a train step by the
  ``jax.named_scope`` its operations carry in their ``op_name``, split by
  the ``SCOPES`` of the cell's family (``benchmark/families/``);
* ``idle_by_span(trace, spans)``: idle time of device 0 summed by the
  innermost program span that covers it.
"""
from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.lib import spec
from benchmark.lib import trace as T
from benchmark.lib.stats import median, percentile  # noqa: F401

from benchmark.layer_metrics._common import TRAIN_STEP, complete_runs

PREFIX = "rtpu."
# spans of CALLER threads: they say who waited for the engine's lock, not
# what the host did for the device, so they cover no idle time
NOT_COVER = ("rtpu.llm.lock_wait.",)
UNSCOPED = "(unscoped)"
NO_SPAN = "(no span)"

_cache: Dict[str, object] = {}


def trace_path(view) -> Optional[str]:
    return T.find_xplane(os.path.join(spec.OUT_DIR, view["cell"]["name"],
                                      "trace"))


# -- program spans of the traced run  -----------------------------------------

def program_spans(view) -> Optional[List[list]]:
    """[[name, start, seconds], ...] sorted by start, on the clock of
    ``view["trace"]`` (seconds from its first device event). None outside
    a traced run or where the trace holds no ``rtpu.*`` span."""
    if view.get("trace") is None:
        return None
    path = trace_path(view)
    if path is None:
        return None
    key = "spans:" + path
    if key not in _cache:
        _cache[key] = T.load_xplane(path, host_prefix=PREFIX).host
    return _cache[key] or None


def span_seconds(spans: Optional[Sequence[list]], prefix: str) -> List[float]:
    return [s[2] for s in spans or () if s[0].startswith(prefix)]


# -- the driver's ring  -------------------------------------------------------

def ring_spans(prefix: str) -> List[dict]:
    """Span events (``dur`` and ``parent`` beside ``ts``, ``kind``,
    ``label``, ``data``) of this process's flight recorder, oldest first.
    The ring holds 4096 events and drops the oldest: a reader that does
    not find its span returns None and does not guess."""
    try:
        from ray_tpu.perf import get_recorder
    except ImportError:
        return []
    return [ev for ev in get_recorder().snapshot(clear=False)
            if "dur" in ev and ev["kind"].startswith(prefix)]


# -- engine counters  ---------------------------------------------------------

def stats_delta(view) -> Optional[dict]:
    """``stats1 - stats0`` of the window for every counter both hold
    (numbers, and dicts of numbers such as ``lock_wait_s``), plus
    ``seconds`` between the two samples."""
    w = view.get("window")
    if not w or not w.get("stats0") or not w.get("stats1"):
        return None
    a, b = w["stats0"], w["stats1"]
    out = {"seconds": b.get("t", 0.0) - a.get("t", 0.0)}
    for k, v in b.items():
        if isinstance(v, dict) and isinstance(a.get(k), dict):
            out[k] = {kk: vv - a[k].get(kk, 0) for kk, vv in v.items()
                      if isinstance(vv, (int, float))}
        elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                and isinstance(a.get(k), (int, float)):
            out[k] = v - a[k]
    return out


def wait_samples(view, who: str) -> List[float]:
    """Seconds of the waits for the engine's lock (``who``: "intake" or
    "observer") that ended inside the window, from the sampled
    ``stats()``: between two consecutive samples ``lock_waits[who]`` rose
    by n and ``lock_wait_s[who]`` by s, which is n waits of s/n (exact
    where one wait ended between two samples 100 ms apart)."""
    w = view.get("window")
    if not w:
        return []
    series = [s for s in [w.get("stats0"), *w.get("samples", ()),
                          w.get("stats1")]
              if s and isinstance(s.get("lock_waits"), dict)]
    series.sort(key=lambda s: s.get("t", 0.0))
    out: List[float] = []
    for a, b in zip(series, series[1:]):
        n = b["lock_waits"].get(who, 0) - a["lock_waits"].get(who, 0)
        s = b["lock_wait_s"].get(who, 0.0) - a["lock_wait_s"].get(who, 0.0)
        if n > 0:
            out.extend([max(0.0, s) / n] * n)
    return out


# -- device self time by named scope  -----------------------------------------

_STRIP = re.compile(r"p?jit\([^()]*\)")


@functools.lru_cache(maxsize=None)
def _scope_rx(scopes: Tuple[str, ...]):
    return re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, scopes))
                      + r")(?=[/)]|$)")


def scope_of(op_name: str, scopes: Sequence[str]) -> str:
    """"jit(step)/jit(main)/transpose(jvp(attn))/dot_general" -> "attn":
    the innermost of ``scopes`` (a family's ``SCOPES``) on the operation's
    name stack, whatever transformation wraps it; names of jitted
    functions are not scopes, and a scope the family does not list is
    none."""
    found = _scope_rx(tuple(scopes)).findall(_STRIP.sub("", op_name))
    return found[-1] if found else UNSCOPED


# On this runtime (jax 0.9, TPU v5e) an operation's ``op_name`` is neither
# in the event's name (the HLO line, without its metadata) nor among the
# event's own statistics, which is all ``jax.profiler.ProfileData`` shows:
# it is the statistic ``tf_op`` of the event's METADATA record in the
# device plane ("jit(step)/transpose(jvp())/while/body/.../attn/div:").
# So the file's protobuf wire format is read here, the few fields needed
# (tsl/profiler/protobuf/xplane.proto: XSpace.planes=1; XPlane.name=2,
# event_metadata=4, stat_metadata=5; map entries key=1, value=2;
# XEventMetadata.name=2, stats=5; XStatMetadata.id=1, name=2;
# XStat.metadata_id=1, str_value=5, ref_value=7).

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def _map_values(plane, field: int):
    for f, entry in _fields(plane):
        if f == field:
            for ef, value in _fields(entry):
                if ef == 2:
                    yield value


def op_names(path: str) -> Dict[str, str]:
    """{operation name ("%fusion.7"): op_name} of the first TPU device
    plane of an xplane file; {} where no event metadata carries the
    statistic (a CPU trace, an older runtime)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for f, plane in _fields(space):
        if f == 1:
            name = next((bytes(v).decode() for pf, v in _fields(plane)
                         if pf == 2), "")
            m = T.DEVICE_PLANE.match(name)
            if m:
                planes[int(m.group(1))] = plane
    if not planes:
        return {}
    plane = planes[min(planes)]
    stat_names = {}
    for meta in _map_values(plane, 5):
        d = dict(_fields(meta))
        stat_names[d.get(1)] = bytes(d.get(2, b"")).decode()
    out: Dict[str, str] = {}
    for meta in _map_values(plane, 4):
        name, value = "", None
        for f, v in _fields(meta):
            if f == 2:
                name = bytes(v).decode(errors="replace")
            elif f == 5:
                d = dict(_fields(v))
                if stat_names.get(d.get(1)) == "tf_op":
                    value = bytes(d[5]).decode(errors="replace") \
                        if 5 in d else stat_names.get(d.get(7), "")
        if value:
            out[T.split_hlo(name)[0]] = value.rstrip(":")
    return out


def device_ops_with_names(path: str) -> Optional[Tuple[List[list], dict]]:
    """-> (operation events [[name, start, seconds], ...] of device 0 on
    the clock of ``lib.trace.load_xplane`` (seconds from the first device
    event), {operation name: op_name}); None if no operation of the trace
    carries an ``op_name``."""
    key = "ops:" + path
    if key in _cache:
        return _cache[key]
    from jax.profiler import ProfileData

    names = op_names(path)
    ops: List[list] = []
    if names:
        planes = {int(m.group(1)): p
                  for p in ProfileData.from_file(path).planes
                  for m in [T.DEVICE_PLANE.match(p.name)] if m}
        t0 = None
        for dev, plane in sorted(planes.items()):
            for line in plane.lines:
                if line.name not in T.OP_LINES + T.PROGRAM_LINES:
                    continue
                for e in line.events:
                    t0 = e.start_ns if t0 is None else min(t0, e.start_ns)
                    if dev == min(planes) and line.name in T.OP_LINES:
                        ops.append([T.split_hlo(e.name)[0], e.start_ns,
                                    e.duration_ns * 1e-9])
        for o in ops:
            o[1] = (o[1] - t0) * 1e-9
    _cache[key] = (ops, names) if ops else None
    return _cache[key]


def scope_ms_per_step(view) -> Optional[Dict[str, float]]:
    """Milliseconds of one train step by the scopes of the cell's family
    (its ``SCOPES`` and ``(unscoped)``): each operation's SELF time (an
    enclosing while loop is charged its duration less its body's) goes to
    the innermost of those scopes in its ``op_name``, forward, backward
    and recomputation alike; ``(unscoped)`` is the rest of the
    step: operations under no scope (optimizer, the scan's stacking
    copies) and any time inside the program in which no operation ran.
    Means over the train-step programs that lie whole inside the trace;
    the values sum to their mean duration. A fusion is charged whole to
    the scope of the operation that names it."""
    tr = view.get("trace")
    if tr is None:
        return None
    scopes = tuple(spec.family_of(view["cell"]).SCOPES)
    path = trace_path(view)
    steps = complete_runs(tr, TRAIN_STEP)
    if path is None or not steps:
        return None
    found = device_ops_with_names(path)
    if found is None:
        return None
    ops, names = found
    scope = {n: scope_of(names.get(n, ""), scopes)
             for n in {o[0] for o in ops}}
    if not any(s != UNSCOPED for s in scope.values()):
        return None                 # the program names none of the scopes
    starts = [p[1] for p in steps]
    ends = [p[1] + p[2] for p in steps]
    inside = []
    for o in ops:
        i = bisect.bisect_right(starts, o[1] + 1e-9) - 1
        if i >= 0 and o[1] < ends[i]:
            inside.append(o)
    out = {s: 0.0 for s in scopes + (UNSCOPED,)}
    for name, sec in T.self_times(inside).items():
        out[scope[name]] += sec
    out[UNSCOPED] += sum(p[2] for p in steps) - sum(out.values())
    return {k: 1e3 * v / len(steps) for k, v in out.items()}


# -- device idle time by program span  ----------------------------------------

def innermost_segments(spans: Sequence[list]) -> List[list]:
    """Spans that may nest and overlap -> disjoint [name, lo, hi] pieces,
    sorted, each named by the shortest of the spans open there."""
    points = sorted({t for _n, start, dur in spans
                     for t in (start, start + dur)})
    by_start = sorted(spans, key=lambda s: s[1])
    active: List[list] = []
    out: List[list] = []
    j = 0
    for lo, hi in zip(points, points[1:]):
        while j < len(by_start) and by_start[j][1] <= lo:
            active.append(by_start[j])
            j += 1
        active = [s for s in active if s[1] + s[2] > lo]
        if active:
            name = min(active, key=lambda s: s[2])[0]
            if out and out[-1][0] == name and out[-1][2] == lo:
                out[-1][2] = hi
            else:
                out.append([name, lo, hi])
    return out


def idle_by_span(trace: T.Trace, spans: Sequence[list]) -> Dict[str, float]:
    """Seconds device 0 ran no operation, inside the traced window, by
    the innermost program span that covers them (the shortest of the spans
    open at that instant; lock waits of caller threads cover nothing),
    ``(no span)`` for idle time no span covers."""
    w = trace.window()
    if w is None or not trace.devices:
        return {}
    dev = trace.devices[min(trace.devices)]
    busy = T.union((e[1], e[1] + e[2]) for e in dev["ops"] or dev["programs"])
    idle = T.subtract([w], busy)
    pieces = innermost_segments(
        [s for s in spans if not s[0].startswith(NOT_COVER)])
    out: Dict[str, float] = {}
    covered = 0.0
    j = 0
    for lo, hi in idle:
        while j < len(pieces) and pieces[j][2] <= lo:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < hi:
            part = min(hi, pieces[k][2]) - max(lo, pieces[k][1])
            if part > 0:
                out[pieces[k][0]] = out.get(pieces[k][0], 0.0) + part
                covered += part
            k += 1
    rest = T.total(idle) - covered
    if rest > 1e-12:
        out[NO_SPAN] = rest
    return out


# -- what the serving readers share (.batch / .online over one function)  -----

def intake_wait_ms_p95(view) -> Optional[float]:
    """95th percentile of the time a request waited for the engine's lock
    at intake (``add_request`` asking for it to having it), over the waits
    that ended inside the window (``wait_samples``)."""
    if view.get("trace") is None:
        return None
    waits = wait_samples(view, "intake")
    return 1e3 * percentile(waits, 95) if waits else None


def decode_span_ms(view, phase: str) -> Optional[float]:
    """Median seconds of the scheduler's ``rtpu.llm.decode.<phase>`` span
    per decode step of the traced stretch, in ms."""
    d = span_seconds(program_spans(view), "rtpu.llm.decode." + phase)
    return 1e3 * median(d) if d else None


def idle_unattributed_share(view) -> Optional[float]:
    """Share (%) of device 0's idle time in the traced stretch that lies
    under no program span: what the tracing still cannot see."""
    spans = program_spans(view)
    if not spans:
        return None
    by = idle_by_span(view["trace"], spans)
    idle = sum(by.values())
    return 100.0 * by.get(NO_SPAN, 0.0) / idle if idle else None
