"""Flight recorder: an always-on, bounded, lock-light per-process ring
of structured runtime events.

Design constraints (ISSUE 17 tentpole b):

- **Always on, bounded.** The ring is a ``collections.deque(maxlen=N)``
  — append is O(1), thread-safe under the GIL, and the oldest event is
  dropped implicitly on overflow. Capacity defaults to
  ``RAY_TPU_FLIGHTREC_CAP`` (4096 events); at ~120 bytes/event the
  steady-state footprint is sub-megabyte per process.
- **Lock-light.** ``record()`` takes no lock: one enabled-flag test, a
  tuple build, a deque append, and a non-atomic length check for the
  drop counter. The drop count is reconciled exactly in ``snapshot()``
  (appended minus retained), so the occasional racy fast-path
  undercount never survives a drain; the reconciled total feeds
  ``ray_tpu_flightrec_dropped_total``.
- **Structured.** Events are ``(ts, kind, label, data)`` tuples —
  ``ts`` is ``time.time()`` (wall clock, so driver+worker rings merge
  on one axis), ``kind`` is a short dotted string from the table in
  docs/OBSERVABILITY.md (``cgraph.op.begin``, ``chan.send``,
  ``llm.admit``, ...), ``label`` identifies the instance (op key,
  channel id, request id) and ``data`` is a small dict or None.
- **One way to time a phase.** ``span(kind)`` is a context manager that
  appends ONE event at its end: the instant event's four fields plus
  ``dur`` (seconds, ``perf_counter``) and ``parent`` (the kind of the
  span that encloses it on this thread: what caused it). Where jax is
  already imported in the process the body also runs inside
  ``jax.profiler.TraceAnnotation(kind)``, so the same span lies on the
  host plane of any profiler trace, on the clock of the device planes.
  This module never imports jax: a driver or head that must not start
  a backend can time its phases too. Span kinds are
  ``rtpu.<layer>.<phase>``; what a reader must know (a prefill bucket,
  intake or observer) is part of the kind, because a profiler trace
  keeps names only. A span always measures (``.dur`` is there after
  the block, recorder on or off); the ring and the annotation are what
  ``enabled`` gates. ``begin()``/``end()`` are the pair for a span that
  crosses a function boundary, possibly a thread: ring only.
- **Start-up is pinned.** A driver's ring turns over within a minute
  (every task it dispatches is an event), so the handful of spans that
  say where a process's start-up went (``pin=True``: ``rtpu.core.init``,
  ``rtpu.core.worker_spawn``, ``rtpu.train.setup_mesh``, ...) are also
  held on a shelf of ``PINNED_CAPACITY`` beside the ring, and
  ``snapshot()`` puts those the ring has dropped in front of it.

Host modules (cgraph executor, channels, engines) hold a module-level
``_FLREC`` pointing at the process singleton — the chaos-layer hook
pattern — and guard every record with ``if _FLREC.enabled`` so the
disabled A/B leg pays one attribute load.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..util import metrics as _metrics

__all__ = ["FlightRecorder", "get_recorder", "record",
           "recorder_enabled", "set_enabled", "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = int(os.environ.get("RAY_TPU_FLIGHTREC_CAP", "4096"))
PINNED_CAPACITY = 256

_C_DROPPED = _metrics.Counter(
    "ray_tpu_flightrec_dropped_total",
    "flight-recorder ring events dropped (oldest-first) on overflow")


_TLS = threading.local()       # .stack: kinds of the spans open on a thread


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` if jax is ALREADY imported here,
    else None (looked up again at the next span)."""
    jax = sys.modules.get("jax")
    try:
        return jax.profiler.TraceAnnotation if jax is not None else None
    except AttributeError:      # jax is being imported on another thread
        return None


class _Span:
    """One timed phase; see ``FlightRecorder.span``. ``data`` may be set
    inside the block, ``keep = False`` leaves the ring alone (a scheduler
    step that found no work), ``dur`` holds the seconds afterwards."""

    __slots__ = ("_rec", "kind", "label", "data", "keep", "dur", "_merge",
                 "_pin", "_ts", "_t0", "_parent", "_ann", "_stack")

    def __init__(self, rec: "FlightRecorder", kind: str, label: str,
                 data: Optional[Dict[str, Any]], merge: bool, pin: bool):
        self._rec, self.kind, self.label, self.data = rec, kind, label, data
        self._merge, self._pin = merge, pin
        self.keep = True
        self.dur = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self._stack = stack
        self._parent = stack[-1] if stack else ""
        stack.append(self.kind)
        if self._rec.enabled:
            cls = _annotation_cls()
            if cls is not None:
                self._ann = cls(self.kind)
                self._ann.__enter__()
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        rec = self._rec
        if not (rec.enabled and self.keep):
            return
        ring = rec._ring
        if self._merge and ring:
            last = ring[-1]
            if len(last) > 4 and last[1] == self.kind \
                    and last[2] == self.label:
                # a repeat straight after its like (an idle wait after
                # an idle wait): one event from the first start to here
                ring[-1] = (last[0], self.kind, self.label, last[3],
                            self._ts - last[0] + dur, self._parent)
                return
        rec._append((self._ts, self.kind, self.label, self.data, dur,
                     self._parent), self._pin)


class FlightRecorder:
    """One process's event ring. ``record()`` is the hot path; all
    bookkeeping that needs exactness happens in ``snapshot()``."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: Optional[bool] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._pinned: deque = deque(maxlen=PINNED_CAPACITY)
        self._appended = 0          # racy-fast increments; see snapshot()
        self._dropped_flushed = 0   # drops already shipped to the metric
        self._snap_lock = threading.Lock()
        if enabled is None:
            enabled = os.environ.get("RAY_TPU_FLIGHTREC", "1") != "0"
        self.enabled = bool(enabled)

    # -- hot path ----------------------------------------------------------

    def record(self, kind: str, label: str = "",
               data: Optional[Dict[str, Any]] = None) -> None:
        """Append one event. No lock: deque.append is GIL-atomic, and the
        ``_appended`` increment may rarely lose a tick under contention —
        acceptable, because ``snapshot()`` recomputes the drop total from
        retained length and never reports fewer drops than really
        happened after a drain."""
        if not self.enabled:
            return
        self._ring.append((time.time(), kind, label, data))
        self._appended += 1

    def span(self, kind: str, label: str = "",
             data: Optional[Dict[str, Any]] = None,
             merge: bool = False, pin: bool = False) -> _Span:
        """``with rec.span("rtpu.llm.step", engine):`` — times the block
        and, at its end, appends one event with ``dur`` and ``parent``
        (module docstring). ``merge=True`` folds a repeat that directly
        follows its like in the ring into that event (a loop's idle
        waits), so a quiet process does not turn its ring over;
        ``pin=True`` keeps a start-up span past the ring's turnover."""
        return _Span(self, kind, label, data, merge, pin)

    def begin(self, kind: str, label: str = "",
              data: Optional[Dict[str, Any]] = None,
              pin: bool = False) -> tuple:
        """Start of a span that ends in another function or on another
        thread (``end``). Ring only: a profiler annotation must end on
        the thread that began it."""
        stack = getattr(_TLS, "stack", None)
        return (time.time(), time.perf_counter(), kind, label, data,
                stack[-1] if stack else "", pin)

    def end(self, token: tuple,
            data: Optional[Dict[str, Any]] = None) -> float:
        """Closes ``begin``'s span; ``data`` is merged over what
        ``begin`` was given. -> the seconds it took."""
        ts, t0, kind, label, d0, parent, pin = token
        dur = time.perf_counter() - t0
        if self.enabled:
            if data is not None:
                d0 = dict(d0 or {}, **data)
            self._append((ts, kind, label, d0, dur, parent), pin)
        return dur

    def add_span(self, kind: str, ts: float, dur: float, label: str = "",
                 data: Optional[Dict[str, Any]] = None,
                 pin: bool = False) -> None:
        """A span that someone else timed (jax its own build phases,
        ``perf/jaxbuild.py``): ``ts`` its start on the wall clock, ``dur``
        its seconds; ``parent`` is the span open on the calling thread."""
        if self.enabled:
            stack = getattr(_TLS, "stack", None)
            self._append((ts, kind, label, data, dur,
                          stack[-1] if stack else ""), pin)

    def _append(self, event: tuple, pin: bool) -> None:
        self._ring.append(event)
        self._appended += 1
        if pin:
            self._pinned.append(event)

    def spans(self, prefix: str = "", since: float = 0.0) -> List[dict]:
        """The span events still in the ring (oldest first) whose kind
        starts with ``prefix`` and that began at or after ``since``."""
        return [ev for ev in self.snapshot(clear=False)
                if "dur" in ev and ev["ts"] >= since
                and ev["kind"].startswith(prefix)]

    # -- drain / accounting ------------------------------------------------

    @property
    def dropped(self) -> int:
        """Events lost to overflow so far (monotone, reconciled)."""
        return max(0, self._appended - len(self._ring))

    def snapshot(self, clear: bool = False) -> List[dict]:
        """Drain the ring into a list of wire-safe dicts (oldest first)
        and flush the drop delta into
        ``ray_tpu_flightrec_dropped_total``."""
        with self._snap_lock:
            events = list(self._ring)
            # pinned spans the ring has dropped, oldest first, in front
            held = set(map(id, events))
            events[:0] = [ev for ev in self._pinned if id(ev) not in held]
            dropped = self.dropped  # BEFORE clear: drained events are
            if clear:               # delivered, not dropped
                self._ring.clear()
                self._pinned.clear()
                # keep the drop ledger: with the ring empty, appended
                # minus retained must still equal the historic total
                self._appended = dropped
            delta = dropped - self._dropped_flushed
            if delta > 0:
                _C_DROPPED.inc(delta)
                self._dropped_flushed += delta
        out = []
        for ev in events:
            d = {"ts": ev[0], "kind": ev[1], "label": ev[2], "data": ev[3]}
            if len(ev) > 4:         # a span: seconds, and what caused it
                d["dur"], d["parent"] = ev[4], ev[5]
            out.append(d)
        return out

    def stats(self) -> dict:
        return {"capacity": self.capacity, "size": len(self._ring),
                "appended": self._appended, "dropped": self.dropped,
                "enabled": self.enabled}


# ---------------------------------------------------------------------------
# process singleton
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_GLOBAL: Optional[FlightRecorder] = None


def get_recorder() -> FlightRecorder:
    global _GLOBAL
    rec = _GLOBAL
    if rec is None:
        with _LOCK:
            rec = _GLOBAL
            if rec is None:
                rec = _GLOBAL = FlightRecorder()
    return rec


def record(kind: str, label: str = "",
           data: Optional[Dict[str, Any]] = None) -> None:
    """Module-level convenience for cold paths (admissions, placements,
    aborts). Hot loops should cache ``get_recorder()`` in a module
    global instead."""
    get_recorder().record(kind, label, data)


def recorder_enabled() -> bool:
    return get_recorder().enabled


def set_enabled(on: bool) -> None:
    """Flip the process recorder (the bench A/B switch). Events already
    in the ring stay; only future records are gated."""
    get_recorder().enabled = bool(on)
