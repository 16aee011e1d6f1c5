"""jax's own account of building a program, as spans of the flight
recorder.

jax publishes, through ``jax.monitoring``, the start and end on the wall
clock of the three phases every program goes through before it first
runs, and what the persistent compile cache did inside the third.
``install_jax_spans()`` turns them into span events of this process's
ring, in the recorder's own shape (``ts`` is the phase's start as jax
took it, ``dur``, ``parent`` the ``rtpu.*`` span open on that thread):

- ``rtpu.jax.trace``, label jax's ``fun_name`` (``bench_train_step``),
  from ``jaxpr_trace_duration``: the Python that traces the function to
  a jaxpr;
- ``rtpu.jax.lower``, label the module (``jit_bench_train_step``), from
  ``jaxpr_to_mlir_module_duration``: jaxpr to MLIR, every Pallas kernel
  to Mosaic;
- ``rtpu.jax.compile``, label the module, from
  ``backend_compile_duration``: the backend's compile, or the read of
  the executable from the persistent cache.

A module's label is its name in the lowered program and on a profiler's
"XLA Modules" line (jax's ``jit(f)`` made a name as jax makes it), so a
ring span and a device trace name a program alike.

Trace and lower run at EVERY start of a process, cached or not: the
persistent cache is keyed by the lowered module. A compile span's data
says what the cache did: ``{"cache": "off"}`` (no cache directory, or
the cache switched off), ``"miss"`` (asked, not found: the backend
compiled) or ``"hit"`` with ``read_s`` (``cache_retrieval_time_sec``) and
``saved_s`` (``compile_time_saved_sec``), from the cache events jax fired
on that thread inside the phase. A span of ``PIN_S`` seconds or more is pinned,
so a step's compile outlives the few hundred eager programs that may
follow it through the ring. ``ray_tpu_jax_compilations_total`` counts the
compile spans that were a backend compile (``cache`` not ``hit``).

Cost: jax fires none of these events on its cached dispatch path, so a
call of a program that is already built runs no line of this module
(``tests/test_perf.py`` holds a second call to an unchanged ring). Each
listener returns at its first test for an event that is not its own, and
at ``rec.enabled`` for one that is: ``RAY_TPU_FLIGHTREC=0`` keeps its
meaning, the counter included.

Only a phase that is the outermost on its thread leaves a span. A traced
function calls jitted helpers (``add``, ``multiply``, ``_where``: four
thousand of them in the smallest cell's step), each traced in turn inside
the outer trace and announced by jax like any other; as spans they would
turn the ring over before the step is built. They are part of the phase
they ran in and are counted there (``data["inner"]``).

Four listeners, because jax gives a phase's end as a time span, its start
as a scalar, the cache's two durations as duration events only, and its
request and hit as bare events; one installer, idempotent in a process,
called where a process of the program brings jax up
(``MeshWorkerMixin.setup_mesh``, ``LLMEngine.__init__``).
"""
from __future__ import annotations

import re
import threading

from ..util import metrics as _metrics
from . import recorder as _recorder

__all__ = ["install_jax_spans", "PIN_S"]

PIN_S = 0.1

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "rtpu.jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "rtpu.jax.lower",
    "/jax/core/compile/backend_compile_duration": "rtpu.jax.compile",
}
_CACHE = "/jax/compilation_cache/"
_CACHE_SECONDS = {_CACHE + "cache_retrieval_time_sec": "read_s",
                  _CACHE + "compile_time_saved_sec": "saved_s"}

_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")   # jax's mlir.sanitize_name

_C_COMPILES = _metrics.Counter(
    "ray_tpu_jax_compilations_total",
    "backend compiles of jax programs in this process (rtpu.jax.compile "
    "spans the persistent cache did not serve), while the flight "
    "recorder is on")

# per thread. .cache: what the persistent cache has said since the
# thread's last compile phase ended (jax asks, reads and compiles on one
# thread); .depth: the phases open on it; .inner: those that ended inside
# the outermost one that is still open
_TLS = threading.local()
_lock = threading.Lock()
_installed = False


def _on_event(event: str, **_kw) -> None:
    if not event.startswith(_CACHE):
        return
    if event.endswith("/compile_requests_use_cache"):
        if _recorder.get_recorder().enabled:
            import jax

            # jax asks its cache even where it has no directory
            _TLS.cache = {"cache": "miss" if
                          jax.config.jax_compilation_cache_dir else "off"}
    elif event.endswith("/cache_hits"):
        cache = getattr(_TLS, "cache", None)
        if cache is not None:
            cache["cache"] = "hit"


def _on_duration(event: str, seconds: float, **_kw) -> None:
    key = _CACHE_SECONDS.get(event)
    if key is not None:
        cache = getattr(_TLS, "cache", None)
        if cache is not None:
            cache[key] = seconds


def _on_begin(event: str, _start: float, **_kw) -> None:
    """jax stamps a phase's start as a scalar of the phase's name."""
    if event in _PHASES:
        _TLS.depth = getattr(_TLS, "depth", 0) + 1


def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_kw) -> None:
    kind = _PHASES.get(event)
    if kind is None:
        return
    depth = _TLS.depth = max(0, getattr(_TLS, "depth", 1) - 1)
    cache, _TLS.cache = getattr(_TLS, "cache", None), None
    if depth:
        # inside another phase on this thread (the jitted helpers a
        # traced function calls: thousands in a model's step): part of
        # that phase, counted there
        _TLS.inner = getattr(_TLS, "inner", 0) + 1
        return
    inner, _TLS.inner = getattr(_TLS, "inner", 0), 0
    rec = _recorder.get_recorder()
    if not rec.enabled:
        return
    data = {"inner": inner} if inner else None
    label = str(fun_name)
    if kind != "rtpu.jax.trace":
        label = _NOT_IN_A_MODULE_NAME.sub("_", label).rstrip("_")
    if kind == "rtpu.jax.compile":
        data = dict(cache or {"cache": "off"}, **(data or {}))
        if data["cache"] != "hit":
            _C_COMPILES.inc()
    rec.add_span(kind, start, end - start, label, data,
                 pin=end - start >= PIN_S)


def install_jax_spans() -> None:
    """Register this module's listeners with ``jax.monitoring``, once a
    process however often it is called. Imports jax: call it where the
    process brings jax up anyway."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring as monitoring

        monitoring.register_scalar_listener(_on_begin)
        monitoring.register_event_time_span_listener(_on_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True
