"""Performance-introspection plane (ISSUE 17).

Three coupled pieces, built so the next perf arc (overlap-scheduled
collectives, chunked prefill, kernel speed) has something to aim at and
something to prove with:

- :mod:`ray_tpu.perf.recorder` — the flight recorder: an always-on,
  bounded, lock-light per-process ring of structured runtime events
  (cgraph op begin/end, channel send/recv seq, engine admissions and
  preemptions, dispatch decisions). Overhead is one attribute test when
  disabled and a deque append + dict build when enabled, asserted
  CPU-count-aware in tests.
- :mod:`ray_tpu.perf.jaxbuild` — jax's own account of building a
  program (trace, lower, compile or the read from the compile cache) as
  ``rtpu.jax.*`` spans of that ring, and the compile counter.
- :mod:`ray_tpu.perf.chipwatch` — the chip watcher: in the process that
  holds a TPU, the runtime's own counters and the host's, sampled a few
  times a second into that ring (``rtpu.chip.sample``), and a standstill
  of device and host together as a span (``rtpu.chip.stall``).
- :mod:`ray_tpu.perf.report` — :class:`StepReport`: the structured
  result of ``CompiledPipelineEngine.profile()`` /
  ``LLMEngine.profile()``, with per-stage exec/bubble/recv/sync
  breakdowns, MFU, chrome-trace export, and microbatch tuning hints.
- :mod:`ray_tpu.perf.postmortem` — merged driver+worker bundle dumps
  triggered by every abort path and, as ``<Result.path>/flight.json``, by
  the end of every ``fit()``; rendered by ``ray_tpu postmortem``.
- :mod:`ray_tpu.perf.snapshot` — the one head RPC feeding
  ``ray_tpu top``.

docs/OBSERVABILITY.md "Profiling & post-mortem" is the schema
reference.
"""
from .chipwatch import start_chip_watch  # noqa: F401
from .jaxbuild import install_jax_spans  # noqa: F401
from .recorder import (FlightRecorder, get_recorder, record,  # noqa: F401
                       recorder_enabled, set_enabled)
from .report import (StepReport, analytic_bubble_frac,  # noqa: F401
                     compute_mfu)
from .postmortem import (dump_bundle, last_bundle_path,  # noqa: F401
                         load_bundle, render_bundle)

__all__ = [
    "FlightRecorder", "get_recorder", "record", "recorder_enabled",
    "set_enabled", "install_jax_spans", "start_chip_watch", "StepReport",
    "analytic_bubble_frac", "compute_mfu",
    "dump_bundle", "last_bundle_path", "load_bundle", "render_bundle",
]
