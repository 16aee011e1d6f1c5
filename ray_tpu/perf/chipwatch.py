"""The chip watcher: what the chip and the host's other threads were doing,
a few times a second, in the flight ring of the process that holds the chip.

A training loop syncs with its device every few steps and stamps the ring
every twenty (``rtpu.train.report``); between two stamps a device that
stood still for seconds leaves nothing (ROADMAP A14). The watcher needs no
cooperation from the loop. One daemon thread (``rtpu-chip-watch``), started
once a process by ``MeshWorkerMixin.setup_mesh`` where the worker's devices
are TPUs and the recorder is on, appends ``SAMPLE_HZ`` times a second ONE
span ``rtpu.chip.sample`` whose ``dur`` is what taking the sample cost its
thread and whose ``data`` holds

- ``chip``: the runtime's own counters from ``libtpu.sdk.tpumonitoring``,
  parsed to numbers, those that proved live inside a chip worker (PERF.md
  section 7 has what each of the fourteen returned): ``queue``
  (``hlo_queue_size``: programs enqueued and not yet dequeued) and
  ``exec_us`` (``hlo_execution_timing``: mean, p50, p90, p95, p99.9 of a
  program's microseconds from enqueue to dequeue; it moves with every
  program that completes), asked for every ``FAST_EVERY`` samples (a
  ``get_metric`` is a call into the runtime's own gRPC server: 3 to 8 ms
  for its caller and some 30 ms of CPU over the runtime's threads, so eight
  a second burned a quarter of a core), with ``age_s``, the age of that
  reading when the sample was stamped, and ``took_s``, what the sweep cost
  ITS thread; ``duty_pct`` (``duty_cycle_pct``) and
  ``hbm_bytes`` (``hbm_capacity_usage``), which the runtime renews every
  five seconds, asked for every ``SLOW_EVERY`` samples, with
  ``slow_age_s``. The first core's where a worker holds several;
- the host's half, all cumulative so that any two samples give a rate:
  ``cpu_s`` (the process's CPU seconds), ``watch_cpu_s`` (this thread's
  share of them), ``loop_cpu_s`` (the CPU clock of the thread inside the
  loop function, ``train/session.loop_state``), ``nvcsw`` / ``nivcsw`` /
  ``majflt`` (``getrusage``), ``load1``, the ``iteration`` of the last
  ``train.report`` and, every ``THREADS_EVERY`` samples where
  ``/proc/self/task`` is readable (a read a thread: 30 ms of a sample under
  the chip machine's sandbox, which is why not at each), ``threads``: the
  ``TOP_THREADS`` threads whose CPU time grew most since the last such
  sweep, ``[name, seconds]`` (the runtime's threads are not Python's, so
  only the kernel names them; its clock ticks 10 ms).

**The chip's half is read on a thread of its own** (``rtpu-chip-source``),
never the loop's and under no lock the loop takes. A ``get_metric`` holds
the GIL while it runs, so the sampler does not wait for it: it asks, and
a sample carries the newest sweep there was at its stamp (``age_s`` old). A source that has stood
asked through ``SOURCE_TIMEOUT_S`` of the sampler's own ticks is given up
for the life of the process with one event ``rtpu.chip.source_lost``, and
the samples go on with the host's half. ``get_metric`` is never called
where the backend is not ``tpu`` (without a chip it does not return, and
one call stops every Python thread of the process).

**A stall is a span.** The signature of A14's standstill is the device idle
WHILE the host is idle too, with steps queued (a compile is the device idle
and the host busy; a steady step is the host idle and the device busy; a
loop that waits for one long program has one program queued, not several).
A sample is STILL when the loop thread's CPU clock did not move, the rest
of the process (less the watcher's own thread) spent under ``BUSY_SHARE``
of a core over the last second (a compile waits on the thread that asked
for it and burns six cores on the compiler's) and, condition ``chip``, the
newest two sweeps show ``STALL_QUEUE`` programs or more enqueued and the
same ``exec_us`` (none completed between them); where no chip counter is
live the first two are the whole test (condition ``host``), and a loop
that waits seconds for one long program cannot be told from a standstill.

Steps of a second are still for three samples in four, so the detector
learns what quiet looks like: ``rtpu.chip.stall`` opens (``begin()``,
pinned) when the samples have been still for ``STILL_FACTOR`` times the
longest still stretch this process has seen end of itself, ``STILL_MIN_S``
(one second; two on the host's test alone) at least, and closes (``end()``) at the first sample that is not. Before
the loop's first report nothing is judged (set-up has its own long
waits), and up to its second the detector only learns. Its ``data``: ``condition`` (which test
fired), ``since`` (the stamp of the last sample before the stretch), the
``iteration`` of the last report before it, ``opened`` / ``closed`` (the
samples at both ends), ``samples`` (how many it lasted) and ``stacks``:
ONE ``util/introspect.dump_stacks()`` taken as it opened. It starts no
profiler session.

Everything is an event of the process's ``FlightRecorder``, stamped
``time.time()``, saved by ``fit()``'s flight record, and switched off with
the recorder (``RAY_TPU_FLIGHTREC=0``) and by nothing else.
"""
from __future__ import annotations

import os
import resource
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import recorder as _recorder

__all__ = ["start_chip_watch", "ChipWatcher", "StallDetector",
           "TpuMonitoring", "SAMPLE_HZ"]

SAMPLE_HZ = 4.0
FAST_EVERY = 2              # samples between two readings of FAST_COUNTERS
SLOW_EVERY = 8              # and of SLOW_COUNTERS (a multiple of FAST_EVERY)
THREADS_EVERY = 8           # and between two sweeps of /proc/self/task
SOURCE_TIMEOUT_S = 2.0      # a source silent for this long is given up
TOP_THREADS = 3

# a sample is still when the loop thread's CPU clock moved by less than
# FLAT_CPU_S since the last one, the rest of the process (less the
# watcher's own thread) spent under BUSY_SHARE of the last BUSY_OVER_S
# (a compile runs on the compiler's threads, six cores of them, while the
# thread that asked for it waits; a standstill read 0.15 of a core, all of
# it the runtime answering this watcher) and the device, where its counters
# are live, completed nothing with STALL_QUEUE programs or more enqueued
FLAT_CPU_S = 0.0005
BUSY_SHARE = 0.5
BUSY_OVER_S = 1.0
STALL_QUEUE = 2             # "steps queued": the running one and one more
# a stall: still for STILL_FACTOR times the longest still stretch this
# process has seen end of itself, and STILL_MIN_S at least
STILL_FACTOR = 3.0
STILL_MIN_S = {"chip": 1.0, "host": 2.0}    # the host's alone: a thread's
# CPU clock ticks 10 ms on the chip machine, three quiet samples in four
LEARN_REPORTS = 1           # reports through which it learns and opens none

# tpumonitoring's metric -> the key of a sample's ``chip``
FAST_COUNTERS = {"hlo_queue_size": "queue", "hlo_execution_timing": "exec_us"}
SLOW_COUNTERS = {"duty_cycle_pct": "duty_pct",
                 "hbm_capacity_usage": "hbm_bytes"}


def _numbers(data: Any) -> List[float]:
    """The numbers of the FIRST string of a ``LibtpuSdkMetric.data()``: the
    runtime hands a list of strings, one a chip or a core (``"100.00"``,
    ``"tensor_core-0: 3"``, ``"tensor_core-0, 436534.32, 459567.87, ..."``);
    what is no number (the core's name) is dropped. [] for an empty list,
    which is what a counter that is not live returns."""
    out: List[float] = []
    for word in str(data[0] if data else "").replace(",", " ").split():
        try:
            out.append(float(word))
        except ValueError:
            pass
    return out


class TpuMonitoring:
    """The runtime's own counters, through ``libtpu.sdk.tpumonitoring``.
    ``read(slow)`` is one sweep over ``FAST_COUNTERS`` (and, with ``slow``,
    ``SLOW_COUNTERS``) -> {key: number, or the list where a metric is a
    distribution}; a counter that returns nothing is left out."""

    def __init__(self):
        from libtpu.sdk import tpumonitoring

        self._get = tpumonitoring.get_metric
        self._supported = set(tpumonitoring.list_supported_metrics())

    def read(self, slow: bool = False) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        wanted = dict(FAST_COUNTERS, **SLOW_COUNTERS) if slow \
            else FAST_COUNTERS
        for name, key in wanted.items():
            if name not in self._supported:
                continue
            nums = _numbers(self._get(name).data())
            if nums:
                out[key] = nums if key == "exec_us" else nums[0]
        return out


class _AskedSource:
    """A counter source on a thread of its own: ``tick()`` wakes it for one
    sweep, ``newest(now)`` is what it has read with its ages, ``lost``
    turns True (for good) once a question has stood through
    ``SOURCE_TIMEOUT_S`` of the asker's ticks (ticks, not the clock alone:
    a process that was itself stopped for seconds has not waited)."""

    def __init__(self, read: Callable[[bool], Dict[str, Any]],
                 clock: Callable[[], float]):
        self._read, self._clock = read, clock
        self._wanted = threading.Event()
        self._slow = False
        self._asked_at: Optional[float] = None
        self._asked_ticks = 0
        self._values: Dict[str, Any] = {}
        self._fast_at = self._slow_at = 0.0
        self._took = 0.0
        self.sweeps = 0
        self.idle = threading.Event()       # no question stands
        self.idle.set()
        self.lost = False
        self.error: Optional[str] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="rtpu-chip-source")
        self._thread.start()

    def _run(self) -> None:
        while True:
            self._wanted.wait()
            self._wanted.clear()
            if self.lost:
                return
            slow, t0 = self._slow, time.perf_counter()
            try:
                values = self._read(slow)
            except Exception as e:  # noqa: BLE001 - a counter, not the job
                self.error, self.lost = repr(e), True
                self.idle.set()
                return
            now = self._clock()
            self._took = time.perf_counter() - t0
            self._values = dict(self._values, **values)
            self._fast_at = now
            if slow:
                self._slow_at = now
            self.sweeps += 1
            self._asked_at = None
            self.idle.set()

    def close(self) -> None:
        self.lost = True
        self._wanted.set()      # to its end, once it is back from a read

    def tick(self, now: float, ask: bool, slow: bool) -> None:
        """One of the asker's ticks: a question that stands is counted
        against ``SOURCE_TIMEOUT_S``; else, with ``ask``, a new one."""
        if self.lost:
            return
        if self._asked_at is not None:
            self._asked_ticks += 1
            if now - self._asked_at >= SOURCE_TIMEOUT_S \
                    and self._asked_ticks >= SOURCE_TIMEOUT_S * SAMPLE_HZ:
                self.close()
        elif ask:
            self._asked_at, self._asked_ticks, self._slow = now, 0, slow
            self.idle.clear()
            self._wanted.set()

    def newest(self, now: float) -> Optional[Dict[str, Any]]:
        if not self.sweeps:
            return None
        out = dict(self._values, age_s=max(0.0, now - self._fast_at),
                   took_s=self._took, sweep=self.sweeps)
        if self._slow_at:
            out["slow_age_s"] = max(0.0, now - self._slow_at)
        return out


class StallDetector:
    """Says, sample by sample, whether the process stands still: ``update``
    -> ``"open"`` (the samples have been still long enough: the module's
    docstring), ``"close"`` (the first sample that is not still after
    that) or None. ``condition`` and ``since`` describe the open stall."""

    def __init__(self):
        self.condition: Optional[str] = None
        self.since: Optional[float] = None
        self._last: Optional[dict] = None
        self._last_sweep: Optional[dict] = None     # the sweep before
        self._stuck = False                         # what the two said
        self._others: List[Tuple[float, float]] = []    # (ts, CPU seconds)
        self._still_since: Optional[float] = None
        self.longest_still_s = 0.0

    def _host_busy(self, sample: dict) -> bool:
        """The process, less the watcher's own thread, spent ``BUSY_SHARE``
        of a core or more over the last ``BUSY_OVER_S``."""
        seen = self._others
        seen.append((sample["ts"],
                     sample["cpu_s"] - sample.get("watch_cpu_s", 0.0)))
        while len(seen) > 2 and sample["ts"] - seen[1][0] >= BUSY_OVER_S:
            del seen[0]
        (t0, cpu0), (t1, cpu1) = seen[0], seen[-1]
        return t1 > t0 and cpu1 - cpu0 >= BUSY_SHARE * (t1 - t0)

    def _is_still(self, last: dict, sample: dict) -> Optional[str]:
        """-> the condition under which ``sample`` is still, or None."""
        busy = self._host_busy(sample)
        if sample.get("loop_cpu_s") is None \
                or last.get("loop_cpu_s") is None \
                or not sample.get("iteration"):
            # no loop on this process, or one that has not reported yet
            # (set-up has its own long waits): nothing to judge
            return None
        idle = not busy and \
            sample["loop_cpu_s"] - last["loop_cpu_s"] < FLAT_CPU_S
        chip = sample.get("chip")
        if chip is None:
            return "host" if idle else None
        if "queue" not in chip or "exec_us" not in chip:
            return None             # a runtime that has run no program yet
        before = self._last_sweep
        if before is None or chip["sweep"] != before["sweep"]:
            self._stuck = (before is not None
                           and chip["queue"] >= STALL_QUEUE
                           and before["queue"] >= STALL_QUEUE
                           and chip["exec_us"] == before["exec_us"])
            self._last_sweep = chip
        return "chip" if idle and self._stuck else None

    def update(self, ts: float, sample: dict) -> Optional[str]:
        sample = dict(sample, ts=ts)
        last, self._last = self._last, sample
        still = self._is_still(last, sample) if last is not None else None
        if still is None:
            if self._still_since is not None and self.condition is None:
                # a stretch that ended of itself: what quiet looks like
                self.longest_still_s = max(self.longest_still_s,
                                           last["ts"] - self._still_since)
            self._still_since = None
            if self.condition is None:
                return None
            self.condition = self.since = None
            return "close"
        if self._still_since is None:
            self._still_since = last["ts"]
        if self.condition is None \
                and sample["iteration"] > LEARN_REPORTS \
                and ts - self._still_since >= max(
                    STILL_MIN_S[still],
                    STILL_FACTOR * self.longest_still_s):
            self.condition, self.since = still, self._still_since
            return "open"
        return None


def _thread_cpu(previous: Dict[str, Tuple[str, float]]
                ) -> Tuple[Optional[list], Dict[str, Tuple[str, float]]]:
    """-> (the ``TOP_THREADS`` tasks whose CPU seconds grew most since
    ``previous``, the table for the next call); (None, {}) where
    ``/proc/self/task`` cannot be read."""
    tick = os.sysconf("SC_CLK_TCK")
    now: Dict[str, Tuple[str, float]] = {}
    try:
        for tid in os.listdir("/proc/self/task"):
            try:
                with open(f"/proc/self/task/{tid}/stat") as f:
                    head, rest = f.read().rsplit(")", 1)
            except OSError:
                continue            # a thread that ended meanwhile
            fields = rest.split()
            # utime + stime: fields 14 and 15 of proc(5)'s stat
            now[tid] = (head.split("(", 1)[1],
                        (int(fields[11]) + int(fields[12])) / tick)
    except (OSError, ValueError, IndexError):
        return None, {}
    grew = sorted(((cpu - previous[tid][1], name)
                   for tid, (name, cpu) in now.items() if tid in previous),
                  reverse=True)[:TOP_THREADS]
    return [[name, round(d, 4)] for d, name in grew if d > 0], now


class ChipWatcher:
    """The sampler. ``sample_once()`` takes one sample and runs the stall
    detector on it (what the thread does ``SAMPLE_HZ`` times a second, and
    what a test drives directly with a fake ``source``, ``host`` and
    ``clock``)."""

    def __init__(self, source: Optional[Callable[[bool], Dict[str, Any]]]
                 = None,
                 host: Optional[Callable[[], dict]] = None,
                 clock: Callable[[], float] = time.time,
                 recorder: Optional[_recorder.FlightRecorder] = None):
        self._rec = recorder or _recorder.get_recorder()
        self._clock = clock
        self._host = host or self._host_counters
        # None once it is lost (which ends its thread) or where there is none
        self._source = _AskedSource(source, clock) if source else None
        self._taken = 0
        self._threads: Dict[str, Tuple[str, float]] = {}
        self._loop_clock: Tuple[Optional[int], Optional[int]] = (None, None)
        self.detector = StallDetector()
        self._stall: Optional[tuple] = None     # begin()'s token
        self._stall_samples = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- the host's half ---------------------------------------------------

    def _host_counters(self) -> dict:
        from ..train.session import loop_state

        ident, iteration = loop_state()
        loop_cpu = None
        if ident is not None:
            if self._loop_clock[0] != ident:
                self._loop_clock = (ident, time.pthread_getcpuclockid(ident))
            loop_cpu = time.clock_gettime(self._loop_clock[1])
        ru = resource.getrusage(resource.RUSAGE_SELF)
        top = None
        if self._taken % THREADS_EVERY == 0:
            top, self._threads = _thread_cpu(self._threads)
        out = {"cpu_s": time.process_time(),
               "watch_cpu_s": time.thread_time(), "loop_cpu_s": loop_cpu,
               "nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw,
               "majflt": ru.ru_majflt, "load1": os.getloadavg()[0],
               "iteration": iteration}
        if top is not None:
            out["threads"] = top
        return out

    # -- one sample --------------------------------------------------------

    def sample_once(self) -> dict:
        rec = self._rec
        ts, t0 = self._clock(), time.perf_counter()
        sample = self._host()
        src = self._source
        if src is not None:
            # what the source knew at this sample's stamp, then the
            # question whose answer the next sample carries (asked last:
            # its thread holds the GIL while the runtime answers)
            chip = src.newest(ts)
            src.tick(ts, ask=self._taken % FAST_EVERY == 0,
                     slow=self._taken % SLOW_EVERY == 0)
            if src.lost:        # silent too long, or it raised: said once,
                self._source = None             # and never asked again
                rec.record("rtpu.chip.source_lost", "tpumonitoring",
                           {"error": src.error} if src.error
                           else {"silent_s": SOURCE_TIMEOUT_S})
            elif chip is not None:
                sample["chip"] = chip
        self._taken += 1
        rec.add_span("rtpu.chip.sample", ts, time.perf_counter() - t0,
                     data=sample)
        self._judge(ts, sample)
        return sample

    def _judge(self, ts: float, sample: dict) -> None:
        verdict = self.detector.update(ts, sample)
        if verdict == "open":
            from ..util.introspect import dump_stacks

            self._stall_samples = 0
            self._stall = self._rec.begin(
                "rtpu.chip.stall", self.detector.condition or "",
                {"condition": self.detector.condition,
                 "since": self.detector.since,
                 "iteration": sample.get("iteration"),
                 "opened": sample, "stacks": dump_stacks()}, pin=True)
        elif verdict == "close" and self._stall is not None:
            self._rec.end(self._stall, {"closed": sample,
                                        "samples": self._stall_samples})
            self._stall = None
        if self._stall is not None:
            self._stall_samples += 1

    # -- the thread --------------------------------------------------------

    def start(self) -> "ChipWatcher":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="rtpu-chip-watch")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._source is not None:
            self._source.close()

    def _run(self) -> None:
        period = 1.0 / SAMPLE_HZ
        due = time.monotonic() + period
        while not self._stop.wait(max(0.0, due - time.monotonic())):
            # on the beat; a beat that was missed is not made up for
            due = max(due + period, time.monotonic())
            if not self._rec.enabled:
                continue
            try:
                self.sample_once()
            except Exception as e:  # noqa: BLE001 - a watcher, not the job
                self._rec.record("rtpu.chip.watch_error", "", {
                    "error": repr(e)})
                return


_lock = threading.Lock()
_watcher: Optional[ChipWatcher] = None


def start_chip_watch(devices) -> Optional[ChipWatcher]:
    """Start this process's watcher, once however often it is called, and
    only where ``devices`` (the worker's) are TPUs and the recorder is on.
    -> the watcher, or None where none runs."""
    global _watcher
    with _lock:
        if _watcher is not None:
            return _watcher
        if not _recorder.get_recorder().enabled or not devices \
                or any(d.platform != "tpu" for d in devices):
            return None
        try:
            source = TpuMonitoring().read
        except Exception as e:  # noqa: BLE001 - no such library: host only
            _recorder.record("rtpu.chip.source_lost", "tpumonitoring",
                             {"error": repr(e)})
            source = None
        _watcher = ChipWatcher(source).start()
        return _watcher
