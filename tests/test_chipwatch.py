"""ISSUE 54: the chip watcher (``ray_tpu/perf/chipwatch.py``), what
``rtpu.train.report`` carries between two reports, and the record a
stalled ``fit()`` leaves. No chip and no sleeping: a fake counter source,
fake host counters and a fake clock drive the watcher's ``sample_once``
directly; ``get_metric`` itself is never called here (without a chip it
does not return, and holds the GIL while it does not)."""
import json
import os
import threading

import pytest

from ray_tpu.perf import chipwatch, postmortem
from ray_tpu.perf.recorder import FlightRecorder
from ray_tpu.train import session, trainer


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _kinds(rec, kind):
    return [ev for ev in rec.snapshot() if ev["kind"] == kind]


_made = []


def _watcher(rec, clock, source=None, host=None):
    _made.append(chipwatch.ChipWatcher(source, host, clock, rec))
    return _made[-1]


def _sample(w):
    """One sample, and the sweep it asked for finished before the next:
    the sampler itself never waits for the source."""
    sample = w.sample_once()
    if w._source is not None:
        assert w._source.idle.wait(10)
    return sample


@pytest.fixture(autouse=True)
def _no_thread_outlives_its_test():
    yield
    while _made:
        _made.pop().stop()
    for t in threading.enumerate():
        if t.name == "rtpu-chip-source":
            t.join(10)


# -- a sample ---------------------------------------------------------------

def test_a_sample_holds_the_chips_half_and_cumulative_host_counters():
    rec, clock = FlightRecorder(capacity=64, enabled=True), Clock()
    fast = {"queue": 3.0, "exec_us": [4.6e5, 4.5e5, 5.1e5, 5.2e5, 5.2e5]}
    slow = {"duty_pct": 100.0, "hbm_bytes": 12.07e9}
    asked = []

    def source(with_slow):
        asked.append(with_slow)
        return dict(fast, **slow) if with_slow else dict(fast)

    session.enter_loop()        # this thread is "the loop's"
    try:
        w = _watcher(rec, clock, source=source)
        first = _sample(w)      # asks; nothing read yet
        sum(i * i for i in range(200_000))      # the loop thread burns CPU
        clock.t += 0.25
        second = _sample(w)
        for _ in range(chipwatch.SLOW_EVERY - 1):
            clock.t += 0.25
            last = _sample(w)
    finally:
        session.shutdown_session()
    spans = _kinds(rec, "rtpu.chip.sample")
    assert [ev["ts"] for ev in spans][:2] == [1000.0, 1000.25]
    assert all(ev["dur"] > 0 for ev in spans)
    assert [ev[3] for ev in rec._ring][:2] == [first, second]
    # the fast counters every FAST_EVERY samples, the slow ones with them
    # every SLOW_EVERY
    assert (chipwatch.FAST_EVERY, chipwatch.SLOW_EVERY) == (2, 8)
    assert asked == [True, False, False, False, True]
    assert "chip" not in first                  # it does not wait
    chip = second["chip"]
    assert {k: chip[k] for k in (*fast, *slow)} == dict(fast, **slow)
    # the reading of the sample before: a quarter second old, and so said
    assert chip["age_s"] == 0.25 and chip["slow_age_s"] == 0.25
    assert chip["took_s"] >= 0 and chip["sweep"] == 1
    assert last["chip"]["age_s"] == 0.5 and last["chip"]["sweep"] == 4
    assert last["chip"]["slow_age_s"] == 0.25 * chipwatch.SLOW_EVERY
    for key in ("cpu_s", "watch_cpu_s", "loop_cpu_s", "nvcsw", "nivcsw",
                "majflt"):
        assert second[key] >= first[key], key   # cumulative
    assert second["loop_cpu_s"] > first["loop_cpu_s"]
    assert second["cpu_s"] > first["cpu_s"]
    assert second["load1"] >= 0 and second["iteration"] == 0
    # the tasks of /proc/self/task every THREADS_EVERY samples: the first
    # sweep has nothing to compare with, the next names who burned CPU
    assert "threads" not in second
    if os.path.isdir("/proc/self/task"):
        assert first["threads"] == []
        assert 0 < len(last["threads"]) <= chipwatch.TOP_THREADS
        assert all(isinstance(n, str) and d > 0 for n, d in last["threads"])
    assert json.dumps(spans)                    # wire-safe, as a ring is


def test_outside_a_loop_function_no_thread_clock_is_read():
    rec = FlightRecorder(capacity=8, enabled=True)
    sample = _watcher(rec, Clock()).sample_once()
    assert sample["loop_cpu_s"] is None and "chip" not in sample


def test_numbers_of_what_the_runtime_hands_out():
    """As a TPU v5e's chip worker got them (PR 54's probe)."""
    assert chipwatch._numbers(["98.84"]) == [98.84]
    assert chipwatch._numbers(["12070151168"]) == [12070151168.0]
    assert chipwatch._numbers(["tensor_core-0: 3"]) == [3.0]
    assert chipwatch._numbers(
        ["tensor_core-0, 436534.32, 459567.87, 509905.93, 516431.24, "
         "522024.36", "tensor_core-1, 1.0, 1.0, 1.0, 1.0, 1.0"]) == [
        436534.32, 459567.87, 509905.93, 516431.24, 522024.36]
    assert chipwatch._numbers([]) == []         # a counter that is not live


# -- a source that hangs ------------------------------------------------------

def test_a_source_that_hangs_is_given_up_and_the_hosts_half_goes_on():
    rec, clock = FlightRecorder(capacity=64, enabled=True), Clock()
    release, entered, called_on = threading.Event(), threading.Event(), []

    def hangs(slow):
        called_on.append(threading.current_thread().name)
        entered.set()
        release.wait(30)
        return {"queue": 1.0}

    w = _watcher(rec, clock, source=hangs)
    ticks = int(chipwatch.SOURCE_TIMEOUT_S * chipwatch.SAMPLE_HZ)
    try:
        for _ in range(ticks):      # asked once, stands, not yet lost
            assert "chip" not in w.sample_once()
            assert entered.wait(10)
            clock.t += 0.25
        assert not _kinds(rec, "rtpu.chip.source_lost")
        for _ in range(3):
            sample = w.sample_once()
            assert "chip" not in sample and "cpu_s" in sample
            clock.t += 0.25
    finally:
        release.set()
    lost = _kinds(rec, "rtpu.chip.source_lost")
    assert len(lost) == 1 and lost[0]["data"] == {
        "silent_s": chipwatch.SOURCE_TIMEOUT_S}
    assert len(_kinds(rec, "rtpu.chip.sample")) == ticks + 3
    # never on the caller's thread, and asked exactly once
    assert called_on == ["rtpu-chip-source"]


def test_a_process_that_was_itself_stopped_has_not_waited():
    """Seconds pass on the clock between two ticks (the standstill this
    watcher is for may stop the watcher too): the source was not silent
    through the asker's ticks, and is kept."""
    rec, clock = FlightRecorder(capacity=64, enabled=True), Clock()
    release = threading.Event()

    def slow_once(slow):
        release.wait(30)
        return {"queue": 1.0}

    w = _watcher(rec, clock, source=slow_once)
    w.sample_once()
    clock.t += 4.0
    w.sample_once()
    release.set()
    assert w._source is not None and w._source.idle.wait(10)
    clock.t += 0.25
    assert _sample(w)["chip"]["queue"] == 1.0
    assert not _kinds(rec, "rtpu.chip.source_lost")


def test_a_source_that_raises_is_given_up_with_its_error():
    rec, clock = FlightRecorder(capacity=64, enabled=True), Clock()

    def raises(slow):
        raise RuntimeError("no such metric")

    w = _watcher(rec, clock, source=raises)
    for _ in range(3):
        source = w._source
        assert "chip" not in w.sample_once()
        assert source is None or source.idle.wait(10)
    lost = _kinds(rec, "rtpu.chip.source_lost")
    assert len(lost) == 1 and "no such metric" in lost[0]["data"]["error"]


# -- a stall as a span --------------------------------------------------------

def _steps(n, queue=3):
    """Steady steps: a program completes between any two sweeps, the loop
    thread dispatches (10 ms of CPU a sample), the runtime's threads turn
    (40 % of a core)."""
    return [({"queue": queue, "exec_us": [4.6e5 + i]}, 0.1, 0.01)
            for i in range(n)]


STEADY = _steps(40)
# a compile: the device idle and nothing queued, the loop thread busy
COMPILE = _steps(4) + [({"queue": 0, "exec_us": [4.6e5]}, 0.0, 0.25)] * 20 \
    + _steps(4)
# a compile as the chip showed it (PR 54, call E): the thread that asked
# waits, the compiler's threads burn six cores, the runtime still counts two
# programs enqueued and none completes
COMPILE_ON_A_POOL = _steps(8) + [({"queue": 2, "exec_us": [4.6e5 + 7.5]},
                                  1.5, 0.0)] * 40 + _steps(4)
# A14's standstill: steps queued, none completing, the host flat
STALL = _steps(8) + [({"queue": 3, "exec_us": [4.6e5 + 7.5]}, 0.0, 0.0)] \
    * 14 + _steps(8)
# the loop waits for ONE long program (the benchmark's reference check):
# nothing completes and the host is flat, but one program is queued
LONG_PROGRAM = _steps(8) + [({"queue": 1, "exec_us": [4.6e5 + 7.5]}, 0.1,
                             0.0)] * 14 + _steps(8)


def _drive(series, with_chip, iteration=3, fast_every=1):
    """One sample a quarter second, and for these series one sweep a
    sample: (the chip's counters, the CPU seconds the process's other
    threads spent since the last sample, the loop thread's)."""
    every, chipwatch.FAST_EVERY = chipwatch.FAST_EVERY, fast_every
    try:
        rec, clock = FlightRecorder(capacity=256, enabled=True), Clock()
        state = {"cpu": 5.0, "loop": 1.0, "chip": None}

        def host():
            return {"cpu_s": state["cpu"], "loop_cpu_s": state["loop"],
                    "nvcsw": 0, "nivcsw": 0, "majflt": 0, "load1": 0.1,
                    "iteration": state["iteration"]}

        w = _watcher(rec, clock, host=host, source=(
            lambda slow: dict(state["chip"])) if with_chip else None)
        for i, (chip, others, loop) in enumerate(series):
            state["chip"] = chip
            state["iteration"] = iteration(i) if callable(iteration) \
                else iteration
            state["cpu"] += others + loop
            state["loop"] += loop
            _sample(w)
            clock.t += 0.25
    finally:
        chipwatch.FAST_EVERY = every
    return rec


@pytest.mark.parametrize("with_chip", [True, False],
                         ids=["chip_counters", "host_only"])
def test_a_stall_opens_and_closes_exactly_one_pinned_span(with_chip):
    rec = _drive(STALL, with_chip)
    stalls = _kinds(rec, "rtpu.chip.stall")
    assert len(stalls) == 1
    ev, data = stalls[0], stalls[0]["data"]
    assert any(p is q for p in rec._pinned for q in rec._ring
               if q[1] == "rtpu.chip.stall")
    assert data["condition"] == ("chip" if with_chip else "host")
    assert ev["label"] == data["condition"]
    # the ninth sample, at +2.0 s, is the first of the standstill: the
    # host's test sees it there, the chip's one sweep later (a sample
    # carries the sweep before it, and two sweeps must agree); `since` is
    # the sample before the first still one
    first_still = 1002.0 + (0.5 if with_chip else 0.0)
    assert data["since"] == first_still - 0.25
    assert data["iteration"] == 3
    assert data["closed"]["loop_cpu_s"] - data["opened"]["loop_cpu_s"] \
        == pytest.approx(0.01)
    # open once still for STILL_MIN_S, closed by the first step after
    lasted = 14 * 0.25 - (first_still - 0.25 - 1002.0) - 0.25 \
        - chipwatch.STILL_MIN_S[data["condition"]]
    assert data["samples"] == round(lasted / 0.25) + 1
    if with_chip:
        assert data["opened"]["chip"]["queue"] == 3
        assert data["opened"]["chip"]["exec_us"] == [4.6e5 + 7.5]
    assert ev["dur"] >= 0       # begin() and end() stamp the real clock
    me = threading.get_ident()
    assert any(t["thread_id"] == me and t["frames"]
               for t in data["stacks"]["threads"])


@pytest.mark.parametrize("with_chip", [True, False],
                         ids=["chip_counters", "host_only"])
@pytest.mark.parametrize("series", [STEADY, COMPILE, COMPILE_ON_A_POOL],
                         ids=["steady_steps", "compile", "compile_on_a_pool"])
def test_no_stall_on_steady_steps_nor_on_a_compile(series, with_chip):
    rec = _drive(series, with_chip)
    assert not _kinds(rec, "rtpu.chip.stall")
    assert len(_kinds(rec, "rtpu.chip.sample")) == len(series)


def test_at_the_watchers_own_cadence_a_stall_is_one_span_too():
    """A sweep every other sample, as the thread asks: the same standstill
    opens a sweep later, and steady steps and a compile open nothing."""
    every = chipwatch.FAST_EVERY
    stall, = _kinds(_drive(STALL, True, fast_every=every),
                    "rtpu.chip.stall")
    assert stall["data"]["condition"] == "chip"
    assert 1002.0 < stall["data"]["since"] <= 1003.0
    for series in (STEADY, COMPILE, COMPILE_ON_A_POOL, LONG_PROGRAM):
        assert not _kinds(_drive(series, True, fast_every=every),
                          "rtpu.chip.stall")


def test_one_long_program_is_no_stall_where_the_chip_says_so():
    """Nothing completes and the loop thread waits, but ONE program is
    queued, not several: the chip's counters tell it from a standstill,
    the host's alone do not (the stated limit of the host's test)."""
    assert not _kinds(_drive(LONG_PROGRAM, True), "rtpu.chip.stall")
    assert len(_kinds(_drive(LONG_PROGRAM, False), "rtpu.chip.stall")) == 1


def test_the_detector_learns_how_long_quiet_lasts():
    """Steps of two and a half seconds: nothing completes and the loop
    thread sleeps through nine samples in ten. Still stretches that end of
    themselves teach the detector what quiet looks like, and a stall is
    several of them."""
    def step(i):
        chip = {"queue": 3, "exec_us": [2.4e6 + i]}
        return [(chip, 0.0, 0.0)] * 9 + [(chip, 0.0, 0.01)]

    quiet = [s for i in range(8) for s in step(i)]
    stalled = quiet[:30] + [quiet[29][:2] + (0.0,)] * 60 + step(9)

    def reports(i):         # the loop's second report after three steps
        return 1 if i < 30 else 2

    for with_chip in (True, False):
        assert not _kinds(_drive(quiet, with_chip, reports),
                          "rtpu.chip.stall")
        assert len(_kinds(_drive(stalled, with_chip, reports),
                          "rtpu.chip.stall")) == 1
        # and with no time to learn it takes a step for a stall
        assert _kinds(_drive(quiet, with_chip, 2), "rtpu.chip.stall")


@pytest.mark.parametrize("iteration", [0, 1])
def test_up_to_the_second_report_no_stall_opens(iteration):
    for with_chip in (True, False):
        rec = _drive(STALL, with_chip, iteration=iteration)
        assert not _kinds(rec, "rtpu.chip.stall")


# -- what the chip showed ------------------------------------------------------

def _replay(name):
    """The samples a TPU v5e's chip worker recorded (PR 54, call E, seconds
    from the run's first report; ``gpt2m_train_s1024``), through a fresh
    detector -> [(opened at, closed at, condition)]."""
    import gzip

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data_pr54_chip_samples.json.gz")
    with gzip.open(path, "rt") as f:
        samples = json.load(f)[name]
    detector, found, opened = chipwatch.StallDetector(), [], None
    for ts, sample in samples:
        verdict = detector.update(ts, sample)
        if verdict == "open":
            opened = (ts, detector.condition)
        elif verdict == "close":
            found.append((opened[0], ts, opened[1]))
    return found


def test_the_standstill_the_chip_showed_is_one_stall():
    """Seed 7504 made 265 steps of 289: between its ninth and tenth reports
    (27.9 s and 35.7 s after the first) nothing completed for 4.3 s with
    three programs enqueued, the loop thread's clock flat, the rest of the
    process at 0.15 of a core."""
    (opened, closed, condition), = _replay("series1-7504")
    assert condition == "chip"
    assert 31.7 < opened < 33.5 and 35.5 < closed < 36.0


def test_the_compile_the_chip_showed_is_no_stall():
    """Seed 7501's reference check compiled its two programs after the
    window (48.2 s to 62.5 s and 62.9 s to 65.9 s): the loop thread waited
    14 s with its clock flat while the compiler's threads burned one to
    seven cores, the runtime counting two programs enqueued throughout."""
    assert _replay("series1-7501") == []


# -- where a watcher runs ---------------------------------------------------

class _Device:
    def __init__(self, platform):
        self.platform = platform


@pytest.fixture
def no_watcher(monkeypatch):
    monkeypatch.setattr(chipwatch, "_watcher", None)
    made = []

    class Source:
        def __init__(self):
            made.append(self)

        def read(self, slow=False):
            return {"queue": 1.0, "exec_us": [1.0]}

    monkeypatch.setattr(chipwatch, "TpuMonitoring", Source)
    yield made
    if chipwatch._watcher is not None:
        chipwatch._watcher.stop()


def _watch_threads():
    return [t for t in threading.enumerate()
            if t.name in ("rtpu-chip-watch", "rtpu-chip-source")]


def test_no_watcher_on_a_cpu_backend(no_watcher):
    import jax

    assert chipwatch.start_chip_watch(jax.devices()) is None
    assert chipwatch.start_chip_watch([]) is None
    assert not no_watcher and not _watch_threads()


def test_no_watcher_with_the_recorder_off(no_watcher, monkeypatch):
    monkeypatch.setattr(chipwatch._recorder.get_recorder(), "enabled",
                        False)
    assert chipwatch.start_chip_watch([_Device("tpu")]) is None
    assert not no_watcher and not _watch_threads()


def test_one_watcher_a_process_where_the_devices_are_tpus(no_watcher):
    w = chipwatch.start_chip_watch([_Device("tpu")])
    assert w is not None and chipwatch.start_chip_watch(
        [_Device("tpu")]) is w
    assert len(no_watcher) == 1
    assert sorted(t.name for t in _watch_threads()) == [
        "rtpu-chip-source", "rtpu-chip-watch"]
    assert all(t.daemon for t in _watch_threads())
    w.stop()
    w._thread.join(5)
    assert not w._thread.is_alive()


# -- rtpu.train.report -------------------------------------------------------

class _Queue:
    def __init__(self):
        self.got = []

    def put(self, payload):
        self.got.append(payload)


def test_a_report_carries_what_lay_between_two_reports(monkeypatch):
    rec = FlightRecorder(capacity=16, enabled=True)
    monkeypatch.setattr(session, "get_recorder", lambda: rec)
    session.init_session(session.TrainContext(0, 1), _Queue())
    try:
        session.enter_loop()
        assert session.loop_state() == (threading.get_ident(), 0)
        session.report({"i": 0})
        sum(i * i for i in range(200_000))
        import gc

        gc.collect()
        session.report({"i": 1})
        assert session.loop_state() == (threading.get_ident(), 2)
    finally:
        session.shutdown_session()
    assert session.loop_state() == (None, 0)
    first, second = (ev["data"] for ev in _kinds(rec, "rtpu.train.report"))
    assert first["iteration"] == 1 and second["iteration"] == 2
    assert set(second) == {"iteration", *session._USAGE_KEYS}
    assert second["since_s"] > 0 and second["gc"] >= 1
    assert 0 < second["loop_cpu_s"] <= second["cpu_s"] + 1e-3
    assert second["loop_cpu_s"] <= second["since_s"] + 1e-3
    assert second["nvcsw"] >= 0 and second["nivcsw"] >= 0
    assert second["majflt"] >= 0


def test_a_report_with_the_recorder_off_reads_no_counter(monkeypatch):
    rec = FlightRecorder(capacity=16, enabled=False)
    monkeypatch.setattr(session, "get_recorder", lambda: rec)
    monkeypatch.setattr(session, "_usage", lambda: 1 / 0)
    queue = _Queue()
    session.init_session(session.TrainContext(0, 1), queue)
    try:
        session.enter_loop()
        session.report({"i": 0})
    finally:
        session.shutdown_session()
    assert len(queue.got) == 1 and not rec.snapshot()


# -- the record of a stalled run -------------------------------------------

class _Executor:
    def __init__(self, ring):
        self._ring = ring

    def ring_fetchers(self):
        return {"train_worker:0": lambda: self._ring}


def _span(kind, ts, dur=0.1, data=None):
    return {"ts": ts, "kind": kind, "label": "", "data": data, "dur": dur,
            "parent": ""}


@pytest.mark.parametrize("stalled", [False, True],
                         ids=["quiet", "stalled"])
def test_a_stalled_runs_record_is_also_left_where_no_run_replaces_it(
        tmp_path, monkeypatch, stalled):
    kept = tmp_path / "postmortem"
    monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(kept))
    monkeypatch.setattr(postmortem, "_last_path", None)
    ring = [_span("rtpu.train.report", 10.0), _span("rtpu.chip.sample", 11.0)]
    if stalled:
        ring.append(_span("rtpu.chip.stall", 12.0, 3.5,
                          {"condition": "chip"}))
    run = tmp_path / "run"
    run.mkdir()
    for _ in range(2):      # the next run of the same job replaces its own
        path = trainer._flight_record(_Executor(ring), str(run), None, 0, 3)
    assert path == str(run / "flight.json")
    record = postmortem.load_bundle(path)
    assert record["reason"] == "fit: ok"
    assert record["rings"]["train_worker:0"] == ring
    bundles = sorted(kept.iterdir()) if kept.exists() else []
    if not stalled:
        assert bundles == []
        return
    assert len(bundles) == 2        # one a run, none overwritten
    bundle = postmortem.load_bundle(str(bundles[0]))
    assert bundle["reason"] == "fit: stalled"
    assert bundle["rings"]["train_worker:0"] == ring
    assert bundle["meta"]["iterations"] == 3
    # and `ray_tpu postmortem` without a path finds it from a process
    # that wrote none
    monkeypatch.setattr(postmortem, "_last_path", None)
    assert postmortem.last_bundle_path() == str(bundles[-1])


def test_a_runs_record_holds_the_drivers_spans_and_its_last_events(
        tmp_path, monkeypatch):
    rec = FlightRecorder(capacity=4096, enabled=True)
    monkeypatch.setattr(trainer, "get_recorder", lambda: rec)
    with rec.span("rtpu.train.setup_mesh", pin=True):
        pass
    for i in range(3000):           # a minute of next_results' polling
        rec.record("dispatch.direct", f"task {i}")
    with rec.span("rtpu.train.late"):
        pass
    path = trainer._flight_record(_Executor([]), str(tmp_path), None, 0, 0)
    driver = postmortem.load_bundle(path)["rings"]["driver"]
    instants = [ev for ev in driver if "dur" not in ev]
    assert [ev["label"] for ev in instants] == [
        f"task {i}" for i in range(3000 - 256, 3000)]
    assert [ev["kind"] for ev in driver if "dur" in ev] == [
        "rtpu.train.setup_mesh", "rtpu.train.late"]
    assert driver[0]["kind"] == "rtpu.train.setup_mesh"     # ring order
    assert len(rec.snapshot()) > 3000       # the ring itself stays whole


def test_an_abort_paths_bundle_keeps_the_ring_whole(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(tmp_path))
    events = [{"ts": float(i), "kind": "dispatch.direct", "label": str(i),
               "data": None} for i in range(600)]
    assert len(postmortem.spans_and_tail(events)) == 256
    assert postmortem.spans_and_tail(events, tail=0) == []
    rec = postmortem.get_recorder()
    n = len(rec.snapshot())
    path = postmortem.dump_bundle("unit: abort", origin="chipwatch-test",
                                  throttle=False)
    assert len(postmortem.load_bundle(path)["rings"]["chipwatch-test"]) >= n
