"""The readers of what PR 54's tracing leaves in a run's flight record:
``train_stall_s`` (the gaps between the chip worker's ``rtpu.train.report``
spans), ``chip_sample_ms`` and ``chip_duty_cycle_min_pct`` (the chip
watcher's ``rtpu.chip.sample`` spans), on records made by hand: a quiet
run, a run with one long gap, a run whose long gap overlaps the profiler's
stretch, and runs with too few reports."""
import gzip
import json
import os

import pytest

from benchmark.lib import spec

READERS = spec.load_metric_readers("layer_metrics")
NEW = ("train_stall_s", "chip_sample_ms", "chip_duty_cycle_min_pct")
T_WINDOW, ELAPSED = 1040.0, 50.0
TWENTY_STEPS = 3.5                 # gpt2m's: 20 steps of 174 ms


def _span(kind, start, dur, data=None):
    return {"ts": start, "kind": kind, "label": "", "data": data,
            "dur": dur, "parent": ""}


def _reports(starts):
    return [_span("rtpu.train.report", T_WINDOW + s, 0.002,
                  {"iteration": i + 1}) for i, s in enumerate(starts)]


def _evenly(n, first=TWENTY_STEPS, jitter=0.0):
    """n reports twenty steps apart, every other gap longer by jitter."""
    out, at = [], first
    for i in range(n):
        out.append(at)
        at += TWENTY_STEPS + (jitter if i % 2 else 0.0)
    return out


def _samples(duty, every=0.25, dur=0.0008, start=0.1):
    return [_span("rtpu.chip.sample", T_WINDOW + start + i * every,
                  dur * (1 + i % 3),
                  {"cpu_s": 1.0 + i, "chip": {"duty_pct": d, "queue": 2.0,
                                              "age_s": 0.001}}
                  if d is not None else {"cpu_s": 1.0 + i})
            for i, d in enumerate(duty)]


def _view(tmp_path, monkeypatch, worker, trace_span=None, cell="fx"):
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    d = tmp_path / cell / "train"
    d.mkdir(parents=True)
    (d / "flight.json").write_text(json.dumps(
        {"reason": "fit: ok", "origin": "driver", "time": 0.0, "meta": {},
         "rings": {"driver": [], "train_worker:0": worker}}))
    return {"cell": {"name": cell}, "trace": None, "spans": {},
            "train": {"t_window": T_WINDOW, "elapsed_s": ELAPSED,
                      "trace_span": trace_span}}


def test_a_quiet_run_reads_no_stall(tmp_path, monkeypatch):
    """Gaps that differ by 1 % are steps of a quiet run, not a stall."""
    worker = _reports([-0.5] + _evenly(13, jitter=0.035) + [51.0])
    view = _view(tmp_path, monkeypatch, worker)
    assert READERS["train_stall_s"].read(view) == 0.0


@pytest.mark.parametrize("lost", [0.6, 3.96, 6.3])
def test_one_long_gap_reads_the_seconds_it_lost(tmp_path, monkeypatch, lost):
    """A run that made fewer steps than its twin: the loss to well within
    one step's time (0.174 s), whatever a quiet gap's jitter."""
    starts = _evenly(12, jitter=0.02)
    starts = starts[:5] + [s + lost for s in starts[5:]]
    view = _view(tmp_path, monkeypatch, _reports(starts))
    assert READERS["train_stall_s"].read(view) == pytest.approx(lost,
                                                                abs=0.03)


def test_the_run_the_chip_showed_reads_what_it_lost(tmp_path, monkeypatch):
    """``data_pr54_stalled_flight.json.gz``: an untraced run of
    ``gpt2m_train_s1024`` on a TPU v5e (PR 54, call E, seed 7504) that made
    265 steps where its twins made 289: the loss to within one step's
    time, though the pair after the standstill lies closer than a quiet
    one; and the watcher's samples and its one stall are in the record."""
    with gzip.open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "data_pr54_stalled_flight.json.gz"),
                   "rt") as f:
        recorded = json.load(f)
    worker = recorded["rings"]["train_worker:0"]
    view = _view(tmp_path, monkeypatch, worker, cell=recorded["cell"])
    view["train"] = recorded["train"]
    lost = (recorded["twin_steps"] - recorded["train"]["steps"]) \
        * recorded["step_s"]
    assert lost == pytest.approx(24 * 0.17444, rel=1e-3)
    assert READERS["train_stall_s"].read(view) == pytest.approx(
        lost, abs=recorded["step_s"])
    assert READERS["train_stall_s"].read(view) == pytest.approx(4.30,
                                                                abs=0.01)
    assert 0.1 < READERS["chip_sample_ms"].read(view) < 0.2
    # the standstill's five seconds read 23.23 % busy, the window's other
    # periods 100 (the first twelve seconds are not counted)
    assert READERS["chip_duty_cycle_min_pct"].read(view) == 23.23
    stall, = [ev for ev in worker if ev["kind"] == "rtpu.chip.stall"]
    assert stall["data"]["condition"] == "chip"
    assert stall["data"]["iteration"] == 9


def test_two_stalls_add_up(tmp_path, monkeypatch):
    starts = _evenly(12)
    starts = starts[:3] + [s + 3.0 for s in starts[3:8]] \
        + [s + 3.0 + 1.5 for s in starts[8:]]
    view = _view(tmp_path, monkeypatch, _reports(starts))
    assert READERS["train_stall_s"].read(view) == pytest.approx(4.5)


@pytest.mark.parametrize("trace_span,stall", [
    ([T_WINDOW + 20.0, T_WINDOW + 23.4], 0.0),     # the profiler's own pause
    ([T_WINDOW + 30.0, T_WINDOW + 33.4], 1.2),     # elsewhere: it counts
    ([T_WINDOW + 20.0, None], 1.2),                # never stopped: no stretch
    (None, 1.2)], ids=["over_the_gap", "beside_it", "half_a_span", "untraced"])
def test_a_gap_over_the_profilers_stretch_is_left_out(tmp_path, monkeypatch,
                                                      trace_span, stall):
    starts = _evenly(12)
    starts = starts[:6] + [s + 1.2 for s in starts[6:]]    # (21.0, 25.7)
    view = _view(tmp_path, monkeypatch, _reports(starts), trace_span)
    assert READERS["train_stall_s"].read(view) == pytest.approx(stall)


@pytest.mark.parametrize("starts,trace_span", [
    ([], None), ([16.0], None), ([16.0, 32.0], None),
    # three reports, both pairs under the profiler's stretch: nothing left
    ([16.0, 21.0, 26.0], [T_WINDOW + 20.0, T_WINDOW + 23.0])],
    ids=["none", "one", "two", "all_traced"])
def test_fewer_than_three_reports_is_none(tmp_path, monkeypatch, starts,
                                          trace_span):
    worker = _reports([-0.5] + starts + [51.0])     # warm-up's, the final
    view = _view(tmp_path, monkeypatch, worker, trace_span)
    assert READERS["train_stall_s"].read(view) is None


def test_three_reports_of_long_steps_read(tmp_path, monkeypatch):
    """kimilinear's sixteen seconds between reports: three in the window,
    one pair under the profiler's stretch, the other alone: 0."""
    view = _view(tmp_path, monkeypatch, _reports([16.0, 32.0, 48.1]),
                 [T_WINDOW + 20.0, T_WINDOW + 23.0])
    assert READERS["train_stall_s"].read(view) == 0.0


def test_the_samples_inside_the_window_are_read(tmp_path, monkeypatch):
    inside = _samples([100.0, 99.5, 100.0, 37.0, 100.0, 100.0], every=2.0,
                      start=10.0)
    outside = [_span("rtpu.chip.sample", T_WINDOW - 5.0, 0.5,
                     {"chip": {"duty_pct": 0.0}}),          # a compile
               _span("rtpu.chip.sample", T_WINDOW + ELAPSED + 1.0, 0.5,
                     {"chip": {"duty_pct": 0.0}})]          # the reference
    view = _view(tmp_path, monkeypatch, outside + inside)
    # durs 0.8, 1.6, 2.4, 0.8, 1.6, 2.4 ms
    assert READERS["chip_sample_ms"].read(view) == pytest.approx(1.6)
    assert READERS["chip_duty_cycle_min_pct"].read(view) == 37.0


def test_the_duty_cycle_speaks_of_the_seconds_before_its_sample(
        tmp_path, monkeypatch):
    """A reading early in the window is of the warm-up's seconds, and one
    just after the profiler's stretch is of the harness's own pause."""
    duty = [41.88, 70.0, 100.0, 99.0, 100.0, 90.71, 55.0, 98.84, 100.0]
    worker = _samples(duty, every=4.0, start=2.0)   # at +2, +6, ... +34 s
    view = _view(tmp_path, monkeypatch, worker,
                 [T_WINDOW + 20.0, T_WINDOW + 23.0])
    # up to +10 they are the warm-up's; +22 to +34 the profiler's
    assert READERS["chip_duty_cycle_min_pct"].read(view) == 99.0
    untraced = _view(tmp_path, monkeypatch, worker, cell="fy")
    assert READERS["chip_duty_cycle_min_pct"].read(untraced) == 55.0


def test_a_watcher_without_a_live_counter_reads_its_cost_only(
        tmp_path, monkeypatch):
    view = _view(tmp_path, monkeypatch, _samples([None] * 4))
    assert READERS["chip_sample_ms"].read(view) == pytest.approx(1.2)
    assert READERS["chip_duty_cycle_min_pct"].read(view) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_watcher_or_a_record_is_none(
        tmp_path, monkeypatch, name):
    """The parent's tree: reports twice in the window at most here, and no
    ``rtpu.chip.*``; a run that left no record; a view that is no run."""
    view = _view(tmp_path, monkeypatch, _reports([10.0, 20.0]))
    assert READERS[name].read(view) is None
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path / "nowhere"))
    assert READERS[name].read(view) is None
    for bare in ({"spans": {}, "cell": {}, "trace": None},
                 {"spans": {}, "cell": {"name": "fx"}, "trace": None,
                  "train": {"elapsed_s": 2.0, "steps": 10}}):
        assert READERS[name].read(bare) is None


@pytest.mark.parametrize("name,layer,unit,source", [
    ("train_stall_s", "trainer", "s", "program_span"),
    ("chip_sample_ms", "cluster runtime", "ms", "program_span"),
    ("chip_duty_cycle_min_pct", "cluster runtime", "%", "program_counter")])
def test_what_a_reader_says_of_itself(name, layer, unit, source):
    mod = READERS[name]
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        layer, unit, source, "train_tokens_per_s")
