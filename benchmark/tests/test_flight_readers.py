"""The five readers of a run's flight record (PR 38): ``step_trace_lower_s``,
``step_compile_s``, ``other_programs_s``, ``setup_unattributed_s``,
``train_report_ms``, through ``layer_metrics/_flight.py``. On
``data_pr38_flight.json.gz``: the record of a traced warm run of
``granite4h_train_s4096`` on a TPU v5e (PR 38, call 1), the chip worker's
ring whole and the driver's cut to its spans and its last few events, with
the three fields of the view the readers use and the five values the run's
own result line printed; and on records made by hand, for the arithmetic of
spans that overlap, straddle the window or are missing."""
import gzip
import json
import os

import pytest

from benchmark.layer_metrics import _flight
from benchmark.lib import spec

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = spec.load_metric_readers("layer_metrics")
NEW = ("step_trace_lower_s", "step_compile_s", "other_programs_s",
       "setup_unattributed_s", "train_report_ms")
T0 = 1000.0          # the hand-made run: process start; the window opens
T_WINDOW = T0 + 40   # 40 s later and lasts 50 s


def _span(kind, start, dur, label="", data=None):
    return {"ts": start, "kind": kind, "label": label, "data": data,
            "dur": dur, "parent": ""}


def _view(tmp_path, monkeypatch, rings, cell="fx", t_window=T_WINDOW,
          whole=T_WINDOW - T0, elapsed=50.0):
    """A run's view over a flight record written where a run of ``cell``
    leaves it; ``rings`` None: the run left none."""
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    if rings is not None:
        d = tmp_path / cell / "train"
        d.mkdir(parents=True)
        (d / "flight.json").write_text(json.dumps(
            {"reason": "fit: ok", "origin": "driver", "time": 0.0,
             "rings": rings, "meta": {}}))
    return {"cell": {"name": cell}, "trace": None,
            "train": {"t_window": t_window, "elapsed_s": elapsed},
            "spans": {"process_start_to_window": whole}}


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data_pr38_flight.json.gz"),
                   "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_a_recorded_run_reads_what_its_own_line_printed(
        recorded, tmp_path, monkeypatch, name):
    view = _view(tmp_path, monkeypatch, recorded["rings"],
                 cell=recorded["cell"],
                 t_window=recorded["train"]["t_window"],
                 whole=recorded["spans"]["process_start_to_window"],
                 elapsed=recorded["train"]["elapsed_s"])
    assert READERS[name].read(view) == pytest.approx(
        recorded["printed"][name], rel=1e-9)


def test_the_recorded_run_holds_what_the_issue_asks_of_every_run(recorded):
    worker = recorded["rings"][_flight.WORKER]
    kinds = {ev["kind"] for ev in worker}
    assert {"rtpu.train.jax_start", "rtpu.train.report", "rtpu.jax.trace",
            "rtpu.jax.lower", "rtpu.jax.compile", "rtpu.ops.flash.path",
            "rtpu.ops.ssd.path", "rtpu.models.stack.runs"} <= kinds
    step = [ev for ev in worker if ev["kind"] == "rtpu.jax.compile"
            and ev["label"] == "jit_bench_train_step"]
    assert len(step) == 1 and step[0]["data"]["cache"] == "hit"
    runs = [ev for ev in worker if ev["kind"] == "rtpu.models.stack.runs"]
    assert runs[0]["data"]["kept_bytes"] == 1342177280
    t0 = recorded["train"]["t_window"]
    reports = [ev for ev in worker if ev["kind"] == "rtpu.train.report"
               and t0 <= ev["ts"] < t0 + recorded["train"]["elapsed_s"]]
    assert len(reports) == recorded["train"]["steps"] // 20


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("rings", [None, {}, {"driver": [], "train_worker:0": [
    {"ts": T0 + 1, "kind": "dispatch.direct", "label": "", "data": None}]}],
    ids=["no_record", "no_rings", "no_spans"])
def test_nothing_to_read_is_none(tmp_path, monkeypatch, name, rings):
    assert READERS[name].read(_view(tmp_path, monkeypatch, rings)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_view_that_is_no_training_run_is_none(tmp_path, monkeypatch, name):
    """What ``test_files.py`` and ``test_trace.py`` hand every reader: a
    view without a final report, a cell's name or a window."""
    _view(tmp_path, monkeypatch, _hand_made())        # a record is there
    for view in ({"spans": {}, "cell": {}, "trace": None},
                 {"spans": {}, "cell": {"name": "fx"}, "trace": None,
                  "train": {"elapsed_s": 2.0, "steps": 10}}):
        assert READERS[name].read(view) is None


def _hand_made():
    step, other = "jit_bench_train_step", "jit_init"
    worker = [
        _span("rtpu.train.jax_start", T0 + 5, 8.0),
        # the other programs: 2 s + 3 s of which 1 s overlap = 4 s covered,
        # and 1 s more that lies under the step's trace: counted there
        _span("rtpu.jax.trace", T0 + 14, 2.0, "init"),
        _span("rtpu.jax.lower", T0 + 15, 3.0, other),
        _span("rtpu.jax.compile", T0 + 20.5, 1.0, "jit_eager"),
        _span("rtpu.jax.trace", T0 + 20, 4.0, "bench_train_step"),
        _span("rtpu.jax.lower", T0 + 24, 6.0, step),
        _span("rtpu.jax.compile", T0 + 30, 5.0, step, {"cache": "hit"}),
        # after the window opened: the reference's programs, no set-up
        _span("rtpu.jax.compile", T_WINDOW + 60, 9.0, "jit_fn"),
        _span("rtpu.jax.compile", T_WINDOW - 1, 2.0, "jit_straddles"),
        _span("rtpu.train.report", T_WINDOW - 0.5, 0.1),     # warm-up's
        _span("rtpu.train.report", T_WINDOW + 10, 0.002),
        _span("rtpu.train.report", T_WINDOW + 20, 0.004),
        _span("rtpu.train.report", T_WINDOW + 30, 0.003),
        _span("rtpu.train.report", T_WINDOW + 51, 0.5),      # the final one
    ]
    driver = [
        _span("rtpu.core.init", T0 + 3, 0.5),
        _span("rtpu.core.init.gcs", T0 + 3, 0.2),            # its child
        _span("rtpu.train.setup_mesh", T0 + 4, 9.5),         # over jax_start
        _span("rtpu.llm.step", T0 + 36, 2.0),                # no set-up kind
        _span("rtpu.train.flight", T_WINDOW + 80, 0.01),
        {"ts": T0 + 2, "kind": "dispatch.direct", "label": "", "data": None},
    ]
    return {"driver": driver, "train_worker:0": worker}


@pytest.mark.parametrize("name,value", [
    ("step_trace_lower_s", 4.0 + 6.0),
    ("step_compile_s", 5.0),
    # [14, 18] of init's trace and lower; jit_eager lies under the step's
    # trace; the straddling and the later compile ended after the window
    ("other_programs_s", 4.0),
    # 40 s less [3, 3.5] + [4, 13.5] + [14, 18] + [20, 35] + the second of
    # the straddling compile that lies before the window
    ("setup_unattributed_s", 40.0 - (0.5 + 9.5 + 4.0 + 15.0 + 1.0)),
    ("train_report_ms", 3.0),
])
def test_the_arithmetic_on_overlapping_spans(tmp_path, monkeypatch, name,
                                             value):
    view = _view(tmp_path, monkeypatch, _hand_made())
    assert READERS[name].read(view) == pytest.approx(value, abs=1e-9)


def test_a_reader_file_says_what_benchmark_json_says():
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        mod, m = READERS[name], declared[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"]), name
        assert m["source"] == "program_span" and m["better"] == "lower"
