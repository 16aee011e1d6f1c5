"""ISSUE 17: flight recorder, step profiler, post-mortem bundles.

Unit layers: ring overflow/drop accounting, StepReport analytic anchors
(1F1B bubble fraction, MFU), chrome-trace schema, suggest() hints,
bundle dangling-op detection + deterministic render (golden), dump
throttling. Integration: a chaos stage kill mid-step must leave a
renderable bundle whose surviving rings carry the killed op's
begin-without-end; the `ray_tpu postmortem` CLI renders it.
"""
import glob
import json
import math
import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu.perf import (StepReport, analytic_bubble_frac, compute_mfu,
                          set_enabled)
from ray_tpu.perf import postmortem, recorder
from ray_tpu.perf.postmortem import (dump_bundle, find_dangling,
                                     load_bundle, render_bundle)
from ray_tpu.perf.recorder import FlightRecorder


# ---------------------------------------------------------------------------
# flight recorder ring
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_overflow_drops_oldest_and_counts(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        before = recorder._C_DROPPED.total()
        for i in range(20):
            rec.record("test.ev", f"e{i}")
        events = rec.snapshot()
        assert [e["label"] for e in events] == [f"e{i}" for i in
                                                range(12, 20)], \
            "ring must retain the NEWEST capacity events"
        assert rec.dropped == 12
        assert recorder._C_DROPPED.total() - before == 12
        # a second drain without new drops must not double-count
        rec.snapshot()
        assert recorder._C_DROPPED.total() - before == 12

    def test_snapshot_clear_keeps_drop_ledger(self):
        rec = FlightRecorder(capacity=4, enabled=True)
        for i in range(6):
            rec.record("test.ev", f"e{i}")
        assert rec.dropped == 2
        assert len(rec.snapshot(clear=True)) == 4
        assert rec.dropped == 2, "clear() must not erase the drop total"
        rec.record("test.ev", "late")
        evs = rec.snapshot()
        assert [e["label"] for e in evs] == ["late"]
        assert rec.dropped == 2

    def test_disabled_recorder_ignores_records(self):
        rec = FlightRecorder(capacity=8, enabled=False)
        rec.record("test.ev", "x")
        assert rec.snapshot() == [] and rec.stats()["appended"] == 0
        rec.enabled = True
        rec.record("test.ev", "y")
        assert [e["label"] for e in rec.snapshot()] == ["y"]

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_FLIGHTREC", "0")
        assert FlightRecorder(capacity=8).enabled is False
        monkeypatch.setenv("RAY_TPU_FLIGHTREC", "1")
        assert FlightRecorder(capacity=8).enabled is True

    def test_set_enabled_flips_process_singleton(self):
        from ray_tpu.perf.recorder import get_recorder, recorder_enabled

        rec = get_recorder()
        was = rec.enabled
        try:
            set_enabled(False)
            assert recorder_enabled() is False and rec.enabled is False
            set_enabled(True)
            assert recorder_enabled() is True
        finally:
            rec.enabled = was

    def test_record_cost_stays_micro(self, machine_load):
        """The hot path is an attribute test + deque append. The bar is
        deliberately loose (loaded CI boxes) — it exists to catch an
        accidental lock/IO/alloc regression, not to bench — and takes its
        scale from the machine at that moment: with L runnable processes
        a core this loop gets a share of one."""
        rec = FlightRecorder(capacity=1024, enabled=True)
        n = 20000
        t0 = time.perf_counter()
        for i in range(n):
            rec.record("test.ev", "hot", None)
        per_on = (time.perf_counter() - t0) / n
        rec.enabled = False
        t0 = time.perf_counter()
        for i in range(n):
            rec.record("test.ev", "hot", None)
        per_off = (time.perf_counter() - t0) / n
        ncpu = os.cpu_count() or 1
        bar_on = (50e-6 if ncpu >= 4 else 200e-6) * (1.0 + machine_load)
        assert per_on < bar_on, f"record() cost {per_on * 1e6:.2f}us"
        assert per_off < per_on, \
            (f"disabled path ({per_off * 1e6:.2f}us) should be cheaper "
             f"than enabled ({per_on * 1e6:.2f}us)")


# ---------------------------------------------------------------------------
# StepReport: analytic anchors, serialization, chrome trace, hints
# ---------------------------------------------------------------------------


def _synthetic_pipeline_report(P=4, M=12, t_ms=5.0) -> StepReport:
    """Ideal equal-cost 1F1B: each stage is busy M*t and recv-blocked
    (P-1)*t per step, so measured bubble_frac == (P-1)/(M+P-1)."""
    stages = [{"stage": f"0.{i}", "exec_ms": M * t_ms,
               "bubble_ms": (P - 1) * t_ms, "recv_ms": (P - 1) * t_ms,
               "send_ms": 0.0, "sync_ms": 0.0, "update_ms": 0.0,
               "ops": [{"key": f"f{i}.0", "method": "forward",
                        "t0": 100.0 + i, "t1": 100.0 + i + t_ms / 1e3}]}
              for i in range(P)]
    step_wall = (M + P - 1) * t_ms
    return StepReport(
        kind="pipeline", engine="synthetic", steps=1,
        wall_s=step_wall / 1e3, step_ms=[step_wall], stages=stages,
        phases={"compute": M * t_ms, "bubble": (P - 1) * t_ms},
        num_stages=P, num_microbatches=M,
        events=[{"ts": 100.0, "kind": "pipeline.step.begin",
                 "label": "s0", "data": None}])


class TestStepReport:
    def test_analytic_bubble_frac(self):
        assert analytic_bubble_frac(4, 12) == pytest.approx(3 / 15)
        assert analytic_bubble_frac(1, 8) == 0.0
        with pytest.raises(ValueError):
            analytic_bubble_frac(0, 8)

    def test_synthetic_1f1b_matches_analytic(self):
        for P, M in ((2, 8), (4, 12), (8, 8)):
            rep = _synthetic_pipeline_report(P=P, M=M)
            assert rep.bubble_frac == pytest.approx(
                analytic_bubble_frac(P, M)), (P, M)

    def test_mfu_formula(self):
        rep = StepReport(tokens_per_s=1.0e4, flops_per_token=6.0e9,
                         peak_flops=9.0e14)
        assert rep.mfu == pytest.approx(1.0e4 * 6.0e9 / 9.0e14)
        assert compute_mfu(0.0, 6e9, 9e14) is None
        assert compute_mfu(1e4, 6e9, 0.0) is None

    def test_phase_wall_ratio(self):
        rep = StepReport(step_ms=[10.0, 10.0],
                         phases={"a": 12.0, "b": 7.0})
        assert rep.phase_wall_ratio() == pytest.approx(0.95)
        assert StepReport().phase_wall_ratio() is None

    def test_dict_roundtrip_and_save(self, tmp_path):
        rep = _synthetic_pipeline_report()
        back = StepReport.from_dict(rep.to_dict())
        assert back.bubble_frac == rep.bubble_frac
        assert back.stages == rep.stages and back.phases == rep.phases
        p = rep.save(str(tmp_path / "rep.json"))
        loaded = json.load(open(p))
        assert loaded["kind"] == "pipeline"
        assert loaded["bubble_frac"] == pytest.approx(rep.bubble_frac)

    def test_chrome_trace_schema(self):
        rep = _synthetic_pipeline_report(P=2, M=4)
        trace = json.loads(json.dumps(rep.to_chrome_trace()))
        assert set(trace) == {"traceEvents", "displayTimeUnit",
                              "otherData"}
        evs = trace["traceEvents"]
        for ev in evs:
            assert {"ph", "name", "pid", "tid"} <= set(ev), ev
            if ev["ph"] != "M":
                assert "ts" in ev, ev
            if ev["ph"] == "X":
                assert ev["dur"] > 0, ev
        cats = {ev.get("cat") for ev in evs}
        assert {"cgraph", "flightrec", "phase"} <= cats
        lanes = {ev["tid"] for ev in evs if ev.get("cat") == "cgraph"}
        assert lanes == {"stage 0.0", "stage 0.1"}

    def test_suggest_pipeline_hints(self):
        # deep pipeline, few microbatches -> raise M
        rep = _synthetic_pipeline_report(P=8, M=8)
        hints = " ".join(rep.suggest())
        assert "raise microbatches" in hints
        # imbalanced: measured bubble far above the analytic floor
        rep2 = _synthetic_pipeline_report(P=2, M=16)
        rep2.stages[0]["bubble_ms"] = 200.0
        assert any("imbalanced" in h or "recv-starved" in h
                   for h in rep2.suggest())
        # sync-dominated update
        rep3 = _synthetic_pipeline_report(P=2, M=16)
        for s in rep3.stages:
            s["sync_ms"] = 0.5 * s["exec_ms"]
        assert any("sync-exposed" in h for h in rep3.suggest())

    def test_suggest_llm_hints(self):
        rep = StepReport(kind="llm", steps=4, step_ms=[5.0] * 4,
                         phases={"admit": 0.1, "prefill": 12.0,
                                 "decode": 7.0, "retire": 0.1},
                         occupancy=[1.0, 1.0, 2.0, 1.0],
                         kv_pressure=[0.5, 0.95, 0.7, 0.6],
                         extra={"max_batch": 8})
        hints = " ".join(rep.suggest())
        assert "admission-starved" in hints
        assert "KV pressure" in hints
        assert "chunked prefill" in hints
        calm = StepReport(kind="llm", steps=1, step_ms=[5.0],
                          phases={"decode": 5.0}, occupancy=[8.0],
                          kv_pressure=[0.2], extra={"max_batch": 8})
        assert calm.suggest() == \
            ["no obvious tuning headroom at this schedule"]


# ---------------------------------------------------------------------------
# post-mortem bundles
# ---------------------------------------------------------------------------


_GOLDEN_BUNDLE = {
    "reason": "abort: TaskError(boom)", "origin": "driver", "time": 1000.6,
    "rings": {
        "driver": [
            {"ts": 1000.0, "kind": "pipeline.step.begin", "label": "step7",
             "data": None},
            {"ts": 1000.5, "kind": "chan.send", "label": "0:fwd->1:fwd",
             "data": {"seq": 3}},
        ],
        "worker:0.1": [
            {"ts": 1000.1, "kind": "cgraph.op.begin", "label": "1:f0.0",
             "data": {"method": "forward"}},
        ],
    },
    "meta": {"step": 7},
}

_GOLDEN_RENDER = """\
== post-mortem bundle ==
reason : abort: TaskError(boom)
origin : driver
rings  : driver(2), worker:0.1(1)
meta   : step = 7

-- in-flight at death (2) --
  ! driver       pipeline.step      step7 (began +0.000s)
  ! worker:0.1   cgraph.op          1:f0.0 (began +0.100s)

-- last 3 of 3 events --
  +    0.000s driver       pipeline.step.begin    step7
  +    0.100s worker:0.1   cgraph.op.begin        1:f0.0  {'method': 'forward'}
  +    0.500s driver       chan.send              0:fwd->1:fwd  {'seq': 3}"""


class TestPostmortem:
    def test_find_dangling(self):
        dangling = find_dangling(_GOLDEN_BUNDLE)
        assert [(d["proc"], d["family"], d["label"]) for d in dangling] \
            == [("driver", "pipeline.step", "step7"),
                ("worker:0.1", "cgraph.op", "1:f0.0")]
        # a matched begin/end pair must NOT dangle
        closed = {"rings": {"w": [
            {"ts": 1.0, "kind": "cgraph.op.begin", "label": "a"},
            {"ts": 2.0, "kind": "cgraph.op.end", "label": "a"}]}}
        assert find_dangling(closed) == []

    def test_render_bundle_golden(self):
        assert render_bundle(_GOLDEN_BUNDLE, tail=5) == _GOLDEN_RENDER

    def test_dump_throttle_and_fetcher_error(self, tmp_path, monkeypatch):
        monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(tmp_path))
        postmortem._recent.clear()
        before = postmortem._C_BUNDLES.total()

        def bad_fetch():
            raise ConnectionError("worker gone")

        p1 = dump_bundle("unit: first", origin="test",
                         extra_rings={"extra": [{"ts": 1.0, "kind": "k",
                                                 "label": "l",
                                                 "data": None}]},
                         ring_fetchers={"worker:dead": bad_fetch},
                         meta={"n": 1})
        assert p1 and os.path.dirname(p1) == str(tmp_path)
        assert postmortem.last_bundle_path() == p1
        b = load_bundle(p1)
        assert b["reason"] == "unit: first" and "test" in b["rings"]
        assert b["rings"]["extra"][0]["label"] == "l"
        assert b["rings"]["worker:dead"][0]["kind"] \
            == "postmortem.fetch_error"
        assert postmortem._C_BUNDLES.total() - before == 1
        # same (origin, reason-prefix) inside the window -> throttled
        assert dump_bundle("unit: again", origin="test") is None
        # explicit opt-out still dumps
        p2 = dump_bundle("unit: forced", origin="test", throttle=False)
        assert p2 and p2 != p1
        assert postmortem._C_BUNDLES.total() - before == 2
        # a named destination (a run's flight record): written there,
        # over what was there, and not counted among the aborts'
        dest = str(tmp_path / "flight.json")
        for reason in ("unit: run", "unit: run again"):
            assert dump_bundle(reason, origin="test", throttle=False,
                               path=dest) == dest
        assert load_bundle(dest)["reason"] == "unit: run again"
        assert postmortem._C_BUNDLES.total() - before == 2

    def test_cli_postmortem_render(self, tmp_path, capsys):
        from ray_tpu import cli

        path = tmp_path / "bundle.json"
        path.write_text(json.dumps(_GOLDEN_BUNDLE))
        assert cli.main(["postmortem", str(path), "--tail", "5"]) == 0
        out = capsys.readouterr().out
        assert _GOLDEN_RENDER in out and str(path) in out

    def test_cli_postmortem_missing_bundle(self, tmp_path, monkeypatch,
                                           capsys):
        from ray_tpu import cli

        monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR",
                           str(tmp_path / "empty"))
        monkeypatch.setattr(postmortem, "_last_path", None)
        assert cli.main(["postmortem"]) != 0
        assert "no post-mortem bundle" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# jax's build phases as spans of the ring (ISSUE 38)
# ---------------------------------------------------------------------------


@pytest.fixture
def jax_spans():
    """The installer in force, the process recorder on, and
    ``built(fn)``: the ``rtpu.jax.*`` spans that lowering and compiling
    ``jax.jit(fn)`` for a vector of 8 appended to the ring (nothing
    runs, so an executable read back from a cache is never executed)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.perf import get_recorder, install_jax_spans

    install_jax_spans()
    rec = get_recorder()
    was = rec.enabled
    rec.enabled = True
    x = jnp.ones(8)     # made here: its own eager programs are not fn's

    def built(fn):
        t0 = time.time()
        compiled = jax.jit(fn).lower(x).compile()
        return compiled, [ev for ev in rec.spans("rtpu.jax.", since=t0)
                          if fn.__name__ in ev["label"]]

    yield built
    rec.enabled = was


@pytest.fixture
def compile_cache(tmp_path):
    """``use(directory or None)`` points jax's persistent compile cache
    at a directory that keeps every program, or at none; what the
    process had is put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}

    def use(directory):
        jax.config.update(keys[0], directory)
        jax.config.update(keys[1], 0)
        jax.config.update(keys[2], -1)
        cc.reset_cache()

    yield use
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


class TestJaxBuildSpans:
    def test_a_known_function_leaves_its_three_phases(self, jax_spans,
                                                      compile_cache):
        compile_cache(None)

        def phases_fn(x):
            return (x * x).sum()

        rec = recorder.get_recorder()
        t0 = time.time()
        with rec.span("rtpu.test.around"):
            _, evs = jax_spans(phases_fn)
        assert [(e["kind"], e["label"]) for e in evs] == [
            ("rtpu.jax.trace", "phases_fn"),
            ("rtpu.jax.lower", "jit_phases_fn"),
            ("rtpu.jax.compile", "jit_phases_fn")]
        # the recorder's own shape: wall-clock start, seconds, what
        # caused it; in the order they ran
        assert all(e["dur"] >= 0 and e["parent"] == "rtpu.test.around"
                   for e in evs)
        assert evs[0]["ts"] <= evs[1]["ts"] <= evs[2]["ts"] <= time.time()
        assert evs[2]["ts"] >= evs[1]["ts"] + evs[1]["dur"] - 1e-3
        assert evs[2]["data"] == {"cache": "off"} and evs[1]["data"] is None
        # the jitted helpers it calls (multiply, sum) are traced inside
        # its trace: counted there, no spans of their own
        assert evs[0]["data"]["inner"] >= 1
        assert rec.spans("rtpu.jax.", since=t0) == evs

    def test_cache_miss_then_hit_and_the_compile_counter(
            self, jax_spans, compile_cache, tmp_path):
        import jax

        from ray_tpu.perf import jaxbuild

        compile_cache(str(tmp_path / "cache"))

        def cached_fn(x):
            return (x + 3.0).prod()

        n0 = jaxbuild._C_COMPILES.total()
        _, first = jax_spans(cached_fn)
        assert first[-1]["kind"] == "rtpu.jax.compile"
        assert first[-1]["data"] == {"cache": "miss"}
        assert jaxbuild._C_COMPILES.total() - n0 == 1   # a backend compile
        jax.clear_caches()       # a new process's view: trace, lower, read
        _, again = jax_spans(cached_fn)
        assert [e["kind"] for e in again] == [
            "rtpu.jax.trace", "rtpu.jax.lower", "rtpu.jax.compile"]
        data = again[-1]["data"]
        assert data["cache"] == "hit"
        assert data["read_s"] > 0 and "saved_s" in data
        assert jaxbuild._C_COMPILES.total() - n0 == 1   # a hit is no compile

    def test_installing_twice_records_once(self, jax_spans):
        from ray_tpu.perf import install_jax_spans

        install_jax_spans()
        install_jax_spans()

        def twice_fn(x):
            return x.max()

        _, evs = jax_spans(twice_fn)
        assert [e["kind"] for e in evs] == [
            "rtpu.jax.trace", "rtpu.jax.lower", "rtpu.jax.compile"]

    def test_a_built_program_records_nothing_when_called(self, jax_spans):
        """The hot path is untouched: jax fires no event on its cached
        dispatch, so a steady-state step leaves the ring as it was."""
        import jax
        import jax.numpy as jnp

        rec = recorder.get_recorder()

        def hot_fn(x):
            return x * 2.0

        f = jax.jit(hot_fn)
        x = jnp.ones(8)
        f(x).block_until_ready()
        assert rec.spans("rtpu.jax.compile")[-1]["label"] == "jit_hot_fn"
        before = rec.stats()["appended"]
        for _ in range(3):
            f(x).block_until_ready()
        assert rec.stats()["appended"] == before

    def test_recorder_off_records_and_counts_nothing(self, jax_spans):
        from ray_tpu.perf import jaxbuild

        rec = recorder.get_recorder()

        def dark_fn(x):
            return x.min()

        rec.enabled = False
        before = rec.stats()["appended"], jaxbuild._C_COMPILES.total()
        _, evs = jax_spans(dark_fn)
        rec.enabled = True
        assert evs == []
        assert (rec.stats()["appended"],
                jaxbuild._C_COMPILES.total()) == before

    def test_long_phases_are_pinned_past_the_rings_turnover(
            self, monkeypatch):
        from ray_tpu.perf import jaxbuild

        rec = FlightRecorder(capacity=4, enabled=True)
        monkeypatch.setattr(recorder, "_GLOBAL", rec)
        event = "/jax/core/compile/backend_compile_duration"
        jaxbuild._on_span(event, 100.0, 100.0 + 2 * jaxbuild.PIN_S,
                          fun_name="jit(the_step)")
        jaxbuild._on_span(event, 101.0, 101.01, fun_name="jit(eager)")
        for i in range(8):
            rec.record("noise", str(i))
        kept = [(e["label"], e["ts"], e["dur"]) for e in rec.snapshot()
                if "dur" in e]
        assert kept == [("jit_the_step", 100.0,
                         pytest.approx(2 * jaxbuild.PIN_S))]
        # an event that is not a build phase is not a span
        jaxbuild._on_span("/jax/other", 1.0, 2.0)
        assert len(rec.snapshot()) == 5


# ---------------------------------------------------------------------------
# chaos integration: stage kill mid-step -> bundle with dangling evidence
# ---------------------------------------------------------------------------


class TestPostmortemChaos:
    def test_stage_kill_mid_step_dumps_renderable_bundle(
            self, ray_start_regular, tmp_path, monkeypatch):
        """Kill the middle stage while a step is in flight. The driver's
        abort path must dump a merged bundle into
        RAY_TPU_POSTMORTEM_DIR whose rings carry begin-without-end
        evidence from the processes that survived (the killed worker's
        ring dies with it), and the bundle must render."""
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        monkeypatch.setenv("RAY_TPU_POSTMORTEM_DIR", str(tmp_path))
        postmortem._recent.clear()

        k = jax.random.PRNGKey(0)

        def mk_mid():
            def sleepy(x):
                time.sleep(0.25)
                return x

            def _cb(x):
                return jax.pure_callback(
                    sleepy, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

            # custom_vjp so the callback survives the engine's jax.vjp —
            # a bare pure_callback raises under JVP and the step would
            # abort on its own BEFORE the kill lands, turning this into
            # an abort-on-error test instead of a mid-step kill
            slow = jax.custom_vjp(_cb)
            slow.defvjp(lambda x: (_cb(x), None), lambda _, g: (g,))

            def fn(p, x):
                return jnp.tanh(slow(x) @ p["w"] + p["b"])
            return fn

        def mk_edge(last):
            def mid(p, x):
                return jnp.tanh(x @ p["w"] + p["b"])

            def tail(p, x, targets):
                return jnp.mean((x @ p["w"] + p["b"] - targets) ** 2)
            return tail if last else mid

        width = 8
        fns = [mk_edge(False), mk_mid(), mk_edge(True)]
        params = [
            {"w": jax.random.normal(jax.random.fold_in(k, i),
                                    (width, width)) * 0.3,
             "b": jnp.zeros((width,))} for i in range(3)]
        xs = jax.random.normal(jax.random.fold_in(k, 9), (8, width))
        ys = jax.random.normal(jax.random.fold_in(k, 10), (8, width))
        mbs = [xs[i * 2:(i + 1) * 2] for i in range(4)]
        tgts = [ys[i * 2:(i + 1) * 2] for i in range(4)]
        eng = CompiledPipelineEngine(
            fns, params, optax.sgd(1e-2), num_microbatches=4,
            channel_bytes=1 << 18, resources_per_stage={"CPU": 0.5})
        result = {}

        def drive():
            try:
                eng.step(mbs, tgts, timeout=60)
                result["ok"] = True
            except BaseException as e:  # noqa: BLE001 — asserted below
                result["err"] = e

        t = threading.Thread(target=drive)
        t.start()
        time.sleep(0.4)   # the slow middle stage is inside the step
        ray_tpu.kill(eng.actor_grid[0][1])
        t.join(timeout=60)
        assert "err" in result, result
        deadline = time.monotonic() + 30
        paths = []
        while not paths and time.monotonic() < deadline:
            paths = glob.glob(str(tmp_path / "postmortem-*.json"))
            time.sleep(0.2)
        assert paths, "no bundle dumped after mid-step stage kill"
        bundle = load_bundle(sorted(paths)[0])
        assert bundle["origin"] == "driver"
        assert bundle["meta"].get("num_stages") == 3
        assert "driver" in bundle["rings"]
        worker_rings = [p for p in bundle["rings"] if p != "driver"]
        assert len(worker_rings) == 3, bundle["rings"].keys()
        dangling = find_dangling(bundle)
        assert dangling, "expected in-flight begin-without-end evidence"
        survivors = {d["proc"] for d in dangling}
        assert any(p != "driver" for p in survivors) \
            or any(d["family"] == "pipeline.step" for d in dangling), \
            dangling
        rendered = render_bundle(bundle)
        assert "== post-mortem bundle ==" in rendered
        assert "in-flight at death" in rendered
        eng.shutdown()
