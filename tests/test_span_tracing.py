"""ISSUE 24: one way to time a phase. ``FlightRecorder.span`` (start,
duration, parent; ring and profiler annotation; never imports jax), the
engine's phases, lock waits and counters built on it, and ``profile()``
filled from spans alone."""
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.perf.recorder import FlightRecorder, get_recorder
from ray_tpu.serve.llm import EngineConfig, LLMEngine, build_model
from ray_tpu.serve.llm.engine import step_phases
from ray_tpu.util import tracing


# ---------------------------------------------------------------------------
# the instrument
# ---------------------------------------------------------------------------


class TestSpan:
    def test_records_start_duration_and_parent_and_nests(self):
        rec = FlightRecorder(capacity=64, enabled=True)
        t0 = time.time()
        with rec.span("rtpu.t.outer", "lbl", {"a": 1}) as outer:
            with rec.span("rtpu.t.inner.b64") as inner:
                time.sleep(0.02)
            with rec.span("rtpu.t.inner.b64"):
                pass
        evs = rec.snapshot()
        # ONE event per span, appended at its end: children first
        assert [e["kind"] for e in evs] == [
            "rtpu.t.inner.b64", "rtpu.t.inner.b64", "rtpu.t.outer"]
        first, second, out = evs
        assert first["parent"] == second["parent"] == "rtpu.t.outer"
        assert out["parent"] == "" and out["label"] == "lbl"
        assert out["data"] == {"a": 1}
        assert t0 <= out["ts"] <= first["ts"] <= second["ts"]
        assert 0.02 <= first["dur"] <= out["dur"] < 5.0
        assert inner.dur == first["dur"] and outer.dur == out["dur"]
        # instants keep their four fields; spans() finds only spans
        rec.record("t.instant", "x")
        assert set(rec.snapshot()[-1]) == {"ts", "kind", "label", "data"}
        assert [e["kind"] for e in rec.spans("rtpu.t.inner")] == [
            "rtpu.t.inner.b64"] * 2
        assert rec.spans("rtpu.", since=time.time() + 1) == []

    @pytest.mark.parametrize("enabled", [True, False])
    def test_always_measures_and_the_ring_is_what_enabled_gates(
            self, enabled):
        rec = FlightRecorder(capacity=8, enabled=enabled)
        with rec.span("rtpu.t.x") as sp:
            time.sleep(0.01)
        assert sp.dur >= 0.01
        assert len(rec.snapshot()) == (1 if enabled else 0)
        tok = rec.begin("rtpu.t.y")
        assert rec.end(tok) >= 0.0
        assert len(rec.snapshot()) == (2 if enabled else 0)

    def test_keep_false_leaves_no_event_and_data_may_be_set_inside(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        with rec.span("rtpu.t.step") as sp:
            sp.keep = False
        with rec.span("rtpu.t.step") as sp:
            sp.data = {"n": 3}
        evs = rec.snapshot()
        assert len(evs) == 1 and evs[0]["data"] == {"n": 3}

    def test_merge_folds_a_repeat_into_the_event_before_it(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        for _ in range(5):
            with rec.span("rtpu.t.idle", "e", merge=True):
                time.sleep(0.002)
        with rec.span("rtpu.t.work", "e"):
            pass
        with rec.span("rtpu.t.idle", "e", merge=True):
            pass
        evs = rec.snapshot()
        assert [e["kind"] for e in evs] == ["rtpu.t.idle", "rtpu.t.work",
                                           "rtpu.t.idle"]
        assert evs[0]["dur"] >= 0.01            # first start to last end
        assert rec.stats()["appended"] == 3 and rec.dropped == 0

    def test_begin_end_cross_a_thread_and_merge_their_data(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        with rec.span("rtpu.t.outer"):
            tok = rec.begin("rtpu.t.spawn", "w1", {"chip": True})
        t = threading.Thread(target=lambda: rec.end(tok, {"pid": 7}))
        t.start()
        t.join(timeout=30)
        ev = rec.spans("rtpu.t.spawn")[0]
        assert ev["parent"] == "rtpu.t.outer" and ev["label"] == "w1"
        assert ev["data"] == {"chip": True, "pid": 7} and ev["dur"] >= 0

    def test_parents_are_per_thread(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        seen = []

        def other():
            with rec.span("rtpu.t.other"):
                pass
            seen.append(rec.spans("rtpu.t.other")[0]["parent"])

        with rec.span("rtpu.t.main"):
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=30)
        assert seen == [""]

    def test_span_does_not_import_jax(self):
        code = (
            "import sys\n"
            "from ray_tpu.perf.recorder import get_recorder\n"
            "rec = get_recorder()\n"
            "with rec.span('rtpu.t.a'):\n"
            "    with rec.span('rtpu.t.b'):\n"
            "        pass\n"
            "rec.end(rec.begin('rtpu.t.c'))\n"
            "assert len(rec.spans('rtpu.t.')) == 3\n"
            "assert 'jax' not in sys.modules, 'span() imported jax'\n")
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]

    def test_with_jax_imported_the_span_is_on_the_profilers_host_plane(
            self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        rec = FlightRecorder(capacity=8, enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with rec.span("rtpu.t.traced.b8"):
                jnp.ones((8, 8)).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        names = {e.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events}
        assert "rtpu.t.traced.b8" in names
        assert rec.spans("rtpu.t.traced")[0]["dur"] > 0


# ---------------------------------------------------------------------------
# the engine on it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    return build_model("gpt-tiny")


def mk_engine(tiny_model, name, **over) -> LLMEngine:
    m, params = tiny_model
    kw = dict(block_size=4, num_blocks=32, max_batch=4,
              max_blocks_per_seq=8, prefill_buckets=(8, 16),
              max_prefill_tokens_per_step=32)
    kw.update(over)
    return LLMEngine(m, params, EngineConfig(**kw), name=name)


def _hold_lock(eng, seconds):
    """A thread that holds the engine's lock, as the scheduler loop does
    through a step; returns once it has it."""
    has = threading.Event()

    def hold():
        with eng._lock:
            has.set()
            time.sleep(seconds)

    t = threading.Thread(target=hold)
    t.start()
    assert has.wait(5)
    return t


OBSERVERS = {
    "stats": lambda e: e.stats(),
    "queue_depth": lambda e: e.queue_depth(),
    "cache_stats": lambda e: e.cache_stats(),
    "kv_bytes_per_chip": lambda e: e.kv_bytes_per_chip(),
}


class TestEngineLockWaits:
    def test_intake_waits_for_the_lock_and_every_sink_shows_it(
            self, tiny_model):
        eng = mk_engine(tiny_model, "lockwait-intake")
        spans = []
        old, tracing.span_export = tracing.span_export, spans.append
        t_mark = time.time()
        try:
            holder = _hold_lock(eng, 0.3)
            stream = eng.add_request([1, 5, 9], max_tokens=2,
                                     trace_ctx=("t" * 32, "p" * 16))
            holder.join(timeout=30)
            eng.run_until_idle(timeout=300)
            assert len(stream.tokens()) == 2
        finally:
            tracing.span_export = old
        st = eng.stats()
        assert st["lock_waits"]["intake"] == 1
        assert 0.2 <= st["lock_wait_s"]["intake"] < 5.0
        assert st["lock_wait_max_s"]["intake"] == \
            st["lock_wait_s"]["intake"]
        # the ring: one span on the caller's thread, named for who asked
        ring = [e for e in get_recorder().spans(
            "rtpu.llm.lock_wait.intake", since=t_mark)
            if e["label"] == eng.name]
        assert len(ring) == 1 and ring[0]["dur"] >= 0.2
        # the request's own trace: the lock wait is its own span, and
        # llm.admit (queue + prefill) starts where it ends
        by = {s["name"]: s for s in spans}
        wait, admit = by["llm.intake_wait"], by["llm.admit"]
        assert wait["end_time"] - wait["time"] >= 0.2
        assert admit["time"] == pytest.approx(wait["end_time"], abs=1e-6)
        assert admit["time"] >= wait["time"] + 0.2

    @pytest.mark.parametrize("call", sorted(OBSERVERS))
    def test_observers_wait_on_their_own_counter(self, tiny_model, call):
        eng = mk_engine(tiny_model, f"lockwait-{call}", prefix_cache=True)
        holder = _hold_lock(eng, 0.15)
        OBSERVERS[call](eng)
        holder.join(timeout=30)
        st = eng.stats()                    # itself one more observer
        assert st["lock_waits"] == {"intake": 0, "observer": 2}
        assert 0.1 <= st["lock_wait_max_s"]["observer"] < 5.0
        assert st["lock_wait_s"]["intake"] == 0.0

    def test_loop_lock_held_grows_with_the_steps(self, tiny_model):
        eng = mk_engine(tiny_model, "lockheld")
        eng.add_request([1, 2, 3], max_tokens=4)
        eng.run_until_idle(timeout=300)
        held = eng.stats()["loop_lock_held_s"]
        steps = [e for e in get_recorder().spans("rtpu.llm.step")
                 if e["label"] == eng.name]
        assert held > 0 and held >= sum(e["dur"] for e in steps) * 0.999


class TestEngineCounters:
    def test_decode_steps_and_prefill_calls_count_a_scripted_run(
            self, tiny_model):
        eng = mk_engine(tiny_model, "counters")
        # both admitted in the first step (5 + 12 <= 32 prefill tokens),
        # each prefill emits the first token, then one decode step per
        # remaining token of the longer answer
        a = eng.add_request([1, 2, 3, 4, 5], max_tokens=4)        # b8
        b = eng.add_request(list(range(1, 13)), max_tokens=6)     # b16
        eng.run_until_idle(timeout=300)
        assert len(a.tokens()) == 4 and len(b.tokens()) == 6
        st = eng.stats()
        assert st["decode_steps"] == 5
        assert st["prefill_calls"] == {"8": 1, "16": 1}
        assert st["extend_calls"] == 0 and st["cow_copies"] == 0
        # a third request later: one more prefill, three more steps
        eng.add_request([7, 7, 7], max_tokens=4)
        eng.run_until_idle(timeout=300)
        st = eng.stats()
        assert st["decode_steps"] == 8
        assert st["prefill_calls"] == {"8": 2, "16": 1}
        # and the spans agree with the counters
        mine = [e for e in get_recorder().spans("rtpu.llm.")
                if e["label"] == eng.name]
        kinds = [e["kind"] for e in mine]
        assert kinds.count("rtpu.llm.decode.dispatch") == 8
        assert kinds.count("rtpu.llm.decode.fetch") == 8
        assert kinds.count("rtpu.llm.prefill.b8") == 2
        assert kinds.count("rtpu.llm.prefill.b16") == 1
        assert kinds.count("rtpu.llm.retire") == 3
        assert {e["parent"] for e in mine
                if e["kind"].startswith("rtpu.llm.prefill.")} == {
            "rtpu.llm.admit"}

    def test_extend_calls_count_a_shared_prefix(self, tiny_model):
        eng = mk_engine(tiny_model, "counters-prefix", prefix_cache=True)
        shared = list(range(1, 10))                  # two full blocks of 4
        eng.add_request(shared + [20], max_tokens=2)
        eng.run_until_idle(timeout=300)
        eng.add_request(shared + [21, 22], max_tokens=2)
        eng.run_until_idle(timeout=300)
        st = eng.stats()
        assert st["extend_calls"] == 1
        assert sum(st["prefill_calls"].values()) == 1
        assert st["prefix_hit_tokens"] >= 8

    def test_an_idle_engine_leaves_one_event_for_the_stretch(
            self, tiny_model):
        eng = mk_engine(tiny_model, "idle-stretch", idle_sleep_s=0.002)
        eng.start()
        try:
            time.sleep(0.3)
        finally:
            eng.stop()
        mine = [e for e in get_recorder().spans("rtpu.llm.")
                if e["label"] == eng.name]
        # its constructor, then some hundred empty steps and waits:
        # merged, and no step event
        assert [e["kind"] for e in mine] == ["rtpu.llm.start",
                                             "rtpu.llm.idle"]
        assert mine[1]["dur"] >= 0.2


class TestProfileFromSpans:
    def test_phases_sum_to_the_steps_wall_time_from_spans_alone(
            self, tiny_model):
        eng = mk_engine(tiny_model, "profile-spans")
        assert not hasattr(eng, "_phase_s")
        for i in range(3):
            eng.add_request([1, 2, 3, 4 + i], max_tokens=5)
        rep = eng.profile(steps=8)
        assert rep.kind == "llm" and rep.steps == len(rep.step_ms) >= 4
        assert set(rep.phases) == {"admit", "prefill", "decode", "retire"}
        assert rep.phases["prefill"] > 0 and rep.phases["decode"] > 0
        assert rep.phase_wall_ratio() == pytest.approx(1.0, abs=2e-3)
        assert len(rep.occupancy) == len(rep.kv_pressure) == rep.steps
        # tokens: the decode steps' (a prefill's first token is not counted)
        assert rep.tokens == 12 and rep.wall_s >= sum(rep.step_ms) / 1e3
        # the report's events hold the spans it was computed from
        again = step_phases(rep.events)
        assert again == (rep.step_ms, rep.phases)

    def test_step_phases_charges_self_time_and_skips_orphans(self):
        def ev(kind, ts, dur, parent):
            return {"ts": ts, "kind": kind, "label": "e", "data": None,
                    "dur": dur, "parent": parent}

        step = "rtpu.llm.step"
        events = [
            # a step that found no work left no event, but its admit did
            ev("rtpu.llm.admit", 0.0, 0.5, step),
            # a working step: 10 ms in all
            ev("rtpu.llm.prefill.b8", 1.001, 0.003, "rtpu.llm.admit"),
            ev("rtpu.llm.admit", 1.0005, 0.004, step),
            ev("rtpu.llm.decode.prepare", 1.005, 0.001, step),
            ev("rtpu.llm.retire", 1.0075, 0.0005,
               "rtpu.llm.decode.sample"),
            ev("rtpu.llm.decode.sample", 1.007, 0.002, step),
            ev(step, 1.0, 0.010, ""),
            # outside any step: a caller's lock wait, a retire from
            # _fail_all, an instant
            ev("rtpu.llm.lock_wait.intake", 1.0, 3.0, ""),
            ev("rtpu.llm.retire", 2.0, 0.1, ""),
            {"ts": 2.0, "kind": "llm.admit", "label": "r", "data": None},
        ]
        step_ms, phases = step_phases(events)
        assert step_ms == [10.0]
        assert phases == {"admit": pytest.approx(1.0 + 3.0),   # self+step
                          "prefill": pytest.approx(3.0),
                          "decode": pytest.approx(1.0 + 1.5),
                          "retire": pytest.approx(0.5)}
        assert sum(phases.values()) == pytest.approx(10.0)

    def test_with_the_recorder_off_there_is_nothing_to_report(
            self, tiny_model):
        eng = mk_engine(tiny_model, "profile-off")
        eng.add_request([1, 2, 3], max_tokens=3)
        rec = get_recorder()
        was, rec.enabled = rec.enabled, False
        try:
            rep = eng.profile(steps=3)
        finally:
            rec.enabled = was
        assert rep.steps == 0 and rep.step_ms == []
        assert rep.tokens == 2 and rep.wall_s > 0   # spans still measure


class TestPinnedStartUp:
    def test_pinned_spans_outlive_the_rings_turnover(self):
        rec = FlightRecorder(capacity=8, enabled=True)
        with rec.span("rtpu.t.init", pin=True):
            with rec.span("rtpu.t.init.gcs", pin=True):
                pass
        rec.end(rec.begin("rtpu.t.spawn", "w", {"chip": True}, pin=True),
                {"pid": 1})
        # while the ring still holds them they are there once
        assert [e["kind"] for e in rec.snapshot()] == [
            "rtpu.t.init.gcs", "rtpu.t.init", "rtpu.t.spawn"]
        for i in range(50):                 # a driver dispatching tasks
            rec.record("dispatch.direct", f"t{i}")
        evs = rec.snapshot()
        assert [e["kind"] for e in evs[:3]] == [
            "rtpu.t.init.gcs", "rtpu.t.init", "rtpu.t.spawn"]
        assert evs[2]["data"] == {"chip": True, "pid": 1}
        assert len(evs) == 3 + 8 and rec.dropped == 45
        # an unpinned span is gone with the ring, and a drain delivers
        # the shelf once
        assert rec.spans("rtpu.t.") == evs[:3]
        rec.snapshot(clear=True)
        assert rec.snapshot() == []


def test_start_up_spans_end_in_the_drivers_ring(tmp_path):
    """``ray_tpu.init`` and a trainer's start leave their spans in THIS
    process's recorder, still there after ``shutdown()``; the chip
    worker's own stamps and its jax start-up ride replies that exist."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        from ray_tpu import train

        train.report({"devices": len(train.get_mesh().devices.flat)})

    t0 = time.time()
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        result = JaxTrainer(
            loop, train_loop_config={"x": 1},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": 1.0}),
            run_config=RunConfig(name="t", storage_path=str(tmp_path))
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None
    by = {}
    for ev in get_recorder().spans("rtpu.", since=t0):
        by.setdefault(ev["kind"], []).append(ev)
    init = by["rtpu.core.init"][0]
    assert {by[k][0]["parent"] for k in ("rtpu.core.init.gcs",
                                         "rtpu.core.init.node")} == {
        "rtpu.core.init"}
    assert init["dur"] >= by["rtpu.core.init.gcs"][0]["dur"]
    chip = [e for e in by["rtpu.core.worker_spawn"] if e["data"]["chip"]]
    assert len(chip) == 1
    stamps = chip[0]["data"]["stamps"]
    assert chip[0]["ts"] - 0.05 <= stamps["process_start"] \
        <= stamps["main_entered"] <= stamps["imports_done"] \
        <= stamps["register_sent"] <= chip[0]["ts"] + chip[0]["dur"] + 0.05
    mesh = by["rtpu.train.setup_mesh"][0]
    assert len(mesh["data"]["jax_start_s"]) == 1
    assert 0 < mesh["data"]["jax_start_s"][0] + mesh["data"]["mesh_s"][0] \
        <= mesh["dur"]
    # the call reached the worker after its process had registered
    assert chip[0]["ts"] + chip[0]["dur"] - 0.05 <= \
        mesh["data"]["entered"][0] <= mesh["ts"] + mesh["dur"]
    for kind in ("rtpu.train.pg_ready", "rtpu.train.setup_session"):
        assert by[kind][0]["dur"] >= 0


def test_setup_mesh_still_answers_a_device_count():
    import pickle

    from ray_tpu.parallel.mesh_group import MeshReady

    r = pickle.loads(pickle.dumps(MeshReady(4, 8.5, 0.25, 1e9)))
    assert r == 4 and r + 1 == 5 and isinstance(r, int)
    assert (r.jax_start_s, r.mesh_s, r.entered) == (8.5, 0.25, 1e9)
    import cloudpickle

    r2 = cloudpickle.loads(cloudpickle.dumps(r))
    assert r2 == 4 and r2.jax_start_s == 8.5
