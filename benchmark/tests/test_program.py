"""What the program says about itself, read by ``layer_metrics/_program.py``
(PR 24): the arithmetic on synthetic events, and every reader built on it on
two fixtures cut by ``scratch/make_fixture.py`` from PR 24's chip runs on a
TPU v5e: ``data_pr24_train.json.gz`` (the training cell run for 45 s so
that a ``train.report`` falls into the trace: two whole train steps with
the ``op_name`` of each operation and the chip process's one program span)
and ``data_pr24_serve.json.gz`` (GPT-2-large, 32 callers in a closed loop,
a 10 s window traced while callers are still being admitted: two prefills
and four decode steps, the scheduler's ``rtpu.llm.*`` spans, the window's
sampled ``stats()``)."""
import gzip
import json
import os

import pytest

from benchmark.layer_metrics import _common as C
from benchmark.layer_metrics import _program as P
from benchmark.lib import spec
from benchmark.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = spec.load_metric_readers("layer_metrics")
GPT_SCOPES = spec.load_family("gpt").SCOPES


def _fixture(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        return json.load(f)


def _view(fx, monkeypatch, cell="fx", family="gpt"):
    """A traced run's view over a fixture: the helper finds 'its' xplane at
    a path that does not exist, already read. The cell is of ``family``
    (a file in ``benchmark/families/``)."""
    tr = T.Trace.from_json(fx["trace"])
    path = f"/nonexistent/{cell}.xplane.pb"
    monkeypatch.setattr(P, "trace_path", lambda view: path)
    ops = [o[:3] for o in tr.devices[0]["ops"]]
    monkeypatch.setitem(P._cache, "spans:" + path, tr.host)
    monkeypatch.setitem(P._cache, "ops:" + path, (ops, fx["op_names"]))
    return {"trace": tr, "cell": {"name": cell, "engine": fx.get("engine"),
                                  "config_file": {
                                      "sizes": {}, "model": {"family": family}}},
            "window": fx.get("window"), "spans": {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


# -- op_name -> scope  --------------------------------------------------------

@pytest.mark.parametrize("op_name,scope", [
    ("jit(bench_train_step)/jit(main)/attn/dot_general", "attn"),
    ("jit(bench_train_step)/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/attn/jit(_var)/reduce_sum", "attn"),
    ("jit(bench_train_step)/transpose(jvp(lm_head))/div", "lm_head"),
    ("jit(bench_train_step)/jvp(embed)/add", "embed"),
    ("jit(s)/jvp()/while/body/checkpoint/mlp/jit(_var)/div", "mlp"),
    ("jit(_decode)/attn/paged_attn/dot_general", "attn"),   # innermost of
    ("jit(_decode)/attn/kv_write/scatter", "attn"),         # the model's five
    ("jit(loss)/jit(main)/mul", P.UNSCOPED),      # a jitted function's name
    ("jit(step)/jit(attn)/mul", P.UNSCOPED),      # is not a scope
    ("jit(bench_train_step)/add", P.UNSCOPED),
    ("", P.UNSCOPED),
])
def test_scope_of_an_op_name(op_name, scope):
    assert P.scope_of(op_name, GPT_SCOPES) == scope


@pytest.mark.parametrize("op_name,scope", [
    ("jit(bench_train_step)/transpose(jvp())/while/body/closed_call/"
     "checkpoint/ssm/dot_general", "ssm"),
    ("jit(bench_train_step)/jvp(moe)/router/top_k", "moe"),
    ("jit(bench_train_step)/jvp()/while/body/attn/div", P.UNSCOPED),
    ("jit(bench_train_step)/jvp(ssm_conv)/mul", P.UNSCOPED),  # whole names
    ("jit(bench_train_step)/moe/ssm/add", "ssm"),             # innermost
    ("jit(moe)/jit(main)/mul", P.UNSCOPED),
])
def test_scope_of_honours_the_scope_list_of_another_family(op_name, scope):
    assert P.scope_of(op_name, ("ssm", "moe")) == scope


def test_a_step_is_split_by_the_scopes_of_the_cells_family(monkeypatch):
    """A family file with SCOPES = ("ssm", "moe"): the returned dict has
    those scopes and (unscoped), and still sums to the mean step."""
    class Fam:
        SCOPES = ("ssm", "moe")
    monkeypatch.setitem(spec._families, "fx_hybrid", Fam)
    names = {"%s": "jit(s)/while/body/checkpoint/ssm/dot_general",
             "%e": "jit(s)/transpose(jvp(moe))/dot_general",
             "%a": "jit(s)/while/body/attn/div"}
    ops = [["%s", 0.0, 0.4, ""], ["%e", 0.4, 0.3, ""], ["%a", 0.7, 0.2, ""]]
    fx = {"trace": {"devices": {"0": {
        "programs": [["jit_bench_train_step", 0.0, 1.0]], "ops": ops,
        "async_ops": []}}, "host": []}, "op_names": names}
    by = P.scope_ms_per_step(_view(fx, monkeypatch, family="fx_hybrid"))
    assert by == {"ssm": pytest.approx(400.0), "moe": pytest.approx(300.0),
                  P.UNSCOPED: pytest.approx(200.0 + 100.0)}
    # the same events under a family that names attn
    by = P.scope_ms_per_step(_view(fx, monkeypatch))
    assert by["attn"] == pytest.approx(200.0) and set(by) == {
        *GPT_SCOPES, P.UNSCOPED}
    with pytest.raises(ValueError, match=r"unknown model family 'nope'.*"
                       r"'gpt'"):
        P.scope_ms_per_step(_view(fx, monkeypatch, family="nope"))


# -- the xplane's event metadata, read from the wire  -------------------------

def _vint(x):
    out = b""
    while True:
        b = x & 0x7F
        x >>= 7
        out += bytes([b | (0x80 if x else 0)])
        if not x:
            return out


def _msg(*fields):
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _vint(num << 3) + _vint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _vint(num << 3 | 2) + _vint(len(data)) + data
    return out


def test_op_names_are_read_from_the_event_metadata_of_device_zero(tmp_path):
    def plane(name, ops):
        stat_meta = [(5, _msg((1, 7), (2, _msg((1, 7), (2, "flops"))))),
                     (5, _msg((1, 9), (2, _msg((1, 9), (2, "tf_op"))))),
                     (5, _msg((1, 11), (2, _msg(
                         (1, 11), (2, "jit(s)/lm_head/dot_general:")))))]
        ev_meta = []
        for i, (hlo, stats) in enumerate(ops, 1):
            meta = _msg((1, i), (2, hlo), *[(5, s) for s in stats])
            ev_meta.append((4, _msg((1, i), (2, meta))))
        return _msg((1, 1), (2, name), *stat_meta, *ev_meta,
                    (3, _msg((1, 1), (2, "XLA Ops"))))

    dev0 = plane("/device:TPU:0", [
        ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
         [_msg((1, 7), (4, 16384)),                    # flops: an int64
          _msg((1, 9), (5, "jit(s)/transpose(jvp(attn))/mul:"))]),
        ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
         [_msg((1, 9), (7, 11))]),                     # a ref_value
        ("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", [_msg((1, 7), (4, 0))]),
    ])
    dev1 = plane("/device:TPU:1", [("%other.1 = f32[] x()",
                                    [_msg((1, 9), (5, "jit(s)/mlp/x:"))])])
    host = _msg((1, 2), (2, "/host:CPU"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, dev1), (1, host), (1, dev0)))
    assert P.op_names(str(path)) == {
        "%fusion.7": "jit(s)/transpose(jvp(attn))/mul",
        "%fusion.8": "jit(s)/lm_head/dot_general"}
    path.write_bytes(_msg((1, host)))
    assert P.op_names(str(path)) == {}                 # a CPU trace


# -- device self time by scope  -----------------------------------------------

def test_self_time_by_scope_on_synthetic_steps(monkeypatch):
    # two whole steps of 1.0 s and a cut one; per whole step: attn 0.3 in a
    # while loop that itself takes 0.1, mlp 0.2, an unscoped copy 0.1, and
    # 0.3 in which nothing ran
    names = {"%while.1": "jit(s)/while", "%a": "jit(s)/while/body/attn/dot",
             "%m": "jit(s)/jvp(mlp)/dot", "%c": ""}
    ops, progs = [], [["jit_bench_train_step", 0.0, 0.4]]
    for t0 in (0.5, 2.0):
        progs.append(["jit_bench_train_step", t0, 1.0])
        ops += [["%while.1", t0, 0.4], ["%a", t0 + 0.05, 0.3],
                ["%m", t0 + 0.5, 0.2], ["%c", t0 + 0.8, 0.1]]
    ops.append(["%a", 0.1, 0.2])                      # inside the cut step
    fx = {"trace": {"devices": {"0": {"programs": progs, "ops": [
        o + [""] for o in sorted(ops, key=lambda o: o[1])],
        "async_ops": []}}, "host": []}, "op_names": names}
    by = P.scope_ms_per_step(_view(fx, monkeypatch))
    assert by == {"embed": 0, "lm_head": 0, "loss": 0,
                  "attn": pytest.approx(300.0), "mlp": pytest.approx(200.0),
                  P.UNSCOPED: pytest.approx(100.0 + 100.0 + 300.0)}
    assert sum(by.values()) == pytest.approx(1000.0)


def test_train_step_by_scope_on_the_recorded_trace(monkeypatch):
    view = _view(_fixture("data_pr24_train.json.gz"), monkeypatch)
    got = {n: READERS[n].read(view) for n in (
        "train_attn_ms", "train_mlp_ms", "train_head_loss_ms",
        "train_unscoped_ms", "train_step_ms")}
    # the four parts are the whole step (two whole steps in the fixture,
    # so their mean is their median)
    assert sum(got[n] for n in got if n != "train_step_ms") == \
        pytest.approx(got["train_step_ms"], rel=1e-9)
    assert got["train_step_ms"] == pytest.approx(197.3, abs=0.15)
    # the chip process's own span: train.report, 1.6 ms on the host while
    # the device runs on (the host is two steps ahead)
    assert [s[0] for s in P.program_spans(view)] == ["rtpu.train.report"]
    assert P.idle_by_span(view["trace"], P.program_spans(view)).get(
        "rtpu.train.report", 0.0) < 1e-5
    # PR 24's chip run: attention is the largest part, then the mlp
    assert got["train_attn_ms"] == pytest.approx(81.5, abs=0.5)
    assert got["train_mlp_ms"] == pytest.approx(56.8, abs=0.5)
    assert got["train_head_loss_ms"] == pytest.approx(22.8, abs=0.5)
    assert got["train_unscoped_ms"] == pytest.approx(36.2, abs=0.5)
    by = P.scope_ms_per_step(view)
    assert by["lm_head"] > by["loss"] > by["embed"] > 0
    # the flash kernels carry the attention scope
    kernels = [o for o in view["trace"].devices[0]["ops"]
               if "tpu_custom_call" in o[3]]
    assert {o[0].split(".")[0] for o in kernels} == {
        "%flash_fwd_single", "%flash_bwd_fused"}
    op_names = P._cache["ops:/nonexistent/fx.xplane.pb"][1]
    assert {P.scope_of(op_names[o[0]], GPT_SCOPES) for o in kernels} == {
        "attn"}
    # the kernels' reader finds them by their pinned names (PR 26); every
    # custom call to tpu_custom_call, PR 23's pattern, selects the same
    # events of this step
    flash = READERS["flash_attention_roofline"]
    by_name = T.ops_matching(view["trace"], flash.KERNEL)
    assert by_name == kernels and len(by_name) == 2 * 2 * 24
    assert C.kernel_s_per_step(view, flash.KERNEL) == C.kernel_s_per_step(
        view, r"custom_call_target=tpu_custom_call") == pytest.approx(
            33.19e-3, abs=0.05e-3)
    roof = flash.read(dict(
        view, train={"batch": 8, "seq": 1024},
        cell={"name": "fx", "config_file": {"sizes": {
            "n_head": 16, "d_model": 1024, "n_layer": 24}}}))
    assert roof == pytest.approx(22.07, abs=0.1)
    # the model's count of operations is the family's: the number mfu read
    # before families were files (6 N + 6 L D S of lib/flops.py)
    from benchmark.lib import flops
    sizes = spec.load_cell("gpt2m_train_s1024")["config_file"]["sizes"]
    assert spec.load_family("gpt").train_flops_per_token(sizes, 1024) == \
        flops.train_flops_per_token(sizes, 1024) == 2279933952
    mfu = READERS["mfu"].read(dict(
        view, train={"batch": 8, "seq": 1024},
        cell={"name": "fx", "config_file": {
            "sizes": sizes, "model": {"family": "gpt"}}}))
    assert mfu == pytest.approx(48.05, abs=0.03)


def test_kernel_time_and_roofline_share_on_synthetic_events():
    # two whole steps of 1.0 s and a cut one; kernel %k.1 runs 0.1 s twice
    # in every step, another custom call once
    cc = "custom-call(...) custom_call_target=tpu_custom_call"
    progs = [["jit_bench_train_step", 0.0, 0.4]]
    ops = [["%k.1", 0.1, 0.1, cc]]
    for t0 in (0.5, 2.0):
        progs.append(["jit_bench_train_step", t0, 1.0])
        ops += [["%k.1", t0, 0.1, cc], ["%k.2", t0 + 0.2, 0.1, cc],
                ["%other", t0 + 0.5, 0.3, cc],
                ["%fusion.1", t0 + 0.8, 0.1, "fusion(...)"]]
    view = {"trace": T.Trace({0: {"programs": progs, "ops": ops,
                                  "async_ops": []}}, []),
            "device": {"kind": "TPU v5 lite"}}
    assert C.kernel_s_per_step(view, r"^%k(\.\d+)?$") == pytest.approx(0.2)
    assert C.kernel_s_per_step(view, "tpu_custom_call") == pytest.approx(0.5)
    assert C.kernel_s_per_step(view, r"^%nothing$") is None
    assert C.kernel_s_per_step({"trace": None}, "k") is None
    # 19.7e12 operations in 0.2 s are half of the peak; bytes bound it
    # where they take longer
    assert C.roofline_pct(view, 0.2, 19.7e12, 0) == pytest.approx(50.0)
    assert C.roofline_pct(view, 0.2, 19.7e12, 819e9 * 0.15) == \
        pytest.approx(75.0)
    assert C.roofline_pct(view, None, 1, 1) is None


def test_a_program_without_scopes_reads_as_nothing(monkeypatch):
    fx = _fixture("data_pr24_train.json.gz")
    fx["op_names"] = {k: "jit(bench_train_step)/dot_general"
                      for k in fx["op_names"]}
    view = _view(fx, monkeypatch)
    assert P.scope_ms_per_step(view) is None
    assert READERS["train_attn_ms"].read(view) is None


# -- device idle time by program span  ----------------------------------------

def test_innermost_segments_and_idle_by_span_on_synthetic_events():
    spans = [["rtpu.llm.step", 0.9, 1.7],
             ["rtpu.llm.decode.sample", 1.0, 0.2],
             ["rtpu.llm.decode.prepare", 1.2, 0.1],
             ["rtpu.llm.decode.fetch", 1.6, 0.9],
             ["rtpu.llm.lock_wait.intake", 0.0, 3.0]]   # a caller's thread
    assert P.innermost_segments(spans[:3]) == [
        ["rtpu.llm.step", 0.9, 1.0], ["rtpu.llm.decode.sample", 1.0, 1.2],
        ["rtpu.llm.decode.prepare", 1.2, 1.3], ["rtpu.llm.step", 1.3, 2.6]]
    tr = T.Trace({0: {"programs": [["jit__decode", 0.0, 1.0],
                                   ["jit__decode", 1.5, 1.4]],
                      "ops": [["%a", 0.0, 1.0, ""], ["%b", 1.5, 0.4, ""],
                              ["%c", 2.0, 0.5, ""], ["%d", 2.8, 0.1, ""]],
                      "async_ops": []}}, [])
    by = P.idle_by_span(tr, spans)
    assert by == {"rtpu.llm.decode.sample": pytest.approx(0.2),
                  "rtpu.llm.decode.prepare": pytest.approx(0.1),
                  "rtpu.llm.step": pytest.approx(0.2 + 0.1),
                  "rtpu.llm.decode.fetch": pytest.approx(0.1),
                  P.NO_SPAN: pytest.approx(0.2)}
    assert sum(by.values()) == pytest.approx(
        2.9 - T.busy_and_window(tr)[0])
    assert P.idle_by_span(T.Trace({}, []), spans) == {}


def test_serving_spans_and_idle_on_the_recorded_trace(monkeypatch):
    fx = _fixture("data_pr24_serve.json.gz")
    view = _view(fx, monkeypatch)
    spans = P.program_spans(view)
    kinds = {s[0] for s in spans}
    assert {"rtpu.llm.step", "rtpu.llm.admit", "rtpu.llm.prefill.b256",
            "rtpu.llm.decode.prepare", "rtpu.llm.decode.dispatch",
            "rtpu.llm.decode.fetch", "rtpu.llm.decode.sample"} <= kinds
    by = P.idle_by_span(view["trace"], spans)
    busy, window = T.busy_and_window(view["trace"])
    assert sum(by.values()) == pytest.approx(window - busy, rel=1e-6)
    # between two decode programs the device waits under the host's
    # sampling; while a program runs the host sits in fetch
    assert max(by, key=by.get) == "rtpu.llm.decode.sample"
    for sfx in ("batch", "online"):
        share = READERS[f"idle_unattributed_share.{sfx}"].read(view)
        assert share == pytest.approx(
            100 * by.get(P.NO_SPAN, 0.0) / sum(by.values()))
        assert 0 <= share < 25
        sample = READERS[f"decode_sample_ms.{sfx}"].read(view)
        prepare = READERS[f"decode_prepare_ms.{sfx}"].read(view)
        assert 8 < sample < 16 and 0.05 < prepare < 1.0
        gap = READERS[f"decode_gap_ms.{sfx}"].read(view)
        # the gap PR 23 measured from outside is the host's sample +
        # prepare + dispatch, now named
        assert gap == pytest.approx(
            sample + prepare
            + 1e3 * P.median(P.span_seconds(
                spans, "rtpu.llm.decode.dispatch")), rel=0.25)


def test_pr23_readers_find_the_pinned_program_names(monkeypatch):
    view = _view(_fixture("data_pr24_serve.json.gz"), monkeypatch)
    names = {p[0] for p in view["trace"].devices[0]["programs"]}
    assert "jit__decode" in names and "jit__prefill" in names
    for sfx in ("batch", "online"):
        assert READERS[f"decode_program_ms.{sfx}"].read(view) == \
            pytest.approx(131.5, abs=1.5)
        assert READERS[f"decode_gap_ms.{sfx}"].read(view) > 5
    assert READERS["prefill_program_ms.online"].read(view) > 20


# -- engine counters  ---------------------------------------------------------

def test_stats_delta_and_wait_samples():
    def st(t, n, s, steps):
        return {"t": t, "engine": "e", "decode_steps": steps,
                "prefill_calls": {"64": steps // 10},
                "lock_waits": {"intake": n, "observer": 0},
                "lock_wait_s": {"intake": s, "observer": 0.0},
                "loop_lock_held_s": t * 0.99}
    w = {"stats0": st(100.0, 4, 0.5, 10),
         "samples": [st(100.1, 4, 0.5, 11), st(100.2, 5, 2.5, 12),
                     {"error": "timeout", "t": 100.3},
                     st(100.4, 8, 5.5, 13)],
         "stats1": st(101.0, 8, 5.5, 20)}
    view = {"window": w, "trace": object()}
    d = P.stats_delta(view)
    assert d["decode_steps"] == 10 and d["prefill_calls"] == {"64": 1}
    assert d["lock_waits"]["intake"] == 4
    assert d["lock_wait_s"]["intake"] == pytest.approx(5.0)
    assert d["loop_lock_held_s"] == pytest.approx(0.99)
    assert d["seconds"] == pytest.approx(1.0) and "engine" not in d
    # one wait of 2 s, then three that ended between two samples: 1 s each
    assert P.wait_samples(view, "intake") == pytest.approx([2.0, 1, 1, 1])
    assert P.wait_samples(view, "observer") == []
    assert P.intake_wait_ms_p95(view) == pytest.approx(2000.0)
    assert P.intake_wait_ms_p95(dict(view, trace=None)) is None
    assert P.stats_delta({"window": None}) is None
    # a parent commit's stats() has no such counter
    old = {"window": {"stats0": {"t": 1.0, "running": 1}, "samples": [],
                      "stats1": {"t": 2.0, "running": 2}}, "trace": object()}
    assert P.wait_samples(old, "intake") == []
    assert P.intake_wait_ms_p95(old) is None


def test_intake_wait_on_the_recorded_window(monkeypatch):
    fx = _fixture("data_pr24_serve.json.gz")
    view = _view(fx, monkeypatch)
    waits = P.wait_samples(view, "intake")
    d = P.stats_delta(view)
    assert len(waits) == d["lock_waits"]["intake"] == 32
    assert sum(waits) == pytest.approx(d["lock_wait_s"]["intake"])
    # the engine's lock at intake: a second, not a millisecond, even in
    # this short window (PERF.md has the 50 s windows: p95 14 s and 28 s)
    for sfx in ("batch", "online"):
        p95 = READERS[f"intake_wait_ms_p95.{sfx}"].read(view)
        assert 500 < p95 <= 1e3 * fx["window"]["stats1"][
            "lock_wait_max_s"]["intake"] + 1
    # the scheduler holds the lock through nearly all of the window
    assert d["loop_lock_held_s"] / d["seconds"] > 0.99
    assert d["decode_steps"] == 271
    assert sum(d["prefill_calls"].values()) == 32


# -- the driver's ring  -------------------------------------------------------

def test_start_up_readers_on_a_ring(monkeypatch):
    from ray_tpu.perf import recorder

    if not hasattr(recorder.FlightRecorder, "span"):
        pytest.skip("this program has no span() to read")
    rec = recorder.FlightRecorder(capacity=16, enabled=True)
    monkeypatch.setattr(recorder, "_GLOBAL", rec)
    view = {"trace": object(), "spans": {}}
    names = ("runtime_init_s", "worker_spawn_s", "worker_jax_start_s")
    assert [READERS[n].read(view) for n in names] == [None] * 3

    def span(kind, dur, data=None, label=""):
        rec._append((1.0, kind, label, data, dur, ""), True)

    span("rtpu.core.init.gcs", 0.25)
    span("rtpu.core.init", 1.5)
    span("rtpu.core.worker_spawn", 0.7, {"chip": False})
    span("rtpu.core.worker_spawn", 2.25, {"chip": True, "stamps": {}})
    span("rtpu.core.worker_spawn", 9.0, {"chip": True})    # a later one
    span("rtpu.train.setup_mesh", 12.0,
         {"jax_start_s": [8.0, 8.5], "mesh_s": [0.5, 0.25]})
    for i in range(40):                       # the ring turns over
        rec.record("dispatch.direct", f"t{i}")
    assert [READERS[n].read(view) for n in names] == [1.5, 2.25, 8.75]
    # per-layer metrics are read in the traced run only
    assert [READERS[n].read(dict(view, trace=None)) for n in names] == \
        [None] * 3
    # a worker that reported nothing (an older program's reply)
    rec2 = recorder.FlightRecorder(capacity=16, enabled=True)
    monkeypatch.setattr(recorder, "_GLOBAL", rec2)
    rec2._append((1.0, "rtpu.train.setup_mesh", "", {
        "jax_start_s": [None], "mesh_s": [None]}, 3.0, ""), True)
    assert READERS["worker_jax_start_s"].read(view) is None
