"""Cluster log aggregation, task attribution, live follow, stacks and
profiles (ref test model: python/ray/tests/test_logging.py +
test_output.py for log_to_driver; `ray stack` / py-spy dump for the
introspection half)."""
import re
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core.log_store import LogStore
from ray_tpu.util import state
from ray_tpu.util.logs import LogBatcher


def _wait_for(pred, timeout=15.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(interval)
    return pred()


# ---------------------------------------------------------------------------
# LogStore / LogBatcher units (no cluster)


def test_log_store_eviction_respects_byte_budget():
    store = LogStore(max_bytes=4000)
    recs = [{"ts": float(i), "node_id": "n", "worker_id": "w", "pid": 1,
             "job_id": "", "task_id": "", "actor_id": "",
             "stream": "stdout", "level": "", "seq": i,
             "line": "x" * 100} for i in range(100)]
    store.append(recs)
    st = store.stats()
    assert st["bytes"] <= 4000
    assert st["evicted_lines"] > 0
    assert st["total_lines"] == 100
    # the survivors are the NEWEST records
    out = store.query(limit=1000)["records"]
    assert out and out[-1]["seq"] == 99
    assert out[0]["seq"] == 100 - len(out)


def test_log_store_query_filters_and_cursor():
    store = LogStore(max_bytes=1 << 20)
    store.append([
        {"ts": 1.0, "node_id": "aa11", "worker_id": "w1", "pid": 1,
         "job_id": "j1", "task_id": "t1", "actor_id": "",
         "stream": "stdout", "level": "", "seq": 0, "line": "one"},
        {"ts": 2.0, "node_id": "bb22", "worker_id": "w2", "pid": 2,
         "job_id": "j1", "task_id": "t2", "actor_id": "ac1",
         "stream": "stderr", "level": "", "seq": 0, "line": "two"},
        {"ts": 3.0, "node_id": "bb22", "worker_id": "w2", "pid": 2,
         "job_id": "j1", "task_id": "", "actor_id": "ac1",
         "stream": "log", "level": "ERROR", "seq": 1, "line": "three"},
    ])
    assert [r["line"] for r in store.query(task_id="t1")["records"]] \
        == ["one"]
    assert [r["line"] for r in store.query(actor_id="ac")["records"]] \
        == ["two", "three"]
    assert [r["line"] for r in store.query(node_id="bb")["records"]] \
        == ["two", "three"]
    assert [r["line"] for r in
            store.query(errors_only=True)["records"]] == ["two", "three"]
    assert [r["line"] for r in
            store.query(stream="stderr")["records"]] == ["two"]
    res = store.query(limit=1000)
    # cursor pages strictly forward
    assert store.query(since=res["cursor"])["records"] == []
    store.append([{"ts": 4.0, "node_id": "aa11", "worker_id": "w1",
                   "pid": 1, "job_id": "j1", "task_id": "t9",
                   "actor_id": "", "stream": "stdout", "level": "",
                   "seq": 1, "line": "four"}])
    newer = store.query(since=res["cursor"])
    assert [r["line"] for r in newer["records"]] == ["four"]


def test_log_store_paging_cursor_never_skips_on_limit():
    """Regression: when `limit` cuts a since-scan short, the returned
    cursor must point at the first UNSCANNED record — a follower paging
    through a burst larger than its limit must see every record."""
    store = LogStore(max_bytes=1 << 20)
    store.append([
        {"ts": float(i), "node_id": "n", "worker_id": "w", "pid": 1,
         "job_id": "", "task_id": "t", "actor_id": "",
         "stream": "stdout", "level": "", "seq": i, "line": f"l{i}"}
        for i in range(250)])
    got, cursor = [], 0
    for _ in range(10):
        res = store.query(task_id="t", since=cursor, limit=100)
        got.extend(r["line"] for r in res["records"])
        cursor = res["cursor"]
        if not res["records"]:
            break
    assert got == [f"l{i}" for i in range(250)], \
        (len(got), got[:5], got[-5:])


def test_log_store_follow_long_polls_until_data():
    store = LogStore(max_bytes=1 << 20)
    cur = store.query(limit=1)["cursor"]
    got = {}

    def follower():
        got["res"] = store.query(since=cur, follow_timeout=10.0)

    t = threading.Thread(target=follower)
    t.start()
    time.sleep(0.3)
    assert t.is_alive(), "follow returned before data arrived"
    store.append([{"ts": 1.0, "node_id": "n", "worker_id": "w", "pid": 1,
                   "job_id": "", "task_id": "", "actor_id": "",
                   "stream": "stdout", "level": "", "seq": 0,
                   "line": "wake"}])
    t.join(timeout=10)
    assert not t.is_alive()
    assert [r["line"] for r in got["res"]["records"]] == ["wake"]
    # and an empty follow times out instead of hanging
    t0 = time.monotonic()
    res = store.query(since=got["res"]["cursor"], follow_timeout=0.3)
    assert res["records"] == [] and time.monotonic() - t0 >= 0.25


def test_log_batcher_rate_limit_drops_with_counter():
    sent = []
    b = LogBatcher(send=sent.append, batch_lines=10_000,
                   flush_interval_s=60.0, rate_lines_per_s=50.0,
                   start_thread=False)
    b.emit("stdout", [f"l{i}" for i in range(500)])
    b.flush()
    assert sent, "nothing flushed"
    payload = sent[0]
    kept = len(payload["recs"])
    assert kept <= 51  # the 1s token-bucket burst
    assert payload.get("dropped", 0) == 500 - kept
    assert b.dropped_total == 500 - kept


def test_log_batcher_seq_monotonic_and_attributed():
    sent = []
    b = LogBatcher(send=sent.append, batch_lines=10_000,
                   flush_interval_s=60.0, rate_lines_per_s=0,
                   task_ids=lambda: ("job1", "task1", "actor1"),
                   start_thread=False)
    b.emit("stdout", ["a", "b"])
    b.emit("stderr", ["c"])
    b.emit("stdout", ["d"])
    b.flush()
    recs = sent[0]["recs"]
    by_stream = {}
    for stream, seq, ts, job, task, actor, level, line in recs:
        assert (job, task, actor) == ("job1", "task1", "actor1")
        by_stream.setdefault(stream, []).append(seq)
    assert by_stream["stdout"] == [0, 1, 2]
    assert by_stream["stderr"] == [0]


def test_driver_mirror_dedups_repeated_lines(capsys):
    from ray_tpu.util.logs import DriverMirror

    m = DriverMirror(enabled=True, color=False)
    m.emit("aabbccdd", 7, "stdout", ["same", "same", "same", "other"])
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln]
    assert lines == [
        "(worker pid=7, node=aabbccdd) same",
        "(worker pid=7, node=aabbccdd) ... last line repeated 2x",
        "(worker pid=7, node=aabbccdd) other",
    ], lines
    # disabled mirror prints nothing
    m2 = DriverMirror(enabled=False, color=False)
    m2.emit("aabbccdd", 7, "stdout", ["x"])
    assert capsys.readouterr().out == ""
    # color mode wraps only the prefix in ANSI
    m3 = DriverMirror(enabled=True, color=True)
    m3.emit("aabbccdd", 7, "stderr", ["tinted"])
    err = capsys.readouterr().err
    assert "\x1b[" in err and err.strip().endswith("tinted")


# ---------------------------------------------------------------------------
# the full path on a live cluster (local node; the remote-node leg is in
# test_logs_multihost below)


@pytest.fixture(scope="module")
def cluster():
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


def test_task_attribution_filters_interleaved_tasks(cluster):
    """Acceptance core: with a noisy unrelated task running, a task-id
    filtered query returns ONLY the target task's lines, correctly
    stamped with {node, worker, task}."""
    @ray_tpu.remote
    def noisy(n):
        for i in range(n):
            print(f"noise-{i}")
            time.sleep(0.005)
        return n

    @ray_tpu.remote
    def target():
        for i in range(5):
            print(f"target-line-{i}")
            time.sleep(0.01)
        return ray_tpu.get_runtime_context().get_node_id()

    noise_ref = noisy.remote(100)
    tref = target.remote()
    nid = ray_tpu.get(tref, timeout=60)
    ray_tpu.get(noise_ref, timeout=60)
    # locate the task id via its stored lines instead of ref internals
    recs = _wait_for(lambda: [
        r for r in state.logs(limit=2000)["records"]
        if r["line"].startswith("target-line-")])
    assert len(recs) == 5, recs
    tids = {r["task_id"] for r in recs}
    assert len(tids) == 1 and "" not in tids
    task_id = tids.pop()
    filtered = state.logs(task_id=task_id, limit=1000)["records"]
    assert [r["line"] for r in filtered] == \
        [f"target-line-{i}" for i in range(5)]
    for r in filtered:
        assert r["node_id"] == nid
        assert r["worker_id"]
        assert r["stream"] == "stdout"


def test_concurrent_writers_do_not_shear_lines(cluster):
    """Many threads printing through one tee concurrently: every stored
    line is exactly one writer's intact line."""
    @ray_tpu.remote
    def storm():
        import threading as th

        def writer(i):
            for j in range(40):
                print(f"w{i:02d}-{j:03d}-" + "z" * 20)

        ts = [th.Thread(target=writer, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        return "storm-done"

    assert ray_tpu.get(storm.remote(), timeout=60) == "storm-done"

    def intact():
        lines = {r["line"] for r in state.logs(limit=10000)["records"]
                 if re.fullmatch(r"w\d{2}-\d{3}-z{20}", r["line"])}
        return lines if len(lines) == 8 * 40 else None

    mine = _wait_for(intact, timeout=20)
    assert mine and len(mine) == 8 * 40, \
        f"expected 320 distinct intact lines, got {len(mine or ())}"


def test_seq_monotonic_per_worker_stream(cluster):
    @ray_tpu.remote
    def burst(tag):
        for i in range(30):
            print(f"seq-{tag}-{i}")
        return 1

    ray_tpu.get([burst.remote(t) for t in ("a", "b")], timeout=60)
    recs = _wait_for(lambda: [
        r for r in state.logs(limit=5000)["records"]
        if r["line"].startswith("seq-")])
    per_ws = {}
    for r in recs:
        per_ws.setdefault((r["worker_id"], r["stream"]), []).append(
            r["seq"])
    assert per_ws
    for key, seqs in per_ws.items():
        assert seqs == sorted(seqs), (key, seqs)
        assert len(set(seqs)) == len(seqs), (key, seqs)


def test_structured_logger_level_and_errors_filter(cluster):
    @ray_tpu.remote
    def speak():
        from ray_tpu.util.logs import get_logger

        # graftcheck: disable=GC003 per-worker lazy handler-install, not driver state
        log = get_logger("ray_tpu.t")
        log.info("structured-info-%d", 1)
        log.warning("structured-warn-%d", 2)
        return 1

    assert ray_tpu.get(speak.remote(), timeout=60) == 1
    recs = _wait_for(lambda: [
        r for r in state.logs(stream="log", limit=2000)["records"]
        if r["line"].startswith("structured-")])
    by_line = {r["line"]: r for r in recs}
    assert by_line["structured-info-1"]["level"] == "INFO"
    assert by_line["structured-warn-2"]["level"] == "WARNING"
    assert by_line["structured-info-1"]["task_id"]
    errs = [r["line"] for r in
            state.logs(errors_only=True, limit=2000)["records"]]
    assert "structured-warn-2" in errs
    assert "structured-info-1" not in errs


def test_stack_report_merges_all_workers_including_blocked_get(cluster):
    """Acceptance: the merged stack report covers every live worker,
    including one deliberately blocked in ray_tpu.get()."""
    @ray_tpu.remote
    def slow_dep():
        time.sleep(8)
        return 1

    @ray_tpu.remote
    def blocked(x):
        return ray_tpu.get(x, timeout=60)  # graftcheck: disable=GC001

    dep = slow_dep.remote()
    ref = blocked.remote([dep])
    time.sleep(1.0)
    t0 = time.monotonic()
    rep = state.stack_report(timeout=5.0)
    elapsed = time.monotonic() - t0
    assert elapsed < 15.0, f"stack merge took {elapsed:.1f}s"
    assert rep["driver"]["threads"]
    live_ids = set()
    for node in cluster.nodes.values():
        for w in node.list_workers():
            if w.channel is not None and not w.channel.closed:
                live_ids.add(w.worker_id.hex())
    reported = {w.get("worker_id") for w in rep["workers"]
                if not w.get("error")}
    assert live_ids and live_ids.issubset(reported), \
        (live_ids, reported)
    # the worker wedged in get() shows the blocking frame
    joined = "\n".join(
        fr for w in rep["workers"] for th in w.get("threads", [])
        for fr in th["frames"])
    assert "get_many" in joined or "fetch_one" in joined, \
        joined[-2000:]
    ray_tpu.get(ref, timeout=60)


def test_profile_worker_collapsed_stacks_catch_hot_fn(cluster):
    @ray_tpu.remote
    def spin_hot():
        t0 = time.time()
        acc = 0
        while time.time() - t0 < 2.5:
            acc += 1
        return acc

    ref = spin_hot.remote()
    time.sleep(0.5)
    rep = state.stack_report(timeout=5.0)
    wid = next((w["worker_id"] for w in rep["workers"]
                if any("spin_hot" in fr for th in w.get("threads", [])
                       for fr in th["frames"])), None)
    assert wid, "spinning worker not found in stack report"
    prof = state.profile_worker(wid, duration_s=0.8, interval_s=0.01)
    assert prof["samples"] > 10
    from ray_tpu.util.introspect import (collapsed_to_text,
                                         profile_to_text)

    collapsed = collapsed_to_text(prof)
    assert "spin_hot" in collapsed
    table = profile_to_text(prof)
    assert "spin_hot" in table and "samples over" in table
    ray_tpu.get(ref, timeout=60)


def test_cli_logs_and_stack(cluster, capsys):
    from ray_tpu.cli import main as cli_main

    @ray_tpu.remote
    def cli_speaker():
        print("cli-visible-line")
        return 1

    ray_tpu.get(cli_speaker.remote(), timeout=60)
    _wait_for(lambda: [r for r in state.logs(limit=2000)["records"]
                       if r["line"] == "cli-visible-line"])
    assert cli_main(["logs", "--limit", "500"]) == 0
    out = capsys.readouterr().out
    assert "cli-visible-line" in out
    assert re.search(r"\[\d\d:\d\d:\d\d\.\d+ \w+ \w+ pid=\d+", out)
    assert cli_main(["logs", "--stream", "stdout", "--limit", "500"]) == 0
    assert "cli-visible-line" in capsys.readouterr().out
    assert cli_main(["stack"]) == 0
    out = capsys.readouterr().out
    assert "=== driver pid=" in out and "worker(s)" in out
    assert "Thread" in out


def test_logs_metrics_counters(cluster):
    from ray_tpu.util import metrics as metrics_mod

    @ray_tpu.remote
    def counted():
        print("metric-counted-line")
        return 1

    ray_tpu.get(counted.remote(), timeout=60)
    _wait_for(lambda: [r for r in state.logs(limit=2000)["records"]
                       if r["line"] == "metric-counted-line"])
    host, port = metrics_mod.start_metrics_server()
    import urllib.request

    with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                timeout=10) as resp:
        body = resp.read().decode()
    assert "ray_tpu_logs_lines_total" in body
    m = re.search(r'ray_tpu_logs_lines_total\{stream="stdout"\} (\d+)',
                  body)
    assert m and int(m.group(1)) >= 1, body[:2000]
    stats = state.log_store_stats()
    assert stats["total_lines"] >= 1 and stats["bytes"] > 0


def test_timeline_span_slices_and_flow_arrows(cluster):
    """Satellite: SPAN events export as chrome-trace slices with ph s/f
    flow links joining parent -> child across processes."""
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def traced_child():
        return 1

    with tracing.trace("span-root") as root:
        assert ray_tpu.get(traced_child.remote(), timeout=60) == 1
    _wait_for(lambda: len(tracing.get_trace(root.trace_id)) >= 2)
    events = state.timeline()
    slices = [e for e in events if e.get("cat") == "span"
              and e.get("ph") == "X"]
    names = {e["name"] for e in slices}
    assert "span-root" in names and "traced_child" in names, names
    child = next(e for e in slices if e["name"] == "traced_child")
    assert child["args"]["trace_id"] == root.trace_id
    flows_s = [e for e in events if e.get("ph") == "s"]
    flows_f = [e for e in events if e.get("ph") == "f"]
    assert flows_s and flows_f
    child_flow_id = child["args"]["span_id"]
    s_ev = next(e for e in flows_s if e["id"] == child_flow_id)
    f_ev = next(e for e in flows_f if e["id"] == child_flow_id)
    # the arrow ends where the child slice begins...
    assert f_ev["pid"] == child["pid"] and f_ev["tid"] == child["tid"]
    assert f_ev["ts"] == child["ts"] and f_ev["bp"] == "e"
    # ...and starts inside the parent's slice (a different process lane
    # when the child ran in a worker)
    parent = next(e for e in slices if e["name"] == "span-root")
    assert s_ev["pid"] == parent["pid"] and s_ev["tid"] == parent["tid"]
    assert parent["ts"] <= s_ev["ts"] <= parent["ts"] + parent["dur"]


def test_spans_dropped_counter_and_single_warning(cluster):
    from ray_tpu.util import tracing

    def bad_export(event):
        raise RuntimeError("exporter down")

    old = tracing.span_export
    tracing.span_export = bad_export
    tracing._warned_reasons.discard("exporter")
    try:
        import warnings as _w

        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            with tracing.trace("drop-one"):
                pass
            with tracing.trace("drop-two"):
                pass
        warned = [x for x in rec
                  if "ray_tpu_spans_dropped_total" in str(x.message)]
        assert len(warned) == 1, [str(x.message) for x in rec]
        with tracing.SPANS_DROPPED._lock:
            n = tracing.SPANS_DROPPED._values.get(("exporter",), 0)
        assert n >= 2
    finally:
        tracing.span_export = old


def test_dashboard_logs_filter_and_stacks_endpoint(cluster):
    import json as _json
    import urllib.request

    from ray_tpu.dashboard import Dashboard

    @ray_tpu.remote
    def dash_speaker():
        print("dash-filter-line")
        return 1

    ray_tpu.get(dash_speaker.remote(), timeout=60)
    recs = _wait_for(lambda: [
        r for r in state.logs(limit=2000)["records"]
        if r["line"] == "dash-filter-line"])
    task_id = recs[0]["task_id"]
    dash = Dashboard(port=0)
    try:
        host, port = dash.address()

        def get(p):
            with urllib.request.urlopen(f"http://{host}:{port}/{p}",
                                        timeout=10) as r:
                return _json.load(r)

        rows = get(f"api/logs?task={task_id}")
        assert rows and all(r["task_id"] == task_id for r in rows)
        assert any(r["line"] == "dash-filter-line" for r in rows)
        rep = get("api/stacks")
        assert rep["driver"]["threads"] and isinstance(
            rep["workers"], list)
        st = get("api/log_store")
        assert st["total_lines"] >= 1
    finally:
        dash.shutdown()


# ---------------------------------------------------------------------------
# graftcheck GC007 satellite


def test_graftcheck_gc007_bare_print():
    from ray_tpu.devtools.graftcheck import check_source

    src = "def f():\n    print('hi')\n"
    founds = check_source(src, path="ray_tpu/core/somelib.py",
                          rules={"GC007"})
    assert [f.rule for f in founds] == ["GC007"]
    # CLI/dashboard/examples/tests are exempt by path
    for path in ("ray_tpu/cli.py", "ray_tpu/dashboard.py",
                 "examples/demo.py", "tests/test_x.py",
                 "ray_tpu/devtools/graftcheck.py"):
        assert check_source(src, path=path, rules={"GC007"}) == [], path
    # line suppression works
    sup = "def f():\n    print('hi')  # graftcheck: disable=GC007\n"
    assert check_source(sup, path="ray_tpu/core/somelib.py",
                        rules={"GC007"}) == []
    # method calls named print (obj.print()) are not flagged
    meth = "def f(o):\n    o.print('hi')\n"
    assert check_source(meth, path="ray_tpu/core/somelib.py",
                        rules={"GC007"}) == []


def test_library_tree_is_gc007_clean():
    """The sweep satellite stays swept: ray_tpu/ library code carries no
    un-suppressed bare print()."""
    import os

    from ray_tpu.devtools.graftcheck import check_file, iter_python_files

    root = os.path.join(os.path.dirname(__file__), "..", "ray_tpu")
    findings = []
    for path in iter_python_files([root]):
        try:
            findings.extend(check_file(path, rules={"GC007"}))
        except SyntaxError:
            pass
    assert findings == [], [f.render() for f in findings]
