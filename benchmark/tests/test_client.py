"""The load generator's loops, against a fake transport."""
import threading
import time

from benchmark.lib.client import LoadRun, finish_record


def _fake(delay, per_token=0.001):
    def send(url, tokens, max_tokens, tag, timeout):
        t = time.perf_counter()
        time.sleep(delay)
        times = [time.perf_counter() + i * per_token
                 for i in range(max_tokens)]
        return finish_record({"tag": tag, "sent": t, "token_times": times,
                              "tokens": list(range(max_tokens)), "ok": True,
                              "error": None})
    return send


def _sess(due, client=None, turns=1):
    return {"due": due, "client": client, "shared": False, "think_s": 0.0,
            "turns": [{"prompt": [1, 2, 3], "max_tokens": 4}] * turns}


def test_open_loop_sends_on_schedule_whatever_the_server_does():
    run = LoadRun("u", [_sess(0.05 * i) for i in range(10)], threads=10,
                  request_timeout=5, send=_fake(0.2))
    t0 = time.perf_counter()
    run.run_open(t0)
    assert len(run.records) == 10
    late = [r["sent"] - r["due"] for r in run.records]
    assert max(late) < 0.05          # nobody waited for an earlier answer
    assert all(abs(r["due"] - (t0 + 0.05 * i)) < 1e-9 for i, r in
               enumerate(sorted(run.records, key=lambda r: r["due"])))


def test_open_loop_starved_pool_shows_as_lateness():
    run = LoadRun("u", [_sess(0.0) for _ in range(4)], threads=1,
                  request_timeout=5, send=_fake(0.1))
    run.run_open(time.perf_counter())
    late = sorted(r["sent"] - r["due"] for r in run.records)
    assert late[-1] > 0.25           # the fourth waited for three answers


def test_closed_loop_each_client_waits_for_its_answer():
    sessions = [_sess(0.0, client=c) for c in range(3) for _ in range(4)]
    run = LoadRun("u", sessions, threads=3, request_timeout=5,
                  send=_fake(0.05))
    t0 = time.perf_counter()
    run.run_closed(t0 + 0.4)
    n = len(run.records)
    assert 3 * 5 <= n <= 3 * 9       # ~8 per client in 0.4 s at 50 ms
    assert threading.active_count() < 10
    assert time.perf_counter() - t0 < 0.7     # ends with the window


def test_session_turns_resend_the_conversation():
    seen = []

    def send(url, tokens, max_tokens, tag, timeout):
        seen.append(list(tokens))
        return _fake(0.0)(url, tokens, max_tokens, tag, timeout)

    run = LoadRun("u", [_sess(0.0, turns=3)], threads=1, request_timeout=5,
                  send=send)
    run.run_open(time.perf_counter())
    assert [len(s) for s in seen] == [3, 3 + 4 + 3, 3 + 4 + 3 + 4 + 3]
    assert [r["turn"] for r in run.records] == [0, 1, 2]
