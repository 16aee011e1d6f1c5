"""The load generator: streams requests over HTTP from a few threads of
this one process, exactly as a user of ``serve.start_http_proxy`` would
(transport copied from chip_smoke.py ``_stream_request``: NDJSON lines,
no system proxy). Records, per request and on one clock
(``time.perf_counter``): when it was due, when it was sent, when each
token line arrived."""
from __future__ import annotations

import json
import queue
import threading
import time
import urllib.request
from typing import Callable, List, Optional

_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def stream_request(url: str, tokens: List[int], max_tokens: int,
                   tag: str, timeout: float) -> dict:
    """One streamed completion. -> {"sent", "token_times", "tokens",
    "ok", "error"}; never raises: a failed request is data."""
    body = json.dumps({"tokens": tokens, "max_tokens": max_tokens,
                       "stream": True, "bench_tag": tag}).encode()
    req = urllib.request.Request(url, body,
                                 {"Content-Type": "application/json"})
    rec = {"tag": tag, "sent": time.perf_counter(), "token_times": [],
           "tokens": [], "ok": False, "error": None,
           "asked": max_tokens, "prompt_len": len(tokens)}
    try:
        with _OPENER.open(req, timeout=timeout) as r:
            for line in r:
                if not line.strip():
                    continue
                rec["token_times"].append(time.perf_counter())
                rec["tokens"].append(int(json.loads(line)))
        rec["ok"] = len(rec["tokens"]) == max_tokens
        if not rec["ok"]:
            rec["error"] = (f"{len(rec['tokens'])} tokens for "
                            f"{max_tokens} asked")
    except Exception as e:  # noqa: BLE001 - a failed stream is data
        rec["error"] = f"{type(e).__name__}: {e}"
    return finish_record(rec)


def finish_record(rec: dict) -> dict:
    tt = rec["token_times"]
    rec["first"] = tt[0] if tt else None
    rec["last"] = tt[-1] if tt else None
    rec["n_tokens"] = len(tt)
    return rec


class LoadRun:
    """Runs sessions against ``url`` from a fixed pool of worker threads.
    Open loop: a session's first turn is handed to a worker at its due
    time (``t0 + due``); if every worker is busy it waits, and the wait
    shows in sent - due. Closed loop: worker i plays the sessions of
    client i back to back until ``stop_at``. Later turns of a session run
    on the worker that ran the first, due ``think_s`` after the answer."""

    def __init__(self, url: str, sessions: List[dict], threads: int,
                 request_timeout: float,
                 send: Optional[Callable] = None):
        self.url = url
        self.sessions = sessions
        self.threads = threads
        self.timeout = request_timeout
        self.records: List[dict] = []
        self._send = send or stream_request
        self._lock = threading.Lock()
        self._n = 0
        self.stop_at = float("inf")   # closed loop: nothing is sent after

    def _play(self, sess: dict, due: float) -> None:
        ctx: List[int] = []
        for k, turn in enumerate(sess["turns"]):
            if time.perf_counter() >= self.stop_at:
                return
            ctx = ctx + turn["prompt"]
            with self._lock:
                tag = f"r{self._n}"
                self._n += 1
            rec = self._send(self.url, ctx, turn["max_tokens"], tag,
                             self.timeout)
            rec.update(due=due, turn=k, shared=sess["shared"], prompt=ctx)
            with self._lock:
                self.records.append(rec)
            if not rec["ok"]:
                return                       # a dead turn ends the session
            ctx = ctx + rec["tokens"]
            due = rec["last"] + sess["think_s"]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)

    def run_open(self, t0: float) -> None:
        """Blocks until every session has been played to its end."""
        q: "queue.Queue" = queue.Queue()

        def worker():
            while True:
                sess = q.get()
                if sess is None:
                    return
                self._play(sess, t0 + sess["due"])

        pool = [threading.Thread(target=worker, daemon=True,
                                 name=f"bench-client-{i}")
                for i in range(self.threads)]
        for th in pool:
            th.start()
        for sess in self.sessions:
            wait = t0 + sess["due"] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            q.put(sess)
        for _ in pool:
            q.put(None)
        for th in pool:
            th.join()

    def run_closed(self, stop_at: float) -> None:
        """Blocks until ``stop_at`` (on ``time.perf_counter``) has passed
        and each client's request in flight has been answered (its tokens
        after that instant are recorded but fall outside the window)."""
        self.stop_at = stop_at
        by_client: dict = {}
        for s in self.sessions:
            by_client.setdefault(s["client"], []).append(s)

        def worker(mine: List[dict]):
            i = 0
            while time.perf_counter() < self.stop_at:
                self._play(mine[i % len(mine)], time.perf_counter())
                i += 1

        pool = [threading.Thread(target=worker, args=(mine,), daemon=True,
                                 name=f"bench-client-{c}")
                for c, mine in sorted(by_client.items())]
        for th in pool:
            th.start()
        for th in pool:
            th.join()
