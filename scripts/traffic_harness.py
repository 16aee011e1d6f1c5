#!/usr/bin/env python
"""Production-shaped LLM traffic harness (ROADMAP item 3 /
docs/LLM_SERVE.md "Prefix caching & sessions").

Every serving bench so far drove FIXED synthetic concurrency; real chat
traffic is nothing like that. This harness generates and replays
SESSION traces with the three properties that dominate production load,
through the REAL serve stack (controller, session-aware router, HTTP
proxy, streaming):

- **Bursty arrivals** — a Poisson-burst process: exponential gaps
  between burst epochs, geometric burst sizes, so concurrency spikes
  and idles instead of holding a constant.
- **Heavy-tailed sessions** — turn counts drawn from a bounded Zipf:
  most conversations are one or two turns, a heavy tail runs long.
- **Shared-prefix mix** — a configurable fraction of sessions opens
  with one of a few long common system prompts; every later turn
  re-sends the full conversation so far (context + the model's own
  completion + fresh user tokens), the exact shape the radix prefix
  cache and session affinity are built to exploit.

Reported: goodput (completed streams/s), p50/p99 TTFT and TPOT,
failure/failover/preemption counts, and the scrape-level prefix-cache
hit rate. Runs under ``RAY_TPU_CHAOS`` (use ``--transport handle`` so
streams ride ``resilient_stream`` failover) — the scale story composes
with the fault story.

    python scripts/traffic_harness.py --sessions 40 --replicas 2
    python scripts/traffic_harness.py --transport handle \
        --chaos "seed=7;kill=replica:LLMServer@4" --json /tmp/row.json

Library use: ``make_trace`` / ``replay`` / ``summarize`` are imported
by scripts/traffic_smoke.py and scripts/trace_smoke.py (CI gates).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# engine shape the harness deploys (smoke-sized; bench overrides)
ENGINE_CFG = dict(block_size=8, num_blocks=256, max_batch=8,
                  max_blocks_per_seq=16, prefill_buckets=(16, 32, 64, 128),
                  max_prefill_tokens_per_step=128, prefix_cache=True)


# ---------------------------------------------------------------------------
# trace generation


def _zipf_turns(rng: random.Random, max_turns: int, a: float = 2.0) -> int:
    """Bounded Zipf sample on [1, max_turns]: P(k) ∝ 1/k^a."""
    weights = [1.0 / (k ** a) for k in range(1, max_turns + 1)]
    return rng.choices(range(1, max_turns + 1), weights=weights)[0]


def make_trace(n_sessions: int, seed: int = 0, *, shared_frac: float = 0.6,
               n_prefixes: int = 2, prefix_len: int = 24,
               user_len: int = 4, max_turns: int = 3, max_tokens: int = 6,
               burst_gap_s: float = 0.4, burst_size_p: float = 0.35,
               vocab: int = 500) -> Dict[str, Any]:
    """Deterministic session trace. Each session: an arrival time (from
    the Poisson-burst process), a Zipf turn count, an opening prefix
    (one of ``n_prefixes`` shared system prompts for a ``shared_frac``
    slice of sessions, unique tokens otherwise), and per-turn fresh user
    token chunks. Completions are NOT in the trace — they come from the
    model at replay time (and, being greedy, are reproducible by a
    reference engine)."""
    rng = random.Random(seed)
    prefixes = [[rng.randrange(1, vocab) for _ in range(prefix_len)]
                for _ in range(n_prefixes)]
    sessions = []
    t = 0.0
    remaining = n_sessions
    while remaining > 0:
        t += rng.expovariate(1.0 / burst_gap_s)   # burst epoch
        size = 1
        while rng.random() > burst_size_p and size < remaining:
            size += 1                             # geometric burst size
        for _ in range(min(size, remaining)):
            sid = f"s{n_sessions - remaining:03d}"
            remaining -= 1
            shared = rng.random() < shared_frac
            prefix = (rng.choice(prefixes) if shared else
                      [rng.randrange(1, vocab) for _ in range(prefix_len)])
            turns = _zipf_turns(rng, max_turns)
            sessions.append({
                "sid": sid,
                "arrival_s": round(t + rng.uniform(0.0, 0.05), 4),
                "shared": shared,
                "prefix": list(prefix),
                "chunks": [[rng.randrange(1, vocab)
                            for _ in range(user_len)]
                           for _ in range(turns)],
                "max_tokens": max_tokens,
            })
    return {"seed": seed, "shared_frac": shared_frac,
            "prefix_len": prefix_len, "sessions": sessions}


def reference_completions(trace: Dict[str, Any], model: str = "gpt-tiny",
                          engine_cfg: Optional[dict] = None
                          ) -> Dict[str, List[List[int]]]:
    """Cache-OFF ground truth: a driver-local engine replays every
    session sequentially (greedy, unshared) — the token streams any
    cache/routing configuration must reproduce exactly."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, build_model

    cfg = dict(engine_cfg or ENGINE_CFG)
    cfg["prefix_cache"] = False
    m, params = build_model(model)
    eng = LLMEngine(m, params, EngineConfig(**cfg))
    out: Dict[str, List[List[int]]] = {}
    for s in trace["sessions"]:
        ctx = list(s["prefix"])
        outs = []
        for chunk in s["chunks"]:
            ctx = ctx + chunk
            st = eng.add_request(ctx, max_tokens=s["max_tokens"])
            eng.run_until_idle(timeout=600)
            toks = st.tokens()
            outs.append(toks)
            ctx = ctx + toks
        out[s["sid"]] = outs
    eng.pool.check_leaks()
    return out


# ---------------------------------------------------------------------------
# replay


def _stream_http(base_url: str, deployment: str, sid: str,
                 payload: dict, timeout: float) -> tuple:
    """One streamed turn over the real HTTP proxy (NDJSON framing).
    Returns (tokens, ttft_s, tpot_list_s)."""
    url = f"{base_url}/{deployment}?stream=1&session={sid}"
    body = json.dumps({**payload, "stream": True}).encode()
    headers = {"Content-Type": "application/json"}
    try:  # propagate an active trace like a W3C-instrumented client
        from ray_tpu.util import tracing as _trc

        tctx = _trc.current_context()
        if tctx:
            headers["traceparent"] = _trc.format_traceparent(tctx)
    except Exception:  # noqa: BLE001 — tracing must never fail traffic
        pass
    req = urllib.request.Request(url, body, headers)
    toks: List[int] = []
    tpots: List[float] = []
    t0 = time.perf_counter()
    ttft = None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        last = t0
        for line in r:
            line = line.strip()
            if not line:
                continue
            now = time.perf_counter()
            if ttft is None:
                ttft = now - t0
            else:
                tpots.append(now - last)
            last = now
            toks.append(int(json.loads(line)))
    return toks, (ttft if ttft is not None else time.perf_counter() - t0), \
        tpots


def _stream_handle(handle, sid: str, payload: dict, timeout: float,
                   resilient: bool) -> tuple:
    """One streamed turn through the routing handle — with
    ``resilient`` the stream rides FailoverResponseGenerator and
    survives replica kills (the chaos-mode transport). Returns
    (tokens, ttft_s, tpots, failovers)."""
    from ray_tpu.serve.llm import resilient_stream

    if resilient:
        gen = resilient_stream(handle, payload, session_id=sid)
    else:
        gen = handle.options(stream=True, session_id=sid).remote(
            {**payload, "stream": True})
    toks: List[int] = []
    tpots: List[float] = []
    t0 = time.perf_counter()
    ttft = None
    last = t0
    deadline = t0 + timeout
    while True:
        try:
            tok = gen.next(timeout=max(1.0, deadline - time.perf_counter()))
        except StopIteration:
            break
        now = time.perf_counter()
        if ttft is None:
            ttft = now - t0
        else:
            tpots.append(now - last)
        last = now
        toks.append(int(tok))
    return toks, (ttft if ttft is not None else time.perf_counter() - t0), \
        tpots, getattr(gen, "failovers", 0)


def replay(trace: Dict[str, Any], *, base_url: Optional[str] = None,
           handle=None, deployment: str = "LLMServer",
           transport: str = "http", timeout: float = 240.0,
           time_scale: float = 1.0, tracing: bool = False) -> Dict[str, Any]:
    """Replay the trace against a live deployment: one thread per
    session (spawned at its arrival time), turns sequential within a
    session, the full conversation re-sent each turn. Returns
    {"records": [...], "wall_s": float} — one record per request with
    tokens/ttft/tpots/ok/failovers for summarize().

    ``tracing`` opens a driver-rooted distributed-trace span around
    every turn (W3C-width trace id): the http transport forwards it as
    a ``traceparent`` header, the handle transports ride the routing
    handle's context capture — so each turn becomes ONE stored trace
    spanning client, proxy/router, replica, and engine."""
    records: List[dict] = []
    rec_lock = threading.Lock()
    t0 = time.perf_counter()

    def run_session(s):
        ctx = list(s["prefix"])
        for turn, chunk in enumerate(s["chunks"]):
            ctx = ctx + chunk
            payload = {"tokens": ctx, "max_tokens": s["max_tokens"]}
            rec = {"sid": s["sid"], "turn": turn, "shared": s["shared"],
                   "ok": False, "failovers": 0}

            def one_turn():
                if transport == "http":
                    toks, ttft, tpots = _stream_http(
                        base_url, deployment, s["sid"], payload, timeout)
                elif transport in ("handle", "resilient"):
                    toks, ttft, tpots, fo = _stream_handle(
                        handle, s["sid"], payload, timeout,
                        resilient=transport == "resilient")
                    rec["failovers"] = fo
                else:
                    raise ValueError(f"unknown transport {transport!r}")
                rec.update(ok=len(toks) > 0, tokens=toks, ttft_s=ttft,
                           tpots_s=tpots)
                return toks

            try:
                if tracing:
                    from ray_tpu.util import tracing as trc

                    # pre-activate a W3C-width trace id so the root
                    # span survives round-tripping through a conformant
                    # proxy byte-identical (trace() alone would mint a
                    # narrower internal id)
                    tok = trc.activate((trc.new_trace_id(), None))
                    try:
                        with trc.trace("traffic.turn", session=s["sid"],
                                       turn=turn) as span:
                            rec["trace_id"] = span.trace_id
                            toks = one_turn()
                    finally:
                        trc.deactivate(tok)
                else:
                    toks = one_turn()
                ctx = ctx + toks
            except Exception as e:  # noqa: BLE001 — a failed stream is DATA
                rec["error"] = f"{type(e).__name__}: {e}"
            with rec_lock:
                records.append(rec)
            if not rec["ok"]:
                return            # a dead turn ends the session

    threads = []
    for s in sorted(trace["sessions"], key=lambda x: x["arrival_s"]):
        delay = s["arrival_s"] * time_scale - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=run_session, args=(s,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=timeout)
    return {"records": records, "wall_s": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# reporting


def _pct(vals: List[float], p: float) -> Optional[float]:
    if not vals:
        return None
    vals = sorted(vals)
    i = min(len(vals) - 1, max(0, math.ceil(p / 100.0 * len(vals)) - 1))
    return vals[i]


def summarize(result: Dict[str, Any]) -> Dict[str, Any]:
    """Trace-replay report row (the bench/CI surface): goodput +
    latency tails + failure/failover counts."""
    recs = result["records"]
    ok = [r for r in recs if r.get("ok")]
    ttfts = [r["ttft_s"] for r in ok]
    tpots = [t for r in ok for t in r.get("tpots_s", ())]

    def ms(v):
        return round(v * 1e3, 1) if v is not None else None

    return {
        "traffic_requests": len(recs),
        "traffic_completed": len(ok),
        "traffic_failed": len(recs) - len(ok),
        "traffic_goodput_rps": round(len(ok) / max(result["wall_s"], 1e-6),
                                     2),
        "traffic_wall_s": round(result["wall_s"], 2),
        "traffic_ttft_p50_ms": ms(_pct(ttfts, 50)),
        "traffic_ttft_p99_ms": ms(_pct(ttfts, 99)),
        "traffic_tpot_p50_ms": ms(_pct(tpots, 50)),
        "traffic_tpot_p99_ms": ms(_pct(tpots, 99)),
        "traffic_failovers": sum(r.get("failovers", 0) for r in recs),
        "traffic_tokens": sum(len(r.get("tokens", ())) for r in ok),
    }


def scrape_counter(scrape: str, name: str) -> float:
    """Sum a counter/gauge family across its tag series on a raw
    /metrics scrape body."""
    total = 0.0
    for line in scrape.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            head = line.split(" ")[0]
            if head == name or head.startswith(name + "{"):
                try:
                    total += float(line.rsplit(" ", 1)[1])
                except ValueError:
                    pass
    return total


def scrape_hit_rate(scrape: str) -> float:
    hit = scrape_counter(scrape, "ray_tpu_llm_prefix_hit_tokens")
    miss = scrape_counter(scrape, "ray_tpu_llm_prefix_miss_tokens")
    return hit / (hit + miss) if hit + miss else 0.0


# ---------------------------------------------------------------------------
# live-cluster plumbing shared with scripts/traffic_smoke.py — ONE deploy
# shape and ONE scrape-wait, so the CI gate and the bench row can't drift


def deploy_llm_app(replicas: int, engine_cfg: dict, **deploy_overrides):
    """Deploy the LLMServer app the harness/smoke drive and warm one
    replica's compile caches. Returns the routing handle."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    opts = dict(num_replicas=replicas, max_concurrent_queries=16,
                health_check_timeout_s=120)
    opts.update(deploy_overrides)
    app = serve.deployment(**opts)(LLMServer).bind(
        model="gpt-tiny", engine_config=engine_cfg)
    handle = serve.run(app, timeout=300)
    ray_tpu.get(handle.remote({"tokens": [1, 2, 3], "max_tokens": 2}),
                timeout=300)
    return handle


def wait_for_scrape(needle: str, timeout: float = 30.0) -> str:
    """Start/reuse the head metrics server and poll /metrics until
    ``needle`` appears (the worker->head delta ship is periodic) or the
    timeout lapses. Returns the last scrape body either way."""
    from ray_tpu.util import metrics as metrics_mod

    mhost, mport = metrics_mod.start_metrics_server()
    deadline = time.time() + timeout
    scrape = ""
    while True:
        with urllib.request.urlopen(
                f"http://{mhost}:{mport}/metrics", timeout=10) as r:
            scrape = r.read().decode()
        if needle in scrape or time.time() > deadline:
            return scrape
        time.sleep(0.5)


# ---------------------------------------------------------------------------
# standalone run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sessions", type=int, default=40)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shared-frac", type=float, default=0.6)
    ap.add_argument("--prefix-len", type=int, default=24)
    ap.add_argument("--max-turns", type=int, default=3)
    ap.add_argument("--max-tokens", type=int, default=6)
    ap.add_argument("--transport", choices=("http", "handle", "resilient"),
                    default="http")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="A/B: deploy with the radix cache disabled")
    ap.add_argument("--chaos", default="",
                    help="RAY_TPU_CHAOS spec (wire-level faults; pair "
                         "with --transport resilient)")
    ap.add_argument("--trace", action="store_true",
                    help="open a driver-rooted distributed-trace span "
                         "around every turn (propagated as traceparent "
                         "over http, via the handle context otherwise); "
                         "inspect with `ray_tpu trace --slowest 5`")
    ap.add_argument("--kill-replica-at", type=float, default=0.0,
                    help="kill a live replica N seconds into the replay "
                         "(seeded pick; use --transport resilient so "
                         "streams fail over instead of failing)")
    ap.add_argument("--json", default="", help="write the report row here")
    args = ap.parse_args()

    if args.chaos:
        os.environ["RAY_TPU_CHAOS"] = args.chaos

    import ray_tpu
    from ray_tpu import serve

    cfg = dict(ENGINE_CFG)
    if args.no_prefix_cache:
        cfg["prefix_cache"] = False
    trace = make_trace(args.sessions, args.seed,
                       shared_frac=args.shared_frac,
                       prefix_len=args.prefix_len,
                       max_turns=args.max_turns,
                       max_tokens=args.max_tokens)
    n_reqs = sum(len(s["chunks"]) for s in trace["sessions"])
    print(f"traffic_harness: {args.sessions} sessions / {n_reqs} requests "
          f"({args.shared_frac:.0%} shared-prefix), transport="
          f"{args.transport}, prefix_cache={cfg['prefix_cache']}")

    ray_tpu.init(num_cpus=max(4, args.replicas + 2))
    try:
        handle = deploy_llm_app(args.replicas, cfg)
        kwargs: Dict[str, Any] = dict(transport=args.transport,
                                      handle=handle, tracing=args.trace)
        if args.transport == "http":
            host, port = serve.start_http_proxy(port=0)
            kwargs["base_url"] = f"http://{host}:{port}"
        if args.kill_replica_at > 0:
            def killer():
                import random as _random

                time.sleep(args.kill_replica_at)
                try:
                    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
                    _v, _q, reps = ray_tpu.get(
                        controller.get_replicas.remote("LLMServer"),
                        timeout=10)
                    if reps:
                        victim = _random.Random(args.seed).choice(reps)
                        print(f"traffic_harness: killing replica "
                              f"{victim._actor_id.hex()[:8]} mid-replay")
                        ray_tpu.kill(victim)
                except Exception as e:  # noqa: BLE001
                    print(f"traffic_harness: kill failed: {e}",
                          file=sys.stderr)
            threading.Thread(target=killer, daemon=True).start()
        result = replay(trace, **kwargs)
        row = summarize(result)

        scrape = wait_for_scrape(
            "" if args.no_prefix_cache else "ray_tpu_llm_prefix",
            timeout=20)
        row["prefix_hit_rate"] = round(scrape_hit_rate(scrape), 4)
        row["llm_preemptions"] = int(scrape_counter(
            scrape, "ray_tpu_llm_preemptions_total"))
        row["session_reroutes"] = int(scrape_counter(
            scrape, "ray_tpu_serve_session_reroutes_total"))

        print(json.dumps(row, indent=2))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(row, f)
        if row["traffic_failed"]:
            failed = [r for r in result["records"] if not r.get("ok")]
            print(f"FAILED streams: {failed[:5]}", file=sys.stderr)
            return 1
        return 0
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


if __name__ == "__main__":
    sys.exit(main())
