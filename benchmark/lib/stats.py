"""Percentile and spread arithmetic (copied from
scripts/traffic_harness.py ``_pct``: nearest rank, no interpolation)."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(vals: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least p % of
    the sample at or below it. None for an empty sample."""
    if not vals:
        return None
    s = sorted(vals)
    i = min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))
    return s[i]


def median(vals: Sequence[float]) -> Optional[float]:
    return statistics.median(vals) if vals else None


def iqr_share(vals: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, as the driver reads a spread."""
    if len(vals) < 2:
        return None
    q = statistics.quantiles(vals, n=4)
    m = statistics.median(vals)
    return (q[2] - q[0]) / m if m else None


def request_latencies(records: Sequence[dict],
                      miss_s: float = math.inf) -> dict:
    """Open-loop timing from per-request client records. A record holds
    ``due``, ``sent``, ``first``, ``last`` (seconds on one clock; first and
    last are None if no token came), ``n_tokens`` and ``ok``.

    * ttft: first token minus DUE time, so the wait a stall imposes on
      later requests counts (choosing-metrics section 5);
    * tpot: (last - first) / (n_tokens - 1) for requests with 2+ tokens;
    * late: sent minus due, how late the generator ran;
    * a failed or unfinished request has no latency and misses any limit:
      it is counted in ``failed`` and enters the tails as ``miss_s`` (the
      client's own time limit: the least such a request can have cost)."""
    ttft, tpot, late = [], [], []
    failed = 0
    for r in records:
        late.append(r["sent"] - r["due"])
        if not r["ok"] or r["first"] is None:
            failed += 1
            ttft.append(miss_s)
            tpot.append(miss_s)
            continue
        ttft.append(r["first"] - r["due"])
        if r["n_tokens"] >= 2:
            tpot.append((r["last"] - r["first"]) / (r["n_tokens"] - 1))
    return {"ttft_s": ttft, "tpot_s": tpot, "late_s": late,
            "attempted": len(records), "failed": failed}


def tokens_in_window(records: Sequence[dict], t0: float, t1: float) -> int:
    """Output tokens whose arrival at a client fell inside [t0, t1)."""
    return sum(1 for r in records for t in r.get("token_times", ())
               if t0 <= t < t1)
