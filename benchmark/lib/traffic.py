"""The one general traffic generator. A traffic mix is a data file under
``traffic/``; this module turns it, a load level and ``--seed`` into the
work of one run. Generator idiom copied from scripts/traffic_harness.py
(``make_trace``: Poisson-burst arrivals, bounded-Zipf turn counts, shared
system prompts), rebuilt on numpy and split in two:

* the SHAPE of the traffic (arrival times, prompt and output lengths,
  turn counts, which sessions share a prefix) is drawn from the file's own
  ``shape_seed`` and is the same in every run of a cell;
* ``--seed`` deals those shapes out in another order and draws the token
  contents (and, elsewhere, the weights).

So two seeds offer the same work at the same instants and differ in which
request carries which length: runs of different seeds are comparable.

Kinds and the keys each understands (all optional except the lengths):

``serve_open``    requests are due on a schedule whatever the server does
                  ``arrivals``: {"burst_mean": 1.0}  geometric bursts of that
                  mean size at Poisson epochs; the mean request rate is the
                  cell's ``load.rate_rps`` either way
``serve_closed``  ``load.clients`` callers, each sends its next request when
                  the last one finished. ``load.requests_per_client``
                  requests are drawn for each caller, who cycles through them
both              ``prompt_len`` / ``output_len``: {"dist": "uniform" |
                  "lognormal" | "fixed", "min", "max", "median", "sigma",
                  "value"}
                  ``sessions``: {"turns_max", "zipf_a", "user_len",
                  "think_s"}: a session re-sends its whole conversation
                  plus fresh user tokens each turn, the next turn being due
                  ``think_s`` after the last answer ended
                  ``shared_prefix``: {"share", "count", "len"}: that share of
                  sessions opens with one of ``count`` system prompts
``train``         ``token_dist``: {"zipf_a"} unigram skew of the token
                  stream (so a falling loss is learnable), batches of
                  [batch, seq] drawn on the host, one per step
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

_MASK = (1 << 63) - 1


def _rng(*keys: int) -> np.random.Generator:
    return np.random.default_rng([int(k) & _MASK for k in keys])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        out = np.full(n, int(spec["value"]))
    elif dist == "uniform":
        out = rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    elif dist == "lognormal":
        out = np.exp(rng.normal(np.log(float(spec["median"])),
                                float(spec["sigma"]), n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = int(spec.get("min", 1))
    hi = int(spec.get("max", 1 << 30))
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def arrival_times(rate_rps: float, seconds: float, burst_mean: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds): burst epochs are Poisson at
    rate / burst_mean, each epoch carries a geometric number of requests
    of mean ``burst_mean`` (1.0 = plain Poisson)."""
    if rate_rps <= 0:
        raise ValueError("an open loop needs load.rate_rps > 0")
    out: List[float] = []
    t = 0.0
    p = 1.0 / max(1.0, float(burst_mean))
    while True:
        t += rng.exponential(burst_mean / rate_rps)
        if t >= seconds:
            break
        out.extend([t] * int(rng.geometric(p)))
    return np.asarray(out)


def _bounded_zipf(rng: np.random.Generator, n: int, hi: int,
                  a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, hi + 1) ** a
    return rng.choice(np.arange(1, hi + 1), size=n, p=w / w.sum())


def make_sessions(traffic: dict, load: dict, seed: int, seconds: float,
                  vocab: int, max_prompt: int) -> List[dict]:
    """-> sessions sorted by due time. A session is {"due": seconds from
    the window's start (0.0 for every closed-loop session), "client": index
    (closed loop) or None, "turns": [{"prompt": [ints] of the FRESH tokens
    of that turn, "max_tokens": n}], "think_s": s, "shared": bool}. Turn k's
    request is everything sent and answered so far plus its fresh tokens."""
    kind = traffic["kind"]
    shape = _rng(int(traffic.get("shape_seed", 0)), 0x5A)
    if kind == "serve_open":
        due = arrival_times(float(load["rate_rps"]), seconds,
                            float(traffic.get("arrivals", {})
                                  .get("burst_mean", 1.0)), shape)
        n = len(due)
        clients = [None] * n
    elif kind == "serve_closed":
        c = int(load["clients"])
        # more than any client can finish in the window; a client cycles
        # through its own list if it ever runs out
        n = c * int(load.get("requests_per_client", 64))
        due = np.zeros(n)
        clients = [i % c for i in range(n)]
    else:
        raise ValueError(f"{kind!r} is not a serving kind")
    ses = traffic.get("sessions", {})
    turns_max = int(ses.get("turns_max", 1))
    turns = (_bounded_zipf(shape, n, turns_max, float(ses.get("zipf_a", 2.0)))
             if turns_max > 1 else np.ones(n, np.int64))
    first_len = draw_lengths(traffic["prompt_len"], n, shape)
    out_len = draw_lengths(traffic["output_len"], int(turns.sum()), shape)
    user_len = draw_lengths(ses.get("user_len", {"value": 16}),
                            int(turns.sum()), shape)
    sp = traffic.get("shared_prefix", {})
    shared = shape.random(n) < float(sp.get("share", 0.0))
    which = shape.integers(0, max(1, int(sp.get("count", 1))), n)
    # --seed: another order of the same shapes, and the token contents
    run = _rng(seed, 0xC3)
    order = run.permutation(n)
    prefixes = [run.integers(0, vocab, int(sp.get("len", 0))).tolist()
                for _ in range(int(sp.get("count", 0)))]
    offs = np.concatenate([[0], np.cumsum(turns)])
    sessions = []
    for slot in range(n):
        src = int(order[slot])
        body = run.integers(0, vocab, int(first_len[src])).tolist()
        if shared[src] and prefixes:
            pre = prefixes[int(which[src])]
            body = pre + body[:max(1, int(first_len[src]) - len(pre))]
        tl = []
        ctx = 0
        for k in range(int(turns[src])):
            j = int(offs[src]) + k
            fresh = body if k == 0 else run.integers(
                0, vocab, int(user_len[j])).tolist()
            n_out = int(out_len[j])
            if ctx + len(fresh) > max_prompt:
                break                 # the conversation outgrew the engine
            tl.append({"prompt": fresh, "max_tokens": n_out})
            ctx += len(fresh) + n_out
        if not tl:
            tl = [{"prompt": body[:max_prompt],
                   "max_tokens": int(out_len[int(offs[src])])}]
        sessions.append({"due": float(due[slot]), "client": clients[slot],
                         "turns": tl, "shared": bool(shared[src]),
                         "think_s": float(ses.get("think_s", 0.0))})
    sessions.sort(key=lambda s: s["due"])
    return sessions


class TokenFeed:
    """Training batches drawn on the host, one per step, from ``--seed``:
    a Zipf unigram over the vocabulary whose ranks are shuffled by the
    seed. The stream is endless and step i's batch depends only on
    (seed, i), so the reference can draw batch 0 again."""

    def __init__(self, traffic: dict, seed: int, vocab: int, batch: int,
                 seq: int):
        a = float(traffic.get("token_dist", {}).get("zipf_a", 1.0))
        w = 1.0 / np.arange(1, vocab + 1) ** a
        self._cdf = np.cumsum(w / w.sum())
        self._rank_to_token = _rng(seed, 0x7E).permutation(vocab)
        self._seed, self._shape = seed, (batch, seq)
        self.vocab = vocab

    def batch(self, step: int) -> np.ndarray:
        u = _rng(self._seed, 0xB0, step).random(self._shape)
        ranks = np.minimum(np.searchsorted(self._cdf, u), self.vocab - 1)
        return self._rank_to_token[ranks].astype(np.int32)


def describe(sessions: List[dict]) -> Dict[str, float]:
    """What a run offered, for the result line's own record."""
    p = [len(t["prompt"]) for s in sessions for t in s["turns"][:1]]
    o = [t["max_tokens"] for s in sessions for t in s["turns"]]
    return {"sessions": len(sessions), "requests": len(o),
            "prompt_tokens_first_turn_median": float(np.median(p)) if p else 0,
            "prompt_tokens_first_turn_max": int(max(p)) if p else 0,
            "output_tokens_total": int(sum(o))}
