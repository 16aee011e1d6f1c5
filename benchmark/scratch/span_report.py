#!/usr/bin/env python3
"""What a traced run's xplane says through the program's own spans and
scopes, for a builder who wants the table behind a per-layer metric:
duration by span kind, device idle time by the innermost span that covers
it, device self time of a train step by named scope and its largest
unscoped operations, and, with a dump of the window's ``stats()`` samples,
the distribution of the waits for the engine's lock.

    python3 benchmark/scratch/span_report.py <dir or .xplane.pb> \
        [--family <family>] [--window <json>]

``--family`` names the file in ``benchmark/families/`` whose ``SCOPES``
split a train step; without it the scope tables are left out.

The numbers of PERF.md section 5 and of PR 24's serving finding came from
this script on the traces of PR 24's chip calls (``pr24_chip_calls.txt``).
"""
import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from benchmark.layer_metrics import _program as P
    from benchmark.layer_metrics._common import TRAIN_STEP, complete_runs
    from benchmark.lib import spec
    from benchmark.lib import trace as T
    from benchmark.lib.stats import median, percentile

    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--family", default="")
    ap.add_argument("--window", default="")
    args = ap.parse_args()
    path = args.xplane
    if os.path.isdir(path):
        path = T.find_xplane(path)
    tr = T.load_xplane(path, host_prefix=P.PREFIX)
    bw = T.busy_and_window(tr)
    if bw:
        print(f"device busy {bw[0]:.6f} s of {bw[1]:.6f} s "
              f"(idle {100 * (1 - bw[0] / bw[1]):.3f} %)")
    progs = collections.defaultdict(list)
    for p in T.programs(tr):
        progs[p[0]].append(p[2])
    for name, d in sorted(progs.items()):
        print(f"  program {name}: x{len(d)} median {1e3 * median(d):.3f} ms")
    by_kind = collections.defaultdict(list)
    for name, _start, dur in tr.host:
        by_kind[name].append(dur)
    print("spans (count, median ms, p95 ms, total ms):")
    for kind, d in sorted(by_kind.items()):
        print(f"  {kind:34s} {len(d):5d} {1e3 * median(d):10.3f} "
              f"{1e3 * percentile(d, 95):10.3f} {1e3 * sum(d):10.3f}")
    idle = P.idle_by_span(tr, tr.host)
    total = sum(idle.values())
    print(f"device idle by innermost span ({1e3 * total:.3f} ms):")
    for kind, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"  {kind:34s} {1e3 * sec:10.3f} ms {100 * sec / total:6.2f} %")
    steps = complete_runs(tr, TRAIN_STEP)
    found = P.device_ops_with_names(path) if steps and args.family else None
    if found:
        P.trace_path = lambda view: path
        by = P.scope_ms_per_step({"trace": tr, "cell": {
            "name": "", "config_file": {"model": {"family": args.family}}}})
        if by:
            print(f"train step by scope (ms, mean of {len(steps)} steps):")
            for scope, ms in by.items():
                print(f"  {scope:12s} {ms:10.3f}")
            print(f"  {'sum':12s} {sum(by.values()):10.3f}")
        ops, names = found
        scopes = spec.load_family(args.family).SCOPES
        st = T.self_times(ops)
        # whole and cut steps alike ran these operations
        n_steps = sum(p[2] for p in T.programs(tr)) * len(steps) \
            / sum(p[2] for p in steps)
        print("largest unscoped operations (ms a step, op_name):")
        for name in sorted((n for n in st if P.scope_of(
                names.get(n, ""), scopes) == P.UNSCOPED),
                key=lambda n: -st[n])[:12]:
            print(f"  {1e3 * st[name] / n_steps:8.3f}"
                  f" {name} {names.get(name, '')[:90]}")
    if args.window:
        with open(args.window) as f:
            w = json.load(f)
        view = {"window": w, "trace": tr}
        d = P.stats_delta(view)
        keys = ("decode_steps", "prefill_calls", "extend_calls",
                "cow_copies", "lock_waits", "lock_wait_s",
                "loop_lock_held_s", "seconds")
        print("stats1 - stats0:", {k: d[k] for k in keys if k in d})
        print("lock_wait_max_s at the end:",
              w["stats1"].get("lock_wait_max_s"))
        for who in ("intake", "observer"):
            ws = P.wait_samples(view, who)
            if ws:
                print(f"  lock wait {who}: count {len(ws)} median "
                      f"{median(ws):.3f} s p95 {percentile(ws, 95):.3f} s "
                      f"max of the interval means {max(ws):.3f} s")
        samples = [s for s in w["samples"] if "running" in s]
        print(f"  stats() samples in the window: {len(samples)}; waiting "
              f"median {median([s['waiting'] for s in samples])} running "
              f"median {median([s['running'] for s in samples])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
