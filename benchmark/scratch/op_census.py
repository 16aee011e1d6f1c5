#!/usr/bin/env python3
"""Device self time of one train step inside ONE named scope, by the last
component of each operation's ``op_name`` (``transpose``, ``reshape``,
``dot_general``, ``pallas_call``, ...), and the scope's largest operations:
the census behind PERF.md's "where the 81 ms of attn go" (PR 29).

    python3 benchmark/scratch/op_census.py <dir or .xplane.pb> \
        --family gpt --scope attn [--top 14]

Same events, self times and scopes as ``span_report.py`` and the
``train_*_ms`` readers; it only splits one scope further.
"""
import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from benchmark.layer_metrics import _program as P
    from benchmark.layer_metrics._common import TRAIN_STEP, complete_runs
    from benchmark.lib import spec
    from benchmark.lib import trace as T

    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--family", required=True)
    ap.add_argument("--scope", required=True)
    ap.add_argument("--top", type=int, default=14)
    args = ap.parse_args()
    path = args.xplane
    if os.path.isdir(path):
        path = T.find_xplane(path)
    tr = T.load_xplane(path, host_prefix=P.PREFIX)
    steps = complete_runs(tr, TRAIN_STEP)
    found = P.device_ops_with_names(path)
    if not steps or not found:
        print("no whole train step, or no op_name, in this trace")
        return 1
    ops, names = found
    scopes = spec.load_family(args.family).SCOPES
    st = T.self_times(ops)
    # whole and cut steps alike ran these operations
    n_steps = sum(p[2] for p in T.programs(tr)) * len(steps) \
        / sum(p[2] for p in steps)
    by = collections.defaultdict(float)
    mine = {}
    for name, sec in st.items():
        op_name = names.get(name, "")
        if P.scope_of(op_name, scopes) == args.scope:
            leaf = op_name.rstrip(":").rsplit("/", 1)[-1]
            by[leaf] += 1e3 * sec / n_steps
            mine[name] = (1e3 * sec / n_steps, leaf)
    print(f"scope {args.scope}: {sum(by.values()):.3f} ms a step "
          f"({n_steps:.2f} steps traced), by the last component of op_name:")
    for leaf, ms in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"  {ms:8.3f} {leaf}")
    print(f"largest operations of {args.scope} (ms a step):")
    for name, (ms, leaf) in sorted(mine.items(),
                                   key=lambda kv: -kv[1][0])[:args.top]:
        print(f"  {ms:8.3f} {name[:60]} [{leaf}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
