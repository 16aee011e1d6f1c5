#!/usr/bin/env python3
"""The largest operations of a train step under each scope of a family,
from a traced run's xplane (PR 45): what ``span_report.py`` prints for
``(unscoped)``, for every scope.

    python3 benchmark/scratch/scope_ops.py .bench_out/<cell> --family <family> [--top 8]

Self times as ``_program.scope_ms_per_step`` charges them (a fusion whole
to the scope of the operation that names it), ms a step over the whole
steps of the trace. A script, not a metric."""
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
    ap.add_argument("--top", type=int, default=8)
    args = ap.parse_args()
    path = args.xplane if args.xplane.endswith(".pb") \
        else T.find_xplane(os.path.join(args.xplane, "trace"))
    scopes = tuple(spec.load_family(args.family).SCOPES)
    steps = complete_runs(T.load_xplane(path), TRAIN_STEP)
    ops, names = P.device_ops_with_names(path)
    spans = [(p[1], p[1] + p[2]) for p in steps]
    inside = [o for o in ops if any(lo <= o[1] < hi for lo, hi in spans)]
    by = collections.defaultdict(list)
    for name, sec in T.self_times(inside).items():
        by[P.scope_of(names.get(name, ""), scopes)].append(
            (1e3 * sec / len(steps), name, names.get(name, "")))
    for scope in scopes + (P.UNSCOPED,):
        rows = sorted(by.get(scope, ()), reverse=True)
        print(f"{scope}: {sum(r[0] for r in rows):.3f} ms a step, "
              f"{len(rows)} operations")
        for ms, name, op_name in rows[:args.top]:
            print(f"  {ms:8.3f} {name} {op_name[-110:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
