#!/usr/bin/env python3
"""Cuts a traced run down to a fixture for ``benchmark/tests``: the first
few programs of device 0 with their operations, the ``rtpu.*`` program
spans of that stretch, the ``op_name`` of every operation kept (the
xplane's event metadata, ``layer_metrics/_program.py``), and, for a
serving run, the window's ``stats()`` samples if a dump of them is given.

    python3 benchmark/scratch/make_fixture.py <dir or .xplane.pb> \
        <out.json.gz> [--first 0] --programs 3 [--window <dump.json>]

(``--window``: a JSON with ``stats0``, ``stats1``, ``samples``, ...)

How ``tests/data_pr24_train.json.gz`` and ``data_pr24_serve.json.gz`` were
made from PR 24's chip runs (PERF.md section 6).
"""
import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KEEP_STATS = ("t", "waiting", "running", "decode_steps", "prefill_calls",
              "extend_calls", "cow_copies", "lock_waits", "lock_wait_s",
              "lock_wait_max_s", "loop_lock_held_s", "total_generated")


def main() -> int:
    from benchmark.layer_metrics._program import PREFIX, op_names
    from benchmark.lib import trace as T

    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--first", type=int, default=0,
                    help="index of the first program of device 0 to keep")
    ap.add_argument("--programs", type=int, default=3,
                    help="how many programs to keep from there")
    ap.add_argument("--window", default="")
    args = ap.parse_args()
    path = args.xplane
    if os.path.isdir(path):
        path = T.find_xplane(path)
    tr = T.load_xplane(path, host_prefix=PREFIX)
    dev = tr.devices[min(tr.devices)]
    progs = dev["programs"][args.first:args.first + args.programs]
    start, end = progs[0][1], progs[-1][1] + progs[-1][2]
    ops = [[o[0], o[1], o[2],
            "custom_call_target=tpu_custom_call"
            if "custom_call_target=tpu_custom_call" in o[3] else ""]
           for o in dev["ops"] if start <= o[1] and o[1] + o[2] <= end]
    host = [h for h in tr.host
            if start - 0.05 <= h[1] and h[1] + h[2] <= end + 0.05]
    kept = {o[0] for o in ops}
    out = {"trace": T.Trace({0: {"programs": progs, "ops": ops,
                                 "async_ops": []}}, host).to_json(),
           "op_names": {k: v for k, v in op_names(path).items()
                        if k in kept}}
    if args.window:
        with open(args.window) as f:
            w = json.load(f)

        def trim(s):
            return {k: s[k] for k in KEEP_STATS if k in s}
        out["window"] = {"stats0": trim(w["stats0"]),
                         "stats1": trim(w["stats1"]),
                         "samples": [trim(s) for s in w["samples"]],
                         "t_window": w["t_window"], "seconds": w["seconds"]}
        out["engine"] = w.get("engine")
    with gzip.open(args.out, "wt") as f:
        json.dump(out, f, separators=(",", ":"))
    print(f"{args.out}: {os.path.getsize(args.out)} bytes; {len(progs)} "
          f"programs, {len(ops)} operations, {len(host)} spans, "
          f"{len(out['op_names'])} op names")
    return 0


if __name__ == "__main__":
    sys.exit(main())
