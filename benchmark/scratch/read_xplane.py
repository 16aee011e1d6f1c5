#!/usr/bin/env python3
"""A cell's per-layer metrics read again off a kept xplane, by the readers
of any checkout: how two trees' readers are compared on the SAME events.

    python3 benchmark/scratch/read_xplane.py <dir or .xplane.pb> \
        --cell <train cell> [--root <checkout>] [--kernels <reader>]

A traced run leaves its xplane under ``.bench_out/<cell>/trace/``; copy it
before the next run clears it. ``--root`` names the checkout whose
``benchmark/`` reads (default: this one). The view is the one ``run.py``
hands the readers as far as a file can give it: the trace, the cell, the
step's batch and sequence, a TPU v5e as the device; readers of spans,
counters and the driver's ring find nothing and are left out. Prints one
JSON object {metric: value}, values with all their digits. With
``--kernels <reader>`` (a ``<kernel>_roofline`` reader of ``--root``) also
what its ``KERNEL`` pattern and every call to ``tpu_custom_call`` select
on device 0: events, summed seconds, whether they are the same events.
"""
import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--cell", required=True)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    ap.add_argument("--kernels", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    from benchmark import run
    from benchmark.layer_metrics import _program as P
    from benchmark.lib import spec
    from benchmark.lib import trace as T

    path = args.xplane
    if os.path.isdir(path):
        path = T.find_xplane(path)
        if path is None:
            sys.exit(f"no .xplane.pb under {args.xplane}")
    P.trace_path = lambda view: path
    cell = spec.load_cell(args.cell)
    tr = cell["trainer"]
    view = {"trace": T.load_xplane(path), "cell": cell, "spans": {},
            "train": {"batch": int(tr["batch"]), "seq": int(tr["seq"])},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    got = run.read_metrics("layer_metrics",
                           run.declared_metrics(args.cell)["per_layer"], view)
    print(json.dumps({"readers_of": os.path.abspath(args.root),
                      "xplane": path,
                      "metrics": {k: v["value"] for k, v in got.items()}}))
    if args.kernels:
        reader = spec.load_metric_readers("layer_metrics")[args.kernels]
        sel = {k: T.ops_matching(view["trace"], pat) for k, pat in (
            ("pinned_names", reader.KERNEL),
            ("tpu_custom_call", r"custom_call_target=tpu_custom_call"))}
        print(json.dumps({
            k: {"events": len(v), "seconds": sum(o[2] for o in v),
                "names": sorted({o[0].split(".")[0] for o in v})}
            for k, v in sel.items()}
            | {"same_events": sel["pinned_names"] == sel["tpu_custom_call"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
