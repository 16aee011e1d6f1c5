#!/usr/bin/env python3
"""Device time of the two single-block flash kernels alone, by the height of
the causal row bands a program works (PR 31), on the chip:

    python3 benchmark/scratch/band_heights.py <tree> [--parent <tree>]
        [--heights 0,512,256,128] [--out <dir>] [--tiny]

For each shape (the cell's B8 S1024 H16 hd64 and B2 S1024 H8 hd128) and each
height (0 = the whole square, the parent's kernel) the module's
``_band_height`` is replaced for the forward and for the backward call alone,
each compiled once, then run ``--calls`` times under ONE ``jax.profiler``
trace; the kernels' events are found by their pinned names and split in
order of time, ``--calls`` to a configuration. ``--parent`` times that tree's
kernels as they stand beside them (it needs no ``_band_height``). Beside each
time: the largest difference of o / dq / dk / dv from the height-0 kernel.
One JSON object on stdout; ``ms_a_step`` is the time of 24 layers' calls at
the cell's shape.
"""
import argparse
import json
import os
import sys
import time

SHAPES = [(8, 1024, 16, 64), (2, 1024, 8, 128)]     # (B, S, H, hd)
TINY = [(1, 256, 2, 64)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("tree")
    ap.add_argument("--parent")
    ap.add_argument("--heights", default="0,512,256,128")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--out", default="chiprun_out/band_heights")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    scratch = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.dirname(os.path.dirname(scratch)), scratch]
    from benchmark.lib import trace as T
    from kernel_equal import load     # a tree's module, imported anew

    heights = [int(h) for h in args.heights.split(",")]
    mods = [("change", load(os.path.abspath(args.tree))[0])]
    if args.parent:
        mods.append(("parent", load(os.path.abspath(args.parent))[0]))
    bf16 = jnp.bfloat16
    plan = []      # (label, kind, compiled, operands)
    checks = {}
    for b, s, h, d in (TINY if args.tiny else SHAPES):
        keys = jax.random.split(jax.random.PRNGKey(b * s + h), 4)
        q, k, v, g = [jax.random.normal(x, (b, s, h * d), jnp.float32)
                      .astype(bf16) for x in keys]
        scale = 1.0 / d ** 0.5
        base = None
        for tag, mod in mods:
            hpb = mod._heads_per_block(h, d)
            for hb in (heights if tag == "change" else [None]):
                if hb is not None:
                    mod._band_height = (lambda hb: lambda *a: hb)(hb)
                label = (f"B{b}_S{s}_H{h}_hd{d}/{tag}/"
                         f"band{'-' if hb is None else hb}")
                try:
                    fwd = jax.jit(lambda q, k, v: mod._flash_fwd(
                        q, k, v, h, hpb, scale, True, 1024, 1024)
                    ).lower(q, k, v).compile()
                    o, lse = fwd(q, k, v)
                    bwd = jax.jit(lambda q, k, v, o, lse, g: mod._flash_bwd(
                        q, k, v, o, lse, g, h, hpb, scale, True, 1024, 1024)
                    ).lower(q, k, v, o, lse, g).compile()
                except Exception as e:  # noqa: BLE001 — e.g. out of VMEM
                    checks[label] = {"refused": str(e)[-300:]}
                    continue
                grads = bwd(q, k, v, o, lse, g)
                got = [np.asarray(x, np.float32) for x in (o, *grads)]
                if base is None:
                    base = got
                checks[label] = {
                    n: float(np.abs(a - c).max())
                    for n, a, c in zip(("o", "dq", "dk", "dv"), base, got)}
                plan.append((label, "flash_fwd_single", fwd, (q, k, v)))
                plan.append((label, "flash_bwd_fused", bwd,
                             (q, k, v, o, lse, g)))
    os.makedirs(args.out, exist_ok=True)
    wall = {}
    jax.profiler.start_trace(args.out)
    for label, kind, fn, ops in plan:
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*ops)
        jax.block_until_ready(out)
        wall[(label, kind)] = (time.perf_counter() - t0) / args.calls
    jax.profiler.stop_trace()

    res = {"device": jax.devices()[0].device_kind, "calls": args.calls,
           "max_abs_vs_first": checks, "ms_a_call": {}}
    path = T.find_xplane(args.out)
    tr = T.load_xplane(path) if path else None
    for kind in ("flash_fwd_single", "flash_bwd_fused"):
        ev = sorted(T.ops_matching(tr, kind), key=lambda e: e[1]) \
            if tr is not None and tr.devices else []
        mine = [(lab, fn) for lab, kd, fn, _ in plan if kd == kind]
        for i, (label, _) in enumerate(mine):
            row = res["ms_a_call"].setdefault(label, {})
            row[kind + "_wall"] = 1e3 * wall[(label, kind)]
            if len(ev) == len(mine) * args.calls:
                chunk = ev[i * args.calls:(i + 1) * args.calls]
                row[kind] = 1e3 * sum(e[2] for e in chunk) / args.calls
    for label, row in res["ms_a_call"].items():
        if label.startswith("B8_S1024_H16_hd64") and len(row) == 4:
            row["ms_a_step"] = 24 * (row["flash_fwd_single"]
                                     + row["flash_bwd_fused"])
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
