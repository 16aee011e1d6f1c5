#!/usr/bin/env python3
"""The selective-scan kernels (``ray_tpu/ops/selective_scan.py``) on the
chip at the cell's shape: held to the token-by-token float32 recurrence (y
and all six gradients), then timed, forward and forward + backward, by the
channels a program works and the chunk:

    python3 benchmark/scratch/selscan_chip.py [--blocks 256,512,1024]
        [--chunks 128,256] [--calls 5] [--tiny]

What is timed is ``selective_scan`` whole: the two kernels and the plain
``jnp`` around them (B and C spread over the lanes, the sums of their
gradients, D x). ``--tiny`` walks the same control flow here in interpret
mode. One JSON object on stdout. PR 43; a script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--chunks", default="128,256")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    sel = importlib.import_module("ray_tpu.ops.selective_scan")
    b, t, c, n = (1, 64, 256, 16) if args.tiny else (1, 8192, 5120, 16)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (b, t, c)).astype(bf)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, c), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (c, n))
    bm = (jax.random.normal(ks[2], (b, t, n)) * 0.5).astype(bf)
    cm = (jax.random.normal(ks[3], (b, t, n)) * 0.5).astype(bf)
    d = jnp.ones((c,), jnp.float32)
    dy = jax.random.normal(ks[4], (b, t, c))
    v = (x, dt, a, bm, cm, d)
    # the recurrence's backward keeps every token's state: the first
    # quarter of the sequence is what is compared, all of it what is timed
    t_eq = t if args.tiny else t // 4
    v_eq = tuple(u[:, :t_eq] if u.ndim == 3 else u for u in v)

    def both(fn):
        def scalar(*a):
            y = fn(*a)
            return jnp.sum(y.astype(jnp.float32) * dy[:, :y.shape[1]]), y
        return jax.value_and_grad(scalar, argnums=tuple(range(6)),
                                  has_aux=True)

    def timed(fn, v=v):
        comp = jax.jit(fn).lower(*v).compile()
        out = jax.block_until_ready(comp(*v))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            last = comp(*v)
        jax.block_until_ready(last)
        return 1e3 * (time.perf_counter() - t0) / args.calls, out

    def flat(out):
        (_, y), grads = out
        return [np.asarray(u, np.float64) for u in (y,) + tuple(grads)]

    with jax.default_matmul_precision("highest"):
        ref_ms, want = timed(both(sel.selective_scan_reference), v_eq)
    want = flat(want)
    res = {"device": jax.devices()[0].device_kind, "shape": [b, t, c, n],
           "compared_tokens": t_eq, "reference_fwd_bwd_ms": ref_ms,
           "runs": []}
    for w in (int(s) for s in args.blocks.split(",")):
        for chunk in (int(s) for s in args.chunks.split(",")):
            sel._MAX_BLOCK = min(w, c)
            fn = lambda *a: sel.selective_scan(*a, chunk=chunk)  # noqa: E731
            row = {"block": sel._block_width(c), "chunk": min(chunk, t)}
            try:
                row["fwd_ms"], _ = timed(fn)
                row["fwd_bwd_ms"], _ = timed(both(fn))
                _, got = timed(both(fn), v_eq)
                names = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
                row["worst"] = {
                    name: float(np.abs(g - r).max() / max(np.abs(r).max(),
                                                          1e-30))
                    for name, g, r in zip(names, flat(got), want)}
                # the largest entry of each on both sides: a worst of 0
                # beside a scale of 0 or inf would be no agreement at all
                row["largest"] = {
                    name: [float(np.abs(g).max()), float(np.abs(r).max())]
                    for name, g, r in zip(names, flat(got), want)}
            except Exception as e:  # noqa: BLE001 - the refusal is the result
                row["error"] = str(e)[:400]
            res["runs"].append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
