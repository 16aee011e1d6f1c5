#!/usr/bin/env python3
"""Wall time on the chip of the state-space scan kernels alone (forward,
and forward + backward) at the cell's shape, by chunk:

    python3 benchmark/scratch/ssd_blocks.py [--chunks 128,256,512]
        [--calls 10] [--tiny]

Each chunk is compiled, run once, then ``--calls`` times between two
``block_until_ready``: calls of milliseconds, so the host's dispatch (tens
of microseconds) hardly shows. What is timed is ``ssd_scan`` whole: the
two kernels and the plain ``jnp`` around them (dt A and its cumulative sum
inside a chunk, the [B, T, H] -> [B, H, T] turn of dt, D x). One JSON
object on stdout. PR 36; a script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", default="128,256,512")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    ssd = importlib.import_module("ray_tpu.ops.ssd_scan")
    b, t, h, p, n = (1, 512, 4, 64, 128) if args.tiny else \
        (2, 4096, 64, 64, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (b, t, h * p)).astype(bf)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    bm = (jax.random.normal(ks[2], (b, t, n)) * 0.5).astype(bf)
    cm = (jax.random.normal(ks[3], (b, t, n)) * 0.5).astype(bf)
    d = jnp.ones((h,), jnp.float32)

    def timed(fn, *v):
        c = jax.jit(fn).lower(*v).compile()
        jax.block_until_ready(c(*v))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = c(*v)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / args.calls

    res = {"device": jax.devices()[0].device_kind, "shape": [b, t, h, p, n],
           "ssd_ms": {}}
    for chunk in (int(c) for c in args.chunks.split(",")):
        if args.tiny:
            chunk = min(chunk, 256)

        def fwd(x, dt, a, bm, cm, d, chunk=chunk):
            # as the model hands them: merged [B, T, H*P], one group
            return ssd.ssd_scan(x.reshape(b, t, h, p), dt, a, bm[:, :, None],
                                cm[:, :, None], d, chunk=chunk)

        def both(*v):
            return jax.grad(lambda *v: fwd(*v).astype(jnp.float32).sum(),
                            argnums=tuple(range(6)))(*v)
        before = ssd.PATH_COUNTS["kernel"]
        try:
            res["ssd_ms"][str(chunk)] = {
                "fwd": timed(fwd, x, dt, a, bm, cm, d),
                "fwd+bwd": timed(both, x, dt, a, bm, cm, d),
                "kernel_route": ssd.PATH_COUNTS["kernel"] == before + 2}
        except Exception as e:  # noqa: BLE001 - e.g. out of VMEM
            res["ssd_ms"][str(chunk)] = {"refused": str(e)[-300:]}
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
