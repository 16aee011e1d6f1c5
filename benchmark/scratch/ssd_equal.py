#!/usr/bin/env python3
"""``ops/ssd_scan.py`` (the kernel route, bf16 arguments as the model hands
them) against Mamba-2's recurrence in float32, one token at a time and one
head at a time, on the chip, at the cell's shape: y and the gradients of
all six arguments (x, dt, A, B, C, D) of sum(y * w), each as the rms and
the largest distance beside the reference's own rms. PR 36.

    python3 benchmark/scratch/ssd_equal.py [--chunk 256] [--tiny]

One JSON object on stdout. ``--tiny`` is the CPU rehearsal's size.
"""
import argparse
import importlib
import json
import os
import sys

SHAPE = (2, 4096, 64, 64, 128)      # (B, T, H, P, N): granite4h_train_s4096
TINY = (1, 256, 4, 64, 128)
NAMES = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")


def reference(x, dt, a, bm, cm, d, w):
    """y and the six gradients in float32: the recurrence as a scan over
    tokens, one head a call of ``lax.map`` (the state is [B, P, N]), B and
    C's gradients summed over the heads of their one group."""
    import jax
    import jax.numpy as jnp

    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    x, bm, cm, w = f32(x), f32(bm)[:, :, 0], f32(cm)[:, :, 0], f32(w)

    def head(xh, dth, ah, dh, bm, cm):       # [B,T,P] [B,T] [] [] [B,T,N] x2
        def token(state, tok):
            xt, dtt, bt, ct = tok
            state = jnp.exp(dtt * ah)[:, None, None] * state \
                + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
            return state, jnp.sum(state * ct[:, None, :], -1) + dh * xt

        _, y = jax.lax.scan(
            token, jnp.zeros(xh.shape[:1] + (xh.shape[-1], bm.shape[-1])),
            tuple(jnp.moveaxis(v, 1, 0) for v in (xh, dth, bm, cm)))
        return jnp.moveaxis(y, 0, 1)

    def one(args):
        xh, dth, ah, dh, wh = args
        y, vjp = jax.vjp(head, xh, dth, ah, dh, bm, cm)
        return (y, *vjp(wh))

    by_head = lambda v: jnp.moveaxis(v, 2, 0)  # noqa: E731
    y, dx, ddt, da, dd, db, dc = jax.lax.map(
        one, (by_head(x), by_head(dt), a, d, by_head(w)))
    back = lambda v: jnp.moveaxis(v, 0, 2)  # noqa: E731
    return (back(y), back(dx), back(ddt), da, db.sum(0)[:, :, None],
            dc.sum(0)[:, :, None], dd)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    ssd = importlib.import_module("ray_tpu.ops.ssd_scan")
    b, t, h, p, n = TINY if args.tiny else SHAPE
    chunk = min(args.chunk, 128) if args.tiny else args.chunk
    ks = jax.random.split(jax.random.PRNGKey(36), 6)
    bf = jnp.bfloat16
    # the model's ranges: dt log-uniform in [0.001, 0.1], A = -(1..H), so
    # the decays exp(dt A) run from 0.999 a token down to 0.002
    x = jax.random.normal(ks[0], (b, t, h, p)).astype(bf)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, t, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32) * 64 / h
    bm = (jax.random.normal(ks[2], (b, t, 1, n)) * 0.5).astype(bf)
    cm = (jax.random.normal(ks[3], (b, t, 1, n)) * 0.5).astype(bf)
    d = jnp.ones((h,), jnp.float32)
    w = jax.random.normal(ks[4], (b, t, h, p)).astype(bf)

    def kernels(x, dt, a, bm, cm, d):
        y, vjp = jax.vjp(lambda *v: ssd.ssd_scan(*v, chunk=chunk),
                         x, dt, a, bm, cm, d)
        return (y, *vjp(w))

    before = ssd.PATH_COUNTS["kernel"]
    got = jax.jit(kernels)(x, dt, a, bm, cm, d)
    assert ssd.PATH_COUNTS["kernel"] == before + 1, dict(ssd.PATH_COUNTS)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(x, dt, a, bm, cm, d, w)
    # the kernels' order is (y, dx, ddt, dA, dB, dC, dD) too
    out = {"device": jax.devices()[0].device_kind, "chunk": chunk,
           "shape": [b, t, h, p, n], "route": "kernel"}
    for name, g, r in zip(NAMES, got, want):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        out[name] = {"rms_distance": float(np.sqrt(np.mean((g - r) ** 2))),
                     "largest_distance": float(np.abs(g - r).max()),
                     "reference_rms": float(np.sqrt(np.mean(r ** 2))),
                     "reference_largest": float(np.abs(r).max())}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
