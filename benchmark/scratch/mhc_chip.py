#!/usr/bin/env python3
"""The hyper-connections' mixing alone, on the chip (PR 45):

    python3 benchmark/scratch/mhc_chip.py [--tiny] [--tokens 8192]

One sublayer's ``hc_coefficients`` + ``hc_pre`` + ``hc_post``
(``ray_tpu/ops/hyper_connection.py``) at the cell's shape (4 streams of
d 3584, 8192 tokens, bfloat16 streams, float32 parameters) with the
sublayer between them the identity: the forward alone and forward +
backward (gradients of the streams and of every parameter), timed over 20
calls, against the bytes ``layer_metrics/mhc_mix_roofline.py`` says a
sublayer needs; and the natural [tokens, n, n] form of the reference on
the same inputs, for the difference. ``--tiny`` walks it on the CPU.
One JSON object on stdout. A script, not a metric."""
import argparse
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tokens", type=int, default=8192)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import deepseek_v3_hc as ref
    from ray_tpu.ops import hyper_connection as hc

    n, d, t = (4, 256, 512) if args.tiny else (4, 3584, args.tokens)
    kw = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6)
    ks = jax.random.split(jax.random.PRNGKey(45), n + 4)
    x = tuple(jax.random.normal(k, (2, t // 2, d), jnp.bfloat16)
              for k in ks[:n])
    shapes = hc.hc_param_shapes(n, d)
    p = {"phi": 0.02 * jax.random.normal(ks[n], shapes["phi"]),
         "gain": jnp.ones(shapes["gain"]),
         "bias": jax.random.normal(ks[n + 1], shapes["bias"]),
         "alpha": jnp.full(shapes["alpha"], 0.01)}
    g = tuple(jax.random.normal(k, xj.shape, jnp.bfloat16)
              for k, xj in zip(jax.random.split(ks[n + 2], n), x))

    def sublayer(x, p):
        pre, post, res = hc.hc_coefficients(x, p, **kw)
        return hc.hc_post(x, hc.hc_pre(x, pre), post, res)

    def loss(x, p):
        return sum(jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32))
                   for a, b in zip(sublayer(x, p), g))

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 20

    fwd, both = jax.jit(sublayer), jax.jit(jax.grad(loss, argnums=(0, 1)))
    unit = t * d * 2
    need = {"fwd": (3 * n + 2) * unit, "fwd_bwd": (8 * n + 5) * unit}
    s_fwd, s_both = timed(fwd, x, p), timed(both, x, p)
    # the reference's natural form, float32, on the same inputs
    with jax.default_matmul_precision("highest"):
        xs = jnp.stack([xj.astype(jnp.float32) for xj in x], 2)
        want = ref.hc_sublayer(xs, p, lambda z: z, eps=1e-6, iters=20,
                               hc_eps=1e-6, clamp=(-30.0, 30.0))
    got = jnp.stack([o.astype(jnp.float32) for o in fwd(x, p)], 2)
    out = {"device": jax.devices()[0].device_kind, "tokens": t, "n": n,
           "d": d, "fwd_ms": 1e3 * s_fwd, "fwd_bwd_ms": 1e3 * s_both,
           "fwd_need_bytes": need["fwd"], "fwd_bwd_need_bytes": need["fwd_bwd"],
           "fwd_GBps_of_need": need["fwd"] / s_fwd / 1e9,
           "fwd_bwd_GBps_of_need": need["fwd_bwd"] / s_both / 1e9,
           "max_abs_diff_to_reference": float(jnp.abs(got - want).max()),
           "reference_abs_max": float(jnp.abs(want).max())}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
