#!/usr/bin/env python3
"""The paired route of the streamed flash kernels
(``ray_tpu/ops/flash_attention.py``, PR 44) on the chip at
``phi4flash_train_s8192``'s shape, q and k [1, 8192, 40 x 64] against v
[1, 8192, 20 x 128], beside the four-head form the model handed the kernels
before (80 heads of 64: each map twice, half the value lanes zeroed):

    python3 benchmark/scratch/paired_chip.py [--calls 5] [--tiny]

For the full causal call and the window of 512: o, dq, dk, dv of the two
forms against each other (bf16 outputs of float32 accumulators: the worst
difference as a share of the largest entry), the first differential head's
against ``mha_reference`` in float32, and the milliseconds of the forward
kernel and of the two backward kernels, each form (``_flash_fwd`` and
``_flash_bwd`` called as the custom VJP calls them, the host's clock around
``--calls`` calls that end in ``block_until_ready``). ``--tiny`` walks the
same control flow here in interpret mode. One JSON object on stdout. A
script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    from ray_tpu.ops import mha_reference

    s, h, d = (256, 4, 64) if args.tiny else (8192, 40, 64)
    block = 128 if args.tiny else 1024
    windows = (None, 100) if args.tiny else (None, 512)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (1, s, h, d)).astype(bf)
    k = jax.random.normal(ks[1], (1, s, h, d)).astype(bf)
    v = jax.random.normal(ks[2], (1, s, h // 2, 2 * d)).astype(bf)
    do = jax.random.normal(ks[3], (1, s, h, 2 * d)).astype(bf)
    scale = 1.0 / d ** 0.5

    def four(q, k, v):
        halves = jnp.tile(v.reshape(1, s, -1, 1, 2 * d),
                          (1, 1, 1, 2, 1)).reshape(1, s, -1, d)
        return jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), halves

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3

    def worst(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))

    out = {"device": jax.devices()[0].device_kind, "S": s, "score_heads": h,
           "block": block, "calls": args.calls, "cases": []}
    for window in windows:
        kw = dict(causal=True, sm_scale=scale, window=window, block_q=block,
                  block_k=block)

        def grads(fn):
            def scalar(q, k, v):
                y = fn(q, k, v)
                return jnp.sum(y.astype(jnp.float32) * do[:, :, :y.shape[2]])
            return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2)))

        forms = {
            "paired": lambda q, k, v: fa.flash_attention(q, k, v, **kw),
            "four": lambda q, k, v: fa.flash_attention(
                *four(q, k, v), **kw).reshape(1, s, -1, 2 * d),
        }
        got = {n: (jax.jit(f)(q, k, v),) + grads(f)(q, k, v)[1]
               for n, f in forms.items()}
        case = {"window": window, "paired_against_four": {
            n: worst(a, b) for n, a, b in zip(
                ("o", "dq", "dk", "dv"), got["paired"], got["four"])}}
        # the first differential head in float32, no kernel
        q1, k1, v1 = (x[:, :, :n].astype(jnp.float32)
                      for x, n in ((q, 2), (k, 2), (v, 1)))
        ref = lambda q, k, v: mha_reference(                    # noqa: E731
            *four(q, k, v), causal=True, sm_scale=scale,
            window=window).reshape(1, s, -1, 2 * d)
        want = (jax.jit(ref)(q1, k1, v1),) + grads(ref)(q1, k1, v1)[1]
        # dv of the first value needs every head that reads it: one pair
        one = (jax.jit(forms["paired"])(q[:, :, :2], k[:, :, :2], v[:, :, :1]),
               ) + grads(forms["paired"])(q[:, :, :2], k[:, :, :2],
                                          v[:, :, :1])[1]
        case["first_pair_against_float32"] = {
            n: worst(a, b) for n, a, b in zip(("o", "dq", "dk", "dv"), one,
                                              want)}
        # the kernels as the custom VJP calls them, on merged arrays
        merge = lambda x: x.reshape(1, s, -1)                   # noqa: E731
        for name, (qm, km, vm), heads, paired in (
                ("paired", (merge(q), merge(k), merge(v)), h, True),
                ("four", tuple(merge(x) for x in four(q, k, v)), 2 * h,
                 False)):
            fwd = jax.jit(lambda q, k, v, heads=heads, paired=paired:
                          fa._flash_fwd(q, k, v, heads, 2, scale, True, block,
                                        block, window, paired))
            o, lse = fwd(qm, km, vm)
            g = merge(do) if paired else jnp.ones_like(o)
            bwd = jax.jit(lambda q, k, v, o, lse, g, heads=heads,
                          paired=paired: fa._flash_bwd(
                              q, k, v, o, lse, g, heads, 2, scale, True,
                              block, block, window, paired))
            case[name + "_fwd_ms"] = timed(fwd, qm, km, vm)
            case[name + "_bwd_ms"] = timed(bwd, qm, km, vm, o, lse, g)
        out["cases"].append(case)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
