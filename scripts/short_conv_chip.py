#!/usr/bin/env python3
"""The gated short convolution alone, on the chip (PR 64).

    chiprun -- python3 scripts/short_conv_chip.py      times, on the chip
    python3 scripts/short_conv_chip.py --tiny          walks it here

``ops.short_conv.in_proj_short_conv(b | c | x, w) = c * conv3(b * x)`` at
``lfm2moe_train_s8192``'s shape (the ONE array [2, 8192, 6144], bf16): both
routes, forward and forward + backward, the median of 5 timings of 10
calls; the kernel by the forward's block of rows and channels and by the
rows of the backward's block (the module's constants, set here); a copy
pass of one chunk beside them as the yardstick (the forward needs 4 such
passes' bytes, the backward 7); every route's outputs against the float32
sums written out.
The last line is JSON: what ``routes_measured`` of the configuration's
file holds."""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

TINY = "--tiny" in sys.argv
if TINY:
    os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402

from ray_tpu.ops import short_conv                           # noqa: E402

SHAPE = (1, 64, 256) if TINY else (2, 8192, 2048)
CUTS = [(32, 128)] if TINY else [(512, 512), (1024, 512), (512, 1024),
                                 (256, 2048), (1024, 1024), (2048, 512)]


def written_out(b, c, x, w):
    b, c, x, w = (v.astype(jnp.float32) for v in (b, c, x, w))
    z = b * x
    zero = jnp.zeros_like(z[:, :1])
    z1 = jnp.concatenate([zero, z[:, :-1]], 1)
    z2 = jnp.concatenate([zero, zero, z[:, :-2]], 1)
    return c * (w[0] * z2 + w[1] * z1 + w[2] * z)


# rows of a block of the backward
BWD_ROWS = [16] if TINY else [64, 128, 256, 512]


def _value_and_vjp(f, dy, *args):
    y, vjp = jax.vjp(f, *args)
    return (y,) + tuple(vjp(dy))


def timed(f, *args) -> float:
    """Milliseconds a call: the median of 5 timings of 10 calls."""
    jax.block_until_ready(f(*args))
    took = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10):
            out = f(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t0) / 10)
    return 1e3 * statistics.median(took)


def main() -> None:
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, c, x = (jax.random.normal(k, SHAPE, jnp.float32).astype(jnp.bfloat16)
               for k in ks[:3])
    w = jax.random.uniform(ks[3], (3, SHAPE[2]), jnp.float32, -0.58, 0.58)
    dy = jax.random.normal(ks[4], SHAPE, jnp.float32).astype(jnp.bfloat16)
    dev = jax.devices()[0]
    out = {"device": dev.device_kind, "shape": list(SHAPE),
           "copy_ms": timed(jax.jit(lambda v: v + 1), x)}

    bcx = jnp.concatenate([b, c, x], -1)
    want = jax.jit(lambda *a: (written_out(*a[:4]),) + jax.vjp(
        written_out, *a[:4])[1](a[4].astype(jnp.float32)))(b, c, x, w, dy)

    def measure(route):
        """W_in's one output in, one cotangent out. y is a result too: the
        forward keeps nothing for the backward, so with the gradients alone
        asked for its kernel would be dead code."""
        f = lambda bcx, w: short_conv._routed(bcx, w, route)   # noqa: E731
        fwd_bwd = jax.jit(lambda bcx, w, dy: _value_and_vjp(f, dy, bcx, w))
        y, dbcx, dw = fwd_bwd(bcx, w, dy)
        got = (y,) + tuple(jnp.split(dbcx, 3, axis=-1)) + (dw,)
        return {"fwd_ms": timed(jax.jit(f), bcx, w),
                "fwd_bwd_ms": timed(fwd_bwd, bcx, w, dy),
                "worst_rel_to_written_out": {
                    n: float(jnp.abs(g.astype(jnp.float32) - r).max()
                             / jnp.abs(r).max())
                    for n, g, r in zip(("y", "db", "dc", "dx", "dw"), got,
                                       want)}}

    out["plain"] = measure("plain")
    print("plain", json.dumps(out["plain"]), flush=True)
    own = (short_conv.BLOCK_T, short_conv.BLOCK_D, short_conv.BWD_BLOCK_T)
    for cut in CUTS:
        short_conv.BLOCK_T, short_conv.BLOCK_D = cut
        name = f"kernel_fwd_{cut[0]}x{cut[1]}"
        out[name] = measure("kernel")
        print(name, json.dumps(out[name]), flush=True)
    short_conv.BLOCK_T, short_conv.BLOCK_D = own[:2]
    for rows in BWD_ROWS:
        short_conv.BWD_BLOCK_T = rows
        name = f"kernel_bwd_rows{rows}"
        out[name] = measure("kernel")
        print(name, json.dumps(out[name]), flush=True)
    short_conv.BWD_BLOCK_T = own[2]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
