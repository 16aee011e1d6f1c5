#!/usr/bin/env python3
"""What the short convolution costs on the chip, alone (PR 55).

    chiprun -- python3 scripts/conv_chip.py            times, on the chip
    python3 scripts/conv_chip.py --describe            fusions, no chip

``silu(causal_conv1d(x, w[, b]))`` at the three cells' shapes, bf16: a plain
copy pass, the convolution by number of taps (every shifted slice of the
one operand is a read of the whole array from HBM: +0.33 to +0.43 ms a tap
at [2, 8192, 8192], PR 55), the forward, autodiff's backward and
``causal_conv1d_silu``'s hand-written one with its two fusions apart. A
script, not a metric: the yardstick for a kernel that reads a block once
(ROADMAP A16(2)) is 7 passes where these forms make 24 a convolution and
step."""
from __future__ import annotations

import json
import os
import sys
import time

DESCRIBE = "--describe" in sys.argv
if DESCRIBE:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from ray_tpu.ops.layers import causal_conv1d, causal_conv1d_silu  # noqa: E402

F32 = jnp.float32
# kimilinear's q, k, v; qwen3next's q | k | v; granite4h's x | B | C (bias)
SHAPES = [((2, 8192, 4096), False), ((2, 8192, 8192), False),
          ((2, 4096, 4352), True)]


def _taps(n):
    """The convolution with its first ``n`` taps alone (n - 1 shifted
    slices and the aligned one)."""
    def f(x, w, b, dy):
        t = x.shape[1]
        xp = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
        return sum(xp[:, k:k + t].astype(F32) * w[k]
                   for k in range(n)).astype(x.dtype)
    return f


def _vjp_of(f):
    def g(x, w, b, dy):
        args = (x, w) if b is None else (x, w, b)
        return jax.vjp(f, *args)[1](dy)
    return g


def _dpre(x, w, b, dy):
    pre = causal_conv1d(x, w, b).astype(F32)
    s = jax.nn.sigmoid(pre)
    return (dy.astype(F32) * s * (1.0 + pre * (1.0 - s))).astype(x.dtype)


def _dx(x, w, b, dy):                      # dy stands for dpre
    k, t = w.shape[0], x.shape[1]
    dp = jnp.pad(dy, ((0, 0), (0, k - 1), (0, 0)))
    return sum(dp[:, k - 1 - j:k - 1 - j + t].astype(F32) * w[j]
               for j in range(k)).astype(x.dtype)


VARIANTS = {
    "copy_pass": lambda x, w, b, dy: (x.astype(F32) * 1.5).astype(x.dtype),
    **{f"conv_{n}_taps": _taps(n) for n in (1, 2, 3, 4)},
    "forward": lambda x, w, b, dy: jax.nn.silu(causal_conv1d(x, w, b)),
    "backward_autodiff": _vjp_of(
        lambda x, *p: jax.nn.silu(causal_conv1d(x, *p))),
    "backward_hand": _vjp_of(causal_conv1d_silu),
    "hand_dpre_alone": _dpre,
    "hand_dx_alone": _dx,
}


def _args(shape, bias, make):
    x, w = make(shape, jnp.bfloat16), make((4, shape[2]), F32)
    return x, w, (make((shape[2],), F32) if bias else None), x


def describe() -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    for shape, bias in SHAPES:
        args = _args(shape, bias, lambda s, d: jax.ShapeDtypeStruct(
            s, d, sharding=one))
        full = "bf16[%d,%d,%d]" % shape
        for name, fn in VARIANTS.items():
            c = jax.jit(fn).lower(*args).compile()
            entry = c.as_text().split("\nENTRY ")[1]
            writes = [line.split(" fusion(")[0].partition(" = ")[2].count(full)
                      for line in entry.splitlines() if " fusion(" in line]
            print(json.dumps({
                "shape": shape, "variant": name,
                "full_size_arrays_written_by_fusion": [n for n in writes if n],
                "temp_bytes": c.memory_analysis().temp_size_in_bytes}))


def main() -> None:
    for shape, bias in SHAPES:
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
        args = _args(shape, bias, lambda s, d: (
            0.5 * jax.random.normal(next(keys), s, F32)).astype(d))
        for name, fn in VARIANTS.items():
            run = jax.jit(fn)
            jax.block_until_ready(run(*args))
            took = []
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(10):
                    out = run(*args)
                jax.block_until_ready(out)
                took.append((time.perf_counter() - t0) / 10)
            print(json.dumps({"shape": shape, "variant": name,
                              "ms": round(1e3 * float(np.median(took)), 3),
                              "device": jax.devices()[0].device_kind}),
                  flush=True)


if __name__ == "__main__":
    describe() if DESCRIBE else main()
