#!/usr/bin/env python3
"""Gated DeltaNet's kernel pair alone (the body for ONE decay a head,
``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` of ``ray_tpu/ops/kda_scan.py``), on
the chip (PR 53):

    python3 benchmark/scratch/gdn_kernel_chip.py [--tiny] [--heads 2,4]
        [--without solve,columns,table,norms] [--ops N] [--skip-plain]

One layer's ``gdn_gated_scan`` at the cell's shape (batch 2 x 8192 tokens,
32 value heads over 16 key heads, every head 128; q, k as a SiLU leaves
them, v and ``a`` bfloat16; A_log and dt_bias as the configuration's
assumed initialisation draws them), as ``gdn_chip.py`` (PR 52) draws it:

* o and the seven gradients of the kernel route against the plain route
  (``l2norm``, the softplus, ``gated_delta_scan``), the largest difference
  as a share of the plain route's largest entry (A_log's and dt_bias's
  also as a share of what their sums add), and the forward and forward +
  backward of both over 5 calls (``--skip-plain`` leaves the plain route
  out);
* ``--heads``: the same timings by value heads a program;
* ``--without``: the same timings with a part of a chunk's work taken out
  (WRONG numbers; the time that goes is that part's): ``solve`` (T = I -
  A), ``columns`` (the rows' [C, 128] columns: zeros in place of the
  product with ones), ``table`` (the [C, C] exp of the differences),
  ``norms`` (q and k taken as they come);
* ``--ops N``: the device operations of a traced forward + backward.

``--tiny`` walks it on the CPU. One JSON object a line on stdout. A
script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import tempfile
import time

NAMES = ("q", "k", "v", "a", "a_log", "dt_bias", "beta")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--heads", default="")
    ap.add_argument("--without", default="")
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--skip-plain", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    kda = importlib.import_module("ray_tpu.ops.kda_scan")

    b, t, hk, hv, d = (2, 256, 2, 4, 128) if args.tiny \
        else (2, 8192, 16, 32, 128)
    r = jax.random.split(jax.random.PRNGKey(52), 8)
    bf = jnp.bfloat16
    q, k = (jax.nn.silu(jax.random.normal(r[i], (b, t, hk * d))).astype(bf)
            for i in range(2))
    v = jax.nn.silu(jax.random.normal(r[2], (b, t, hv * d))).astype(bf)
    a = (0.5 * jax.random.normal(r[3], (b, t, hv))).astype(bf)
    a_log = jnp.log(jax.random.uniform(r[4], (hv,), minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(r[5], (hv,), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    beta = jax.nn.sigmoid(jax.random.normal(r[6], (b, t, hv)))
    do = jax.random.normal(r[7], (b, t, hv * d)).astype(bf)
    scale = d ** -0.5
    inputs = (q, k, v, a, a_log, dt_bias, beta)
    say = lambda **kw: print(json.dumps(kw), flush=True)     # noqa: E731

    def kernel(*x):
        return kda.gdn_gated_scan(*x, scale=scale)

    chosen = kda._route

    def plain(*x):
        kda._route = lambda *shape: "chunked_jnp"
        try:
            return kda.gdn_gated_scan(*x, scale=scale)
        finally:
            kda._route = chosen

    def with_grads(fn):
        def scalar(*x):
            o = fn(*x)
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o
        return jax.jit(jax.value_and_grad(
            scalar, argnums=tuple(range(7)), has_aux=True))

    def timed(fn, n=5):
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / n

    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731
    share = lambda x, y: float(                              # noqa: E731
        jnp.abs(f32(x) - f32(y)).max() / (jnp.abs(f32(y)).max() + 1e-30))
    say(device=jax.devices()[0].device_kind, shape=[b, t, hk, hv, d])
    if not args.skip_plain:
        got = {}
        for name, fn in (("kernel", kernel), ("plain", plain)):
            (_, o), grads = with_grads(fn)(*inputs)
            got[name] = dict(zip(("o",) + NAMES, (o,) + grads))
        x = f32(a) + dt_bias
        da = jnp.abs(f32(got["plain"]["a"]))
        added = {"a_log": float((da * jax.nn.softplus(x) / jax.nn.sigmoid(x)
                                 ).sum((0, 1)).max()),
                 "dt_bias": float(da.sum((0, 1)).max())}
        say(form="kernel", against_plain={
            n: share(got["kernel"][n], got["plain"][n])
            for n in got["plain"]},
            of_what_the_sums_add={n: float(jnp.abs(
                f32(got["kernel"][n]) - f32(got["plain"][n])).max()
                / added[n]) for n in added},
            finite=bool(all(jnp.all(jnp.isfinite(f32(x)))
                            for x in got["kernel"].values())),
            shapes={n: list(x.shape) for n, x in got["kernel"].items()},
            dtypes={n: str(x.dtype) for n, x in got["kernel"].items()},
            routes=dict(kda.PATH_COUNTS))
        say(form="plain", fwd_ms=timed(jax.jit(plain)),
            fwd_bwd_ms=timed(with_grads(plain)))

    def times(label):
        for hpb in [int(x) for x in args.heads.split(",") if x] or [
                kda._MAX_HEADS_PER_BLOCK]:
            kda._MAX_HEADS_PER_BLOCK = hpb
            jax.clear_caches()     # the units' bodies are jit's: traced anew
            t0 = time.perf_counter()
            lowered = with_grads(lambda *x: kernel(*x)).lower(*inputs)
            t1 = time.perf_counter()
            lowered.compile()
            say(form="kernel", what=label, heads_a_program=hpb,
                trace_lower_s=round(t1 - t0, 2),
                compile_s=round(time.perf_counter() - t1, 2),
                fwd_ms=timed(jax.jit(lambda *x: kernel(*x))),
                fwd_bwd_ms=timed(with_grads(lambda *x: kernel(*x))))

    times("whole")
    whole = {n: getattr(kda, n) for n in (
        "_solve", "_columns", "_decay", "_unit", "_unit_bwd")}
    def no_solve(a, r):       # one matrix, or a list of them in lock step
        if isinstance(a, (list, tuple)):
            return [no_solve(x, r) for x in a]
        return kda._same_block(a.shape, 1).astype(jnp.float32) - a

    less = {
        "solve": {"_solve": no_solve},
        "columns": {"_columns": lambda rows: [jnp.zeros(
            (row.shape[1], 128), jnp.float32) for row in rows]},
        "table": {"_decay": lambda x: jnp.ones_like(x)},
        "norms": {"_unit": lambda x, eps: (x, jnp.ones_like(x)),
                  "_unit_bwd": lambda dy, y, inv: dy},
    }
    for part in [p for p in args.without.split(",") if p]:
        for name, fn in less[part].items():
            setattr(kda, name, fn)
        times("without " + part)
        for name, fn in whole.items():
            setattr(kda, name, fn)
    if args.ops:
        from benchmark.lib import trace as T
        jax.clear_caches()
        both = with_grads(kernel)
        jax.block_until_ready(both(*inputs))
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(3):
                out = both(*inputs)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            tr = T.load_xplane(T.find_xplane(tmp))
        if tr.devices:
            ops = tr.devices[min(tr.devices)]["ops"]
            total = T.self_times(ops)
            top = sorted(total.items(), key=lambda kv: -kv[1])[:args.ops]
            say(ops_ms_a_call={name: round(1e3 * s / 3, 3)
                               for name, s in top},
                all_ops_ms_a_call=round(1e3 * sum(total.values()) / 3, 3),
                distinct_ops=len(total))
    return 0


if __name__ == "__main__":
    sys.exit(main())
