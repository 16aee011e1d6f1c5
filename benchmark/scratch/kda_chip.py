#!/usr/bin/env python3
"""The delta-rule scan alone, on the chip (PR 49):

    python3 benchmark/scratch/kda_chip.py [--tiny] [--chunks 64,128] [--ops 40]

One KDA layer's ``kda_scan`` (``ray_tpu/ops/kda_scan.py``) at the cell's
shape (batch 2 x 8192 tokens, 32 heads of 128 x 128, bfloat16 q, k, v,
float32 gates, decays as the configuration's ``assumed`` initialisation
makes them): the forward alone and forward + backward (the gradient of all
five inputs), timed over 5 calls each, against the bytes and operations
``layer_metrics/kda_scan_roofline.py`` says a layer needs, for each chunk
of ``--chunks`` (how the scan is cut); the difference to the token-by-token
reference on the first 1024 tokens; and, with ``--ops N``, the N device
operations of the forward + backward that take the most time in a
profiler trace of 3 calls, same-named operations (a loop's turns) summed.
A train step runs a layer's forward once in the forward sweep and forward +
backward in the backward sweep. ``--tiny`` walks it on the CPU. One JSON
object a line on stdout. A script, not a metric."""
import argparse
import collections
import json
import os
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--chunks", default="64")
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import kimi_linear as ref
    kda = importlib.import_module("ray_tpu.ops.kda_scan")

    b, t, h, d = (2, 256, 2, 128) if args.tiny else (2, 8192, 32, 128)
    r = jax.random.split(jax.random.PRNGKey(49), 8)
    shape = (b, t, h * d)
    unit = lambda x: ref.l2norm(x.reshape(b, t, h, d)).reshape(shape)  # noqa: E731
    q = unit(jax.random.normal(r[0], shape)).astype(jnp.bfloat16)
    k = unit(jax.random.normal(r[1], shape)).astype(jnp.bfloat16)
    v = jax.random.normal(r[2], shape).astype(jnp.bfloat16)
    a = jax.random.uniform(r[3], (h,), minval=1.0, maxval=16.0)
    step = jnp.exp(jax.random.uniform(r[4], (b, t, h, d), minval=np.log(1e-3),
                                      maxval=np.log(0.1)))
    g = (-a[:, None] * step).reshape(shape)
    beta = jax.nn.sigmoid(jax.random.normal(r[5], (b, t, h)))
    do = jax.random.normal(r[6], shape).astype(jnp.bfloat16)
    scale = d ** -0.5
    inputs = (q, k, v, g, beta)

    def timed(fn, n=5):
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    # what a layer needs (the roofline reader's count, one layer: forward,
    # and forward + backward without the rematerialised forward)
    fwd_ops = 2 * 32 * (3 * d + 2 * d) + 6 * d * d
    states = d * d * 4 // 64
    reads = 3 * d * 2 + d * 4 + 4
    need = {"fwd_bytes": b * t * h * (reads + d * 2 + states),
            "fwd_bwd_bytes": b * t * h * (2 * (reads + d * 2 + states)
                                          + 3 * d * 2 + d * 4 + 4),
            "fwd_flops": b * t * h * fwd_ops}
    for chunk in [int(c) for c in args.chunks.split(",")]:
        fwd = jax.jit(lambda *x, c=chunk: kda.kda_scan(*x, scale=scale,
                                                       chunk=c))
        both = jax.jit(jax.grad(lambda *x, c=chunk: jnp.sum(
            kda.kda_scan(*x, scale=scale, chunk=c).astype(jnp.float32)
            * do.astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))
        s_fwd, s_both = timed(fwd), timed(both)
        print(json.dumps({
            "device": jax.devices()[0].device_kind, "shape": [b, t, h, d],
            "chunk": chunk, "fwd_ms": 1e3 * s_fwd, "fwd_bwd_ms": 1e3 * s_both,
            "a_step_of_4_layers_ms": 4e3 * (s_fwd + s_both),
            "fwd_need_ms_hbm": 1e3 * need["fwd_bytes"] / 819e9,
            "fwd_bwd_need_ms_hbm": 1e3 * need["fwd_bwd_bytes"] / 819e9,
            "fwd_need_ms_mxu": 1e3 * need["fwd_flops"] / 197e12}),
            flush=True)
    # against the recurrence, the first tokens (the state starts from zero)
    n = min(t, 1024)
    cut = lambda x: x[:, :n]                                 # noqa: E731
    per_head = lambda x: x.reshape(b, n, h, -1)              # noqa: E731
    want = ref.delta_rule(*(per_head(cut(x).astype(jnp.float32))
                            for x in (q, k, v, g)), cut(beta))
    got = kda.kda_scan(*(cut(x) for x in inputs), scale=scale, chunk=chunk)
    print(json.dumps({
        "tokens_compared": n,
        "max_abs_diff_to_recurrence": float(jnp.abs(
            got.astype(jnp.float32) - want.reshape(b, n, -1)).max()),
        "mean_abs_diff": float(jnp.abs(
            got.astype(jnp.float32) - want.reshape(b, n, -1)).mean()),
        "got_abs_max": float(jnp.abs(got.astype(jnp.float32)).max()),
        "recurrence_abs_max": float(jnp.abs(want).max())}), flush=True)
    if args.ops:
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(3):
                out = both(*inputs)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            from benchmark.lib import trace as T
            tr = T.load_xplane(T.find_xplane(tmp))
        if not tr.devices:          # the CPU's trace has no device plane
            print(json.dumps({"ops_ms_a_call": None}))
            return 0
        ops = tr.devices[min(tr.devices)]["ops"]
        count = collections.Counter(o[0] for o in ops)
        about = {o[0]: (o[3] if len(o) > 3 else "") for o in ops}
        total = T.self_times(ops)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:args.ops]
        print(json.dumps({
            "ops_ms_a_call": {
                f"{name} x{count[name] // 3} {about[name][:60]}":
                round(1e3 * s / 3, 3) for name, s in top},
            "all_ops_ms_a_call": round(1e3 * sum(total.values()) / 3, 3),
            "distinct_ops": len(total)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
