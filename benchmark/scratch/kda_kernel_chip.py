#!/usr/bin/env python3
"""The delta rule's Pallas kernel pair alone, on the chip (PR 50):

    python3 benchmark/scratch/kda_kernel_chip.py [--tiny] [--heads 2,4,8]
        [--without solve,diagonal,earlier] [--ops N]

One KDA layer's ``kda_scan`` (``ray_tpu/ops/kda_scan.py``) at the cell's
shape (batch 2 x 8192 tokens, 32 heads of 128 x 128, bfloat16 q, k, v,
float32 gates): the kernel route against the plain route (o and the five
gradients, largest difference as a share of the plain route's largest
entry), both against the token-by-token recurrence on the first 1024
tokens, the forward and forward + backward timed over 5 calls for each
number of heads a program (``--heads``), and with ``--without`` the same
timings with a part of the chunk's work taken out (WRONG numbers; the
time that goes is that part's): ``solve`` (T = I - A), ``diagonal`` (the
[8, 8, 128] differences of the diagonal sub-blocks), ``earlier`` (the
scaling of the block rows and of the earlier columns, not their
products). ``--ops N`` lists the device
operations of a traced forward + backward. ``--tiny`` walks it on the CPU.
One JSON object a line on stdout. A script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import tempfile
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--heads", default="")
    ap.add_argument("--without", default="")
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import kimi_linear as ref
    kda = importlib.import_module("ray_tpu.ops.kda_scan")

    b, t, h, d = (2, 256, 2, 128) if args.tiny else (2, 8192, 32, 128)
    r = jax.random.split(jax.random.PRNGKey(50), 8)
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
    say = lambda **kw: print(json.dumps(kw), flush=True)     # noqa: E731

    def kernel(*x):
        return kda.kda_scan(*x, scale=scale)

    def plain(*x):
        return kda._chunked(*x, h, 64, scale)

    def with_grads(fn):
        return jax.jit(jax.value_and_grad(lambda *x: jnp.sum(
            fn(*x).astype(jnp.float32) * do.astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4)))

    def timed(fn, n=5):
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / n

    share = lambda x, y: float(                              # noqa: E731
        jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max()
        / (jnp.abs(y.astype(jnp.float32)).max() + 1e-30))
    names = ("q", "k", "v", "g", "beta")
    o_k, o_p = jax.jit(kernel)(*inputs), jax.jit(plain)(*inputs)
    (_, g_k), (_, g_p) = with_grads(kernel)(*inputs), with_grads(plain)(
        *inputs)
    say(device=jax.devices()[0].device_kind, shape=[b, t, h, d],
        kernel_against_plain=dict(
            o=share(o_k, o_p), **{n: share(x, y)
                                  for n, x, y in zip(names, g_k, g_p)}),
        finite=bool(all(jnp.all(jnp.isfinite(x.astype(jnp.float32)))
                        for x in (o_k,) + tuple(g_k))),
        routes=dict(kda.PATH_COUNTS))
    # against the recurrence, the first tokens (the state starts from zero)
    n = min(t, 1024)
    cut = lambda x: x[:, :n]                                 # noqa: E731
    per_head = lambda x: x.reshape(b, n, h, -1)              # noqa: E731
    want = ref.delta_rule(*(per_head(cut(x).astype(jnp.float32))
                            for x in (q, k, v, g)), cut(beta)).reshape(
        b, n, -1)
    for name, got in (("kernel", cut(o_k)), ("plain", cut(o_p))):
        say(route=name, tokens_compared=n,
            max_abs_diff_to_recurrence=float(
                jnp.abs(got.astype(jnp.float32) - want).max()),
            mean_abs_diff=float(
                jnp.abs(got.astype(jnp.float32) - want).mean()),
            recurrence_abs_max=float(jnp.abs(want).max()))
    say(route="plain", fwd_ms=timed(jax.jit(plain)),
        fwd_bwd_ms=timed(with_grads(plain)))

    def times(label):
        for hpb in [int(x) for x in args.heads.split(",") if x] or [
                kda._MAX_HEADS_PER_BLOCK]:
            kda._MAX_HEADS_PER_BLOCK = hpb
            jax.clear_caches()     # the pairs' bodies are jit's: traced anew
            say(route="kernel", what=label, heads_a_program=hpb,
                fwd_ms=timed(jax.jit(lambda *x: kernel(*x))),
                fwd_bwd_ms=timed(with_grads(lambda *x: kernel(*x))))

    times("whole")
    whole = {n: getattr(kda, n) for n in (
        "_solve", "_diagonal", "_diagonal_bwd", "_against_earlier")}
    less = {
        "solve": {"_solve": lambda a, r: kda._same_block(
            a.shape, 1).astype(jnp.float32) - a},
        "diagonal": {
            "_diagonal": lambda qf, kf, cum, r: (
                jnp.zeros((qf.shape[0],) * 2, jnp.float32),) * 2,
            "_diagonal_bwd": lambda qf, kf, cum, dsq, dsk, r: (
                jnp.zeros_like(qf),) * 3},
        "earlier": {"_against_earlier": lambda qb, kb, gb, kf, cum, dt: (
            jnp.concatenate([qb, kb], 0).astype(dt),
            jnp.ones((2 * gb.shape[0], gb.shape[1]), jnp.float32),
            kf.astype(dt), jnp.ones(kf.shape, jnp.float32))},
    }
    for part in [p for p in args.without.split(",") if p]:
        for name, fn in less[part].items():
            setattr(kda, name, fn)
        times("without " + part)
        for name, fn in whole.items():
            setattr(kda, name, fn)
    if args.ops:
        from benchmark.lib import trace as T
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
