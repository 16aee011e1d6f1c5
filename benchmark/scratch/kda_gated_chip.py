#!/usr/bin/env python3
"""One KDA layer's scan from what the convolutions and the gate projection
made, on the chip (PR 51):

    python3 benchmark/scratch/kda_gated_chip.py [--tiny] [--ops N]

At the cell's shape (batch 2 x 8192 tokens, 32 heads of 128 x 128; q, k as
a SiLU leaves them, v and the gate projection's step in bfloat16; A_log and
dt_bias as the configuration's assumed initialisation draws them) three
forms of the same layer:

* ``fused``: ``kda_gated_scan`` (``ray_tpu/ops/kda_scan.py``): on the
  kernel route the l2 norms of q and k and the gate's softplus run inside
  ``kda_chunk_fwd`` / ``kda_chunk_bwd``;
* ``jnp_prologue``: what the model called before PR 51: ``l2norm`` of q and
  k a head, g = -exp(A_log) softplus(step + dt_bias) written in float32,
  then ``kda_scan`` (the same kernels, g ready);
* ``plain``: ``kda_gated_scan`` with the route held to ``chunked_jnp``: the
  definition.

Prints o's and the seven gradients' (q, k, v, step, A_log, dt_bias, beta)
largest difference of ``fused`` and of ``jnp_prologue`` to ``plain`` as a
share of plain's largest entry (A_log's and dt_bias's also as a share of the
sum of the magnitudes their sums add: they cancel), o against the
token-by-token recurrence on the first 1024 tokens, the forward and forward
+ backward of each over 5 calls, and with ``--ops N`` the largest device
operations of a traced forward + backward of ``fused`` and of
``jnp_prologue``. ``--tiny`` walks it on the CPU. One JSON object a line on
stdout. A script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import tempfile
import time

NAMES = ("q", "k", "v", "step", "a_log", "dt_bias", "beta")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import kimi_linear as ref
    from ray_tpu.ops import l2norm
    kda = importlib.import_module("ray_tpu.ops.kda_scan")

    b, t, h, d = (2, 256, 2, 128) if args.tiny else (2, 8192, 32, 128)
    r = jax.random.split(jax.random.PRNGKey(51), 8)
    shape = (b, t, h * d)
    bf = jnp.bfloat16
    q, k, v = (jax.nn.silu(jax.random.normal(r[i], shape)).astype(bf)
               for i in range(3))
    step = (0.5 * jax.random.normal(r[3], shape)).astype(bf)
    a_log = jnp.log(jax.random.uniform(r[4], (h,), minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(r[5], (h * d,), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    beta = jax.nn.sigmoid(jax.random.normal(r[6], (b, t, h)))
    do = jax.random.normal(r[7], shape).astype(bf)
    scale = d ** -0.5
    inputs = (q, k, v, step, a_log, dt_bias, beta)
    say = lambda **kw: print(json.dumps(kw), flush=True)     # noqa: E731

    def made(q, k, step, a_log, dt_bias):
        """q and k of unit length a head and g, as the model made them."""
        unit = lambda x: l2norm(x.reshape(b, -1, h, d)).reshape(  # noqa: E731
            x.shape)
        g = -jnp.repeat(jnp.exp(a_log), d) * jax.nn.softplus(
            step.astype(jnp.float32) + dt_bias)
        return unit(q), unit(k), g

    def fused(*x):
        return kda.kda_gated_scan(*x, scale=scale)

    def jnp_prologue(q, k, v, step, a_log, dt_bias, beta):
        qn, kn, g = made(q, k, step, a_log, dt_bias)
        return kda.kda_scan(qn, kn, v, g, beta, scale=scale)

    chosen = kda._route

    def plain(*x):
        kda._route = lambda *shape: "chunked_jnp"
        try:
            return kda.kda_gated_scan(*x, scale=scale)
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
    forms = {"fused": fused, "jnp_prologue": jnp_prologue, "plain": plain}
    got = {}
    for name, fn in forms.items():
        (_, o), grads = with_grads(fn)(*inputs)
        got[name] = dict(zip(("o",) + NAMES, (o,) + grads))
    # what A_log's and dt_bias's gradients add up, in magnitude: dg g a
    # head and dstep a channel, from the plain form's own dstep
    dstep = jnp.abs(f32(got["plain"]["step"]))
    _, _, g = made(q, k, step, a_log, dt_bias)
    slope = jax.nn.sigmoid(f32(step) + dt_bias) / jax.nn.softplus(
        f32(step) + dt_bias)                  # dstep = dg g slope
    added = {"a_log": float((dstep / slope).reshape(b, t, h, d).sum(
        (0, 1, 3)).max()), "dt_bias": float(dstep.sum((0, 1)).max())}
    for name in ("fused", "jnp_prologue"):
        say(device=jax.devices()[0].device_kind, shape=[b, t, h, d],
            form=name, against_plain={
                n: share(got[name][n], got["plain"][n]) for n in got[name]},
            of_what_the_sums_add={n: float(jnp.abs(
                f32(got[name][n]) - f32(got["plain"][n])).max() / added[n])
                for n in added},
            finite=bool(all(jnp.all(jnp.isfinite(f32(x)))
                            for x in got[name].values())),
            dtypes={n: str(x.dtype) for n, x in got[name].items()})
    say(routes=dict(kda.PATH_COUNTS))
    # against the recurrence, the first tokens (the state starts from zero)
    n = min(t, 1024)
    cut = lambda x: x[:, :n]                                 # noqa: E731
    per_head = lambda x: f32(x).reshape(b, n, h, -1)         # noqa: E731
    qn = ref.l2norm(per_head(cut(q)))
    kn = ref.l2norm(per_head(cut(k)))
    want = ref.delta_rule(qn, kn, per_head(cut(v)), per_head(cut(g)),
                          cut(beta)).reshape(b, n, -1)
    for name in forms:
        diff = jnp.abs(f32(cut(got[name]["o"])) - want)
        say(form=name, tokens_compared=n,
            max_abs_diff_to_recurrence=float(diff.max()),
            mean_abs_diff=float(diff.mean()),
            recurrence_abs_max=float(jnp.abs(want).max()))
    for name, fn in forms.items():
        say(form=name, fwd_ms=timed(jax.jit(fn)),
            fwd_bwd_ms=timed(with_grads(fn)))
    if args.ops:
        from benchmark.lib import trace as T
        for name in ("fused", "jnp_prologue"):
            both = with_grads(forms[name])
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
                say(form=name, ops_ms_a_call={
                    op: round(1e3 * s / 3, 3) for op, s in top},
                    all_ops_ms_a_call=round(
                        1e3 * sum(total.values()) / 3, 3),
                    distinct_ops=len(total))
    return 0


if __name__ == "__main__":
    sys.exit(main())
