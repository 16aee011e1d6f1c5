#!/usr/bin/env python3
"""One Gated DeltaNet layer's scan from what the convolution and the b | a
projection made, on the chip (PR 52):

    python3 benchmark/scratch/gdn_chip.py [--tiny]

At the cell's shape (batch 2 x 8192 tokens, 32 value heads over 16 key
heads, every head 128; q, k as a SiLU leaves them, v and ``a`` in bfloat16;
A_log and dt_bias as the configuration's assumed initialisation draws them)
two forms of the same layer:

* ``kernel``: ``gdn_gated_scan`` (``ray_tpu/ops/kda_scan.py``) as the shape
  routes it: KDA's kernel pair, q and k repeated to the value heads, ``a``
  over a head's lanes, the norms and the gate made in the kernels;
* ``plain``: the same call with the route held to ``chunked_jnp``:
  ``l2norm``, the softplus and ``gated_delta_scan``, the definition.

Prints o's and the seven gradients' (q, k, v, a, A_log, dt_bias, beta)
largest difference of ``kernel`` to ``plain`` as a share of plain's largest
entry (A_log's and dt_bias's also as a share of the sum of the magnitudes
their sums add: they cancel), o of both against the token-by-token
recurrence of ``benchmark/reference/qwen3_next.py`` on the first 1024
tokens, and the forward and forward + backward of each over 5 calls.
``--tiny`` walks it on the CPU. One JSON object a line on stdout. A script,
not a metric."""
import argparse
import importlib
import json
import os
import sys
import time

NAMES = ("q", "k", "v", "a", "a_log", "dt_bias", "beta")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import qwen3_next as ref
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
    forms = {"kernel": kernel, "plain": plain}
    got = {}
    for name, fn in forms.items():
        (_, o), grads = with_grads(fn)(*inputs)
        got[name] = dict(zip(("o",) + NAMES, (o,) + grads))
    x = f32(a) + dt_bias
    da = jnp.abs(f32(got["plain"]["a"]))
    added = {"a_log": float((da * jax.nn.softplus(x) / jax.nn.sigmoid(x)
                             ).sum((0, 1)).max()),
             "dt_bias": float(da.sum((0, 1)).max())}
    say(device=jax.devices()[0].device_kind, shape=[b, t, hk, hv, d],
        form="kernel", against_plain={
            n: share(got["kernel"][n], got["plain"][n]) for n in got["plain"]},
        of_what_the_sums_add={n: float(jnp.abs(
            f32(got["kernel"][n]) - f32(got["plain"][n])).max() / added[n])
            for n in added},
        finite=bool(all(jnp.all(jnp.isfinite(f32(x)))
                        for x in got["kernel"].values())),
        dtypes={n: str(x.dtype) for n, x in got["kernel"].items()})
    say(routes=dict(kda.PATH_COUNTS))
    # against the recurrence, the first tokens (the state starts from zero)
    n = min(t, 1024)
    cut = lambda x: x[:, :n]                                 # noqa: E731
    to_v = lambda x: jnp.repeat(ref.l2norm(                  # noqa: E731
        f32(cut(x)).reshape(b, n, hk, d)), hv // hk, 2)
    g = -jnp.exp(a_log) * jax.nn.softplus(f32(cut(a)) + dt_bias)
    want = ref.delta_rule(to_v(q) * scale, to_v(k),
                          f32(cut(v)).reshape(b, n, hv, d), g, cut(beta)
                          ).reshape(b, n, -1)
    for name in forms:
        diff = jnp.abs(f32(cut(got[name]["o"])) - want)
        say(form=name, tokens_compared=n,
            max_abs_diff_to_recurrence=float(diff.max()),
            mean_abs_diff=float(diff.mean()),
            recurrence_abs_max=float(jnp.abs(want).max()))
    for name, fn in forms.items():
        say(form=name, fwd_ms=timed(jax.jit(fn)),
            fwd_bwd_ms=timed(with_grads(fn)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
