#!/usr/bin/env python3
"""The hyper-connections' kernel pair alone, on the chip (PR 47; the
sibling of ``mhc_chip.py``, which times the plain form by its names):

    python3 benchmark/scratch/mhc_kernel_chip.py [--tiny] [--tokens 8192]
        [--tiles 128,256] [--rows 16,8]

One sublayer through ``ops.hyper_connection.hc_mix`` at the cell's shape
(4 streams of d 3584, 8192 tokens, bfloat16 streams, float32 parameters)
with the sublayer between the two mixings the identity: the forward alone
and forward + backward (gradients of the streams and of every parameter),
timed over 20 calls as ``mhc_chip.py`` times them, against the bytes
``layer_metrics/mhc_mix_roofline.py`` says a sublayer needs; each of the
four kernels alone; the plain ``jax.numpy`` form on the same inputs (its
times, and how far the pair's outputs and gradients stand from it and
from the reference's natural [tokens, n, n] form in float32, and each
side's gradients from the float32 gradients of the same function); the
seconds one sublayer's forward + backward takes to trace and to lower.
``--tiles`` and ``--rows`` time the pair again with ``TOKEN_TILE`` /
``_ROWS`` replaced in turn (how the work is cut, not what is computed). ``--tiny``
walks it on the CPU. One JSON object on stdout. A script, not a metric."""
import argparse
import functools
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--tiles", default="")
    ap.add_argument("--rows", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp

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

    def pair(x, p):
        return hc.hc_mix(x, p, lambda z: (z, None), **kw)[0]

    def plain(x, p):
        pre, post, res = hc.hc_coefficients(x, p, **kw)
        return hc.hc_post(x, hc.hc_pre(x, pre), post, res)

    def against_g(out):
        return sum(jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32))
                   for a, b in zip(out, g))

    def loss(fn):
        return lambda x, p: against_g(fn(x, p))

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 20

    unit = t * d * 2
    # ``fwd_bwd`` is mhc_chip.py's protocol: gradients only, so nothing
    # makes X' (the loss is linear in it) and the bytes moved are the first
    # mixing's n + 1 units and the backward's 5 n + 3; ``fwd_bwd_all``
    # hands X' back beside the gradients: the 8 n + 5 units of the count
    need = {"fwd": (3 * n + 2) * unit, "fwd_bwd": (6 * n + 4) * unit,
            "fwd_bwd_all": (8 * n + 5) * unit}

    def with_outputs(fn):
        def f(x, p):
            out = fn(x, p)
            return against_g(out), out
        return jax.grad(f, argnums=(0, 1), has_aux=True)

    def both_ms(fn):
        s_fwd = timed(jax.jit(fn), x, p)
        s_both = timed(jax.jit(jax.grad(loss(fn), argnums=(0, 1))), x, p)
        s_all = timed(jax.jit(with_outputs(fn)), x, p)
        return {"fwd_ms": 1e3 * s_fwd, "fwd_bwd_ms": 1e3 * s_both,
                "fwd_bwd_all_ms": 1e3 * s_all,
                "fwd_GBps_of_need": need["fwd"] / s_fwd / 1e9,
                "fwd_bwd_GBps_of_need": need["fwd_bwd"] / s_both / 1e9,
                "fwd_bwd_all_GBps_of_need": need["fwd_bwd_all"] / s_all / 1e9}

    def kernels_ms():
        """Each kernel alone, on operands of the shapes the pair hands
        it."""
        flat = tuple(xj.reshape(-1, d) for xj in x)
        gf = tuple(gj.reshape(-1, d) for gj in g)
        phi_t, scale, bias = hc._kernel_operands(p, n, jnp.bfloat16)
        sb = hc._scale_bias_lanes(scale, bias)
        pre_fwd = functools.partial(hc._pre_fwd, **kw)
        pre_bwd = functools.partial(
            hc._pre_bwd, **{k: v for k, v in kw.items() if k != "rms_eps"})
        z, coef, raw = pre_fwd(flat, phi_t, sb)
        dy, dcoef = hc._post_bwd(flat, gf, z, coef)
        # pre_bwd writes dX over dX', which a caller's argument may not
        # be: its time here holds the copy of the n dX' that XLA makes
        # for it (so do fwd_bwd_ms and fwd_bwd_all_ms; the step, whose
        # dX' is a temporary, makes none)
        return {
            "pre_fwd": 1e3 * timed(pre_fwd, flat, phi_t, sb),
            "post_fwd": 1e3 * timed(hc._post_fwd, flat, z, coef),
            "post_bwd": 1e3 * timed(hc._post_bwd, flat, gf, z, coef),
            "pre_bwd_and_a_copy_of_dX'": 1e3 * timed(
                pre_bwd, flat, gf, dy, raw, dcoef, phi_t, sb)}

    out = {"device": jax.devices()[0].device_kind, "tokens": t, "n": n,
           "d": d, "need_bytes": need,
           "tile": hc.TOKEN_TILE, "rows": hc._ROWS,
           "pair": both_ms(pair), "kernels_ms": kernels_ms(),
           "plain": both_ms(plain)}
    # the pair against the plain form (bfloat16 both) and the reference's
    # natural form in float32
    with jax.default_matmul_precision("highest"):
        xs = jnp.stack([xj.astype(jnp.float32) for xj in x], 2)
        want = ref.hc_sublayer(xs, p, lambda z: z, eps=1e-6, iters=20,
                               hc_eps=1e-6, clamp=(-30.0, 30.0))
    f32 = lambda tree: jax.tree.map(                          # noqa: E731
        lambda a: a.astype(jnp.float32), tree)
    got, base = f32(jax.jit(pair)(x, p)), f32(jax.jit(plain)(x, p))
    out["max_abs_diff_to_reference"] = float(
        jnp.abs(jnp.stack(got, 2) - want).max())
    out["plain_max_abs_diff_to_reference"] = float(
        jnp.abs(jnp.stack(base, 2) - want).max())
    out["reference_abs_max"] = float(jnp.abs(want).max())
    grads = [f32(jax.jit(jax.grad(loss(fn), argnums=(0, 1)))(x, p))
             for fn in (pair, plain)]
    # the float32 gradients of the same function: the plain form on the
    # streams widened to float32, products at the highest precision
    with jax.default_matmul_precision("highest"):
        truth = jax.jit(jax.grad(loss(plain), argnums=(0, 1)))(f32(x), p)
    out["grad_max_abs_diff_[pair_to_f32,plain_to_f32,f32_abs_max]"] = {
        jax.tree_util.keystr(path): [float(jnp.abs(a - c).max()),
                                     float(jnp.abs(b - c).max()),
                                     float(jnp.abs(c).max())]
        for (path, a), b, c in zip(
            jax.tree_util.tree_leaves_with_path(grads[0]),
            jax.tree.leaves(grads[1]), jax.tree.leaves(truth))}
    def build_s():
        """Seconds to trace and to lower one sublayer's forward + backward
        with nothing cached."""
        jax.clear_caches()
        t0 = time.perf_counter()
        traced = jax.jit(jax.grad(loss(pair), argnums=(0, 1))).trace(x, p)
        t1 = time.perf_counter()
        traced.lower()
        return {"trace_s": t1 - t0, "lower_s": time.perf_counter() - t1}

    out["build"] = build_s()
    sweep = {}
    cuts = [(int(a), int(b))
            for a in (args.tiles or str(hc.TOKEN_TILE)).split(",")
            for b in (args.rows or str(hc._ROWS)).split(",")]
    for tile, rows in cuts if len(cuts) > 1 else ():
        hc.TOKEN_TILE, hc._ROWS = tile, rows
        name = f"tile{tile}_rows{rows}"
        try:
            sweep[name] = dict(both_ms(pair), kernels_ms=kernels_ms(),
                               build=build_s())
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            sweep[name] = {"refused": str(e)[-300:]}
    if sweep:
        out["sweep"] = sweep
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
