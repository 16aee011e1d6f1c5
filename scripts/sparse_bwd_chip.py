#!/usr/bin/env python3
"""Sparse attention's masked BACKWARD alone at `keyevl2_train_s16384`'s
shape on the chip (PR 60): this tree's `_masked_bwd` (one kernel, 5
products a block pair and query head) beside another tree's (PR 59's pair
of kernels, 7 products), on the same inputs.

* dq, dk, dv of both trees element by element (largest difference, share
  of equal elements) and, with `--reference`, against a float32 form
  worked one key/value head at a time;
* the time of each tree's backward by block shape (`ms` the median of
  `--reps` calls after one warm-up) and a head-tile's microseconds: the
  time over the causal block pairs' area in tiles of 1024 x 1024 and the
  query heads.

    chiprun -- python3 scripts/sparse_bwd_chip.py --other chip_check/parent

One JSON line a reading. `--tiny` walks it on the CPU at a small shape
(control flow only).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def load(tree: str, name: str):
    """`ops/sparse_attention.py` of another tree as a module of THIS
    tree's package (its relative imports find this tree's modules)."""
    spec = importlib.util.spec_from_file_location(
        "ray_tpu.ops." + name,
        os.path.join(tree, "ray_tpu", "ops", "sparse_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--other", default="", help="a second tree's root")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", default="1024x1024,512x1024,512x2048,"
                    "256x1024,1024x512,512x512")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    trees = {"this": importlib.import_module("ray_tpu.ops.sparse_attention")}
    if args.other:
        trees["other"] = load(args.other, "sparse_attention_other")
    if args.tiny:
        b, s, h, kv, d, hi, di, topk, chunk = 1, 512, 4, 2, 128, 4, 64, 64, 128
        shapes = [(128, 256), (256, 128)]
    else:
        b, s, h, kv, d, hi, di, topk, chunk = (1, 16384, 32, 4, 128, 16, 64,
                                               2048, 512)
        shapes = [tuple(int(x) for x in t.split("x"))
                  for t in args.blocks.split(",")]
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "shape": dict(b=b, s=s, h=h, kv=kv, d=d, topk=topk)}),
          flush=True)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, h, s, d), bf)
    k = jax.random.normal(ks[1], (b, kv, s, d), bf)
    v = jax.random.normal(ks[2], (b, kv, s, d), bf)
    qi = jax.random.normal(ks[3], (b, s, hi, di), bf)
    ki = jax.random.normal(ks[4], (b, s, di), bf)
    wi = jax.random.normal(ks[5], (b, s, hi), jnp.float32) * (hi * di) ** -0.5
    g = jax.random.normal(ks[6], (b, h, s, d), bf)
    scale = d ** -0.5
    this = trees["this"]
    mask = jax.jit(lambda *a: this.selection_mask(
        *a, topk=topk, q_chunk=chunk))(qi, ki, wi)
    o, lse = jax.jit(lambda *a: this._masked_fwd(
        *a, scale, shapes[0][0], shapes[0][1]))(q, k, v, mask)
    jax.block_until_ready((o, lse))

    def tiles(bq, bk):
        """Causal block pairs' area in tiles of 1024 x 1024, by heads."""
        pairs = sum(1 for i in range(s // bq) for j in range(s // bk)
                    if i * bq + bq - 1 >= j * bk)
        return pairs * bq * bk / (1024 * 1024) * h * b

    got = {}
    for bq, bk in shapes:
        for name, mod in trees.items():
            fn = jax.jit(lambda *a, mod=mod: mod._masked_bwd(
                *a, scale, bq, bk))
            t0 = time.time()
            out = jax.block_until_ready(fn(q, k, v, mask, o, lse, g))
            first = time.time() - t0
            ts = []
            for _ in range(args.reps):
                t0 = time.time()
                jax.block_until_ready(fn(q, k, v, mask, o, lse, g))
                ts.append((time.time() - t0) * 1e3)
            ms = statistics.median(ts)
            print(json.dumps({
                "what": "masked bwd", "tree": name, "block": [bq, bk],
                "ms": round(ms, 3), "min_ms": round(min(ts), 3),
                "first_s": round(first, 2),
                "us_a_head_tile": round(ms * 1e3 / tiles(bq, bk), 3)}),
                flush=True)
            got[name, bq, bk] = out
        if "other" in trees:
            for nm, x, y in zip(("dq", "dk", "dv"), got["this", bq, bk],
                                got["other", bq, bk]):
                x, y = x.astype(jnp.float32), y.astype(jnp.float32)
                print(json.dumps({
                    "what": "this against other", "block": [bq, bk],
                    "grad": nm, "max_abs_diff": float(jnp.abs(x - y).max()),
                    "max_abs": float(jnp.abs(y).max()),
                    "equal_share": float(jnp.mean(x == y))}), flush=True)

    if args.reference:
        hi_p = jax.lax.Precision.HIGHEST
        f32 = lambda x: x.astype(jnp.float32)                  # noqa: E731
        grp = h // kv

        def head(qh, kh, vh, gh, m):                # [S, d] x 4, [S, S]
            def attn(qh, kh, vh):
                sc = jnp.dot(qh, kh.T, precision=hi_p) * scale
                p = jax.nn.softmax(jnp.where(m != 0, sc, -jnp.inf), axis=-1)
                return jnp.dot(p, vh, precision=hi_p)
            return jax.vjp(attn, qh, kh, vh)[1](gh)

        @jax.jit
        def reference(q, k, v, g, mask):
            def one(args):
                qh, gh, j = args
                return head(f32(qh), f32(k[0, j // grp]), f32(v[0, j // grp]),
                            f32(gh), mask[0])
            dq, dk, dv = jax.lax.map(one, (q[0], g[0], jnp.arange(h)))
            return (dq[None], dk.reshape(kv, grp, s, d).sum(1)[None],
                    dv.reshape(kv, grp, s, d).sum(1)[None])

        want = reference(q, k, v, g, mask)
        for name in trees:
            for nm, x, y in zip(("dq", "dk", "dv"),
                                got[(name,) + shapes[0]], want):
                err = float(jnp.abs(f32(x) - y).max())
                print(json.dumps({
                    "what": "against float32", "tree": name,
                    "block": list(shapes[0]), "grad": nm,
                    "max_abs_diff": err, "max_abs": float(jnp.abs(y).max()),
                    "rel": err / float(jnp.abs(y).max())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
