#!/usr/bin/env python3
"""ONE layer's sparse attention at `keyevl2_train_s16384`'s shape on the
chip, a part at a time (PR 59): the index scores and the selection as the
program makes them (`ops/sparse_attention.selection_mask`), `lax.top_k` on
one block of index scores beside it, and the attention over the selection
by either route:

* masked: the program's three kernels over the int8 mask (forward, forward
  + backward), by block shape;
* gather: 2048 key and value rows gathered a query, a block of queries at
  a time, plain XLA, the backward autodiff's scatter-add (no part of the
  program: what the masked route was chosen against);
* dense: `flash_attention(causal=True)` with k and v repeated, what the
  same layer costs with no selection.

Prints one JSON line a timing (`ms` the median of `--reps` calls after one
warm-up) and the share of mask tiles that hold no selected pair, by tile
shape. `--tiny` walks it on the CPU at a small shape (control flow only).

    chiprun -- python3 scripts/sparse_routes_chip.py
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip", default="", help="comma list: topk,gather,"
                    "dense,blocks")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    import importlib

    from ray_tpu.ops import flash_attention
    sa = importlib.import_module("ray_tpu.ops.sparse_attention")

    skip = set(filter(None, args.skip.split(",")))
    if args.tiny:
        b, s, h, kv, d, hi, di, topk, chunk = 1, 512, 4, 2, 128, 4, 64, 64, 128
    else:
        b, s, h, kv, d, hi, di, topk, chunk = (1, 16384, 32, 4, 128, 16, 64,
                                               2048, 512)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "shape": dict(b=b, s=s, h=h, kv=kv, d=d, hi=hi, di=di,
                                    topk=topk)}), flush=True)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (b, s, h, d), bf)
    k = jax.random.normal(ks[1], (b, s, kv, d), bf)
    v = jax.random.normal(ks[2], (b, s, kv, d), bf)
    qi = jax.random.normal(ks[3], (b, s, hi, di), bf)
    ki = jax.random.normal(ks[4], (b, s, di), bf)
    wi = jax.random.normal(ks[5], (b, s, hi), jnp.float32) * (hi * di) ** -0.5
    w = jax.random.normal(ks[6], (b, s, h, d), bf)

    def timed(name, fn, *a, **facts):
        fn = jax.jit(fn)
        t0 = time.time()
        out = jax.block_until_ready(fn(*a))
        first = time.time() - t0
        ts = []
        for _ in range(args.reps):
            t0 = time.time()
            jax.block_until_ready(fn(*a))
            ts.append((time.time() - t0) * 1e3)
        print(json.dumps(dict({"what": name,
                               "ms": round(statistics.median(ts), 3),
                               "min_ms": round(min(ts), 3),
                               "first_s": round(first, 2)}, **facts)),
              flush=True)
        return out

    # -- the indexer's scores and the selection ----------------------------
    mask = timed("index_scores+select (selection_mask)",
                 lambda *a: sa.selection_mask(*a, topk=topk, q_chunk=chunk),
                 qi, ki, wi)
    timed("index_scores alone, every block against all keys",
          lambda qi, ki, wi: jax.lax.map(
              lambda a: jnp.max(sa.index_scores(a[0], ki[0], a[1]), -1),
              (qi[0].reshape(s // chunk, chunk, hi, di),
               wi[0].reshape(s // chunk, chunk, hi))), qi, ki, wi)
    scores = jax.jit(sa.index_scores)(qi[0, -chunk:], ki[0], wi[0, -chunk:])
    timed("select alone, the last block of queries",
          lambda x: sa.select(x, topk, s - chunk), scores,
          blocks_a_layer=s // chunk)
    if "topk" not in skip:
        timed("lax.top_k alone, the last block of queries",
              lambda x: jax.lax.top_k(x, topk)[1], scores,
              blocks_a_layer=s // chunk)
    # the device's own lax.top_k against select on scores full of ties,
    # +0.0 beside -0.0 among them: the same set, row by row?
    tied = jnp.round(jax.random.normal(ks[7], (chunk, s)) * 4) / 4
    tied = jnp.where(jnp.abs(tied) < 0.3, jnp.where(tied < 0, -0.0, 0.0),
                     tied)
    got = jax.jit(lambda x: sa.select(x, topk, s - chunk))(tied)
    seen = (s - chunk + jnp.arange(chunk))[:, None] >= jnp.arange(s)[None, :]
    chosen = jax.jit(lambda x: jax.lax.top_k(
        jnp.where(seen, x, -jnp.inf), topk)[1])(tied)
    want = jnp.zeros((chunk, s), bool).at[
        jnp.arange(chunk)[:, None], chosen].set(True) & seen
    print(json.dumps({"select_is_the_devices_top_k_on_ties":
                      bool(jnp.all(want == (got != 0))),
                      "rows_that_differ":
                      int(jnp.sum(jnp.any(want != (got != 0), 1)))}),
          flush=True)
    sel = int(jnp.sum(mask.astype(jnp.int32)))
    print(json.dumps({"selected_pairs": sel,
                      "expected": sa.selected_pairs(s, topk) * b,
                      "causal_pairs": b * s * (s + 1) // 2}), flush=True)
    if "blocks" not in skip:
        for tq, tk in ((512, 1024), (128, 512), (128, 128), (8, 128)):
            if s % tq or s % tk:
                continue
            tiles = mask.reshape(b, s // tq, tq, s // tk, tk).max((2, 4))
            causal = (jnp.arange(s // tq)[:, None] * tq + tq - 1
                      >= jnp.arange(s // tk)[None, :] * tk)
            print(json.dumps({
                "tile": [tq, tk], "causal_tiles": int(causal.sum()) * b,
                "empty": int(jnp.sum((tiles == 0) & causal[None]))}),
                flush=True)

    # -- the attention over the selection ----------------------------------
    major = lambda x: jnp.swapaxes(x, 1, 2)                    # noqa: E731
    scale = d ** -0.5

    def masked(bq, bk):
        return lambda q, k, v, m: major(sa._masked_gqa(
            major(q), major(k), major(v), m, scale, bq, bk))

    def with_grad(f):
        return lambda q, k, v, *a: jax.grad(
            lambda q, k, v: jnp.sum(
                (f(q, k, v, *a) * w).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    shapes = ((128, 128),) if args.tiny else (
        (512, 1024), (1024, 1024), (512, 2048), (256, 1024))
    for bq, bk in shapes:
        timed("masked fwd", masked(bq, bk), q, k, v, mask, block=[bq, bk])
        timed("masked fwd+bwd", with_grad(masked(bq, bk)), q, k, v, mask,
              block=[bq, bk])

    if "dense" not in skip:
        def dense(q, k, v):
            return flash_attention(q, jnp.repeat(k, h // kv, 2),
                                   jnp.repeat(v, h // kv, 2), causal=True)
        timed("dense causal flash fwd", dense, q, k, v)
        timed("dense causal flash fwd+bwd", with_grad(dense), q, k, v)

    if "gather" not in skip:
        # a query's keys: random causal positions (the selected keys of
        # random weights are scattered evenly); rows before topk repeat
        # positions, which costs the same
        idx = (jax.random.uniform(ks[7], (s, topk))
               * (jnp.arange(s)[:, None] + 1)).astype(jnp.int32)
        gq = 128 if not args.tiny else 64       # queries a gathered block

        def gather(q, k, v, idx):
            def rows(a):
                qb, ib = a                       # [gq, h, d], [gq, topk]
                kb = k[0][ib].astype(jnp.float32)      # [gq, topk, kv, d]
                vb = v[0][ib].astype(jnp.float32)
                qg = qb.astype(jnp.float32).reshape(gq, kv, h // kv, d)
                sc = jnp.einsum("tkgd,tskd->tkgs", qg, kb) * scale
                o = jnp.einsum("tkgs,tskd->tkgd", jax.nn.softmax(sc, -1), vb)
                return o.reshape(gq, h, d).astype(q.dtype)
            # a block's gathered rows are made again in its backward, not
            # kept for all blocks (34 GB at the cell's shape)
            o = jax.lax.map(jax.checkpoint(rows),
                            (q[0].reshape(s // gq, gq, h, d),
                             idx.reshape(s // gq, gq, topk)))
            return o.reshape(1, s, h, d)

        timed("gather fwd", gather, q, k, v, idx, queries_a_block=gq)
        timed("gather fwd+bwd", with_grad(gather), q, k, v, idx,
              queries_a_block=gq)
    return 0


if __name__ == "__main__":
    sys.exit(main())
