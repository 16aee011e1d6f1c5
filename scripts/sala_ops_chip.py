#!/usr/bin/env python3
"""The two mixers' kernels of `minicpmsala_train_s32768` alone at the cell's
shape on the chip (PR 69): one row of 32 768 tokens, 16 heads of 128.

* the Lightning recurrence (`ops/lightning_attention.py`): the KERNEL route
  beside the plain chunked route (`_lightning_chunked`, what a shape the
  kernels do not take runs) on the same inputs, forward and forward +
  backward, o and the three gradients compared element by element, and both
  against the token-by-token recurrence in float32 on the row's first 2048
  tokens;
* the masked kernel pair over a BLOCK selection
  (`ops/sparse_attention.py` `block_sparse_attention`'s parts): the
  selection (pooled scores, max-pool, top 96) and its expansion to a byte a
  pair, then `_masked_gqa` forward and forward + backward by block shape, a
  group of 16 query heads on one key/value head; a shape whose backward
  `_bwd_vmem` refuses is reported as refused, not run.

    chiprun -- python3 scripts/sala_ops_chip.py

One JSON line a reading (`ms` the median of `--reps` calls after one
warm-up). `--tiny` walks it on the CPU at a small shape (control flow only).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", default="512x512,256x512,512x256,256x256,"
                    "1024x256,256x1024,1024x1024")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    la = importlib.import_module("ray_tpu.ops.lightning_attention")
    sa = importlib.import_module("ray_tpu.ops.sparse_attention")
    t, h, d = (512, 2, 128) if args.tiny else (32768, 16, 128)
    sel = dict(block=16, blocks=6, init_blocks=1, local_blocks=2,
               pool=(8, 4)) if args.tiny else dict(
        block=64, blocks=96, init_blocks=1, local_blocks=32, pool=(32, 16))
    dtype = jnp.float32 if args.tiny else jnp.bfloat16

    def say(**row):
        print(json.dumps(row), flush=True)

    def timed(name, fn, *a, **facts):
        out = jax.block_until_ready(fn(*a))
        ms = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ms.append((time.perf_counter() - t0) * 1e3)
        say(what=name, ms=statistics.median(ms), **facts)
        return out

    def far(a, b):
        a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
        return float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(b).max(),
                                                         1e-30))

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    q, k, v, do = (jax.random.normal(kk, (1, t, h, d), dtype)
                   for kk in keys[:4])
    # the published heads 0 .. h - 1 of 32 at layer 1
    decay = -(2.0 ** (-8.0 * (jnp.arange(h) + 1.0) / 32)) \
        * (1 - 1 / 31 + 1e-5)
    scale = d ** -0.5

    # -- the recurrence: kernel route against the plain chunked route -------
    routes = {
        "kernel": lambda q, k, v: la.lightning_attention(
            q, k, v, decay, scale=scale),
        "chunked": lambda q, k, v: la._lightning_chunked(
            q, k, v, decay, 256 if not args.tiny else 128, scale)}
    outs = {}
    for name, fn in routes.items():
        fwd = jax.jit(fn)
        both = jax.jit(lambda q, k, v, fn=fn: jax.vjp(fn, q, k, v)[1](do))
        outs[name] = (timed("lightning_fwd", fwd, q, k, v, route=name),
                      timed("lightning_fwd_bwd", both, q, k, v, route=name))
    say(what="lightning_kernel_vs_chunked",
        o=far(outs["kernel"][0], outs["chunked"][0]),
        grads=[far(a, b) for a, b in zip(outs["kernel"][1],
                                         outs["chunked"][1])])
    # the plain reference's token-by-token scan on the row's first tokens
    ref = importlib.import_module("benchmark.reference.minicpm_sala")
    n = min(t, 2048)
    first = lambda x: x[:, :n].astype(jnp.float32)            # noqa: E731
    want = jax.jit(ref.lightning_scan, static_argnums=4)(
        first(q), first(k), first(v), decay, scale)
    say(what="lightning_vs_recurrence", tokens=n,
        kernel=far(outs["kernel"][0][:, :n], want),
        chunked=far(outs["chunked"][0][:, :n], want))

    # -- the masked pair over a block selection, by block shape -------------
    kg, vg = k[:, :, :1], v[:, :, :1]
    picked = timed("block_selection", jax.jit(lambda q, k: sa.block_selection(
        q, k, sm_scale=scale, **sel)), q, kg)
    mask = timed("expand_blocks", jax.jit(
        lambda p: sa.expand_blocks(p[:, 0], sel["block"])), picked)
    say(what="selection", selected_pairs=int(jnp.sum(mask.astype(jnp.int32))),
        counted=sa.block_selected_pairs(t, sel["block"], sel["blocks"]),
        causal_pairs=t * (t + 1) // 2,
        empty_tiles_of_512=int(jnp.sum(jnp.all(
            mask[0].reshape(t // min(t, 512), min(t, 512), -1, min(t, 512))
            == 0, axis=(1, 3)))))
    major = lambda x: jnp.swapaxes(x, 1, 2)                   # noqa: E731
    mq, mk, mv, mdo = major(q), major(kg), major(vg), major(do)
    for shape in args.blocks.split(","):
        bq, bk = (min(int(x), t) for x in shape.split("x"))
        need = sa._bwd_vmem(t, bq, bk, h, d, q.dtype.itemsize)
        if need > sa.VMEM_BYTES:
            say(what="masked_pair", blocks=[bq, bk], refused=need,
                limit=sa.VMEM_BYTES)
            continue
        fn = lambda q, k, v, bq=bq, bk=bk: sa._masked_gqa(    # noqa: E731
            q, k, v, mask, scale, bq, bk)
        try:
            timed("masked_fwd", jax.jit(fn), mq, mk, mv, blocks=[bq, bk])
            timed("masked_fwd_bwd", jax.jit(
                lambda q, k, v, fn=fn: jax.vjp(fn, q, k, v)[1](mdo)),
                mq, mk, mv, blocks=[bq, bk], vmem=need)
        except Exception as e:  # noqa: BLE001 - a refusal is the reading
            say(what="masked_pair", blocks=[bq, bk], failed=str(e)[:300])
    return 0


if __name__ == "__main__":
    sys.exit(main())
