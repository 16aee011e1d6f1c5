#!/usr/bin/env python3
"""Wall time on the chip of the latent flash kernels alone (forward, and
forward + backward) at the cell's shape by block size, and of the grouped
product by row tile:

    python3 benchmark/scratch/latent_blocks.py [--blocks 1024x1024,512x512]
        [--tiles 256,128] [--calls 5] [--tiny]

Each configuration is compiled, run once, then ``--calls`` times between
two ``block_until_ready``: kernels of tens of milliseconds, so the host's
dispatch (tens of microseconds) does not show. One JSON object on stdout.
PR 33; a script, not a metric."""
import argparse
import importlib
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="1024x1024,512x512,512x1024,1024x512")
    ap.add_argument("--tiles", default="256,128,512")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    el = importlib.import_module("ray_tpu.ops.expert_layer")
    b, s, h = (1, 256, 2) if args.tiny else (2, 8192, 32)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    rnd = lambda k, *sh: jax.random.normal(k, sh, jnp.float32).astype(bf)  # noqa: E731
    q, k, v = rnd(ks[0], b, s, h, 128), rnd(ks[1], b, s, h, 128), \
        rnd(ks[2], b, s, h, 128)
    qr, kr = rnd(ks[3], b, s, h, 64), rnd(ks[4], b, s, 64)

    def timed(fn, *a):
        c = jax.jit(fn).lower(*a).compile()
        jax.block_until_ready(c(*a))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = c(*a)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / args.calls

    res = {"device": jax.devices()[0].device_kind, "shape": [b, s, h],
           "latent_ms": {}, "grouped_ms": {}}
    for blk in args.blocks.split(","):
        bq, bk = (int(x) for x in blk.split("x"))
        if args.tiny:
            bq, bk = min(bq, 128), min(bk, 128)

        def fwd(q, k, v, qr, kr, bq=bq, bk=bk):
            return fa.flash_attention(q, k, v, causal=True, block_q=bq,
                                      block_k=bk, q_rope=qr, k_rope=kr)

        def both(*a):
            return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2, 3, 4))(*a)
        try:
            res["latent_ms"][blk] = {"fwd": timed(fwd, q, k, v, qr, kr),
                                     "fwd+bwd": timed(both, q, k, v, qr, kr)}
        except Exception as e:  # noqa: BLE001 - e.g. out of VMEM
            res["latent_ms"][blk] = {"refused": str(e)[-300:]}

    tokens, d, f, held, top_k = (256, 64, 32, 2, 3) if args.tiny else \
        (16384, 2048, 768, 16, 6)
    x = rnd(ks[5], tokens, d)
    wg, wd = rnd(ks[6], held, d, f), rnd(ks[7], held, f, d)
    chosen = jax.random.randint(ks[5], (tokens, top_k), 0, 8 * held)
    for tile in (int(t) for t in args.tiles.split(",")):
        if args.tiny:
            tile = 8
        rows = el.buffer_rows(tokens, top_k, held, tile)

        def layer(x, wg, wd, tile=tile, rows=rows):
            at = el.sort_rows(chosen, held, 0, rows, tile)
            at.pop("held_rows")
            mm = lambda a, w: el.grouped_matmul(  # noqa: E731
                a, w, at["tile_expert"], at["n_used"], tile)
            buf = el.tokens_to_rows(x, at)
            return el.rows_to_tokens(
                mm(jax.nn.silu(mm(buf, wg)) * mm(buf, wg), wd), at)

        def both(x, wg, wd):
            return jax.grad(lambda *a: layer(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(x, wg, wd)
        try:
            res["grouped_ms"][str(tile)] = {
                "rows_buffer": rows, "fwd": timed(layer, x, wg, wd),
                "fwd+bwd": timed(both, x, wg, wd)}
        except Exception as e:  # noqa: BLE001
            res["grouped_ms"][str(tile)] = {"refused": str(e)[-300:]}
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
