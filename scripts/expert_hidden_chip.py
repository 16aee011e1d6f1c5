#!/usr/bin/env python3
"""The routed experts of ONE layer alone, at a cell's shape, by how full the
row buffer is (PR 68).

    chiprun -- python3 scripts/expert_hidden_chip.py [--shape a,b] [--fill ...]
        [--block <of F>]
    python3 scripts/expert_hidden_chip.py --describe   # compiles, no chip
    python3 scripts/expert_hidden_chip.py --tiny       # walks it here

``held_expert_layer``'s ``experts`` scope and nothing else: the row buffer
[rows, K] in bfloat16 (``buffer_rows`` of the cell: the worst case), the rows'
weights, ``tile_expert`` and ``n_used`` written down directly for a FILLING of
the buffer (``one``: one tile an expert, what an expert with no row holds;
``eighth``; ``full``: every tile used, each expert an equal run of tiles), and
a cotangent of the output. Two routes on the same inputs:

* ``fused``: ``_held_mlp``, the layer's own: the kernel pair
  ``expert_hidden_fwd`` / ``expert_hidden_bwd`` and ``e_down``'s grouped
  product;
* ``unfused``: ``_mlp`` over ``grouped_matmul`` with the rows' weights, the
  route the layer had until PR 68 (``_gated`` / ``_relu2``: what the shared
  expert still runs), which writes the pre-activations of the whole buffer.

For each it times forward + backward (y, dx, the three weight gradients, the
rows' weights' gradient) and compares the two element by element OVER THE
USED TILES' ROWS (rows past ``n_used`` are written by neither and read by
nobody): the largest difference over the unfused route's largest entry, and
the share of elements that differ. A script, not a metric."""
import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ray_tpu.ops import expert_layer as el  # noqa: E402
from ray_tpu.ops import kernel_common  # noqa: E402

# tokens, K (the rows' width: the latent where the layer has one), F, experts
# held, slots a token, kind
SHAPES = {
    "nemotron3super": (16384, 1024, 2688, 8, 8, "relu2"),
    "keyevl2": (16384, 2048, 768, 16, 8, "swiglu"),
    "lfm2moe": (16384, 2048, 1792, 8, 4, "swiglu"),
    "kanana2": (16384, 2048, 768, 16, 6, "swiglu"),
    "qwen3next": (16384, 2048, 512, 32, 10, "swiglu"),
    "kimilinear": (16384, 2304, 1024, 8, 8, "swiglu"),
    "xing4": (8192, 3584, 1024, 8, 4, "swiglu"),
}
TINY = {"tiny_relu2": (64, 128, 256, 4, 4, "relu2"),
        "tiny_swiglu": (64, 128, 256, 4, 4, "swiglu")}


def tables(n_tiles, held, fill):
    """(tile_expert [n_tiles], n_used [1]) of a buffer whose leading tiles
    are used, each expert an equal run of them (at least one)."""
    used = {"one": held, "eighth": max(held, n_tiles // 8),
            "full": n_tiles}[fill]
    run = used // held
    expert = np.minimum(np.arange(n_tiles) // run, held - 1)
    return jnp.asarray(expert, jnp.int32), jnp.asarray([used], jnp.int32)


def draw(shape, tile, seed):
    t, k, f, held, slots, expert = shape
    rows = el.buffer_rows(t, slots, held, tile)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    p = {n: 0.02 * jax.random.normal(next(keys), s, jnp.float32)
         for n, s in (("e_gate", (held, k, f)), ("e_up", (held, k, f)),
                      ("e_down", (held, f, k)))}
    if expert == "relu2":
        del p["e_gate"]
    buf = jax.random.normal(next(keys), (rows, k)).astype(jnp.bfloat16)
    # a weight a row, 0 for one row in eight (padding rows)
    weight = jnp.where(jnp.arange(rows) % 8 == 7, 0.0,
                       jax.random.uniform(next(keys), (rows,), jnp.float32))
    cot = jax.random.normal(next(keys), (rows, k)).astype(jnp.bfloat16)
    return p, buf, weight, cot, rows


def routes(expert, tile):
    def fused(buf, p, weight, at):
        return el._held_mlp(expert, buf, p, weight, at, tile)

    def unfused(buf, p, weight, at):
        return el._mlp(expert, buf, p, "e",
                       lambda a, w: el.grouped_matmul(
                           a, w, at["tile_expert"], at["n_used"], tile),
                       weight)

    return {"fused": fused, "unfused": unfused}


def both(fn, cot, used_rows):
    """fn's output and gradients under the cotangent ``cot``, which is 0 on
    the rows of unused tiles (what ``rows_to_tokens``' vjp never gathers
    may hold anything; here it is made to hold nothing)."""
    def run(buf, p, weight, at):
        y, pull = jax.vjp(lambda b, p, w: fn(b, p, w, at), buf, p, weight)
        mask = (jnp.arange(y.shape[0]) < used_rows)[:, None]
        return (y,) + pull(jnp.where(mask, cot, 0).astype(y.dtype))
    return jax.jit(run)


def timed(fn, *args, n=10):
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def used_part(v, rows, used):
    """v in f32, of an array a row of the buffer the used tiles' rows."""
    v = np.asarray(v.astype(jnp.float32))
    return v[:used] if v.shape[:1] == (rows,) else v


def apart(a, b, rows, used):
    a, b = (used_part(v, rows, used) for v in (a, b))
    scale = np.abs(b).max() or 1.0
    return [float(np.abs(a - b).max() / scale), float((a != b).mean())]


def describe(shapes, tile):
    """Compile forward + backward of the fused route for a DESCRIBED v5e."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    kernel_common.use_interpret = lambda: False
    sd = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)  # noqa
    for name, shape in shapes.items():
        t, k, f, held, slots, expert = shape
        rows = el.buffer_rows(t, slots, held, tile)
        p = {"e_up": sd((held, k, f), jnp.float32),
             "e_down": sd((held, f, k), jnp.float32)}
        if expert == "swiglu":
            p["e_gate"] = sd((held, k, f), jnp.float32)
        fn = routes(expert, tile)["fused"]

        def loss(buf, p, weight, te, nu):
            return fn(buf, p, weight, {"tile_expert": te, "n_used": nu}
                      ).astype(jnp.float32).sum()

        t0 = time.perf_counter()
        c = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            sd((rows, k), jnp.bfloat16), p, sd((rows,), jnp.float32),
            sd((rows // tile,), jnp.int32), sd((1,), jnp.int32)).compile()
        m = c.memory_analysis()
        print(name, shape, "rows", rows, "block of F",
              el.hidden_block(k, f, 1 + (expert == "swiglu"), 2, tile),
              f"compiled in {time.perf_counter() - t0:.1f} s, temporaries "
              f"{m.temp_size_in_bytes / 1e6:.1f} MB")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--shape", default=",".join(SHAPES))
    ap.add_argument("--fill", default="one,eighth,full")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block", type=int, default=0,
                    help="a block of F in place of hidden_block's choice")
    args = ap.parse_args()
    if args.block:
        el.hidden_block = lambda k, f, *a: min(args.block, f)
    tile = 8 if args.tiny else el.ROW_TILE
    shapes = TINY if args.tiny else {
        n: SHAPES[n] for n in args.shape.split(",")}
    if args.describe:
        describe(shapes, tile)
        return 0
    for name, shape in shapes.items():
        t, k, f, held, slots, expert = shape
        p, buf, weight, cot, rows = draw(shape, tile, args.seed)
        print("device", jax.devices()[0].device_kind, name, shape, "rows",
              rows, "tiles", rows // tile, "block of F",
              el.hidden_block(k, f, len(p) - 1, 2, tile))
        for fill in args.fill.split(","):
            te, nu = tables(rows // tile, held, fill)
            at = {"tile_expert": te, "n_used": nu}
            used = int(nu[0]) * tile
            fns = {n: both(fn, cot, used)
                   for n, fn in routes(expert, tile).items()}
            got = {n: fn(buf, p, weight, at) for n, fn in fns.items()}
            names = ["y", "dx"] + [f"d{n}" for n in sorted(p)] + ["dweight"]
            flat = {n: [g[0], g[1]] + [g[2][m] for m in sorted(p)] + [g[3]]
                    for n, g in got.items()}
            diff = {n: apart(a, b, rows, used) for n, a, b in zip(
                names, flat["fused"], flat["unfused"])}
            finite = all(bool(np.isfinite(used_part(v, rows, used)).all())
                         for v in flat["fused"])
            ms = [(n, timed(fns[n], buf, p, weight, at))
                  for n in ("fused", "unfused", "unfused", "fused")]
            best = {n: min(v for m, v in ms if m == n) for n in fns}
            print(f"  {fill}: {int(nu[0])} tiles used; forward + backward ms "
                  f"fused {best['fused']:.3f} unfused {best['unfused']:.3f} "
                  f"(in the order run {[round(v, 3) for _, v in ms]}); "
                  f"finite {finite}; fused against unfused [largest "
                  f"difference over the unfused's largest entry, share of "
                  f"elements that differ]: {diff}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
