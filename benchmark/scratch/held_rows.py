#!/usr/bin/env python3
"""What the routing of a cell's model does, on the chip (PR 33):

    python3 benchmark/scratch/held_rows.py --cell <cell> --seeds 101,202,...
        [--rehearse]

1. The held-expert layer alone at the cell's size (tokens of one step,
   the configuration's widths, random weights) against the plain
   reference (``reference/<family>.py``: ``shared_expert`` +
   ``routed_experts``, float32, matmul precision "highest"): the largest
   difference of the output and of two gradients, as a share of the
   reference's largest entry, and the rows the grouped product worked.
2. For each seed, the model built from the seed as ``train_loop`` builds
   it and the cell's own first two batches: the rows each expert layer's
   held experts work (``model.routing_stats``), of ``row_buffer``.
3. With ``--train-steps N``: the first seed's model trained by the
   cell's own step on the cell's own batches, the held rows every ten
   steps. A share of the experts is trained without the others, so the
   router is free to learn its way around the ones that are here.

One JSON object on stdout. A script, not a metric."""
import argparse
import importlib
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", default="101,202,303,2147483749,2147484949,"
                    "2147489999")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--train-steps", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import spec
    from benchmark.lib.traffic import TokenFeed
    from ray_tpu.ops import expert_layer as el

    cell = spec.load_cell(args.cell, rehearse=args.rehearse)
    model = spec.family_of(cell).build(cell["config_file"]["model"])
    ref = importlib.import_module(
        "benchmark.reference." + cell["config_file"]["reference"])
    c = model.config
    b, s = int(cell["trainer"]["batch"]), int(cell["trainer"]["seq"])
    out = {"device": jax.devices()[0].device_kind, "cell": args.cell,
           "tokens": b * s,
           "row_buffer": el.buffer_rows(b * s, c.top_k, c.experts_held)}

    # 1. the layer alone
    shapes = {n.split(".", 1)[1]: v.shape[1:] for n, v in jax.eval_shape(
        model.init, jax.random.PRNGKey(0)).items() if n.startswith("moe.")}
    keys = jax.random.split(jax.random.PRNGKey(33), len(shapes) + 1)
    lp = {n: 0.02 * jax.random.normal(k, sh, jnp.float32)
          for k, (n, sh) in zip(keys, sorted(shapes.items()))}
    lp["router_bias"] = jnp.zeros_like(lp["router_bias"])
    x = jax.random.normal(keys[-1], (b * s, c.d_model), jnp.float32)
    kw = dict(experts_held=c.experts_held, expert_offset=c.expert_offset,
              top_k=c.top_k, routed_scale=c.routed_scaling_factor)

    def mine(x, lp):
        y, rows = el.held_expert_layer(x.astype(c.dtype), lp, **kw)
        return y.astype(jnp.float32), rows

    def theirs(x, lp):
        return ref.shared_expert(x, lp) + ref.routed_experts(
            x, lp, top_k=c.top_k, routed_scale=c.routed_scaling_factor,
            expert_offset=c.expert_offset)

    wrt = ("e_gate", "e_down")

    def grads(fn):
        def loss(x, part):
            return jnp.sum(fn(x, dict(lp, **part)) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    # the reference routes on what the program routes on: x and the
    # router's weights rounded to bf16 (the scores then agree to the order
    # of a float32 sum; on float32 inputs every fifth token's sixth and
    # seventh expert would change places and the largest difference would
    # be one expert's whole output)
    round16 = lambda a: a.astype(c.dtype).astype(jnp.float32)  # noqa: E731
    x = round16(x)
    lp["w_router"] = round16(lp["w_router"])
    y, rows = jax.jit(mine)(x, lp)
    part = {n: lp[n] for n in wrt}
    g_mine = grads(lambda x, lp: mine(x, lp)[0])(x, part)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(theirs)(x, lp)
        g_want = grads(theirs)(x, part)

    def rel(a, r):
        """(largest difference, share of rows further than 5 %), both
        against the reference's largest entry."""
        d = jnp.abs(a - r).reshape(a.shape[0], -1).max(1) / jnp.abs(r).max()
        return [float(d.max()), float((d > 0.05).mean())]

    out["layer_alone"] = {
        "held_rows": int(rows), "out": rel(y, want),
        "dx": rel(g_mine[0], g_want[0]),
        **{f"d{n}": rel(g_mine[1][n], g_want[1][n]) for n in wrt}}

    # the two kernels alone on the layer's own buffer, against one dense
    # product an expert over every row
    def buffer(x):
        _, chosen = el.route(x.astype(c.dtype), lp["w_router"],
                             lp["router_bias"], top_k=c.top_k,
                             routed_scale=1.0)
        at = el.sort_rows(chosen, c.experts_held, c.expert_offset,
                          out["row_buffer"])
        used, held = at["n_used"][0], at.pop("held_rows")
        return el.tokens_to_rows(x.astype(c.dtype), at), at, used, held

    def run_kernels(buf, at, w, dy):
        got, vjp = jax.vjp(lambda b, w: el.grouped_matmul(
            b, w, at["tile_expert"], at["n_used"]), buf, w)
        return (got,) + vjp(dy)

    def dense(buf, at, w, dy, got, dbuf, dw):
        e_row = jnp.repeat(at["tile_expert"], el.ROW_TILE)
        live = jnp.arange(buf.shape[0]) < at["n_used"][0] * el.ROW_TILE
        b32, w32, dy32 = (t.astype(jnp.float32) for t in (buf, w, dy))
        want = jnp.zeros(got.shape, jnp.float32)
        want_db = jnp.zeros(buf.shape, jnp.float32)
        want_dw = []
        for e in range(c.experts_held):
            m = ((e_row == e) & live)[:, None]
            want = want + jnp.where(m, b32 @ w32[e], 0)
            want_db = want_db + jnp.where(m, dy32 @ w32[e].T, 0)
            want_dw.append(jnp.where(m, b32, 0).T @ dy32)
        keep = lambda a: jnp.where(live[:, None], a.astype(jnp.float32), 0)  # noqa: E731
        return (rel(keep(got), want), rel(keep(dbuf), want_db),
                rel(dw.astype(jnp.float32), jnp.stack(want_dw)))

    buf, at, used, held = jax.jit(buffer)(x)
    w = lp["e_gate"].astype(c.dtype)
    dy = jax.random.normal(keys[0], (buf.shape[0], w.shape[-1])).astype(
        c.dtype)
    dy = jnp.where((jnp.arange(buf.shape[0]) < used * el.ROW_TILE)[:, None],
                   dy, 0)
    got = jax.jit(run_kernels)(buf, at, w, dy)
    with jax.default_matmul_precision("highest"):
        names = ("rows_kernel", "rows_kernel_transposed", "weights_kernel")
        # rel() is host-side; the dense products are jitted one by one
        out["kernels_alone"] = dict(
            zip(names, dense(buf, at, w, dy, *got)),
            tiles_used=int(used), held_rows=int(held))

    del x, y, g_mine, want, g_want, buf, at, w, dy, got, lp, part
    jax.clear_caches()

    # 2. the model's routing by seed
    init = jax.jit(model.init)
    stats = jax.jit(model.routing_stats)
    out["held_rows_by_seed"] = {}
    for seed in (int(n) for n in args.seeds.split(",")):
        params = init(jax.random.PRNGKey(seed % (1 << 31)))
        feed = TokenFeed(cell["traffic_file"], seed,
                         int(c.vocab_size), b, s)
        out["held_rows_by_seed"][str(seed)] = [
            np.asarray(stats(params, feed.batch(i))).tolist()
            for i in (0, 1)]
        del params
    # 3. the routing while the cell trains: the held rows every ten steps
    if args.train_steps:
        from benchmark.lib import chip

        seed = int(args.seeds.split(",")[0])
        tx = chip.make_optimizer(cell["trainer"].get("optimizer", {}))
        objective = spec.objective_of(spec.family_of(cell), ref)
        step = jax.jit(chip.make_train_step(
            model, tx, objective and objective(model)), donate_argnums=(0, 1))
        params = init(jax.random.PRNGKey(seed % (1 << 31)))
        opt = jax.jit(tx.init)(params)
        feed = TokenFeed(cell["traffic_file"], seed, int(c.vocab_size), b, s)
        out["held_rows_while_training"] = {"seed": seed, "by_step": {}}
        for i in range(args.train_steps + 1):
            if i % 10 == 0:
                out["held_rows_while_training"]["by_step"][str(i)] = \
                    np.asarray(stats(params, feed.batch(i))).tolist()
            _, params, opt = step(params, opt, jnp.asarray(feed.batch(i)))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
