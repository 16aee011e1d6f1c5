#!/usr/bin/env python3
"""``held_rows.py`` for a model whose layers are walked by
``models/stack.py`` (PR 49): its parameters are ``<run>.<kind>.<name>``,
not ``moe.<name>``, which is where ``held_rows.py`` looks for the expert
layer's shapes.

    python3 benchmark/scratch/held_rows_stack.py --cell <cell>
        [--seeds 101,202,...] [--train-steps 80] [--rehearse]

1. The held-expert layer alone at the cell's size (the tokens of one step,
   the configuration's widths, random weights) against the plain reference
   (``reference/<family>.py``: ``shared_expert`` + ``routed_experts``,
   float32, matmul precision "highest"): the largest difference of the
   output and of three gradients, as a share of the reference's largest
   entry, and the rows the grouped product worked.
2. For each seed, the model built from the seed as ``train_loop`` builds it
   and the cell's own first two batches: the rows each expert layer's held
   experts work (``model.routing_stats``), of ``row_buffer``.
3. With ``--train-steps N``: the first seed's model trained by the cell's
   own step on the cell's own batches, the held rows every ten steps.

One JSON object on stdout. A script, not a metric."""
import argparse
import importlib
import json
import os
import sys

LEAVES = ("w_router", "router_bias", "s_gate", "s_up", "s_down", "e_gate",
          "e_up", "e_down")


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

    from benchmark.lib import chip, spec
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

    # 1. the layer alone: the first expert layer's shapes, one layer of it
    shaped = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = next(n[:-len("w_router")] for n in shaped
                  if n.endswith(".w_router"))
    keys = jax.random.split(jax.random.PRNGKey(33), len(LEAVES) + 1)
    lp = {n: 0.02 * jax.random.normal(k, shaped[prefix + n].shape[1:],
                                      jnp.float32)
          for k, n in zip(keys, LEAVES)}
    lp["router_bias"] = jnp.zeros_like(lp["router_bias"])
    kw = dict(experts_held=c.experts_held, expert_offset=c.expert_offset,
              top_k=c.top_k, routed_scale=c.routed_scaling_factor)
    # the reference routes on what the program routes on: x and the
    # router's weights rounded to bf16 (held_rows.py has the reason)
    round16 = lambda a: a.astype(c.dtype).astype(jnp.float32)  # noqa: E731
    x = round16(jax.random.normal(keys[-1], (b * s, c.d_model), jnp.float32))
    lp["w_router"] = round16(lp["w_router"])

    def mine(x, lp):
        y, rows = el.held_expert_layer(x.astype(c.dtype), lp, **kw)
        return y.astype(jnp.float32), rows

    def theirs(x, lp):
        return ref.shared_expert(x, lp) + ref.routed_experts(
            x, lp, top_k=c.top_k, routed_scale=c.routed_scaling_factor,
            expert_offset=c.expert_offset)

    wrt = ("e_gate", "e_down", "s_up")

    def grads(fn):
        def loss(x, part):
            return jnp.sum(fn(x, dict(lp, **part)) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

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
    del x, y, g_mine, want, g_want, lp, part
    jax.clear_caches()

    # 2. the model's routing by seed
    init = jax.jit(model.init)
    stats = jax.jit(model.routing_stats)
    out["held_rows_by_seed"] = {}
    seeds = [int(n) for n in args.seeds.split(",")]
    for seed in seeds:
        params = init(jax.random.PRNGKey(seed % (1 << 31)))
        feed = TokenFeed(cell["traffic_file"], seed, int(c.vocab_size), b, s)
        out["held_rows_by_seed"][str(seed)] = [
            np.asarray(stats(params, feed.batch(i))).tolist() for i in (0, 1)]
        del params
    # 3. the routing while the cell trains: the held rows every ten steps
    if args.train_steps:
        tx = chip.make_optimizer(cell["trainer"].get("optimizer", {}))
        objective = spec.objective_of(spec.family_of(cell), ref)
        step = jax.jit(chip.make_train_step(
            model, tx, objective and objective(model)), donate_argnums=(0, 1))
        params = init(jax.random.PRNGKey(seeds[0] % (1 << 31)))
        opt = jax.jit(tx.init)(params)
        feed = TokenFeed(cell["traffic_file"], seeds[0], int(c.vocab_size),
                         b, s)
        by_step = {}
        for i in range(args.train_steps + 1):
            if i % 10 == 0:
                by_step[str(i)] = np.asarray(
                    stats(params, feed.batch(i))).tolist()
            _, params, opt = step(params, opt, jnp.asarray(feed.batch(i)))
        out["held_rows_while_training"] = {"seed": seeds[0],
                                           "by_step": by_step}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
