#!/usr/bin/env python3
"""What a balancing term does to the held rows of ``keyevl2_train_s16384``
while the cell trains, on the chip (PR 59, after the driver refused the cell
for the spread of its seeds):

    python3 benchmark/scratch/pr59_balance_probe.py <out.jsonl> <steps>
        <coef:seed,seed,...> [...]

For each coefficient and seed: the cell's model built from the seed as
``train_loop`` builds it, trained by the cell's optimizer on the cell's own
batches with loss = next-token + coef x the layers' balancing terms (the
coefficient a traced scalar, so one program serves them all), the held rows
a layer (``model.routing_stats``) at steps 0, 1, 2 and every third step,
and every step's time. One JSON line a run. A script, not a metric."""
import json
import os
import sys
import time


def main() -> int:
    out_path, steps = sys.argv[1], int(sys.argv[2])
    plan = []
    for arg in sys.argv[3:]:
        coef, seeds = arg.split(":")
        plan += [(float(coef), int(s)) for s in seeds.split(",")]
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.lib import chip, spec
    from benchmark.lib.traffic import TokenFeed

    cell = spec.load_cell("keyevl2_train_s16384",
                          rehearse=bool(os.environ.get("PROBE_REHEARSE")))
    model = spec.family_of(cell).build(cell["config_file"]["model"])
    c = model.config
    b, s = int(cell["trainer"]["batch"]), int(cell["trainer"]["seq"])
    tx = chip.make_optimizer(cell["trainer"].get("optimizer", {}))

    def loss(params, tokens, coef):
        logits, aux = model.forward(params, tokens, balance=True)
        from ray_tpu.ops import cross_entropy_loss
        ce = cross_entropy_loss(logits, jnp.roll(tokens, -1, axis=1))
        return ce + coef * jnp.mean(aux), (ce, jnp.mean(aux))

    def step(params, opt, tokens, coef):
        (_, (ce, aux)), g = jax.value_and_grad(loss, has_aux=True)(
            params, tokens, coef)
        up, opt = tx.update(g, opt, params)
        return ce, aux, optax.apply_updates(params, up), opt

    step = jax.jit(step, donate_argnums=(0, 1))
    init = jax.jit(model.init)
    stats = jax.jit(model.routing_stats)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    for coef, seed in plan:
        params = init(jax.random.PRNGKey(seed % (1 << 31)))
        opt = jax.jit(tx.init)(params)
        feed = TokenFeed(cell["traffic_file"], seed, int(c.vocab_size), b, s)
        rows, ces, auxs, times = {}, [], [], []
        for i in range(steps):
            batch = jnp.asarray(feed.batch(i))
            if i < 3 or i % 3 == 0 or i == steps - 1:
                rows[i] = np.asarray(stats(params, batch)).tolist()
            t0 = time.perf_counter()
            ce, aux, params, opt = step(params, opt, batch,
                                        jnp.float32(coef))
            ces.append(float(ce))
            auxs.append(float(aux))
            times.append(time.perf_counter() - t0)
        del params, opt
        with open(out_path, "a") as f:
            f.write(json.dumps({
                "coef": coef, "seed": seed, "steps": steps,
                "device": jax.devices()[0].device_kind, "rows": rows,
                "ce": ces, "aux": auxs,
                "step_s": [round(t, 4) for t in times]}) + "\n")
        print(coef, seed, "rows", {k: sum(v) for k, v in rows.items()},
              "aux", [round(a, 2) for a in auxs[::6]],
              "step_s", round(float(np.median(times[3:])), 4), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
