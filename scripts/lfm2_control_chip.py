#!/usr/bin/env python3
"""Controls for ``lfm2moe_train_s8192``'s ``correct`` (PR 64): what the
harness's own comparison (``benchmark.lib.chip.train_reference_check``: the
program's loss against the plain float32 reference's on the same batch,
within ``mean_loss_tolerance``) says of a program that is NOT the model.

    chiprun -- python3 scripts/lfm2_control_chip.py [--seeds a b ...]
    python3 scripts/lfm2_control_chip.py --tiny        walks it here

At the cell's sizes, from ``--seed``'s weights and batch 0, as the harness's
first look. In the program's place:

* ``sound``: the model as it is;
* ``b_dropped`` / ``c_dropped``: a conv operator's gate B (C) taken for 1;
* ``bf16_parameters``: the program fed its parameters rounded to bfloat16
  (the reference keeps the float32 ones).

Then what bfloat16 MASTER weights would do over ``--steps`` steps of the
cell's own step (``make_train_step``, the cell's optimizer): the
parameters rounded to bfloat16 after every update against float32 ones,
each read as the norm of the parameters' change over the norm of the
parameters, and held to the reference by the same comparison.
One JSON line a reading; the last line holds them all."""
from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--seeds", type=int, nargs="+", default=[64301, 2147548302])
ap.add_argument("--steps", type=int, default=20)
ap.add_argument("--master-weights-only", action="store_true")
args = ap.parse_args()
if args.tiny:
    os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

from benchmark.lib import chip, spec                         # noqa: E402
from benchmark.lib.traffic import TokenFeed                  # noqa: E402
from ray_tpu.models import lfm2_moe                          # noqa: E402

CELL = "lfm2moe_train_s8192"
KEEP = ("ok", "loss", "reference_loss", "abs_diff", "tolerance",
        "bf16_mean_shift", "bf16_mean_se")


def gate_taken_for_one(chunk: int):
    """``in_proj_short_conv`` with chunk 0 (b) or 1 (c) of b | c | x at 1."""
    real = lfm2_moe.in_proj_short_conv

    def departed(bcx, w):
        d = w.shape[1]
        return real(bcx.at[..., chunk * d:(chunk + 1) * d].set(1), w)
    return mock.patch.object(lfm2_moe, "in_proj_short_conv", departed)


def rounded_to_bf16(tree):
    """Every parameter at the nearest bfloat16, in its own dtype.
    ``reduce_precision`` and not two ``astype``s: under ``jit`` the compiler
    takes a convert pair for excess precision and drops it (call 6 read the
    float32 run's numbers to the last digit that way)."""
    return jax.tree.map(lambda v: jax.lax.reduce_precision(v, 8, 7), tree)


def norm(tree) -> float:
    return float(np.sqrt(sum(float(np.sum(np.square(np.asarray(
        v, np.float64)))) for v in tree.values())))


def main() -> None:
    cell = spec.load_cell(CELL, rehearse=args.tiny)
    config, tr = cell["config_file"], cell["trainer"]
    family = spec.load_family(config["model"]["family"])
    ref = chip._reference_module(config["reference"])
    model = family.build(config["model"])
    rows = int(tr.get("reference_rows", 4))
    objective = family.objective(model)
    out = {"device": jax.devices()[0].device_kind, "cell": CELL,
           "router_aux_coef": config["model"].get("router_aux_coef"),
           "readings": []}

    def read(name, seed, params, tokens, loss, **more):
        check = chip.train_reference_check(ref, model, params, tokens,
                                           float(loss), rows)
        line = dict({"control": name, "seed": seed},
                    **{k: check[k] for k in KEEP}, **more)
        out["readings"].append(line)
        print(json.dumps(line), flush=True)

    def feed_of(seed):
        return TokenFeed(cell["traffic_file"], seed,
                         int(model.config.vocab_size), int(tr["batch"]),
                         int(tr["seq"]))

    init = jax.jit(model.init)
    for seed in [] if args.master_weights_only else args.seeds:
        params = init(jax.random.PRNGKey(seed % (1 << 31)))
        tokens = feed_of(seed).batch(0)
        read("sound", seed, params, tokens, jax.jit(objective)(params, tokens))
        for name, chunk in (("b_dropped", 0), ("c_dropped", 1)):
            with gate_taken_for_one(chunk):
                loss = jax.jit(lambda p, t: objective(p, t))(params, tokens)
            read(name, seed, params, tokens, loss)
        read("bf16_parameters", seed, params, tokens,
             jax.jit(objective)(rounded_to_bf16(params), tokens))

    # bfloat16 master weights over the cell's own step, the first seed
    seed = args.seeds[0]
    feed = feed_of(seed)
    tx = chip.make_optimizer(tr.get("optimizer", {}))
    step = jax.jit(chip.make_train_step(model, tx, objective),
                   donate_argnums=(0, 1))
    to_bf16 = jax.jit(rounded_to_bf16, donate_argnums=(0,))
    for name in ("float32_master_weights", "bf16_master_weights"):
        params = init(jax.random.PRNGKey(seed % (1 << 31)))
        if name.startswith("bf16"):
            params = to_bf16(params)
        first = jax.device_get(params)
        opt_state = jax.jit(tx.init)(params)
        for i in range(args.steps):
            _, params, opt_state = step(params, opt_state, feed.batch(i))
            if name.startswith("bf16"):
                params = to_bf16(params)
        del opt_state
        last = jax.device_get(params)
        moved = norm({k: np.asarray(last[k], np.float64)
                      - np.asarray(first[k], np.float64) for k in first})
        tokens = feed.batch(args.steps)
        read(name, seed, params, tokens, jax.jit(objective)(params, tokens),
             steps=args.steps, parameters_change_norm_share=moved
             / norm(first))
        del params
    print(json.dumps(out))


if __name__ == "__main__":
    main()
