#!/usr/bin/env python3
"""Described-chip compiles: sizes a cell before any chip time.

    JAX_PLATFORMS=cpu python3 benchmark/scratch/describe_compile.py train \
        --cell <train cell> --batches 16,24,32
    JAX_PLATFORMS=cpu python3 benchmark/scratch/describe_compile.py serve \
        --cell <serving cell> --blocks 1024,1536,1792

Compiles, for a TPU v5e that is described and not attached
(``v5e:2x2``; one device, or the 2x2 mesh when the cell's engine has
``tp`` > 1), the very programs the cell runs: the train step of
``benchmark/lib/chip.py`` at each batch, or the engine's decode program
and its largest prefill program at each ``num_blocks``. Prints one JSON
line per attempt: accepted or refused (with the compiler's words), the
bytes of ``memory_analysis()`` per device, seconds. Nothing runs, so
nothing here is a measurement of speed. The topology is described inside
``main``, never at import.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "alias_size_in_bytes", "temp_size_in_bytes")}


def _attempt(label: dict, lower) -> None:
    t0 = time.time()
    try:
        compiled = lower().compile()
        text = compiled.as_text()
        row = dict(label, accepted=True, **_mem(compiled),
                   all_reduces=text.count(" all-reduce("),
                   all_gathers=text.count(" all-gather("))
    except Exception as e:  # noqa: BLE001 - the refusal is the result
        msg = str(e)
        i = msg.find("Used ")
        row = dict(label, accepted=False,
                   refused=(msg[i:i + 160] if i >= 0 else msg[:300]))
    row["compile_s"] = round(time.time() - t0, 1)
    print(json.dumps(row), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("--cell", required=True)
    ap.add_argument("--batches", default="")
    ap.add_argument("--blocks", default="")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import chip, spec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.cell)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree, sharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    model = spec.family_of(cell).build(cell["config_file"]["model"])
    if args.what == "train":
        tx = chip.make_optimizer(cell["trainer"].get("optimizer", {}))
        # the family's own objective where its two files state one
        objective = spec.objective_of(
            spec.family_of(cell),
            chip._reference_module(cell["config_file"]["reference"]))
        step = chip.make_train_step(model, tx,
                                    objective and objective(model))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt = jax.eval_shape(tx.init, params)
        S = int(cell["trainer"]["seq"])
        for b in [int(x) for x in args.batches.split(",")]:
            toks = jax.ShapeDtypeStruct((b, S), jnp.int32, sharding=one)
            _attempt({"cell": args.cell, "program": "train_step",
                      "batch": b, "seq": S},
                     lambda: jax.jit(step, donate_argnums=(0, 1)).lower(
                         shaped(params, one), shaped(opt, one), toks))
        return 0

    from ray_tpu.serve.llm import EngineConfig

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    base = dict(cell["engine"])
    tp = int(base.get("tp", 1))
    if tp > 1:
        from ray_tpu.parallel.sharding import MeshOwner

        owner = MeshOwner.tp_mesh(tp, devices=list(topo.devices),
                                  name="describe")
        pspecs = owner.layout.param_specs(model)
        rep = owner.sharding(owner.layout.replicated())
        kvs = owner.sharding(owner.layout.kv_cache_blocks())
        p_sh = {n: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=owner.sharding(pspecs[n]))
                for n, v in params.items()}
    else:
        rep = kvs = one
        p_sh = shaped(params, one)

    def decode(params, kc, vc, tokens, positions, rows, active):
        logits, cache = model.paged_decode_step(
            params, {"k": kc, "v": vc}, tokens, positions, rows, active)
        return logits, cache["k"], cache["v"]

    def prefill(params, kc, vc, tokens, length, block_row):
        logits, cache = model.paged_prefill(
            params, {"k": kc, "v": vc}, tokens, length, block_row)
        return logits, cache["k"], cache["v"]

    for nb in [int(x) for x in args.blocks.split(",")]:
        cfg = EngineConfig(**dict(base, num_blocks=nb))
        cache = jax.eval_shape(
            lambda: model.init_paged_cache(nb, cfg.block_size))
        kc = jax.ShapeDtypeStruct(cache["k"].shape, cache["k"].dtype,
                                  sharding=kvs)
        B, M = cfg.max_batch, cfg.max_blocks_per_seq
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
        # as engine.py: the tp=1 decode program does not donate the pool
        # (engine.py:340-341), the tp>1 one does (engine.py:357-366)
        donate = (1, 2) if tp > 1 else ()
        kw = {}
        if tp > 1:
            kw = {"out_shardings": (rep, kvs, kvs)}
        label = {"cell": args.cell, "num_blocks": nb, "tp": tp,
                 "pool_bytes_per_device": 2 * int(
                     jnp.dtype(kc.dtype).itemsize) * int(
                     __import__("math").prod(kc.shape)) // tp}
        _attempt(dict(label, program="decode", max_batch=B),
                 lambda: jax.jit(decode, donate_argnums=donate, **kw).lower(
                     p_sh, kc, kc, i32(B), i32(B), i32(B, M),
                     jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=rep)))
        big = max(min(b, cfg.max_context, model.config.max_seq)
                  for b in cfg.prefill_buckets)
        _attempt(dict(label, program="prefill", bucket=big),
                 lambda: jax.jit(prefill, **kw).lower(
                     p_sh, kc, kc, i32(1, big), i32(), i32(M)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
