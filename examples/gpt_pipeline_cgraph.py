"""Pipeline-parallel GPT forward pass on compiled graphs (ISSUE 4 demo).

The MPMD shape compiled graphs exist for (arxiv 2412.14374): the
transformer stack is split into N stage actors, each holding its layer
slice resident; a compiled graph wires them driver -> stage0 -> ... ->
stageN-1 -> driver through pre-allocated channels, and the driver keeps
`depth` batches in flight so every stage computes every tick — sustained
pipeline throughput with zero per-hop scheduling or task-spec traffic.

Run: python examples/gpt_pipeline_cgraph.py [--stages 2] [--iters 20]
(CPU-friendly tiny config by default; scale --layers/--d-model on TPU.)
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.cgraph import InputNode  # noqa: E402


@ray_tpu.remote
class GPTStage:
    """One pipeline stage: a contiguous slice of the transformer stack.
    Stage 0 owns the embedding; the last stage owns the final layernorm
    and LM head. All stages init the same seeded params and keep only
    their slice — no parameter shipping at runtime."""

    def __init__(self, cfg_kw: dict, stage_idx: int, num_stages: int,
                 seed: int = 0):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt import GPT, GPTConfig
        from ray_tpu.ops import layernorm

        cfg = GPTConfig(dtype=jnp.float32, use_flash=False, remat=False,
                        **cfg_kw)
        model = GPT(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(seed))
        L = cfg.n_layer
        per = L // num_stages
        lo = stage_idx * per
        hi = L if stage_idx == num_stages - 1 else lo + per
        head_keys = ("wte", "wpe", "lnf_g", "lnf_b")
        lp = {k: v[lo:hi] for k, v in params.items() if k not in head_keys}
        first = stage_idx == 0
        last = stage_idx == num_stages - 1
        wte, wpe = params["wte"], params["wpe"]
        lnf_g, lnf_b = params["lnf_g"], params["lnf_b"]

        def fwd(x):
            if first:
                x = model._embed(wte, wpe, x)
            x = model._run_layers(x, lp, remat=False, scan=False)
            if last:
                x = layernorm(x, lnf_g, lnf_b)
                return model._lm_head(wte, x)
            return x

        self._fwd = jax.jit(fwd)
        self._jnp = jnp

    def fwd(self, x):
        return np.asarray(self._fwd(self._jnp.asarray(x)))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--stages", type=int, default=2)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=128)
    args = p.parse_args()
    assert args.layers % args.stages == 0, "layers must split evenly"

    cfg_kw = dict(vocab_size=512, n_layer=args.layers, n_head=2,
                  d_model=args.d_model, d_ff=4 * args.d_model,
                  max_seq=args.seq)
    ray_tpu.init(num_cpus=float(max(4, args.stages + 1)))
    stages = [GPTStage.remote(cfg_kw, i, args.stages)
              for i in range(args.stages)]

    with InputNode() as inp:
        node = inp
        for s in stages:
            node = s.fwd.bind(node)
    compiled = node.experimental_compile(
        channel_bytes=64 * 1024 * 1024)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, size=(args.batch, args.seq),
                          dtype=np.int32)
    # warmup: trace + compile each stage once
    logits = compiled.execute(tokens).get(timeout=600)
    assert logits.shape[:2] == (args.batch, args.seq), logits.shape

    # sustained throughput: keep the pipeline full (one batch in flight
    # per stage) so every stage computes on every tick
    depth = args.stages + 1
    t0 = time.perf_counter()
    inflight = []
    done = 0
    for i in range(args.iters):
        inflight.append(compiled.execute(tokens))
        if len(inflight) >= depth:
            inflight.pop(0).get(timeout=600)
            done += 1
    for r in inflight:
        r.get(timeout=600)
        done += 1
    dt = time.perf_counter() - t0
    toks = args.batch * args.seq * done / dt
    print(f"pipeline: {args.stages} stages x {args.layers} layers, "
          f"{done} iters, {toks:.0f} tokens/s")

    compiled.teardown()
    ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
