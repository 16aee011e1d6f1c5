"""MFU sweep: full-train-step medians for candidate configs on the TPU.

Usage: python scripts/mfu_sweep.py [quick|full]
Prints one line per config: median sec/step, tokens/s, MFU.
"""
import functools
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, ".")
from ray_tpu.models import GPT, GPTConfig  # noqa: E402

PEAK = 197e12  # v5e bf16


def time_config(name, cfg, batch, loss_kind, steps=6, warmup=2,
                num_chunks=None):
    model = GPT(cfg)
    tx = optax.adamw(3e-4, weight_decay=0.1)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, 1024), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    if loss_kind == "plain":
        loss_fn = model.loss
    elif num_chunks is None:
        loss_fn = model.loss_chunked
    else:
        import functools as _ft

        loss_fn = _ft.partial(model.loss_chunked, num_chunks=num_chunks)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    for _ in range(warmup):
        loss, params, opt_state = step(params, opt_state, tokens, targets)
    float(loss)
    # time in chunks of `inner` steps with ONE host sync each (bench.py
    # style): a per-step sync would stall the dispatch queue every step
    inner = 5
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        for _ in range(inner):
            loss, params, opt_state = step(params, opt_state, tokens, targets)
        float(loss)
        times.append((time.perf_counter() - t0) / inner)
    med = statistics.median(times)
    toks = batch * 1024 / med
    mfu = model.flops_per_token(1024) * toks / PEAK
    print(f"{name:44s} med={med*1000:7.1f}ms tok/s={toks:9.0f} mfu={mfu:.4f}",
          flush=True)
    return mfu


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "quick"
    base = dict(dtype=jnp.bfloat16, use_flash=True)
    runs = [
        ("B16 flash1024 plain (r2 baseline)",
         GPTConfig.small(**base), 16, "plain"),
        ("B32 flash1024 chunked",
         GPTConfig.small(**base), 32, "chunked"),
        ("B32 flash1024 plain",
         GPTConfig.small(**base), 32, "plain"),
        ("B32 flash512q1024k chunked",
         GPTConfig.small(flash_block_q=512, **base), 32, "chunked"),
        ("B32 flash512q512k chunked",
         GPTConfig.small(flash_block_q=512, flash_block_k=512, **base),
         32, "chunked"),
        ("B32 noflash chunked",
         GPTConfig.small(dtype=jnp.bfloat16, use_flash=False), 32, "chunked"),
        ("B16 noflash plain",
         GPTConfig.small(dtype=jnp.bfloat16, use_flash=False), 16, "plain"),
    ]
    if mode == "full":
        runs += [
            ("B24 flash chunked", GPTConfig.small(**base), 24, "chunked"),
            ("B48 flash chunked", GPTConfig.small(**base), 48, "chunked"),
            ("B32 flash chunked noremat",
             GPTConfig.small(remat=False, **base), 32, "chunked"),
            ("B16 flash plain noremat",
             GPTConfig.small(remat=False, **base), 16, "plain"),
        ]
    if mode == "r3b":
        un = dict(scan_layers=False, **base)
        nc = lambda b, rows: (b * 1024) // rows  # noqa: E731
        runs = [
            ("b32 noremat c4096 (r3 best)",
             GPTConfig.small(remat=False, **un), 32, "chunked", nc(32, 4096)),
            ("b16 noremat c4096",
             GPTConfig.small(remat=False, **un), 16, "chunked", nc(16, 4096)),
            ("b24 noremat c4096",
             GPTConfig.small(remat=False, **un), 24, "chunked", nc(24, 4096)),
            ("b32 noremat 512x1024",
             GPTConfig.small(remat=False, flash_block_q=512, **un),
             32, "chunked", nc(32, 4096)),
            ("b32 noremat plain-loss",
             GPTConfig.small(remat=False, **un), 32, "plain", None),
            ("b16 noremat plain-loss",
             GPTConfig.small(remat=False, **un), 16, "plain", None),
            ("b48 noremat c4096",
             GPTConfig.small(remat=False, **un), 48, "chunked", nc(48, 4096)),
            ("b32 noremat scan",
             GPTConfig.small(remat=False, scan_layers=True, **base),
             32, "chunked", nc(32, 4096)),
        ]
        for name, cfg, b, kind, chunks in runs:
            try:
                time_config(name, cfg, b, kind, num_chunks=chunks)
            except Exception as e:
                print(f"{name:44s} FAILED: {type(e).__name__}: "
                      f"{str(e)[:140]}", flush=True)
        return
    if mode == "r3":
        runs = []
        un = dict(scan_layers=False, **base)
        nc = lambda b, rows: (b * 1024) // rows  # noqa: E731
        runs = [
            ("b64 1024x1024 c4096 (bench now)",
             GPTConfig.small(**un), 64, "chunked", nc(64, 4096)),
            ("b64 512x512 c4096",
             GPTConfig.small(flash_block_q=512, flash_block_k=512, **un),
             64, "chunked", nc(64, 4096)),
            ("b64 512x1024 c4096",
             GPTConfig.small(flash_block_q=512, **un),
             64, "chunked", nc(64, 4096)),
            ("b64 256x512 c4096",
             GPTConfig.small(flash_block_q=256, flash_block_k=512, **un),
             64, "chunked", nc(64, 4096)),
            ("b96 1024x1024 c4096",
             GPTConfig.small(**un), 96, "chunked", nc(96, 4096)),
            ("b64 1024x1024 c8192",
             GPTConfig.small(**un), 64, "chunked", nc(64, 8192)),
            ("b64 1024x1024 c16384",
             GPTConfig.small(**un), 64, "chunked", nc(64, 16384)),
            ("b64 1024x1024 c2048",
             GPTConfig.small(**un), 64, "chunked", nc(64, 2048)),
            ("b32 noremat c4096",
             GPTConfig.small(remat=False, **un), 32, "chunked", nc(32, 4096)),
            ("b64 noremat c4096",
             GPTConfig.small(remat=False, **un), 64, "chunked", nc(64, 4096)),
            ("b48 1024x1024 c4096",
             GPTConfig.small(**un), 48, "chunked", nc(48, 4096)),
        ]
        for name, cfg, b, kind, chunks in runs:
            try:
                time_config(name, cfg, b, kind, num_chunks=chunks)
            except Exception as e:
                print(f"{name:44s} FAILED: {type(e).__name__}: "
                      f"{str(e)[:140]}", flush=True)
        return
    for name, cfg, b, kind in runs:
        try:
            time_config(name, cfg, b, kind)
        except Exception as e:
            print(f"{name:44s} FAILED: {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
