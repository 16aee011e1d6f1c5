"""Achievable-peak calibration for the bench chip — the reproducible
artifact behind docs/PERF_NOTES.md's "nominal vs achievable" analysis.

Measures, on the attached device:
  1. sustained bf16 matmul throughput on clean large shapes (the
     best-case MXU number this chip will actually deliver): dependent
     N- and 2N-length matmul chains plus independent dispatches, with
     the 2N-minus-N delta (median of 3) as the headline — it cancels
     the fixed per-dispatch overhead that skews short raw probes low;
  2. the nominal peak used as the MFU denominator in bench.py;
  3. the GPT-2 bench step's implied sustained TF/s.

Prints ONE JSON line:
  {"nominal_tflops": .., "achievable_tflops": .., "achievable_frac": ..,
   "model_tflops": .., "mfu_nominal": .., "mfu_achievable": ..}

Not measured on today's code or chip set-up: run it through the chip
tool before quoting a number from it. Holds the chip itself (one
process per chip): start nothing else that needs jax beside it.

Usage: python scripts/mfu_calibrate.py
"""
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _sync(x):
    return jax.block_until_ready(x)


def measure_matmul_peak(n: int = 8192, iters: int = 48) -> dict:
    """Sustained TF/s on a clean [n,n]x[n,n] bf16 matmul, three ways."""
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    flops = 2 * n * n * n

    mm = jax.jit(lambda a, b: a @ b)
    _sync(mm(a, b))  # compile

    # method 1: dependent chain, one dispatch — each output FEEDS the
    # next (scaled so ones stay ones), so neither loop-invariant
    # hoisting nor DCE can elide any matmul. (An earlier version used
    # `* 0 + a` re-anchoring / an unused a@b per step — both of which
    # XLA may legally optimize away; numbers from those were unstable
    # in iteration count, the tell.)
    @jax.jit
    def chain(x, b):
        def body(x, _):
            return (x @ b) * jnp.bfloat16(1.0 / n), None

        x, _ = jax.lax.scan(body, x, None, length=iters)
        return x

    # method 2: independent back-to-back dispatches, wall-clocked
    # (upper-bounded by per-dispatch overhead)
    _sync(chain(a, b))
    t0 = time.perf_counter()
    outs = [mm(a, b) for _ in range(iters)]
    _sync(outs[-1])
    dt2 = (time.perf_counter() - t0) / iters

    # method 3: the dependent chain at 2x length — comparing its TF/s
    # with the N-chain's detects elision (they'd diverge wildly) and
    # feeds the delta below
    @jax.jit
    def chain2(x, b):
        def body(x, _):
            return (x @ b) * jnp.bfloat16(1.0 / n), None

        x, _ = jax.lax.scan(body, x, None, length=2 * iters)
        return x

    _sync(chain2(a, b))

    # headline: the 2N-minus-N delta cancels the fixed per-dispatch
    # overhead that skews raw chains low. Sample 3x and take the
    # median; a swamped delta falls back to the raw 2N chain (a lower
    # bound, never absurd).
    deltas = []
    t1s, t3s = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(chain(a, b))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync(chain2(a, b))
        t3 = time.perf_counter() - t0
        t1s.append(t1)
        t3s.append(t3)
        deltas.append(t3 - t1)
    deltas.sort()
    delta = deltas[1]
    if delta <= 0:
        delta = min(t3s) / 2
    dt1 = min(t1s) / iters
    dt3 = min(t3s) / (2 * iters)
    return {
        # labeled, unsorted: chain_N vs chain_2N must stay comparable
        # (divergence = elided work = invalid run)
        "methods_tflops": {
            "chain_N": round(flops / dt1 / 1e12, 1),
            "independent_dispatches": round(flops / dt2 / 1e12, 1),
            "chain_2N": round(flops / dt3 / 1e12, 1),
        },
        "achievable_tflops": round(flops / (delta / iters) / 1e12, 1),
    }


def nominal_peak(device) -> float:
    # same table as bench.py _peak_flops
    kind = getattr(device, "device_kind", "")
    table = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v4": 275e12,
             "TPU v5p": 459e12, "TPU v6e": 918e12}
    for k, v in table.items():
        if k in str(kind):
            return v
    raise ValueError(f"no peak FLOP/s on record for device kind {kind!r}")


def measure_model_step(batch: int = 40, steps: int = 10) -> dict:
    """The GPT-2 bench config's sustained TF/s (same path as bench.py)."""
    import optax

    from ray_tpu.models import GPT, GPTConfig

    cfg = GPTConfig.small(dtype=jnp.bfloat16, use_flash=True,
                          scan_layers=False, remat=False)
    model = GPT(cfg)
    tx = optax.adamw(3e-4, weight_decay=0.1)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    opt_state = jax.jit(tx.init)(params)
    seq = 1024
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    num_chunks = max(1, (batch * seq) // 4096)
    while (batch * seq) % num_chunks:
        num_chunks -= 1

    def loss_fn(p, t, g):
        return model.loss_chunked(p, t, g, num_chunks=num_chunks)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        import optax as _o

        return loss, _o.apply_updates(params, updates), opt_state

    loss, params, opt_state = step(params, opt_state, tokens, targets)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state, tokens, targets)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    tok_s = batch * seq / dt
    model_tflops = model.flops_per_token(seq) * tok_s / 1e12
    return {"sec_per_step": round(dt, 4), "model_tflops": round(model_tflops, 1)}


def main() -> None:
    dev = jax.devices()[0]
    peak = nominal_peak(dev)
    mat = measure_matmul_peak()
    mdl = measure_model_step()
    out = {
        "device": str(getattr(dev, "device_kind", dev)),
        "nominal_tflops": round(peak / 1e12, 1),
        **mat,
        **mdl,
        "achievable_frac": round(mat["achievable_tflops"] * 1e12 / peak, 4),
        "mfu_nominal": round(mdl["model_tflops"] * 1e12 / peak, 4),
        "mfu_achievable": round(
            mdl["model_tflops"] / mat["achievable_tflops"], 4),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
