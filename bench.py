"""Benchmark entry point (to be rebuilt as cells by the benchmark PR).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Headline: GPT-2-small training-step throughput on one chip (tokens/s) with
MFU. vs_baseline = achieved MFU / 0.50, the BASELINE.md north-star target
(the reference publishes no absolute tokens/s for this — BASELINE.json
published:{} — so the MFU target is the comparison line).

Without a TPU this fails; RTPU_BENCH_SMOKE=1 runs a tiny config on the CPU
(control flow only: its record carries no MFU). One process per chip: this
process is the one that holds the chip, so every suite that starts a
cluster runs in a child pinned to the CPU, and its rows are recorded under
``cpu_suites`` — never beside the device's numbers. A suite that raises
is reported and makes the exit code non-zero.
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time
import traceback

from ray_tpu.core.worker_env import use_compile_cache

SMOKE = os.environ.get("RTPU_BENCH_SMOKE", "") == "1"

if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"
use_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

_FAILED: list = []  # suites that raised; non-empty -> exit code 1


def _suite_failed(name: str) -> None:
    """Call from an except block: a broken suite must not look like 0."""
    traceback.print_exc()
    _FAILED.append(name)


_PEAK_BF16 = {
    # chip kind substring -> peak bf16 FLOP/s per chip
    "v5 lite": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v5": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12, "v6e": 918e12,
    "v3": 123e12, "v2": 45e12,
}


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in _PEAK_BF16.items():
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s on record for device kind {kind!r}")


def main() -> int:
    from ray_tpu.models import GPT, GPTConfig

    # the CPU children go first: no cluster is ever started from a
    # process that already holds the chip
    cpu_suites = _run_cpu_suites()
    on_tpu = jax.default_backend() == "tpu"
    if not (SMOKE or on_tpu):
        raise SystemExit("bench.py: jax found no TPU (set RTPU_BENCH_SMOKE=1 "
                         "for the tiny CPU control-flow run)")
    if SMOKE:
        cfg = GPTConfig.tiny(dtype=jnp.float32, use_flash=False)
        batch, seq, steps, warmup = 2, 128, 3, 1
    else:
        # measured-best single-chip config (scripts/mfu_sweep.py r3/r3b):
        # unrolled layers (no scan residual-stacking DUS), chunked LM head
        # (no [B,S,V] f32 logits), and remat OFF — everything fits HBM at
        # B=48, so rematerialising the elementwise chains was pure
        # overhead (0.409 -> 0.460 MFU). Remaining gap to 0.50 is
        # per-program overhead in the flash kernel (in-model attention
        # ~3.2 ms/layer vs ~0.5 ms roofline at d=64; faster than both
        # jax's official flash and splash kernels at this shape).
        cfg = GPTConfig.small(dtype=jnp.bfloat16, use_flash=True,
                              scan_layers=False, remat=False)
        batch = int(os.environ.get("RTPU_BENCH_BATCH", "40"))
        seq, steps, warmup = 1024, 30, 3

    model = GPT(cfg)
    import optax

    tx = optax.adamw(3e-4, weight_decay=0.1)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    # ~4096-row LM-head chunks; must divide batch*seq (loss_chunked asserts)
    num_chunks = max(1, (batch * seq) // 4096)
    while (batch * seq) % num_chunks != 0:
        num_chunks -= 1

    def loss_fn(params, tokens, targets):
        return model.loss_chunked(params, tokens, targets,
                                  num_chunks=num_chunks)

    # donate params/opt_state: in-place update, no per-step HBM copy
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    for _ in range(warmup):
        loss, params, opt_state = train_step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        loss, params, opt_state = train_step(params, opt_state, tokens, targets)
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / dt

    n = model.num_params()
    achieved = model.flops_per_token(seq) * tokens_per_sec
    # a CPU run has no peak to be measured against: no MFU in its record
    mfu = achieved / _peak_flops(jax.devices()[0]) if on_tpu else None
    achievable = _probe_achievable_tflops() if on_tpu else 0.0
    dev = jax.devices()[0]

    print(json.dumps({
        "metric": "gpt2_small_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.50, 4) if on_tpu else None,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "failed_suites": list(_FAILED),
        "detail": {
            "mfu": round(mfu, 4) if on_tpu else None,
            # vs the chip's MEASURED clean-matmul rate (delta-method
            # probe below; scripts/mfu_calibrate.py is the full
            # artifact). Measured correctly the device reaches 80-100%
            # of nominal, so this usually tracks `mfu` — kept as the
            # standing check that the denominator stays honest
            "achievable_tflops": round(achievable / 1e12, 1),
            "mfu_achievable": (round(achieved / achievable, 4)
                               if achievable else None),
            "loss": loss_val,
            "params": n,
            "batch": batch, "seq": seq,
            "steps_timed": steps,
            "sec_per_step": round(dt / steps, 4),
            # in this process, on the device named above
            **_bench_ppo_atari(),
            **_bench_llm_serve(),
            # each in a child on the CPU (clusters, actor planes)
            "cpu_suites": {"platform": "cpu", **cpu_suites},
        },
    }))
    return 1 if _FAILED else 0


# suites that start a cluster (or need virtual CPU devices): one child each,
# pinned to the CPU, so this process stays the only one on the chip
_CPU_SUITES = ("ppo", "ppo_atari_host", "cgraph", "dispatch", "pipeline",
               "collectives", "sharding", "traffic", "perf", "data")


def _run_cpu_suites() -> dict:
    out: dict = {}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in _CPU_SUITES:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--only", name],
            env=env, capture_output=True, text=True, timeout=3600)
        lines = proc.stdout.strip().splitlines()
        if proc.returncode != 0 or not lines:
            print(proc.stdout[-2000:])
            print(proc.stderr[-2000:], file=sys.stderr)
            _FAILED.append(name)
            continue
        out.update(json.loads(lines[-1])["value"])
    return out


def _probe_achievable_tflops(n: int = 8192, iters: int = 48) -> float:
    """Quick sustained-TF/s probe on a clean [n,n]x[n,n] bf16 matmul —
    the denominator for mfu_achievable (full method comparison lives in
    scripts/mfu_calibrate.py)."""
    a = jnp.ones((n, n), jnp.bfloat16)

    # dependent matmul chain (each output feeds the next, scaled so
    # ones stay ones): hoisting/DCE can't elide the work. Timing the
    # DIFFERENCE between a 2N- and an N-length chain cancels the
    # fixed per-dispatch overhead, which otherwise dominates short
    # probes.
    def make(length):
        @jax.jit
        def fused(x):
            def body(x, _):
                return ((x @ a) * jnp.bfloat16(1.0 / n)), None

            x, _ = jax.lax.scan(body, x, None, length=length)
            return jnp.sum(x[:1, :1])

        return fused

    short, long_ = make(iters), make(2 * iters)
    float(short(a))
    float(long_(a))  # compile + sync
    deltas = []
    t_long_min = None
    for _ in range(3):  # dispatch-overhead noise >> signal; sample
        t0 = time.perf_counter()
        float(short(a))
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(long_(a))
        t_long = time.perf_counter() - t0
        t_long_min = (t_long if t_long_min is None
                      else min(t_long_min, t_long))
        deltas.append(t_long - t_short)
    deltas.sort()
    delta = deltas[1]  # median of 3
    if delta <= 0:
        # noise swamped the delta: fall back to the raw 2N chain
        # (a LOWER bound — still overhead-polluted, never absurd)
        delta = t_long_min / 2
    return 2 * n * n * n / (delta / iters)


def _bench_cgraph_chain() -> dict:
    """Compiled-graph vs dynamic 3-actor chain round trip (ISSUE 4 —
    tracked in the bench json detail so the cgraph speedup is a
    standing regression line next to the model numbers)."""
    try:
        import ray_tpu
        from bench_core import chain_roundtrip_us

        ray_tpu.init(num_cpus=4)
        try:
            return chain_roundtrip_us(50 if SMOKE else 300)
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("cgraph_chain")
        return {}


def _bench_dispatch() -> dict:
    """Direct-dispatch rows (ISSUE 6): direct actor-call round trip /
    pipelined rate and the multi-driver aggregate tasks/s envelope —
    tracked per round in the BENCH json detail."""
    try:
        import ray_tpu
        from bench_core import direct_actor_call_us, multi_driver_tasks_per_s

        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
        try:
            out = direct_actor_call_us(50 if SMOKE else 300)
            out.update(multi_driver_tasks_per_s())
            return out
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("dispatch")
        return {}


def _bench_llm_serve() -> dict:
    """LLM serving rows (ISSUE 7): continuous-batching vs sequential
    tokens/s, sustained requests/s, TTFT/TPOT p50/p99 — tracked per
    round in the BENCH json detail. In-process engine; no cluster.
    Plus the ISSUE 18 tracing A/B: median tokens/s overhead of
    per-request lifecycle spans (acceptance <= 3%)."""
    out: dict = {}
    try:
        from bench_core import llm_serve_bench

        out.update(llm_serve_bench(concurrency=4 if SMOKE else 8))
    except Exception:
        _suite_failed("llm_serve")
    try:
        from bench_core import llm_trace_overhead_bench

        out.update(llm_trace_overhead_bench(concurrency=4 if SMOKE else 8))
    except Exception:
        _suite_failed("llm_serve")
    return out


def _bench_traffic() -> dict:
    """Traffic-shaped serving rows (ISSUE 14): (a) prefix-cache TTFT —
    shared 512-token prefix, 32-token suffixes, concurrency 8, cache-on
    vs cache-off on the same engine (acceptance: cached >= 3x better,
    token-identical); (b) a trace replay through the REAL serve stack
    (bursty Poisson arrivals, Zipf sessions, 60% shared prefix,
    session-aware HTTP routing) reporting goodput + p99 TTFT/TPOT +
    preemption/failover counts, run under chaos so zero-failed-streams
    composes with the fault story. The replay runs in a subprocess: it
    owns a whole serve cluster + proxy and must not inherit this
    process's jax/cluster state. A CPU suite (_CPU_SUITES): the child
    inherits this process's platform pin."""
    out: dict = {}
    try:
        from bench_core import prefix_cache_bench

        out.update(prefix_cache_bench(concurrency=4 if SMOKE else 8))
    except Exception:
        _suite_failed("traffic")
    try:
        import tempfile

        harness = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "scripts", "traffic_harness.py")
        with tempfile.NamedTemporaryFile(suffix=".json") as tf:
            argv = [sys.executable, harness, "--json", tf.name,
                    "--sessions", "12" if SMOKE else "40",
                    "--max-turns", "2" if SMOKE else "3"]
            if not SMOKE:
                # chaos-on replay: a seeded mid-burst replica kill, with
                # streams on the resilient transport — the acceptance
                # run that must complete with zero failed streams
                argv += ["--transport", "resilient",
                         "--kill-replica-at", "4"]
            proc = subprocess.run(argv, capture_output=True,
                                  text=True, timeout=900)
            if proc.returncode != 0:
                raise RuntimeError(f"traffic replay rc={proc.returncode}\n"
                                   f"{proc.stdout[-2000:]}\n"
                                   f"{proc.stderr[-2000:]}")
            with open(tf.name) as f:
                row = json.load(f)
            out.update({k: v for k, v in row.items()
                        if k.startswith(("traffic_", "prefix_hit",
                                         "llm_preempt", "session_"))})
            out["traffic_chaos_on"] = not SMOKE
    except Exception:
        _suite_failed("traffic")
    return out


def _bench_pipeline() -> dict:
    """Pipeline training-engine rows (ISSUE 8): compiled-graph 1F1B step
    time vs the dynamic `.remote()` engine, GPT-tiny pipeline tokens/s,
    and the ZeRO-sharded vs replicated dp=2 update — tracked per round
    in the BENCH json detail. CPU actor plane; the in-mesh TPU path is
    covered by the multichip dryrun."""
    try:
        import ray_tpu
        from bench_core import pipeline_train_bench

        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
        try:
            return pipeline_train_bench()
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("pipeline")
        return {}


def _bench_data() -> dict:
    """Streaming data-plane rows (ISSUE 19, docs/DATA.md):
    `data_ingest_mb_s` through a byte-budgeted read->map plan,
    `shuffle_epoch_ms` for one windowed_shuffle epoch, and
    `feed_vs_handfed_tokens_ratio` (>= 0.95 acceptance bar, also
    asserted live by scripts/data_smoke.py) — tracked per round in the
    BENCH json detail."""
    try:
        import ray_tpu
        from bench_core import data_plane_bench

        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
        try:
            return data_plane_bench()
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("data")
        return {}


def _bench_perf() -> dict:
    """Observability rows (ISSUE 17): flight-recorder overhead A/B on
    the pipeline acceptance config (`profiler_overhead_pct`, bar <= 3%)
    and the measured-vs-analytic 1F1B bubble fraction from
    `CompiledPipelineEngine.profile()` (`pipeline_bubble_frac`) —
    tracked in the bench json detail."""
    try:
        import ray_tpu
        from bench_core import perf_overhead_bench

        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
        try:
            return perf_overhead_bench()
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("perf")
        return {}


def _bench_collectives() -> dict:
    """Quantized-collective rows (ISSUE 13, docs/COLLECTIVES.md):
    host-plane ZeRO dp=2 sync time + per-rank bytes at a fixed 1M-param
    vector, fp32 vs int8 (the <= 30% bytes acceptance bar rides along
    as `zero_sync_bytes_ratio`), and the disagg prefill->decode
    generate latency with the KV shipment raw vs quantized."""
    try:
        import ray_tpu
        from bench_core import collective_codec_bench

        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4))
        try:
            return collective_codec_bench()
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("collectives")
        return {}


def _bench_sharding() -> dict:
    """Sharded-execution rows (ISSUE 11): llm tokens/s at tp in
    {1,2,4} and pipeline step ms at fsdp in {1,2}, with the
    token-identity / loss-bitwise acceptance booleans riding along.
    Runs in a SUBPROCESS because the tp/fsdp meshes need
    --xla_force_host_platform_device_count seeded before jax import.
    A CPU suite (_CPU_SUITES): four VIRTUAL CPU devices, not chips."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "bench_core.py"),
             "--sharding-json"],
            env=env, capture_output=True, text=True, timeout=1200)
        for line in proc.stdout.splitlines():
            if line.startswith("SHARDING_JSON:"):
                return json.loads(line[len("SHARDING_JSON:"):])
        raise RuntimeError(f"no SHARDING_JSON line, rc={proc.returncode}\n"
                           f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    except Exception:
        _suite_failed("sharding")
        return {}


def _bench_ppo_steps() -> dict:
    """PPO env-steps/s through the real multi-worker actor path: N rollout
    actors (numpy policy, no jax in workers) -> JAX learner in this
    process (a CPU suite, _CPU_SUITES: the learner is on the CPU too)
    -> one object-store weight broadcast per iteration (the
    BASELINE.md configuration; north star >100k steps/s). Worker count
    scales with the bench host's cores (override RTPU_BENCH_PPO_WORKERS)."""
    try:
        import ray_tpu
        from ray_tpu.rllib.algorithm import PPOConfig

        cores = os.cpu_count() or 1
        if SMOKE:
            n_workers, n_envs, T, iters = 2, 8, 64, 1
            mb, epochs = 512, 2
        else:
            n_workers = int(os.environ.get(
                "RTPU_BENCH_PPO_WORKERS", max(2, min(32, cores))))
            # large rollouts + few big minibatches amortize the per-call
            # dispatch cost of the learner's jit programs
            n_envs, T, iters = 64, 512, 3
            mb, epochs = 8192, 2
        ray_tpu.init(num_cpus=float(max(4, n_workers + 1)))
        try:
            algo = (PPOConfig()
                    .environment("CartPole-v1")
                    .rollouts(num_rollout_workers=n_workers,
                              num_envs_per_worker=n_envs,
                              rollout_fragment_length=T)
                    .training(sgd_minibatch_size=mb, num_sgd_epochs=epochs)
                    .build())
            algo.train()  # warmup: spawn workers, first jit compile
            t0 = time.perf_counter()
            total = 0
            for _ in range(iters):
                total += algo.train()["timesteps_this_iter"]
            dt = time.perf_counter() - t0
            algo.stop()
            return {"ppo_env_steps_per_sec": round(total / dt, 1)}
        finally:
            ray_tpu.shutdown()
    except Exception:
        _suite_failed("ppo_steps")
        return {}


def _bench_ppo_atari() -> dict:
    """PPO env-steps/s on the Atari-shaped pipeline (84x84x4 uint8 pixel
    obs, NatureCNN policy) — the BASELINE PPO config is Atari Breakout.

    Headline: the TPU-native fused pipeline (ray_tpu.rllib.PPOJax —
    device-resident env, rollout+GAE+SGD in one compiled program;
    docs/PERF_NOTES.md round 5). Steady-state discipline matches the GPT
    bench: warmup dispatches, then >=10 timed train() calls, median
    per-call rate reported with min/max spread.

    The host actor path on the same pixels (numpy envs -> object store
    -> learner) is its own CPU suite, _bench_ppo_atari_host_steps."""
    out: dict = {}
    try:
        from ray_tpu.rllib import PPOJaxConfig

        if SMOKE:
            n_envs, T, ips, timed = 8, 16, 2, 3
        else:
            n_envs, T, ips, timed = 128, 64, 4, 12
        algo = PPOJaxConfig(env="BreakoutShaped-v0", num_envs=n_envs,
                            rollout_len=T, iters_per_step=ips,
                            sgd_minibatch_size=min(2048, n_envs * T),
                            num_sgd_epochs=1, hidden=(512,)).build()
        algo.train()
        algo.train()  # warmup: compile + steady caches
        rates = []
        for _ in range(timed):
            r = algo.train()
            rates.append(r["env_steps_per_sec"])
        rates.sort()
        out["ppo_atari_env_steps_per_sec"] = round(
            rates[len(rates) // 2], 1)
        out["ppo_atari_spread"] = [round(rates[0], 1), round(rates[-1], 1)]
        out["ppo_atari_steps_per_call"] = n_envs * T * ips
    except Exception:
        _suite_failed("ppo_atari")
    return out


def _bench_ppo_atari_host_steps() -> dict:
    """The host actor path on the same pixels pipeline, with the
    per-stage breakdown (env / inference / learner; the remainder of
    sample time is serialization + RPC)."""
    import ray_tpu
    from ray_tpu.rllib.algorithm import PPOConfig

    cores = os.cpu_count() or 1
    if SMOKE:
        n_workers, n_envs, T, iters = 1, 4, 16, 1
        mb, epochs = 64, 1
    else:
        n_workers = int(os.environ.get(
            "RTPU_BENCH_ATARI_WORKERS", max(2, min(16, cores))))
        n_envs, T, iters = 8, 64, 2
        mb, epochs = 1024, 1
    ray_tpu.init(num_cpus=float(max(4, n_workers + 1)))
    try:
        algo = (PPOConfig(hidden=(512,))
                .environment("BreakoutShaped-v0")
                .rollouts(num_rollout_workers=n_workers,
                          num_envs_per_worker=n_envs,
                          rollout_fragment_length=T)
                .training(sgd_minibatch_size=mb, num_sgd_epochs=epochs)
                .build())
        algo.train()  # warmup: spawn workers, first jit compile
        t0 = time.perf_counter()
        total, env_s, infer_s, sample_s, learn_s = 0, 0.0, 0.0, 0.0, 0.0
        for _ in range(iters):
            r = algo.train()
            total += r["timesteps_this_iter"]
            env_s += r["rollout_env_time_s"]
            infer_s += r["rollout_infer_time_s"]
            sample_s += r["sample_time_s"]
            learn_s += r["learn_time_s"]
        dt = time.perf_counter() - t0
        algo.stop()
        return {"ppo_atari_host": {
            "env_steps_per_sec": round(total / dt, 1),
            "breakdown_s": {"env": round(env_s, 2),
                            "inference": round(infer_s, 2),
                            "sample_total": round(sample_s, 2),
                            "learner": round(learn_s, 2)}}}
    finally:
        ray_tpu.shutdown()


if __name__ == "__main__":
    if "--only" in sys.argv:
        # single-suite entry (docs/DATA.md: `python bench.py --only data`)
        # — skips the GPT headline and prints just that suite's rows
        which = sys.argv[sys.argv.index("--only") + 1]
        suites = {"data": _bench_data, "pipeline": _bench_pipeline,
                  "perf": _bench_perf, "collectives": _bench_collectives,
                  "sharding": _bench_sharding, "traffic": _bench_traffic,
                  "llm": _bench_llm_serve, "dispatch": _bench_dispatch,
                  "cgraph": _bench_cgraph_chain, "ppo": _bench_ppo_steps,
                  "ppo_atari_host": _bench_ppo_atari_host_steps}
        if which not in suites:
            print(f"unknown suite {which!r}; one of {sorted(suites)}")
            sys.exit(2)
        if which in _CPU_SUITES and jax.default_backend() != "cpu":
            # these start clusters: their process must not hold the chip
            sys.exit(f"suite {which!r} runs on the CPU: set JAX_PLATFORMS=cpu")
        value = suites[which]()
        print(json.dumps({"metric": f"bench_{which}", "value": value,
                          "platform": jax.default_backend(),
                          "failed_suites": list(_FAILED)}))
        sys.exit(1 if _FAILED else 0)
    sys.exit(main())
