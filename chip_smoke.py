#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             one TPU chip: train, then serve, GPT-2 small
    python chip_smoke.py --chips 4   one host's four chips: Llama-2-7B served tp=4
    python chip_smoke.py --rehearse-cpu [--chips 4]
                                     the same control flow at a tiny size on the
                                     CPU; never prints an "ok" line for a tpu

Drives the two paths users come for through the entry points they call:

* trainer — ``ray_tpu.init(num_tpus=1)`` -> ``JaxTrainer`` (one worker,
  ``use_tpu=True``) -> ``train.get_mesh()`` -> a jitted adamw step on
  ``GPT(GPTConfig.small(bf16, flash))`` at S=1024, loss reported per step;
* server — ``serve.run(serve.deployment(...)(LLMServer).bind(...))`` behind
  ``serve.start_http_proxy``, concurrent streamed requests over HTTP;
* reference — a process that holds the chip alone runs a plain full-sequence
  float32 forward (no KV cache, no block tables, XLA attention) over what the
  server answered, and the model's paged prefill for a logit-level comparison.

One process per chip: this parent never initialises a jax backend. Each phase
runs under its own ``ray_tpu.init`` ... ``ray_tpu.shutdown`` and its chip
worker has exited before the next phase starts. Device facts in the last line
come from the workers that held the chip. Any phase that fails raises: the
script then exits non-zero and prints no result line. With no TPU it fails.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import json
import math
import os
import sys
import time
import urllib.request

import ray_tpu
from ray_tpu import serve, train
from ray_tpu.core.worker_env import use_compile_cache
from ray_tpu.serve.llm import LLMServer
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

# The reference is the plain forward in float32. The server computes in
# bf16, so it is held to the reference within a tolerance that the run
# measures itself: NOISE_FACTOR times the largest distance between the
# same plain forward in bf16 and in float32 (two logits, each off by that
# much, can swap places). A wrong cache, mask or shard is off by O(1).
# And bf16 itself may not drift further than NOISE_CEILING of the largest
# reference logit, or the yardstick is broken.
NOISE_FACTOR = 2.0
NOISE_CEILING = 0.2
F32_FLOOR = 1e-4  # two float32 programs differ in the last bits too

# chips -> phases at full width. Depth and width are the models' own;
# only the batch (train) and the KV pool (serve) are sized to the chip.
FULL = {
    1: {
        # B=8 from the described-chip compile: 1.49 GB of arguments (f32
        # params + adam moments, donated) and 5.7 GB of temporaries of the
        # 15.75 GB the compiler allows; B=48 is refused (19.42 GB)
        "train": {"full": True, "batch": 8, "seq": 1024, "steps": 6},
        "serve": {
            "model": "gpt2-small", "vocab": 50257,
            "engine": {"block_size": 16, "num_blocks": 320, "max_batch": 8,
                       "max_blocks_per_seq": 40,
                       "prefill_buckets": (256, 512),
                       "max_prefill_tokens_per_step": 512},
            "prompt_lens": (200, 420, 233, 377, 310, 268, 405, 190),
            "new_tokens": 32, "ref_pad": 512},
    },
    4: {
        # 27 GB of float32 parameters: does not fit one 16 GB chip
        "serve": {
            "model": "llama2-7b", "vocab": 32000,
            "engine": {"tp": 4, "block_size": 16, "num_blocks": 128,
                       "max_batch": 4, "max_blocks_per_seq": 20,
                       "prefill_buckets": (256,),
                       "max_prefill_tokens_per_step": 256},
            "prompt_lens": (180, 250, 211, 156), "new_tokens": 24,
            "ref_pad": 288},
    },
}
TINY = {
    1: {
        "train": {"full": False, "batch": 2, "seq": 128, "steps": 4},
        "serve": {
            "model": "gpt-tiny", "vocab": 512,
            "engine": {"block_size": 16, "num_blocks": 64, "max_batch": 4,
                       "max_blocks_per_seq": 8, "prefill_buckets": (64, 128),
                       "max_prefill_tokens_per_step": 128},
            "prompt_lens": (40, 90, 70, 55, 101), "new_tokens": 8,
            "ref_pad": 128},
    },
    4: {
        "serve": {
            "model": "llama-tiny", "vocab": 512,
            "engine": {"tp": 4, "block_size": 16, "num_blocks": 64,
                       "max_batch": 4, "max_blocks_per_seq": 8,
                       "prefill_buckets": (128,),
                       "max_prefill_tokens_per_step": 128},
            "prompt_lens": (40, 90, 70, 55, 101), "new_tokens": 8,
            "ref_pad": 128},
    },
}


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# code that runs inside the worker that holds the chip
# ---------------------------------------------------------------------------

def _device_facts(require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs), "pid": os.getpid(),
             "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
    if require_tpu and facts["platform"] != "tpu":
        raise RuntimeError(f"no TPU: jax found {facts}")
    return facts


class _CacheCounts:
    """Counts jax's own persistent-compile-cache events in this process."""

    def __init__(self):
        import jax.monitoring

        self.n = collections.Counter()
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            self.n[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> dict:
        return {"requests": self.n["compile_requests_use_cache"],
                "hits": self.n["cache_hits"],
                "misses": self.n["cache_misses"]}


def _per_device_bytes(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return {str(k): int(v) for k, v in sorted(out.items())}


def _hbm_in_use() -> dict:
    import jax

    out = {}
    for d in jax.devices():
        st = d.memory_stats() or {}
        out[str(d.id)] = {k: int(st[k]) for k in
                          ("bytes_in_use", "peak_bytes_in_use") if k in st}
    return out


def train_loop(config: dict) -> None:
    """examples/gpt2_ddp_train.py's loop on a fixed batch, with the step
    compiled ahead so its text and memory analysis can be reported."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import GPT, GPTConfig

    facts = _device_facts(config["require_tpu"])
    counts = _CacheCounts()
    mesh = train.get_mesh()
    cfg = (GPTConfig.small(dtype=jnp.bfloat16, use_flash=True)
           if config["full"] else
           GPTConfig.tiny(dtype=jnp.float32, use_flash=True))
    model = GPT(cfg)
    params = jax.jit(model.init,
                     out_shardings=model.param_shardings(mesh))(
        jax.random.PRNGKey(config["seed"]))
    tx = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = jax.jit(tx.init)(params)
    B, S = config["batch"], config["seq"]
    data_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    rng = np.random.default_rng(config["seed"])
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
        data_sharding)
    targets = jnp.roll(tokens, -1, axis=1)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens, targets).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    header = {
        "device": facts, "compile_s": round(compile_s, 2),
        "kernel_calls_in_step": compiled.as_text().count("tpu_custom_call"),
        "memory_analysis": {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "alias_size_in_bytes", "temp_size_in_bytes")},
        "n_params": model.num_params(), "batch": B, "seq": S}
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        loss, params, opt_state = compiled(params, opt_state, tokens, targets)
        loss = float(loss)  # the host needs the value: waits for the step
        step_s = time.perf_counter() - t0
        row = {"step": i, "loss": loss, "step_s": round(step_s, 4)}
        if i == config["steps"] - 1:
            row.update(header, cache=counts.snapshot(), hbm=_hbm_in_use())
        train.report(row)


class SmokeServer(LLMServer):
    """LLMServer plus one read-only method, so the facts in the smoke's
    output come from the replica process that holds the chip."""

    def __init__(self, *args, require_tpu: bool = True, **kw):
        self._facts = _device_facts(require_tpu)
        self._counts = _CacheCounts()
        super().__init__(*args, **kw)

    def probe(self) -> dict:
        eng = self.engine
        return {"device": self._facts, "cache": self._counts.snapshot(),
                "param_bytes_per_device": _per_device_bytes(eng.params),
                "kv_bytes_per_device": _per_device_bytes(eng._cache),
                "hbm": _hbm_in_use(), "stats": eng.stats()}


@ray_tpu.remote(num_cpus=1)
def host_worker_view() -> dict:
    """What a worker WITHOUT TPU in its lease gets when it imports jax
    while another process holds the chip."""
    import jax

    return {"pid": os.getpid(),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
            "platform": jax.devices()[0].platform}


def reference(spec: dict, seed: int, sequences: list, prompt_lens: list,
              require_tpu: bool) -> dict:
    """Plain full-sequence float32 forward over what the server answered,
    teacher forced: row j of request i holds the logits that pick
    generated token j given everything before it. If every engine token
    is the argmax of its row, a free-running greedy decode of this forward
    produces exactly the engine's tokens (by induction), and vice versa up
    to the first miss. Shares nothing with the engine's paged path: no KV
    cache, no block tables, XLA attention (``use_flash=False``, printed as
    such). The same forward in bf16 measures what bf16 alone moves."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.parallel.sharding import MeshOwner, lower_jit, sharded_init
    from ray_tpu.serve.llm import build_model

    facts = _device_facts(require_tpu)
    counts = _CacheCounts()
    es = spec["engine"]
    tp = int(es.get("tp", 1))
    n_new = spec["new_tokens"]
    m, params = build_model(spec["model"], seed=seed, tp=tp)
    plain16 = type(m)(dataclasses.replace(m.config, use_flash=False))
    plain32 = type(m)(dataclasses.replace(m.config, use_flash=False,
                                          dtype=jnp.float32))
    R, S = len(sequences), spec["ref_pad"]
    toks = np.zeros((R, S), np.int32)
    for i, seq in enumerate(sequences):
        toks[i, :len(seq)] = seq
    starts = np.asarray(prompt_lens, np.int32) - 1

    def gen_logits(plain):
        def fn(params, tokens, starts):
            logits = plain.apply(params, tokens)              # [R, S, V]
            return jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
                row, s, n_new, 0))(logits, starts)            # [R, n_new, V]
        return fn

    def first_logits(params, kc, vc, tokens, length, row):
        return m.paged_prefill(params, {"k": kc, "v": vc}, tokens, length,
                               row)[0]

    def make_cache():
        return m.init_paged_cache(es["num_blocks"], es["block_size"])

    if tp > 1:
        owner = MeshOwner.tp_mesh(tp, name="smoke-ref")
        pspecs = owner.layout.param_specs(m)
        rep, kv = owner.layout.replicated(), owner.layout.kv_cache_blocks()
        gen16, gen32 = (lower_jit(gen_logits(p), owner,
                                  in_specs=(pspecs, rep, rep), out_specs=rep)
                        for p in (plain16, plain32))
        first_fn = lower_jit(first_logits, owner,
                             in_specs=(pspecs, kv, kv, rep, rep, rep),
                             out_specs=rep)
        cache = sharded_init(make_cache, owner, {"k": kv, "v": kv})()
    else:
        gen16, gen32 = jax.jit(gen_logits(plain16)), jax.jit(
            gen_logits(plain32))
        first_fn = jax.jit(first_logits)
        cache = jax.jit(make_cache)()
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(gen32(params, toks, starts))
    forward_s = time.perf_counter() - t0
    noise = float(np.abs(np.asarray(gen16(params, toks, starts)) - ref).max())
    tol = NOISE_FACTOR * noise + F32_FLOOR

    exact = near = wrong = 0
    worst_gap = 0.0
    for i, seq in enumerate(sequences):
        for j, tok in enumerate(seq[prompt_lens[i]:]):
            row = ref[i, j]
            gap = float(row.max() - row[tok])
            worst_gap = max(worst_gap, gap)
            if int(row.argmax()) == tok:
                exact += 1
            elif gap <= tol:
                near += 1
            else:
                wrong += 1
    # logit level: the model's paged prefill of request 0 at the engine's
    # bucket against the plain forward's row for its first new token
    p0 = prompt_lens[0]
    bucket = min(b for b in es["prefill_buckets"] if b >= p0)
    ptoks = np.zeros((1, bucket), np.int32)
    ptoks[0, :p0] = sequences[0][:p0]
    row = np.full((es["max_blocks_per_seq"],), -1, np.int32)
    nb = math.ceil(p0 / es["block_size"])
    row[:nb] = np.arange(nb)
    paged = np.asarray(first_fn(params, cache["k"], cache["v"], ptoks,
                                np.int32(p0), row))
    top2 = np.sort(ref[0, 0])[-2:]
    return {"device": facts, "cache": counts.snapshot(),
            "forward_s": round(forward_s, 2),
            "bf16_noise": noise, "tolerance": tol,
            "tokens_total": exact + near + wrong, "tokens_exact": exact,
            "tokens_near_tie": near, "tokens_wrong": wrong,
            "worst_gap_to_ref_top": round(worst_gap, 5),
            "first_logits_max_abs_diff": float(
                np.abs(paged - ref[0, 0]).max()),
            "first_logits_finite": bool(np.isfinite(paged).all()),
            "ref_top1_minus_top2": float(top2[1] - top2[0]),
            "ref_logit_abs_max": float(np.abs(ref[0, 0]).max()),
            "param_bytes_per_device": _per_device_bytes(params),
            "hbm": _hbm_in_use()}


# ---------------------------------------------------------------------------
# the parent: never touches a jax backend
# ---------------------------------------------------------------------------

def _wait_gone(pid: int, timeout: float = 60.0) -> None:
    """The chip belongs to one process at a time: the next phase may only
    start once the worker that held it has exited."""
    deadline = time.monotonic() + timeout
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() > deadline:
            raise RuntimeError(f"chip worker pid {pid} still alive "
                               f"{timeout}s after shutdown")
        time.sleep(0.1)
    say(f"  chip worker pid {pid} has exited")


def _stream_request(url: str, tokens: list, max_tokens: int) -> dict:
    body = json.dumps({"tokens": tokens, "max_tokens": max_tokens,
                       "stream": True}).encode()
    req = urllib.request.Request(url, body,
                                 {"Content-Type": "application/json"})
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    out: list = []
    t0 = time.perf_counter()
    ttft = None
    with opener.open(req, timeout=1100) as r:
        for line in r:
            if not line.strip():
                continue
            if ttft is None:
                ttft = time.perf_counter() - t0
            out.append(int(json.loads(line)))
    return {"tokens": out, "ttft_s": ttft,
            "total_s": time.perf_counter() - t0}


def phase_train(cfg: dict, chips: int, require_tpu: bool, seed: int) -> dict:
    ray_tpu.init(num_cpus=8, num_tpus=chips)
    try:
        result = JaxTrainer(
            train_loop,
            train_loop_config=dict(cfg, require_tpu=require_tpu, seed=seed),
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(name="chip_smoke_train",
                                 storage_path=OUT_DIR)).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    hist = result.metrics_history
    losses = [h["loss"] for h in hist]
    last = hist[-1]
    say(f"  device (from the train worker): {last['device']}")
    say(f"  GPT params {last['n_params']:,}  batch {last['batch']} x "
        f"seq {last['seq']}  step compile {last['compile_s']} s  "
        f"tpu_custom_call in step: {last['kernel_calls_in_step']}")
    say(f"  step memory_analysis: {last['memory_analysis']}")
    say(f"  hbm after steps: {last['hbm']}")
    say(f"  loss per step: {[round(x, 4) for x in losses]}")
    say(f"  seconds per step: {[h['step_s'] for h in hist]}")
    say(f"  compile cache: {last['cache']}")
    if len(losses) != cfg["steps"]:
        raise RuntimeError(f"{len(losses)} reports for {cfg['steps']} steps")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a fixed batch: {losses}")
    if last["device"]["platform"] == "tpu" \
            and last["kernel_calls_in_step"] < 1:
        raise RuntimeError("the compiled train step holds no Pallas kernel")
    _wait_gone(last["device"]["pid"])
    return {"device": last["device"], "cache": last["cache"],
            "losses": losses, "compile_s": last["compile_s"]}


def phase_serve(spec: dict, chips: int, require_tpu: bool, seed: int) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, spec["vocab"], n).tolist()
               for n in spec["prompt_lens"]]
    ray_tpu.init(num_cpus=8, num_tpus=chips)
    try:
        t0 = time.perf_counter()
        app = serve.deployment(
            name="llm", num_replicas=1,
            ray_actor_options={"num_tpus": chips},
            # the first requests compile under the engine's lock
            health_check_period_s=10.0, health_check_timeout_s=600.0,
        )(SmokeServer).bind(model=spec["model"],
                            engine_config=spec["engine"], seed=seed,
                            require_tpu=require_tpu)
        handle = serve.run(app, timeout=900.0)
        host, port = serve.start_http_proxy()
        say(f"  replica healthy after {time.perf_counter() - t0:.1f} s; "
            f"HTTP proxy at {host}:{port}")
        url = f"http://{host}:{port}/llm?stream=1"
        view = host_worker_view.remote()
        n_new = spec["new_tokens"]
        # one request alone per prefill bucket first: its time to first
        # token is where this replica's compiles show (cold vs warm)
        buckets = sorted(spec["engine"]["prefill_buckets"])
        first = {}
        for i, p in enumerate(prompts):
            first.setdefault(min(b for b in buckets if b >= len(p)), i)
        answers: dict = {}
        for b, i in sorted(first.items()):
            answers[i] = _stream_request(url, prompts[i], n_new)
            say(f"  request {i} alone (bucket {b}, prompt "
                f"{len(prompts[i])}): first token after "
                f"{answers[i]['ttft_s']:.2f} s, all {n_new} after "
                f"{answers[i]['total_s']:.2f} s  (compiles included)")
        rest = [i for i in range(len(prompts)) if i not in answers]
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(rest)) as pool:
            futs = {i: pool.submit(_stream_request, url, prompts[i], n_new)
                    for i in rest}
            for i, f in futs.items():
                answers[i] = f.result()
        say(f"  {len(rest)} concurrent streamed requests answered in "
            f"{time.perf_counter() - t0:.2f} s; first tokens after "
            f"{[round(answers[i]['ttft_s'], 3) for i in rest]} s")
        probe = ray_tpu.get(handle.probe.remote(), timeout=120)
        view = ray_tpu.get(view, timeout=300)
    finally:
        ray_tpu.shutdown()
    say(f"  device (from the replica): {probe['device']}")
    say(f"  a worker without TPU in its lease, meanwhile: {view}")
    say(f"  parameter bytes per device: {probe['param_bytes_per_device']}")
    say(f"  KV pool bytes per device:   {probe['kv_bytes_per_device']}")
    say(f"  hbm per device: {probe['hbm']}")
    say(f"  engine stats: {probe['stats']}")
    say(f"  compile cache: {probe['cache']}")
    say("  this path holds no Pallas kernel: paged prefill calls "
        "mha_reference, paged attention is plain jnp")
    for i, a in sorted(answers.items()):
        if len(a["tokens"]) != spec["new_tokens"]:
            raise RuntimeError(f"request {i}: {len(a['tokens'])} tokens "
                               f"for {spec['new_tokens']} asked")
    if view["platform"] != "cpu" or view["pid"] == probe["device"]["pid"]:
        raise RuntimeError(f"a worker without a TPU lease saw {view}")
    per_dev = probe["param_bytes_per_device"]
    if len(per_dev) != chips or \
            max(per_dev.values()) > 1.5 * sum(per_dev.values()) / chips:
        raise RuntimeError(f"parameters not spread over {chips} devices: "
                           f"{per_dev}")
    _wait_gone(probe["device"]["pid"])
    return {"device": probe["device"], "cache": probe["cache"],
            "prompts": prompts,
            "generated": [answers[i]["tokens"] for i in range(len(prompts))]}


def phase_reference(spec: dict, chips: int, require_tpu: bool, seed: int,
                    served: dict) -> dict:
    sequences = [p + g for p, g in zip(served["prompts"],
                                       served["generated"])]
    ray_tpu.init(num_cpus=8, num_tpus=chips)
    try:
        ref = ray_tpu.get(
            ray_tpu.remote(reference).options(
                num_cpus=1, num_tpus=chips).remote(
                spec, seed, sequences, [len(p) for p in served["prompts"]],
                require_tpu), timeout=1100)
    finally:
        ray_tpu.shutdown()
    say(f"  device (from the reference worker): {ref['device']}")
    say("  reference: float32 forward, attention mha_reference (plain "
        "XLA), not the kernel")
    say(f"  the same forward in bf16 is off by at most "
        f"{ref['bf16_noise']:.5f}; tolerance {NOISE_FACTOR} x that + {F32_FLOOR} = "
        f"{ref['tolerance']:.5f} (|logit| max "
        f"{ref['ref_logit_abs_max']:.3f})")
    say(f"  tokens matched: {ref['tokens_exact']} of {ref['tokens_total']} "
        f"equal the reference's greedy pick; {ref['tokens_near_tie']} "
        f"within tolerance of it; {ref['tokens_wrong']} wrong "
        f"(worst gap {ref['worst_gap_to_ref_top']})")
    say(f"  first-token logits, paged prefill vs reference: max abs diff "
        f"{ref['first_logits_max_abs_diff']:.5f} (reference top1-top2 "
        f"{ref['ref_top1_minus_top2']:.4f})")
    say(f"  parameter bytes per device: {ref['param_bytes_per_device']}")
    say(f"  plain forward {ref['forward_s']} s (compile included); "
        f"compile cache: {ref['cache']}")
    if ref["bf16_noise"] > NOISE_CEILING * ref["ref_logit_abs_max"]:
        raise RuntimeError(f"bf16 drifts too far from float32: {ref}")
    if ref["tokens_wrong"] or not ref["first_logits_finite"] \
            or ref["first_logits_max_abs_diff"] > ref["tolerance"]:
        raise RuntimeError(f"server disagrees with the reference: {ref}")
    _wait_gone(ref["device"]["pid"])
    return ref


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; proves control flow only")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count"
                                   f"={args.chips}")
    plan = (TINY if args.rehearse_cpu else FULL)[args.chips]
    require_tpu = not args.rehearse_cpu
    cache_dir = use_compile_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    say(f"chip_smoke: chips={args.chips} seed={args.seed} "
        f"rehearse_cpu={args.rehearse_cpu}")
    say(f"compile cache directory: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries at start)")
    from ray_tpu.native import load_store_lib, load_wirefast

    say(f"native build from this tree: store "
        f"{'built' if load_store_lib() is not None else 'pure Python'}, "
        f"wire decoder "
        f"{'built' if load_wirefast() is not None else 'pure Python'}")
    t_all = time.perf_counter()
    seconds: dict = {}
    facts = []
    caches = {}

    def run(name, fn, *a):
        say(f"[{name}]")
        t0 = time.perf_counter()
        out = fn(*a)
        seconds[name] = round(time.perf_counter() - t0, 1)
        say(f"  {name}: {seconds[name]} s")
        facts.append(out["device"])
        caches[name] = out["cache"]
        return out

    if "train" in plan:
        run("train", phase_train, plan["train"], args.chips, require_tpu,
            args.seed)
    served = run("serve", phase_serve, plan["serve"], args.chips,
                 require_tpu, args.seed)
    run("reference", phase_reference, plan["serve"], args.chips, require_tpu,
        args.seed, served)

    devices = {(f["platform"], f["kind"], f["count"]) for f in facts}
    if len(devices) != 1:
        raise RuntimeError(f"phases disagree about the device: {facts}")
    platform, kind, count = devices.pop()
    if count != args.chips:
        raise RuntimeError(f"asked for {args.chips} chips, workers saw "
                           f"{count}")
    jax_mod = sys.modules.get("jax")
    touched = bool(jax_mod and jax_mod._src.xla_bridge._backends)
    say(f"parent initialised a jax backend: {touched}")
    if touched:
        raise RuntimeError("the parent touched a jax backend")
    say(f"seconds per phase: {seconds}  total "
        f"{time.perf_counter() - t_all:.1f}")
    say(f"compile cache {cache_dir}: " + "; ".join(
        f"{k} {v['hits']} hits / {v['misses']} misses"
        for k, v in caches.items()))
    device = {"platform": platform, "kind": kind, "count": count}
    if args.rehearse_cpu:
        say(json.dumps({"ok": False, "rehearsal_passed": True,
                        "device": device}))
        return 0
    if platform != "tpu":
        raise RuntimeError(f"not a TPU: {device}")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
