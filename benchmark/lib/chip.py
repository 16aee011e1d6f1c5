"""Code that runs in the one process that holds the chip: the serving
replica's subclass and the trainer's loop function. Process discipline and
the reference check are copied from chip_smoke.py (``_device_facts``,
``_CacheCounts``, ``SmokeServer``, ``reference``): the parent never
initialises a jax backend, so device facts, the profiler and the float32
reference can only be driven from here.

``BenchServer`` adds read-only methods to ``LLMServer`` and wraps
``__call__`` only to timestamp a request's entry and its first token.
"""
from __future__ import annotations

import collections
import importlib
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.serve.llm import LLMServer

from .spec import load_family, objective_of

# correctness yardstick, as chip_smoke.py: the server computes in bf16, so
# it is held to the float32 reference within NOISE_FACTOR times the largest
# distance the run measures between the same plain forward in bf16 and in
# float32 (two logits, each off by that much, can swap places); bf16 itself
# may not drift further than NOISE_CEILING of the largest reference logit
NOISE_FACTOR = 2.0
NOISE_CEILING = 0.2
F32_FLOOR = 1e-4
MEAN_SIGMAS = 4.0       # standard errors allowed on a mean (training loss)


def device_facts(require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs), "pid": os.getpid(),
             "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
    if require_tpu and facts["platform"] != "tpu":
        raise RuntimeError(f"no TPU: jax found {facts}")
    return facts


def memory_facts(program_temp_bytes: int = 0) -> dict:
    """Peak memory on the fullest chip. The runtime's ``peak_bytes_in_use``
    counts live buffers only on this runtime (PERF.md section 7): a
    program's temporaries are reserved apart and show in no counter. Where
    the caller holds the compiled program it hands over the temporaries the
    compiler allots it (``memory_analysis().temp_size_in_bytes``, the figure
    the compiler's own HBM check adds to the arguments); the peak is then
    buffers + temporaries, else the runtime's figure alone, a floor."""
    import jax

    buffers = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        buffers = max(buffers, int(st.get("peak_bytes_in_use",
                                          st.get("bytes_in_use", 0))))
    return {"memory_peak_bytes": buffers + int(program_temp_bytes),
            "memory_buffers_peak_bytes": buffers,
            "memory_program_temp_bytes": int(program_temp_bytes)}


class CompileCounts:
    """Counts jax's own compile and persistent-cache events in this
    process, so a compile inside the window shows."""

    def __init__(self):
        import jax.monitoring

        self.n = collections.Counter()
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on_d)

    def _on(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            self.n[event.rsplit("/", 1)[1]] += 1

    def _on_d(self, event: str, _secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.n["backend_compiles"] += 1

    def snapshot(self) -> dict:
        return {"requests": self.n["compile_requests_use_cache"],
                "hits": self.n["cache_hits"],
                "misses": self.n["cache_misses"],
                "backend_compiles": self.n["backend_compiles"]}


class Profiler:
    """jax.profiler around a stretch of the window: device planes and
    TraceAnnotations only, no Python call tracing."""

    def start(self, trace_dir: str) -> float:
        import jax

        os.makedirs(trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        return time.time()

    def stop(self) -> float:
        import jax

        jax.profiler.stop_trace()
        return time.time()


def _reference_module(name: str):
    return importlib.import_module(f"benchmark.reference.{name}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

class BenchServer(LLMServer):
    """LLMServer as deployed by a serving cell, plus what only the chip's
    process can tell: device facts, compile counts, ``stats()``, per
    request the replica-side time to the first token, the profiler, and
    the float32 reference check. Warm-up drives every prefill bucket and
    the decode program once through ``engine.add_request`` before the
    replica reports healthy, so nothing compiles inside the window."""

    def __init__(self, model: Any, engine_config: Dict[str, Any],
                 seed: int = 0, require_tpu: bool = True,
                 reference: str = ""):
        self._t_enter = time.time()
        self._init_error: Optional[str] = None
        self._req_lock = threading.Lock()
        self._req_times: Dict[str, list] = {}
        self._profiler = Profiler()
        self._reference = reference
        try:
            self._build(model, engine_config, seed, require_tpu)
        except BaseException:  # noqa: BLE001 - reported, then re-raised by the parent
            # a constructor that raises leaves the controller waiting for a
            # replica that never answers (three health-check timeouts); the
            # replica comes up instead and hands the parent the traceback
            import traceback

            self._init_error = traceback.format_exc()

    def _build(self, model, engine_config, seed, require_tpu) -> None:
        self._facts = device_facts(require_tpu)
        self._t_jax_up = time.time()
        self._counts = CompileCounts()
        super().__init__(model=model, engine_config=engine_config, seed=seed)
        import jax

        jax.block_until_ready(self.engine.params)
        self._t_built = time.time()
        eng = self.engine
        room = eng.max_seq_len
        for b in eng.buckets:
            # a prompt that pads to exactly this bucket, two tokens so the
            # decode program runs as well
            p = max(1, min(b, room - 2))
            eng.add_request([1] * p, max_tokens=2).tokens()
        self._t_warm = time.time()
        self._compiles_at_warm = self._counts.snapshot()

    def check_health(self) -> None:
        if self._init_error is None:
            super().check_health()

    # -- request path: timestamps only ---------------------------------------

    def __call__(self, payload: Dict[str, Any]):
        tag = payload.get("bench_tag") if isinstance(payload, dict) else None
        if tag is None or not payload.get("stream"):
            return super().__call__(payload)
        t_in = time.time()
        gen = super().__call__(payload)
        return self._stamp_first(gen, tag, t_in)

    def _stamp_first(self, gen, tag: str, t_in: float):
        first = True
        for tok in gen:
            if first:
                with self._req_lock:
                    self._req_times[tag] = [t_in, time.time()]
                first = False
            yield tok

    # -- read-only probes -------------------------------------------------------

    def bench_setup(self) -> dict:
        if self._init_error is not None:
            return {"init_error": self._init_error}
        return {"device": self._facts, "t_enter": self._t_enter,
                "t_jax_up": self._t_jax_up, "t_built": self._t_built,
                "t_warm": self._t_warm, "compiles": self._compiles_at_warm,
                "buckets": list(self.engine.buckets),
                "max_prompt": self.engine.max_prompt,
                "max_seq_len": self.engine.max_seq_len,
                "vocab": int(self.engine.model.config.vocab_size)}

    def bench_stats(self) -> dict:
        return dict(self.engine.stats(), t=time.time())

    def trace_start(self, trace_dir: str) -> float:
        return self._profiler.start(trace_dir)

    def trace_stop(self) -> float:
        return self._profiler.stop()

    def bench_finish(self, sample: List[dict], ref_pad: int) -> dict:
        """After the window. ``sample``: [{"prompt": [...], "generated":
        [...]}]; -> device facts, counters, per-request replica times and
        the verdict of the float32 reference on the sample."""
        with self._req_lock:
            req_times = dict(self._req_times)
        out = {"device": dict(self._facts, **memory_facts()),
               "stats": self.engine.stats(),
               "compiles": self._counts.snapshot(),
               "compiles_at_warm": self._compiles_at_warm,
               "req_times": req_times}
        t0 = time.time()
        out["reference"] = serve_reference_check(
            self.engine, self._reference, sample, ref_pad)
        out["reference"]["seconds"] = time.time() - t0
        out["compiles_after_reference"] = self._counts.snapshot()
        return out


def serve_reference_check(engine, reference: str, sample: List[dict],
                          ref_pad: int) -> dict:
    """Teacher-forces the plain float32 forward over prompt + answer of
    each sampled request (no cache, no block table, plain attention): row
    j holds the logits that pick generated token j. Every served token must
    be the reference's greedy pick or lie within the measured tolerance of
    it. For the first position the engine's own prefill program is run
    again on the prompt and its logits are compared with the reference's
    row. Runs while the engine is idle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = _reference_module(reference)
    model, params = engine.model, engine.params
    kw = ref.model_kwargs(model.config)
    n_new = max(len(s["generated"]) for s in sample)
    shardings = None
    if engine.owner is not None:
        pspecs = engine.owner.layout.param_specs(model)
        rep = engine.owner.sharding(engine.owner.layout.replicated())
        shardings = ({n: engine.owner.sharding(pspecs[n]) for n in params},
                     rep, rep)

    def rows(dtype):
        def fn(params, tokens, start):
            h = ref.hidden(params, tokens, dtype=dtype, **kw)     # [1,S,D]
            h = jax.lax.dynamic_slice_in_dim(h[0], start, n_new, 0)
            return ref.head(params, h, dtype)                      # [n,V]
        if shardings is None:
            return jax.jit(fn)
        return jax.jit(fn, in_shardings=shardings, out_shardings=shardings[1])

    f32, b16 = rows(jnp.float32), rows(jnp.bfloat16)
    exact = near = wrong = 0
    noise = worst_gap = first_diff = logit_max = 0.0
    finite = True
    cfg = engine.config
    for s in sample:
        p, g = len(s["prompt"]), list(s["generated"])
        toks = np.zeros((1, ref_pad), np.int32)
        seq = list(s["prompt"]) + g
        toks[0, :len(seq)] = seq
        start = np.int32(p - 1)
        with jax.default_matmul_precision("highest"):
            r32 = np.asarray(f32(params, toks, start))
        r16 = np.asarray(b16(params, toks, start))
        noise = max(noise, float(np.abs(r16[:len(g)] - r32[:len(g)]).max()))
        logit_max = max(logit_max, float(np.abs(r32[:len(g)]).max()))
        s["_ref"] = r32
    tol = NOISE_FACTOR * noise + F32_FLOOR
    for s in sample:
        r32, g = s.pop("_ref"), list(s["generated"])
        for j, tok in enumerate(g):
            gap = float(r32[j].max() - r32[j][tok])
            worst_gap = max(worst_gap, gap)
            if int(r32[j].argmax()) == tok:
                exact += 1
            elif gap <= tol:
                near += 1
            else:
                wrong += 1
        # first position, logit level: the engine's own prefill program
        p = len(s["prompt"])
        bucket = next(b for b in engine.buckets if b >= p)
        ptoks = np.zeros((1, bucket), np.int32)
        ptoks[0, :p] = s["prompt"]
        nb = math.ceil(p / cfg.block_size)
        with engine._lock:
            blocks = engine.pool.alloc(nb)
            if blocks is None:
                raise RuntimeError("engine not idle: no blocks for the "
                                   "reference's prefill")
            try:
                row = np.full((cfg.max_blocks_per_seq,), -1, np.int32)
                row[:nb] = blocks
                logits, _, _ = engine._prefill_fn(
                    params, engine._cache["k"], engine._cache["v"],
                    jnp.asarray(ptoks), jnp.int32(p), jnp.asarray(row))
                paged = np.asarray(logits)
            finally:
                engine.pool.free(blocks)
        finite = finite and bool(np.isfinite(paged).all())
        first_diff = max(first_diff, float(np.abs(paged - r32[0]).max()))
    ok = (wrong == 0 and finite and first_diff <= tol
          and noise <= NOISE_CEILING * logit_max)
    return {"ok": bool(ok), "sampled": len(sample),
            "tokens_total": exact + near + wrong, "tokens_exact": exact,
            "tokens_near_tie": near, "tokens_wrong": wrong,
            "bf16_noise": noise, "tolerance": tol,
            "bf16_noise_limit": NOISE_CEILING * logit_max,
            "worst_gap_to_ref_top": worst_gap,
            "first_logits_max_abs_diff": first_diff,
            "first_logits_finite": finite, "ref_logit_abs_max": logit_max}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def make_train_step(model, tx, objective=None):
    """The step a training cell runs: the family's ``objective(params,
    tokens) -> scalar`` where its file states one (``spec.objective_of``),
    else the next-token loss (targets are the tokens rolled by one, on the
    device); adamw update, parameters and optimizer state donated. An
    objective gets the parameters and the batch and nothing else. Named,
    so the trace finds it."""
    import jax
    import jax.numpy as jnp
    import optax

    def bench_train_step(params, opt_state, tokens):
        if objective is None:
            # the targets are made outside what is differentiated: the
            # program, to its operations' names, that every cell before
            # the hook compiled
            targets = jnp.roll(tokens, -1, axis=1)
            loss, grads = jax.value_and_grad(model.loss)(params, tokens,
                                                         targets)
        else:
            loss, grads = jax.value_and_grad(objective)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        return loss, optax.apply_updates(params, updates), opt_state

    return bench_train_step


def make_optimizer(spec: dict):
    import optax

    if spec.get("name", "adamw") != "adamw":
        raise ValueError(f"unknown optimizer {spec.get('name')!r}")
    return optax.adamw(float(spec.get("lr", 3e-4)),
                       weight_decay=float(spec.get("weight_decay", 0.1)))


def train_loop(config: dict) -> None:
    """Loop function handed to ``JaxTrainer``: builds the model on the
    device from the seed, warms the step up, trains for ``seconds`` on a
    fresh host-drawn batch per step, then checks itself against the plain
    float32 loss. Everything the parent needs rides in ``train.report``."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train

    from .traffic import TokenFeed

    t_enter = time.time()
    facts = device_facts(config["require_tpu"])
    t_jax_up = time.time()
    counts = CompileCounts()
    tr = config["trainer"]
    B, S = int(tr["batch"]), int(tr["seq"])
    mesh = train.get_mesh()
    family = load_family(config["model"]["family"])
    ref = _reference_module(config["reference"])
    objective = objective_of(family, ref)    # half an objective fails here
    model = family.build(config["model"])
    init = jax.jit(model.init, out_shardings=model.param_shardings(mesh))
    params = init(jax.random.PRNGKey(config["seed"] % (1 << 31)))
    tx = make_optimizer(tr.get("optimizer", {}))
    opt_state = jax.jit(tx.init)(params)
    step = jax.jit(make_train_step(model, tx, objective and objective(model)),
                   donate_argnums=(0, 1))
    data_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
    feed = TokenFeed(config["traffic"], config["seed"],
                     int(model.config.vocab_size), B, S)

    def put(i):
        return jax.device_put(feed.batch(i), data_sharding)

    # compiled ahead (or read from the cache): the program the jitted call
    # would build, held so that the temporaries the compiler allots it can
    # be added to the runtime's count of buffers
    program = step.lower(params, opt_state, put(0)).compile()
    temp_bytes = getattr(program.memory_analysis(), "temp_size_in_bytes", 0)
    jax.block_until_ready(params)
    t_built = time.time()

    # warm-up: the first two steps of the job; their losses count as steps
    # 0 and 1
    losses = []
    for i in range(2):
        loss, params, opt_state = program(params, opt_state, put(i))
        losses.append(loss)
    jax.block_until_ready(losses)
    t_warm = time.time()
    compiles_at_warm = counts.snapshot()

    seconds = float(config["seconds"])
    lead = int(tr.get("host_lead_steps", 2))
    every = int(tr.get("report_every", 20))
    profiler = Profiler()
    trace_at = (seconds * 0.4, float(config.get("trace_seconds", 3.0))) \
        if config.get("trace_dir") else None
    trace_span = None
    t0w, t0 = time.time(), time.perf_counter()
    i = len(losses)
    first_in_window = i
    while time.perf_counter() - t0 < seconds:
        if trace_at and trace_span is None \
                and time.perf_counter() - t0 >= trace_at[0]:
            trace_span = [profiler.start(config["trace_dir"]), None]
        loss, params, opt_state = program(params, opt_state, put(i))
        losses.append(loss)
        i += 1
        # never more than `lead` steps ahead of the device: waits for a
        # step that is already behind the one running, so the device is
        # not stalled and the window ends within `lead` steps of its time
        losses[i - 1 - lead].block_until_ready()
        if trace_span and trace_span[1] is None and \
                time.perf_counter() - t0 >= trace_at[0] + trace_at[1]:
            trace_span[1] = profiler.stop()
        if (i - first_in_window) % every == 0:
            train.report({"step": i - 1 - lead,
                          "loss": float(losses[i - 1 - lead])})
    jax.block_until_ready(losses)
    elapsed = time.perf_counter() - t0
    if trace_span and trace_span[1] is None:
        trace_span[1] = profiler.stop()
    n_steps = i - first_in_window
    compiles_at_end = counts.snapshot()
    all_losses = [float(x) for x in losses]
    memory = memory_facts(temp_bytes or 0)
    # after the window, for the reference: one more step from the TRAINED
    # parameters, kept on the host meanwhile because the step donates them
    # (a second copy does not fit on the chip beside the step's temporaries)
    trained = jax.device_get(params)
    loss_end, params, opt_state = program(params, opt_state, put(i))
    loss_end = float(loss_end)
    del params, opt_state, loss, losses
    t_ref0 = time.time()
    rows = int(tr.get("reference_rows", 4))
    seed_key = jax.random.PRNGKey(config["seed"] % (1 << 31))
    # where the model counts the rows its held experts work, that count at
    # the two points the reference looks at: one more program, after the
    # window, and none for a model that has no such count
    stats = jax.jit(model.routing_stats) \
        if hasattr(model, "routing_stats") else None
    checks, held = [], []

    def look(at, batch, step_loss):
        checks.append(train_reference_check(ref, model, at, batch,
                                            step_loss, rows))
        if stats is not None:
            held.append(np.asarray(stats(at, batch)).tolist())

    look(init(seed_key), feed.batch(0), all_losses[0])
    look(jax.device_put(trained, model.param_shardings(mesh)),
         feed.batch(i), loss_end)
    n_params = int(model.num_params())
    from_sizes = int(ref.num_params(config["sizes"],
                                    int(model.config.padded_vocab)))
    verdict = {"ok": bool(all(c["ok"] for c in checks)
                          and n_params == from_sizes),
               "first_step": checks[0], "after_window": checks[1],
               "after_window_step": i, "n_params": n_params,
               "n_params_from_sizes": from_sizes,
               "seconds": time.time() - t_ref0}
    train.report({
        "final": True, "device": dict(facts, **memory),
        "t_enter": t_enter, "t_jax_up": t_jax_up, "t_built": t_built,
        "t_warm": t_warm, "t_window": t0w, "elapsed_s": elapsed,
        "steps": n_steps, "tokens": n_steps * B * S, "batch": B, "seq": S,
        "losses": all_losses, "first_in_window": first_in_window,
        "compiles_at_warm": compiles_at_warm,
        "compiles_at_end": compiles_at_end,
        "compiles_after_reference": counts.snapshot(),
        "trace_span": trace_span, "reference": verdict,
        **({"held_rows": dict(zip(("first_step", "after_window"), held))}
           if held else {})})


def mean_loss_tolerance(n16, n32) -> dict:
    """How far a bf16 computation's MEAN loss may lie from the float32
    reference's, measured the way the compared quantity is formed: the
    plain forward's per-token losses in bf16 (``n16``) and in float32
    (``n32``) differ by d; the means differ by mean(d), known only to its
    standard error std(d)/sqrt(N). Another bf16 route (flash kernel, remat,
    fused matmuls) has its own shift and its own draw of the noise, so the
    tolerance is NOISE_FACTOR x (|mean(d)| + MEAN_SIGMAS standard errors)."""
    import numpy as np

    d = np.asarray(n16, np.float64) - np.asarray(n32, np.float64)
    shift = float(abs(d.mean()))
    se = float(d.std() / math.sqrt(d.size))
    return {"tolerance": NOISE_FACTOR * (shift + MEAN_SIGMAS * se)
            + F32_FLOOR,
            "bf16_mean_shift": shift, "bf16_mean_se": se,
            "bf16_noise": float(np.abs(d).mean())}


def train_reference_check(ref, model, params, tokens, loss: float,
                          rows_per_call: int) -> dict:
    """``loss``, as the step program computed it from ``params`` on the
    batch ``tokens``, against the plain float32 loss from the same
    parameters on the same batch (the mean of the reference's ``losses``
    where it states the family's objective, else of the next-token terms),
    within ``mean_loss_tolerance`` as this run measures it on that batch,
    ``rows_per_call`` rows at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kw = ref.model_kwargs(model.config)
    stated = getattr(ref, "losses", None)

    def terms(dtype):
        """The objective's per-position terms [b, n] whose mean is the
        loss: the reference's own ``losses`` where it states them, else
        the next-token terms from its ``hidden`` and ``head``."""
        def fn(params, toks):
            if stated is not None:
                return stated(params, toks, dtype=dtype, **kw)
            targets = jnp.roll(toks, -1, axis=1)
            h = ref.hidden(params, toks, dtype=dtype, **kw)
            logits = ref.head(params, h, dtype).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            return lse - gold                                   # [b, S]
        return jax.jit(fn)

    f32, b16 = terms(jnp.float32), terms(jnp.bfloat16)
    n32, n16 = [], []
    for lo in range(0, tokens.shape[0], rows_per_call):
        chunk = tokens[lo:lo + rows_per_call]
        with jax.default_matmul_precision("highest"):
            n32.append(np.asarray(f32(params, chunk)))
        n16.append(np.asarray(b16(params, chunk)))
    n32, n16 = np.concatenate(n32), np.concatenate(n16)
    ref_loss = float(n32.mean())
    out = mean_loss_tolerance(n16, n32)
    out.update(loss=loss, reference_loss=ref_loss,
               abs_diff=abs(loss - ref_loss),
               bf16_noise_limit=NOISE_CEILING * ref_loss)
    out["ok"] = bool(out["abs_diff"] <= out["tolerance"]
                     and out["bf16_noise"] <= out["bf16_noise_limit"])
    return out
