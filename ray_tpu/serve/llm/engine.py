"""LLMEngine — continuous (iteration-level) batching over a paged KV cache.

The modern LLM-serving core (vLLM/Orca-style, PAPERS.md) on this
runtime's models: one engine owns a block-pool KV cache
(`model.init_paged_cache`) and runs a scheduler loop where every
iteration (a) admits waiting prompts into the running batch under a
prefill-token budget and the block budget, (b) runs ONE fixed-shape
decode step for every resident sequence, (c) retires finished sequences
(EOS / max_tokens) and frees their blocks, and (d) preempts the
latest-admitted sequence back to the waiting queue when the pool can't
grow a running one — greedy decode makes the requeued continuation
produce exactly the tokens the unpreempted run would have.

XLA compiles a handful of programs, not one per request: decode is a
single `(max_batch,)` program; prefill compiles once per bucket in
`prefill_buckets` (prompts pad up to the nearest bucket).

With ``tp > 1`` the same programs lower under a per-replica device mesh
(parallel.sharding MeshOwner + SpecLayout, docs/SHARDING.md): attention
heads, FFN hidden, and vocab shard on the ``tp`` axis, the paged KV
pool block-shards per chip (BlockPool mirrors the layout and balances
allocation across chips), and greedy decode is token-identical to
tp=1 — the scheduler, streams, and serve integration are unchanged.

    engine = LLMEngine(model, params, EngineConfig(max_batch=8))
    engine.start()                       # background scheduler thread
    stream = engine.add_request([1, 5, 9], max_tokens=32)
    for tok in stream: ...               # sync; `async for` also works

Metrics (OBSERVABILITY.md schema): `ray_tpu_llm_queue_depth`,
`ray_tpu_llm_kv_blocks_used`, `ray_tpu_llm_tokens_per_s` gauges and
`ray_tpu_llm_ttft_seconds` / `ray_tpu_llm_tpot_seconds` histograms, all
tagged by engine name — shipped to the head scrape by the standard
worker delta path and consumed by the serve autoscaler via the
replica's queue_depth (replica.py / controller.py).
"""
from __future__ import annotations

import collections
import itertools
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...perf.jaxbuild import install_jax_spans
from ...perf.recorder import get_recorder as _get_recorder
from ...util import metrics as _metrics
from ...util import tracing as _tracing
from .kv_cache import BlockPool, blocks_for_tokens

_FLREC = _get_recorder()

_G_QUEUE = _metrics.Gauge(
    "ray_tpu_llm_queue_depth",
    "LLM engine requests waiting + running", tag_keys=("engine",))
_G_BLOCKS = _metrics.Gauge(
    "ray_tpu_llm_kv_blocks_used",
    "KV-cache pool blocks currently allocated (chip label: per-chip "
    "occupancy of a tp-sharded pool; unlabeled: engine total)",
    tag_keys=("engine", "chip"))
_G_TOKPS = _metrics.Gauge(
    "ray_tpu_llm_tokens_per_s",
    "generated tokens/s over the trailing window", tag_keys=("engine",))
_H_TTFT = _metrics.Histogram(
    "ray_tpu_llm_ttft_seconds",
    "time to first token (submission -> first emit, queue wait included)",
    tag_keys=("engine",))
_H_INTAKE = _metrics.Histogram(
    "ray_tpu_llm_intake_wait_seconds",
    "time a request waited for the engine's lock at intake (submission "
    "-> queued), before any queueing or prefill",
    boundaries=_metrics.FAST_BOUNDARIES, tag_keys=("engine",))
_H_TPOT = _metrics.Histogram(
    "ray_tpu_llm_tpot_seconds",
    "time per output token during decode (inter-token latency)",
    boundaries=_metrics.FAST_BOUNDARIES, tag_keys=("engine",))
_C_PREFIX_HIT = _metrics.Counter(
    "ray_tpu_llm_prefix_hit_tokens",
    "prompt tokens whose KV came from the radix prefix cache (block-"
    "table reuse, no prefill compute)", tag_keys=("engine",))
_C_PREFIX_MISS = _metrics.Counter(
    "ray_tpu_llm_prefix_miss_tokens",
    "prompt tokens that paid prefill compute (cold or divergent)",
    tag_keys=("engine",))
_G_HIT_RATE = _metrics.Gauge(
    "ray_tpu_llm_cache_hit_rate",
    "cumulative prefix-cache hit rate: hit_tokens / (hit + miss)",
    tag_keys=("engine",))
_C_PREEMPT = _metrics.Counter(
    "ray_tpu_llm_preemptions_total",
    "sequences preempted-and-requeued on KV pool exhaustion",
    tag_keys=("engine",))


@dataclass
class EngineConfig:
    """Scheduler + cache knobs (docs/LLM_SERVE.md)."""
    block_size: int = 16
    num_blocks: int = 128
    max_batch: int = 8                 # decode program batch (slots)
    max_blocks_per_seq: int = 16       # block-table width (M)
    # tensor parallelism: one replica = one mesh spanning tp chips. The
    # prefill/decode programs lower under the mesh with attention heads
    # + FFN sharded on `tp` and the KV pool block-sharded per chip
    # (docs/SHARDING.md); num_blocks must be a multiple of tp
    tp: int = 1
    # prefill-token admission budget per scheduler iteration; at least
    # one waiting request is always admitted so a long prompt can't starve
    max_prefill_tokens_per_step: int = 256
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128, 256)
    eos_id: Optional[int] = None       # engine-wide default EOS
    idle_sleep_s: float = 0.002        # background-loop sleep when idle
    # radix prefix cache (prefix_cache.py, docs/LLM_SERVE.md "Prefix
    # caching & sessions"): retired/preempted sequences leave their
    # full-block prompt+completion KV indexed in a radix tree; a new
    # request reuses the longest cached prefix (refcounted block
    # sharing, copy-on-write at a mid-block divergence) and prefills
    # only its suffix. LRU-evicted under pool pressure. Greedy decode
    # keeps outputs token-identical to cache-off.
    prefix_cache: bool = False

    @property
    def max_context(self) -> int:
        """Longest context a sequence can hold in its block table."""
        return self.max_blocks_per_seq * self.block_size


class TokenStream:
    """Per-request token iterator — sync (`for tok in stream`) and async
    (`async for tok in stream`) views over the same queue. The engine
    pushes tokens as the scheduler emits them; a sentinel closes the
    stream with `finish_reason` in {"eos","length","error"}."""

    _DONE = object()

    def __init__(self, request_id: str):
        self.request_id = request_id
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self._q: "queue.Queue" = queue.Queue()
        self._consumed_done = False

    # engine side ----------------------------------------------------------
    def _put(self, tok: int) -> None:
        self._q.put(tok)

    def _finish(self, reason: str,
                error: Optional[BaseException] = None) -> None:
        self.finish_reason = reason
        self.error = error
        self._q.put(self._DONE)

    # consumer side --------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self) -> int:
        return self.next()

    def next(self, timeout: Optional[float] = 300.0) -> int:
        if self._consumed_done:
            raise StopIteration
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"{self.request_id}: no token within {timeout}s") from None
        if item is self._DONE:
            self._consumed_done = True
            if self.error is not None:
                raise self.error
            raise StopIteration
        return item

    def __aiter__(self):
        return self

    async def __anext__(self) -> int:
        from ..handle import executor_anext

        return await executor_anext(self.next)

    def tokens(self, timeout: Optional[float] = 300.0) -> List[int]:
        """Drain to completion -> the full completion, in order."""
        out = []
        while True:
            try:
                out.append(self.next(timeout=timeout))
            except StopIteration:
                return out


@dataclass
class Request:
    request_id: str
    prompt: List[int]                  # context to (re-)prefill
    max_tokens: int
    eos_id: Optional[int]
    stream: TokenStream
    submitted_at: float
    generated: List[int] = field(default_factory=list)
    first_token_at: Optional[float] = None
    preemptions: int = 0
    # distributed tracing: (trace_id, parent_span_id) captured at
    # add_request — the scheduler thread emits lifecycle spans against
    # it (contextvars can't cross the submit->scheduler thread hop).
    # Wall-clock stamps ride along because Span times are time.time()
    # while the engine's latency math stays on perf_counter.
    trace_ctx: Optional[tuple] = None
    submitted_wall: float = 0.0
    # last enqueue (submit or preempt), stamped once the engine's lock
    # is HELD: submitted_wall -> queued_wall is the wait for the lock
    # (span llm.intake_wait), queued_wall -> first token is queue and
    # prefill (span llm.admit)
    queued_wall: float = 0.0
    cache_hit_tokens: int = 0
    cache_miss_tokens: int = 0


class _Sequence:
    """A running request's batch-slot state."""

    __slots__ = ("req", "slot", "blocks", "seq_len", "pending",
                 "last_emit_at", "tokens", "dec_count", "dec_wall0")

    def __init__(self, req: Request, slot: int, blocks: List[int],
                 seq_len: int, pending: int,
                 tokens: Optional[List[int]] = None):
        self.req = req
        self.slot = slot
        self.blocks = blocks           # pool block ids, table order
        self.seq_len = seq_len         # tokens whose KV is in cache
        self.pending = pending         # emitted token awaiting its KV write
        self.last_emit_at = time.perf_counter()
        self.dec_count = 0             # decode steps since last span flush
        self.dec_wall0 = 0.0
        # the token identity of the resident KV, position by position —
        # what the prefix cache indexes at retire/preempt time
        self.tokens: List[int] = list(tokens if tokens is not None
                                      else req.prompt)


# The four device programs of an engine. Their names are part of the
# measurement: a profiler trace shows a jitted program as
# ``jit_<__name__>``, and the benchmark's readers find ``jit__decode``,
# ``jit__prefill``, ``jit__extend`` and ``jit__cow`` by name
# (tests/test_tracing_names.py pins them on both lowering paths).

def _engine_programs(model) -> Dict[str, Any]:
    def _decode(params, kc, vc, tokens, positions, rows, active):
        logits, cache = model.paged_decode_step(
            params, {"k": kc, "v": vc}, tokens, positions, rows, active)
        return logits, cache["k"], cache["v"]

    def _prefill(params, kc, vc, tokens, length, block_row):
        logits, cache = model.paged_prefill(
            params, {"k": kc, "v": vc}, tokens, length, block_row)
        return logits, cache["k"], cache["v"]

    def _extend(params, kc, vc, tokens, start, length, block_row):
        logits, cache = model.paged_prefill_extend(
            params, {"k": kc, "v": vc}, tokens, start, length,
            block_row)
        return logits, cache["k"], cache["v"]

    def _cow(kc, vc, src, dst):
        # duplicate one pool block (copy-on-write divergence point):
        # block axis is axis 1 of the [L, N, Bs, KH, hd] cache
        return (kc.at[:, dst].set(kc[:, src]),
                vc.at[:, dst].set(vc[:, src]))

    programs = {"_decode": _decode, "_prefill": _prefill,
                "_extend": _extend, "_cow": _cow}
    for name, fn in programs.items():
        fn.__name__ = fn.__qualname__ = name      # pinned, see above
    return programs


_PHASE_OF = (("rtpu.llm.prefill.", "prefill"), ("rtpu.llm.extend.", "prefill"),
             ("rtpu.llm.decode.", "decode"), ("rtpu.llm.retire", "retire"),
             ("rtpu.llm.admit", "admit"), ("rtpu.llm.step", "admit"))


def step_phases(events: List[dict]) -> Tuple[List[float], Dict[str, float]]:
    """One engine's flight-recorder events (ring order) -> (wall ms of
    each ``rtpu.llm.step`` span, ms by phase over those steps). A span is
    charged its SELF time, its duration less that of the spans it caused
    (``parent``), so the phases sum to the steps' wall time: ``admit`` is
    scheduling net of the prefills and retires it triggered plus the
    step's own bookkeeping, ``prefill`` the prefill and extend programs
    (dispatch and wait), ``decode`` the four decode spans net of retires,
    ``retire`` the retires. Spans outside a step (a lock wait, a retire
    from ``_fail_all``) have no parent and are left out."""
    step_ms: List[float] = []
    phases = {"admit": 0.0, "prefill": 0.0, "decode": 0.0, "retire": 0.0}
    children: Dict[str, list] = {}   # parent kind -> [(start, s)] closed
    pending: List[tuple] = []        # (start, phase, own s) since a step
    for ev in events:
        if "dur" not in ev or not ev["kind"].startswith("rtpu.llm."):
            continue
        kind, ts, dur = ev["kind"], ev["ts"], ev["dur"]
        is_step = kind == "rtpu.llm.step"
        if not (is_step or ev["parent"].startswith("rtpu.llm.")):
            continue
        # a span closes after its children, so they are waiting for it;
        # one that began before it belongs to a step that left no event
        own = max(0.0, dur - sum(d for t, d in children.pop(kind, ())
                                 if t >= ts))
        phase = next((ph for prefix, ph in _PHASE_OF
                      if kind.startswith(prefix)), None)
        if is_step:
            step_ms.append(round(dur * 1e3, 4))
            phases[phase] += own
            for t, ph, o in pending:
                if t >= ts and ph is not None:
                    phases[ph] += o
            pending.clear()
        else:
            children.setdefault(ev["parent"], []).append((ts, dur))
            pending.append((ts, phase, own))
    return step_ms, {k: round(v * 1e3, 3) for k, v in phases.items()}


class _LockWait:
    """``with engine._locked("intake")``: takes the engine's lock on the
    CALLER's thread inside a ``rtpu.llm.lock_wait.<who>`` span (from
    asking for the lock to having it) and counts the wait, under the
    lock, into ``stats()``. -> the seconds waited."""

    __slots__ = ("_eng", "_who", "_kind")

    def __init__(self, eng: "LLMEngine", who: str):
        self._eng, self._who = eng, who
        self._kind = "rtpu.llm.lock_wait." + who

    def __enter__(self) -> float:
        eng = self._eng
        with _FLREC.span(self._kind, eng.name) as sp:
            # released in __exit__: this object IS the `with` block
            # graftcheck: disable=GC006,GC030
            eng._lock.acquire()
        c = eng._lock_waits[self._who]
        c[0] += 1
        c[1] += sp.dur
        c[2] = max(c[2], sp.dur)
        return sp.dur

    def __exit__(self, *exc) -> None:
        self._eng._lock.release()


class LLMEngine:
    """One replica's inference engine. Thread-safe: `add_request` may be
    called from any thread; the scheduler runs either on the background
    thread (`start()`) or driven explicitly (`step()` /
    `run_until_idle()` — never both)."""

    _ids = itertools.count()

    def __init__(self, model: Any, params: Dict[str, Any],
                 config: Optional[EngineConfig] = None, name: str = ""):
        # a replica's start-up gets the trainer's account: every program
        # built from here on leaves rtpu.jax.* in this process's ring,
        # those of the constructor as children of rtpu.llm.start (a
        # bucket's program is built at its first request, under
        # rtpu.llm.prefill.b<bucket>)
        install_jax_spans()
        with _FLREC.span("rtpu.llm.start", pin=True) as start:
            self._build(model, params, config, name)
            start.label = self.name

    def _build(self, model: Any, params: Dict[str, Any],
               config: Optional[EngineConfig], name: str) -> None:
        import jax

        self.model = model
        self.params = params
        cfg = config or EngineConfig()
        buckets = tuple(sorted(set(
            min(b, cfg.max_context, model.config.max_seq)
            for b in cfg.prefill_buckets)))
        if not buckets:
            raise ValueError("prefill_buckets must be non-empty")
        self.config = cfg
        self.buckets = buckets
        self.max_prompt = buckets[-1]
        # hard context ceiling: the block table AND the model's trained
        # positions — past max_seq the embedding/RoPE gathers clamp
        # under jit and silently reuse the last row
        self.max_seq_len = min(cfg.max_context, model.config.max_seq)
        self.name = name or f"llm-{next(self._ids)}"
        self.tp = int(cfg.tp)
        self.owner = None
        self.pool = BlockPool(cfg.num_blocks, shards=self.tp)
        self._cache_sharding = None
        if self.tp == 1:
            self._cache = model.init_paged_cache(cfg.num_blocks,
                                                 cfg.block_size)
        else:
            # sharded execution layer (docs/SHARDING.md): one mesh per
            # replica; params shard per SpecLayout family (heads/FFN/
            # vocab on tp), the KV pool block-shards per chip, and the
            # host-side scheduler stays unchanged
            from ...parallel.sharding import MeshOwner, sharded_init

            self.owner = MeshOwner.tp_mesh(self.tp,
                                           name=f"llm-{self.name}")
            pspecs = self.owner.layout.param_specs(model)
            self.params = params = {
                n: jax.device_put(v, self.owner.sharding(pspecs[n]))
                for n, v in params.items()}
            kvspec = self.owner.layout.kv_cache_blocks()
            self._cache_sharding = self.owner.sharding(kvspec)
            # born block-sharded: the whole pool never sits on one chip
            self._cache = sharded_init(
                lambda: model.init_paged_cache(cfg.num_blocks,
                                               cfg.block_size),
                self.owner, {"k": kvspec, "v": kvspec})()
        self._lock = threading.RLock()
        self._waiting: "collections.deque[Request]" = collections.deque()
        self._running: List[_Sequence] = []
        self._free_slots = list(range(cfg.max_batch - 1, -1, -1))
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._total_generated = 0
        self._total_preemptions = 0
        # counters for stats(); every one is written under the lock
        self._decode_steps = 0
        self._prefill_calls: Dict[int, int] = {b: 0 for b in buckets}
        self._extend_calls = 0
        self._cow_copies = 0
        # [count, seconds, longest] of waits for the lock, by who asked:
        # "intake" (add_request/add_prefilled) and "observer" (stats,
        # queue_depth, cache_stats, kv_bytes_per_chip)
        self._lock_waits = {"intake": [0, 0.0, 0.0],
                            "observer": [0, 0.0, 0.0]}
        self._loop_lock_held_s = 0.0   # seconds step() held the lock
        self._span_prefill = {b: f"rtpu.llm.prefill.b{b}" for b in buckets}
        self._span_extend = {b: f"rtpu.llm.extend.b{b}" for b in buckets}
        self._prof: Optional[Dict[str, list]] = None
        self._peak_blocks = 0
        self._peak_per_chip: List[int] = [0] * self.tp
        self._tok_events: "collections.deque" = collections.deque()
        self.prefix_cache = None
        self._prefix_hits = 0          # tokens served from cached KV
        self._prefix_misses = 0        # tokens that paid prefill compute
        if cfg.prefix_cache:
            from .prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(self.pool, cfg.block_size)

        # jit entry points; jax caches one compiled program per argument
        # shape, so decode compiles once and prefill (and the suffix
        # extend variant) once per bucket — the buckets BOUND the
        # program count
        progs = _engine_programs(model)
        _decode, _prefill = progs["_decode"], progs["_prefill"]
        _extend, _cow = progs["_extend"], progs["_cow"]
        if self.owner is None:
            self._decode_fn = jax.jit(_decode)
            self._prefill_fn = jax.jit(_prefill)
            self._extend_fn = jax.jit(_extend)
            self._cow_fn = jax.jit(_cow)
        else:
            # pjit plane (sharding/lower.py): GSPMD partitions the body
            # under the replica's mesh. Host-side inputs (tokens/rows/
            # lengths) replicate; logits come back replicated so the
            # scheduler's argmax sees full vocab; the cache stays
            # block-sharded across calls. Decode donates its cache
            # buffers on accelerator backends so the pool updates in
            # place (the forced-host CPU backend has no donation).
            from ...parallel.sharding import lower_jit

            rep = self.owner.layout.replicated()
            kvspec = self.owner.layout.kv_cache_blocks()
            donate = (1, 2) if \
                self.owner.devices[0].platform != "cpu" else ()
            self._decode_fn = lower_jit(
                _decode, self.owner,
                in_specs=(pspecs, kvspec, kvspec, rep, rep, rep, rep),
                out_specs=(rep, kvspec, kvspec),
                donate_argnums=donate)
            self._prefill_fn = lower_jit(
                _prefill, self.owner,
                in_specs=(pspecs, kvspec, kvspec, rep, rep, rep),
                out_specs=(rep, kvspec, kvspec))
            self._extend_fn = lower_jit(
                _extend, self.owner,
                in_specs=(pspecs, kvspec, kvspec, rep, rep, rep, rep),
                out_specs=(rep, kvspec, kvspec))
            self._cow_fn = lower_jit(
                _cow, self.owner,
                in_specs=(kvspec, kvspec, rep, rep),
                out_specs=(kvspec, kvspec))

    # -- request intake -------------------------------------------------------

    def add_request(self, prompt: Sequence[int], max_tokens: int = 16,
                    eos_id: Any = "__default__",
                    request_id: Optional[str] = None,
                    trace_ctx: Optional[tuple] = None) -> TokenStream:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds engine capacity "
                f"{self.max_prompt} (largest prefill bucket)")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        rid = request_id or f"req-{next(self._ids)}"
        stream = TokenStream(rid)
        if trace_ctx is None:
            # the replica activates the request's context around the
            # user-callable invocation, which reaches here synchronously
            trace_ctx = _tracing.current_context()
        req = Request(rid, prompt, int(max_tokens),
                      self.config.eos_id if eos_id == "__default__"
                      else eos_id,
                      stream, time.perf_counter(),
                      trace_ctx=tuple(trace_ctx) if trace_ctx else None,
                      submitted_wall=time.time())
        with self._locked("intake") as waited:
            self._queued(req, waited)
            self._waiting.append(req)
            self._update_gauges()
        return stream

    def _locked(self, who: str) -> _LockWait:
        return _LockWait(self, who)

    def _queued(self, req: Request, waited: float) -> None:
        """Under the lock, at intake: the request is in the engine from
        now; what came before was the wait for the lock."""
        req.queued_wall = time.time()
        _H_INTAKE.observe(waited, tags={"engine": self.name},
                          exemplar=req.trace_ctx[0]
                          if req.trace_ctx else None)
        if req.trace_ctx is not None:
            _tracing.record_span(
                "llm.intake_wait", req.trace_ctx, req.submitted_wall,
                end=req.queued_wall, request_id=req.request_id,
                engine=self.name)

    def add_prefilled(self, prompt: Sequence[int], kv_blocks: Dict[str, Any],
                      first_token: int, max_tokens: int = 16,
                      eos_id: Any = "__default__",
                      timeout: float = 60.0) -> TokenStream:
        """Disaggregated-prefill intake: the prompt's KV was computed by a
        prefill stage (disagg.py) and arrives as block-shaped arrays
        k/v [L, nb, block_size, KH, hd]; this engine copies them into
        freshly allocated pool blocks and the sequence enters decode
        directly — no local prefill pass."""
        import jax.numpy as jnp

        prompt = [int(t) for t in prompt]
        nb = int(kv_blocks["k"].shape[1])
        if nb != blocks_for_tokens(len(prompt), self.config.block_size):
            raise ValueError(
                f"shipped {nb} blocks for a {len(prompt)}-token prompt "
                f"(block_size {self.config.block_size})")
        rid = f"req-{next(self._ids)}"
        stream = TokenStream(rid)
        trace_ctx = _tracing.current_context()
        req = Request(rid, prompt, int(max_tokens),
                      self.config.eos_id if eos_id == "__default__"
                      else eos_id,
                      stream, time.perf_counter(),
                      trace_ctx=tuple(trace_ctx) if trace_ctx else None,
                      submitted_wall=time.time())
        deadline = time.monotonic() + timeout
        while True:
            with self._locked("intake") as waited:
                if not req.queued_wall:
                    self._queued(req, waited)
                # evicting alloc: a prefix-cached decode stage would
                # otherwise wedge once rc-1 cache residency drains the
                # free list (nothing here runs _admit's eviction path)
                blocks = self._alloc_with_evict(nb)
                slot = self._free_slots.pop() if (
                    blocks is not None and self._free_slots) else None
                if blocks is not None and slot is None:
                    self.pool.free(blocks)
                    blocks = None
                if blocks is not None:
                    idx = jnp.asarray(blocks, jnp.int32)
                    self._cache = {
                        "k": self._cache["k"].at[:, idx].set(
                            jnp.asarray(kv_blocks["k"],
                                        self._cache["k"].dtype)),
                        "v": self._cache["v"].at[:, idx].set(
                            jnp.asarray(kv_blocks["v"],
                                        self._cache["v"].dtype)),
                    }
                    if self._cache_sharding is not None:
                        # the host-side scatter above runs outside the
                        # lowered programs and may leave the result on
                        # GSPMD's preferred layout — pin it back to the
                        # block-sharded residence the decode program
                        # expects
                        import jax as _jax

                        self._cache = {
                            k: _jax.device_put(v, self._cache_sharding)
                            for k, v in self._cache.items()}
                    seq = _Sequence(req, slot, blocks, len(prompt),
                                    int(first_token))
                    self._running.append(seq)
                    req.first_token_at = time.perf_counter()
                    _H_TTFT.observe(req.first_token_at - req.submitted_at,
                                    tags={"engine": self.name},
                                    exemplar=req.trace_ctx[0]
                                    if req.trace_ctx else None)
                    if req.trace_ctx is not None:
                        # disagg intake: prefill happened remotely (on
                        # the SAME trace via the shipped trace_ctx)
                        _tracing.record_span(
                            "llm.admit", req.trace_ctx, req.queued_wall,
                            request_id=req.request_id, engine=self.name,
                            prompt=len(prompt), disagg=True)
                    self._emit(seq, int(first_token), decode_step=False)
                    self._update_gauges()
                    return stream
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{self.name}: no capacity for prefilled sequence "
                    f"({nb} blocks) after {timeout}s")
            time.sleep(0.005)

    # -- scheduler ------------------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration: retire/admit/decode. Returns True if
        any work was done (callers can sleep when False)."""
        with self._lock:
            # every phase below is a child span of this one (perf/
            # recorder.py); a step that found no work leaves no event
            with _FLREC.span("rtpu.llm.step", self.name) as sp:
                admitted = self._admit()
                decoded = self._decode_iteration()
                sp.keep = worked = admitted or decoded
                if self._prof is not None and worked:
                    self._prof["occupancy"].append(float(len(self._running)))
                    self._prof["kv_pressure"].append(round(
                        self.pool.used_count / self.pool.num_blocks, 4))
                self._update_gauges()
            self._loop_lock_held_s += sp.dur
            return worked

    def _alloc_with_evict(self, n: int) -> Optional[List[int]]:
        """Pool alloc that spends cached prefixes under pressure: when
        the free list can't cover ``n``, LRU-evict refcount-1 cache
        nodes until it can (cache residency is a best-effort use of idle
        blocks, never a reason to preempt live work)."""
        blocks = self.pool.alloc(n)
        if blocks is None and self.prefix_cache is not None:
            short = n - self.pool.free_count
            if short > 0:
                self.prefix_cache.evict(short)
            blocks = self.pool.alloc(n)
        return blocks

    def _admit(self) -> bool:
        if not (self._waiting and self._free_slots):
            return False
        # scheduling; the prefills it starts are its child spans
        with _FLREC.span("rtpu.llm.admit", self.name):
            return self._admit_waiting()

    def _admit_waiting(self) -> bool:
        cfg = self.config
        budget = cfg.max_prefill_tokens_per_step
        admitted = False
        while self._waiting and self._free_slots:
            req = self._waiting[0]
            p = len(req.prompt)
            if p > self.max_prompt:
                # grew past capacity through preemption requeues
                self._waiting.popleft()
                req.stream._finish("error", RuntimeError(
                    f"{req.request_id}: context {p} exceeds engine "
                    f"capacity {self.max_prompt}"))
                continue
            # longest cached prefix (at most p-1: the last prompt token
            # always prefills so its logits pick the first new token)
            match = None
            cached = 0
            if self.prefix_cache is not None:
                match = self.prefix_cache.match(req.prompt[:-1])
                cached = match.num_tokens + match.partial_len
            if admitted and p - cached > budget:
                break                     # token budget for this iteration
            nb = blocks_for_tokens(p, cfg.block_size)
            reused = list(match.blocks) if match else []
            # pin the matched blocks (and the COW source) before any
            # eviction the alloc below may trigger can free them; the
            # pin rides match.blocks into the sequence's table and is
            # freed at retire/preempt through seq.blocks
            if reused:
                self.pool.retain(reused)  # graftcheck: disable=GC030
            if match is not None and match.partial_block is not None:
                self.pool.retain([match.partial_block])
            blocks = self._alloc_with_evict(nb - len(reused))
            if blocks is None:
                if reused:
                    self.pool.free(reused)
                if match is not None and match.partial_block is not None:
                    self.pool.free([match.partial_block])
                if not self._running and nb > self.pool.num_blocks:
                    self._waiting.popleft()
                    req.stream._finish("error", RuntimeError(
                        f"{req.request_id}: prompt needs {nb} blocks; "
                        f"pool holds {self.pool.num_blocks}"))
                    continue
                if not self._running and self.prefix_cache is not None \
                        and self.prefix_cache.resident_blocks:
                    # nothing running will ever free blocks, and partial
                    # matches can pin nodes eviction must skip: drop the
                    # whole cache and retry cold — progress beats warmth
                    self.prefix_cache.clear()
                    continue
                break                     # wait for decode frees/preemption
            self._waiting.popleft()
            budget -= p - cached
            admitted = True
            tw0 = time.time()
            if cached:
                self._prefill_cached(req, match, blocks)
            else:
                self._prefill_into(req, blocks)
            if req.trace_ctx is not None:
                _tracing.record_span(
                    "llm.prefill", req.trace_ctx, tw0,
                    request_id=req.request_id, engine=self.name,
                    tokens=p - cached, cached_tokens=cached)
        return admitted

    def _prefill_into(self, req: Request, blocks: List[int]) -> None:
        import jax.numpy as jnp

        cfg = self.config
        p = len(req.prompt)
        bucket = next(b for b in self.buckets if b >= p)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :p] = req.prompt
        row = np.full((cfg.max_blocks_per_seq,), -1, np.int32)
        row[:len(blocks)] = blocks
        with _FLREC.span(self._span_prefill[bucket], self.name):
            # dispatch, and the wait for the first token's logits
            logits, kc, vc = self._prefill_fn(
                self.params, self._cache["k"], self._cache["v"],
                jnp.asarray(toks), jnp.int32(p), jnp.asarray(row))
            self._cache = {"k": kc, "v": vc}
            first = int(np.asarray(logits).argmax())
        self._prefill_calls[bucket] += 1
        self._count_prefix(0, p)
        req.cache_hit_tokens, req.cache_miss_tokens = 0, p
        self._start_sequence(req, blocks, p, first)

    def _prefill_cached(self, req: Request, match, blocks: List[int]) -> None:
        """Suffix-only prefill over a matched cached prefix: the
        sequence's table is [reused full blocks | fresh blocks]; a
        mid-block divergence first duplicates the partially-shared block
        into the first fresh one (COW), then only prompt[cached:] runs
        through the extend program — the dominant cost of a shared-
        prefix request becomes this block-table splice."""
        import jax.numpy as jnp

        cfg = self.config
        p = len(req.prompt)
        cached = match.num_tokens + match.partial_len
        table = list(match.blocks) + blocks
        if match.partial_len:
            # COW at the divergence point: blocks[0] becomes this
            # sequence's private copy of the partially-shared block
            kc, vc = self._cow_fn(
                self._cache["k"], self._cache["v"],
                jnp.int32(match.partial_block), jnp.int32(blocks[0]))
            self._cache = {"k": kc, "v": vc}
            self._cow_copies += 1
            # the pin taken at match time was only for the copy
            self.pool.free([match.partial_block])
        suffix = req.prompt[cached:]
        s = len(suffix)
        bucket = next(b for b in self.buckets if b >= s)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :s] = suffix
        row = np.full((cfg.max_blocks_per_seq,), -1, np.int32)
        row[:len(table)] = table
        with _FLREC.span(self._span_extend[bucket], self.name):
            logits, kc, vc = self._extend_fn(
                self.params, self._cache["k"], self._cache["v"],
                jnp.asarray(toks), jnp.int32(cached), jnp.int32(s),
                jnp.asarray(row))
            self._cache = {"k": kc, "v": vc}
            first = int(np.asarray(logits).argmax())
        self._extend_calls += 1
        self._count_prefix(cached, s)
        req.cache_hit_tokens, req.cache_miss_tokens = cached, s
        self._start_sequence(req, table, p, first)

    def _start_sequence(self, req: Request, blocks: List[int], p: int,
                        first: int) -> None:
        slot = self._free_slots.pop()
        seq = _Sequence(req, slot, blocks, p, first)
        self._running.append(seq)
        if _FLREC.enabled:
            _FLREC.record("llm.admit", req.request_id,
                          {"engine": self.name, "prompt": p, "slot": slot})
        if self.prefix_cache is not None:
            # index the prompt's full blocks NOW so concurrent requests
            # sharing the prefix hit before this sequence even retires
            self.prefix_cache.insert(seq.tokens, seq.blocks)
        now = time.perf_counter()
        if req.first_token_at is None:
            req.first_token_at = now
            _H_TTFT.observe(now - req.submitted_at,
                            tags={"engine": self.name},
                            exemplar=req.trace_ctx[0]
                            if req.trace_ctx else None)
        if req.trace_ctx is not None:
            # queue wait + prefill (the wait for the engine's lock is
            # llm.intake_wait and ends where this starts), with the
            # prefix-cache outcome as attributes (hit tokens reused KV;
            # miss tokens paid compute)
            _tracing.record_span(
                "llm.admit", req.trace_ctx, req.queued_wall,
                request_id=req.request_id, engine=self.name,
                prompt=p, slot=seq.slot, preemptions=req.preemptions,
                cache_hit_tokens=req.cache_hit_tokens,
                cache_miss_tokens=req.cache_miss_tokens)
        self._emit(seq, first, decode_step=False)

    def _count_prefix(self, hit: int, miss: int) -> None:
        if self.prefix_cache is None:
            return
        tags = {"engine": self.name}
        if hit:
            self._prefix_hits += hit
            _C_PREFIX_HIT.inc(hit, tags=tags)
        if miss:
            self._prefix_misses += miss
            _C_PREFIX_MISS.inc(miss, tags=tags)

    def _decode_iteration(self) -> bool:
        if not self._running:
            return False
        import jax.numpy as jnp

        with _FLREC.span("rtpu.llm.decode.prepare", self.name):
            host = self._decode_prepare()
        if host is None:
            return False
        with _FLREC.span("rtpu.llm.decode.dispatch", self.name):
            logits, kc, vc = self._decode_fn(
                self.params, self._cache["k"], self._cache["v"],
                *(jnp.asarray(a) for a in host))
        self._cache = {"k": kc, "v": vc}
        self._decode_steps += 1
        with _FLREC.span("rtpu.llm.decode.fetch", self.name):
            arr = np.asarray(logits)      # the host waits for the device
        with _FLREC.span("rtpu.llm.decode.sample", self.name):
            emitted = 0
            for seq in list(self._running):
                seq.seq_len += 1          # pending's KV landed this step
                seq.tokens.append(seq.pending)
                tok = int(arr[seq.slot].argmax())
                seq.pending = tok
                self._emit(seq, tok, decode_step=True)
                emitted += 1
        self._tok_events.append((time.perf_counter(), emitted))
        self._total_generated += emitted
        return True

    def _decode_prepare(self) -> Optional[tuple]:
        """Host side of a decode step: grows the block tables for this
        iteration's writes (preempting the latest-admitted sequence when
        the pool is out of blocks), duplicates shared write blocks, and
        builds the program's four host arrays (tokens, positions, block
        rows, active). -> None when nothing is left running."""
        cfg = self.config
        i = 0
        while i < len(self._running):
            seq = self._running[i]
            # this iteration writes the pending token at position
            # seq_len, so the context must still have room for it
            if seq.seq_len >= self.max_seq_len:
                self._retire(seq, "length")
                continue
            need = seq.seq_len // cfg.block_size + 1
            if need > cfg.max_blocks_per_seq:
                self._retire(seq, "length")
                continue
            grow = need - len(seq.blocks)
            # a shared block under the write position must be
            # duplicated before this sequence extends it (COW): decode
            # structurally writes only private tail blocks, but a
            # refcount > 1 here — however it arose — would corrupt
            # every other holder's context
            wi = seq.seq_len // cfg.block_size
            cow = (grow <= 0 and self.prefix_cache is not None
                   and self.pool.refcount(seq.blocks[wi]) > 1)
            if grow > 0 or cow:
                got = self._alloc_with_evict(max(grow, 0) + int(cow))
                if got is None:
                    victim = self._running[-1]
                    if victim is seq and len(self._running) == 1:
                        if self.prefix_cache is not None and \
                                self.prefix_cache.resident_blocks:
                            # partially-shared nodes can pin blocks LRU
                            # eviction must skip — drop the whole cache
                            # before declaring the pool exhausted.
                            # Clearing may also drop the only other
                            # reference on the write block: recompute
                            # cow so the rescue doesn't pay a pointless
                            # device copy
                            self.prefix_cache.clear()
                            cow = (grow <= 0 and self.pool.refcount(
                                seq.blocks[wi]) > 1)
                            got = self.pool.alloc(max(grow, 0) + int(cow))
                        if got is None:
                            # sole runner and the pool still can't grow
                            # it: blocks are held outside this engine —
                            # fail loud
                            self._retire(seq, "error", RuntimeError(
                                f"{seq.req.request_id}: KV pool exhausted "
                                f"with no preemptible sequence"))
                            continue
                    else:
                        self._preempt(victim)
                        if victim is seq:
                            continue      # seq left the running list
                        continue          # retry the same seq
                if cow:
                    self._cow_block(seq, wi, got.pop())
                seq.blocks.extend(got)
            i += 1
        if not self._running:
            return None
        b, m = cfg.max_batch, cfg.max_blocks_per_seq
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        rows = np.full((b, m), -1, np.int32)
        active = np.zeros((b,), bool)
        for seq in self._running:
            tokens[seq.slot] = seq.pending
            positions[seq.slot] = seq.seq_len
            rows[seq.slot, :len(seq.blocks)] = seq.blocks
            active[seq.slot] = True
        return tokens, positions, rows, active

    # decode spans aggregate: one span per this many steps, not one per
    # token — span traffic stays O(tokens/32) while the trace still
    # shows decode progress and inter-span gaps
    _DECODE_SPAN_STEPS = 32

    def _flush_decode_span(self, seq: "_Sequence") -> None:
        if seq.dec_count and seq.req.trace_ctx is not None:
            _tracing.record_span(
                "llm.decode", seq.req.trace_ctx, seq.dec_wall0,
                request_id=seq.req.request_id, engine=self.name,
                tokens=seq.dec_count)
        seq.dec_count = 0

    def _emit(self, seq: _Sequence, tok: int, decode_step: bool) -> None:
        req = seq.req
        now = time.perf_counter()
        if decode_step:
            _H_TPOT.observe(now - seq.last_emit_at,
                            tags={"engine": self.name},
                            exemplar=req.trace_ctx[0]
                            if req.trace_ctx else None)
            if req.trace_ctx is not None:
                if seq.dec_count == 0:
                    seq.dec_wall0 = time.time()
                seq.dec_count += 1
                if seq.dec_count >= self._DECODE_SPAN_STEPS:
                    self._flush_decode_span(seq)
        seq.last_emit_at = now
        req.generated.append(tok)
        req.stream._put(tok)
        if req.eos_id is not None and tok == req.eos_id:
            self._retire(seq, "eos")
        elif len(req.generated) >= req.max_tokens:
            self._retire(seq, "length")

    def _cow_block(self, seq: _Sequence, wi: int, fresh: int) -> None:
        """Copy-on-write: duplicate seq.blocks[wi] into ``fresh`` on
        device, swap the table entry, release this sequence's reference
        on the shared original."""
        import jax.numpy as jnp

        kc, vc = self._cow_fn(self._cache["k"], self._cache["v"],
                              jnp.int32(seq.blocks[wi]), jnp.int32(fresh))
        self._cache = {"k": kc, "v": vc}
        self._cow_copies += 1
        self.pool.free([seq.blocks[wi]])
        seq.blocks[wi] = fresh

    def _retire(self, seq: _Sequence, reason: str,
                error: Optional[BaseException] = None) -> None:
        with _FLREC.span("rtpu.llm.retire", self.name):
            self._retire_now(seq, reason, error)

    def _retire_now(self, seq: _Sequence, reason: str,
                    error: Optional[BaseException]) -> None:
        self._running.remove(seq)
        if self.prefix_cache is not None and error is None:
            # leave the full-block KV of prompt+completion behind for
            # followers (multi-turn sessions re-send this context); the
            # cache takes its own references, so the free below releases
            # only this sequence's claim
            self.prefix_cache.insert(seq.tokens, seq.blocks)
        self.pool.free(seq.blocks)
        self._free_slots.append(seq.slot)
        if _FLREC.enabled:
            _FLREC.record("llm.retire", seq.req.request_id,
                          {"engine": self.name, "reason": reason,
                           "generated": len(seq.req.generated)})
        req = seq.req
        if req.trace_ctx is not None:
            self._flush_decode_span(seq)
            _tracing.record_span(
                "llm.retire", req.trace_ctx, req.submitted_wall,
                request_id=req.request_id, engine=self.name,
                reason=reason, generated=len(req.generated),
                preemptions=req.preemptions,
                cache_hit_tokens=req.cache_hit_tokens,
                cache_miss_tokens=req.cache_miss_tokens,
                error=type(error).__name__ if error is not None else "")
        seq.req.stream._finish(reason, error)

    def _preempt(self, seq: _Sequence) -> None:
        """Free everything the sequence holds and requeue it at the front
        of the waiting queue with prompt = full context so far; greedy
        re-prefill continues the exact token sequence (and, with the
        prefix cache on, mostly re-uses its own still-cached KV — the
        private tail is the only real loss)."""
        self._running.remove(seq)
        if self.prefix_cache is not None:
            self.prefix_cache.insert(seq.tokens, seq.blocks)
        self.pool.free(seq.blocks)
        self._free_slots.append(seq.slot)
        req = seq.req
        # full context to re-prefill = what this run prefilled plus every
        # token it generated (seq_len - prefill_len KV writes + pending)
        n_new = seq.seq_len - len(req.prompt) + 1
        req.prompt = list(req.prompt) + req.generated[-n_new:]
        req.preemptions += 1
        self._total_preemptions += 1
        _C_PREEMPT.inc(tags={"engine": self.name})
        if _FLREC.enabled:
            _FLREC.record("llm.preempt", req.request_id,
                          {"engine": self.name,
                           "context": len(req.prompt)})
        if req.trace_ctx is not None:
            self._flush_decode_span(seq)
            now_w = time.time()
            # the trace store always tail-keeps traces with this span
            _tracing.record_span(
                "llm.preempt", req.trace_ctx, now_w, end=now_w,
                request_id=req.request_id, engine=self.name,
                context=len(req.prompt), preemptions=req.preemptions)
            req.queued_wall = now_w
        self._waiting.appendleft(req)

    # -- loop drivers ---------------------------------------------------------

    def start(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"llm-engine-{self.name}")
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                worked = self.step()
            except Exception as e:  # noqa: BLE001 — fail every stream loud
                self._fail_all(e)
                worked = False
            if not worked:
                # merged in the ring: an idle stretch is one event
                with _FLREC.span("rtpu.llm.idle", self.name, merge=True):
                    self._stop.wait(self.config.idle_sleep_s)

    def _fail_all(self, error: BaseException) -> None:
        try:
            from ...perf.postmortem import dump_bundle

            # advisory queue depths for the crash report: stale is fine
            # graftcheck: disable=GC050
            waiting = len(self._waiting)
            # graftcheck: disable=GC050
            running = len(self._running)
            dump_bundle(f"llm engine poisoned: {error!r}",
                        origin=f"llm:{self.name}",
                        meta={"engine": self.name,
                              "waiting": waiting,
                              "running": running})
        except Exception:
            pass
        with self._lock:
            for seq in list(self._running):
                self._retire(seq, "error", error)
            while self._waiting:
                self._waiting.popleft().stream._finish("error", error)

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    def is_alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def run_until_idle(self, timeout: float = 300.0) -> None:
        """Drive the scheduler inline until no request is waiting or
        running (bench/test mode; don't mix with start())."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                idle = not self._waiting and not self._running
            if idle:
                return
            self.step()
            if time.monotonic() > deadline:
                raise TimeoutError(f"{self.name}: not idle after {timeout}s")

    # -- introspection --------------------------------------------------------

    def profile(self, steps: int = 8, flops_per_token: Optional[float] = None,
                peak_flops: Optional[float] = None, timeout: float = 60.0):
        """Profile ``steps`` scheduler iterations and return a
        :class:`ray_tpu.perf.StepReport` (kind="llm") with the
        admit/prefill/decode/retire phase split, batch-occupancy and
        KV-pressure series, tokens/s and MFU.

        With the background thread running (``start()``) this observes
        passively until ``steps`` working iterations elapsed; otherwise
        it drives ``step()`` inline over whatever is queued.
        ``flops_per_token`` defaults to ``model.flops_per_token()`` when
        the model has one; ``peak_flops`` to ``RAY_TPU_PEAK_FLOPS``."""
        from ...perf.report import StepReport

        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if peak_flops is None:
            peak_flops = float(os.environ.get("RAY_TPU_PEAK_FLOPS", 0))
        if flops_per_token is None:
            fpt = getattr(self.model, "flops_per_token", None)
            flops_per_token = float(fpt()) if callable(fpt) else 0.0
        with self._locked("observer"):
            self._prof = {"occupancy": [], "kv_pressure": []}
            gen0 = self._total_generated
        t_start = time.time()
        with _FLREC.span("rtpu.llm.profile", self.name) as window:
            try:
                if self.is_alive():
                    deadline = time.monotonic() + timeout
                    while time.monotonic() < deadline:
                        # a racy length is good enough to stop polling
                        # graftcheck: disable=GC050
                        if len(self._prof["occupancy"]) >= steps:
                            break
                        time.sleep(0.003)
                else:
                    for _ in range(steps):
                        self.step()
            finally:
                with self._locked("observer"):
                    prof, self._prof = self._prof, None
                    gen = self._total_generated - gen0
        wall_s = window.dur
        # the window's phases and step times come from its spans alone
        # (the flight recorder must be on: it is the one timer there is)
        events = [ev for ev in _FLREC.snapshot(clear=False)
                  if ev["ts"] >= t_start
                  and ("dur" not in ev or ev["label"] == self.name)][-2000:]
        step_ms, phases = step_phases(events)
        return StepReport(
            kind="llm", engine=self.name, steps=len(step_ms),
            wall_s=wall_s, step_ms=step_ms, phases=phases,
            tokens=float(gen),
            tokens_per_s=gen / wall_s if gen and wall_s > 0 else 0.0,
            flops_per_token=flops_per_token, peak_flops=peak_flops,
            occupancy=prof["occupancy"], kv_pressure=prof["kv_pressure"],
            events=events,
            extra={"max_batch": self.config.max_batch,
                   "num_blocks": self.config.num_blocks,
                   "preemptions": self._total_preemptions})

    def queue_depth(self) -> int:
        with self._locked("observer"):
            return len(self._waiting) + len(self._running)

    def _tokens_per_s(self, window_s: float = 10.0) -> float:
        now = time.perf_counter()
        while self._tok_events and now - self._tok_events[0][0] > window_s:
            self._tok_events.popleft()
        if len(self._tok_events) < 2:
            return 0.0
        span = now - self._tok_events[0][0]
        return sum(n for _, n in self._tok_events) / max(span, 1e-6)

    def _update_gauges(self) -> None:
        tags = {"engine": self.name}
        _G_QUEUE.set(len(self._waiting) + len(self._running), tags=tags)
        # used_count counts shared blocks ONCE (refcounted pool), so
        # this gauge can never report occupancy above pool capacity
        _G_BLOCKS.set(self.pool.used_count, tags=tags)
        _G_TOKPS.set(round(self._tokens_per_s(), 1), tags=tags)
        if self.prefix_cache is not None:
            seen = self._prefix_hits + self._prefix_misses
            _G_HIT_RATE.set(
                round(self._prefix_hits / seen, 4) if seen else 0.0,
                tags=tags)
        self._peak_blocks = max(self._peak_blocks, self.pool.used_count)
        if self.tp > 1:
            for chip, used in enumerate(self.pool.used_per_shard()):
                _G_BLOCKS.set(used, tags={"engine": self.name,
                                          "chip": str(chip)})
                self._peak_per_chip[chip] = max(
                    self._peak_per_chip[chip], used)

    def kv_bytes_per_chip(self) -> Dict[int, int]:
        """Resident KV-cache bytes per CHIP — keyed by mesh position
        0..tp-1 (same keying as the pool's shard accounting and the
        `{chip=}` gauge; raw jax device ids are global on multi-host
        TPUs and would not line up)."""
        # metrics thread: the step loop mutates _cache
        with self._locked("observer"):
            cache = dict(self._cache)
        return self._kv_bytes(cache)

    def _kv_bytes(self, cache: Dict[str, Any]) -> Dict[int, int]:
        if self.owner is None:
            total = sum(int(np.asarray(v).nbytes)
                        for v in cache.values())
            return {0: total}
        by_dev = self.owner.per_device_bytes(cache)
        return {chip: by_dev.get(d.id, 0)
                for chip, d in enumerate(self.owner.devices)}

    def cache_stats(self) -> Dict[str, Any]:
        """Prefix-cache health — the replica ships this in its health
        ping (replica.py) so the controller/balancer can prefer
        cache-warm replicas. All zeros with the cache disabled."""
        with self._locked("observer"):
            return self._cache_stats()

    def _cache_stats(self) -> Dict[str, Any]:
        hit, miss = self._prefix_hits, self._prefix_misses
        pc = self.prefix_cache
        return {
            "cache_hit_rate": round(hit / (hit + miss), 4)
            if hit + miss else 0.0,
            "prefix_hit_tokens": hit,
            "prefix_miss_tokens": miss,
            "prefix_blocks_resident": pc.resident_blocks if pc else 0,
            "prefix_nodes": pc.num_nodes if pc else 0,
            "prefix_evictions": pc.evictions if pc else 0,
        }

    def stats(self) -> Dict[str, Any]:
        with self._locked("observer"):
            waits = self._lock_waits
            out = {
                "engine": self.name,
                "waiting": len(self._waiting),
                "running": len(self._running),
                "queue_depth": len(self._waiting) + len(self._running),
                "kv_blocks_used": self.pool.used_count,
                "kv_blocks_total": self.pool.num_blocks,
                "kv_occupancy": round(
                    self.pool.used_count / self.pool.num_blocks, 4),
                "tokens_per_s": round(self._tokens_per_s(), 1),
                "total_generated": self._total_generated,
                "preemptions": self._total_preemptions,
                "tp": self.tp,
                "kv_blocks_peak": self._peak_blocks,
                # what the scheduler did, counted (the spans of
                # perf/recorder.py say how long each took)
                "decode_steps": self._decode_steps,
                "prefill_calls": {str(b): n for b, n
                                  in self._prefill_calls.items()},
                "extend_calls": self._extend_calls,
                "cow_copies": self._cow_copies,
                # waits for this lock, by who asked: intake =
                # add_request/add_prefilled, observer = stats() and kin
                "lock_waits": {w: c[0] for w, c in waits.items()},
                "lock_wait_s": {w: c[1] for w, c in waits.items()},
                "lock_wait_max_s": {w: c[2] for w, c in waits.items()},
                "loop_lock_held_s": self._loop_lock_held_s,
            }
            if self.prefix_cache is not None:
                out.update(self._cache_stats())
            if self.tp > 1:
                out["kv_blocks_per_chip"] = self.pool.used_per_shard()
                out["kv_blocks_peak_per_chip"] = list(self._peak_per_chip)
                out["kv_bytes_per_chip"] = {
                    str(d): b
                    for d, b in self._kv_bytes(dict(self._cache)).items()}
            return out
