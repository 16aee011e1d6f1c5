"""Continuous-batching LLM engine (ray_tpu.serve.llm, ISSUE 7).

Block-pool accounting, preemption-and-requeue equivalence, iteration-
level admission, retirement, concurrent streaming order, metric
accuracy, the >=3x batching-speedup envelope (acceptance criterion),
and the disaggregated prefill/decode path.

The pure-accounting tests (TestBlockPool) never touch jax; engine tests
share one tiny GPT (module fixture) so the suite pays for compilation
once.
"""
import threading

import pytest

from ray_tpu.serve.llm import (BlockPool, EngineConfig, LLMEngine,
                               blocks_for_tokens, build_model)


# ---------------------------------------------------------------------------
# block pool — pure accounting, no jax


class TestBlockPool:
    def test_alloc_free_roundtrip(self):
        pool = BlockPool(8)
        got = pool.alloc(3)
        assert len(got) == 3 and len(set(got)) == 3
        assert pool.used_count == 3 and pool.free_count == 5
        pool.free(got)
        assert pool.used_count == 0 and pool.free_count == 8
        pool.check_leaks()

    def test_alloc_is_all_or_nothing(self):
        pool = BlockPool(4)
        assert pool.alloc(5) is None          # over capacity: no partial
        assert pool.used_count == 0 and pool.free_count == 4
        a = pool.alloc(3)
        assert pool.alloc(2) is None          # only 1 left
        assert pool.free_count == 1
        pool.free(a)
        pool.check_leaks()

    def test_alloc_zero_and_negative(self):
        pool = BlockPool(2)
        assert pool.alloc(0) == []
        with pytest.raises(ValueError):
            pool.alloc(-1)

    def test_free_validates(self):
        pool = BlockPool(4)
        with pytest.raises(ValueError):
            pool.free([99])                   # unknown block
        got = pool.alloc(2)
        pool.free(got)
        with pytest.raises(ValueError):
            pool.free(got)                    # double free over-returns

    def test_leak_detection(self):
        pool = BlockPool(4)
        # intentional leak: this test exists to prove check_leaks sees it
        pool.alloc(2)  # graftcheck: disable=GC030
        pool._used -= 1                       # simulate lost accounting
        with pytest.raises(AssertionError, match="leak"):
            pool.check_leaks()

    def test_blocks_for_tokens(self):
        assert blocks_for_tokens(0, 16) == 0
        assert blocks_for_tokens(1, 16) == 1
        assert blocks_for_tokens(16, 16) == 1
        assert blocks_for_tokens(17, 16) == 2
        assert blocks_for_tokens(33, 16) == 3


# ---------------------------------------------------------------------------
# engine — one shared tiny model per module


@pytest.fixture(scope="module")
def tiny_model():
    return build_model("gpt-tiny")


def mk_engine(tiny_model, **over) -> LLMEngine:
    m, params = tiny_model
    kw = dict(block_size=4, num_blocks=32, max_batch=4,
              max_blocks_per_seq=8, prefill_buckets=(8, 16),
              max_prefill_tokens_per_step=32)
    kw.update(over)
    return LLMEngine(m, params, EngineConfig(**kw))


def reference_tokens(tiny_model, prompt, max_tokens, **over):
    """The unconstrained (no-preemption, solo) greedy completion."""
    eng = mk_engine(tiny_model, **over)
    st = eng.add_request(prompt, max_tokens=max_tokens)
    eng.run_until_idle(timeout=300)
    toks = st.tokens()
    eng.pool.check_leaks()
    return toks


def _batching_row(n_requests: int, concurrency: int, max_tokens: int) -> dict:
    """Continuous batching against one request at a time on gpt-tiny:
    the tokens and the decode steps each engine took for them
    (``stats()["decode_steps"]``, the warm-up's taken off), the ratio of
    aggregate tokens/s, and the median TTFT / TPOT of the
    batched window read back from the engine's metric histograms. The
    engine runs in-process (it IS the replica's inner loop; the serve
    layer adds only routing)."""
    import time

    from ray_tpu.serve.llm import EngineConfig, LLMEngine, build_model
    from ray_tpu.serve.llm.engine import _H_TPOT, _H_TTFT
    from ray_tpu.util.metrics import percentile_from_buckets

    m, params = build_model("gpt-tiny")

    def mk(batch: int, name: str) -> LLMEngine:
        return LLMEngine(m, params, EngineConfig(
            max_batch=batch, num_blocks=max(64, concurrency * 8),
            block_size=8, max_blocks_per_seq=8, prefill_buckets=(8, 16),
            max_prefill_tokens_per_step=64), name=name)

    prompts = [[1 + (i % 50), 5, 9, 2] for i in range(n_requests)]

    # sequential baseline: one request at a time, batch-1 program
    seq_eng = mk(1, "bench-seq")
    s = seq_eng.add_request([1, 2, 3], max_tokens=2)
    seq_eng.run_until_idle(timeout=600)   # warmup: compile prefill+decode
    s.tokens()
    seq_tokens, seq_steps = 0, seq_eng.stats()["decode_steps"]
    t0 = time.perf_counter()
    for p in prompts:
        st = seq_eng.add_request(p, max_tokens=max_tokens)
        seq_eng.run_until_idle(timeout=600)
        seq_tokens += len(st.tokens())
    seq_rate = seq_tokens / (time.perf_counter() - t0)
    seq_steps = seq_eng.stats()["decode_steps"] - seq_steps

    # continuous batching: all clients at once, one shared program
    eng = mk(concurrency, "bench-llm")
    s = eng.add_request([1, 2, 3], max_tokens=2)
    eng.run_until_idle(timeout=600)       # warmup compile at this batch
    s.tokens()
    # the warmup's TTFT/TPOT samples carry XLA compile time under the
    # SAME engine tag; snapshot buckets so the reported percentiles are
    # the measured window's delta only
    tags = {"engine": "bench-llm"}

    def snap(h):
        with h._lock:
            return list(h._buckets.get(h._key(tags), ()))

    pre = {id(h): snap(h) for h in (_H_TTFT, _H_TPOT)}
    steps = eng.stats()["decode_steps"]
    # the scheduler is driven inline and the streams drained after the
    # clock stops, which keeps client-thread GIL noise out of the window
    t0 = time.perf_counter()
    streams = [eng.add_request(p, max_tokens=max_tokens) for p in prompts]
    eng.run_until_idle(timeout=900)
    wall = time.perf_counter() - t0
    total = sum(len(st.tokens(timeout=60)) for st in streams)
    steps = eng.stats()["decode_steps"] - steps
    eng.pool.check_leaks()

    def p50_ms(h):
        post = snap(h)
        before = pre[id(h)] or [0] * len(post)
        delta = [b - a for a, b in zip(before, post)] if post else []
        v = percentile_from_buckets(h.boundaries, delta, 50)
        return round(v * 1e3, 1) if v is not None else None

    return {"tokens": (seq_tokens, total),
            "decode_steps": (seq_steps, steps),
            "llm_batching_speedup": round(total / wall / seq_rate, 2),
            "llm_ttft_p50_ms": p50_ms(_H_TTFT),
            "llm_tpot_p50_ms": p50_ms(_H_TPOT)}


class TestEngine:
    def test_generate_and_block_accounting(self, tiny_model):
        eng = mk_engine(tiny_model)
        st = eng.add_request([1, 5, 9], max_tokens=6)
        eng.run_until_idle(timeout=300)
        toks = st.tokens()
        assert len(toks) == 6 and st.finish_reason == "length"
        # every block came back after retirement
        assert eng.pool.used_count == 0
        eng.pool.check_leaks()

    def test_eos_retirement(self, tiny_model):
        # discover the greedy continuation, then declare as EOS a token
        # at its own first occurrence (greedy outputs repeat; an earlier
        # duplicate would stop the run sooner than the chosen index)
        ref = reference_tokens(tiny_model, [1, 5, 9], 8)
        k = next((i for i in range(len(ref)) if ref[i] not in ref[:i]), 0)
        eng = mk_engine(tiny_model)
        st = eng.add_request([1, 5, 9], max_tokens=8, eos_id=ref[k])
        eng.run_until_idle(timeout=300)
        toks = st.tokens()
        assert st.finish_reason == "eos"
        assert toks == ref[:k + 1]            # EOS token itself is emitted
        assert eng.pool.used_count == 0

    def test_oversize_prompt_rejected(self, tiny_model):
        eng = mk_engine(tiny_model)
        with pytest.raises(ValueError, match="exceeds engine capacity"):
            eng.add_request(list(range(1, 40)), max_tokens=2)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.add_request([], max_tokens=2)

    def test_unsatisfiable_prompt_errors_stream(self, tiny_model):
        # fits the prefill bucket but not the pool: the stream fails
        # loudly instead of waiting forever
        eng = mk_engine(tiny_model, num_blocks=2, max_blocks_per_seq=8)
        st = eng.add_request(list(range(1, 16)), max_tokens=2)  # 4 blocks
        eng.step()
        with pytest.raises(RuntimeError, match="pool holds"):
            st.tokens()
        assert st.finish_reason == "error"
        eng.pool.check_leaks()

    def test_continuous_admission_mid_decode(self, tiny_model):
        """A request arriving while another decodes is admitted into the
        running batch (not after it), and both complete correctly."""
        eng = mk_engine(tiny_model)
        a = eng.add_request([1, 5, 9], max_tokens=12)
        eng.step()                            # prefill A
        eng.step()                            # A decoding
        assert len(eng._running) == 1
        b = eng.add_request([2, 6], max_tokens=4)
        eng.step()                            # admits B mid-decode
        assert len(eng._running) == 2         # joint iteration batch
        eng.run_until_idle(timeout=300)
        assert a.tokens() == reference_tokens(tiny_model, [1, 5, 9], 12)
        assert b.tokens() == reference_tokens(tiny_model, [2, 6], 4)
        eng.pool.check_leaks()

    def test_preemption_requeue_equivalence(self, tiny_model):
        """Under a pool too small for both sequences to grow, the victim
        is preempted, requeued, re-prefilled — and still produces exactly
        the unpreempted run's tokens (greedy determinism)."""
        want = {p: reference_tokens(tiny_model, list(p), 12)
                for p in ((1, 5, 9), (2, 6, 4))}
        # 7 blocks x 4 tokens: both sequences grow to 4 blocks (context
        # 12+) so they can't coexist; the later admission gets preempted
        # while its re-prefill context still fits the largest bucket
        eng = mk_engine(tiny_model, num_blocks=7)
        sa = eng.add_request([1, 5, 9], max_tokens=12)
        sb = eng.add_request([2, 6, 4], max_tokens=12)
        eng.run_until_idle(timeout=300)
        assert eng._total_preemptions >= 1, "scenario must actually preempt"
        assert sa.tokens() == want[(1, 5, 9)]
        assert sb.tokens() == want[(2, 6, 4)]
        assert sa.finish_reason == sb.finish_reason == "length"
        assert eng.pool.used_count == 0
        eng.pool.check_leaks()

    def test_sole_runner_pool_exhaustion_fails_loud(self, tiny_model):
        # one sequence, pool too small to grow it: error retire, not hang
        eng = mk_engine(tiny_model, num_blocks=2, max_blocks_per_seq=8,
                        prefill_buckets=(8,))
        st = eng.add_request([1, 5, 9, 2, 6, 4, 3, 7], max_tokens=16)
        eng.run_until_idle(timeout=300)
        with pytest.raises(RuntimeError, match="exhausted"):
            st.tokens()
        eng.pool.check_leaks()

    def test_kv_occupancy_metric_accuracy(self, tiny_model):
        from ray_tpu.serve.llm.engine import _G_BLOCKS, _G_QUEUE

        eng = mk_engine(tiny_model)

        def gauge(g):
            return g._values.get(g._key({"engine": eng.name}))

        st = eng.add_request([1, 5, 9, 2, 6], max_tokens=6)
        assert gauge(_G_QUEUE) == 1           # waiting counts
        eng.step()                            # prefilled: blocks live
        assert gauge(_G_BLOCKS) == eng.pool.used_count > 0
        eng.run_until_idle(timeout=300)
        st.tokens()
        assert gauge(_G_BLOCKS) == 0 == eng.pool.used_count
        assert gauge(_G_QUEUE) == 0

    def test_streaming_order_under_concurrency(self, tiny_model):
        """N concurrent client threads each stream their own request;
        every client sees its full completion, in order, with no
        cross-request token leakage."""
        prompts = [[1 + i, 5, 9] for i in range(6)]
        want = [reference_tokens(tiny_model, p, 10) for p in prompts]
        eng = mk_engine(tiny_model, max_batch=4)  # forces queuing too
        eng.start()
        try:
            got = [None] * len(prompts)

            def client(i):
                st = eng.add_request(prompts[i], max_tokens=10)
                got[i] = [tok for tok in st]  # token-at-a-time iteration

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert got == want
        finally:
            eng.stop()
        assert eng.pool.used_count == 0
        eng.pool.check_leaks()

    def test_batching_speedup_envelope(self, tiny_model, machine_load):
        """Acceptance: a batch SHARES decode steps. 16 requests at
        concurrency 8 produce the tokens the sequential engine produces in
        at most a quarter of its decode steps (two waves of 8 against 16
        alone: an eighth where nothing waits). The wall-clock speed-up is
        printed with the machine's load and not asserted: beside five
        other test workers it is a fact about the machine."""
        row = _batching_row(n_requests=16, concurrency=8, max_tokens=16)
        print(f"batching speed-up {row['llm_batching_speedup']:.2f}x "
              f"(load {machine_load:.2f}/core)")
        seq_tokens, tokens = row["tokens"]
        seq_steps, steps = row["decode_steps"]
        assert tokens == seq_tokens == 16 * 16, row
        assert 0 < steps <= seq_steps / 4, row
        assert row["llm_ttft_p50_ms"] is not None
        assert row["llm_tpot_p50_ms"] is not None


def test_model_max_seq_caps_context():
    """Decode retires at the model's max_seq even when the block table
    has room — positions past max_seq would silently clamp their
    embedding/RoPE gathers under jit and corrupt the generation."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT, GPTConfig

    m = GPT(GPTConfig(n_layer=1, n_head=2, d_model=32, d_ff=64,
                      vocab_size=64, max_seq=12, dtype=jnp.float32,
                      use_flash=False))
    params = jax.jit(m.init)(jax.random.PRNGKey(0))
    # block table allows 32 tokens, the model only 12
    eng = LLMEngine(m, params, EngineConfig(
        block_size=4, num_blocks=16, max_batch=2, max_blocks_per_seq=8,
        prefill_buckets=(8,)))
    assert eng.max_seq_len == 12
    st = eng.add_request([1, 5, 9], max_tokens=30)
    eng.run_until_idle(timeout=300)
    toks = st.tokens()
    assert st.finish_reason == "length"
    # prompt 3 + prefill emit 1 + decode writes at positions 3..11 = 9
    # more emits; the emit that would write at position 12 never happens
    assert len(toks) == 10
    eng.pool.check_leaks()


@pytest.mark.parametrize("name", ["gpt-tiny", "llama-tiny"])
def test_paged_path_matches_dense_forward(name):
    """The paged prefill+decode pipeline reproduces greedy decode under
    the model's ordinary dense forward (full-context recompute each
    token) — for GPT and for llama's GQA + RoPE path."""
    import jax
    import numpy as np

    m, params = build_model(name)
    prompt = [1, 5, 9]
    steps = 6

    apply = jax.jit(m.apply)
    ctx = list(prompt)
    dense = []
    for _ in range(steps):
        logits = np.asarray(apply(params, np.asarray([ctx], np.int32)))
        tok = int(logits[0, -1].argmax())
        dense.append(tok)
        ctx.append(tok)

    eng = LLMEngine(m, params, EngineConfig(
        block_size=4, num_blocks=16, max_batch=2, max_blocks_per_seq=4,
        prefill_buckets=(8,)))
    st = eng.add_request(prompt, max_tokens=steps)
    eng.run_until_idle(timeout=300)
    assert st.tokens() == dense
    eng.pool.check_leaks()


# ---------------------------------------------------------------------------
# disaggregated prefill/decode (cgraph channel path)


def test_disagg_prefill_decode_smoke():
    ray_tpu = pytest.importorskip("ray_tpu")
    from ray_tpu.serve.llm import DisaggLLM

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    try:
        m, params = build_model("gpt-tiny")
        ref_eng = LLMEngine(m, params, EngineConfig(
            block_size=4, num_blocks=32, max_batch=2,
            max_blocks_per_seq=8, prefill_buckets=(8,)))
        st = ref_eng.add_request([1, 5, 9], max_tokens=6)
        ref_eng.run_until_idle(timeout=300)
        want = st.tokens()

        llm = DisaggLLM(model="gpt-tiny", block_size=4,
                        engine_config=dict(num_blocks=32, max_batch=2,
                                           max_blocks_per_seq=8,
                                           prefill_buckets=(8,)))
        try:
            out = llm.generate([1, 5, 9], max_tokens=6, timeout=300)
            # KV computed by the prefill stage, decoded by the decode
            # stage — same tokens as the single-engine run
            assert out["tokens"] == want
            assert out["finish_reason"] == "length"
            stats = llm.stats()
            assert stats["kv_blocks_used"] == 0    # blocks returned
            assert stats["total_generated"] >= 5   # decode-side emits
        finally:
            llm.shutdown()
    finally:
        ray_tpu.shutdown()
