"""Streaming train feed (ray_tpu/data/feed.py + attach_feed).

ISSUE 19 tentpole (c) acceptance surface: a feed-fed
CompiledPipelineEngine's loss trajectory is BIT-IDENTICAL to
hand-feeding the same microbatches, steady-state fed steps make ZERO
driver dispatches (dispatch_counts-asserted), detach hands the rings
back cleanly (seq handoff), pump death is a typed DataFeedError and
recover() re-attaches, and teardown leaks no channel segments.
"""
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions


def _mlp_chunks(num_chunks, width=8, seed=0):
    import jax
    import jax.numpy as jnp

    k = jax.random.PRNGKey(seed)

    def mk_mid():
        def fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])
        return fn

    def mk_last():
        def fn(p, x, targets):
            return jnp.mean((x @ p["w"] + p["b"] - targets) ** 2)
        return fn

    fns = [mk_mid() for _ in range(num_chunks - 1)] + [mk_last()]
    params = [
        {"w": jax.random.normal(jax.random.fold_in(k, i),
                                (width, width)) * 0.3,
         "b": jnp.zeros((width,))}
        for i in range(num_chunks)]
    return fns, params


def _mlp_batches(M, width=8, mb_size=2, seed=7):
    import jax

    k = jax.random.PRNGKey(seed)
    xs = jax.random.normal(jax.random.fold_in(k, 0), (M * mb_size, width))
    ys = jax.random.normal(jax.random.fold_in(k, 1), (M * mb_size, width))
    mbs = [xs[i * mb_size:(i + 1) * mb_size] for i in range(M)]
    tgts = [ys[i * mb_size:(i + 1) * mb_size] for i in range(M)]
    return mbs, tgts


def _repeat_factory(mbs, tgts, steps):
    """Zero-arg factory (cloudpickled into the pump actor) yielding the
    exact microbatch sequence step() would have been hand-fed."""
    mbs = [np.asarray(x) for x in mbs]
    tgts = [np.asarray(t) for t in tgts]

    def factory():
        def it():
            for _ in range(steps):
                for x, t in zip(mbs, tgts):
                    yield x, t
        return it()
    return factory


class TestDataFeed:
    def test_fed_matches_handfed_bit_identical_zero_dispatch(
            self, ray_start_regular):
        """The acceptance triple: >=5 fed steps, loss trajectory equals
        the hand-fed reference bit-for-bit, zero driver dispatches in
        steady state, and detach hands the rings back for hand-feeding
        (seq handoff is exact)."""
        import optax

        from ray_tpu.core.runtime import dispatch_counts
        from ray_tpu.data import DataFeed
        from ray_tpu.train.pipeline_cgraph import (CompiledPipelineEngine,
                                                   run_reference_1f1b)

        STEPS, M = 6, 4
        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(M)
        tx = optax.adam(1e-2)
        ref_losses, _ = run_reference_1f1b(fns, params, tx,
                                           [(mbs, tgts)] * (STEPS + 1))
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=M,
                                     channel_bytes=1 << 18)
        try:
            eng.attach_feed(DataFeed([_repeat_factory(mbs, tgts, STEPS)]))
            losses = [eng.step()]
            d0, r0 = dispatch_counts()
            losses += [eng.step() for _ in range(STEPS - 1)]
            d1, r1 = dispatch_counts()
            assert losses == ref_losses[:STEPS]
            assert (d1 - d0, r1 - r0) == (0, 0), \
                "steady-state fed steps must make zero driver dispatches"
            st = eng.feed_stats()
            assert st[0]["sent"] == STEPS * M and st[0]["error"] is None
            # hand the rings back: the very next hand-fed step continues
            # the same trajectory
            eng.detach_feed()
            assert eng.step(mbs, tgts) == ref_losses[STEPS]
        finally:
            eng.shutdown()

    def test_step_arg_discipline(self, ray_start_regular):
        """Fed engines refuse batches; unfed engines require them;
        mis-sharded feeds are rejected before any actor spawns."""
        import optax

        from ray_tpu.data import DataFeed
        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        try:
            with pytest.raises(ValueError, match="needs microbatches"):
                eng.step()
            with pytest.raises(ValueError, match="sharded 2-wide"):
                eng.attach_feed(DataFeed(
                    [_repeat_factory(mbs, tgts, 1)] * 2))
            eng.attach_feed(DataFeed([_repeat_factory(mbs, tgts, 4)]))
            with pytest.raises(ValueError, match="feed is attached"):
                eng.step(mbs, tgts)
            eng.step()
        finally:
            eng.shutdown()

    def test_detach_requires_drained_feed(self, ray_start_regular):
        """A mid-stream detach (live iterator, or fed steps not yet
        read) raises instead of silently leaving stale envelopes in the
        rings; draining per the error's guidance then detaching works
        and the next hand-fed step continues the trajectory."""
        import optax

        from ray_tpu import exceptions as exc
        from ray_tpu.data import DataFeed
        from ray_tpu.train.pipeline_cgraph import (CompiledPipelineEngine,
                                                   run_reference_1f1b)

        STEPS, M = 4, 2
        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(M)
        tx = optax.sgd(1e-2)
        ref_losses, _ = run_reference_1f1b(fns, params, tx,
                                           [(mbs, tgts)] * (STEPS + 1))
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=M,
                                     channel_bytes=1 << 18)
        try:
            # live iterator: refused outright
            eng.attach_feed(DataFeed([_repeat_factory(mbs, tgts, 1000)]))
            eng.step()
            with pytest.raises(exc.CompiledGraphError, match="undrained"):
                eng.detach_feed(timeout=3.0)
            eng.shutdown()

            # finite feed, detached too early: refused until every fed
            # step is read, then clean
            eng = CompiledPipelineEngine(fns, params, tx,
                                         num_microbatches=M,
                                         channel_bytes=1 << 18)
            eng.attach_feed(DataFeed([_repeat_factory(mbs, tgts, STEPS)]))
            losses = [eng.step() for _ in range(STEPS - 1)]
            with pytest.raises(exc.CompiledGraphError, match="undrained"):
                eng.detach_feed(timeout=3.0)
            losses.append(eng.step())
            eng.detach_feed()
            assert losses == ref_losses[:STEPS]
            assert eng.step(mbs, tgts) == ref_losses[STEPS]
        finally:
            eng.shutdown()

    def test_pump_death_typed_error_and_recover_reattaches(
            self, ray_start_regular, wait_engine_aborted):
        """Killing a pump actor aborts the engine with DataFeedError;
        recover() respawns the stages AND re-attaches the feed from its
        factories (a fresh iterator), so fed steps run again."""
        import optax

        from ray_tpu.data import DataFeed
        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        try:
            eng.attach_feed(DataFeed([_repeat_factory(mbs, tgts, 100)]))
            first = eng.step()
            ray_tpu.kill(eng._feed_actors[0])
            assert wait_engine_aborted(eng)
            assert isinstance(eng._closed_error, exceptions.DataFeedError)
            with pytest.raises(exceptions.DataFeedError):
                eng.step()
            assert eng.recover() == 0
            # feed factory restarted from scratch -> step-0 trajectory
            assert eng.step() == first
        finally:
            eng.shutdown()

    def test_shutdown_with_live_feed_leaks_nothing(self, ray_start_regular):
        """shutdown() with pumps still attached kills them without a
        spurious DataFeedError and releases every channel segment."""
        import optax

        from ray_tpu.data import DataFeed
        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        rt = ray_start_regular
        node = rt.nodes[rt.head_node_id]
        before = node.store.stats()["num_channels"]
        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        eng.attach_feed(DataFeed([_repeat_factory(mbs, tgts, 100)]))
        eng.step()
        eng.shutdown()
        assert node.store.stats()["num_channels"] == before
