"""Compiled-graph pipeline engine (train/pipeline_cgraph.py).

ISSUE 8 acceptance surface: 1F1B over pre-allocated cgraph channels
matches the single-process reference bit-for-bit, interleaved (virtual
stages) matches non-interleaved, the ZeRO-sharded dp update matches the
replicated update with ~1/dp optimizer-state bytes, stage death
surfaces a typed error and shutdown leaks no channel segments.
"""
import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions


def _mlp_chunks(num_chunks, width=8, seed=0):
    """num_chunks tanh-MLP chunk fns + params (closures — cloudpickled
    by value into the stage actors). Last chunk computes an MSE loss."""
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


def _tiny_gpt(batch, seq):
    """A float32 GPT-tiny without flash or remat, its parameters, a batch
    of tokens and the next-token targets."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT, GPTConfig

    model = GPT(GPTConfig.tiny(dtype=jnp.float32, use_flash=False,
                               remat=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                model.config.vocab_size)
    return model, params, tokens, jnp.roll(tokens, -1, axis=1)


# ---------------------------------------------------------------------------
# interleaved schedule (parallel/pipeline.py) — pure, no cluster
# ---------------------------------------------------------------------------


class TestInterleavedSchedule:
    def test_reduces_to_1f1b_for_virtual_1(self):
        from ray_tpu.parallel.pipeline import (schedule_1f1b,
                                               schedule_interleaved_1f1b)

        for P, M in ((2, 4), (3, 8), (4, 4)):
            got = schedule_interleaved_1f1b(P, M, 1)
            want = [[(k, 0, mb) for k, mb in ops]
                    for ops in schedule_1f1b(P, M)]
            assert got == want

    @pytest.mark.parametrize("P,M,V", [(2, 4, 2), (2, 8, 2), (3, 6, 2),
                                       (2, 4, 3), (4, 8, 2)])
    def test_complete_ordered_and_deadlock_free(self, P, M, V):
        """Every (chunk, microbatch) fwd+bwd exactly once on the right
        actor, fwd before bwd, and a blocking-recv replay of the
        per-actor orders never stalls (the runtime deadlock-freedom
        argument, executed)."""
        from ray_tpu.parallel.pipeline import schedule_interleaved_1f1b

        sched = schedule_interleaved_1f1b(P, M, V)
        G = P * V
        seen = set()
        pos = {}
        for i, ops in enumerate(sched):
            for idx, (kind, v, mb) in enumerate(ops):
                g = v * P + i
                assert (kind, g, mb) not in seen
                seen.add((kind, g, mb))
                pos[(kind, g, mb)] = (i, idx)
        assert len(seen) == 2 * G * M
        for g in range(G):
            for mb in range(M):
                assert pos[("fwd", g, mb)][1] < pos[("bwd", g, mb)][1] \
                    or pos[("fwd", g, mb)][0] != pos[("bwd", g, mb)][0]
        # replay: blocking recvs, non-blocking sends
        ptr = [0] * P
        finished = set()
        while any(ptr[i] < len(sched[i]) for i in range(P)):
            progressed = False
            for i in range(P):
                while ptr[i] < len(sched[i]):
                    kind, v, mb = sched[i][ptr[i]]
                    g = v * P + i
                    if kind == "fwd":
                        ok = g == 0 or ("fwd", g - 1, mb) in finished
                    else:
                        ok = ("fwd", g, mb) in finished and (
                            g == G - 1 or ("bwd", g + 1, mb) in finished)
                    if not ok:
                        break
                    finished.add((kind, g, mb))
                    ptr[i] += 1
                    progressed = True
            assert progressed, f"schedule deadlocked: P={P} M={M} V={V}"

    @pytest.mark.parametrize("P,M", [(2, 4), (2, 8), (4, 8)],
                             ids=["P2-M4", "P2-M8", "P4-M8"])
    def test_1f1b_in_flight_bound(self, P, M):
        """The point of 1F1B over GPipe, read off the order the engine's
        stage actors execute: on stage i the forwards whose backward has
        not run yet (the residuals it holds) peak at P - i at most. A
        GPipe order (all forwards first) would read M on every stage."""
        from ray_tpu.parallel.pipeline import (schedule_1f1b,
                                               schedule_interleaved_1f1b)

        sched = schedule_1f1b(P, M)
        # what CompiledPipelineEngine builds its per-stage loops from
        assert schedule_interleaved_1f1b(P, M, 1) == [
            [(kind, 0, mb) for kind, mb in ops] for ops in sched]
        for i, ops in enumerate(sched):
            held, peak = set(), 0
            for kind, mb in ops:
                if kind == "fwd":
                    held.add(mb)
                else:
                    held.remove(mb)       # KeyError: bwd before its fwd
                peak = max(peak, len(held))
            assert not held
            assert peak <= P - i, (i, peak)
            assert peak < M or M <= P - i


# ---------------------------------------------------------------------------
# numeric equivalence
# ---------------------------------------------------------------------------


class TestNumericEquivalence:
    def test_mlp_matches_reference_bit_for_bit(self, ray_start_regular):
        """3-step loss trajectory AND final params equal the
        single-process reference exactly — the channels move bytes, the
        stages run the same jitted programs in the same order."""
        import jax
        import optax

        from ray_tpu.train.pipeline_cgraph import (CompiledPipelineEngine,
                                                   run_reference_1f1b)

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(4)
        tx = optax.adam(1e-2)
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=4,
                                     channel_bytes=1 << 18)
        try:
            losses = [eng.step(mbs, tgts) for _ in range(3)]
            new_params = eng.get_params()
        finally:
            eng.shutdown()
        ref_losses, ref_params = run_reference_1f1b(
            fns, params, tx, [(mbs, tgts)] * 3)
        assert losses == ref_losses
        for a, b in zip(jax.tree.leaves(new_params),
                        jax.tree.leaves(ref_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gpt_matches_reference_bit_for_bit(self, ray_start_regular):
        """The dryrun's ref path on GPT: the engine's 2-step trajectory
        equals run_reference_1f1b exactly, and step-1 loss matches the
        single-program model.loss."""
        import optax

        from ray_tpu.models.gpt import gpt_pipeline_stages
        from ray_tpu.train.pipeline_cgraph import (CompiledPipelineEngine,
                                                   run_reference_1f1b)

        model, params, tokens, targets = _tiny_gpt(8, 16)
        mbs = [tokens[i * 2:(i + 1) * 2] for i in range(4)]
        tgts = [targets[i * 2:(i + 1) * 2] for i in range(4)]
        fns, sp, tied = gpt_pipeline_stages(model, params, 2)
        tx = optax.adam(1e-3)
        eng = CompiledPipelineEngine(fns, sp, tx, num_microbatches=4,
                                     tied=tied, channel_bytes=1 << 19)
        try:
            losses = [eng.step(mbs, tgts) for _ in range(2)]
        finally:
            eng.shutdown()
        ref_losses, _ = run_reference_1f1b(fns, sp, tx,
                                           [(mbs, tgts)] * 2, tied=tied)
        assert losses == ref_losses
        # and the stage split itself is faithful to the single program
        full_loss = float(model.loss(params, tokens, targets))
        assert abs(losses[0] - full_loss) < 1e-3

    def test_gpt_tied_embedding_update_matches_single_process(
            self, ray_start_regular):
        """One adam step over 2 stages x 2 microbatches leaves the
        parameters where the single-process step on the whole batch
        leaves them: the embedding on stage 0 and its tied copy, the
        head on the last stage (they exchange gradients every step),
        each half of the layer stack, and the final norm."""
        import jax
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        model, params, tokens, targets = _tiny_gpt(4, 32)
        tx = optax.adam(1e-3)
        fns, sp, tied = model.pipeline_stages(params, 2)
        eng = CompiledPipelineEngine(fns, sp, tx, num_microbatches=2,
                                     tied=tied, channel_bytes=1 << 19)
        try:
            loss = eng.step([tokens[:2], tokens[2:]],
                            [targets[:2], targets[2:]])
            stage0, stage1 = eng.get_params()
        finally:
            eng.shutdown()

        loss_ref, grads = jax.value_and_grad(model.loss)(
            params, tokens, targets)
        assert abs(loss - float(loss_ref)) < 1e-4
        updates, _ = tx.update(grads, tx.init(params), params)
        want = optax.apply_updates(params, updates)
        half = model.config.n_layer // 2
        for got, ref in (
                (stage0["wte"], want["wte"]),
                (stage1["head"], want["wte"]),
                (stage0["layers"]["w_qkv"], want["w_qkv"][:half]),
                (stage1["layers"]["w_qkv"], want["w_qkv"][half:]),
                (stage1["lnf_g"], want["lnf_g"])):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_interleaved_matches_non_interleaved(self, ray_start_regular):
        """4 chunks on 2 actors (virtual_stages=2, interleaved 1F1B)
        produces the same trajectory as 4 plain stages."""
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(4)
        mbs, tgts = _mlp_batches(4)
        tx = optax.adam(1e-2)
        res = {"CPU": 0.5}
        trajectories = []
        for V in (1, 2):
            eng = CompiledPipelineEngine(
                fns, params, tx, num_microbatches=4, virtual_stages=V,
                channel_bytes=1 << 18, resources_per_stage=res)
            try:
                trajectories.append(
                    [eng.step(mbs, tgts) for _ in range(3)])
            finally:
                eng.shutdown()
        assert trajectories[0] == trajectories[1]

    def test_remat_matches_saved_residuals(self, ray_start_regular):
        """Activation rematerialization recomputes the same values: the
        remat=True trajectory equals remat=False bit-for-bit."""
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(4)
        tx = optax.sgd(1e-2)
        trajectories = []
        for remat in (False, True):
            eng = CompiledPipelineEngine(
                fns, params, tx, num_microbatches=4, remat=remat,
                channel_bytes=1 << 18)
            try:
                trajectories.append(
                    [eng.step(mbs, tgts) for _ in range(2)])
            finally:
                eng.shutdown()
        assert trajectories[0] == trajectories[1]


# ---------------------------------------------------------------------------
# ZeRO-sharded dp update
# ---------------------------------------------------------------------------


class TestZeroUpdate:
    def test_zero_matches_replicated_and_shards_opt_state(
            self, ray_start_regular):
        """dp=2 x P=2: the ZeRO reduce-scatter/shard-update/all-gather
        trajectory matches the replicated allreduce update, and each
        replica holds ~1/dp of the optimizer-state bytes."""
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2, width=16)
        mbs, tgts = _mlp_batches(8, width=16)  # dp=2 x M=4
        tx = optax.adam(1e-2)
        res = {"CPU": 0.5}
        runs = {}
        for zero in (True, False):
            eng = CompiledPipelineEngine(
                fns, params, tx, num_microbatches=4, dp=2,
                zero_update=zero, channel_bytes=1 << 18,
                resources_per_stage=res)
            try:
                losses = [eng.step(mbs, tgts) for _ in range(3)]
                runs[zero] = (losses, eng.opt_state_bytes())
            finally:
                eng.shutdown()
        np.testing.assert_allclose(runs[True][0], runs[False][0],
                                   rtol=1e-6, atol=1e-7)
        for sharded, full in zip(runs[True][1], runs[False][1]):
            ratio = sharded / full
            assert 0.4 < ratio < 0.62, (sharded, full)

    def test_spmd_zero_update_matches_replicated(self):
        """The in-jit psum_scatter path (parallel/zero.py) against the
        plain full-state update on a virtual dp mesh."""
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.parallel import MeshSpec, build_mesh
        from ray_tpu.parallel.zero import make_zero_update_spmd

        mesh = build_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
        tx = optax.adamw(1e-2)
        params = {"w": jnp.arange(20., dtype=jnp.float32).reshape(4, 5)
                  / 20.0, "b": jnp.ones((3,), jnp.float32)}
        key = jax.random.PRNGKey(0)
        per = [jax.tree.map(
            lambda l, k=k: jax.random.normal(
                jax.random.fold_in(key, k), l.shape), params)
            for k in range(4)]
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *per)
        init_fn, update_fn = make_zero_update_spmd(tx, mesh, "dp")
        opt = init_fn(params)
        p1, opt = update_fn(params, stacked, opt)
        p2, _ = update_fn(p1, stacked, opt)
        # replicated reference, two chained steps
        gmean = jax.tree.map(lambda s: s.mean(0), stacked)
        ref_opt = tx.init(params)
        ref = params
        for _ in range(2):
            upd, ref_opt = tx.update(gmean, ref_opt, ref)
            ref = optax.apply_updates(ref, upd)
        for k in params:
            np.testing.assert_allclose(np.asarray(p2[k]),
                                       np.asarray(ref[k]),
                                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# faults + lifecycle
# ---------------------------------------------------------------------------


class TestFaultsAndLifecycle:
    def test_stage_death_mid_step_raises_typed_error(
            self, ray_start_regular, tmp_path):
        """Killing a MIDDLE stage while a step is in flight aborts the
        engine: step() raises CompiledGraphClosedError and shutdown()
        releases every channel segment."""
        import jax
        import jax.numpy as jnp
        import optax

        rt = ray_start_regular
        node = rt.nodes[rt.head_node_id]
        before = node.store.stats()["num_channels"]

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        started = tmp_path / "mid_stage_started"

        def mk_slow_mid():
            def sleepy(x):
                started.touch()
                time.sleep(0.25)
                return x

            def _cb(x):
                return jax.pure_callback(
                    sleepy, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

            # custom_vjp so the callback survives the engine's jax.vjp: a
            # bare pure_callback raises under JVP, and the step then
            # aborts on that error whenever it beats the kill
            slow = jax.custom_vjp(_cb)
            slow.defvjp(lambda x: (_cb(x), None), lambda _, g: (g,))

            def fn(p, x):
                return jnp.tanh(slow(x) @ p["w"] + p["b"])
            return fn

        fns, params = _mlp_chunks(3)
        fns[1] = mk_slow_mid()
        mbs, tgts = _mlp_batches(4)
        res = {"CPU": 0.5}
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=4,
                                     channel_bytes=1 << 18,
                                     resources_per_stage=res)
        assert node.store.stats()["num_channels"] > before
        result = {}

        def drive():
            try:
                eng.step(mbs, tgts, timeout=60)
                result["ok"] = True
            except BaseException as e:  # noqa: BLE001 — asserted below
                result["err"] = e

        t = threading.Thread(target=drive)
        t.start()
        # kill once the slow middle stage is inside the step (its four
        # forwards sleep 0.25 s each, so the step outlasts the kill)
        deadline = time.monotonic() + 60
        while not started.exists() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert started.exists(), "middle stage never ran"
        ray_tpu.kill(eng.actor_grid[0][1])
        t.join(timeout=60)
        assert not t.is_alive(), "step() wedged after stage death"
        assert isinstance(result.get("err"),
                          exceptions.CompiledGraphClosedError), result
        with pytest.raises(exceptions.CompiledGraphClosedError):
            eng.step(mbs, tgts)
        eng.shutdown()
        assert node.store.stats()["num_channels"] == before

    def test_stage_exception_propagates_and_poisons(
            self, ray_start_regular):
        """A raising stage fn surfaces as the original TaskError; the
        engine refuses further steps (state is indeterminate) but shuts
        down leak-free."""
        import optax

        rt = ray_start_regular
        node = rt.nodes[rt.head_node_id]
        before = node.store.stats()["num_channels"]

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        def mk_boom():
            def fn(p, x, targets):
                raise ValueError("stage exploded")
            return fn

        fns, params = _mlp_chunks(2)
        fns[1] = mk_boom()
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        try:
            with pytest.raises(exceptions.TaskError,
                               match="stage exploded"):
                eng.step(mbs, tgts, timeout=60)
            with pytest.raises(exceptions.CompiledGraphError,
                               match="poisoned"):
                eng.step(mbs, tgts)
        finally:
            eng.shutdown()
        assert node.store.stats()["num_channels"] == before

    def test_backward_error_on_middle_chunk_not_swallowed(
            self, ray_start_regular):
        """An error raised in a NON-last chunk's backward propagates
        only upstream, where chunk 0's backward has no outgoing channel
        — the latch in the executor's iterative loop must ship it to
        the driver via the stage report instead of letting step()
        return a clean-looking loss over corrupted gradients."""
        import jax
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        def mk_bwd_boom():
            import jax.numpy as jnp

            @jax.custom_vjp
            def poison(x):
                return x

            def p_fwd(x):
                return x, None

            def p_bwd(res, g):
                raise RuntimeError("backward exploded")

            poison.defvjp(p_fwd, p_bwd)

            def fn(p, x):
                return jnp.tanh(poison(x) @ p["w"] + p["b"])
            return fn

        fns, params = _mlp_chunks(3)
        fns[1] = mk_bwd_boom()
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        try:
            with pytest.raises(exceptions.TaskError,
                               match="backward exploded"):
                eng.step(mbs, tgts, timeout=60)
            with pytest.raises(exceptions.CompiledGraphError,
                               match="poisoned"):
                eng.step(mbs, tgts)
        finally:
            eng.shutdown()

    def test_shutdown_releases_channels_and_closes_engine(
            self, ray_start_regular):
        import optax

        rt = ray_start_regular
        node = rt.nodes[rt.head_node_id]
        before = node.store.stats()["num_channels"]

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        during = node.store.stats()["num_channels"]
        # in + targets + loss + fwd + bwd + 2 reports = 7 segments
        assert during - before == 7
        eng.step(mbs, tgts)
        eng.shutdown()
        eng.shutdown()  # idempotent
        assert node.store.stats()["num_channels"] == before
        with pytest.raises(exceptions.CompiledGraphClosedError):
            eng.step(mbs, tgts)

    def test_step_input_validation(self, ray_start_regular):
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(4)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=4,
                                     channel_bytes=1 << 18)
        try:
            with pytest.raises(ValueError, match="num_microbatches"):
                eng.step(mbs[:2], tgts[:2])
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# checkpoint + recover (ISSUE 10)
# ---------------------------------------------------------------------------


class TestCheckpointRecover:
    def test_checkpoint_commit_is_atomic_and_latest_points_at_it(
            self, ray_start_regular, tmp_path):
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, optax.adam(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18,
                                     checkpoint_dir=d, checkpoint_every=1)
        try:
            eng.step(mbs, tgts)
            eng.step(mbs, tgts)
            eng.wait_for_checkpoints()
            names = sorted(os.listdir(d))
            # step-0 commit at construction + one per step, no tmp litter
            assert names == ["LATEST", "ckpt-00000000.pkl",
                             "ckpt-00000001.pkl", "ckpt-00000002.pkl"]
            latest = CompiledPipelineEngine.latest_checkpoint(d)
            assert latest.endswith("ckpt-00000002.pkl")
            ckpt = CompiledPipelineEngine.load_checkpoint(latest)
            assert ckpt["step"] == 2
            assert len(ckpt["states"]) == 1          # dp rows
            assert len(ckpt["states"][0]) == 2       # stages
        finally:
            eng.shutdown()

    def test_recover_after_stage_kill_matches_clean_restart_bitwise(
            self, ray_start_regular, tmp_path, wait_engine_aborted):
        """The ISSUE 10 acceptance bar: kill a stage mid-step, recover,
        and the resumed loss trajectory + final params are bit-identical
        to a fresh engine restarted from the same checkpoint."""
        import jax
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(4)
        tx = optax.adam(1e-2)
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=4,
                                     channel_bytes=1 << 18,
                                     checkpoint_dir=d, checkpoint_every=2)
        eng.step(mbs, tgts)
        eng.step(mbs, tgts)                      # checkpoint at step 2
        eng.wait_for_checkpoints()
        ray_tpu.kill(eng.actor_grid[0][1])       # stage death
        with pytest.raises((exceptions.CompiledGraphClosedError,
                            exceptions.CompiledGraphError)):
            # the death may abort before or during the next step
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                eng.step(mbs, tgts, timeout=30)
        assert wait_engine_aborted(eng)
        ck_at_kill = CompiledPipelineEngine.latest_checkpoint(d)
        resumed_from = eng.recover()
        assert resumed_from == 2
        resumed = [eng.step(mbs, tgts) for _ in range(2)]
        params_a = eng.get_params()
        eng.shutdown()

        fresh = CompiledPipelineEngine(fns, params, tx,
                                       num_microbatches=4,
                                       channel_bytes=1 << 18)
        try:
            assert fresh.restore(ck_at_kill) == 2
            replay = [fresh.step(mbs, tgts) for _ in range(2)]
            params_b = fresh.get_params()
        finally:
            fresh.shutdown()
        assert resumed == replay
        for a, b in zip(jax.tree.leaves(params_a),
                        jax.tree.leaves(params_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("recover_when", [
        "abort-done",
        # as a user's loop does: recover() as soon as the engine is closed
        pytest.param("at-once", marks=pytest.mark.xfail(
            strict=False, reason="ROADMAP C11: a late abort thread tears "
                                 "down the recovered graph")),
    ])
    def test_recover_without_checkpoint_restarts_from_step_zero(
            self, ray_start_regular, wait_engine_aborted, recover_when):
        """No checkpoint_dir: recover() respawns with the construction
        params — a step-0 restart with the exact initial trajectory."""
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        tx = optax.sgd(1e-2)
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=2,
                                     channel_bytes=1 << 18)
        try:
            first = eng.step(mbs, tgts)
            ray_tpu.kill(eng.actor_grid[0][0])
            if recover_when == "abort-done":
                assert wait_engine_aborted(eng)
            else:
                deadline = time.monotonic() + 30
                while eng._closed_error is None:
                    assert time.monotonic() < deadline
                    time.sleep(0.05)
            assert eng.recover() == 0
            assert eng.step(mbs, tgts) == first
        finally:
            eng.shutdown()

    def test_zero_sharded_opt_state_roundtrips_through_checkpoint(
            self, ray_start_regular, tmp_path):
        """dp=2 ZeRO: each rank's 1/dp opt-state shard is persisted and
        restored shard-for-shard — the restored trajectory matches an
        uninterrupted run exactly."""
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2, width=16)
        mbs, tgts = _mlp_batches(8, width=16)    # dp=2 x M=4
        tx = optax.adam(1e-2)
        res = {"CPU": 0.5}
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=4,
                                     dp=2, channel_bytes=1 << 18,
                                     resources_per_stage=res,
                                     checkpoint_dir=d, checkpoint_every=2)
        losses = [eng.step(mbs, tgts) for _ in range(4)]
        eng.wait_for_checkpoints()
        eng.shutdown()
        fresh = CompiledPipelineEngine(fns, params, tx,
                                       num_microbatches=4, dp=2,
                                       channel_bytes=1 << 18,
                                       resources_per_stage=res)
        try:
            ck = os.path.join(d, "ckpt-00000002.pkl")
            ckpt = CompiledPipelineEngine.load_checkpoint(ck)
            assert ckpt["states"][0][0]["kind"] == "zero"
            assert fresh.restore(ck) == 2
            replay = [fresh.step(mbs, tgts) for _ in range(2)]
        finally:
            fresh.shutdown()
        assert replay == losses[2:]

    def test_restore_rejects_mismatched_shape(self, ray_start_regular,
                                              tmp_path):
        import optax

        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18,
                                     checkpoint_dir=d)
        try:
            path = eng.save_checkpoint(blocking=True)
        finally:
            eng.shutdown()
        fns3, params3 = _mlp_chunks(3)
        other = CompiledPipelineEngine(fns3, params3, optax.sgd(1e-2),
                                       num_microbatches=2,
                                       channel_bytes=1 << 18)
        try:
            with pytest.raises(ValueError, match="shape"):
                other.restore(path)
        finally:
            other.shutdown()


# ---------------------------------------------------------------------------
# observability + perf envelope
# ---------------------------------------------------------------------------


class TestPerfAndObservability:
    def test_pipeline_metrics_emitted(self, ray_start_regular):
        import optax

        from ray_tpu.util import metrics
        from ray_tpu.train.pipeline_cgraph import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(2)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=2,
                                     channel_bytes=1 << 18)
        try:
            for _ in range(3):
                eng.step(mbs, tgts)
            assert eng.last_reports and all(
                r["in_flight_residuals"] == 0 for r in eng.last_reports)
        finally:
            eng.shutdown()
        body = metrics._render()
        assert "ray_tpu_pipeline_step_seconds" in body
        # worker-side stage metrics ship on the throttled delta path
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            body = metrics._render()
            if "ray_tpu_pipeline_stage_exec_seconds" in body \
                    and "ray_tpu_pipeline_bubble_wait_seconds" in body:
                break
            time.sleep(0.3)
        assert "ray_tpu_pipeline_stage_exec_seconds" in body
        assert "ray_tpu_pipeline_bubble_wait_seconds" in body
