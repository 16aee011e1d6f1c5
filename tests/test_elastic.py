"""Elastic capacity on preemptible pods (ISSUE 12).

Acceptance surface: `resize(dp±k)` resumes a loss trajectory and final
params bit-identical to a fixed-size run at the new width restored from
the same (resharded) checkpoint — for zero AND fsdp opt-state kinds;
ZeRO opt-state shards round-trip across widths exactly; a draining serve
replica finishes its in-flight streams with zero failures while the
router stops assigning it new ones; a preemption notice shrinks a live
training run hands-off, and a premature SIGKILL (axe beats the drain)
falls back to the PR 9 checkpoint/recover path; the autoscaler turns
provider preemption notices into the NODE_PREEMPTING drain pipeline and
counts outcomes in `ray_tpu_node_preemptions_total`.
"""
import os
import threading
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


def _dump_ckpt(tmp_path, payload, name):
    import cloudpickle

    p = str(tmp_path / name)
    with open(p, "wb") as f:
        cloudpickle.dump(payload, f)
    return p


# ---------------------------------------------------------------------------
# opt-state resharding — pure data plane, no cluster
# ---------------------------------------------------------------------------


class TestOptReshard:
    def test_zero_shards_roundtrip_across_widths(self):
        """Merge-then-split is exact at any width chain: shards saved at
        dp=3 re-split across dp=2 and back merge to the same bytes."""
        import jax
        import optax

        from ray_tpu.parallel.zero import (flatten_tree, merge_opt_shards,
                                           shard_bounds, split_opt_state)

        params = {"w": np.arange(40, dtype=np.float32).reshape(8, 5) / 7,
                  "b": np.ones((3,), np.float32)}
        flat, spec = flatten_tree(params)
        tx = optax.adam(1e-2)
        shards3 = [jax.jit(tx.init)(flat[lo:hi])
                   for lo, hi in shard_bounds(spec.size, 3)]
        full = merge_opt_shards(shards3)
        # every moment leaf covers the whole vector after the merge
        for leaf in jax.tree.leaves(full):
            if np.ndim(leaf) >= 1:
                assert np.shape(leaf) == (spec.size,)
        again = merge_opt_shards(split_opt_state(full, 2, spec.size))
        for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(again)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the reference layout matches what tx.init of the full flat
        # vector would produce (same treedef, same shapes)
        ref = tx.init(flat)
        assert jax.tree.structure(ref) == jax.tree.structure(full)

    def test_full_tree_and_flat_plane_convert_exactly(self):
        """flatten_opt_state (grow path) produces exactly tx.init(flat),
        and unflatten_opt_state (shrink-to-1 path) inverts it."""
        import jax
        import optax

        from ray_tpu.parallel.zero import (flatten_opt_state, flatten_tree,
                                           unflatten_opt_state)

        params = {"0": {"w": np.full((4, 4), 0.25, np.float32),
                        "b": np.zeros((4,), np.float32)},
                  "1": {"w": np.full((4, 2), -1.0, np.float32)}}
        tx = optax.adam(1e-2)
        tree_state = tx.init(params)
        flat, spec = flatten_tree(params)
        flat_state = flatten_opt_state(tree_state, params)
        ref = tx.init(flat)
        assert jax.tree.structure(flat_state) == jax.tree.structure(ref)
        for a, b in zip(jax.tree.leaves(flat_state), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        back = unflatten_opt_state(flat_state, spec)
        assert jax.tree.structure(back) == jax.tree.structure(tree_state)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_reshard_checkpoint_rejects_bad_width(self):
        from ray_tpu.train import reshard_checkpoint

        ckpt = {"step": 0,
                "engine": {"num_chunks": 2, "num_stages": 2, "virtual": 1,
                           "dp": 2, "fsdp": 1, "zero_update": True,
                           "num_microbatches": 4},
                "states": [[{"params": [0], "opt": None, "kind": "none"}] * 2
                           for _ in range(2)]}
        with pytest.raises(ValueError, match="divide"):
            reshard_checkpoint(ckpt, 3)
        with pytest.raises(ValueError, match=">= 1"):
            reshard_checkpoint(ckpt, 0)


# ---------------------------------------------------------------------------
# resize(dp±k) — the training tentpole
# ---------------------------------------------------------------------------


class TestResize:
    def test_shrink_bitwise_vs_fixed_size_reference(self, ray_start_regular,
                                                    tmp_path):
        """dp=2 (ZeRO shards) -> resize(1): the continued trajectory AND
        final params equal a fixed-size dp=1 engine restored from the
        SAME checkpoint resharded to width 1 (acceptance bar)."""
        import jax
        import optax

        from ray_tpu.train import (CompiledPipelineEngine,
                                   reshard_checkpoint)

        fns, params = _mlp_chunks(2, width=16)
        mbs, tgts = _mlp_batches(8, width=16)   # dp*M = 8 global mbs
        tx = optax.adam(1e-2)
        res = {"CPU": 0.5}
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=4,
                                     dp=2, channel_bytes=1 << 18,
                                     resources_per_stage=res,
                                     checkpoint_dir=d)
        eng.step(mbs, tgts)
        eng.step(mbs, tgts)
        ck = eng.save_checkpoint(blocking=True)
        assert eng.resize(1) == 2
        assert eng.dp == 1 and eng.num_microbatches == 8
        resumed = [eng.step(mbs, tgts) for _ in range(2)]
        params_a = eng.get_params()
        eng.shutdown()

        resharded = reshard_checkpoint(
            CompiledPipelineEngine.load_checkpoint(ck), 1)
        assert resharded["states"][0][0]["kind"] == "full"
        p = _dump_ckpt(tmp_path, resharded, "resharded1.pkl")
        fresh = CompiledPipelineEngine(fns, params, tx, num_microbatches=8,
                                       channel_bytes=1 << 18,
                                       resources_per_stage=res)
        try:
            assert fresh.restore(p) == 2
            replay = [fresh.step(mbs, tgts) for _ in range(2)]
            params_b = fresh.get_params()
        finally:
            fresh.shutdown()
        assert resumed == replay
        for a, b in zip(jax.tree.leaves(params_a),
                        jax.tree.leaves(params_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_grow_bitwise_vs_fixed_size_reference(self, ray_start_regular,
                                                  tmp_path):
        """dp=1 (replicated tree opt state) -> resize(2): the full state
        converts to flat ZeRO shards and the continued run equals a
        fixed-size dp=2 engine restored from the resharded checkpoint."""
        import jax
        import optax

        from ray_tpu.train import (CompiledPipelineEngine,
                                   reshard_checkpoint)

        fns, params = _mlp_chunks(2, width=16)
        mbs, tgts = _mlp_batches(8, width=16)
        tx = optax.adam(1e-2)
        res = {"CPU": 0.5}
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=8,
                                     channel_bytes=1 << 18,
                                     resources_per_stage=res,
                                     checkpoint_dir=d)
        eng.step(mbs, tgts)
        eng.step(mbs, tgts)
        ck = eng.save_checkpoint(blocking=True)
        assert eng.resize(2) == 2
        assert eng.dp == 2 and eng.num_microbatches == 4
        resumed = [eng.step(mbs, tgts) for _ in range(2)]
        params_a = eng.get_params()
        eng.shutdown()

        resharded = reshard_checkpoint(
            CompiledPipelineEngine.load_checkpoint(ck), 2)
        assert resharded["states"][0][0]["kind"] == "zero"
        p = _dump_ckpt(tmp_path, resharded, "resharded2.pkl")
        fresh = CompiledPipelineEngine(fns, params, tx, num_microbatches=4,
                                       dp=2, channel_bytes=1 << 18,
                                       resources_per_stage=res)
        try:
            assert fresh.restore(p) == 2
            replay = [fresh.step(mbs, tgts) for _ in range(2)]
            params_b = fresh.get_params()
        finally:
            fresh.shutdown()
        assert resumed == replay
        for a, b in zip(jax.tree.leaves(params_a),
                        jax.tree.leaves(params_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resize_fsdp_kind_bitwise(self, ray_start_regular, tmp_path):
        """fsdp=2 stages (sharded opt state on the in-actor mesh): the
        dp axis resizes around the fsdp plane — checkpoint kind 'fsdp'
        replicates across new rows and the grown run equals the
        fixed-size reference restored from the resharded checkpoint."""
        import jax
        import optax

        from ray_tpu.train import (CompiledPipelineEngine,
                                   reshard_checkpoint)

        fns, params = _mlp_chunks(2, width=16)
        mbs, tgts = _mlp_batches(8, width=16)
        tx = optax.adam(1e-2)
        res = {"CPU": 0.5}
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, tx, num_microbatches=8,
                                     fsdp=2, channel_bytes=1 << 18,
                                     resources_per_stage=res,
                                     checkpoint_dir=d)
        eng.step(mbs, tgts)
        ck = eng.save_checkpoint(blocking=True)
        ckpt = CompiledPipelineEngine.load_checkpoint(ck)
        assert ckpt["states"][0][0]["kind"] == "fsdp"
        assert eng.resize(2) == 1
        resumed = [eng.step(mbs, tgts) for _ in range(2)]
        params_a = eng.get_params()
        eng.shutdown()

        resharded = reshard_checkpoint(ckpt, 2)
        assert resharded["states"][1][0]["kind"] == "fsdp"
        p = _dump_ckpt(tmp_path, resharded, "resharded_fsdp.pkl")
        fresh = CompiledPipelineEngine(fns, params, tx, num_microbatches=4,
                                       dp=2, fsdp=2,
                                       channel_bytes=1 << 18,
                                       resources_per_stage=res)
        try:
            assert fresh.restore(p) == 1
            replay = [fresh.step(mbs, tgts) for _ in range(2)]
            params_b = fresh.get_params()
        finally:
            fresh.shutdown()
        assert resumed == replay
        for a, b in zip(jax.tree.leaves(params_a),
                        jax.tree.leaves(params_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resize_validation(self, ray_start_regular):
        import optax

        from ray_tpu.train import CompiledPipelineEngine

        fns, params = _mlp_chunks(2)
        mbs, tgts = _mlp_batches(4)
        eng = CompiledPipelineEngine(fns, params, optax.sgd(1e-2),
                                     num_microbatches=4,
                                     channel_bytes=1 << 18)
        try:
            first = eng.step(mbs, tgts)
            with pytest.raises(ValueError, match="divide"):
                eng.resize(3)
            with pytest.raises(ValueError, match=">= 1"):
                eng.resize(0)
            assert eng.resize(eng.dp) == 1   # same width: no-op
            # the engine still steps after rejected resizes
            assert isinstance(first, float)
            eng.step(mbs, tgts)
        finally:
            eng.shutdown()

    def test_recover_reshards_stale_width_checkpoint(self,
                                                     ray_start_regular,
                                                     tmp_path,
                                                     wait_engine_aborted):
        """recover() after a resize finds the newest commit written at
        the OLD width and reshards it to the current one instead of
        rejecting the restore."""
        import optax

        from ray_tpu.train import CompiledPipelineEngine

        fns, params = _mlp_chunks(2, width=16)
        mbs, tgts = _mlp_batches(8, width=16)
        res = {"CPU": 0.5}
        d = str(tmp_path / "ck")
        eng = CompiledPipelineEngine(fns, params, optax.adam(1e-2),
                                     num_microbatches=4, dp=2,
                                     channel_bytes=1 << 18,
                                     resources_per_stage=res,
                                     checkpoint_dir=d, checkpoint_every=1)
        try:
            eng.step(mbs, tgts)          # commit at step 1, width dp=2
            eng.wait_for_checkpoints()
            eng.resize(1)
            ray_tpu.kill(eng.actors[0])  # unplanned death after resize
            assert wait_engine_aborted(eng)
            assert eng.recover() == 1    # dp=2 commit resharded to dp=1
            assert eng.dp == 1
            eng.step(mbs, tgts)
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# serve draining — notice -> drain -> handoff -> clean exit
# ---------------------------------------------------------------------------


class TestServeDraining:
    def test_drain_under_load_zero_failed_streams(self, ray_start_regular):
        """Mark the replica serving live streams draining: the router
        stops assigning it NEW streams, the in-flight ones complete with
        every token (failover path covers an early kill), the controller
        starts a replacement and retires the corpse once idle."""
        from ray_tpu import serve
        from ray_tpu.serve.llm import resilient_stream

        @serve.deployment(num_replicas=2, health_check_period_s=0.3,
                          health_check_timeout_s=2.0)
        class DetLLM:
            def __call__(self, payload):
                toks = list(payload["tokens"])
                n = int(payload.get("max_tokens", 16))

                def gen(ctx=toks, n=n):
                    ctx = list(ctx)
                    for _ in range(n):
                        t = (sum(ctx) * 31 + len(ctx)) % 97
                        ctx.append(t)
                        time.sleep(0.03)
                        yield t

                return gen()

        h = serve.run(DetLLM.bind())
        try:
            n_clients, n_tokens = 4, 24
            prompts = [[3, 1, 4], [2, 7], [1, 8, 2, 8], [9]]
            wants = []
            for p in prompts:
                ctx, want = list(p), []
                for _ in range(n_tokens):
                    t = (sum(ctx) * 31 + len(ctx)) % 97
                    ctx.append(t)
                    want.append(t)
                wants.append(want)

            gens = [resilient_stream(h, {"tokens": prompts[i],
                                         "max_tokens": n_tokens})
                    for i in range(n_clients)]
            got = [[] for _ in range(n_clients)]
            errs = [None] * n_clients
            state = {"drained": None}
            lock = threading.Lock()

            def client(i):
                try:
                    for tok in gens[i]:
                        got[i].append(tok)
                        with lock:
                            due = (state["drained"] is None
                                   and sum(len(g) for g in got) >= 8)
                            if due:
                                state["drained"] = \
                                    gens[i].replica_actor_id
                        if due:
                            controller = ray_tpu.get_actor(
                                "SERVE_CONTROLLER")
                            marked = ray_tpu.get(
                                controller.drain_replicas.remote(
                                    [state["drained"].hex()], 30.0),
                                timeout=30)
                            assert marked == 1
                except BaseException as e:  # noqa: BLE001 — checked below
                    errs[i] = e

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads), "client hung"
            assert not any(errs), f"stream errors during drain: {errs}"
            for i in range(n_clients):
                assert got[i] == wants[i], f"stream {i} lost tokens"
            drained = state["drained"]
            assert drained is not None

            # the drained replica leaves the routing table, a replacement
            # arrives, and the corpse is retired once idle
            controller = ray_tpu.get_actor("SERVE_CONTROLLER")
            deadline = time.monotonic() + 30
            while True:
                st = ray_tpu.get(controller.status.remote(),
                                 timeout=30)["DetLLM"]
                _, _, reps = ray_tpu.get(
                    controller.get_replicas.remote("DetLLM"), timeout=30)
                if (st["running"] == 2 and st["draining"] == 0
                        and all(r._actor_id != drained for r in reps)):
                    break
                assert time.monotonic() < deadline, st
                time.sleep(0.2)
        finally:
            serve.shutdown()

    def test_draining_visible_in_ping_and_status(self, ray_start_regular):
        from ray_tpu import serve

        @serve.deployment(num_replicas=1, health_check_period_s=0.3)
        class Echo:
            def __call__(self, x):
                return x

        h = serve.run(Echo.bind())
        try:
            controller = ray_tpu.get_actor("SERVE_CONTROLLER")
            _, _, reps = ray_tpu.get(
                controller.get_replicas.remote("Echo"), timeout=30)
            assert len(reps) == 1
            ping = ray_tpu.get(reps[0].ping.remote(), timeout=30)
            assert ping["draining"] is False
            marked = ray_tpu.get(controller.drain_replicas.remote(
                [reps[0]._actor_id.hex()], 60.0), timeout=30)
            assert marked == 1
            st = ray_tpu.get(controller.status.remote(),
                             timeout=30)["Echo"]
            assert st["draining"] == 1
            # the replica's own ping flips once the mark lands
            deadline = time.monotonic() + 10
            while True:
                ping = ray_tpu.get(reps[0].ping.remote(), timeout=30)
                if ping["draining"]:
                    break
                assert time.monotonic() < deadline
                time.sleep(0.1)
            # router-facing table no longer offers the draining replica
            _, _, visible = ray_tpu.get(
                controller.get_replicas.remote("Echo"), timeout=30)
            assert all(r._actor_id != reps[0]._actor_id for r in visible)
        finally:
            serve.shutdown()


# ---------------------------------------------------------------------------
# preemption notices end to end — autoscaler, chaos, hands-off resize
# ---------------------------------------------------------------------------


class TestPreemptionNotice:
    def test_autoscaler_delivers_notice_and_counts_drained(self):
        """FakeSliceProvider scheduled preemption -> autoscaler update
        delivers the NODE_PREEMPTING drain: node excluded from
        scheduling views, then terminated cleanly once idle, counted
        outcome=drained."""
        from ray_tpu.autoscaler import (AutoscalerConfig, FakeSliceProvider,
                                        StandardAutoscaler)
        from ray_tpu.util import metrics

        rt = ray_tpu.init(num_cpus=1)
        provider = FakeSliceProvider(rt, resources_per_node={"CPU": 2.0})
        sc = StandardAutoscaler(rt, provider, AutoscalerConfig(
            min_workers=0, max_workers=2, idle_timeout_s=60.0))
        try:
            sc.request_resources([{"CPU": 2.0}])
            stats = sc.update()
            assert stats["launched"] == 1
            nid = provider.non_terminated_nodes()[0]
            assert any(v.node_id == nid for v in rt._views())

            provider.schedule_preemption(nid, notice_in_s=0.0,
                                         grace_s=30.0)
            sc.request_resources([])  # drop the floor: node is idle
            stats = sc.update()
            assert stats["notices_delivered"] == 1
            node = rt.nodes[nid]
            assert node.draining
            info = next(n for n in rt.gcs.nodes() if n.node_id == nid)
            assert info.draining and info.alive
            # drained out of the scheduler's world while still alive
            assert all(v.node_id != nid for v in rt._views())

            # idle + draining -> clean terminate on the next pass, no
            # idle_timeout wait; outcome counts as drained
            deadline = time.monotonic() + 20
            while provider.non_terminated_nodes():
                sc.update()
                assert time.monotonic() < deadline
                time.sleep(0.2)
            body = metrics._render()
            assert 'ray_tpu_node_preemptions_total{outcome="drained"}' \
                in body
        finally:
            sc.stop()
            provider.shutdown()
            ray_tpu.shutdown()

    def test_chaos_preempt_grammar(self):
        from ray_tpu.chaos import ChaosPlan, PreemptSpec

        plan = ChaosPlan.parse("seed=3;preempt=node:ab12@1.5+4")
        assert plan.preempts == (
            PreemptSpec(at_s=1.5, grace_s=4.0, target="node:ab12"),)
        # grace defaults when omitted; bare node target allowed
        plan = ChaosPlan.parse("preempt=node@2")
        assert plan.preempts[0].grace_s == 5.0
        with pytest.raises(ValueError, match="unknown chaos spec"):
            ChaosPlan.parse("preemptt=node@1")

    def test_notice_resizes_live_training_hands_off(self, tmp_path):
        """A NODE_PREEMPTING event for a node hosting dp rows shrinks
        the engine at the next step boundary — no operator in the loop —
        and the shrunken engine keeps training off the doomed node."""
        import optax

        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.train import CompiledPipelineEngine

        c = Cluster(head_resources={"CPU": 2.0})
        try:
            remote = c.add_remote_node(num_cpus=2.0)
            fns, params = _mlp_chunks(2, width=16)
            mbs, tgts = _mlp_batches(8, width=16)
            eng = CompiledPipelineEngine(
                fns, params, optax.adam(1e-2), num_microbatches=4, dp=2,
                channel_bytes=1 << 18, resources_per_stage={"CPU": 0.5})
            try:
                eng.enable_elastic(min_dp=1, grow_on_join=False)
                eng.step(mbs, tgts)
                n_remote = sum(1 for row in eng._plans for p in row
                               if p.node.node_id == remote.node_id)
                assert n_remote >= 1, "SPREAD left the remote empty"
                c.runtime.on_preemption_notice(remote.node_id, 60.0)
                # next step triggers the pending shrink — off the doomed
                # node, no operator in the loop
                loss = eng.step(mbs, tgts)
                assert isinstance(loss, float)
                assert eng.dp == 1
                assert all(p.node.node_id != remote.node_id
                           for row in eng._plans for p in row)
                eng.step(mbs, tgts)
            finally:
                eng.shutdown()
        finally:
            c.shutdown()

    def test_notice_then_premature_sigkill_recovers(self, tmp_path,
                                                    wait_engine_aborted):
        """The race the ISSUE names: notice delivered, but the axe lands
        before the drain finishes — the engine falls back to the PR 9
        checkpoint/recover path and resumes bit-consistently."""
        import optax

        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.train import CompiledPipelineEngine

        c = Cluster(head_resources={"CPU": 2.0})
        try:
            remote = c.add_remote_node(num_cpus=2.0)
            fns, params = _mlp_chunks(2, width=16)
            mbs, tgts = _mlp_batches(8, width=16)
            d = str(tmp_path / "ck")
            eng = CompiledPipelineEngine(
                fns, params, optax.adam(1e-2), num_microbatches=4, dp=2,
                channel_bytes=1 << 18, resources_per_stage={"CPU": 0.5},
                checkpoint_dir=d, checkpoint_every=1)
            try:
                eng.enable_elastic(min_dp=1, grow_on_join=False)
                eng.step(mbs, tgts)
                eng.wait_for_checkpoints()
                # notice... and the axe beats the next step boundary.
                # Depending on when the death lands relative to the
                # pending shrink, the failure surfaces as the abort
                # (CompiledGraphClosedError), a poisoned step, or a
                # replica-loss error from the resize's state pull —
                # all of which the recover() fallback must absorb.
                c.runtime.on_preemption_notice(remote.node_id, 0.1)
                c.remove_node(remote, kill=True)
                with pytest.raises((exceptions.CompiledGraphClosedError,
                                    exceptions.CompiledGraphError,
                                    exceptions.GetTimeoutError,
                                    exceptions.ActorDiedError,
                                    exceptions.ActorUnavailableError,
                                    exceptions.WorkerCrashedError,
                                    exceptions.ObjectLostError,
                                    TimeoutError)):
                    deadline = time.monotonic() + 30
                    while time.monotonic() < deadline:
                        eng.step(mbs, tgts, timeout=30)
                # whichever error came first, the node's death aborts the
                # engine too: let that finish before recover()
                assert wait_engine_aborted(eng)
                resumed_from = eng.recover()
                assert resumed_from >= 1
                # resize may still be pending from the notice; stepping
                # applies it against the now-dead node's absence
                eng.step(mbs, tgts)
            finally:
                eng.shutdown()
        finally:
            c.shutdown()
