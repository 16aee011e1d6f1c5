"""Tests for the mesh/collective layer (ray_tpu.parallel).

Runs on the virtual 8-device CPU mesh set up in conftest.py — the
reference-style way to exercise pod-scale sharding logic in CI
(ref: python/ray/tests multi-node via cluster_utils; here the analog is
xla_force_host_platform_device_count)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import (AxisRules, MeshSpec, allgather, allreduce,
                              barrier, broadcast, build_mesh,
                              create_collective_group, MeshGroup,
                              MeshWorkerMixin, reducescatter, send, recv,
                              shard_constraint, virtual_mesh)


class TestMesh:
    def test_resolve_wildcard(self):
        d = MeshSpec(dp=-1, tp=2).resolve(8)
        assert d["dp"] == 4 and d["tp"] == 2

    def test_resolve_exact(self):
        d = MeshSpec(dp=2, tp=2, sp=2).resolve(8)
        assert d["dp"] == 2 and d["tp"] == 2 and d["sp"] == 2

    def test_resolve_mismatch(self):
        with pytest.raises(ValueError):
            MeshSpec(dp=3).resolve(8)

    def test_build_mesh_axes(self):
        mesh = virtual_mesh(8, MeshSpec(dp=2, tp=4))
        assert mesh.shape["dp"] == 2
        assert mesh.shape["tp"] == 4
        assert mesh.shape["pp"] == 1

    def test_axis_rules(self):
        rules = AxisRules()
        spec = rules.mesh_axes(("batch", "seq", "embed"))
        assert spec == P(("dp", "fsdp"), "sp", "fsdp")
        assert rules.mesh_axes(("unknown",)) == P()

    def test_sharded_matmul(self):
        mesh = virtual_mesh(8, MeshSpec(dp=2, tp=4))
        x = jnp.ones((16, 32))
        w = jnp.ones((32, 64))

        @jax.jit
        def f(x, w):
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(("dp", "fsdp"), None)))
            w = jax.lax.with_sharding_constraint(
                w, NamedSharding(mesh, P(None, "tp")))
            return x @ w

        out = f(x, w)
        np.testing.assert_allclose(np.asarray(out), 32.0)

    def test_shard_constraint_logical(self):
        mesh = virtual_mesh(8, MeshSpec(dp=8))
        x = jnp.zeros((8, 4))
        y = shard_constraint(x, mesh, "batch", None)
        assert y.shape == x.shape


class TestCollective:
    def test_allreduce_broadcast_gather(self, ray_start_regular):
        import ray_tpu

        @ray_tpu.remote
        class Worker:
            def __init__(self, rank, world):
                self.rank = rank
                # actor-lifetime group: dies with the worker process
                create_collective_group(  # graftcheck: disable=GC030
                    world, rank, group_name="g1")

            def do_allreduce(self):
                return allreduce(np.full((4,), self.rank + 1.0), "g1")

            def do_broadcast(self):
                return broadcast(np.array([self.rank]), src_rank=2, group_name="g1")

            def do_gather(self):
                return allgather(self.rank, "g1")

            def do_rs(self):
                return reducescatter(np.arange(8.0), "g1")

        world = 4
        ws = [Worker.remote(i, world) for i in range(world)]
        outs = ray_tpu.get([w.do_allreduce.remote() for w in ws], timeout=60)
        for o in outs:
            np.testing.assert_allclose(o, np.full((4,), 1.0 + 2 + 3 + 4))
        outs = ray_tpu.get([w.do_broadcast.remote() for w in ws], timeout=60)
        for o in outs:
            assert o[0] == 2
        outs = ray_tpu.get([w.do_gather.remote() for w in ws], timeout=60)
        assert outs[0] == [0, 1, 2, 3]
        outs = ray_tpu.get([w.do_rs.remote() for w in ws], timeout=60)
        np.testing.assert_allclose(outs[1], np.array([2., 3.]) * 4)

    def test_send_recv(self, ray_start_regular):
        import ray_tpu

        @ray_tpu.remote
        class P2P:
            def __init__(self, rank, world):
                self.rank = rank
                # actor-lifetime group: dies with the worker process
                create_collective_group(  # graftcheck: disable=GC030
                    world, rank, group_name="p2p")

            def do_send(self):
                send(np.array([42.0]), dst_rank=1, group_name="p2p", tag=7)
                return True

            def do_recv(self):
                return recv(src_rank=0, group_name="p2p", tag=7)

        a, b = P2P.remote(0, 2), P2P.remote(1, 2)
        r = b.do_recv.remote()
        ray_tpu.get(a.do_send.remote(), timeout=60)
        np.testing.assert_allclose(ray_tpu.get(r, timeout=60), [42.0])


class TestMeshGroup:
    def test_gang_spmd(self, ray_start_regular):
        class W(MeshWorkerMixin):
            pass

        group = MeshGroup(num_workers=2, spec=MeshSpec(dp=-1),
                          worker_cls=W, devices_per_process=4)
        assert group.devices_per_worker == [4, 4]

        def step(self, scale):
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            x = jnp.arange(8.0).reshape(8, 1)

            def f(x):
                return (x * scale).sum()

            out = jax.jit(f, in_shardings=NamedSharding(self.mesh, P("dp")),
                          out_shardings=None)(x)
            return float(out)

        outs = group.run(step, 3.0)
        assert outs == [84.0, 84.0]
        group.shutdown()


class TestPipeline:
    def test_1f1b_schedule_structure(self):
        from ray_tpu.parallel.pipeline import schedule_1f1b

        for P_, M in ((2, 4), (4, 8), (4, 2), (3, 3)):
            sched = schedule_1f1b(P_, M)
            assert len(sched) == P_
            for i, ops in enumerate(sched):
                fwds = [m for k, m in ops if k == "fwd"]
                bwds = [m for k, m in ops if k == "bwd"]
                # every microbatch exactly once per direction, in order
                assert fwds == list(range(M)), (i, ops)
                assert bwds == list(range(M)), (i, ops)
                # bwd(j) only after fwd(j) on the same stage
                pos = {("fwd", m): t for t, (k, m) in enumerate(ops)
                       if k == "fwd"}
                for t, (k, m) in enumerate(ops):
                    if k == "bwd":
                        assert pos[("fwd", m)] < t
                # 1F1B memory bound: in-flight fwds never exceed P - i
                live = 0
                peak = 0
                for k, m in ops:
                    live += 1 if k == "fwd" else -1
                    peak = max(peak, live)
                assert peak <= min(P_ - i, M), (i, peak)

    def test_1f1b_warmup_counts(self):
        from ray_tpu.parallel.pipeline import schedule_1f1b

        sched = schedule_1f1b(4, 8)
        for i, ops in enumerate(sched):
            warmup = 0
            for k, _ in ops:
                if k != "fwd":
                    break
                warmup += 1
            assert warmup == min(4 - i, 8)
            # steady state alternates b/f
            steady = ops[warmup:warmup + 2 * (8 - warmup)]
            kinds = [k for k, _ in steady]
            assert kinds == ["bwd", "fwd"] * (len(steady) // 2)

    def test_pipeline_spmd_matches_sequential(self):
        import numpy as np
        from ray_tpu.parallel.pipeline import pipeline_spmd, stack_stages

        mesh = virtual_mesh(8, MeshSpec(pp=4, dp=2))
        rng = jax.random.PRNGKey(0)
        L, D = 8, 16
        w = jax.random.normal(rng, (L, D, D)) * 0.3
        x_mb = jax.random.normal(jax.random.PRNGKey(1), (4, 6, D))

        def stage_fn(lp, x):
            def blk(h, wl):
                return jnp.tanh(h @ wl), None
            h, _ = jax.lax.scan(blk, x, lp)
            return h

        stages = stack_stages({"w": w}, 4)
        y = jax.jit(lambda s, x: pipeline_spmd(
            lambda lp, h: stage_fn(lp["w"], h), s, x, mesh))(stages, x_mb)

        # sequential reference
        def seq(x):
            for i in range(L):
                x = jnp.tanh(x @ w[i])
            return x
        ref = jnp.stack([seq(x_mb[i]) for i in range(4)])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_pipeline_spmd_grad_matches(self):
        import numpy as np
        from ray_tpu.parallel.pipeline import pipeline_spmd, stack_stages

        mesh = virtual_mesh(8, MeshSpec(pp=2, dp=2, tp=2))
        L, D = 4, 8
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3
        x_mb = jax.random.normal(jax.random.PRNGKey(1), (2, 4, D))

        def stage_fn(lp, x):
            def blk(h, wl):
                return jnp.tanh(h @ wl), None
            h, _ = jax.lax.scan(blk, x, lp)
            return h

        def loss_pp(w):
            stages = stack_stages({"w": w}, 2)
            y = pipeline_spmd(lambda lp, h: stage_fn(lp["w"], h),
                              stages, x_mb, mesh)
            return jnp.sum(y ** 2)

        def loss_seq(w):
            def seq(x):
                for i in range(L):
                    x = jnp.tanh(x @ w[i])
                return x
            y = jnp.stack([seq(x_mb[i]) for i in range(2)])
            return jnp.sum(y ** 2)

        g1 = jax.jit(jax.grad(loss_pp))(w)
        g2 = jax.grad(loss_seq)(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-5, rtol=1e-5)

    def test_gpt_loss_pp_matches_plain(self):
        import numpy as np
        from ray_tpu.models import GPT, GPTConfig

        mesh = virtual_mesh(8, MeshSpec(pp=2, dp=2, tp=2))
        cfg = GPTConfig.tiny(dtype=jnp.float32, use_flash=False, remat=False)
        model = GPT(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                    cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)
        l_pp = jax.jit(lambda p: model.loss_pp(p, tokens, targets, mesh,
                                               num_microbatches=2))(params)
        l_ref = jax.jit(lambda p: model.loss(p, tokens, targets))(params)
        np.testing.assert_allclose(float(l_pp), float(l_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_gpt_loss_pp_grads_match(self):
        import numpy as np
        from ray_tpu.models import GPT, GPTConfig

        mesh = virtual_mesh(8, MeshSpec(pp=2, dp=4))
        cfg = GPTConfig.tiny(dtype=jnp.float32, use_flash=False, remat=False)
        model = GPT(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                    cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)
        g_pp = jax.jit(jax.grad(lambda p: model.loss_pp(
            p, tokens, targets, mesh, num_microbatches=2)))(params)
        g_ref = jax.jit(jax.grad(lambda p: model.loss(
            p, tokens, targets)))(params)
        for k in g_ref:
            np.testing.assert_allclose(
                np.asarray(g_pp[k]), np.asarray(g_ref[k]),
                atol=2e-3, rtol=2e-3, err_msg=k)


class TestMultiSlice:
    def test_build_two_slice_mesh(self):
        from ray_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(MeshSpec(slices=2, dp=2, tp=2),
                          devices=jax.devices()[:8])
        assert mesh.shape["slice"] == 2
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2
        # each slice's submesh holds a disjoint contiguous device group
        devs = np.asarray(mesh.devices)
        s0 = set(d.id for d in devs[0].ravel())
        s1 = set(d.id for d in devs[1].ravel())
        assert not (s0 & s1) and len(s0) == len(s1) == 4

    def test_resolve_wildcard_per_slice(self):
        d = MeshSpec(slices=2, dp=-1, tp=2).resolve(8)
        assert d["dp"] == 2 and d["tp"] == 2  # 4 devices per slice

    def test_slice_count_mismatch_raises(self):
        with pytest.raises(ValueError):
            MeshSpec(slices=3).resolve(8)

    def test_dp_over_dcn_training_step(self):
        """A dp-over-DCN step on a 2-slice mesh: batch sharded over
        (slice, dp), params replicated; grads psum across both axes —
        the collective over "slice" is the DCN hop."""
        import numpy as np
        import optax
        from jax.sharding import NamedSharding

        from ray_tpu.parallel.mesh import (AxisRules, build_mesh,
                                           default_axis_rules)

        mesh = build_mesh(MeshSpec(slices=2, dp=2, tp=2),
                          devices=jax.devices()[:8])
        rules = AxisRules(default_axis_rules(multislice=True))
        w = jnp.ones((8, 8)) * 0.1
        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(0), (8, 8)),
            NamedSharding(mesh, rules.mesh_axes(("batch", None))))
        y = jnp.ones((8,))
        tx = optax.sgd(0.01)
        opt = tx.init(w)

        @jax.jit
        def step(w, opt, x, y):
            def loss(w):
                return jnp.mean((jnp.tanh(x @ w).sum(axis=1) - y) ** 2)
            l, g = jax.value_and_grad(loss)(w)
            u, opt2 = tx.update(g, opt)
            return l, optax.apply_updates(w, u), opt2

        losses = []
        for _ in range(5):
            l, w, opt = step(w, opt, x, y)
            losses.append(float(l))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_gpt_step_on_two_slices(self):
        """GPT training step with batch over (slice, dp): the full-model
        dp-over-DCN configuration from SURVEY §5."""
        import numpy as np
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models import GPT, GPTConfig
        from ray_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(MeshSpec(slices=2, dp=2, tp=2),
                          devices=jax.devices()[:8])
        cfg = GPTConfig.tiny(dtype=jnp.float32, use_flash=False, remat=False)
        model = GPT(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                               cfg.vocab_size),
            NamedSharding(mesh, P(("slice", "dp"), None)))
        targets = jnp.roll(tokens, -1, axis=1)
        tx = optax.adam(1e-3)
        opt = jax.jit(tx.init)(params)

        @jax.jit
        def step(params, opt, tokens, targets):
            loss, grads = jax.value_and_grad(model.loss)(params, tokens,
                                                         targets)
            u, opt2 = tx.update(grads, opt)
            return loss, optax.apply_updates(params, u), opt2

        loss, params, opt = step(params, opt, tokens, targets)
        assert np.isfinite(float(loss))
