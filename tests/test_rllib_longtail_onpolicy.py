"""Long-tail RLlib algorithm families (round-5 additions): A2C, PG, ARS, MAML.

One of four files by family (test_rllib_longtail_*.py): a file is the
unit the tier-1 run balances across workers, so none may grow past
~150 s alone (ROADMAP.md, Tier-1 verify).

Learning thresholds follow the package's test strategy (short budgets,
clear pass bars — the analog of rllib's tuned_examples quick runs).
"""
import numpy as np

from _rl_fixtures import cluster  # noqa: F401


class TestA2C:
    def test_a2c_improves_cartpole(self, cluster):
        from ray_tpu.rllib import A2CConfig

        algo = A2CConfig(num_rollout_workers=2, num_envs_per_worker=16,
                         rollout_fragment_length=64, lr=2e-3, lam=0.95,
                         entropy_coeff=0.001, max_grad_norm=1.0,
                         seed=0).build()
        try:
            first = None
            best = 0.0
            for _ in range(100):
                r = algo.train()
                m = r["episode_reward_mean"]
                if first is None and np.isfinite(m):
                    first = m
                if np.isfinite(m):
                    best = max(best, m)
                if best >= 120:
                    break
            assert first is not None
            assert best >= 120, (first, best)
        finally:
            algo.stop()

    def test_a2c_microbatch_matches_whole_batch_step(self):
        """Grad accumulation over microbatches must equal the whole-batch
        gradient (same loss surface, one optimizer step either way)."""
        from ray_tpu.rllib import A2CConfig
        from ray_tpu.rllib.a2c import A2CLearner

        cfg = A2CConfig(seed=3)
        rng = np.random.default_rng(0)
        batch = {
            "obs": rng.normal(size=(64, 4)).astype(np.float32),
            "actions": rng.integers(0, 2, 64),
            "advantages": rng.normal(size=64).astype(np.float32),
            "returns": rng.normal(size=64).astype(np.float32),
            "rewards": rng.normal(size=64).astype(np.float32),
        }
        whole = A2CLearner(4, 2, cfg)
        # 24 does NOT divide 64: the tail microbatch rides padded+masked
        micro = A2CLearner(4, 2, A2CConfig(seed=3, microbatch_size=24))
        sw = whole.update(batch)
        sm = micro.update(batch)
        import jax

        pw = jax.device_get(whole.params)
        pm = jax.device_get(micro.params)
        for k in pw:
            # advantages normalize once over the whole batch and slice
            # losses are weighted sums over total_n, so accumulation is
            # EXACT (fp noise only) — a sign-flipped or tail-dropping
            # gradient would diverge far beyond this tolerance
            np.testing.assert_allclose(pw[k], pm[k], atol=1e-5,
                                       err_msg=k)
        for k in sw:
            np.testing.assert_allclose(sw[k], sm[k], rtol=1e-4,
                                       err_msg=k)

    def test_a2c_checkpoint_roundtrip(self, cluster):
        from ray_tpu.rllib import A2CConfig

        a = A2CConfig(num_rollout_workers=1, num_envs_per_worker=4,
                      rollout_fragment_length=16, seed=1).build()
        try:
            a.train()
            ckpt = a.save()
            b = A2CConfig(num_rollout_workers=1, num_envs_per_worker=4,
                          rollout_fragment_length=16, seed=2).build()
            try:
                b.restore(ckpt)
                import jax

                pa = jax.device_get(a.learner.params)
                pb = jax.device_get(b.learner.params)
                for k in pa:
                    np.testing.assert_allclose(pa[k], pb[k])
                assert b._iteration == a._iteration
            finally:
                b.stop()
        finally:
            a.stop()


class TestPG:
    def test_pg_improves_cartpole(self, cluster):
        """REINFORCE (critic off, MC returns) must still learn, just
        more slowly than A2C."""
        from ray_tpu.rllib import PGConfig

        algo = PGConfig(num_rollout_workers=2, num_envs_per_worker=16,
                        rollout_fragment_length=64, lr=1e-3,
                        seed=0).build()
        try:
            best = 0.0
            for _ in range(100):
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    best = max(best, m)
                if best >= 100:
                    break
            assert best >= 100, best
            # the critic really is off: its loss carries zero weight
            assert algo.config.vf_loss_coeff == 0.0
        finally:
            algo.stop()


class TestARS:
    def test_ars_solves_cartpole(self, cluster):
        from ray_tpu.rllib import ARSConfig

        algo = ARSConfig(num_workers=2, num_rollouts=24, rollouts_used=8,
                         hidden=(32,), lr=0.05, sigma=0.1,
                         seed=0).build()
        try:
            best = 0.0
            for _ in range(80):
                r = algo.train()
                best = max(best, r["episode_reward_mean"])
                if best >= 300:
                    break
            assert best >= 300, best
        finally:
            algo.stop()

    def test_ars_filter_and_checkpoint(self, cluster):
        from ray_tpu.rllib import ARSConfig

        a = ARSConfig(num_workers=1, num_rollouts=4, seed=1).build()
        try:
            a.train()
            assert a.filter.rs.n > 0  # worker deltas merged centrally
            ckpt = a.save()
            b = ARSConfig(num_workers=1, num_rollouts=4, seed=2).build()
            try:
                b.restore(ckpt)
                np.testing.assert_allclose(b.theta, a.theta)
                assert b.filter.rs.n == a.filter.rs.n
            finally:
                b.stop()
        finally:
            a.stop()


class TestMAML:
    CFG = dict(num_tasks=4, num_envs_per_worker=16,
               episodes_per_rollout=4, inner_lr=0.5, outer_lr=3e-3)

    def test_maml_meta_init_beats_random_init(self, cluster):
        """The MAML claim: after meta-training, ONE adaptation step on
        a held-out task beats the same adaptation from a random init."""
        from ray_tpu.rllib import MAMLConfig

        held_out = (-0.35, 0.45)
        algo = MAMLConfig(seed=0, **self.CFG).build()
        try:
            gains = []
            for _ in range(80):
                r = algo.train()
                gains.append(r["adaptation_gain"])
            meta = algo.adapt_to(held_out)
            # adaptation helps on average once meta-trained
            assert np.mean(gains[-20:]) > 0, np.mean(gains[-20:])
        finally:
            algo.stop()  # release CPUs before the baseline spawns
        fresh = MAMLConfig(seed=99, **self.CFG).build()
        try:
            rand = fresh.adapt_to(held_out)
        finally:
            fresh.stop()
        assert meta["post_reward"] > rand["post_reward"] + 1.5, \
            (meta, rand)

    def test_maml_second_order_differs_from_fomaml(self, cluster):
        """first_order=True must change the meta-gradient (the
        second-order term through the inner update is real, not traced
        away)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.rllib import MAMLConfig
        from ray_tpu.rllib.maml import MAMLLearner

        rng = np.random.default_rng(0)
        batch = {
            "obs": rng.normal(size=(4, 8, 20, 2)).astype(np.float32),
            "actions": rng.normal(size=(4, 8, 20, 2)).astype(np.float32),
            "rewards": rng.normal(size=(4, 8, 20)).astype(np.float32),
        }
        second = MAMLLearner(2, 2, MAMLConfig(seed=3))
        first = MAMLLearner(2, 2, MAMLConfig(seed=3, first_order=True))
        l2 = second.meta_update(batch, batch)
        l1 = first.meta_update(batch, batch)
        assert np.isfinite(l1) and np.isfinite(l2)
        p2 = jax.device_get(second.params)
        p1 = jax.device_get(first.params)
        diff = max(float(np.abs(p2[k] - p1[k]).max()) for k in p2)
        assert diff > 1e-7, diff  # the curvature term moved something

    def test_maml_checkpoint_roundtrip(self, cluster):
        from ray_tpu.rllib import MAMLConfig

        a = MAMLConfig(seed=1, num_tasks=2, num_envs_per_worker=4,
                       episodes_per_rollout=1).build()
        try:
            a.train()
            ckpt = a.save()
            b = MAMLConfig(seed=2, num_tasks=2, num_envs_per_worker=4,
                           episodes_per_rollout=1).build()
            try:
                b.restore(ckpt)
                import jax

                pa = jax.device_get(a.learner.params)
                pb = jax.device_get(b.learner.params)
                for k in pa:
                    np.testing.assert_allclose(pa[k], pb[k], err_msg=k)
            finally:
                b.stop()
        finally:
            a.stop()


class TestMAMLMultiStep:
    def test_multi_step_adaptation_compounds(self, cluster):
        """adaptation_steps=k must move the params k inner steps away
        from the meta-init, not repeatedly one step."""
        import jax

        from ray_tpu.rllib import MAMLConfig

        algo = MAMLConfig(seed=0, num_tasks=1, num_envs_per_worker=8,
                          episodes_per_rollout=2, inner_lr=0.5).build()
        try:
            theta = jax.device_get(algo.learner.params)
            one = algo.adapt_to((0.3, 0.3), adaptation_steps=1)
            three = algo.adapt_to((0.3, 0.3), adaptation_steps=3)

            def dist(a, b):
                return sum(float(np.abs(a[k] - b[k]).sum()) for k in a)

            # compounded steps end strictly farther from the meta-init
            # (each clipped step moves ~inner_lr of param norm)
            assert dist(three["params"], theta) \
                > dist(one["params"], theta) * 1.5, \
                (dist(three["params"], theta), dist(one["params"], theta))
        finally:
            algo.stop()
