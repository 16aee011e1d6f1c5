"""Long-tail RLlib algorithm families (round-5 additions): CRR, Decision Transformer, SlateQ.

One of four files by family (test_rllib_longtail_*.py): a file is the
unit the tier-1 run balances across workers, so none may grow past
~150 s alone (ROADMAP.md, Tier-1 verify).

Learning thresholds follow the package's test strategy (short budgets,
clear pass bars — the analog of rllib's tuned_examples quick runs).
"""
import numpy as np
import pytest

from _rl_fixtures import cluster  # noqa: F401


class TestCRR:
    def test_crr_recovers_expert_from_mixed_data(self):
        """Advantage-weighted regression with a Q-critic must filter
        the random 2/3 of the dataset and reach near-expert return."""
        from ray_tpu.rllib import CRRConfig
        from ray_tpu.rllib.env import CartPoleVecEnv
        from ray_tpu.rllib.offline import collect_experiences

        def pd_policy(obs):
            return (obs[:, 2] + 0.5 * obs[:, 3] > 0).astype(np.int64)

        rng = np.random.default_rng(0)

        def rand_policy(obs):
            return rng.integers(0, 2, len(obs))

        good = collect_experiences(CartPoleVecEnv(num_envs=8, seed=0),
                                   pd_policy, 20, seed=1)
        bad = collect_experiences(CartPoleVecEnv(num_envs=8, seed=2),
                                  rand_policy, 40, seed=3)
        algo = CRRConfig(episodes=good + bad, seed=0).build()
        best = 0.0
        for _ in range(8):
            algo.train()
            ev = algo.evaluate(num_episodes=4)
            best = max(best, ev["episode_reward_mean"])
            if best >= 300:
                break
        assert best >= 300, best
        ckpt = algo.save()
        algo.restore(ckpt)

    def test_crr_binary_mode_runs(self):
        from ray_tpu.rllib import CRRConfig
        from ray_tpu.rllib.env import CartPoleVecEnv
        from ray_tpu.rllib.offline import collect_experiences

        rng = np.random.default_rng(1)
        eps = collect_experiences(
            CartPoleVecEnv(num_envs=4, seed=0),
            lambda o: rng.integers(0, 2, len(o)), 8, seed=1)
        algo = CRRConfig(episodes=eps, weight_mode="binary",
                         num_updates_per_iter=20, seed=1).build()
        r = algo.train()
        assert np.isfinite(r["critic_loss"]) and np.isfinite(
            r["actor_loss"])


class TestDecisionTransformer:
    def _mixed_dataset(self):
        from ray_tpu.rllib.env import CartPoleVecEnv
        from ray_tpu.rllib.offline import collect_experiences

        def pd_policy(obs):  # near-expert PD controller on the angle
            return (obs[:, 2] + 0.5 * obs[:, 3] > 0).astype(np.int64)

        rng = np.random.default_rng(0)

        def rand_policy(obs):
            return rng.integers(0, 2, len(obs))

        good = collect_experiences(CartPoleVecEnv(num_envs=8, seed=0),
                                   pd_policy, 20, seed=1)
        bad = collect_experiences(CartPoleVecEnv(num_envs=8, seed=2),
                                  rand_policy, 20, seed=3)
        return good, bad

    @pytest.mark.time_limit(270)  # 39 s alone, 69-91 s beside five workers
    def test_dt_return_conditioning(self):
        """Trained on mixed expert+random data, the policy must obey the
        return prompt: a high target recovers near-expert behavior, a
        low target yields commensurately low returns — the capability
        that separates DT from behavior cloning."""
        from ray_tpu.rllib import DTConfig

        good, bad = self._mixed_dataset()
        # budget: d_model 64 x 2 layers, 12 x 32 updates. The default
        # 128 x 3 for 20 x 32 spent 460 s of tier-1 in train() alone (a
        # B=64 update is ~0.4 s of CPU matmul; evaluate is ~5 s); this
        # size clears both bars 2x over from iteration 6 on, seeds 0-2
        algo = DTConfig(episodes=good + bad, context_len=20,
                        d_model=64, n_layer=2,
                        num_updates_per_iter=32, seed=0).build()
        for _ in range(12):
            r = algo.train()
        assert r["loss"] < 0.45, r
        hi = algo.evaluate(target_return=500.0, num_episodes=4)
        lo = algo.evaluate(target_return=30.0, num_episodes=4)
        assert hi["episode_reward_mean"] >= 150, (hi, lo)
        assert lo["episode_reward_mean"] <= hi["episode_reward_mean"] / 2, \
            (hi, lo)

    def test_dt_checkpoint_roundtrip(self):
        from ray_tpu.rllib import DTConfig

        _, bad = self._mixed_dataset()
        a = DTConfig(episodes=bad, context_len=8, num_updates_per_iter=2,
                     train_batch_size=8, d_model=32, n_layer=1,
                     n_head=2, seed=1).build()
        a.train()
        ckpt = a.save()
        b = DTConfig(episodes=bad, context_len=8, num_updates_per_iter=2,
                     train_batch_size=8, d_model=32, n_layer=1,
                     n_head=2, seed=2).build()
        b.restore(ckpt)
        import jax

        pa, pb = jax.device_get(a.params), jax.device_get(b.params)
        for k in pa:
            np.testing.assert_allclose(pa[k], pb[k], err_msg=k)


class TestSlateQ:
    def test_choice_model_is_a_distribution(self):
        from ray_tpu.rllib import InterestEvolutionVecEnv

        env = InterestEvolutionVecEnv(num_envs=6, seed=0)
        env.reset()
        slates = np.tile(np.arange(env.slate_size), (6, 1))
        p = env.choice_probs(slates)
        assert p.shape == (6, env.slate_size + 1)
        np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-6)
        assert (p > 0).all()  # no-click always possible

    def test_slateq_improves_engagement(self, cluster):
        """Decomposed per-item Q must beat the random-slate baseline
        (the epsilon=1 warmup period) on session engagement."""
        from ray_tpu.rllib import SlateQConfig

        algo = SlateQConfig(num_rollout_workers=2,
                            num_envs_per_worker=8,
                            rollout_fragment_length=40,
                            learning_starts=500, seed=0).build()
        try:
            first, best = None, -1e9
            for _ in range(60):
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    if first is None:
                        first = m  # epsilon ~1: random-slate baseline
                    best = max(best, m)
                if first is not None and best >= first + 0.8:
                    break
            assert best >= first + 0.6, (first, best)
        finally:
            algo.stop()

    def test_slateq_checkpoint_roundtrip(self, cluster):
        from ray_tpu.rllib import SlateQConfig

        cfg = dict(num_rollout_workers=1, num_envs_per_worker=4,
                   rollout_fragment_length=20, learning_starts=40,
                   train_batch_size=32, num_updates_per_iter=2)
        a = SlateQConfig(seed=1, **cfg).build()
        try:
            a.train()
            a.train()
            ckpt = a.save()
            b = SlateQConfig(seed=2, **cfg).build()
            try:
                b.restore(ckpt)
                import jax

                pa = jax.device_get(a.learner.params)
                pb = jax.device_get(b.learner.params)
                for k in pa:
                    np.testing.assert_allclose(pa[k], pb[k], err_msg=k)
                assert len(b.buffer) == len(a.buffer)
            finally:
                b.stop()
        finally:
            a.stop()
