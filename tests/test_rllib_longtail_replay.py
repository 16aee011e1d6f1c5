"""Long-tail RLlib algorithm families (round-5 additions): R2D2, Ape-X DQN, MADDPG.

One of four files by family (test_rllib_longtail_*.py): a file is the
unit the tier-1 run balances across workers, so none may grow past
~150 s alone (ROADMAP.md, Tier-1 verify).

Learning thresholds follow the package's test strategy (short budgets,
clear pass bars — the analog of rllib's tuned_examples quick runs).
"""
import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def cluster():
    # a cluster per test, unlike the other long-tail files: these tests
    # leave replay-shard actors behind, and each actor holds a CPU of the
    # four until shutdown (on a shared cluster the next build waits for ever)
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


class TestR2D2:
    def test_np_jax_cell_parity(self):
        """The worker's numpy LSTM must match the learner's jax cell —
        stored hidden states feed the learner's unroll directly."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.rllib.r2d2 import init_r2d2_params, lstm_step_np

        params = init_r2d2_params(jax.random.PRNGKey(0), 3, 2, 16, 8)
        p_np = {k: np.asarray(v) for k, v in params.items()}
        rng = np.random.default_rng(1)
        obs = rng.normal(size=(4, 3)).astype(np.float32)
        h = rng.normal(size=(4, 8)).astype(np.float32)
        c = rng.normal(size=(4, 8)).astype(np.float32)
        q_np, h_np, c_np = lstm_step_np(p_np, obs, h, c)

        def jax_cell(p, obs, h, c):
            x = jax.nn.relu(obs @ p["enc_w"] + p["enc_b"])
            z = x @ p["lstm_wx"] + h @ p["lstm_wh"] + p["lstm_b"]
            H = h.shape[1]
            i = jax.nn.sigmoid(z[:, :H])
            f = jax.nn.sigmoid(z[:, H:2 * H] + 1.0)
            g = jnp.tanh(z[:, 2 * H:3 * H])
            o = jax.nn.sigmoid(z[:, 3 * H:])
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return h @ p["q_w"] + p["q_b"], h, c

        q_j, h_j, c_j = jax_cell(params, jnp.asarray(obs), jnp.asarray(h),
                                 jnp.asarray(c))
        np.testing.assert_allclose(q_np, np.asarray(q_j), atol=1e-5)
        np.testing.assert_allclose(h_np, np.asarray(h_j), atol=1e-5)
        np.testing.assert_allclose(c_np, np.asarray(c_j), atol=1e-5)

    def test_r2d2_solves_memory_task_feedforward_cannot(self, cluster):
        """MemoryCue needs the cue carried across the delay: R2D2 must
        clear 0.85 where a memoryless policy caps at ~0.5 expected."""
        from ray_tpu.rllib import R2D2Config

        algo = R2D2Config(env="MemoryCue-v0", num_rollout_workers=2,
                          num_envs_per_worker=8,
                          rollout_fragment_length=64, seq_len=8,
                          burn_in=2, lr=1e-3, train_batch_size=32,
                          num_updates_per_iter=8, learning_starts=100,
                          target_update_freq=50,
                          epsilon_decay_steps=4000, seed=0).build()
        try:
            best = 0.0
            for _ in range(40):
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    best = max(best, m)
                if best >= 0.85:
                    break
            assert best >= 0.85, best
        finally:
            algo.stop()

    def test_r2d2_checkpoint_roundtrip(self, cluster):
        from ray_tpu.rllib import R2D2Config

        cfg = dict(env="MemoryCue-v0", num_rollout_workers=1,
                   num_envs_per_worker=4, rollout_fragment_length=16,
                   seq_len=8, burn_in=0, learning_starts=4,
                   train_batch_size=4, num_updates_per_iter=2)
        a = R2D2Config(seed=1, **cfg).build()
        try:
            a.train()
            a.train()
            ckpt = a.save()
            b = R2D2Config(seed=2, **cfg).build()
            try:
                b.restore(ckpt)
                import jax

                pa = jax.device_get(a.learner.params)
                pb = jax.device_get(b.learner.params)
                for k in pa:
                    np.testing.assert_allclose(pa[k], pb[k])
                assert len(b.buffer) == len(a.buffer)
                assert b.learner.num_updates == a.learner.num_updates
            finally:
                b.stop()
        finally:
            a.stop()


class TestApexDQN:
    def test_epsilon_ladder(self):
        from ray_tpu.rllib import per_worker_epsilons

        eps = per_worker_epsilons(4, base=0.4, alpha=7.0)
        assert eps[0] == pytest.approx(0.4)
        assert eps[-1] == pytest.approx(0.4 ** 8)
        assert all(a > b for a, b in zip(eps, eps[1:]))  # monotone ladder

    def test_replay_shard_roundtrip(self, cluster):
        """Worker-supplied priorities (not max-default) drive sampling;
        priority updates land on the shard's ring indices."""
        from ray_tpu.rllib.apex import ReplayShardActor

        shard = ray_tpu.remote(ReplayShardActor).remote(64, 0.6, 0.4)
        batch = {"obs": np.arange(8, dtype=np.float32).reshape(8, 1),
                 "rewards": np.zeros(8, np.float32)}
        prios = np.array([1e-6] * 7 + [100.0], np.float32)
        ray_tpu.get(shard.add.remote(batch, prios), timeout=120)
        # warming-up contract: None until batch_size rows exist
        assert ray_tpu.get(shard.sample.remote(32), timeout=60) is None
        got, idx, gen, w = ray_tpu.get(shard.sample.remote(8), timeout=60)
        # the one high-priority row must dominate proportional sampling
        assert (got["obs"][:, 0] == 7).mean() > 0.8
        dropped = ray_tpu.get(
            shard.update_priorities.remote(idx, gen, np.ones(len(idx))),
            timeout=60)
        assert dropped == 0
        # stale write-back: overwrite the ring (capacity 64 here, so 64
        # new rows bump every slot's generation), then replay the OLD
        # (idx, gen) — every update must be dropped, not applied
        big = {"obs": np.full((64, 1), -1.0, np.float32),
               "rewards": np.zeros(64, np.float32)}
        ray_tpu.get(shard.add.remote(big, np.ones(64)), timeout=60)
        dropped = ray_tpu.get(
            shard.update_priorities.remote(idx, gen,
                                           np.full(len(idx), 99.0)),
            timeout=60)
        assert dropped == len(idx)
        # shard checkpoint round-trips through a fresh actor
        state = ray_tpu.get(shard.state.remote(), timeout=60)
        shard2 = ray_tpu.remote(ReplayShardActor).remote(64, 0.6, 0.4)
        ray_tpu.get(shard2.restore_state.remote(state), timeout=60)
        assert ray_tpu.get(shard2.size.remote(), timeout=60) == 64

    def test_apex_restore_across_shard_count_change(self, cluster):
        """PBT exploit can hand a 2-shard checkpoint to a 1-shard trial:
        every checkpointed transition must survive redistribution."""
        from ray_tpu.rllib import ApexDQNConfig

        base = dict(num_rollout_workers=2, num_envs_per_worker=4,
                    rollout_fragment_length=16, learning_starts=50,
                    checkpoint_replay_buffer=True)
        a = ApexDQNConfig(num_replay_shards=2, seed=0, **base).build()
        try:
            for _ in range(3):
                a.train()
            ckpt = a.save()
            total = sum(len(s["buffer"]["cols"]["rewards"])
                        for s in ckpt["shards"])
            assert total > 0
            b = ApexDQNConfig(num_replay_shards=1, seed=1,
                              **base).build()
            try:
                b.restore(ckpt)
                size = ray_tpu.get(b.shards[0].size.remote(), timeout=60)
                assert size == total, (size, total)
            finally:
                b.stop()
        finally:
            a.stop()

    def test_apex_solves_cartpole(self, cluster):
        from ray_tpu.rllib import ApexDQNConfig

        algo = ApexDQNConfig(num_rollout_workers=4,
                             num_envs_per_worker=8,
                             rollout_fragment_length=32,
                             num_replay_shards=2, learning_starts=500,
                             lr=1e-3, num_updates_per_iter=32,
                             target_update_freq=100, seed=0).build()
        try:
            best = 0.0
            for _ in range(80):
                r = algo.train()
                m = r["episode_reward_mean_greedy"]
                if np.isfinite(m):
                    best = max(best, m)
                if best >= 150:
                    break
            assert best >= 150, best
        finally:
            algo.stop()


class TestMADDPG:
    def test_maddpg_learns_rendezvous(self, cluster):
        """Centralized-critic cooperative control: two agents meet on
        the plane. Random policy sits near -26; learned ~-3."""
        from ray_tpu.rllib import MADDPGConfig

        algo = MADDPGConfig(num_rollout_workers=1,
                            num_envs_per_worker=16,
                            rollout_fragment_length=25,
                            learning_starts=800, seed=0).build()
        try:
            best = -1e9
            for _ in range(60):
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    best = max(best, m)
                if best >= -8.0:
                    break
            assert best >= -8.0, best
        finally:
            algo.stop()

    def test_maddpg_centralized_critic_shape(self, cluster):
        """Critic weights must span the JOINT obs+action space — the
        structural property that distinguishes MADDPG from independent
        DDPG."""
        from ray_tpu.rllib import MADDPGConfig

        algo = MADDPGConfig(num_rollout_workers=1,
                            num_envs_per_worker=4,
                            rollout_fragment_length=25,
                            learning_starts=10_000, seed=0).build()
        try:
            # Rendezvous: obs_dim 4, action_dim 2, two agents
            w0 = algo.learner.params["critic_a0"]["w0"]
            assert w0.shape[0] == 2 * (4 + 2)
            # actors stay decentralized: own obs only
            assert algo.learner.params["actor_a0"]["w0"].shape[0] == 4
            ckpt = algo.save()
            algo.restore(ckpt)
        finally:
            algo.stop()
