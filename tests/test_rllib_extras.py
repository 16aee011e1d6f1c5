"""ES, QMIX, and the external-env protocol (round-5 RLlib additions).

Learning thresholds follow the package's test strategy (short budgets,
clear pass bars — the analog of rllib's tuned_examples quick runs).
"""
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from _rl_fixtures import cluster  # noqa: F401


class TestES:
    def test_es_solves_cartpole(self, cluster):
        from ray_tpu.rllib import ESConfig

        algo = ESConfig(num_workers=2, episodes_per_batch=24,
                        hidden=(32, 32), lr=0.03, sigma=0.1,
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

    def test_es_checkpoint_roundtrip(self, cluster):
        from ray_tpu.rllib import ESConfig

        cfg = ESConfig(num_workers=1, episodes_per_batch=4, seed=1)
        a = cfg.build()
        try:
            a.train()
            ckpt = a.save()
            b = cfg.build()
            try:
                b.restore(ckpt)
                np.testing.assert_allclose(a.theta, b.theta)
                assert b._seed_seq == a._seed_seq
            finally:
                b.stop()
        finally:
            a.stop()


class TestQMIX:
    def test_qmix_learns_coordination(self):
        from ray_tpu.rllib import QMIXConfig

        algo = QMIXConfig(num_envs=16, rollout_len=50,
                          num_updates_per_iter=16,
                          train_batch_size=128, seed=0).build()
        best = 0.0
        for _ in range(80):
            r = algo.train()
            m = r["episode_reward_mean"]
            if np.isfinite(m):
                best = max(best, m)
            if best >= 20:
                break
        # random matching scores ~8.3/25; >=20 needs real coordination
        assert best >= 20, best

    def test_qmix_beats_untrained(self):
        """Sanity floor: a fresh policy's greedy matching is near the
        1/3 chance rate; training must clear it decisively (the
        'beats independent/no learning' bar)."""
        from ray_tpu.rllib import QMIXConfig

        # a single fresh init is a random variable — one lucky seed can
        # match well above chance (seed 3 greedy-scores 24.4 on jax
        # 0.4.37), so bound the MEAN over a few independent inits
        bases = []
        for seed in (3, 4, 5):
            fresh = QMIXConfig(num_envs=8, rollout_len=30, seed=seed,
                               epsilon_start=0.0, epsilon_end=0.0).build()
            r0 = fresh.train()
            if np.isfinite(r0["episode_reward_mean"]):
                bases.append(float(r0["episode_reward_mean"]))
        assert not bases or float(np.mean(bases)) < 18, bases

    def test_qmix_checkpoint_roundtrip(self):
        import jax

        from ray_tpu.rllib import QMIXConfig

        cfg = QMIXConfig(num_envs=4, rollout_len=40, learning_starts=50,
                         train_batch_size=32, seed=2)
        a = cfg.build()
        a.train()
        ckpt = a.save()
        b = cfg.build()
        b.restore(ckpt)
        la = jax.tree.leaves(a.learner.params)
        lb = jax.tree.leaves(b.learner.params)
        for x, y in zip(la, lb):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y))


class TestExternalEnv:
    CLIENT = r'''
import math, sys, time
sys.path.insert(0, %(repo)r)
from ray_tpu.rllib.policy_client import PolicyClient

def reset(r):
    import random
    return [random.Random(r).uniform(-0.05, 0.05) for _ in range(4)]

def step(s, a):
    x, xd, th, thd = s
    force = 10.0 if a == 1 else -10.0
    costh, sinth = math.cos(th), math.sin(th)
    temp = (force + 0.05 * thd * thd * sinth) / 1.1
    thacc = (9.8 * sinth - costh * temp) / (0.5 * (4/3 - 0.1 * costh**2 / 1.1))
    xacc = temp - 0.05 * thacc * costh / 1.1
    x += 0.02 * xd; xd += 0.02 * xacc; th += 0.02 * thd; thd += 0.02 * thacc
    return [x, xd, th, thd], 1.0, abs(x) > 2.4 or abs(th) > 0.2095

client = PolicyClient(sys.argv[1])
deadline = time.time() + float(sys.argv[2])
ep = 0
while time.time() < deadline:
    eid = client.start_episode()
    s = reset(ep); ep += 1
    done = False
    for t in range(500):
        a = client.get_action(eid, s)
        s, r, done = step(s, a)
        client.log_returns(eid, r)
        if done:
            break
    client.end_episode(eid, None if done else s, truncated=not done)
'''

    @pytest.mark.slow  # 49-70 s alone, 189 s beside five workers
    @pytest.mark.time_limit(400)  # its own learning deadline is 240 s
    def test_external_process_client_learns(self):
        """The VERDICT bar: an external-process CartPole client (own
        physics, no ray_tpu runtime — only the thin PolicyClient HTTP
        shim) learns through the policy server."""
        from ray_tpu.rllib import ExternalPPOConfig

        algo = ExternalPPOConfig(obs_dim=4, num_actions=2,
                                 train_batch_size=384,
                                 num_sgd_epochs=4, lr=3e-3).build()
        host, port = algo.address
        with tempfile.NamedTemporaryFile("w", suffix=".py",
                                         delete=False) as f:
            f.write(self.CLIENT % {"repo": os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))})
            path = f.name
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        procs = [subprocess.Popen(
            [sys.executable, path, f"http://{host}:{port}", "240"],
            env=env) for _ in range(2)]
        try:
            best = 0.0
            t0 = time.time()
            while time.time() - t0 < 240:
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    best = max(best, m)
                if best >= 120:
                    break
            assert best >= 120, best
        finally:
            for p in procs:
                p.kill()
            algo.stop()

    def test_client_protocol_errors(self):
        from ray_tpu.rllib import PolicyClient
        from ray_tpu.rllib.policy_server import PolicyServerInput

        srv = PolicyServerInput()
        try:
            host, port = srv.address
            client = PolicyClient(f"http://{host}:{port}")
            with pytest.raises(RuntimeError):
                client.get_action("nope", [0, 0, 0, 0])
        finally:
            srv.shutdown()


class TestTD3:
    def test_td3_learns_pendulum(self, cluster):
        from ray_tpu.rllib import TD3Config

        algo = TD3Config(num_rollout_workers=1, num_envs_per_worker=8,
                         rollout_fragment_length=50, learning_starts=1000,
                         train_batch_size=256, num_updates_per_iter=400,
                         explore_sigma=0.2, hidden=(128, 128),
                         seed=1).build()
        try:
            rews = []
            for _ in range(50):
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    rews.append(m)
                if rews and rews[-1] > -750:
                    break
            # random play sits near -1300; learning must be decisive
            assert rews and rews[-1] > -900, rews[-3:]
            assert rews[-1] > rews[0] + 250, (rews[0], rews[-1])
        finally:
            algo.stop()

    def test_td3_checkpoint_roundtrip(self, cluster):
        import jax

        from ray_tpu.rllib import TD3Config

        cfg = TD3Config(num_rollout_workers=1, num_envs_per_worker=4,
                        rollout_fragment_length=25, learning_starts=100,
                        train_batch_size=64, num_updates_per_iter=8,
                        seed=3)
        a = cfg.build()
        try:
            a.train()
            a.train()
            ckpt = a.save()
            b = cfg.build()
            try:
                b.restore(ckpt)
                xa = jax.tree.leaves(a.learner.params)
                xb = jax.tree.leaves(b.learner.params)
                for u, v in zip(xa, xb):
                    np.testing.assert_allclose(np.asarray(u),
                                               np.asarray(v))
                assert len(b.buffer) == len(a.buffer) > 0
            finally:
                b.stop()
        finally:
            a.stop()

    def test_ddpg_config_is_td3_degenerate(self, cluster):
        from ray_tpu.rllib import DDPGConfig

        cfg = DDPGConfig(num_rollout_workers=1, seed=0)
        assert cfg.policy_delay == 1 and cfg.target_noise == 0.0
        algo = cfg.build()
        try:
            r = algo.train()
            assert r["timesteps_this_iter"] > 0
        finally:
            algo.stop()

    def test_td3_rejects_discrete_env(self, cluster):
        from ray_tpu.rllib import TD3Config

        with pytest.raises(ValueError, match="continuous"):
            TD3Config(env="CartPole-v1").build()


class TestBandits:
    def test_linucb_regret_decreases(self):
        from ray_tpu.rllib import BanditLinUCBConfig

        algo = BanditLinUCBConfig(seed=0, alpha=0.5).build()
        first = algo.train()["regret_per_pull"]
        for _ in range(40):
            r = algo.train()
        assert r["regret_per_pull"] < first * 0.5, (first, r)

    def test_thompson_regret_decreases(self):
        from ray_tpu.rllib import BanditLinTSConfig

        algo = BanditLinTSConfig(seed=1, alpha=0.5).build()
        first = algo.train()["regret_per_pull"]
        for _ in range(40):
            r = algo.train()
        assert r["regret_per_pull"] < first * 0.5, (first, r)

    def test_bandit_checkpoint_roundtrip(self):
        from ray_tpu.rllib import BanditLinUCBConfig

        a = BanditLinUCBConfig(seed=2).build()
        for _ in range(5):
            a.train()
        ckpt = a.save()
        b = BanditLinUCBConfig(seed=2).build()
        b.restore(ckpt)
        np.testing.assert_allclose(a._A, b._A)
        np.testing.assert_allclose(a._b, b._b)


class TestCQL:
    def _mixed_dataset(self, tmp_path):
        from ray_tpu.rllib.env import make_env
        from ray_tpu.rllib.offline import (collect_experiences,
                                           write_experiences)

        env = make_env("CartPole-v1", num_envs=8, seed=0)
        flip_rng = np.random.default_rng(0)

        def heuristic(obs):
            a = (obs[:, 2] + 0.4 * obs[:, 3] > 0).astype(np.int64)
            flip = flip_rng.random(len(a)) < 0.25
            return np.where(flip, 1 - a, a)

        eps = collect_experiences(env, heuristic, 60, seed=0)
        rng = np.random.default_rng(1)
        eps += collect_experiences(
            env, lambda o: rng.integers(0, 2, len(o)), 40, seed=1)
        path = str(tmp_path / "exp.jsonl")
        write_experiences(path, eps)
        avg = float(np.mean([ep["rewards"].sum() for ep in eps]))
        return path, avg

    def test_cql_beats_its_dataset(self, tmp_path):
        """Offline RL's bar: stitch a policy BETTER than the mediocre
        behavior data (BC can only match it)."""
        from ray_tpu.rllib import CQLConfig

        path, data_avg = self._mixed_dataset(tmp_path)
        algo = CQLConfig(input_paths=path, num_updates_per_iter=200,
                         cql_alpha=1.0, seed=0).build()
        for _ in range(15):
            r = algo.train()
        assert np.isfinite(r["loss"]) and r["cql_penalty"] >= 0
        ev = algo.evaluate(num_episodes=16)
        assert ev["evaluation_reward_mean"] > data_avg * 2, \
            (ev, data_avg)

    def test_cql_checkpoint_roundtrip(self, tmp_path):
        import jax

        from ray_tpu.rllib import CQLConfig

        path, _ = self._mixed_dataset(tmp_path)
        cfg = CQLConfig(input_paths=path, num_updates_per_iter=20,
                        seed=2)
        a = cfg.build()
        a.train()
        ckpt = a.save()
        b = cfg.build()
        b.restore(ckpt)
        for x, y in zip(jax.tree.leaves(a.params),
                        jax.tree.leaves(b.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y))
        assert b.num_updates == a.num_updates

    def test_cql_requires_input(self):
        from ray_tpu.rllib import CQLConfig

        with pytest.raises(ValueError, match="offline"):
            CQLConfig().build()
