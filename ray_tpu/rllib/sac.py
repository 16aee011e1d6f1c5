"""SAC — soft actor-critic for continuous control.

ref: rllib/algorithms/sac/sac.py (SACConfig: twin Q, tanh-squashed
gaussian, target entropy = -|A|, polyak tau) and
sac/sac_torch_policy.py (actor/critic/alpha losses :220-300).

House TPU shape (the DQN recipe): numpy behavior policy in rollout
actors, host-side replay buffer, and the WHOLE per-iteration update
block — K minibatches of critic+actor+alpha+polyak — as ONE jitted
lax.scan with donated buffers, so the device sees one dispatch and one
stats readback per train() call.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import cloudpickle
import numpy as np

import ray_tpu

from .env import make_env
from .replay_buffer import ReplayBuffer
from .rollout_worker import EnvWorkerBase, worker_opts

LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0


# ---------------------------------------------------------------------------
# networks (param-dict style, matching models.py)
# ---------------------------------------------------------------------------


def init_sac_params(rng, obs_dim: int, action_dim: int,
                    hidden: Tuple[int, ...] = (256, 256)) -> Dict:
    import jax
    import jax.numpy as jnp

    def mlp(key, sizes, out):
        p = {}
        last = sizes[0]
        ks = jax.random.split(key, len(sizes))
        for i, h in enumerate(sizes[1:]):
            p[f"w{i}"] = jax.random.normal(
                ks[i], (last, h), jnp.float32) * np.sqrt(2.0 / last)
            p[f"b{i}"] = jnp.zeros((h,), jnp.float32)
            last = h
        p["w_out"] = jax.random.normal(
            ks[-1], (last, out), jnp.float32) * 0.01
        p["b_out"] = jnp.zeros((out,), jnp.float32)
        return p

    import jax

    ka, k1, k2 = jax.random.split(rng, 3)
    return {
        # actor emits mean and log_std per action dim
        "actor": mlp(ka, (obs_dim, *hidden), 2 * action_dim),
        "q1": mlp(k1, (obs_dim + action_dim, *hidden), 1),
        "q2": mlp(k2, (obs_dim + action_dim, *hidden), 1),
    }


def _mlp_forward(p: Dict, x):
    import jax.numpy as jnp

    i = 0
    while f"w{i}" in p:
        x = jnp.maximum(x @ p[f"w{i}"] + p[f"b{i}"], 0.0)
        i += 1
    return x @ p["w_out"] + p["b_out"]


def actor_dist(p: Dict, obs):
    """-> (mu, log_std) for the tanh-squashed gaussian."""
    import jax.numpy as jnp

    out = _mlp_forward(p, obs)
    mu, log_std = jnp.split(out, 2, axis=-1)
    return mu, jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX)


def sample_action_jax(p: Dict, obs, key, action_scale: float):
    """Reparameterized tanh-gaussian sample -> (action, logp)."""
    import jax
    import jax.numpy as jnp

    mu, log_std = actor_dist(p, obs)
    std = jnp.exp(log_std)
    eps = jax.random.normal(key, mu.shape)
    pre = mu + std * eps
    a = jnp.tanh(pre)
    # log-prob with the tanh change-of-variables (SAC appendix C)
    logp = (-0.5 * (eps ** 2 + 2 * log_std + np.log(2 * np.pi))
            - jnp.log(1 - a ** 2 + 1e-6)).sum(axis=-1)
    return a * action_scale, logp


def sample_action_np(p: Dict, obs: np.ndarray, rng: np.random.Generator,
                     action_scale: float, deterministic: bool = False
                     ) -> np.ndarray:
    """Numpy rollout-side sampling (np_policy rationale: no jax in
    actors)."""
    x = obs
    i = 0
    while f"w{i}" in p:
        x = np.maximum(x @ p[f"w{i}"] + p[f"b{i}"], 0.0)
        i += 1
    out = x @ p["w_out"] + p["b_out"]
    mu, log_std = np.split(out, 2, axis=-1)
    if deterministic:
        return np.tanh(mu) * action_scale
    std = np.exp(np.clip(log_std, LOG_STD_MIN, LOG_STD_MAX))
    pre = mu + std * rng.standard_normal(mu.shape)
    return np.tanh(pre) * action_scale


# ---------------------------------------------------------------------------
# rollout worker
# ---------------------------------------------------------------------------


class SACRolloutWorker(EnvWorkerBase):
    def __init__(self, env_name: str, num_envs: int, rollout_len: int,
                 action_scale: float, seed: int = 0, env_creator=None):
        super().__init__(env_name, num_envs, rollout_len, seed, env_creator)
        self.action_scale = action_scale

    def sample(self, actor_params: Dict, random_actions: bool = False
               ) -> Dict[str, np.ndarray]:
        p = {k: np.asarray(v, np.float32) for k, v in actor_params.items()}
        T, n = self.rollout_len, self.env.num_envs
        ad = self.env.action_dim
        obs_buf = np.empty((T, n, self.env.obs_dim), np.float32)
        next_buf = np.empty((T, n, self.env.obs_dim), np.float32)
        act_buf = np.empty((T, n, ad), np.float32)
        rew_buf = np.empty((T, n), np.float32)
        done_buf = np.empty((T, n), np.bool_)
        obs = self._obs
        for t in range(T):
            # actions are stored UNSCALED (tanh range [-1,1]) so the
            # learner's Q-nets, Bellman targets, and actor loss all live
            # on one action scale; the env boundary applies the scale
            if random_actions:  # warmup exploration
                a = self._rng.uniform(-1, 1, (n, ad))
            else:
                a = sample_action_np(p, obs, self._rng, 1.0)
            obs_buf[t], act_buf[t] = obs, a
            obs, reward, done, info = self.env.step(a * self.action_scale)
            rew_buf[t], done_buf[t] = reward, done
            next_buf[t] = obs
            if done.any():
                idx = np.nonzero(done)[0]
                if "final_obs" in info:
                    next_buf[t, idx] = info["final_obs"][idx]
                if "truncated" in info:
                    # time-limit cut still bootstraps
                    done_buf[t] &= ~info["truncated"]
            self._track_returns(reward, done)
        self._obs = obs
        flat = lambda a: a.reshape(T * n, *a.shape[2:])  # noqa: E731
        return {"obs": flat(obs_buf), "actions": flat(act_buf),
                "rewards": flat(rew_buf), "dones": flat(done_buf),
                "next_obs": flat(next_buf)}


# ---------------------------------------------------------------------------
# learner + algorithm
# ---------------------------------------------------------------------------


@dataclass
class SACConfig:
    """ref: sac/sac.py SACConfig defaults (tau 5e-3, twin Q,
    target_entropy='auto' = -|A|, initial_alpha 1.0)."""
    env: str = "Pendulum-v1"
    env_creator: Optional[Callable] = None
    num_rollout_workers: int = 1
    num_envs_per_worker: int = 8
    rollout_fragment_length: int = 32
    gamma: float = 0.99
    tau: float = 5e-3
    actor_lr: float = 3e-4
    critic_lr: float = 3e-4
    alpha_lr: float = 3e-4
    buffer_size: int = 100_000
    train_batch_size: int = 256
    num_updates_per_iter: int = 32
    learning_starts: int = 1_000
    hidden: tuple = (256, 256)
    seed: int = 0
    checkpoint_replay_buffer: bool = True
    worker_resources: Dict[str, float] = field(default_factory=dict)

    def build(self) -> "SAC":
        return SAC(self)


class SACLearner:
    def __init__(self, obs_dim: int, action_dim: int, c: SACConfig):
        import jax
        import jax.numpy as jnp
        import optax

        self.params = init_sac_params(jax.random.PRNGKey(c.seed), obs_dim,
                                      action_dim, tuple(c.hidden))
        self.target_q = jax.tree.map(
            lambda a: a.copy(), {"q1": self.params["q1"],
                                 "q2": self.params["q2"]})
        self.log_alpha = jnp.zeros(())
        self.target_entropy = -float(action_dim)
        self.opt_actor = optax.adam(c.actor_lr)
        self.opt_critic = optax.adam(c.critic_lr)
        self.opt_alpha = optax.adam(c.alpha_lr)
        self.state_actor = self.opt_actor.init(self.params["actor"])
        self.state_critic = self.opt_critic.init(
            {"q1": self.params["q1"], "q2": self.params["q2"]})
        self.state_alpha = self.opt_alpha.init(self.log_alpha)
        self.num_updates = 0
        self._key = jax.random.PRNGKey(c.seed + 1)
        self._update_many = jax.jit(self._make_update_many(c),
                                    donate_argnums=(0, 1, 2, 3))

    def _make_update_many(self, c: SACConfig):
        import jax
        import jax.numpy as jnp
        import optax

        gamma, tau = c.gamma, c.tau
        tgt_ent = self.target_entropy

        def q_val(qp, obs, act):
            return _mlp_forward(qp, jnp.concatenate([obs, act],
                                                    axis=-1))[:, 0]

        def one_update(params, target_q, log_alpha, opt_states, batch, key):
            sa, sc, sal = opt_states
            alpha = jnp.exp(log_alpha)
            k1, k2 = jax.random.split(key)

            # --- critic: entropy-regularized twin-min Bellman target
            a_next, logp_next = sample_action_jax(params["actor"],
                                                  batch["next_obs"], k1, 1.0)
            tq = jnp.minimum(
                q_val(target_q["q1"], batch["next_obs"], a_next),
                q_val(target_q["q2"], batch["next_obs"], a_next))
            not_done = 1.0 - batch["dones"].astype(jnp.float32)
            y = batch["rewards"] + gamma * not_done * (
                tq - alpha * logp_next)
            y = jax.lax.stop_gradient(y)

            def critic_loss(qs):
                l1 = jnp.mean((q_val(qs["q1"], batch["obs"],
                                     batch["actions"]) - y) ** 2)
                l2 = jnp.mean((q_val(qs["q2"], batch["obs"],
                                     batch["actions"]) - y) ** 2)
                return l1 + l2

            qs = {"q1": params["q1"], "q2": params["q2"]}
            closs, cgrads = jax.value_and_grad(critic_loss)(qs)
            cupd, sc = self.opt_critic.update(cgrads, sc, qs)
            qs = optax.apply_updates(qs, cupd)
            params = {**params, "q1": qs["q1"], "q2": qs["q2"]}

            # --- actor: maximize twin-min Q + entropy
            def actor_loss(ap):
                a, logp = sample_action_jax(ap, batch["obs"], k2, 1.0)
                q = jnp.minimum(q_val(params["q1"], batch["obs"], a),
                                q_val(params["q2"], batch["obs"], a))
                return jnp.mean(alpha * logp - q), jnp.mean(logp)

            (aloss, mean_logp), agrads = jax.value_and_grad(
                actor_loss, has_aux=True)(params["actor"])
            aupd, sa = self.opt_actor.update(agrads, sa, params["actor"])
            params = {**params,
                      "actor": optax.apply_updates(params["actor"], aupd)}

            # --- temperature: drive entropy toward the target
            def alpha_loss(la):
                return -jnp.exp(la) * jax.lax.stop_gradient(
                    mean_logp + tgt_ent)

            lloss, lgrad = jax.value_and_grad(alpha_loss)(log_alpha)
            lupd, sal = self.opt_alpha.update(lgrad, sal, log_alpha)
            log_alpha = optax.apply_updates(log_alpha, lupd)

            # --- polyak target update
            target_q = jax.tree.map(
                lambda t, o: t * (1 - tau) + o * tau, target_q,
                {"q1": params["q1"], "q2": params["q2"]})
            stats = {"critic_loss": closs, "actor_loss": aloss,
                     "alpha": jnp.exp(log_alpha), "entropy": -mean_logp}
            return params, target_q, log_alpha, (sa, sc, sal), stats

        def update_many(params, target_q, log_alpha, opt_states, batches,
                        key):
            def body(carry, batch_k):
                params, target_q, log_alpha, opt_states, key = carry
                key, sub = jax.random.split(key)
                params, target_q, log_alpha, opt_states, stats = one_update(
                    params, target_q, log_alpha, opt_states, batch_k, sub)
                return (params, target_q, log_alpha, opt_states, key), stats

            (params, target_q, log_alpha, opt_states, _), stats = \
                jax.lax.scan(body,
                             (params, target_q, log_alpha, opt_states, key),
                             batches)
            return (params, target_q, log_alpha, opt_states,
                    jax.tree.map(jnp.mean, stats))

        return update_many

    def update_many(self, batches: Dict[str, np.ndarray]) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        K = batches["obs"].shape[0]
        self._key, sub = jax.random.split(self._key)
        jb = {k: jnp.asarray(v) for k, v in batches.items()}
        opt_states = (self.state_actor, self.state_critic, self.state_alpha)
        (self.params, self.target_q, self.log_alpha, opt_states, stats) = \
            self._update_many(self.params, self.target_q, self.log_alpha,
                              opt_states, jb, sub)
        self.state_actor, self.state_critic, self.state_alpha = opt_states
        self.num_updates += K
        return {k: float(v) for k, v in jax.device_get(stats).items()}


class SAC:
    """Tune-trainable-shaped SAC (train/save/restore/stop)."""

    def __init__(self, config: SACConfig):
        self.config = c = config
        probe = make_env(c.env, num_envs=1, seed=c.seed) \
            if c.env_creator is None else c.env_creator(num_envs=1,
                                                        seed=c.seed)
        if not getattr(probe, "continuous", False):
            raise ValueError("SAC needs a continuous-action env")
        self.action_scale = float(probe.action_high)
        obs_dim, act_dim = probe.obs_dim, probe.action_dim
        creator_blob = (cloudpickle.dumps(c.env_creator)
                        if c.env_creator else None)
        worker_cls = ray_tpu.remote(SACRolloutWorker)
        opts = worker_opts(c.worker_resources)
        self.workers: List = [
            worker_cls.options(**opts).remote(
                c.env, c.num_envs_per_worker, c.rollout_fragment_length,
                self.action_scale, seed=c.seed + 1000 * i,
                env_creator=creator_blob)
            for i in range(c.num_rollout_workers)
        ]
        self.learner = SACLearner(obs_dim, act_dim, c)
        self.buffer = ReplayBuffer(c.buffer_size, seed=c.seed)
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent: List[float] = []

    def train(self) -> Dict[str, Any]:
        c = self.config
        t0 = time.monotonic()
        warmup = self._total_steps < c.learning_starts
        actor_ref = ray_tpu.put(
            {k: np.asarray(v) for k, v in
             __import__("jax").device_get(
                 self.learner.params["actor"]).items()})
        batches = ray_tpu.get(
            [w.sample.remote(actor_ref, warmup) for w in self.workers],
            timeout=300)
        steps = 0
        for b in batches:
            self.buffer.add(b)
            steps += len(b["rewards"])
        sample_time = time.monotonic() - t0
        t1 = time.monotonic()
        stats: Dict[str, float] = {}
        self._total_steps += steps
        if len(self.buffer) >= max(c.learning_starts, c.train_batch_size):
            K, B = c.num_updates_per_iter, c.train_batch_size
            mb = self.buffer.sample(K * B)
            stacked = {k: v.reshape(K, B, *v.shape[1:])
                       for k, v in mb.items()}
            stats = self.learner.update_many(stacked)
        learn_time = time.monotonic() - t1
        for rets in ray_tpu.get(
                [w.episode_returns.remote() for w in self.workers],
                timeout=60):
            self._recent.extend(rets)
            self._total_episodes += len(rets)
        self._recent = self._recent[-100:]
        self._iteration += 1
        return {
            "training_iteration": self._iteration,
            "timesteps_total": self._total_steps,
            "timesteps_this_iter": steps,
            "episode_reward_mean": (float(np.mean(self._recent))
                                    if self._recent else float("nan")),
            "episodes_total": self._total_episodes,
            "env_steps_per_sec": steps / max(1e-9, sample_time + learn_time),
            "num_updates": self.learner.num_updates,
            **stats,
        }

    def save(self) -> Dict:
        import jax

        L = self.learner
        ckpt = {"params": jax.device_get(L.params),
                "target_q": jax.device_get(L.target_q),
                "log_alpha": float(L.log_alpha),
                # Adam moments + the sampling key survive the round-trip
                # (the PPO.save invariant) — a restored run continues,
                # not restarts, its optimization trajectory
                "opt_states": jax.device_get((L.state_actor, L.state_critic,
                                              L.state_alpha)),
                "rng_key": jax.device_get(L._key),
                "iteration": self._iteration,
                "total_steps": self._total_steps}
        if self.config.checkpoint_replay_buffer:
            # same contract as DQN: a restored trial (PBT exploit,
            # pause/resume) resumes warm instead of stalling until
            # learning_starts refills
            ckpt["buffer"] = self.buffer.state()
        return ckpt

    def restore(self, ckpt: Dict) -> None:
        import jax.numpy as jnp
        import jax

        as_jnp = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        L = self.learner
        L.params = as_jnp(ckpt["params"])
        L.target_q = as_jnp(ckpt["target_q"])
        L.log_alpha = jnp.asarray(ckpt.get("log_alpha", 0.0))
        if "opt_states" in ckpt:
            (L.state_actor, L.state_critic, L.state_alpha) = as_jnp(
                ckpt["opt_states"])
        if "rng_key" in ckpt:
            L._key = jnp.asarray(ckpt["rng_key"])
        self._iteration = int(ckpt.get("iteration", 0))
        self._total_steps = int(ckpt.get("total_steps", 0))
        if "buffer" in ckpt:
            self.buffer.restore(ckpt["buffer"])

    def stop(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
