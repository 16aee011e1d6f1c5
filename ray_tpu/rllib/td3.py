"""TD3 — twin delayed deep deterministic policy gradient.

ref: rllib/algorithms/td3/td3.py (TD3Config: twin_q,
policy_delay=2, smooth_target_policy with target_noise 0.2 clipped
at 0.5, exploration gaussian sigma 0.1) layered over
ddpg/ddpg_torch_policy.py losses — Fujimoto et al. 2018.

House TPU shape (the SAC/DQN recipe): numpy behavior policy in rollout
actors (deterministic tanh head + exploration noise), host replay
buffer, and the whole per-iteration update block — K minibatches of
twin-critic TD, every-other-step actor + polyak — as ONE jitted
lax.scan with donated buffers: one dispatch, one stats readback per
train() call (the learner rule of this package).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import cloudpickle
import numpy as np

import ray_tpu

from .replay_buffer import ReplayBuffer
from .rollout_worker import EnvWorkerBase, worker_opts


def _mlp_init(rng, sizes: Tuple[int, ...], out: int):
    import jax
    import jax.numpy as jnp

    p = {}
    last = sizes[0]
    ks = jax.random.split(rng, len(sizes))
    for i, h in enumerate(sizes[1:]):
        p[f"w{i}"] = jax.random.normal(
            ks[i], (last, h), jnp.float32) * np.sqrt(2.0 / last)
        p[f"b{i}"] = jnp.zeros((h,), jnp.float32)
        last = h
    p["w_out"] = jax.random.normal(ks[-1], (last, out), jnp.float32) * 0.01
    p["b_out"] = jnp.zeros((out,), jnp.float32)
    return p


def _mlp_np(p: Dict[str, np.ndarray], x: np.ndarray) -> np.ndarray:
    i = 0
    while f"w{i}" in p:
        x = np.maximum(x @ p[f"w{i}"] + p[f"b{i}"], 0.0)
        i += 1
    return x @ p["w_out"] + p["b_out"]


def init_td3_params(rng, obs_dim: int, action_dim: int,
                    hidden: Tuple[int, ...]) -> Dict:
    import jax

    ka, k1, k2 = jax.random.split(rng, 3)
    return {"actor": _mlp_init(ka, (obs_dim, *hidden), action_dim),
            "q1": _mlp_init(k1, (obs_dim + action_dim, *hidden), 1),
            "q2": _mlp_init(k2, (obs_dim + action_dim, *hidden), 1)}


class TD3RolloutWorker(EnvWorkerBase):
    """Deterministic tanh policy + Gaussian exploration noise (the DDPG
    behavior policy; SAC's worker samples its stochastic head instead)."""

    def __init__(self, env_name: str, num_envs: int, rollout_len: int,
                 action_scale: float, explore_sigma: float,
                 seed: int = 0, env_creator=None):
        super().__init__(env_name, num_envs, rollout_len, seed, env_creator)
        self.action_scale = action_scale
        self.sigma = explore_sigma

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
            if random_actions:
                a = self._rng.uniform(-1, 1, (n, ad))
            else:
                a = np.tanh(_mlp_np(p, obs)) \
                    + self._rng.normal(0, self.sigma, (n, ad))
                a = np.clip(a, -1.0, 1.0)
            obs_buf[t], act_buf[t] = obs, a
            obs, reward, done, info = self.env.step(a * self.action_scale)
            rew_buf[t], done_buf[t] = reward, done
            next_buf[t] = obs
            if done.any():
                idx = np.nonzero(done)[0]
                if "final_obs" in info:
                    next_buf[t, idx] = info["final_obs"][idx]
                if "truncated" in info:
                    done_buf[t] &= ~info["truncated"]
            self._track_returns(reward, done)
        self._obs = obs
        flat = lambda a: a.reshape(T * n, *a.shape[2:])  # noqa: E731
        return {"obs": flat(obs_buf), "actions": flat(act_buf),
                "rewards": flat(rew_buf), "dones": flat(done_buf),
                "next_obs": flat(next_buf)}


@dataclass
class TD3Config:
    """ref: td3.py TD3Config defaults."""
    env: str = "Pendulum-v1"
    env_creator: Optional[Callable] = None
    num_rollout_workers: int = 1
    num_envs_per_worker: int = 8
    rollout_fragment_length: int = 32
    gamma: float = 0.99
    tau: float = 5e-3
    actor_lr: float = 1e-3
    critic_lr: float = 1e-3
    buffer_size: int = 100_000
    train_batch_size: int = 256
    num_updates_per_iter: int = 32
    learning_starts: int = 1_000
    policy_delay: int = 2
    target_noise: float = 0.2
    target_noise_clip: float = 0.5
    explore_sigma: float = 0.1
    hidden: tuple = (256, 256)
    seed: int = 0
    checkpoint_replay_buffer: bool = True
    worker_resources: Dict[str, float] = field(default_factory=dict)
    extra: Dict[str, Any] = field(default_factory=dict)

    def build(self) -> "TD3":
        return TD3(self)


class TD3Learner:
    def __init__(self, obs_dim: int, action_dim: int, c: TD3Config):
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        self.params = init_td3_params(jax.random.PRNGKey(c.seed), obs_dim,
                                      action_dim, tuple(c.hidden))
        self.target = jax.tree.map(lambda a: a.copy(), self.params)
        self.opt_actor = optax.adam(c.actor_lr)
        self.opt_critic = optax.adam(c.critic_lr)
        self.state_actor = self.opt_actor.init(self.params["actor"])
        self.state_critic = self.opt_critic.init(
            {"q1": self.params["q1"], "q2": self.params["q2"]})
        self._key = jax.random.PRNGKey(c.seed + 7)
        self.num_updates = 0

        from .sac import _mlp_forward as mlp  # one canonical jnp MLP

        def q(p, obs, act):
            return mlp(p, jnp.concatenate([obs, act], axis=-1))[:, 0]

        def critic_loss(qs, target, batch, key):
            noise = jnp.clip(
                jax.random.normal(key, batch["actions"].shape)
                * c.target_noise, -c.target_noise_clip,
                c.target_noise_clip)
            a_next = jnp.clip(
                jnp.tanh(mlp(target["actor"], batch["next_obs"])) + noise,
                -1.0, 1.0)  # smoothed target policy
            tq = jnp.minimum(q(target["q1"], batch["next_obs"], a_next),
                             q(target["q2"], batch["next_obs"], a_next))
            y = batch["rewards"] + c.gamma \
                * (1.0 - batch["dones"].astype(jnp.float32)) * tq
            y = jax.lax.stop_gradient(y)
            l1 = jnp.mean(jnp.square(
                q(qs["q1"], batch["obs"], batch["actions"]) - y))
            l2 = jnp.mean(jnp.square(
                q(qs["q2"], batch["obs"], batch["actions"]) - y))
            return l1 + l2

        def actor_loss(actor_p, q1_p, batch):
            a = jnp.tanh(mlp(actor_p, batch["obs"]))
            return -jnp.mean(q(q1_p, batch["obs"], a))

        def polyak(t, p):
            return jax.tree.map(
                lambda a, b: a * (1 - c.tau) + b * c.tau, t, p)

        def one_update(carry, xs):
            params, target, s_a, s_c, key = carry
            batch, step_i = xs
            key, ck = jax.random.split(key)
            qs = {"q1": params["q1"], "q2": params["q2"]}
            closs, grads = jax.value_and_grad(critic_loss)(
                qs, target, batch, ck)
            upd, s_c = self.opt_critic.update(grads, s_c, qs)
            qs = optax.apply_updates(qs, upd)
            params = {**params, **qs}

            # delayed policy update: every policy_delay-th step
            def do_actor(args):
                params, target, s_a = args
                aloss, ag = jax.value_and_grad(actor_loss)(
                    params["actor"], params["q1"], batch)
                au, s_a = self.opt_actor.update(ag, s_a, params["actor"])
                actor_p = optax.apply_updates(params["actor"], au)
                params = {**params, "actor": actor_p}
                target = polyak(target, params)
                return params, target, s_a, aloss

            def skip_actor(args):
                params, target, s_a = args
                return params, target, s_a, jnp.zeros(())

            params, target, s_a, aloss = jax.lax.cond(
                step_i % c.policy_delay == 0, do_actor, skip_actor,
                (params, target, s_a))
            return ((params, target, s_a, s_c, key),
                    {"critic_loss": closs, "actor_loss": aloss})

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def update_many(params, target, s_a, s_c, key, batches):
            K = batches["rewards"].shape[0]
            (params, target, s_a, s_c, key), stats = jax.lax.scan(
                one_update, (params, target, s_a, s_c, key),
                (batches, jnp.arange(K)))
            return params, target, s_a, s_c, key, jax.tree.map(
                jnp.mean, stats)

        self._update_many = update_many

    def update(self, stacked: Dict[str, np.ndarray]) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        batches = {k: jnp.asarray(v) for k, v in stacked.items()}
        (self.params, self.target, self.state_actor, self.state_critic,
         self._key, stats) = self._update_many(
            self.params, self.target, self.state_actor,
            self.state_critic, self._key, batches)
        self.num_updates += int(stacked["rewards"].shape[0])
        return {k: float(v) for k, v in jax.device_get(stats).items()}


class TD3:
    """Tune-trainable TD3 (same driver shape as SAC)."""

    def __init__(self, config: TD3Config):
        from .env import make_env

        c = self.config = config
        probe = (cloudpickle.loads(cloudpickle.dumps(c.env_creator))(
            num_envs=1, seed=0) if c.env_creator is not None
            else make_env(c.env, num_envs=1, seed=0))
        if not hasattr(probe, "action_dim"):
            raise ValueError(f"TD3 needs a continuous-action env; "
                             f"{c.env!r} has no action_dim")
        obs_dim, act_dim = probe.obs_dim, probe.action_dim
        scale = float(getattr(probe, "action_scale", 1.0))
        creator_blob = (cloudpickle.dumps(c.env_creator)
                        if c.env_creator is not None else None)
        cls = ray_tpu.remote(TD3RolloutWorker)
        opts = worker_opts(c.worker_resources)
        self.workers = [
            cls.options(**opts).remote(
                c.env, c.num_envs_per_worker, c.rollout_fragment_length,
                scale, c.explore_sigma, seed=c.seed + 31 * i,
                env_creator=creator_blob)
            for i in range(c.num_rollout_workers)
        ]
        self.learner = TD3Learner(obs_dim, act_dim, c)
        self.buffer = ReplayBuffer(c.buffer_size, seed=c.seed)
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent: list = []

    def train(self) -> Dict[str, float]:
        import jax

        c = self.config
        t0 = time.monotonic()
        warmup = self._total_steps < c.learning_starts
        actor_np = jax.device_get(self.learner.params["actor"])
        batches = ray_tpu.get(
            [w.sample.remote(actor_np, random_actions=warmup)
             for w in self.workers], timeout=300)
        steps = 0
        for b in batches:
            self.buffer.add(b)
            steps += len(b["rewards"])
        self._total_steps += steps
        stats: Dict[str, float] = {}
        if len(self.buffer) >= max(c.learning_starts, c.train_batch_size):
            K, B = c.num_updates_per_iter, c.train_batch_size
            mb = self.buffer.sample(K * B)
            stacked = {k: v.reshape(K, B, *v.shape[1:])
                       for k, v in mb.items()}
            stats = self.learner.update(stacked)
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
            "num_updates": self.learner.num_updates,
            "time_this_iter_s": time.monotonic() - t0,
            **stats,
        }

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        L = self.learner
        ckpt = {"params": jax.device_get(L.params),
                "target": jax.device_get(L.target),
                "opt_states": jax.device_get((L.state_actor,
                                              L.state_critic)),
                "rng_key": jax.device_get(L._key),
                "iteration": self._iteration,
                "total_steps": self._total_steps}
        if self.config.checkpoint_replay_buffer:
            ckpt["buffer"] = self.buffer.state()
        return ckpt

    def restore(self, ckpt: Dict) -> None:
        import jax
        import jax.numpy as jnp

        as_jnp = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        L = self.learner
        L.params = as_jnp(ckpt["params"])
        L.target = as_jnp(ckpt["target"])
        if "opt_states" in ckpt:
            L.state_actor, L.state_critic = as_jnp(ckpt["opt_states"])
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


# DDPG is TD3 with its innovations switched off (ref: ddpg.py — the
# reference implements TD3 as a DDPG subclass; the relation inverts
# cleanly here)
def DDPGConfig(**kw) -> TD3Config:  # noqa: N802 — ref naming
    kw.setdefault("policy_delay", 1)
    kw.setdefault("target_noise", 0.0)
    kw.setdefault("target_noise_clip", 0.0)
    return TD3Config(**kw)
