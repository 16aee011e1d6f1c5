"""DQN — off-policy Q-learning through the rollout-actor/learner split.

ref: rllib/algorithms/dqn/dqn.py (DQNConfig, training_step :623:
sample → store → N replay updates → target sync) and
dqn/dqn_torch_policy.py (double-Q loss, huber TD, PER weight).

TPU-native shape mirrors PPO here: epsilon-greedy rollout inference is
pure numpy on the actor CPUs (np_policy.py rationale), the learner is one
jitted donated-buffer update on the JAX device, and the replay buffer
lives host-side in the driver where sampling is pointer math, not device
traffic. Only minibatches cross to the device.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import cloudpickle
import numpy as np

import ray_tpu

from . import sample_batch as sb
from .np_policy import ensure_numpy, forward_np
from .rollout_worker import EnvWorkerBase, worker_opts
from .replay_buffer import (PrioritizedReplayBuffer, ReplayBuffer,
                            fused_replay_update)

NEXT_OBS = "next_obs"


class DQNRolloutWorker(EnvWorkerBase):
    """Actor collecting epsilon-greedy transitions (ref:
    rollout_worker.py sample + dqn's EpsilonGreedy exploration). The Q-net
    reuses the fcnet param layout; the policy head IS the Q head."""

    def sample(self, params: Dict, epsilon: float) -> sb.Batch:
        params = ensure_numpy(params)
        T, n = self.rollout_len, self.env.num_envs
        A = self.env.num_actions
        obs_buf = np.empty((T, n, *self.env.obs_shape), self.env.obs_dtype)
        next_buf = np.empty((T, n, *self.env.obs_shape), self.env.obs_dtype)
        act_buf = np.empty((T, n), np.int64)
        rew_buf = np.empty((T, n), np.float32)
        done_buf = np.empty((T, n), np.bool_)
        obs = self._obs
        for t in range(T):
            q, _ = forward_np(params, obs)
            actions = q.argmax(axis=1)
            explore = self._rng.random(n) < epsilon
            actions = np.where(explore, self._rng.integers(0, A, size=n),
                               actions).astype(np.int64)
            obs_buf[t], act_buf[t] = obs, actions
            obs, reward, done, info = self.env.step(actions)
            rew_buf[t], done_buf[t] = reward, done
            next_buf[t] = obs
            self._track_returns(reward, done)
            if done.any():
                idx = np.nonzero(done)[0]
                if "final_obs" in info:
                    # auto-reset handed back the NEW episode's obs; the
                    # transition's s' is the pre-reset terminal state
                    next_buf[t, idx] = info["final_obs"][idx]
                if "truncated" in info:
                    # time-limit truncation still bootstraps: don't cut
                    # the target at a non-terminal state
                    done_buf[t] &= ~info["truncated"]
        self._obs = obs
        flat = lambda a: a.reshape(T * n, *a.shape[2:])  # noqa: E731
        return {sb.OBS: flat(obs_buf), sb.ACTIONS: flat(act_buf),
                sb.REWARDS: flat(rew_buf), sb.DONES: flat(done_buf),
                NEXT_OBS: flat(next_buf)}


class DQNLearner:
    """Jitted double-DQN update with a periodically synced target net
    (ref: dqn_torch_policy.py build_q_losses; learner.py donation
    rationale). Returns |TD| so prioritized replay can refresh
    priorities without a second device pass."""

    def __init__(self, obs_dim, num_actions: int, *, lr: float = 5e-4,
                 gamma: float = 0.99, double_q: bool = True,
                 hidden=(64, 64), seed: int = 0,
                 max_grad_norm: float = 10.0):
        import jax
        import optax

        from .models import init_policy_params

        self.params = init_policy_params(jax.random.PRNGKey(seed), obs_dim,
                                         num_actions, tuple(hidden))
        self.target_params = jax.tree.map(lambda a: a.copy(), self.params)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(max_grad_norm), optax.adam(lr))
        self.opt_state = self.optimizer.init(self.params)
        self._update = jax.jit(self._make_update(gamma, double_q),
                               donate_argnums=(0, 1))
        self._update_many = jax.jit(
            self._make_update_many(gamma, double_q), donate_argnums=(0, 1))
        self.num_updates = 0

    def _make_update(self, gamma: float, double_q: bool):
        import jax
        import jax.numpy as jnp
        import optax

        from .models import forward

        def q_values(params, obs):
            logits, _ = forward(params, obs)  # policy head doubles as Q head
            return logits

        def loss_fn(params, target_params, batch, weights):
            q = q_values(params, batch[sb.OBS])
            q_sa = jnp.take_along_axis(
                q, batch[sb.ACTIONS][:, None], axis=1)[:, 0]
            q_next_target = q_values(target_params, batch[NEXT_OBS])
            if double_q:
                # online net selects, target net evaluates
                a_star = q_values(params, batch[NEXT_OBS]).argmax(axis=1)
            else:
                a_star = q_next_target.argmax(axis=1)
            q_next = jnp.take_along_axis(
                q_next_target, a_star[:, None], axis=1)[:, 0]
            not_done = 1.0 - batch[sb.DONES].astype(jnp.float32)
            y = batch[sb.REWARDS] + gamma * not_done \
                * jax.lax.stop_gradient(q_next)
            td = q_sa - y
            huber = optax.huber_loss(q_sa, y, delta=1.0)
            loss = jnp.mean(weights * huber)
            return loss, (jnp.abs(td), jnp.mean(q_sa))

        def update(params, opt_state, target_params, batch, weights):
            (loss, (td_abs, mean_q)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, target_params, batch, weights)
            updates, opt_state = self.optimizer.update(grads, opt_state,
                                                       params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, td_abs, mean_q

        return update

    def _make_update_many(self, gamma: float, double_q: bool):
        """The whole per-iteration SGD block as ONE jitted lax.scan over
        pre-sampled minibatches — one dispatch and one readback no matter
        how many updates, so the host never sits between two updates
        (the rule of learner.py make_epoch_update_fn)."""
        import jax

        step = self._make_update(gamma, double_q)

        def update_many(params, opt_state, target_params, batches, weights):
            def body(carry, xs):
                params, opt_state = carry
                batch_k, w_k = xs
                params, opt_state, loss, td_abs, mean_q = step(
                    params, opt_state, target_params, batch_k, w_k)
                return (params, opt_state), (loss, td_abs, mean_q)

            (params, opt_state), (losses, td_abs, mean_qs) = jax.lax.scan(
                body, (params, opt_state), (batches, weights))
            return params, opt_state, losses, td_abs, mean_qs

        return update_many

    def update_many(self, batches: sb.Batch,
                    weights: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """batches: dict of [K, B, ...] arrays — K minibatches applied
        sequentially on-device. Returns per-minibatch |TD| [K, B]."""
        import jax
        import jax.numpy as jnp

        K, B = batches[sb.OBS].shape[:2]
        w = jnp.ones((K, B)) if weights is None else jnp.asarray(weights)
        jb = {k: jnp.asarray(batches[k]) for k in
              (sb.OBS, sb.ACTIONS, sb.REWARDS, sb.DONES, NEXT_OBS)}
        (self.params, self.opt_state, losses, td_abs,
         mean_qs) = self._update_many(self.params, self.opt_state,
                                      self.target_params, jb, w)
        self.num_updates += K
        out = jax.device_get((losses, td_abs, mean_qs))
        return {"loss": float(np.mean(out[0])),
                "mean_q": float(np.mean(out[2])),
                "td_abs": np.asarray(out[1])}

    def update(self, batch: sb.Batch,
               weights: Optional[np.ndarray] = None) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        n = len(batch[sb.OBS])
        w = jnp.ones(n) if weights is None else jnp.asarray(weights)
        jb = {k: jnp.asarray(batch[k]) for k in
              (sb.OBS, sb.ACTIONS, sb.REWARDS, sb.DONES, NEXT_OBS)}
        self.params, self.opt_state, loss, td_abs, mean_q = self._update(
            self.params, self.opt_state, self.target_params, jb, w)
        self.num_updates += 1
        return {"loss": float(loss), "mean_q": float(mean_q),
                "td_abs": np.asarray(jax.device_get(td_abs))}

    def sync_target(self) -> None:
        import jax

        self.target_params = jax.tree.map(lambda a: a.copy(), self.params)

    def get_params(self) -> Dict:
        import jax

        return jax.device_get(self.params)


@dataclass
class DQNConfig:
    """ref: dqn.py DQNConfig defaults (buffer 50k, eps 1.0→0.02,
    target_network_update_freq, training_intensity)."""
    env: str = "CartPole-v1"
    env_creator: Optional[Callable] = None
    num_rollout_workers: int = 2
    num_envs_per_worker: int = 8
    rollout_fragment_length: int = 32
    gamma: float = 0.99
    lr: float = 5e-4
    buffer_size: int = 50_000
    prioritized_replay: bool = True
    prioritized_replay_alpha: float = 0.6
    prioritized_replay_beta: float = 0.4
    train_batch_size: int = 64
    num_updates_per_iter: int = 16
    learning_starts: int = 1_000
    target_update_freq: int = 200  # in learner updates
    epsilon_initial: float = 1.0
    epsilon_final: float = 0.02
    epsilon_decay_steps: int = 10_000
    double_q: bool = True
    hidden: tuple = (64, 64)
    seed: int = 0
    worker_resources: Dict[str, float] = field(default_factory=dict)
    # include the replay buffer in save() so a restored trial (Tune PBT
    # exploit, pause/resume) resumes warm; disable for image/large buffers
    # where checkpoints would be GB-sized (ref: algorithm_config
    # store_buffer_in_checkpoints)
    checkpoint_replay_buffer: bool = True

    def environment(self, env: str = None, *, env_creator=None) -> "DQNConfig":
        if env is not None:
            self.env = env
        if env_creator is not None:
            self.env_creator = env_creator
        return self

    def rollouts(self, *, num_rollout_workers: int = None,
                 num_envs_per_worker: int = None,
                 rollout_fragment_length: int = None) -> "DQNConfig":
        for k, v in [("num_rollout_workers", num_rollout_workers),
                     ("num_envs_per_worker", num_envs_per_worker),
                     ("rollout_fragment_length", rollout_fragment_length)]:
            if v is not None:
                setattr(self, k, v)
        return self

    def training(self, *, lr: float = None, gamma: float = None,
                 train_batch_size: int = None, buffer_size: int = None,
                 num_updates_per_iter: int = None,
                 learning_starts: int = None,
                 target_update_freq: int = None,
                 prioritized_replay: bool = None,
                 epsilon_decay_steps: int = None) -> "DQNConfig":
        for k, v in [("lr", lr), ("gamma", gamma),
                     ("train_batch_size", train_batch_size),
                     ("buffer_size", buffer_size),
                     ("num_updates_per_iter", num_updates_per_iter),
                     ("learning_starts", learning_starts),
                     ("target_update_freq", target_update_freq),
                     ("prioritized_replay", prioritized_replay),
                     ("epsilon_decay_steps", epsilon_decay_steps)]:
            if v is not None:
                setattr(self, k, v)
        return self

    def build(self) -> "DQN":
        return DQN(self)


class DQN:
    """Synchronous DQN (ref: dqn.py training_step): parallel epsilon-greedy
    sample → replay add → N prioritized updates → periodic target sync.
    Tune-trainable shaped like PPO."""

    def __init__(self, config: DQNConfig):
        self.config = c = config
        creator_blob = (cloudpickle.dumps(c.env_creator)
                        if c.env_creator else None)
        worker_cls = ray_tpu.remote(DQNRolloutWorker)
        opts = worker_opts(c.worker_resources)
        self.workers: List = [
            worker_cls.options(**opts).remote(
                c.env, c.num_envs_per_worker, c.rollout_fragment_length,
                seed=c.seed + 1000 * i, env_creator=creator_blob)
            for i in range(c.num_rollout_workers)]
        info = ray_tpu.get(self.workers[0].env_info.remote(), timeout=180)
        self.learner = DQNLearner(
            info.get("obs_shape", info["obs_dim"]), info["num_actions"], lr=c.lr, gamma=c.gamma,
            double_q=c.double_q, hidden=c.hidden, seed=c.seed)
        if c.prioritized_replay:
            self.buffer = PrioritizedReplayBuffer(
                c.buffer_size, alpha=c.prioritized_replay_alpha,
                beta=c.prioritized_replay_beta, seed=c.seed)
        else:
            self.buffer = ReplayBuffer(c.buffer_size, seed=c.seed)
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent_returns: List[float] = []

    def _epsilon(self) -> float:
        c = self.config
        frac = min(1.0, self._total_steps / max(1, c.epsilon_decay_steps))
        return c.epsilon_initial + frac * (c.epsilon_final
                                           - c.epsilon_initial)

    def train(self) -> Dict[str, Any]:
        c = self.config
        t0 = time.monotonic()
        eps = self._epsilon()
        params_ref = ray_tpu.put(self.learner.get_params())
        batches = ray_tpu.get(
            [w.sample.remote(params_ref, eps) for w in self.workers],
            timeout=300)
        batch = sb.concat(batches)
        steps = sb.num_steps(batch)
        self._total_steps += steps
        self.buffer.add(batch)
        sample_time = time.monotonic() - t0
        t1 = time.monotonic()
        stats: Dict[str, Any] = {}
        if len(self.buffer) >= c.learning_starts:
            # All K updates ride ONE device dispatch (lax.scan). PER
            # priorities refresh after the block rather than between
            # minibatches — K·B-transition staleness, the standard
            # trade for distributed/batched DQN variants (cf. Ape-X,
            # where actors' priorities are a full generation stale).
            K = c.num_updates_per_iter
            out = fused_replay_update(self.buffer,
                                      self.learner.update_many, K,
                                      c.train_batch_size, "td_abs")
            # target sync at block granularity (at most K updates late)
            n = self.learner.num_updates
            if n // c.target_update_freq > (n - K) // c.target_update_freq:
                self.learner.sync_target()
            stats = {"loss": out["loss"], "mean_q": out["mean_q"],
                     "num_updates": n}
        learn_time = time.monotonic() - t1
        for rets in ray_tpu.get(
                [w.episode_returns.remote() for w in self.workers],
                timeout=60):
            self._recent_returns.extend(rets)
            self._total_episodes += len(rets)
        self._recent_returns = self._recent_returns[-100:]
        self._iteration += 1
        mean_ret = (float(np.mean(self._recent_returns))
                    if self._recent_returns else float("nan"))
        return {"training_iteration": self._iteration,
                "timesteps_total": self._total_steps,
                "timesteps_this_iter": steps,
                "episode_reward_mean": mean_ret,
                "episodes_total": self._total_episodes,
                "epsilon": eps,
                "buffer_size": len(self.buffer),
                "env_steps_per_sec": steps / max(1e-9,
                                                 sample_time + learn_time),
                "sample_time_s": sample_time, "learn_time_s": learn_time,
                **stats}

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        ckpt = {"params": jax.device_get(self.learner.params),
                "target_params": jax.device_get(self.learner.target_params),
                "opt_state": jax.device_get(self.learner.opt_state),
                "iteration": self._iteration,
                "total_steps": self._total_steps,
                "num_updates": self.learner.num_updates}
        if self.config.checkpoint_replay_buffer:
            # a restored trial (Tune PBT exploit, pause/resume) must not
            # restart cold: without the buffer it stalls until
            # learning_starts refills and all PER priorities are lost
            ckpt["buffer"] = self.buffer.state()
        return ckpt

    def restore(self, ckpt: Dict) -> None:
        import jax
        import jax.numpy as jnp

        as_jnp = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        self.learner.params = as_jnp(ckpt["params"])
        self.learner.target_params = as_jnp(ckpt["target_params"])
        if "opt_state" in ckpt:
            self.learner.opt_state = as_jnp(ckpt["opt_state"])
        self.learner.num_updates = int(ckpt.get("num_updates", 0))
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
