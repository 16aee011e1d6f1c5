"""QMIX — monotonic value decomposition for cooperative multi-agent RL.

ref: rllib/algorithms/qmix/qmix.py + qmix_policy.py (mixer in
rllib/algorithms/qmix/model.py QMixer): per-agent Q-networks (shared
parameters + one-hot agent id) pick decentralized greedy actions; a
mixing hypernetwork conditioned on the GLOBAL state combines the chosen
per-agent Q values into Q_tot with non-negative mixing weights, so
argmax_a Q_tot = per-agent argmaxes (the monotonicity constraint —
centralized training, decentralized execution).

TPU-native shape: the whole K-minibatch update (per-agent Q forward,
target mixer, TD loss, Adam) is ONE jitted lax.scan dispatch
(`update_many`) — the same fused-learner rule every off-policy algo in
this package follows (the learner rule: no host round
trip between updates). The env steps in-process: cooperative
small-team games are sampler-light, learner-heavy.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np

from .multi_agent import make_multi_agent_env


def _init_mlp(rng, sizes):
    import jax
    import jax.numpy as jnp

    params = {}
    keys = jax.random.split(rng, len(sizes) - 1)
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        params[f"w{i}"] = jax.random.normal(keys[i], (a, b),
                                            jnp.float32) * np.sqrt(2.0 / a)
        params[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return params


def _mlp(params, x, n_layers):
    import jax.numpy as jnp

    for i in range(n_layers):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < n_layers - 1:
            x = jnp.maximum(x, 0.0)
    return x


@dataclass
class QMIXConfig:
    """ref: qmix.py QMIXConfig defaults (mixing_embed_dim 32, double_q,
    target update period, epsilon anneal)."""
    env: str = "Coordination-v0"
    num_envs: int = 16
    gamma: float = 0.99
    lr: float = 5e-4
    buffer_size: int = 50_000
    train_batch_size: int = 128
    num_updates_per_iter: int = 16
    rollout_len: int = 50
    learning_starts: int = 500
    target_update_freq: int = 40    # in updates
    mixing_embed_dim: int = 32
    hidden: tuple = (64,)
    epsilon_start: float = 1.0
    epsilon_end: float = 0.05
    epsilon_anneal_steps: int = 5_000
    seed: int = 0
    extra: Dict[str, Any] = field(default_factory=dict)

    def build(self) -> "QMIX":
        return QMIX(self)


class QMIXLearner:
    def __init__(self, obs_dim: int, num_actions: int, n_agents: int,
                 state_dim: int, c: QMIXConfig):
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        self.n_agents, self.num_actions = n_agents, num_actions
        k = jax.random.split(jax.random.PRNGKey(c.seed), 5)
        h = list(c.hidden)
        emb = c.mixing_embed_dim
        # shared per-agent Q net; input = obs ++ one-hot agent id
        self.params = {
            "q": _init_mlp(k[0], [obs_dim + n_agents, *h, num_actions]),
            # hypernetworks: state -> mixing weights (abs() for
            # monotonicity) and biases (ref: qmix/model.py QMixer)
            "hyp_w1": _init_mlp(k[1], [state_dim, n_agents * emb]),
            "hyp_b1": _init_mlp(k[2], [state_dim, emb]),
            "hyp_w2": _init_mlp(k[3], [state_dim, emb]),
            "hyp_b2": _init_mlp(k[4], [state_dim, emb, 1]),
        }
        self.target = jax.tree.map(lambda a: a.copy(), self.params)
        self.opt = optax.adam(c.lr)
        self.opt_state = self.opt.init(self.params)
        self.num_updates = 0
        n_q_layers = len(h) + 1

        def agent_qs(qp, obs_all):
            # obs_all [B, n_agents, obs_dim] -> [B, n_agents, A]
            B = obs_all.shape[0]
            ids = jnp.eye(n_agents, dtype=jnp.float32)
            ids = jnp.broadcast_to(ids[None], (B, n_agents, n_agents))
            x = jnp.concatenate([obs_all, ids], axis=-1)
            return _mlp(qp, x, n_q_layers)

        def mix(mp, chosen_q, state):
            # chosen_q [B, n_agents], state [B, S] -> Q_tot [B]
            B = chosen_q.shape[0]
            w1 = jnp.abs(_mlp(mp["hyp_w1"], state, 1)).reshape(
                B, n_agents, emb)
            b1 = _mlp(mp["hyp_b1"], state, 1)
            hidden_l = jnp.einsum("ba,bae->be", chosen_q, w1) + b1
            hidden_l = jnp.where(hidden_l > 0, hidden_l,
                                 jnp.expm1(hidden_l))  # ELU
            w2 = jnp.abs(_mlp(mp["hyp_w2"], state, 1))
            b2 = _mlp(mp["hyp_b2"], state, 2)[:, 0]
            return jnp.sum(hidden_l * w2, axis=-1) + b2

        self._agent_qs = jax.jit(agent_qs)

        def td_loss(params, target, batch):
            qs = agent_qs(params["q"], batch["obs"])          # [B,n,A]
            chosen = jnp.take_along_axis(
                qs, batch["actions"][..., None], axis=-1)[..., 0]
            q_tot = mix(params, chosen, batch["state"])
            # double-Q: online net picks a', target net evaluates
            next_online = agent_qs(params["q"], batch["next_obs"])
            a_next = jnp.argmax(next_online, axis=-1)
            next_target = agent_qs(target["q"], batch["next_obs"])
            chosen_next = jnp.take_along_axis(
                next_target, a_next[..., None], axis=-1)[..., 0]
            q_tot_next = mix(target, chosen_next, batch["next_state"])
            y = batch["reward"] + c.gamma * (1.0 - batch["done"]) \
                * q_tot_next
            y = jax.lax.stop_gradient(y)
            return jnp.mean(jnp.square(q_tot - y))

        def one_update(carry, mb):
            params, opt_state, target = carry
            loss, grads = jax.value_and_grad(td_loss)(params, target, mb)
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, target), loss

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update_many(params, opt_state, target, batches):
            (params, opt_state, _), losses = jax.lax.scan(
                one_update, (params, opt_state, target), batches)
            return params, opt_state, jnp.mean(losses)

        self._update_many = update_many
        import jax.numpy as jnp  # noqa: F811 — keep local alias bound

    def greedy_actions(self, obs_all: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        qs = self._agent_qs(self.params["q"], jnp.asarray(obs_all))
        return np.asarray(jnp.argmax(qs, axis=-1))

    def update(self, stacked: Dict[str, np.ndarray]) -> float:
        import jax.numpy as jnp

        batches = {k: jnp.asarray(v) for k, v in stacked.items()}
        self.params, self.opt_state, loss = self._update_many(
            self.params, self.opt_state, self.target, batches)
        self.num_updates += int(stacked["reward"].shape[0])
        return float(loss)

    def sync_target(self) -> None:
        import jax

        self.target = jax.tree.map(lambda a: a.copy(), self.params)


class QMIX:
    """Tune-trainable QMIX on a MultiAgentVecEnv (all agents active each
    step, shared team reward)."""

    def __init__(self, config: QMIXConfig):
        c = self.config = config
        self.env = make_multi_agent_env(c.env, num_envs=c.num_envs,
                                        seed=c.seed)
        self.agents = list(self.env.agent_ids)
        n = len(self.agents)
        obs_dim = self.env.obs_dim
        self.learner = QMIXLearner(obs_dim, self.env.num_actions, n,
                                   state_dim=n * obs_dim, c=c)
        self._rng = np.random.default_rng(c.seed + 1)
        self._obs = self.env.reset(seed=c.seed)
        # flat ring buffer of team transitions
        self._buf: Dict[str, np.ndarray] = {}
        self._buf_n = 0
        self._buf_pos = 0
        self._total_steps = 0
        self._iteration = 0
        self._ep_ret = np.zeros(c.num_envs, np.float64)
        self._recent: list = []

    def _stack_obs(self, obs: Dict[str, np.ndarray]) -> np.ndarray:
        return np.stack([obs[a] for a in self.agents], axis=1)  # [n,agents,D]

    def _epsilon(self) -> float:
        c = self.config
        frac = min(1.0, self._total_steps / max(1, c.epsilon_anneal_steps))
        return c.epsilon_start + frac * (c.epsilon_end - c.epsilon_start)

    def _add(self, tr: Dict[str, np.ndarray]) -> None:
        cap = self.config.buffer_size
        n = len(tr["reward"])
        if not self._buf:
            self._buf = {k: np.empty((cap, *v.shape[1:]), v.dtype)
                         for k, v in tr.items()}
        for k, v in tr.items():
            idx = (self._buf_pos + np.arange(n)) % cap
            self._buf[k][idx] = v
        self._buf_pos = (self._buf_pos + n) % cap
        self._buf_n = min(cap, self._buf_n + n)

    def train(self) -> Dict[str, float]:
        c = self.config
        t0 = time.monotonic()
        steps = 0
        for _ in range(c.rollout_len):
            obs_all = self._stack_obs(self._obs)          # [n, agents, D]
            greedy = self.learner.greedy_actions(obs_all)  # [n, agents]
            eps = self._epsilon()
            explore = self._rng.random(greedy.shape) < eps
            randoms = self._rng.integers(0, self.env.num_actions,
                                         greedy.shape)
            acts = np.where(explore, randoms, greedy)
            action_dict = {a: acts[:, i] for i, a in enumerate(self.agents)}
            next_obs, rewards, done, info = self.env.step(action_dict)
            team_r = np.mean([rewards[a] for a in self.agents],
                             axis=0).astype(np.float32)
            next_all = self._stack_obs(next_obs)
            state = obs_all.reshape(len(obs_all), -1)
            # time-limit truncation bootstraps (final_obs), termination
            # doesn't — Coordination's cap is a truncation
            trunc = info.get("truncated")
            term = done & ~trunc if trunc is not None else done
            nxt = next_all
            if trunc is not None and trunc.any():
                fin = self._stack_obs(info["final_obs"])
                nxt = np.where(trunc[:, None, None], fin, next_all)
            self._add({"obs": obs_all.astype(np.float32),
                       "actions": acts.astype(np.int32),
                       "reward": team_r,
                       "done": term.astype(np.float32),
                       "next_obs": nxt.astype(np.float32),
                       "state": state.astype(np.float32),
                       "next_state": nxt.reshape(len(nxt), -1)
                       .astype(np.float32)})
            self._ep_ret += team_r
            if done.any():
                idx = np.nonzero(done)[0]
                self._recent.extend(self._ep_ret[idx].tolist())
                self._ep_ret[idx] = 0.0
            self._obs = next_obs
            steps += c.num_envs
        self._total_steps += steps
        loss = float("nan")
        if self._buf_n >= max(c.learning_starts, c.train_batch_size):
            K, B = c.num_updates_per_iter, c.train_batch_size
            idx = self._rng.integers(0, self._buf_n, K * B)
            stacked = {k: v[idx].reshape(K, B, *v.shape[1:])
                       for k, v in self._buf.items()}
            loss = self.learner.update(stacked)
            if self.learner.num_updates // c.target_update_freq != \
                    (self.learner.num_updates - K) // c.target_update_freq:
                self.learner.sync_target()
        self._recent = self._recent[-100:]
        self._iteration += 1
        return {
            "training_iteration": self._iteration,
            "timesteps_total": self._total_steps,
            "episode_reward_mean": (float(np.mean(self._recent))
                                    if self._recent else float("nan")),
            "epsilon": self._epsilon(),
            "loss": loss,
            "time_this_iter_s": time.monotonic() - t0,
        }

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        return {"params": jax.device_get(self.learner.params),
                "target": jax.device_get(self.learner.target),
                "opt_state": jax.device_get(self.learner.opt_state),
                "num_updates": self.learner.num_updates,
                "iteration": self._iteration,
                "total_steps": self._total_steps}

    def restore(self, ckpt: Dict) -> None:
        import jax
        import jax.numpy as jnp

        as_jnp = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        self.learner.params = as_jnp(ckpt["params"])
        self.learner.target = as_jnp(ckpt["target"])
        if "opt_state" in ckpt:
            self.learner.opt_state = as_jnp(ckpt["opt_state"])
        self.learner.num_updates = int(ckpt.get("num_updates", 0))
        self._iteration = int(ckpt.get("iteration", 0))
        self._total_steps = int(ckpt.get("total_steps", 0))

    def stop(self) -> None:
        pass
