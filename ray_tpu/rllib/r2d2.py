"""R2D2 — recurrent experience replay in distributed RL
(Kapturowski et al. 2019).

ref: rllib/algorithms/r2d2/r2d2.py (R2D2Config: replay sequences with
burn-in, zero-or-stored init states, h-function value rescaling) +
r2d2_torch_policy.py (double-Q over the LSTM unroll, sequence-level
priorities eta*max + (1-eta)*mean of |TD|).

House TPU shape: rollout actors run a small numpy LSTM per step (no jax
in workers — np_policy.py rationale) and emit fixed-length SEQUENCES
with the recurrent state captured at each window start; the driver keeps
a prioritized replay of sequences; the learner unrolls burn-in (gradient
stopped) + training segment as lax.scan inside ONE jitted dispatch per
train() call (the learner rule of this package). Episode boundaries
inside a window reset the hidden state identically in worker and
learner, so stored and recomputed unrolls agree.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import cloudpickle
import numpy as np

import ray_tpu

from . import sample_batch as sb
from .replay_buffer import (PrioritizedReplayBuffer, ReplayBuffer,
                            fused_replay_update)
from .rollout_worker import EnvWorkerBase, worker_opts

H0, C0 = "h0", "c0"


def init_r2d2_params(rng, obs_dim: int, num_actions: int,
                     encoder_hidden: int, cell_size: int) -> Dict:
    import jax
    import jax.numpy as jnp

    k1, k2, k3, k4 = jax.random.split(rng, 4)
    H = cell_size
    return {
        "enc_w": jax.random.normal(k1, (obs_dim, encoder_hidden),
                                   jnp.float32)
        * np.sqrt(2.0 / obs_dim),
        "enc_b": jnp.zeros((encoder_hidden,), jnp.float32),
        "lstm_wx": jax.random.normal(k2, (encoder_hidden, 4 * H),
                                     jnp.float32)
        * np.sqrt(1.0 / encoder_hidden),
        "lstm_wh": jax.random.normal(k3, (H, 4 * H), jnp.float32)
        * np.sqrt(1.0 / H),
        "lstm_b": jnp.zeros((4 * H,), jnp.float32),
        "q_w": jax.random.normal(k4, (H, num_actions), jnp.float32) * 0.01,
        "q_b": jnp.zeros((num_actions,), jnp.float32),
    }


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


def lstm_step_np(p: Dict[str, np.ndarray], obs: np.ndarray, h: np.ndarray,
                 c: np.ndarray):
    """One numpy LSTM step: obs [n, obs_dim], h/c [n, H] -> (q, h, c).
    Mirrors the learner's jax cell bit-for-bit in structure (forget-gate
    bias +1)."""
    x = np.maximum(obs @ p["enc_w"] + p["enc_b"], 0.0)
    z = x @ p["lstm_wx"] + h @ p["lstm_wh"] + p["lstm_b"]
    H = h.shape[1]
    i, f = _sigmoid(z[:, :H]), _sigmoid(z[:, H:2 * H] + 1.0)
    g, o = np.tanh(z[:, 2 * H:3 * H]), _sigmoid(z[:, 3 * H:])
    c = f * c + i * g
    h = o * np.tanh(c)
    q = h @ p["q_w"] + p["q_b"]
    return q, h, c


class R2D2RolloutWorker(EnvWorkerBase):
    """Epsilon-greedy sampling through the recurrent policy; emits
    non-overlapping seq_len windows with (h, c) captured at each window
    start (the 'stored state' strategy — ref: r2d2.py
    zero_init_states=False path)."""

    def __init__(self, env_name: str, num_envs: int, rollout_len: int,
                 seq_len: int, cell_size: int, seed: int = 0,
                 env_creator=None):
        super().__init__(env_name, num_envs, rollout_len, seed, env_creator)
        if rollout_len % seq_len != 0:
            raise ValueError(f"rollout_fragment_length {rollout_len} must "
                             f"be a multiple of seq_len {seq_len}")
        self.seq_len = seq_len
        n = self.env.num_envs
        self._h = np.zeros((n, cell_size), np.float32)
        self._c = np.zeros((n, cell_size), np.float32)

    def sample(self, params: Dict, epsilon: float) -> sb.Batch:
        p = {k: np.asarray(v, np.float32) for k, v in params.items()}
        T, L = self.rollout_len, self.seq_len
        n, A = self.env.num_envs, self.env.num_actions
        n_win = T // L
        Hc = self._h.shape[1]
        obs_buf = np.empty((T + 1, n, self.env.obs_dim), np.float32)
        act_buf = np.empty((T, n), np.int64)
        rew_buf = np.empty((T, n), np.float32)
        done_buf = np.empty((T, n), np.bool_)
        h0_buf = np.empty((n_win, n, Hc), np.float32)
        c0_buf = np.empty((n_win, n, Hc), np.float32)
        obs = self._obs
        for t in range(T):
            if t % L == 0:
                h0_buf[t // L], c0_buf[t // L] = self._h, self._c
            q, self._h, self._c = lstm_step_np(p, obs, self._h, self._c)
            actions = q.argmax(axis=1)
            explore = self._rng.random(n) < epsilon
            actions = np.where(explore, self._rng.integers(0, A, size=n),
                               actions).astype(np.int64)
            obs_buf[t], act_buf[t] = obs, actions
            obs, reward, done, info = self.env.step(actions)
            rew_buf[t], done_buf[t] = reward, done
            self._track_returns(reward, done)
            if done.any():
                # episode boundary: recurrent state resets (time-limit
                # truncation treated as termination here — the sequence
                # target is cut either way; documented divergence from
                # dqn.py's bootstrap-through-truncation)
                idx = np.nonzero(done)[0]
                self._h[idx] = 0.0
                self._c[idx] = 0.0
        obs_buf[T] = obs
        self._obs = obs

        # windows [n_win, L(+1), n, ...] -> sequence rows [n_win*n, ...]
        def rows(a, extra: int = 0):
            w = np.stack([a[i * L:(i + 1) * L + extra]
                          for i in range(n_win)])
            return np.swapaxes(w, 1, 2).reshape(n_win * n, L + extra,
                                                *a.shape[2:])

        return {
            sb.OBS: rows(obs_buf, extra=1),
            sb.ACTIONS: rows(act_buf),
            sb.REWARDS: rows(rew_buf),
            sb.DONES: rows(done_buf),
            H0: h0_buf.reshape(n_win * n, Hc),
            C0: c0_buf.reshape(n_win * n, Hc),
        }


class R2D2Learner:
    """Jitted recurrent double-DQN over sequence minibatches: burn-in
    unroll (stop_gradient), training-segment unroll, h-function value
    rescaling, sequence priorities (ref: r2d2_torch_policy.py
    r2d2_loss)."""

    def __init__(self, obs_dim: int, num_actions: int, *, lr: float,
                 gamma: float, seq_len: int, burn_in: int,
                 encoder_hidden: int, cell_size: int,
                 use_h_function: bool = True, double_q: bool = True,
                 seed: int = 0, max_grad_norm: float = 10.0):
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        self.params = init_r2d2_params(jax.random.PRNGKey(seed), obs_dim,
                                       num_actions, encoder_hidden,
                                       cell_size)
        self.target_params = jax.tree.map(lambda a: a.copy(), self.params)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(max_grad_norm), optax.adam(lr))
        self.opt_state = self.optimizer.init(self.params)
        self.num_updates = 0
        eps_h = 1e-3

        def h_fn(x):
            if not use_h_function:
                return x
            return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) \
                + eps_h * x

        def h_inv(x):
            if not use_h_function:
                return x
            inner = jnp.sqrt(1.0 + 4.0 * eps_h * (jnp.abs(x) + 1.0 + eps_h))
            return jnp.sign(x) * (((inner - 1.0) / (2.0 * eps_h)) ** 2
                                  - 1.0)

        def cell(p, obs, h, c):
            x = jax.nn.relu(obs @ p["enc_w"] + p["enc_b"])
            z = x @ p["lstm_wx"] + h @ p["lstm_wh"] + p["lstm_b"]
            H = h.shape[1]
            i = jax.nn.sigmoid(z[:, :H])
            f = jax.nn.sigmoid(z[:, H:2 * H] + 1.0)
            g = jnp.tanh(z[:, 2 * H:3 * H])
            o = jax.nn.sigmoid(z[:, 3 * H:])
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return (h @ p["q_w"] + p["q_b"]), h, c

        def unroll(p, obs_tl, resets_tl, h, c):
            """obs_tl [L', B, obs], resets [L', B] -> q [L', B, A]."""
            def body(carry, xs):
                h, c = carry
                obs_t, reset_t = xs
                keep = (1.0 - reset_t)[:, None]
                q, h, c = cell(p, obs_t, h * keep, c * keep)
                return (h, c), q

            (h, c), qs = jax.lax.scan(body, (h, c), (obs_tl, resets_tl))
            return qs, h, c

        def loss_fn(params, target_params, batch, weights):
            obs = jnp.swapaxes(batch[sb.OBS], 0, 1)      # [L+1, B, obs]
            dones = jnp.swapaxes(batch[sb.DONES], 0, 1)  # [L, B]
            d = dones.astype(jnp.float32)
            # reset entering step t is done at t-1 (first step: stored
            # state is already post-reset in the worker)
            resets = jnp.concatenate(
                [jnp.zeros((1, d.shape[1])), d], axis=0)  # [L+1, B]
            h, c = batch[H0], batch[C0]
            th, tc = batch[H0], batch[C0]
            if burn_in > 0:
                _, h, c = unroll(params, obs[:burn_in], resets[:burn_in],
                                 h, c)
                h, c = jax.lax.stop_gradient((h, c))
                _, th, tc = unroll(target_params, obs[:burn_in],
                                   resets[:burn_in], th, tc)
            q_on, _, _ = unroll(params, obs[burn_in:], resets[burn_in:],
                                h, c)                     # [L+1-b, B, A]
            q_tg, _, _ = unroll(target_params, obs[burn_in:],
                                resets[burn_in:], th, tc)
            acts = jnp.swapaxes(batch[sb.ACTIONS], 0, 1)[burn_in:]
            rews = jnp.swapaxes(batch[sb.REWARDS], 0, 1)[burn_in:]
            d_tr = d[burn_in:]                            # [L-b, B]
            q_sa = jnp.take_along_axis(q_on[:-1], acts[..., None],
                                       axis=2)[..., 0]
            if double_q:
                a_star = q_on[1:].argmax(axis=2)
            else:
                a_star = q_tg[1:].argmax(axis=2)
            q_next = jnp.take_along_axis(q_tg[1:], a_star[..., None],
                                         axis=2)[..., 0]
            y = h_fn(rews + gamma * (1.0 - d_tr)
                     * jax.lax.stop_gradient(h_inv(q_next)))
            td = q_sa - y
            huber = optax.huber_loss(q_sa, y, delta=1.0)  # [L-b, B]
            loss = jnp.mean(weights[None, :] * huber)
            td_abs = jnp.abs(td)
            # sequence priority: eta*max + (1-eta)*mean (ref r2d2 paper)
            prio = 0.9 * td_abs.max(axis=0) + 0.1 * td_abs.mean(axis=0)
            return loss, (prio, jnp.mean(q_sa))

        def one_update(params, opt_state, target_params, batch, weights):
            (loss, (prio, mean_q)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, target_params, batch,
                                       weights)
            updates, opt_state = self.optimizer.update(grads, opt_state,
                                                       params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss, prio, mean_q

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update_many(params, opt_state, target_params, batches, weights):
            def body(carry, xs):
                params, opt_state = carry
                batch_k, w_k = xs
                params, opt_state, loss, prio, mean_q = one_update(
                    params, opt_state, target_params, batch_k, w_k)
                return (params, opt_state), (loss, prio, mean_q)

            (params, opt_state), outs = jax.lax.scan(
                body, (params, opt_state), (batches, weights))
            return params, opt_state, outs

        self._update_many = update_many

    _KEYS = (sb.OBS, sb.ACTIONS, sb.REWARDS, sb.DONES, H0, C0)

    def update_many(self, batches: Dict[str, np.ndarray],
                    weights: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """batches: dict of [K, B, L(+1), ...] arrays; -> per-sequence
        priorities [K, B]."""
        import jax
        import jax.numpy as jnp

        K, B = batches[sb.REWARDS].shape[:2]
        w = jnp.ones((K, B)) if weights is None else jnp.asarray(weights)
        jb = {k: jnp.asarray(batches[k]) for k in self._KEYS}
        (self.params, self.opt_state,
         (losses, prios, mean_qs)) = self._update_many(
            self.params, self.opt_state, self.target_params, jb, w)
        self.num_updates += K
        out = jax.device_get((losses, prios, mean_qs))
        return {"loss": float(np.mean(out[0])),
                "mean_q": float(np.mean(out[2])),
                "priorities": np.asarray(out[1])}

    def sync_target(self) -> None:
        import jax

        self.target_params = jax.tree.map(lambda a: a.copy(), self.params)

    def get_params(self) -> Dict:
        import jax

        return jax.device_get(self.params)


@dataclass
class R2D2Config:
    """ref: r2d2.py R2D2Config (burn_in, zero_init_states, h-function;
    sequence replay defaults)."""
    env: str = "CartPole-v1"
    env_creator: Optional[Callable] = None
    num_rollout_workers: int = 2
    num_envs_per_worker: int = 8
    rollout_fragment_length: int = 64
    seq_len: int = 16
    burn_in: int = 4
    gamma: float = 0.99
    lr: float = 5e-4
    buffer_size: int = 4_000          # sequences, not transitions
    prioritized_replay: bool = True
    prioritized_replay_alpha: float = 0.6
    prioritized_replay_beta: float = 0.4
    train_batch_size: int = 32        # sequences per minibatch
    num_updates_per_iter: int = 8
    learning_starts: int = 200        # sequences
    target_update_freq: int = 100     # learner updates
    epsilon_initial: float = 1.0
    epsilon_final: float = 0.02
    epsilon_decay_steps: int = 10_000
    use_h_function: bool = True
    double_q: bool = True
    encoder_hidden: int = 64
    cell_size: int = 64
    seed: int = 0
    checkpoint_replay_buffer: bool = True
    worker_resources: Dict[str, float] = field(default_factory=dict)

    def build(self) -> "R2D2":
        return R2D2(self)


class R2D2:
    """Synchronous R2D2 driver (DQN shape, sequence granularity)."""

    def __init__(self, config: R2D2Config):
        self.config = c = config
        if c.burn_in >= c.seq_len:
            raise ValueError("burn_in must be < seq_len")
        creator_blob = (cloudpickle.dumps(c.env_creator)
                        if c.env_creator else None)
        worker_cls = ray_tpu.remote(R2D2RolloutWorker)
        opts = worker_opts(c.worker_resources)
        self.workers: List = [
            worker_cls.options(**opts).remote(
                c.env, c.num_envs_per_worker, c.rollout_fragment_length,
                c.seq_len, c.cell_size, seed=c.seed + 1000 * i,
                env_creator=creator_blob)
            for i in range(c.num_rollout_workers)]
        info = ray_tpu.get(self.workers[0].env_info.remote(), timeout=180)
        self.learner = R2D2Learner(
            info["obs_dim"], info["num_actions"], lr=c.lr, gamma=c.gamma,
            seq_len=c.seq_len, burn_in=c.burn_in,
            encoder_hidden=c.encoder_hidden, cell_size=c.cell_size,
            use_h_function=c.use_h_function, double_q=c.double_q,
            seed=c.seed)
        if c.prioritized_replay:
            self.buffer = PrioritizedReplayBuffer(
                c.buffer_size, alpha=c.prioritized_replay_alpha,
                beta=c.prioritized_replay_beta, seed=c.seed)
        else:
            self.buffer = ReplayBuffer(c.buffer_size, seed=c.seed)
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent: List[float] = []

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
        n_seq = len(batch[sb.REWARDS])
        steps = n_seq * c.seq_len
        self._total_steps += steps
        self.buffer.add(batch)
        sample_time = time.monotonic() - t0
        t1 = time.monotonic()
        stats: Dict[str, Any] = {}
        if len(self.buffer) >= c.learning_starts:
            K = c.num_updates_per_iter
            out = fused_replay_update(self.buffer,
                                      self.learner.update_many, K,
                                      c.train_batch_size, "priorities")
            n = self.learner.num_updates
            if n // c.target_update_freq > (n - K) // c.target_update_freq:
                self.learner.sync_target()
            stats = {"loss": out["loss"], "mean_q": out["mean_q"],
                     "num_updates": n}
        learn_time = time.monotonic() - t1
        for rets in ray_tpu.get(
                [w.episode_returns.remote() for w in self.workers],
                timeout=60):
            self._recent.extend(rets)
            self._total_episodes += len(rets)
        self._recent = self._recent[-100:]
        self._iteration += 1
        return {"training_iteration": self._iteration,
                "timesteps_total": self._total_steps,
                "timesteps_this_iter": steps,
                "episode_reward_mean": (float(np.mean(self._recent))
                                        if self._recent else float("nan")),
                "episodes_total": self._total_episodes,
                "epsilon": eps,
                "buffer_sequences": len(self.buffer),
                "env_steps_per_sec": steps / max(1e-9,
                                                 sample_time + learn_time),
                "sample_time_s": sample_time, "learn_time_s": learn_time,
                **stats}

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        ckpt = {"params": jax.device_get(self.learner.params),
                "target_params": jax.device_get(
                    self.learner.target_params),
                "opt_state": jax.device_get(self.learner.opt_state),
                "iteration": self._iteration,
                "total_steps": self._total_steps,
                "num_updates": self.learner.num_updates}
        if self.config.checkpoint_replay_buffer:
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
