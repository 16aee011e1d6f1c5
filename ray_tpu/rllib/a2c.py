"""A2C — synchronous advantage actor-critic.

ref: rllib/algorithms/a2c/a2c.py (A2CConfig: microbatch_size grad
accumulation, sync sampling over the WorkerSet) and
rllib/algorithms/a3c/a3c_torch_policy.py (the loss: plain policy
gradient x advantage + value MSE + entropy bonus — no ratio clipping,
no multi-epoch SGD). The reference's A3C (async HogWild gradients) is
represented in this stack by the async-sampling IMPALA/APPO family;
A2C is its synchronous batched form (the reference makes the same
reduction: a2c.py subclasses a3c.py and synchronizes it).

House TPU shape: rollout workers are the shared numpy `RolloutWorker`
(GAE worker-side), and the learner applies ONE jitted update per
train() call — microbatch gradient accumulation runs as a lax.scan
inside the same dispatch, so the host pays one round trip regardless
of microbatch count (the learner rule of this package).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import cloudpickle
import numpy as np

import ray_tpu

from . import sample_batch as sb
from .rollout_worker import RolloutWorker, worker_opts


@dataclass
class A2CConfig:
    """ref: a2c.py A2CConfig defaults (lr 1e-4 order, vf_loss_coeff 0.5,
    entropy_coeff 0.01, optional microbatch_size)."""
    env: str = "CartPole-v1"
    env_creator: Optional[Callable] = None
    num_rollout_workers: int = 2
    num_envs_per_worker: int = 8
    rollout_fragment_length: int = 32
    gamma: float = 0.99
    lam: float = 1.0            # A2C default: plain returns (GAE off)
    lr: float = 7e-4
    vf_loss_coeff: float = 0.5
    entropy_coeff: float = 0.01
    max_grad_norm: float = 0.5
    # None -> one whole-batch step; else grads accumulate over
    # ceil(B / microbatch_size) slices before the single optimizer step
    microbatch_size: Optional[int] = None
    hidden: tuple = (64, 64)
    observation_filter: str = "NoFilter"
    seed: int = 0
    worker_resources: Dict[str, float] = field(default_factory=dict)

    def build(self) -> "A2C":
        return A2C(self)


def PGConfig(**kw) -> A2CConfig:  # noqa: N802 — ref naming
    """Vanilla policy gradient / REINFORCE (ref: rllib/algorithms/pg/
    pg.py — the reference implements PG as the minimal policy-gradient
    loss; here that is A2C with the critic's loss weight zeroed and
    Monte-Carlo returns, the same reduction DDPGConfig makes over
    TD3)."""
    kw.setdefault("vf_loss_coeff", 0.0)
    kw.setdefault("lam", 1.0)
    return A2CConfig(**kw)


class A2CLearner:
    """One jitted grad-accumulate + apply per update()."""

    def __init__(self, obs_shape, num_actions: int, c: A2CConfig):
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        from .models import forward, init_policy_params

        self.params = init_policy_params(
            jax.random.PRNGKey(c.seed), obs_shape, num_actions,
            tuple(c.hidden))
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(c.max_grad_norm), optax.adam(c.lr))
        self.opt_state = self.optimizer.init(self.params)

        def loss_fn(params, batch, total_n):
            """Weighted-SUM losses over one slice, divided by the WHOLE
            batch size: summing slice grads then equals the whole-batch
            mean gradient exactly, pads (weight 0) contribute nothing,
            and microbatch_size is a pure memory knob — advantages are
            normalized once in update(), not per slice."""
            logits, values = forward(params, batch[sb.OBS])
            w = batch["_w"]
            logp_all = jax.nn.log_softmax(logits)
            logp = jnp.take_along_axis(
                logp_all, batch[sb.ACTIONS][:, None], axis=1)[:, 0]
            adv = jax.lax.stop_gradient(batch[sb.ADVANTAGES])
            # score-function gradient: advantage is a constant multiplier
            policy_loss = -jnp.sum(w * logp * adv) / total_n
            vf_loss = jnp.sum(
                w * (values - batch[sb.RETURNS]) ** 2) / total_n
            entropy = jnp.sum(
                -w * jnp.sum(jnp.exp(logp_all) * logp_all, axis=1)
            ) / total_n
            loss = (policy_loss + c.vf_loss_coeff * vf_loss
                    - c.entropy_coeff * entropy)
            return loss, {"policy_loss": policy_loss, "vf_loss": vf_loss,
                          "entropy": entropy}

        @functools.partial(jax.jit, donate_argnums=(0, 1),
                           static_argnums=(3,))
        def update(params, opt_state, batch, total_n):
            # batch arrives [n_micro, mb, ...]; slice grads SUM to the
            # whole-batch mean gradient (see loss_fn)
            def body(acc, mb):
                (loss, stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb, total_n)
                acc_g, acc_s = acc
                return (jax.tree.map(jnp.add, acc_g, grads),
                        jax.tree.map(jnp.add, acc_s,
                                     {**stats, "loss": loss})), None

            zero_g = jax.tree.map(jnp.zeros_like, params)
            zero_s = jax.tree.map(
                jnp.asarray, {"policy_loss": 0.0, "vf_loss": 0.0,
                              "entropy": 0.0, "loss": 0.0})
            (grads, stats), _ = jax.lax.scan(body, (zero_g, zero_s), batch)
            updates, opt_state = self.optimizer.update(grads, opt_state,
                                                       params)
            return optax.apply_updates(params, updates), opt_state, stats

        self._update = update
        self._micro = c.microbatch_size

    _LOSS_KEYS = (sb.OBS, sb.ACTIONS, sb.ADVANTAGES, sb.RETURNS)

    def update(self, batch: sb.Batch) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        n = len(batch[sb.OBS])
        if n == 0:
            return {}
        mb = min(self._micro or n, n)
        n_micro = -(-n // mb)  # ceil: the tail rides padded, masked out
        padded = n_micro * mb
        adv = batch[sb.ADVANTAGES].astype(np.float32)
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)  # whole-batch, once
        cols = {**{k: batch[k] for k in self._LOSS_KEYS},
                sb.ADVANTAGES: adv,
                "_w": np.ones(n, np.float32)}
        jb = {}
        for k, v in cols.items():
            if padded != n:
                pad = np.zeros((padded - n, *v.shape[1:]), v.dtype)
                v = np.concatenate([v, pad])
            jb[k] = jnp.asarray(v).reshape(n_micro, mb, *v.shape[1:])
        self.params, self.opt_state, stats = self._update(
            self.params, self.opt_state, jb, n)
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def get_params(self) -> Dict:
        import jax

        return jax.device_get(self.params)


class A2C:
    """Tune-trainable synchronous A2C (same driver shape as PPO)."""

    def __init__(self, config: A2CConfig):
        from .connectors import NoFilter, make_connector

        self.config = c = config
        creator_blob = (cloudpickle.dumps(c.env_creator)
                        if c.env_creator else None)
        worker_cls = ray_tpu.remote(RolloutWorker)
        opts = worker_opts(c.worker_resources)
        self.workers: List = [
            worker_cls.options(**opts).remote(
                c.env, c.num_envs_per_worker, c.rollout_fragment_length,
                c.gamma, c.lam, seed=c.seed + 1000 * i,
                env_creator=creator_blob,
                observation_filter=c.observation_filter)
            for i in range(c.num_rollout_workers)
        ]
        info = ray_tpu.get(self.workers[0].env_info.remote(), timeout=180)
        self.obs_filter = make_connector(
            c.observation_filter, info.get("obs_shape", (info["obs_dim"],)))
        self._no_filter = isinstance(self.obs_filter, NoFilter)
        self.learner = A2CLearner(
            info.get("obs_shape", info["obs_dim"]), info["num_actions"], c)
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent: List[float] = []

    def train(self) -> Dict[str, Any]:
        from .connectors import merge_deltas

        t0 = time.monotonic()
        params_ref = ray_tpu.put(self.learner.get_params())
        batches = ray_tpu.get(
            [w.sample.remote(params_ref) for w in self.workers],
            timeout=300)
        sample_time = time.monotonic() - t0
        batch = sb.concat(batches)
        t1 = time.monotonic()
        stats = self.learner.update(batch)
        learn_time = time.monotonic() - t1
        if not self._no_filter:
            deltas = ray_tpu.get(
                [w.filter_delta.remote() for w in self.workers], timeout=60)
            state = merge_deltas(self.obs_filter, deltas)
            for w in self.workers:
                w.sync_filter.remote(state)
        for rets in ray_tpu.get(
                [w.episode_returns.remote() for w in self.workers],
                timeout=60):
            self._recent.extend(rets)
            self._total_episodes += len(rets)
        self._recent = self._recent[-100:]
        self._iteration += 1
        steps = sb.num_steps(batch)
        self._total_steps += steps
        return {
            "training_iteration": self._iteration,
            "timesteps_total": self._total_steps,
            "timesteps_this_iter": steps,
            "episode_reward_mean": (float(np.mean(self._recent))
                                    if self._recent else float("nan")),
            "episodes_total": self._total_episodes,
            "env_steps_per_sec": steps / max(1e-9,
                                             sample_time + learn_time),
            "sample_time_s": sample_time,
            "learn_time_s": learn_time,
            **stats,
        }

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        ckpt = {"params": jax.device_get(self.learner.params),
                "opt_state": jax.device_get(self.learner.opt_state),
                "iteration": self._iteration,
                "total_steps": self._total_steps}
        if not self._no_filter:
            ckpt["obs_filter"] = self.obs_filter.state()
        return ckpt

    def restore(self, ckpt: Dict) -> None:
        import jax
        import jax.numpy as jnp

        self.learner.params = jax.tree.map(jnp.asarray, ckpt["params"])
        if "opt_state" in ckpt:
            self.learner.opt_state = jax.tree.map(jnp.asarray,
                                                  ckpt["opt_state"])
        self._iteration = int(ckpt.get("iteration", 0))
        self._total_steps = int(ckpt.get("total_steps", 0))
        if "obs_filter" in ckpt and not self._no_filter:
            self.obs_filter.set_state(ckpt["obs_filter"])
            ray_tpu.get([w.sync_filter.remote(ckpt["obs_filter"])
                         for w in self.workers], timeout=60)

    def stop(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
