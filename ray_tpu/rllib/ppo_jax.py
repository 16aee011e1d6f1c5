"""PPOJax — the whole PPO loop (rollout + GAE + SGD) as one compiled
TPU program over a device-resident env.

ref: rllib/algorithms/ppo/ppo.py training_step (sample -> learn) — but
where the reference moves every observation host->device per iteration,
here the env IS a jax function (ray_tpu.rllib.jax_env), so an entire
training iteration — T env steps x n envs, bootstrap, GAE, E epochs of
minibatch SGD — is a single XLA dispatch (the Podracer/"Anakin" layout,
arXiv:2104.06272). `iters_per_step` stacks several full PPO iterations
into one dispatch via lax.scan, amortizing host round-trips. The only
per-train() traffic is a PRNG key in and a stats pytree out.

Multi-chip: pass `mesh_axis="dp"` + a Mesh to shard envs across chips;
gradients pmean over ICI inside the same compiled program
(the LearnerGroup-DDP analog; ref: rllib/core/learner/learner_group.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from . import sample_batch as sb


def make_gae_fn(gamma: float, lam: float):
    """GAE over a [T, n] rollout as a reverse lax.scan (the jax analog of
    sample_batch.compute_gae)."""
    import jax
    import jax.numpy as jnp

    def gae(rewards, values, dones, last_values):
        def body(carry, xs):
            last_gae, next_value = carry
            reward, value, done = xs
            not_done = 1.0 - done.astype(jnp.float32)
            delta = reward + gamma * next_value * not_done - value
            last_gae = delta + gamma * lam * not_done * last_gae
            return (last_gae, value), last_gae

        (_, _), adv = jax.lax.scan(
            body, (jnp.zeros_like(last_values), last_values),
            (rewards, values, dones), reverse=True)
        return adv, adv + values

    return gae


def make_train_step(env, optimizer, *, rollout_len: int, gamma: float,
                    lam: float, clip: float, vf_coeff: float,
                    ent_coeff: float, minibatch_size: int, num_epochs: int,
                    iters_per_step: int, mesh_axis: Optional[str] = None):
    """Build the pure (params, opt_state, env_state, obs, ep_ret, key) ->
    (params, opt_state, env_state, obs, ep_ret, key, stats) function.
    Everything inside is lax control flow: one trace, one executable."""
    import jax
    import jax.numpy as jnp

    from .learner import make_epoch_update_fn
    from .models import forward

    T = rollout_len
    gae = make_gae_fn(gamma, lam)
    epoch_update = make_epoch_update_fn(optimizer, clip, vf_coeff,
                                        ent_coeff, mesh_axis)

    def one_iter(carry, _):
        params, opt_state, env_state, obs, ep_ret, key = carry

        def rollout_body(c, _):
            env_state, obs, ep_ret, fin_sum, fin_cnt, key = c
            logits, value = forward(params, obs)
            key, sk = jax.random.split(key)
            actions = jax.random.categorical(sk, logits)
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                       actions[:, None], axis=1)[:, 0]
            env_state, next_obs, reward, done = env.step(env_state, actions)
            ep_ret = ep_ret + reward
            fin_sum = fin_sum + jnp.sum(jnp.where(done, ep_ret, 0.0))
            fin_cnt = fin_cnt + jnp.sum(done.astype(jnp.float32))
            ep_ret = jnp.where(done, 0.0, ep_ret)
            return ((env_state, next_obs, ep_ret, fin_sum, fin_cnt, key),
                    (obs, actions, logp, value, reward, done))

        n = obs.shape[0]
        init = (env_state, obs, ep_ret, jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32), key)
        (env_state, obs, ep_ret, fin_sum, fin_cnt, key), traj = \
            jax.lax.scan(rollout_body, init, None, length=T)
        obs_t, act_t, logp_t, val_t, rew_t, done_t = traj
        _, last_values = forward(params, obs)
        adv, ret = gae(rew_t, val_t, done_t, last_values)

        flat = lambda a: a.reshape((T * n,) + a.shape[2:])  # noqa: E731
        batch = {sb.OBS: flat(obs_t), sb.ACTIONS: flat(act_t),
                 sb.LOGP: flat(logp_t), sb.ADVANTAGES: flat(adv),
                 sb.RETURNS: flat(ret)}

        N = T * n
        mb = min(minibatch_size, N)
        n_mb = N // mb
        key, pk = jax.random.split(key)
        idx = jnp.concatenate(
            [jax.random.permutation(k, N)[:n_mb * mb].reshape(n_mb, mb)
             for k in jax.random.split(pk, num_epochs)], axis=0)
        params, opt_state, ustats = epoch_update(params, opt_state, batch,
                                                 idx)
        rps = jnp.mean(rew_t)
        if mesh_axis is not None:
            # episode bookkeeping is per-shard; fold it here so the
            # replicated out_specs carry true global numbers
            fin_sum = jax.lax.psum(fin_sum, mesh_axis)
            fin_cnt = jax.lax.psum(fin_cnt, mesh_axis)
            rps = jax.lax.pmean(rps, mesh_axis)
        stats = {**ustats, "episode_return_sum": fin_sum,
                 "episodes": fin_cnt, "reward_per_step": rps}
        return (params, opt_state, env_state, obs, ep_ret, key), stats

    def train_step(params, opt_state, env_state, obs, ep_ret, key):
        if mesh_axis is not None:
            # decorrelate sampling + env noise across shards
            idx = jax.lax.axis_index(mesh_axis)
            key = jax.random.fold_in(key, idx)
            env_state = env.fold_key(env_state, idx)
        carry = (params, opt_state, env_state, obs, ep_ret, key)
        carry, stats = jax.lax.scan(one_iter, carry, None,
                                    length=iters_per_step)
        return carry, stats

    return train_step


@dataclass
class PPOJaxConfig:
    """ref: ppo.py PPOConfig — subset that applies to the fused
    single-program design. `iters_per_step` PPO iterations run per
    train() dispatch."""
    env: str = "CartPole-v1"
    num_envs: int = 64
    rollout_len: int = 64
    iters_per_step: int = 4
    lr: float = 3e-4
    gamma: float = 0.99
    lam: float = 0.95
    clip_param: float = 0.2
    vf_loss_coeff: float = 0.5
    entropy_coeff: float = 0.01
    sgd_minibatch_size: int = 1024
    num_sgd_epochs: int = 1
    hidden: Tuple[int, ...] = (64, 64)
    max_grad_norm: float = 0.5
    seed: int = 0
    # optional multi-chip: name of the mesh axis to shard envs over
    mesh_axis: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    def build(self, mesh=None) -> "PPOJax":
        return PPOJax(self, mesh=mesh)


class PPOJax:
    """Tune-trainable fused PPO. Single-device by default; with
    `mesh` + `config.mesh_axis` the same program runs shard_map'd with
    envs split across the axis and gradients pmean'd over ICI."""

    def __init__(self, config: PPOJaxConfig, mesh=None):
        import jax
        import jax.numpy as jnp
        import optax

        from .jax_env import make_jax_env
        from .models import init_policy_params

        c = self.config = config
        self.env = make_jax_env(c.env, num_envs=c.num_envs)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(c.max_grad_norm), optax.adam(c.lr))
        obs_shape = (self.env.obs_shape if len(self.env.obs_shape) > 1
                     else int(self.env.obs_shape[0]))
        self.params = init_policy_params(
            jax.random.PRNGKey(c.seed), obs_shape, self.env.num_actions,
            tuple(c.hidden))
        self.opt_state = self.optimizer.init(self.params)

        key = jax.random.PRNGKey(c.seed + 1)
        key, rk = jax.random.split(key)
        self.env_state, self.obs = self.env.reset(rk)
        self.ep_ret = jnp.zeros(c.num_envs, jnp.float32)
        self.key = key

        step = make_train_step(
            self.env, self.optimizer, rollout_len=c.rollout_len,
            gamma=c.gamma, lam=c.lam, clip=c.clip_param,
            vf_coeff=c.vf_loss_coeff, ent_coeff=c.entropy_coeff,
            minibatch_size=c.sgd_minibatch_size,
            num_epochs=c.num_sgd_epochs,
            iters_per_step=c.iters_per_step, mesh_axis=c.mesh_axis)
        if mesh is not None and c.mesh_axis is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            if c.num_envs % mesh.shape[c.mesh_axis]:
                raise ValueError(
                    f"num_envs={c.num_envs} must divide the "
                    f"{c.mesh_axis!r} axis ({mesh.shape[c.mesh_axis]})")
            ax = c.mesh_axis
            rep, shd = P(), P(ax)
            # env state is a pytree mixing batched leaves (leading dim =
            # num_envs, shard those) and unbatched ones (the PRNG key —
            # replicate); derive the spec per leaf from the live state
            state_spec = jax.tree.map(
                lambda a: shd if (a.ndim and a.shape[0] == c.num_envs)
                else rep, self.env_state)
            step = shard_map(
                step, mesh=mesh,
                in_specs=(rep, rep, state_spec, shd, shd, rep),
                out_specs=((rep, rep, state_spec, shd, shd, rep), rep),
                check_vma=False)
        # obs may alias a buffer inside env_state (CartPole's state IS
        # its observation), so only the never-aliased args are donated
        self._step = jax.jit(step, donate_argnums=(0, 1, 4))
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent: list = []

    @property
    def steps_per_train(self) -> int:
        c = self.config
        return c.num_envs * c.rollout_len * c.iters_per_step

    def train(self) -> Dict[str, Any]:
        import jax

        t0 = time.monotonic()
        (self.params, self.opt_state, self.env_state, self.obs,
         self.ep_ret, self.key), stats = self._step(
            self.params, self.opt_state, self.env_state, self.obs,
            self.ep_ret, self.key)
        stats = jax.device_get(stats)  # forces the dispatch to finish
        dt = time.monotonic() - t0
        steps = self.steps_per_train
        self._iteration += 1
        self._total_steps += steps
        eps = float(stats["episodes"].sum())
        if eps > 0:
            self._recent.append(
                float(stats["episode_return_sum"].sum()) / eps)
            self._recent = self._recent[-100:]
            self._total_episodes += int(eps)
        out = {k: float(np.mean(v)) for k, v in stats.items()
               if k not in ("episode_return_sum", "episodes")}
        return {
            "training_iteration": self._iteration,
            "timesteps_total": self._total_steps,
            "timesteps_this_iter": steps,
            "episode_reward_mean": (float(np.mean(self._recent))
                                    if self._recent else float("nan")),
            "episodes_total": self._total_episodes,
            "env_steps_per_sec": steps / max(1e-9, dt),
            "train_time_s": dt,
            **out,
        }

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        return {"params": jax.device_get(self.params),
                "opt_state": jax.device_get(self.opt_state),
                "key": jax.device_get(self.key),
                "iteration": self._iteration,
                "total_steps": self._total_steps}

    def restore(self, ckpt: Dict) -> None:
        import jax
        import jax.numpy as jnp

        as_jnp = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        self.params = as_jnp(ckpt["params"])
        self.opt_state = as_jnp(ckpt["opt_state"])
        if "key" in ckpt:
            self.key = jnp.asarray(ckpt["key"])
        self._iteration = int(ckpt.get("iteration", 0))
        self._total_steps = int(ckpt.get("total_steps", 0))
        # env state restarts fresh: episodes in flight are not part of
        # the learning state (same stance as worker restart in PPO)

    def stop(self) -> None:
        pass
