"""PPO learner — jitted clipped-surrogate SGD, mesh-ready.

ref: rllib/algorithms/ppo/ppo_torch_policy.py loss;
rllib/core/learner/learner.py:229 (compute_gradients :558 /
apply_gradients :680 / update :1190). TPU-native shape: the whole
minibatch update is ONE jitted function with donated params/opt-state;
for multi-chip data-parallel learning, `make_update_fn(mesh_axis=...)`
inserts a psum over the mesh axis so the same code runs under
shard_map/pjit on a Mesh (the LearnerGroup-DDP analog over ICI).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import sample_batch as sb
from .models import forward, init_policy_params


def ppo_loss(params: Dict, batch: Dict, clip: float, vf_coeff: float,
             ent_coeff: float) -> Tuple[jax.Array, Dict]:
    logits, values = forward(params, batch[sb.OBS])
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(
        logp_all, batch[sb.ACTIONS][:, None], axis=1)[:, 0]
    ratio = jnp.exp(logp - batch[sb.LOGP])
    adv = batch[sb.ADVANTAGES]
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    surr = jnp.minimum(ratio * adv,
                       jnp.clip(ratio, 1 - clip, 1 + clip) * adv)
    policy_loss = -surr.mean()
    vf_loss = jnp.mean((values - batch[sb.RETURNS]) ** 2)
    entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=1))
    loss = policy_loss + vf_coeff * vf_loss - ent_coeff * entropy
    stats = {"policy_loss": policy_loss, "vf_loss": vf_loss,
             "entropy": entropy,
             "kl": jnp.mean(batch[sb.LOGP] - logp)}
    return loss, stats


def make_update_fn(optimizer, clip: float, vf_coeff: float, ent_coeff: float,
                   mesh_axis: Optional[str] = None):
    """One donated-buffer minibatch step; with mesh_axis set, gradients
    psum over the data-parallel mesh axis (XLA collective over ICI —
    the NCCL-allreduce replacement)."""

    def update(params, opt_state, batch):
        (loss, stats), grads = jax.value_and_grad(
            ppo_loss, has_aux=True)(params, batch, clip, vf_coeff, ent_coeff)
        if mesh_axis is not None:
            grads = jax.lax.pmean(grads, axis_name=mesh_axis)
            stats = jax.lax.pmean(stats, axis_name=mesh_axis)
            loss = jax.lax.pmean(loss, axis_name=mesh_axis)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, stats

    return update


def make_epoch_update_fn(optimizer, clip: float, vf_coeff: float,
                         ent_coeff: float, mesh_axis: Optional[str] = None):
    """The FULL epochs x minibatches SGD pass as one jitted lax.scan over a
    host-shuffled index matrix. One dispatch and one stats readback per
    `update()`: a host sync per minibatch would leave the device idle
    between every two updates."""
    step = make_update_fn(optimizer, clip, vf_coeff, ent_coeff, mesh_axis)

    def epoch_update(params, opt_state, batch, idx):
        # idx: [n_updates, minibatch] int32 gather indices into batch rows
        def body(carry, ix):
            params, opt_state = carry
            mb = jax.tree.map(lambda a: a[ix], batch)
            params, opt_state, loss, stats = step(params, opt_state, mb)
            return (params, opt_state), {**stats, "loss": loss}

        (params, opt_state), stats = jax.lax.scan(body, (params, opt_state),
                                                  idx)
        return params, opt_state, jax.tree.map(jnp.mean, stats)

    return epoch_update


class PPOLearner:
    """Single-process learner; LearnerGroup-style scale-out runs this under
    shard_map on a MeshGroup with mesh_axis="dp"."""

    def __init__(self, obs_dim, num_actions: int, *,
                 lr: float = 3e-4, clip: float = 0.2, vf_coeff: float = 0.5,
                 ent_coeff: float = 0.01, minibatch_size: int = 256,
                 num_epochs: int = 4, hidden=(64, 64), seed: int = 0,
                 max_grad_norm: float = 0.5):
        self.params = init_policy_params(jax.random.PRNGKey(seed), obs_dim,
                                         num_actions, tuple(hidden))
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(max_grad_norm), optax.adam(lr))
        self.opt_state = self.optimizer.init(self.params)
        self.minibatch_size = minibatch_size
        self.num_epochs = num_epochs
        self._seed = seed
        self._epoch_update = jax.jit(
            make_epoch_update_fn(self.optimizer, clip, vf_coeff, ent_coeff),
            donate_argnums=(0, 1))

    # only these batch columns feed the loss; uploading the rest would
    # waste host->device bandwidth
    _LOSS_KEYS = (sb.OBS, sb.ACTIONS, sb.LOGP, sb.ADVANTAGES, sb.RETURNS)

    def update(self, batch: sb.Batch) -> Dict[str, float]:
        n = len(batch[sb.OBS])
        if n == 0:
            return {}
        mb = min(self.minibatch_size, n)
        n_mb = n // mb
        rng = np.random.default_rng(self._seed)
        self._seed += 1
        idx = np.concatenate(
            [rng.permutation(n)[:n_mb * mb].reshape(n_mb, mb)
             for _ in range(self.num_epochs)], axis=0).astype(np.int32)
        jb = {k: jnp.asarray(batch[k]) for k in self._LOSS_KEYS}
        self.params, self.opt_state, stats = self._epoch_update(
            self.params, self.opt_state, jb, jnp.asarray(idx))
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def get_params(self) -> Dict:
        return jax.device_get(self.params)
