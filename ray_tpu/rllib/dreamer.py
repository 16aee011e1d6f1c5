"""Dreamer — model-based RL: learn a latent world model, train the
policy inside its imagination (Hafner et al., DreamerV3 2023).

ref: rllib/algorithms/dreamerv3/dreamerv3.py + torch/dreamerv3_torch_model
(RSSM with categorical latents, symlog heads, KL balancing with free
bits, imagination-trained actor-critic with percentile return
normalization). This is the "lite" shape of that recipe for vector
observations: GRU-deterministic + (K categoricals x C classes)
stochastic latent, symlog MSE for reconstruction/reward/value instead
of two-hot, REINFORCE actor on imagined lambda-returns.

House TPU shape: rollout actors run the RSSM policy as numpy (GRU +
posterior + actor samples — np_policy.py rationale, mirroring the
learner's jax cells bit-for-bit in structure), the driver keeps a
sequence replay (zero-initialized latent per sequence: the posterior
re-syncs from observations within a few steps), and the ENTIRE
world-model + imagination actor-critic update block for all K sequence
minibatches runs as one jitted lax.scan dispatch per train() call
(the learner rule of this package)."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import cloudpickle
import numpy as np

import ray_tpu

from .replay_buffer import ReplayBuffer
from .rollout_worker import EnvWorkerBase, worker_opts


# ---------------------------------------------------------------------------
# symlog + parameter init
# ---------------------------------------------------------------------------


def symlog_np(x):
    return np.sign(x) * np.log1p(np.abs(x))


def _dense(rng, shapes: Dict[str, tuple]) -> Dict:
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(rng, len(shapes))
    out = {}
    for k_rng, (name, shp) in zip(ks, sorted(shapes.items())):
        if name.endswith("_b"):
            out[name] = jnp.zeros(shp, jnp.float32)
        else:
            out[name] = jax.random.normal(k_rng, shp, jnp.float32) \
                * np.sqrt(2.0 / shp[0])
    return out


def init_dreamer_params(rng, obs_dim: int, num_actions: int, *,
                        deter: int, n_cat: int, n_cls: int,
                        hidden: int) -> Dict:
    import jax

    z_dim = n_cat * n_cls
    ks = jax.random.split(rng, 8)
    p = {}
    # encoder obs -> emb
    p.update({f"enc_{k}": v for k, v in _dense(ks[0], {
        "w0": (obs_dim, hidden), "w0_b": (hidden,),
        "w1": (hidden, hidden), "w1_b": (hidden,)}).items()})
    # GRU: x = [z, a_onehot] -> 3*deter gates
    p.update({f"gru_{k}": v for k, v in _dense(ks[1], {
        "wx": (z_dim + num_actions, 3 * deter),
        "wh": (deter, 3 * deter), "wx_b": (3 * deter,)}).items()})
    # prior h -> z logits ; posterior [h, emb] -> z logits
    p.update({f"prior_{k}": v for k, v in _dense(ks[2], {
        "w0": (deter, hidden), "w0_b": (hidden,),
        "w1": (hidden, z_dim), "w1_b": (z_dim,)}).items()})
    p.update({f"post_{k}": v for k, v in _dense(ks[3], {
        "w0": (deter + hidden, hidden), "w0_b": (hidden,),
        "w1": (hidden, z_dim), "w1_b": (z_dim,)}).items()})
    # decoder / reward / continue heads on [h, z]
    s_dim = deter + z_dim
    p.update({f"dec_{k}": v for k, v in _dense(ks[4], {
        "w0": (s_dim, hidden), "w0_b": (hidden,),
        "w1": (hidden, obs_dim), "w1_b": (obs_dim,)}).items()})
    # reward/continue condition on (state, action): "taking a at s
    # yields r and ends/continues the episode". This sidesteps the
    # terminal-state problem entirely — auto-reset envs never hand the
    # terminal observation out, so a state-only cont head would be
    # trained on post-reset states instead (which taught the model that
    # FRESH states terminate — the round-5 probe's failure mode)
    p.update({f"rew_{k}": v for k, v in _dense(ks[5], {
        "w0": (s_dim + num_actions, hidden), "w0_b": (hidden,),
        "w1": (hidden, 1), "w1_b": (1,)}).items()})
    p.update({f"cont_{k}": v for k, v in _dense(ks[6], {
        "w0": (s_dim + num_actions, hidden), "w0_b": (hidden,),
        "w1": (hidden, 1), "w1_b": (1,)}).items()})
    return p


def init_ac_params(rng, deter: int, z_dim: int, num_actions: int,
                   hidden: int) -> Dict:
    import jax

    s_dim = deter + z_dim
    ks = jax.random.split(rng, 2)
    p = {}
    p.update({f"actor_{k}": v for k, v in _dense(ks[0], {
        "w0": (s_dim, hidden), "w0_b": (hidden,),
        "w1": (hidden, num_actions), "w1_b": (num_actions,)}).items()})
    # small-init the value head so early returns don't swing the actor
    ac = _dense(ks[1], {"w0": (s_dim, hidden), "w0_b": (hidden,),
                        "w1": (hidden, 1), "w1_b": (1,)})
    ac["w1"] = ac["w1"] * 0.01
    p.update({f"critic_{k}": v for k, v in ac.items()})
    return p


# ---------------------------------------------------------------------------
# numpy inference (rollout side) — mirrors the jax cells in the learner
# ---------------------------------------------------------------------------


def _np_mlp2(p, prefix, x, act_last=False):
    h = np.maximum(x @ p[f"{prefix}_w0"] + p[f"{prefix}_w0_b"], 0.0)
    out = h @ p[f"{prefix}_w1"] + p[f"{prefix}_w1_b"]
    return np.maximum(out, 0.0) if act_last else out


def _np_gru(p, x, h):
    z = x @ p["gru_wx"] + h @ p["gru_wh"] + p["gru_wx_b"]
    G = h.shape[1]
    r = 1.0 / (1.0 + np.exp(-z[:, :G]))
    u = 1.0 / (1.0 + np.exp(-(z[:, G:2 * G] - 1.0)))  # update-gate bias
    c = np.tanh(z[:, 2 * G:] + (r - 1.0) * (h @ p["gru_wh"][:, 2 * G:]))
    return u * h + (1.0 - u) * c


def np_policy_step(p, ac, obs, h, z_prev, a_prev_onehot, rng, n_cat, n_cls,
                   greedy=False):
    """One rollout inference step -> (action, h, z). Mirrors the
    learner's cells; unimix 1% on the posterior like the learner."""
    x = np.concatenate([z_prev, a_prev_onehot], axis=1)
    h = _np_gru(p, x, h)
    emb = _np_mlp2(p, "enc", obs.astype(np.float32), act_last=True)
    logits = _np_mlp2(p, "post", np.concatenate([h, emb], axis=1))
    B = len(obs)
    logits = logits.reshape(B, n_cat, n_cls)
    ex = np.exp(logits - logits.max(axis=2, keepdims=True))
    probs = ex / ex.sum(axis=2, keepdims=True)
    probs = 0.99 * probs + 0.01 / n_cls
    # sample each categorical
    cdf = probs.cumsum(axis=2)
    u = rng.random((B, n_cat, 1))
    idx = (u > cdf).sum(axis=2)
    z = np.eye(n_cls, dtype=np.float32)[idx].reshape(B, -1)
    s = np.concatenate([h, z], axis=1)
    a_logits = _np_mlp2(ac, "actor", s)
    if greedy:
        a = a_logits.argmax(axis=1)
    else:
        ex = np.exp(a_logits - a_logits.max(axis=1, keepdims=True))
        ap = ex / ex.sum(axis=1, keepdims=True)
        cdf = ap.cumsum(axis=1)
        a = (rng.random((B, 1)) > cdf).sum(axis=1)
    return a.astype(np.int64), h, z


class DreamerRolloutWorker(EnvWorkerBase):
    """Samples with the latent-state policy; emits fixed-length
    sequence windows (obs/actions/rewards/dones), zero-init latent per
    sequence on the learner side."""

    def __init__(self, env_name: str, num_envs: int, rollout_len: int,
                 seq_len: int, deter: int, n_cat: int, n_cls: int,
                 seed: int = 0, env_creator=None):
        super().__init__(env_name, num_envs, rollout_len, seed,
                         env_creator)
        if rollout_len % seq_len != 0:
            raise ValueError("rollout_len must be a multiple of seq_len")
        self.seq_len = seq_len
        self.n_cat, self.n_cls = n_cat, n_cls
        n = self.env.num_envs
        self._h = np.zeros((n, deter), np.float32)
        self._z = np.zeros((n, n_cat * n_cls), np.float32)
        self._a_prev = np.zeros((n, self.env.num_actions), np.float32)

    def sample(self, wm_params: Dict, ac_params: Dict) -> Dict:
        p = {k: np.asarray(v, np.float32) for k, v in wm_params.items()}
        ac = {k: np.asarray(v, np.float32) for k, v in ac_params.items()}
        T, L = self.rollout_len, self.seq_len
        n, A = self.env.num_envs, self.env.num_actions
        obs_buf = np.empty((T, n, self.env.obs_dim), np.float32)
        act_buf = np.empty((T, n), np.int64)
        rew_buf = np.empty((T, n), np.float32)
        done_buf = np.empty((T, n), np.bool_)
        obs = self._obs
        eye = np.eye(A, dtype=np.float32)
        for t in range(T):
            a, self._h, self._z = np_policy_step(
                p, ac, obs, self._h, self._z, self._a_prev, self._rng,
                self.n_cat, self.n_cls)
            obs_buf[t], act_buf[t] = obs, a
            self._a_prev = eye[a]
            obs, reward, done, info = self.env.step(a)
            rew_buf[t], done_buf[t] = reward, done
            self._track_returns(reward, done)
            if done.any():
                idx = np.nonzero(done)[0]
                self._h[idx] = 0.0
                self._z[idx] = 0.0
                self._a_prev[idx] = 0.0
                if "truncated" in info:
                    # model learns continue-probability: time-limit
                    # truncation is not a terminal (cont stays 1)
                    done_buf[t] &= ~info["truncated"]
        self._obs = obs
        n_win = T // L

        def rows(a):
            w = np.stack([a[i * L:(i + 1) * L] for i in range(n_win)])
            return np.swapaxes(w, 1, 2).reshape(n_win * n, L,
                                                *a.shape[2:])

        return {"obs": rows(obs_buf), "actions": rows(act_buf),
                "rewards": rows(rew_buf), "dones": rows(done_buf)}


@dataclass
class DreamerConfig:
    """ref: dreamerv3.py DreamerV3Config (model_size ladder, horizon 15,
    kl balancing 0.5/0.1, free bits 1.0, unimix 0.01)."""
    env: str = "CartPole-v1"
    env_creator: Optional[Callable] = None
    num_rollout_workers: int = 1
    num_envs_per_worker: int = 8
    rollout_fragment_length: int = 64
    seq_len: int = 16
    deter: int = 128
    n_cat: int = 8
    n_cls: int = 8
    hidden: int = 128
    gamma: float = 0.997
    lam: float = 0.95
    horizon: int = 15
    wm_lr: float = 3e-4
    ac_lr: float = 1e-4
    free_bits: float = 1.0
    kl_dyn_scale: float = 0.5
    kl_rep_scale: float = 0.1
    entropy_coeff: float = 3e-3
    buffer_size: int = 4_000       # sequences
    train_batch_size: int = 16     # sequences per minibatch
    num_updates_per_iter: int = 4
    learning_starts: int = 100     # sequences
    seed: int = 0
    checkpoint_replay_buffer: bool = True
    worker_resources: Dict[str, float] = field(default_factory=dict)

    def build(self) -> "Dreamer":
        return Dreamer(self)


class DreamerLearner:
    """World-model + imagination actor-critic, fused per-iteration."""

    def __init__(self, obs_dim: int, num_actions: int, c: DreamerConfig):
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        self.c = c
        z_dim = c.n_cat * c.n_cls
        self.wm = init_dreamer_params(
            jax.random.PRNGKey(c.seed), obs_dim, num_actions,
            deter=c.deter, n_cat=c.n_cat, n_cls=c.n_cls, hidden=c.hidden)
        self.ac = init_ac_params(jax.random.PRNGKey(c.seed + 1), c.deter,
                                 z_dim, num_actions, c.hidden)
        self.opt_wm = optax.chain(optax.clip_by_global_norm(100.0),
                                  optax.adam(c.wm_lr))
        self.opt_ac = optax.chain(optax.clip_by_global_norm(10.0),
                                  optax.adam(c.ac_lr))
        self.s_wm = self.opt_wm.init(self.wm)
        self.s_ac = self.opt_ac.init(self.ac)
        self._key = jax.random.PRNGKey(c.seed + 2)
        self.num_updates = 0
        A = num_actions

        def mlp2(p, prefix, x, act_last=False):
            h = jax.nn.relu(x @ p[f"{prefix}_w0"] + p[f"{prefix}_w0_b"])
            out = h @ p[f"{prefix}_w1"] + p[f"{prefix}_w1_b"]
            return jax.nn.relu(out) if act_last else out

        def gru(p, x, h):
            zg = x @ p["gru_wx"] + h @ p["gru_wh"] + p["gru_wx_b"]
            G = h.shape[1]
            r = jax.nn.sigmoid(zg[:, :G])
            u = jax.nn.sigmoid(zg[:, G:2 * G] - 1.0)
            cand = jnp.tanh(zg[:, 2 * G:]
                            + (r - 1.0) * (h @ p["gru_wh"][:, 2 * G:]))
            return u * h + (1.0 - u) * cand

        def symlog(x):
            return jnp.sign(x) * jnp.log1p(jnp.abs(x))

        def symexp(x):
            return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)

        def bounded(x, lim):
            """Smooth clamp in symlog space — the lite stand-in for the
            reference's bounded two-hot bins: an exploited model can
            hallucinate at most symexp(lim) per step, which is what kept
            the un-clamped probe's imagined returns from 2e7 blowups."""
            return lim * jnp.tanh(x / lim)

        def rew_out(p, sa):
            return symexp(bounded(mlp2(p, "rew", sa)[..., 0], 5.0))

        def val_out(p, s):
            return symexp(bounded(mlp2(p, "critic", s)[..., 0], 7.0))

        def z_dist(logits):
            lg = logits.reshape(*logits.shape[:-1], c.n_cat, c.n_cls)
            probs = 0.99 * jax.nn.softmax(lg) + 0.01 / c.n_cls
            return jnp.log(probs)

        def sample_z(key, logp):
            idx = jax.random.categorical(key, logp)
            one = jax.nn.one_hot(idx, c.n_cls)
            probs = jnp.exp(logp)
            st = one + probs - jax.lax.stop_gradient(probs)  # ST grads
            return st.reshape(*st.shape[:-2], z_dim)

        def kl_cat(lp, lq):
            """KL(p || q) summed over categoricals."""
            return (jnp.exp(lp) * (lp - lq)).sum(-1).sum(-1)

        def wm_loss(wm, batch, key):
            obs = batch["obs"]                      # [B, L, obs]
            acts = jax.nn.one_hot(batch["actions"], A)  # [B, L, A]
            d = batch["dones"].astype(jnp.float32)  # [B, L]
            B, L = d.shape
            emb = mlp2(wm, "enc", obs, act_last=True)
            a_prev = jnp.concatenate(
                [jnp.zeros((B, 1, A)), acts[:, :-1]], axis=1)
            resets = jnp.concatenate(
                [jnp.zeros((B, 1)), d[:, :-1]], axis=1)
            keys = jax.random.split(key, L)

            def step(carry, xs):
                h, z = carry
                emb_t, a_t, reset_t, k = xs
                keep = (1.0 - reset_t)[:, None]
                h, z = h * keep, z * keep
                a_t = a_t * keep
                h = gru(wm, jnp.concatenate([z, a_t], axis=1), h)
                prior_lp = z_dist(mlp2(wm, "prior", h))
                post_lp = z_dist(mlp2(
                    wm, "post", jnp.concatenate([h, emb_t], axis=1)))
                z = sample_z(k, post_lp)
                return (h, z), (h, z, prior_lp, post_lp)

            h0 = jnp.zeros((B, c.deter))
            z0 = jnp.zeros((B, z_dim))
            _, (hs, zs, prior_lp, post_lp) = jax.lax.scan(
                step, (h0, z0),
                (emb.swapaxes(0, 1), a_prev.swapaxes(0, 1),
                 resets.swapaxes(0, 1), keys))
            # [L, B, ...] -> [B, L, ...]
            hs, zs = hs.swapaxes(0, 1), zs.swapaxes(0, 1)
            prior_lp = prior_lp.swapaxes(0, 1)
            post_lp = post_lp.swapaxes(0, 1)
            s = jnp.concatenate([hs, zs], axis=-1)
            recon = mlp2(wm, "dec", s)
            l_rec = jnp.mean((recon - symlog(obs)) ** 2)
            # reward/continue heads on (s_t, a_t): r_t and 1-d_t for
            # EVERY step — no terminal-obs needed (see init note)
            sa = jnp.concatenate([s, acts], axis=-1)
            rew_pred = bounded(mlp2(wm, "rew", sa)[..., 0], 5.0)
            l_rew = jnp.mean((rew_pred
                              - symlog(batch["rewards"])) ** 2)
            cont_logit = mlp2(wm, "cont", sa)[..., 0]
            cont_tgt = 1.0 - d
            l_cont = jnp.mean(optax.sigmoid_binary_cross_entropy(
                cont_logit, cont_tgt))
            # KL balancing with free bits (ref dreamerv3 kl_dyn/kl_rep)
            kl_dyn = kl_cat(jax.lax.stop_gradient(post_lp), prior_lp)
            kl_rep = kl_cat(post_lp, jax.lax.stop_gradient(prior_lp))
            l_kl = (c.kl_dyn_scale * jnp.maximum(kl_dyn, c.free_bits)
                    + c.kl_rep_scale
                    * jnp.maximum(kl_rep, c.free_bits)).mean()
            loss = l_rec + l_rew + l_cont + l_kl
            stats = {"wm_loss": loss, "recon_loss": l_rec,
                     "reward_loss": l_rew, "kl": kl_dyn.mean()}
            # flattened posterior states seed imagination
            return loss, (jax.lax.stop_gradient(
                s.reshape(B * L, -1)), stats)

        def imagine(wm, ac, s0, key):
            """Roll the actor through the model: returns imagined
            states [H+1, N, s], actions [H, N], rewards/conts [H, N]."""
            def step(carry, k):
                s = carry
                a_logits = mlp2(ac, "actor", s)
                a = jax.random.categorical(k, a_logits)
                a_one = jax.nn.one_hot(a, A)
                sa = jnp.concatenate([s, a_one], axis=1)
                r = rew_out(wm, sa)
                cont = jax.nn.sigmoid(mlp2(wm, "cont", sa)[:, 0])
                h, z = s[:, :c.deter], s[:, c.deter:]
                h = gru(wm, jnp.concatenate([z, a_one], axis=1), h)
                k2 = jax.random.fold_in(k, 1)
                z = sample_z(k2, z_dist(mlp2(wm, "prior", h)))
                s_next = jnp.concatenate([h, z], axis=1)
                return s_next, (s_next, a, a_logits, r, cont)

            keys = jax.random.split(key, c.horizon)
            _, (ss, a_s, alog, rs, conts) = jax.lax.scan(step, s0, keys)
            return ss, a_s, alog, rs, conts

        def ac_loss(ac, wm, s0, key):
            ss, a_s, alog, rs, conts = imagine(wm, ac, s0, key)
            # full state sequence INCLUDING the replay-posterior start:
            # s_0..s_H, so the baseline for the action taken at s_t is
            # v(s_t) and the bootstrap for step t is v(s_{t+1})
            ss_full = jnp.concatenate([s0[None], ss], axis=0)  # [H+1,N,s]
            vs = val_out(ac, ss_full)                 # v(s_0)..v(s_H)
            disc = c.gamma * conts
            # lambda-returns, backward: R_t = r_t + d_t((1-lam)v_{t+1}
            #                                           + lam R_{t+1})
            def lam_step(nxt, xs):
                r, dsc, v = xs
                ret = r + dsc * ((1 - c.lam) * v + c.lam * nxt)
                return ret, ret

            _, rets = jax.lax.scan(
                lam_step, vs[-1],
                (rs[::-1], disc[::-1], vs[1:][::-1]))
            rets = rets[::-1]                         # R_0..R_{H-1}
            base = vs[:-1]                            # v(s_0)..v(s_{H-1})
            # percentile return normalization, per update (ref
            # dreamerv3: scale = max(1, P95 - P5) of the return batch)
            scale = jnp.maximum(
                1.0, jnp.percentile(rets, 95) - jnp.percentile(rets, 5))
            adv = jax.lax.stop_gradient((rets - base) / scale)
            logp = jax.nn.log_softmax(alog)
            lp_a = jnp.take_along_axis(
                logp, a_s[..., None], axis=-1)[..., 0]
            # discounted weights so early imagined steps dominate
            w = jnp.cumprod(
                jnp.concatenate([jnp.ones((1,) + disc.shape[1:]),
                                 disc[:-1]], axis=0), axis=0)
            w = jax.lax.stop_gradient(w)
            ent = -(jnp.exp(logp) * logp).sum(-1)
            actor_loss = -(w * (lp_a * adv
                                + c.entropy_coeff * ent)).mean()
            v_pred = bounded(mlp2(ac, "critic", ss_full[:-1])[..., 0],
                             7.0)
            critic_loss = jnp.mean(
                w * (v_pred - jax.lax.stop_gradient(
                    symlog(rets))) ** 2)
            loss = actor_loss + critic_loss
            return loss, {"actor_loss": actor_loss,
                          "critic_loss": critic_loss,
                          "imag_return": rets.mean(),
                          "entropy": ent.mean()}

        def one_update(carry, xs):
            wm, ac, s_wm, s_ac, key = carry
            batch = xs
            key, k1, k2 = jax.random.split(key, 3)
            (wl, (s0, wm_stats)), wg = jax.value_and_grad(
                wm_loss, has_aux=True)(wm, batch, k1)
            up, s_wm = self.opt_wm.update(wg, s_wm, wm)
            wm = optax.apply_updates(wm, up)
            (al, ac_stats), ag = jax.value_and_grad(
                ac_loss, has_aux=True)(ac, wm, s0, k2)
            up, s_ac = self.opt_ac.update(ag, s_ac, ac)
            ac = optax.apply_updates(ac, up)
            return (wm, ac, s_wm, s_ac, key), {**wm_stats, **ac_stats}

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def update_many(wm, ac, s_wm, s_ac, key, batches):
            (wm, ac, s_wm, s_ac, key), stats = jax.lax.scan(
                one_update, (wm, ac, s_wm, s_ac, key), batches)
            return wm, ac, s_wm, s_ac, key, jax.tree.map(jnp.mean, stats)

        self._update_many = update_many

    def update(self, stacked: Dict[str, np.ndarray]) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        K = stacked["rewards"].shape[0]
        batches = {k: jnp.asarray(v) for k, v in stacked.items()}
        (self.wm, self.ac, self.s_wm, self.s_ac, self._key,
         stats) = self._update_many(self.wm, self.ac, self.s_wm,
                                    self.s_ac, self._key, batches)
        self.num_updates += K
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def params_np(self):
        import jax

        return jax.device_get(self.wm), jax.device_get(self.ac)


class Dreamer:
    """Tune-trainable Dreamer driver (DQN shape, sequence replay)."""

    def __init__(self, config: DreamerConfig):
        self.config = c = config
        creator_blob = (cloudpickle.dumps(c.env_creator)
                        if c.env_creator else None)
        cls = ray_tpu.remote(DreamerRolloutWorker)
        opts = worker_opts(c.worker_resources)
        self.workers: List = [
            cls.options(**opts).remote(
                c.env, c.num_envs_per_worker, c.rollout_fragment_length,
                c.seq_len, c.deter, c.n_cat, c.n_cls,
                seed=c.seed + 1000 * i, env_creator=creator_blob)
            for i in range(c.num_rollout_workers)]
        info = ray_tpu.get(self.workers[0].env_info.remote(), timeout=180)
        self.learner = DreamerLearner(info["obs_dim"],
                                      info["num_actions"], c)
        self.buffer = ReplayBuffer(c.buffer_size, seed=c.seed)
        self._iteration = 0
        self._total_steps = 0
        self._total_episodes = 0
        self._recent: List[float] = []

    def train(self) -> Dict[str, Any]:
        c = self.config
        t0 = time.monotonic()
        wm_np, ac_np = self.learner.params_np()
        wm_ref, ac_ref = ray_tpu.put(wm_np), ray_tpu.put(ac_np)
        batches = ray_tpu.get(
            [w.sample.remote(wm_ref, ac_ref) for w in self.workers],
            timeout=300)
        steps = 0
        for b in batches:
            self.buffer.add(b)
            steps += b["rewards"].shape[0] * c.seq_len
        self._total_steps += steps
        stats: Dict[str, float] = {}
        if len(self.buffer) >= c.learning_starts:
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
            "buffer_sequences": len(self.buffer),
            "time_this_iter_s": time.monotonic() - t0,
            **stats,
        }

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        L = self.learner
        ckpt = {"wm": jax.device_get(L.wm), "ac": jax.device_get(L.ac),
                "opt": jax.device_get((L.s_wm, L.s_ac)),
                "key": jax.device_get(L._key),
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
        L.wm = as_jnp(ckpt["wm"])
        L.ac = as_jnp(ckpt["ac"])
        if "opt" in ckpt:
            L.s_wm, L.s_ac = as_jnp(ckpt["opt"])
        if "key" in ckpt:
            L._key = jnp.asarray(ckpt["key"])
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
