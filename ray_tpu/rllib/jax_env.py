"""Device-resident vectorized environments (pure-jax, jit/vmap/scan-able).

The reference's rollout architecture — CPU envs feeding a GPU learner
over a NCCL/object-store hop (rllib/evaluation/rollout_worker.py:660,
env_runner_v2.py) — is a CUDA-era shape. On TPU the idiomatic design is
the Podracer/"Anakin" layout (DeepMind, arXiv:2104.06272; PureJaxRL):
the env itself is a pure jax function, so rollout, GAE and the SGD
update fuse into ONE compiled program on the chip. Observations never
cross the host boundary, which removes the pixel upload (28 KB/frame at
Atari scale) from every step.

A `JaxVectorEnv` is a bundle of pure functions over a batched state
pytree (leading dim = num_envs):

    state, obs = env.reset(key)
    state, obs, reward, done = env.step(state, actions)

Auto-reset on done matches the host `VectorEnv` contract
(ray_tpu/rllib/env.py): a done env's returned obs is the FIRST frame of
the new episode.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class JaxVectorEnv:
    """Protocol base. Subclasses define pure reset/step over a state
    pytree; num_envs is static (shapes are compile-time constants)."""

    obs_shape: Tuple[int, ...]
    num_actions: int
    num_envs: int

    def reset(self, key: jax.Array):
        raise NotImplementedError

    def step(self, state, actions: jax.Array):
        raise NotImplementedError

    def fold_key(self, state, idx):
        """Decorrelate per-shard env randomness under shard_map: the
        global reset replicates the state's PRNG key to every device, so
        without this fold each shard's auto-reset noise would be
        identical."""
        if isinstance(state, dict) and "key" in state:
            return {**state, "key": jax.random.fold_in(state["key"], idx)}
        return state


_JAX_ENVS: Dict[str, Callable[..., JaxVectorEnv]] = {}


def register_jax_env(name: str, creator: Callable[..., JaxVectorEnv]) -> None:
    _JAX_ENVS[name] = creator


def make_jax_env(name: str, num_envs: int = 8) -> JaxVectorEnv:
    if name not in _JAX_ENVS:
        raise KeyError(f"unknown jax env {name!r}; "
                       f"registered: {sorted(_JAX_ENVS)}")
    return _JAX_ENVS[name](num_envs=num_envs)


class CartPoleJax(JaxVectorEnv):
    """CartPole-v1 dynamics (Barto-Sutton-Anderson; same constants as the
    numpy CartPoleVecEnv in ray_tpu/rllib/env.py): +1 per step, done on
    |x|>2.4, |theta|>12deg, or 500 steps."""

    GRAVITY, MASSCART, MASSPOLE = 9.8, 1.0, 0.1
    LENGTH, FORCE_MAG, TAU = 0.5, 10.0, 0.02
    X_LIMIT, THETA_LIMIT, MAX_STEPS = 2.4, 12 * 2 * np.pi / 360, 500

    obs_shape = (4,)
    num_actions = 2

    def __init__(self, num_envs: int = 8):
        self.num_envs = num_envs

    def _spawn(self, key: jax.Array, n: int) -> jax.Array:
        return jax.random.uniform(key, (n, 4), jnp.float32, -0.05, 0.05)

    def reset(self, key: jax.Array):
        key, sk = jax.random.split(key)
        x = self._spawn(sk, self.num_envs)
        state = {"x": x, "t": jnp.zeros(self.num_envs, jnp.int32),
                 "key": key}
        return state, x

    def step(self, state, actions: jax.Array):
        x, xd, th, thd = (state["x"][:, 0], state["x"][:, 1],
                          state["x"][:, 2], state["x"][:, 3])
        force = jnp.where(actions == 1, self.FORCE_MAG, -self.FORCE_MAG)
        total_m = self.MASSCART + self.MASSPOLE
        pml = self.MASSPOLE * self.LENGTH
        costh, sinth = jnp.cos(th), jnp.sin(th)
        temp = (force + pml * thd ** 2 * sinth) / total_m
        th_acc = (self.GRAVITY * sinth - costh * temp) / (
            self.LENGTH * (4.0 / 3.0 - self.MASSPOLE * costh ** 2 / total_m))
        x_acc = temp - pml * th_acc * costh / total_m
        x = x + self.TAU * xd
        xd = xd + self.TAU * x_acc
        th = th + self.TAU * thd
        thd = thd + self.TAU * th_acc
        t = state["t"] + 1
        done = ((jnp.abs(x) > self.X_LIMIT)
                | (jnp.abs(th) > self.THETA_LIMIT)
                | (t >= self.MAX_STEPS))
        new_x = jnp.stack([x, xd, th, thd], axis=1)
        key, sk = jax.random.split(state["key"])
        fresh = self._spawn(sk, x.shape[0])
        d = done[:, None]
        obs = jnp.where(d, fresh, new_x)
        state = {"x": obs, "t": jnp.where(done, 0, t), "key": key}
        return state, obs, jnp.ones(x.shape[0], jnp.float32), done


class BreakoutShapedJax(JaxVectorEnv):
    """The pixels env, device-resident: same game and constants as
    BreakoutShapedVecEnv (ray_tpu/rllib/preprocessors.py:145) with the
    WarpFrame + FrameStack(4) composition folded into the render — each
    84x84 output pixel samples the same nearest-neighbor source
    coordinate WarpFrameVec would, so the observation tensor matches the
    host pipeline's (84, 84, 4) uint8 shape and statistics.

    Ball drops from the top with horizontal drift, bounces off walls;
    the paddle must intercept: +1 per catch, 5 drops per episode.
    """

    H, W = 210, 160
    PADDLE_Y, PADDLE_HALF, BALL_HALF = 190, 8, 2
    PADDLE_SPEED, BALL_VY, DROPS = 6, 5, 5
    SIZE = 84
    # luma of the (200, 72, 72) sprite color after WarpFrameVec's
    # float->uint8 truncation
    LUMA = np.uint8(int(200 * 0.299 + 72 * 0.587 + 72 * 0.114))

    obs_shape = (84, 84, 4)
    num_actions = 4

    def __init__(self, num_envs: int = 8):
        self.num_envs = num_envs
        # nearest-neighbor source coordinates, identical to WarpFrameVec
        self._rows = jnp.asarray(
            np.linspace(0, self.H - 1, self.SIZE).round(), jnp.float32)
        self._cols = jnp.asarray(
            np.linspace(0, self.W - 1, self.SIZE).round(), jnp.float32)

    def _spawn(self, key: jax.Array, n: int):
        kx, kv = jax.random.split(key)
        bx = jax.random.uniform(kx, (n,), jnp.float32, 10.0, self.W - 10.0)
        bvx = jax.random.uniform(kv, (n,), jnp.float32, -3.0, 3.0)
        return bx, jnp.full((n,), 10.0, jnp.float32), bvx

    def _frame(self, bx, by, px) -> jax.Array:
        """One warped grayscale frame [n, 84, 84] uint8 from ball/paddle
        positions — the composition of _render + WarpFrameVec._warp,
        evaluated directly on the 84-grid."""
        bh, ph = float(self.BALL_HALF), float(self.PADDLE_HALF)
        bxi, byi, pxi = (jnp.floor(bx)[:, None], jnp.floor(by)[:, None],
                         jnp.floor(px)[:, None])
        r, c = self._rows[None, :], self._cols[None, :]
        ball_r = (r >= jnp.maximum(0.0, byi - bh)) & (r < byi + bh)
        ball_c = (c >= jnp.maximum(0.0, bxi - bh)) & (c < bxi + bh)
        pad_r = (r >= self.PADDLE_Y) & (r < self.PADDLE_Y + 4)
        pad_c = (c >= jnp.maximum(0.0, pxi - ph)) & (c < pxi + ph)
        mask = (ball_r[:, :, None] & ball_c[:, None, :]) \
            | (pad_r[:, :, None] & pad_c[:, None, :])
        return jnp.where(mask, self.LUMA, jnp.uint8(0))

    def reset(self, key: jax.Array):
        n = self.num_envs
        key, sk = jax.random.split(key)
        bx, by, bvx = self._spawn(sk, n)
        px = jnp.full((n,), self.W / 2.0, jnp.float32)
        frame = self._frame(bx, by, px)
        stack = jnp.repeat(frame[..., None], 4, axis=-1)
        state = {"bx": bx, "by": by, "bvx": bvx, "px": px,
                 "drops": jnp.full((n,), self.DROPS, jnp.int32),
                 "stack": stack, "key": key}
        return state, stack

    def step(self, state, actions: jax.Array):
        # local batch from the state, NOT self.num_envs: under shard_map
        # each device steps its own slice of the env batch
        n = state["bx"].shape[0]
        dx = jnp.where(actions == 2, float(self.PADDLE_SPEED),
                       jnp.where(actions == 3, -float(self.PADDLE_SPEED),
                                 0.0))
        px = jnp.clip(state["px"] + dx, self.PADDLE_HALF,
                      self.W - self.PADDLE_HALF)
        bx = state["bx"] + state["bvx"]
        bounce = (bx < self.BALL_HALF) | (bx > self.W - self.BALL_HALF)
        bvx = jnp.where(bounce, -state["bvx"], state["bvx"])
        bx = jnp.clip(bx, self.BALL_HALF, self.W - self.BALL_HALF)
        by = state["by"] + self.BALL_VY
        landed = by >= self.PADDLE_Y
        caught = landed & (jnp.abs(bx - px)
                           <= self.PADDLE_HALF + self.BALL_HALF)
        reward = caught.astype(jnp.float32)
        drops = state["drops"] - landed.astype(jnp.int32)
        done = landed & (drops <= 0)
        drops = jnp.where(done, self.DROPS, drops)
        key, sk = jax.random.split(state["key"])
        sbx, sby, sbvx = self._spawn(sk, n)
        bx = jnp.where(landed, sbx, bx)
        by = jnp.where(landed, sby, by)
        bvx = jnp.where(landed, sbvx, bvx)
        px = jnp.where(done, self.W / 2.0, px)
        frame = self._frame(bx, by, px)
        # FrameStackVec semantics: rolling history, but a done env's
        # whole stack refills with the new episode's first frame
        rolled = jnp.concatenate([state["stack"][..., 1:],
                                  frame[..., None]], axis=-1)
        refilled = jnp.repeat(frame[..., None], 4, axis=-1)
        stack = jnp.where(done[:, None, None, None], refilled, rolled)
        new_state = {"bx": bx, "by": by, "bvx": bvx, "px": px,
                     "drops": drops, "stack": stack, "key": key}
        return new_state, stack, reward, done


register_jax_env("CartPole-v1", lambda num_envs=8: CartPoleJax(num_envs))
register_jax_env("BreakoutShaped-v0",
                 lambda num_envs=8: BreakoutShapedJax(num_envs))
