"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606): a layer's residual is n streams
X ∈ R^{n×d} a token, and around every sublayer F three maps made from the
token's own row u = vec(X) (stream-major, n·d wide) say how the streams
are read, written and mixed:

    ũ = g ⊙ u / sqrt(mean(u²) + rms_eps);  [p | q | r] = ũ Φ   (n, n, n² wide)
    H_pre  = σ(α_pre p + b_pre)             H_post = 2 σ(α_post q + b_post)
    A = clip(α_res r + b_res, lo, hi) as n × n;  M = exp(A)
    iters times: M ← M / (colsum M + eps); M ← M / (rowsum M + eps);  H_res = M
    z = Σ_j H_pre[j] X[j];  y = F(RMSNorm(z));  X'[i] = Σ_j H_res[i, j] X[j] + H_post[i] y

Layout. X is a tuple of its n streams, each an ordinary activation
[..., d] (stacked, the n mixed streams a sublayer writes would be copied
once more into the stack: 235 MB read and written again a sublayer at
8192 tokens of d 3584), and every coefficient is tokens-minor: ``H_pre``,
``H_post`` [n, tokens], ``H_res`` [n, n, tokens], float32. Written
[tokens, n, n] the 16 values a token would be padded to an (8, 128) tile,
64 times their bytes, through 2 x iters normalisations and their backward.
X stays in the compute dtype; the statistic, the coefficients, the
Sinkhorn iterations and the sums over streams are float32, and ũΦ runs on
the MXU (operands in X's dtype, float32 sums). Everything runs under the
scope ``mhc``; plain ``jax.numpy`` and autodiff: on a v5e one sublayer's
mixing at 8192 tokens takes 1.6 ms forward and 6.0 ms forward + backward,
48 % of what its bytes need (PERF.md, PR 45), so no kernel was written.

Parameters of one sublayer's set (``HC_PARAMS``): ``phi`` [n·d, 2n + n²],
``gain`` [n·d], ``bias`` [2n + n²], ``alpha`` [3] (pre, post, res).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

HC_PARAMS = ("phi", "gain", "bias", "alpha")
SCOPE = "mhc"


def hc_param_shapes(n: int, d: int) -> Dict[str, Tuple[int, ...]]:
    k = 2 * n + n * n
    return {"phi": (n * d, k), "gain": (n * d,), "bias": (k,), "alpha": (3,)}


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m [n, n, tokens] positive -> doubly stochastic a token: ``iters``
    times every column (axis 0 summed) and then every row (axis 1) is
    divided by its sum + eps. Rows sum to 1 / (1 + eps); columns as near
    as the iterations bring them."""
    def step(_, m):
        m = m / (m.sum(0, keepdims=True) + eps)
        return m / (m.sum(1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, step, m)


def hc_coefficients(x: Sequence[jax.Array], p: Dict[str, jax.Array], *,
                    iters: int, eps: float, clamp: Tuple[float, float],
                    rms_eps: float):
    """x: n streams [..., d]; p: one set of ``HC_PARAMS`` -> (H_pre
    [n, tokens], H_post [n, tokens], H_res [n, n, tokens]), float32,
    tokens the flattened leading axes."""
    n, d = len(x), x[0].shape[-1]
    with jax.named_scope(SCOPE):
        u = [xj.reshape(-1, d) for xj in x]
        f32 = jnp.float32
        ss = sum(jnp.sum(jnp.square(uj.astype(f32)), -1) for uj in u)
        inv = jax.lax.rsqrt(ss / (n * d) + rms_eps)              # [tokens]
        g_phi = (p["gain"].astype(f32)[:, None] * p["phi"].astype(f32)
                 ).astype(u[0].dtype).reshape(n, d, -1)
        raw = sum(jnp.einsum("dc,td->ct", g_phi[j], u[j],
                             preferred_element_type=f32)
                  for j in range(n)) * inv                       # [2n+n², t]
        alpha, bias = p["alpha"].astype(f32), p["bias"].astype(f32)[:, None]
        pre = jax.nn.sigmoid(alpha[0] * raw[:n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[n:2 * n] + bias[n:2 * n])
        a = jnp.clip(alpha[2] * raw[2 * n:] + bias[2 * n:], *clamp)
        res = sinkhorn(jnp.exp(a).reshape(n, n, -1), iters, eps)
        return pre, post, res


def _over_d(h: jax.Array, like: jax.Array) -> jax.Array:
    """A coefficient [tokens] against an activation [..., d] of those
    tokens."""
    return h.reshape(like.shape[:-1] + (1,))


def hc_pre(x: Sequence[jax.Array], h_pre: jax.Array) -> jax.Array:
    """n streams [..., d], H_pre [n, tokens] -> z = Σ_j H_pre[j] x[j]."""
    with jax.named_scope(SCOPE):
        z = sum(_over_d(h_pre[j], xj) * xj.astype(jnp.float32)
                for j, xj in enumerate(x))
        return z.astype(x[0].dtype)


def hc_post(x: Sequence[jax.Array], y: jax.Array, h_post: jax.Array,
            h_res: jax.Array) -> Tuple[jax.Array, ...]:
    """n streams [..., d] and the sublayer's y [..., d] -> the n streams
    X'[i] = Σ_j H_res[i, j] x[j] + H_post[i] y."""
    with jax.named_scope(SCOPE):
        xs = [xj.astype(jnp.float32) for xj in x]
        yf = y.astype(jnp.float32)
        return tuple(
            (sum(_over_d(h_res[i, j], y) * xj for j, xj in enumerate(xs))
             + _over_d(h_post[i], y) * yf).astype(y.dtype)
            for i in range(len(x)))
