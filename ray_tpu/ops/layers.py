"""Fused numeric layers.

Shaped so XLA fuses them into adjacent matmuls (elementwise chains ride the
epilogue/prologue of MXU ops — no hand kernels needed for these; Pallas is
reserved for attention where fusion can't happen automatically). All stats
in f32 even under bf16 params — the TPU mixed-precision recipe.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


def rope_cache(seq_len: int, head_dim: int,
               base: float = 10000.0) -> Tuple[jax.Array, jax.Array]:
    """Precompute rotary cos/sin tables: [seq_len, head_dim/2] each (f32)."""
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding. x: [B, S, H, D]; cos/sin: [S_max, D/2];
    positions: [B, S] overrides the default arange (decode steps)."""
    dtype = x.dtype
    if positions is not None:
        c = cos[positions]          # [B, S, D/2]
        s = sin[positions]
    else:
        c = cos[None, : x.shape[1]]  # [1, S, D/2]
        s = sin[None, : x.shape[1]]
    c = c[:, :, None, :]            # [B|1, S, 1, D/2]
    s = s[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rot = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return rot.astype(dtype)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ignore_index: int = -100,
                       z_loss: float = 0.0) -> jax.Array:
    """Token-mean cross entropy with optional z-loss (logit drift control,
    the PaLM trick). logits [..., V] f32-upcast; labels [...] int."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    mask = (labels != ignore_index).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def causal_conv1d(x: jax.Array, weight: jax.Array,
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution over the sequence: x [B, T, C], weight
    [K, C] (tap K-1 meets the token itself, tap 0 the one K-1 before it;
    positions before the sequence are zeros), bias [C] -> [B, T, C] in x's
    dtype, summed in f32. K shifted products in one pass: the padded copy
    stays in x's dtype and each shifted slice is widened where it is used
    (widened first, a bf16 x is written and read back as a padded f32 array:
    1.42 against 0.58 ms forward at [2, 4096, 4352] on a v5e, PR 36)."""
    taps, t = weight.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = sum(xp[:, k:k + t].astype(jnp.float32) * w[k] for k in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def gated_rmsnorm(y: jax.Array, gate: jax.Array, weight: jax.Array,
                  eps: float = 1e-6) -> jax.Array:
    """rmsnorm(y * silu(gate)) * weight over the last axis (Mamba-2's
    output norm, one group), statistics in f32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    return rmsnorm(gated.astype(y.dtype), weight, eps)
