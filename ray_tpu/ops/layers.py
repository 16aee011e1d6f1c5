"""Fused numeric layers.

Shaped so XLA fuses them into adjacent matmuls (elementwise chains ride the
epilogue/prologue of MXU ops — no hand kernels needed for these; Pallas is
reserved for attention where fusion can't happen automatically). All stats
in f32 even under bf16 params — the TPU mixed-precision recipe.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..perf.recorder import record as _record


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6, *,
            plus_one: bool = False) -> jax.Array:
    """x / sqrt(mean(x^2) + eps) * weight over the last axis, statistics
    in f32; with ``plus_one`` the gain is ``1 + weight``, the weight drawn
    from zero (the ``qwen3_next`` form)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    return (y * (1.0 + w if plus_one else w)).astype(dtype)


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


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's m(k) = 0.1 k ln(factor) + 1 (1 at factor <= 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_rope_cache(seq_len: int, head_dim: int, base: float = 10000.0, *,
                    factor: float, original_max: int, beta_fast: float = 32.0,
                    beta_slow: float = 1.0, mscale: float = 1.0,
                    mscale_all_dim: float = 0.0
                    ) -> Tuple[jax.Array, jax.Array]:
    """``rope_cache`` under YaRN (``rope_scaling`` type ``yarn``, the
    DeepSeek-V3 convention): pair i of head_dim/2 turns by f'_i = f_i /
    factor * ramp_i + f_i (1 - ramp_i), f_i = base^(-2i/head_dim), ramp_i
    = clip((i - low) / (high - low), 0, 1) with low (high) the floor
    (ceil) of head_dim ln(original_max / (beta 2π)) / (2 ln base) at
    beta_fast (beta_slow), kept inside the table: the pairs that turn
    more than beta_fast times in ``original_max`` positions keep their
    frequency, those that turn less than beta_slow times are stretched by
    ``factor``. The blend holds at every length. cos and sin are scaled
    by m(mscale) / m(mscale_all_dim), m of ``yarn_mscale``. Factor 1 is
    ``rope_cache``."""
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))

    def pair(beta):
        return head_dim * math.log(original_max / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    angles = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32),
                       freqs / factor * ramp + freqs * (1.0 - ramp))
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def yarn_softmax_scale(qk_head_dim: int, factor: float,
                       mscale_all_dim: float) -> float:
    """The softmax scale of a YaRN-scaled attention: qk_head_dim^(-1/2)
    times m(mscale_all_dim)² (1 where that key is 0)."""
    m = yarn_mscale(factor, mscale_all_dim)
    return qk_head_dim ** -0.5 * m * m


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding. x: [B, S, H, D]; cos/sin: [S_max, R/2], R <= D
    the channels that turn: the first R of a head, channel i paired with
    i + R/2, the other D - R as they are (``partial_rotary_factor``; the
    tables' width says how many). positions: [B, S] overrides the default
    arange (decode steps)."""
    dtype = x.dtype
    rotary = 2 * cos.shape[-1]
    rest = None
    if rotary != x.shape[-1]:
        x, rest = x[..., :rotary], x[..., rotary:]
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
    rot = rot.astype(dtype)
    return rot if rest is None else jnp.concatenate([rot, rest], axis=-1)


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


def chunked_head_nll(head: jax.Array, x: jax.Array, targets: jax.Array,
                     num_chunks: int) -> jax.Array:
    """The head and the token-mean next-token loss walked in ``num_chunks``
    chunks of tokens, each under ``jax.checkpoint``: head [V, D] (already in
    the compute dtype), x [..., D], targets [...] -> the mean of logsumexp -
    gold over all tokens, float32. The float32 logits of one chunk exist at
    a time, forward and backward (a whole row's are tokens x V x 4 bytes and
    as much again for their cotangent). Scopes ``lm_head`` and ``loss``
    inside."""
    T = targets.size
    xt = x.reshape(T, -1)
    tg = targets.reshape(T)
    if T % num_chunks:
        raise ValueError(f"{T} tokens are not {num_chunks} whole chunks")
    xt = xt.reshape(num_chunks, T // num_chunks, -1)
    tg = tg.reshape(num_chunks, T // num_chunks)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_nll(carry, xt_tg):
        xc, tc = xt_tg
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("td,vd->tv", xc, head,
                                preferred_element_type=jnp.float32)
        with jax.named_scope("loss"):
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, tc[:, None], axis=-1)[:, 0]
            return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(chunk_nll, jnp.float32(0.0), (xt, tg))
    return total / T


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


def causal_conv1d_silu(x: jax.Array, weight: jax.Array,
                       bias: Optional[jax.Array] = None) -> jax.Array:
    """``jax.nn.silu(causal_conv1d(x, weight, bias))``, to the bit, with the
    gradient written by hand (``_conv_silu_bwd``): autodiff's backward of
    that expression is three fusions that write one full-size array a tap
    and read them all back, this one two fusions and one array (5.5 against
    7.5 ms at [2, 8192, 8192] bf16 on a v5e, PR 55). Which traced steps hold
    it is the flight-recorder event ``rtpu.ops.conv``, once a traced call."""
    _record("rtpu.ops.conv", "hand_vjp",
            {"tokens": x.shape[0] * x.shape[1], "channels": x.shape[2],
             "taps": weight.shape[0], "bias": bias is not None})
    return _conv_silu(x, weight, bias)


@jax.custom_vjp
def _conv_silu(x, weight, bias):
    return jax.nn.silu(causal_conv1d(x, weight, bias))


def _conv_silu_fwd(x, weight, bias):
    # no pre-activation is kept: the backward makes it again from x
    return _conv_silu(x, weight, bias), (x, weight, bias)


def _conv_silu_bwd(res, dy):
    """dpre = dy silu'(pre) in x's dtype (as autodiff rounds it) with the
    float32 sums for dw and db out of the same fusion, then dx as the
    transposed convolution in the forward's own form: dpre padded at the
    END, K shifted slices each widened where it is used, which the compiler
    fuses into one loop (a roll or a stack of shifted copies it does not).
    Every shifted slice is a read of the whole array from HBM, in any such
    form: reading it once is a kernel's to do."""
    x, weight, bias = res
    taps, t = weight.shape[0], x.shape[1]
    # in a rematerialised layer the forward stands beside this backward, and
    # the compiler would take its pre-activation for this one: written whole
    # by the forward's fusion, kept, and read back here (one array more of
    # temporaries). Behind the barrier the taps are not the forward's to its
    # eyes, so this one is made in the fusion that reads x for dw anyway,
    # and x and dy stay free to fuse with what makes them.
    weight, bias = jax.lax.optimization_barrier((weight, bias))
    w = weight.astype(jnp.float32)
    pre = causal_conv1d(x, weight, bias).astype(jnp.float32)
    s = jax.nn.sigmoid(pre)
    dpre = (dy.astype(jnp.float32) * s * (1.0 + pre * (1.0 - s))).astype(
        x.dtype)
    dpf = dpre.astype(jnp.float32)
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(xp[:, k:k + t].astype(jnp.float32) * dpf,
                            axis=(0, 1)) for k in range(taps)])
    db = None if bias is None else jnp.sum(dpf, axis=(0, 1)).astype(bias.dtype)
    dp = jnp.pad(dpre, ((0, 0), (0, taps - 1), (0, 0)))
    dx = sum(dp[:, taps - 1 - k:taps - 1 - k + t].astype(jnp.float32) * w[k]
             for k in range(taps))
    return dx.astype(x.dtype), dw.astype(weight.dtype), db


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def gated_rmsnorm(y: jax.Array, gate: jax.Array, weight: jax.Array,
                  eps: float = 1e-6) -> jax.Array:
    """rmsnorm(y * silu(gate)) * weight over the last axis (Mamba-2's
    output norm, one group), statistics in f32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    return rmsnorm(gated.astype(y.dtype), weight, eps)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum(x^2) + eps) over the last axis (one head's q or k of a
    delta-rule layer: a key of unit length is what makes ``beta`` the
    share of the held value that a write replaces), statistics in f32."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(
        jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + eps)).astype(x.dtype)


def rmsnorm_then_gate(y: jax.Array, gate: jax.Array, weight: jax.Array,
                      eps: float = 1e-6, *,
                      activation=jax.nn.sigmoid) -> jax.Array:
    """rmsnorm(y) * weight * activation(gate) over the last axis: the norm
    first, THEN the gate (``gated_rmsnorm`` gates by SiLU before the
    norm), statistics in f32. Kimi Delta Attention gates by the sigmoid,
    Gated DeltaNet by SiLU."""
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    normed = yf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return (normed * activation(gate.astype(jnp.float32))).astype(y.dtype)


def sigmoid_gated_rmsnorm(y: jax.Array, gate: jax.Array, weight: jax.Array,
                          eps: float = 1e-6) -> jax.Array:
    """``rmsnorm_then_gate`` under the sigmoid."""
    return rmsnorm_then_gate(y, gate, weight, eps)
