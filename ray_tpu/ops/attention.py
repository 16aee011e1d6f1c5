"""Reference (non-Pallas) attention — the correctness oracle.

Used by tests to validate the Pallas kernels and as the fallback path on
platforms without Mosaic. Pure jnp; XLA still fuses this well enough for
small models.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  sm_scale: Optional[float] = None,
                  bias: Optional[jax.Array] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Multi-head attention. Shapes: q [B, Sq, H, D], k/v [B, Skv, H, D]
    (supports Sq != Skv for ring-attention blocks). Returns [B, Sq, H, D].
    Computed in f32 regardless of input dtype (matches the kernel). With a
    ``window`` a causal query sees itself and the window - 1 keys before
    it."""
    orig_dtype = q.dtype
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        # offset aligns the diagonals when Sq != Skv (final-block semantics)
        mask = qi + (sk - sq) >= ki
        if window is not None:
            mask = mask & (qi + (sk - sq) - ki < window)
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(orig_dtype)
