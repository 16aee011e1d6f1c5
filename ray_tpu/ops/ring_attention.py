"""Ring attention — context/sequence parallelism over the `sp` mesh axis.

The reference has NO long-context machinery (SURVEY.md §5 "Long-context /
sequence parallelism: absent") — this is first-class new work for the TPU
build. Sequence is sharded over `sp`; each device keeps its Q shard
resident and K/V shards rotate around the ring via `ppermute` (lowered to
ICI neighbor exchanges by XLA), overlapping transfer with the block
attention compute. Online-softmax partials (out, logsumexp) merge across
steps, so the result is exact attention over the full sequence with
per-device memory O(S/n · S/n).

Call inside shard_map/pjit with q/k/v sharded as [B, S/sp, H, D] on the
`sp` axis. Differentiable (ppermute transposes to ppermute; XLA re-plans
the reverse ring).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _block_attn_lse(q, k, v, sm_scale: float, causal: bool):
    """Attention over one (q_shard, kv_shard) pair returning normalized out
    and per-row logsumexp. f32 stats. Shapes [B,S,H,D] -> ([B,S,H,D],
    [B,H,S])."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((rows + (sk - sq) >= cols)[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                   # [B,H,Q]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = out / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out, lse


def _merge(o1, lse1, o2, lse2):
    """Numerically-stable merge of two normalized attention partials."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)                                    # [B,H,Q]
    w2 = jnp.exp(lse2 - m)
    tot = jnp.maximum(w1 + w2, 1e-30)
    # [B,H,Q] -> [B,Q,H,1] broadcast against [B,Q,H,D]
    def bc(w):
        return w.transpose(0, 2, 1)[..., None]
    o = (o1 * bc(w1) + o2 * bc(w2)) / bc(tot)
    lse = m + jnp.log(tot)
    return o, lse


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp",
                   causal: bool = True,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Exact attention with sequence sharded on `axis_name`.

    q/k/v: local shards [B, S_local, H, D]. Must be invoked inside a
    shard_map/pjit body where `axis_name` is bound.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    n = jax.lax.psum(1, axis_name)  # static for a named mesh axis
    my = jax.lax.axis_index(axis_name)

    # Step 0: the diagonal block (our own K/V) — causal within the shard.
    out, lse = _block_attn_lse(q, k, v, sm_scale, causal=causal)
    perm = [(i, (i + 1) % n) for i in range(n)]
    dtype = q.dtype
    for r in range(1, n):
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        # After r rotations we hold the K/V shard of device (my - r) mod n.
        o_r, lse_r = _block_attn_lse(q, k, v, sm_scale, causal=False)
        if causal:
            # Wrapped shards ((my - r) < 0) are in our future: masked out by
            # sending their weight to zero in the merge.
            valid = (my >= r)
            lse_r = jnp.where(valid, lse_r, _NEG_INF)
        out, lse = _merge(out, lse, o_r, lse_r)
    return out.astype(dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "sp",
                           causal: bool = True,
                           sm_scale: Optional[float] = None):
    """Convenience wrapper: shard_map ring_attention over `mesh` with
    sequence on `axis_name`, batch on dp/fsdp, heads on tp."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(("dp", "fsdp"), axis_name, "tp", None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                          sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
