"""DeepSeek-V3 (``model_type: deepseek_v3``; DeepSeek-AI 2024, and the
``modeling_deepseek_v3.py`` the published checkpoints name) forward pass,
plain: ``jax.numpy`` only, no kernel, nothing of the program imported.

Per layer, x̂ = RMSNorm(x): multi-head latent attention without a query
bottleneck (``q_lora_rank`` null) — per head q = [q_nope | q_rope] = x̂ W_q,
[c | k_pe] = x̂ W_kva, [k_nope | v] = RMSNorm(c) W_kvb, RoPE on neighbouring
pairs (``rope_interleave``) of q_rope and of the one k_pe, the head's key
k = [k_nope | k_pe] written out 192 wide, softmax(q kᵀ / sqrt(192)) causal,
o = P v, out concat(o) W_o — then a gated SiLU MLP in the leading dense
layers, and in the others

    s = sigmoid(x̂ W_g) (float32); chosen = top k of s + b;
    w = s[chosen] / sum(s[chosen]) * routed_scaling_factor;
    y = x + shared(x̂) + sum over chosen e of w_e expert_e(x̂).

Reads the parameter dict of ``ray_tpu.models.deepseek_v3.DeepseekV3`` (the
published matrices cut by columns into ``w_q_nope`` / ``w_q_rope``,
``w_kv_a`` / ``w_k_rope``, ``w_k_b`` / ``w_v_b``; layers stacked under
``dense.`` and ``moe.``) and puts the columns back together.

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share: given ``experts_held`` and ``expert_offset`` the sum
  over chosen experts runs over the held ones only; what the absent
  experts would add is left out, and goes on missing into the next layer.
  The router, the top k and the normalisation are over all experts;
* ``n_group`` = ``topk_group`` = 1: group-limited routing is the identity
  and is not written;
* the vocabulary (a slice, for one chip of several) is padded to a
  multiple of 128 and the padded rows take part in the softmax.

Every expert held is computed for every token and weighted by zero where
it was not chosen: no sort, no buffer, no token can be dropped. Attention
runs one block of queries at a time so that S = 8192 fits in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 256


def _rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


def rope_pairs(x, base: float):
    """x [B, S, H, D]: the pair (x[2i], x[2i+1]) turned by the angle
    position * base^(-2i/D)."""
    b, s, h, d = x.shape
    theta = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)   # [D/2]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta[None]   # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(b, s, h, d).astype(x.dtype)


def attention(xn, lp, *, n_head, rope_base, eps):
    """x̂ [B, S, D] -> concat_h(o_h) W_o."""
    b, s, _ = xn.shape
    dr = lp["w_k_rope"].shape[1]
    per_head = lambda t: t.reshape(b, s, n_head, -1)  # noqa: E731
    c = _rmsnorm(xn @ lp["w_kv_a"], lp["kv_norm"], eps)
    k_pe = rope_pairs((xn @ lp["w_k_rope"])[:, :, None, :], rope_base)
    q = jnp.concatenate([per_head(xn @ lp["w_q_nope"]),
                         rope_pairs(per_head(xn @ lp["w_q_rope"]),
                                    rope_base)], -1)
    k = jnp.concatenate([per_head(c @ lp["w_k_b"]),
                         jnp.broadcast_to(k_pe, (b, s, n_head, dr))], -1)
    v = per_head(c @ lp["w_v_b"])
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    blk = min(Q_BLOCK, s)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1).astype(jnp.float32)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kf) * scale
        seen = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, -1).astype(xn.dtype)
    return o @ lp["w_o"]


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def shared_expert(xn, lp):
    return _gated(xn, lp["s_gate"], lp["s_up"], lp["s_down"])


def routed_experts(xn, lp, *, top_k, routed_scale, expert_offset=0):
    """x̂ [..., D] -> the part of sum_e w_e expert_e(x̂) that the experts
    in ``lp`` (those from ``expert_offset`` on) give."""
    s = jax.nn.sigmoid(jnp.einsum("...d,de->...e", xn, lp["w_router"],
                                  preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                              top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    w = picked / picked.sum(-1, keepdims=True) * routed_scale   # [..., k]

    def add_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e + expert_offset, w, 0.0), -1)
        return out + w_e[..., None] * _gated(xn, w_gate, w_up, w_down).astype(
            jnp.float32), None

    held = lp["e_gate"].shape[0]
    out, _ = jax.lax.scan(add_expert, jnp.zeros(xn.shape, jnp.float32),
                          (jnp.arange(held), lp["e_gate"], lp["e_up"],
                           lp["e_down"]))
    return out.astype(xn.dtype)


def hidden(params: dict, tokens: jax.Array, dtype, *, n_head, rope_base,
           eps, top_k, routed_scale, expert_offset) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    attn = dict(n_head=n_head, rope_base=rope_base, eps=eps)

    def layers(kind):
        stacked = {k.split(".", 1)[1]: v for k, v in p.items()
                   if k.startswith(kind + ".")}
        n = next(iter(stacked.values())).shape[0]
        return [{k: v[i] for k, v in stacked.items()} for i in range(n)]

    for lp in layers("dense"):
        x = x + attention(_rmsnorm(x, lp["attn_norm"], eps), lp, **attn)
        x = x + _gated(_rmsnorm(x, lp["mlp_norm"], eps), lp["w_gate"],
                       lp["w_up"], lp["w_down"])
    for lp in layers("moe"):
        x = x + attention(_rmsnorm(x, lp["attn_norm"], eps), lp, **attn)
        xn = _rmsnorm(x, lp["mlp_norm"], eps)
        x = x + shared_expert(xn, lp) + routed_experts(
            xn, lp, top_k=top_k, routed_scale=routed_scale,
            expert_offset=expert_offset)
    return _rmsnorm(x, p["out_norm"], eps)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] -> logits [..., V_padded] in float32."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["lm_head"].astype(dtype),
                      preferred_element_type=jnp.float32)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "rope_base": c.rope_base, "eps": c.rms_eps,
            "top_k": c.top_k, "routed_scale": c.routed_scaling_factor,
            "expert_offset": c.expert_offset}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d, h = c["hidden_size"], c["num_attention_heads"]
    attn = d * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) \
        + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d + c["kv_lora_rank"] + 2 * d   # three norms
    dense = attn + 3 * d * c["intermediate_size"]
    f = c["moe_intermediate_size"]
    moe = attn + d * c["n_routed_experts"] + c["n_routed_experts"] \
        + 3 * d * c["n_shared_experts"] * f + c["experts_held"] * 3 * d * f
    k = c["first_k_dense_replace"]
    return 2 * vocab_rows * d + d + k * dense \
        + (c["num_hidden_layers"] - k) * moe
