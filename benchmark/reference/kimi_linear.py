"""Kimi-Linear (``model_type: kimi_linear``; arXiv:2510.26692, and the
family's public modelling code with the ``fla`` kernels it calls) forward
pass, plain: ``jax.numpy`` only, no kernel, no chunk, nothing of the
program imported. The unchanged pieces of ``reference/deepseek_v3.py``
(RMSNorm, the shared expert, the routed experts) and of
``reference/granite_hybrid.py`` (SiLU, the walk over ``<run>.<kind>.<name>``
parameters) are used as they are.

d = ``hidden_size``. Published layer numbers are 1-based
(``linear_attn_config.full_attn_layers`` ends in ``num_hidden_layers``):
layers 4, 8, ... 24, 27 are latent attention, the other 20 KDA; layer 1
(``first_k_dense_replace`` 1) has a dense gated MLP, layers 2-27 the expert
layer. Every layer, eps ``rms_norm_eps``:

    x = x + mixer(RMSNorm(x));   x = x + ffn(RMSNorm(x))

KDA mixer (``linear_attn_config``: H heads, head_dim for keys and values,
a convolution of ``short_conv_kernel_size`` taps), x̂ the normed input:

    q = l2norm_head(silu(conv(x̂ W_q)));  k = l2norm_head(silu(conv(x̂ W_k)))
    v = silu(conv(x̂ W_v))                            W_*: [d, H head_dim]
    g = -exp(A_log)[head] * softplus((x̂ W_fa) W_fb + dt_bias)
                                 one a key channel a token, <= 0, float32
    beta = sigmoid(x̂ W_beta)                         one a head
    per head, token by token, S [head_dim, head_dim] float32 from zero:
        S  <- diag(exp(g_t)) S
        S  <- S + beta_t k_t (v_t - S^T k_t)^T
        o_t = S^T (q_t / sqrt(head_dim))
    y = (RMSNorm_head(o; w_norm) * sigmoid((x̂ W_ga) W_gb)) W_o

conv: causal, depthwise, no bias, ``out_t = sum_k w[k] x_{t-K+1+k}``;
l2norm: ``x / sqrt(sum(x^2) + 1e-6)`` over a head. **The recurrence is a
``lax.scan`` over the tokens.**

Latent-attention mixer: DeepSeek-V3's without a query bottleneck and
WITHOUT rotation (``mla_use_nope``): q = x̂ W_q in heads of 128 + 64;
[c | k_pe] = x̂ W_kva split 512 + 64; [k_nope | v] = RMSNorm(c) W_kvb;
score ((q_nope . k_nope) + (q_pe . k_pe)) / sqrt(192), causal; k_pe one
vector a position for all heads; out concat(o) W_o.

Expert layer: ``reference/deepseek_v3.py``'s: sigmoid scores + selection
bias, top k of all experts, weights normalised over the k and x
``routed_scaling_factor``, one shared expert.

Reads the parameter dict of ``ray_tpu.models.kimi_linear.KimiLinear``
(``<run>.<kind>.<name>`` stacked over a run's layers, kind
``<mixer>_<ffn>``; the latent projections cut by columns as
``reference/deepseek_v3.py`` describes).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share: the sum over chosen experts runs over the held ones
  only (``reference/deepseek_v3.py``); ``num_expert_group`` = ``topk_group``
  = 1, so group-limited routing is the identity and is not written;
* the vocabulary is a slice; its rows are padded to a multiple of 128 (none
  at 20 480) and padded rows take part in the softmax;
* what ``config.json`` does not give (the l2 norm's eps, q's scale, SiLU
  inside the convolution, the norm-then-sigmoid gate, no bias anywhere) is
  the modelling code's, listed under ``assumed`` in the configuration's
  file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import (Q_BLOCK, _gated, _rmsnorm,
                                             routed_experts, shared_expert)
from benchmark.reference.granite_hybrid import _layers, _silu

__all__ = ["hidden", "head", "model_kwargs", "num_params", "shared_expert",
           "routed_experts"]

L2_EPS = 1e-6


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x.astype(jnp.float32)))


def causal_conv(x, w):
    """x [B, T, C], w [K, C]: out_t = sum_k w[k] x_{t-K+1+k}, no bias."""
    taps, t = w.shape[0], x.shape[1]
    out = jnp.zeros(x.shape, jnp.float32)
    for k in range(taps):
        back = taps - 1 - k                     # how far tap k looks back
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        out = out + shifted.astype(jnp.float32) * w[k].astype(jnp.float32)
    return out.astype(x.dtype)


def l2norm(x, eps=L2_EPS):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


def delta_rule(q, k, v, g, beta):
    """q, k [B, T, H, dk], v [B, T, H, dv], g [B, T, H, dk] f32 (<= 0),
    beta [B, T, H] f32 -> o [B, T, H, dv]: the state decayed, corrected
    and read once a token, float32 (sums on the VPU: no matmul unit's
    precision stands between the definition and the number)."""
    b, t, h, dk = q.shape
    dtype = v.dtype
    scale = dk ** -0.5
    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731

    def token(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok       # [B,H,dk] x2, [B,H,dv], [B,H,dk], [B,H]
        s = jnp.exp(g_t)[..., None] * s
        held = jnp.sum(s * f32(k_t)[..., None], axis=-2)     # S^T k  [B,H,dv]
        s = s + (b_t[..., None] * f32(k_t))[..., None] \
            * (f32(v_t) - held)[..., None, :]
        read = s.astype(dtype).astype(jnp.float32)
        return s, jnp.sum(read * (f32(q_t) * scale)[..., None], axis=-2)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(dtype)


def kda_mixer(xn, lp, *, heads, eps):
    """x̂ [B, T, D] -> y W_o."""
    b, t, _ = xn.shape
    per_head = lambda x: x.reshape(b, t, heads, -1)          # noqa: E731
    q = l2norm(per_head(_silu(causal_conv(xn @ lp["w_q"], lp["conv_q"]))))
    k = l2norm(per_head(_silu(causal_conv(xn @ lp["w_k"], lp["conv_k"]))))
    v = per_head(_silu(causal_conv(xn @ lp["w_v"], lp["conv_v"])))
    step = ((xn @ lp["w_f_a"]) @ lp["w_f_b"]).astype(jnp.float32) \
        + lp["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[:, None] \
        * per_head(jnp.logaddexp(step, 0.0))                 # softplus
    beta = _sigmoid(xn @ lp["w_beta"])
    o = delta_rule(q, k, v, g, beta)
    gate = _sigmoid(per_head((xn @ lp["w_g_a"]) @ lp["w_g_b"]))
    y = (_rmsnorm(o, lp["o_norm"], eps).astype(jnp.float32) * gate
         ).astype(xn.dtype)
    return y.reshape(b, t, -1) @ lp["w_o"]


def attention(xn, lp, *, n_head, eps):
    """x̂ [B, S, D] -> concat_h(o_h) W_o: latent attention, no rotation."""
    b, s, _ = xn.shape
    dr = lp["w_k_rope"].shape[1]
    per_head = lambda t: t.reshape(b, s, n_head, -1)  # noqa: E731
    c = _rmsnorm(xn @ lp["w_kv_a"], lp["kv_norm"], eps)
    k_pe = (xn @ lp["w_k_rope"])[:, :, None, :]
    q = jnp.concatenate([per_head(xn @ lp["w_q_nope"]),
                         per_head(xn @ lp["w_q_rope"])], -1)
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


def hidden(params: dict, tokens: jax.Array, dtype, *, n_head, kda_heads, eps,
           top_k, routed_scale, expert_offset) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    for kind, lp in _layers(p):
        mixer, ffn = kind.split("_")
        if mixer == "kda":
            x = x + kda_mixer(_rmsnorm(x, lp["norm"], eps), lp,
                              heads=kda_heads, eps=eps)
        else:
            x = x + attention(_rmsnorm(x, lp["attn_norm"], eps), lp,
                              n_head=n_head, eps=eps)
        xn = _rmsnorm(x, lp["mlp_norm"], eps)
        if ffn == "dense":
            x = x + _gated(xn, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
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
    return {"n_head": c.n_head, "kda_heads": c.kda_n_heads, "eps": c.rms_eps,
            "top_k": c.top_k, "routed_scale": c.routed_scaling_factor,
            "expert_offset": c.expert_offset}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d = c["hidden_size"]
    kw = c["kda_num_heads"] * c["kda_head_dim"]
    kda = 3 * d * kw + 3 * c["kda_conv_size"] * kw \
        + c["kda_num_heads"] + kw \
        + 2 * (d * c["kda_gate_rank"] + c["kda_gate_rank"] * kw) \
        + d * c["kda_num_heads"] + c["kda_head_dim"] + kw * d   # .. o_norm, W_o
    h = c["num_attention_heads"]
    mla = d * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) \
        + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d + c["kv_lora_rank"]           # .. kv_norm
    f = c["moe_intermediate_size"]
    dense = 3 * d * c["intermediate_size"]
    moe = d * c["num_experts"] + c["num_experts"] \
        + 3 * d * c["num_shared_experts"] * f + c["experts_held"] * 3 * d * f
    kinds = c["layer_types"]
    k = c["first_k_dense_replace"]
    return 2 * vocab_rows * d + d + len(kinds) * 2 * d \
        + kinds.count("kda") * kda + kinds.count("mla") * mla \
        + k * dense + (len(kinds) - k) * moe
