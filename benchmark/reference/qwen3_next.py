"""Qwen3-Next (``model_type: qwen3_next``; ``config.json`` and the public
``qwen3_next`` modelling code of ``transformers`` wherever ``config.json``
is silent) forward pass, plain: ``jax.numpy`` only, no kernel, no chunk,
nothing of the program imported. SiLU, the walk over ``<run>.<kind>.<name>``
parameters (``reference/granite_hybrid.py``), the gated MLP
(``reference/deepseek_v3.py``), the causal convolution and the l2 norm
(``reference/kimi_linear.py``) are used as they are.

d = ``hidden_size``. ``zrms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``,
float32 statistics, eps ``rms_norm_eps``: every norm but the one inside a
Gated DeltaNet layer. Layers are counted from 0; layer i is attention where
(i + 1) % ``full_attention_interval`` == 0, else Gated DeltaNet; every
layer has experts (``mlp_only_layers`` [], ``decoder_sparse_step`` 1):

    x = x + mixer(zrms(x));   x = x + ffn(zrms(x));   logits = zrms(x) W_head

Gated DeltaNet mixer (``linear_num_key_heads`` Hk, ``linear_num_value_heads``
Hv, ``linear_key_head_dim`` = ``linear_value_head_dim`` = dh, a convolution
of ``linear_conv_kernel_dim`` taps), x̂ the normed input:

    q | k | v | z = x̂ W_qkvz            Hk dh, Hk dh, Hv dh, Hv dh columns
    b | a         = x̂ W_ba              Hv, Hv
    q | k | v    <- silu(conv(q | k | v))    causal, depthwise, no bias,
                                             out_t = sum_j w[j] x_{t-K+1+j}
    q, k         <- x / sqrt(sum_head(x^2) + 1e-6);   q <- q / sqrt(dh)
    value head j reads key head j // (Hv / Hk)
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   float32,
                                             one a value head and token
    per value head, token by token, S [dh, dh] float32 from zero:
        S  <- exp(g_t) S
        S  <- S + beta_t k_t (v_t - S^T k_t)^T
        o_t = S^T q_t
    y = (o / sqrt(mean_head(o^2) + eps) * w_norm * silu(z)) W_o

**The recurrence is a ``lax.scan`` over the tokens.**

Attention mixer (H = ``num_attention_heads`` query heads, Hkv =
``num_key_value_heads``, every head ``head_dim`` = D):

    q | gate = x̂ W_q        a head's D of q beside its D of gate
    k = x̂ W_k;  v = x̂ W_v
    q <- zrms_head(q; w_q),  k <- zrms_head(k; w_k)
    the first R = D * ``partial_rotary_factor`` channels of each head of q
    and k: channel i < R/2 pairs with i + R/2, turned by position *
    ``rope_theta``^(-2i/R); the other D - R as they are
    o = causal softmax(q k^T / sqrt(D)) v, query head j on key/value head
    j // (H / Hkv)
    y = (o * sigmoid(gate)) W_o

Expert sublayer: p = softmax(x̂ W_r) over ALL ``num_experts`` in float32;
the ``num_experts_per_tok`` largest; their weights p_e / (sum of the chosen
p) (``norm_topk_prob``); an expert is W_down (silu(W_gate x̂) * W_up x̂); the
shared expert one more such MLP times sigmoid(x̂ w_sg); the sum of both.

Reads the parameter dict of ``ray_tpu.models.qwen3_next.Qwen3Next``
(``<run>.<kind>.<name>`` stacked over a run's layers, kinds ``gdn_moe`` and
``attn_moe``).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share: the sum over chosen experts runs over the held ones
  only (``expert_offset`` and the leading axis of ``e_gate``); the shared
  expert is whole;
* the vocabulary is a slice; its rows are padded to a multiple of 128
  (18 992 -> 19 072) and the padded rows take part in the softmax;
* the columns of ``W_qkvz`` and ``W_ba`` stand in blocks (all of q, then k,
  v, z; all of b, then a) where the checkpoint interleaves them a key
  head: a permutation of columns, nothing to random weights;
* no multi-token-prediction layer (``config.json`` has no key for it, the
  public modelling code drops its weights);
* what ``config.json`` does not give (the l2 norm's eps, q's scale, SiLU
  inside the convolution, the norm-then-SiLU gate, ``1 + w``, the
  rotate-half pairing, the shared expert's gate, no bias anywhere) is the
  modelling code's, listed under ``assumed`` in the configuration's file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import Q_BLOCK, _gated
from benchmark.reference.granite_hybrid import _layers, _silu
from benchmark.reference.kimi_linear import causal_conv, l2norm

__all__ = ["hidden", "head", "model_kwargs", "num_params", "shared_expert",
           "routed_experts"]


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x.astype(jnp.float32)))


def zrms(x, w, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def delta_rule(q, k, v, g, beta):
    """q, k, v [B, T, H, dh] (q scaled, q and k already of the value
    heads), g, beta [B, T, H] f32 -> o [B, T, H, dh]: the state decayed,
    corrected and read once a token, float32 sums on the VPU."""
    b, t, h, dh = q.shape
    dtype = v.dtype
    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731

    def token(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok           # [B,H,dh] x3, [B,H] x2
        s = jnp.exp(g_t)[..., None, None] * s
        held = jnp.sum(s * f32(k_t)[..., None], axis=-2)     # S^T k  [B,H,dh]
        s = s + (b_t[..., None] * f32(k_t))[..., None] \
            * (f32(v_t) - held)[..., None, :]
        return s, jnp.sum(s * f32(q_t)[..., None], axis=-2)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dh, dh), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(dtype)


def gdn_mixer(xn, lp, *, key_heads, value_heads, eps):
    """x̂ [B, T, D] -> y W_o."""
    b, t, _ = xn.shape
    dh = lp["o_norm"].shape[-1]
    gk, gv = key_heads * dh, value_heads * dh
    qkvz = xn @ lp["w_qkvz"]
    qkv = _silu(causal_conv(qkvz[..., :2 * gk + gv], lp["conv"]))
    z = qkvz[..., 2 * gk + gv:].reshape(b, t, value_heads, dh)
    per_key = lambda x: jnp.repeat(                          # noqa: E731
        l2norm(x.reshape(b, t, key_heads, dh)), value_heads // key_heads, 2)
    q = per_key(qkv[..., :gk]) * jnp.asarray(dh ** -0.5, xn.dtype)
    k = per_key(qkv[..., gk:2 * gk])
    v = qkv[..., 2 * gk:].reshape(b, t, value_heads, dh)
    ba = xn @ lp["w_ba"]
    beta = _sigmoid(ba[..., :value_heads])
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jnp.logaddexp(
        ba[..., value_heads:].astype(jnp.float32)
        + lp["dt_bias"].astype(jnp.float32), 0.0)            # softplus
    o = delta_rule(q, k, v, g, beta).astype(jnp.float32)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * lp["o_norm"].astype(jnp.float32)
    zf = z.astype(jnp.float32)
    y = (o * zf * _sigmoid(zf)).astype(xn.dtype)
    return y.reshape(b, t, gv) @ lp["w_o"]


def rotate_first(x, rotary: int, base: float):
    """x [B, S, H, D]: of the first ``rotary`` channels of a head, channel
    i < rotary/2 and channel i + rotary/2 turned by position *
    base^(-2i/rotary)."""
    s, half = x.shape[1], rotary // 2
    theta = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * theta[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xf = x.astype(jnp.float32)
    lo, hi = xf[..., :half], xf[..., half:rotary]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos,
                            xf[..., rotary:]], -1).astype(x.dtype)


def attention(xn, lp, *, n_head, n_kv_head, rotary, rope_base, eps):
    """x̂ [B, S, D] -> (o * sigmoid(gate)) W_o."""
    b, s, _ = xn.shape
    hd = lp["q_norm"].shape[-1]
    qg = (xn @ lp["w_q"]).reshape(b, s, n_head, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (xn @ lp["w_k"]).reshape(b, s, n_kv_head, hd)
    v = (xn @ lp["w_v"]).reshape(b, s, n_kv_head, hd)
    q = rotate_first(zrms(q, lp["q_norm"], eps), rotary, rope_base)
    k = rotate_first(zrms(k, lp["k_norm"], eps), rotary, rope_base)
    group = n_head // n_kv_head
    kf = jnp.repeat(k, group, 2).astype(jnp.float32)
    vf = jnp.repeat(v, group, 2).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    blk = min(Q_BLOCK, s)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1).astype(jnp.float32)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kf) * scale
        seen = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, n_head, hd)
    o = (o * _sigmoid(gate)).astype(xn.dtype)
    return o.reshape(b, s, n_head * hd) @ lp["w_o"]


def shared_expert(xn, lp):
    """x̂ [..., D] -> the shared expert times its gate."""
    y = _gated(xn, lp["s_gate"], lp["s_up"], lp["s_down"])
    return (y.astype(jnp.float32) * _sigmoid(xn @ lp["s_gate_w"])
            ).astype(xn.dtype)


def routed_experts(xn, lp, *, top_k, expert_offset=0):
    """x̂ [..., D] -> the part of sum_e w_e expert_e(x̂) that the experts
    in ``lp`` (those from ``expert_offset`` on) give."""
    p = jax.nn.softmax(jnp.einsum("...d,de->...e", xn, lp["w_router"],
                                  preferred_element_type=jnp.float32), -1)
    picked, chosen = jax.lax.top_k(p, top_k)
    w = picked / picked.sum(-1, keepdims=True)                  # [..., k]

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


def hidden(params: dict, tokens: jax.Array, dtype, *, n_head, n_kv_head,
           rotary, rope_base, gdn_key_heads, gdn_value_heads, eps, top_k,
           expert_offset) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    for kind, lp in _layers(p):
        xn = zrms(x, lp["norm"], eps)
        if kind.startswith("gdn"):
            x = x + gdn_mixer(xn, lp, key_heads=gdn_key_heads,
                              value_heads=gdn_value_heads, eps=eps)
        else:
            x = x + attention(xn, lp, n_head=n_head, n_kv_head=n_kv_head,
                              rotary=rotary, rope_base=rope_base, eps=eps)
        xn = zrms(x, lp["mlp_norm"], eps)
        x = x + shared_expert(xn, lp) + routed_experts(
            xn, lp, top_k=top_k, expert_offset=expert_offset)
    return zrms(x, p["out_norm"], eps)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] -> logits [..., V_padded] in float32."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["lm_head"].astype(dtype),
                      preferred_element_type=jnp.float32)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "n_kv_head": c.n_kv_head,
            "rotary": c.rotary_dim, "rope_base": c.rope_base,
            "gdn_key_heads": c.gdn_key_heads,
            "gdn_value_heads": c.gdn_value_heads, "eps": c.rms_eps,
            "top_k": c.top_k, "expert_offset": c.expert_offset}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d, dh = c["hidden_size"], c["linear_key_head_dim"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    gk, gv = hk * dh, hv * c["linear_value_head_dim"]
    gdn = d * (2 * gk + 2 * gv) + d * 2 * hv \
        + c["linear_conv_kernel_dim"] * (2 * gk + gv) + 2 * hv \
        + c["linear_value_head_dim"] + gv * d               # .. o_norm, W_o
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * h * 2 * hd + 2 * d * kv * hd + 2 * hd + h * hd * d
    f, fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    moe = d * c["num_experts"] + 3 * d * fs + d + c["experts_held"] * 3 * d * f
    kinds = c["layer_types"]
    n_attn = kinds.count("attention")
    return 2 * vocab_rows * d + d + len(kinds) * (2 * d + moe) \
        + (len(kinds) - n_attn) * gdn + n_attn * attn
