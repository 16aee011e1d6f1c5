"""Olmo-Hybrid (``model_type: olmo_hybrid``; ``config.json``, the public
Gated DeltaNet layer for the ``linear_*`` keys and the Olmo 2/3 block for
everything ``config.json`` is silent on) forward pass, plain: ``jax.numpy``
only, no kernel, no chunk, nothing of the program imported. SiLU and the
walk over ``<run>.<kind>.<name>`` parameters (``reference/granite_hybrid.py``),
the query blocks' size (``reference/deepseek_v3.py``), the causal
convolution and the l2 norm (``reference/kimi_linear.py``) are used as they
are.

d = ``hidden_size``. ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32
statistics, eps ``rms_norm_eps``. Layers are counted from 0; layer i is
``layer_types[i]``: ``full_attention`` where (i + 1) % 4 == 0, else
``linear_attention``. POST-norm residuals: a sublayer reads the stream
itself and its output is normed before it is added,

    h = x + rms(mixer(x); w_1);   out = h + rms(mlp(h); w_2)
    mlp(h) = (silu(h W_gate) * h W_up) W_down;   logits = rms(x; w_out) W_head

Gated DeltaNet mixer (H = ``linear_num_key_heads`` = ``linear_num_value_heads``
heads, d_k = ``linear_key_head_dim``, d_v = ``linear_value_head_dim``, a
convolution of ``linear_conv_kernel_dim`` taps):

    q | k | v | gate = x W_qkvg          H d_k, H d_k, H d_v, H d_v columns
    b | a            = x W_ba            H, H
    q | k | v <- silu(conv(q | k | v))   causal, depthwise, no bias,
                                         out_t = sum_j w[j] x_{t-K+1+j}
    q, k      <- x / sqrt(sum_head(x^2) + 1e-6);   q <- q / sqrt(d_k)
    beta = 2 sigmoid(b)                  ``linear_allow_neg_eigval``
    g = -exp(A_log) * softplus(a + dt_bias)        float32, a head and token
    per head, token by token, S [d_k, d_v] float32 from zero:
        S  <- exp(g_t) S
        S  <- S + beta_t k_t (v_t - S^T k_t)^T
        o_t = S^T q_t
    y = (o / sqrt(mean_head(o^2) + eps) * w_norm * silu(gate)) W_o

**The recurrence is a ``lax.scan`` over the tokens**, the state [d_k, d_v].

Attention mixer (H = ``num_attention_heads`` = ``num_key_value_heads`` heads
of D = ``hidden_size`` / the published 30 heads):

    q = rms(x W_q; w_q);  k = rms(x W_k; w_k)    each over ALL of the
                                                 layer's channels here
    v = x W_v;  o = causal softmax(q k^T / sqrt(D)) v,  no rotation
    y = o W_o

Reads the parameter dict of ``ray_tpu.models.olmo_hybrid.OlmoHybrid``
(``<run>.<kind>.<name>`` stacked over a run's layers, kinds ``gdn`` and
``attn``).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share of a tensor-parallel pair: the parameters are those of
  the heads held (the leading blocks' widths say how many); a mixer's
  output is those heads' part of ``o W_o``, and that partial result is
  what the post-norm reads and what goes on. The q/k norm's mean of
  squares runs over the channels HELD (the whole layer's would need the
  other chip's half: the one number a pair would exchange);
* the vocabulary is a slice, its rows a multiple of 128;
* the columns of ``W_qkvg`` and ``W_ba`` stand in blocks (all of q, then k,
  v, gate; all of b, then a) and the three convolutions side by side as one
  over their channels: a permutation of columns, nothing to random weights;
* what ``config.json`` does not give (post-norm order, the full-width q/k
  norm, no rotation, no bias in the convolutions, the l2 norm's eps, q's
  scale, the norm-then-SiLU gate) is listed under ``assumed`` in the
  configuration's file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import Q_BLOCK
from benchmark.reference.granite_hybrid import _layers, _silu
from benchmark.reference.kimi_linear import causal_conv, l2norm

__all__ = ["hidden", "head", "model_kwargs", "num_params", "gdn_mixer",
           "attention"]


def rms(x, w, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x.astype(jnp.float32)))


def delta_rule(q, k, v, g, beta):
    """q, k [B, T, H, d_k] (q scaled), v [B, T, H, d_v], g, beta [B, T, H]
    f32 -> o [B, T, H, d_v]: the state [d_k, d_v] decayed, corrected and
    read once a token, float32 sums on the VPU."""
    b, t, h, dk = q.shape
    f32 = lambda x: x.astype(jnp.float32)                    # noqa: E731

    def token(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok        # [B,H,dk] x2, [B,H,dv], [B,H] x2
        s = jnp.exp(g_t)[..., None, None] * s
        held = jnp.sum(s * f32(k_t)[..., None], axis=-2)     # S^T k  [B,H,dv]
        s = s + (b_t[..., None] * f32(k_t))[..., None] \
            * (f32(v_t) - held)[..., None, :]
        return s, jnp.sum(s * f32(q_t)[..., None], axis=-2)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def gdn_mixer(x, lp, *, key_dim, eps):
    """x [B, T, D] -> the held heads' part of y W_o."""
    b, t, _ = x.shape
    dv = lp["o_norm"].shape[-1]
    heads = lp["A_log"].shape[-1]
    gk, gv = heads * key_dim, heads * dv
    qkvg = x @ lp["w_qkvg"]
    qkv = _silu(causal_conv(qkvg[..., :2 * gk + gv], lp["conv"]))
    gate = qkvg[..., 2 * gk + gv:].reshape(b, t, heads, dv)
    per_head = lambda y: l2norm(y.reshape(b, t, heads, key_dim))  # noqa: E731
    q = per_head(qkv[..., :gk]) * jnp.asarray(key_dim ** -0.5, x.dtype)
    k = per_head(qkv[..., gk:2 * gk])
    v = qkv[..., 2 * gk:].reshape(b, t, heads, dv)
    ba = x @ lp["w_ba"]
    beta = 2.0 * _sigmoid(ba[..., :heads])
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jnp.logaddexp(
        ba[..., heads:].astype(jnp.float32)
        + lp["dt_bias"].astype(jnp.float32), 0.0)            # softplus
    o = delta_rule(q, k, v, g, beta).astype(jnp.float32)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * lp["o_norm"].astype(jnp.float32)
    zf = gate.astype(jnp.float32)
    y = (o * zf * _sigmoid(zf)).astype(x.dtype)
    return y.reshape(b, t, gv) @ lp["w_o"]


def attention(x, lp, *, head_dim, eps):
    """x [B, S, D] -> the held heads' part of o W_o; the q/k norms over all
    the channels ``lp`` holds."""
    b, s, _ = x.shape
    q = rms(x @ lp["w_q"], lp["q_norm"], eps)
    k = rms(x @ lp["w_k"], lp["k_norm"], eps)
    v = x @ lp["w_v"]
    heads = q.shape[-1] // head_dim
    q, k, v = (t.reshape(b, s, heads, head_dim) for t in (q, k, v))
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))
    blk = min(Q_BLOCK, s)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1).astype(jnp.float32)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kf) * scale
        seen = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, heads * head_dim).astype(x.dtype)
    return o @ lp["w_o"]


def mlp(x, lp):
    return (_silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def hidden(params: dict, tokens: jax.Array, dtype, *, head_dim, key_dim,
           eps) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    for kind, lp in _layers(p):
        y = gdn_mixer(x, lp, key_dim=key_dim, eps=eps) if kind == "gdn" \
            else attention(x, lp, head_dim=head_dim, eps=eps)
        x = x + rms(y, lp["mix_norm"], eps)
        x = x + rms(mlp(x, lp), lp["mlp_norm"], eps)
    return rms(x, p["out_norm"], eps)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] -> logits [..., V_padded] in float32."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["lm_head"].astype(dtype),
                      preferred_element_type=jnp.float32)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"head_dim": c.head_dim, "key_dim": c.gdn_key_dim,
            "eps": c.rms_eps}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d, f = c["hidden_size"], c["intermediate_size"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    gk, gv = hk * c["linear_key_head_dim"], hv * c["linear_value_head_dim"]
    gdn = d * (2 * gk + 2 * gv) + d * 2 * hv \
        + c["linear_conv_kernel_dim"] * (2 * gk + gv) + 2 * hv \
        + c["linear_value_head_dim"] + gv * d               # .. o_norm, W_o
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd + kv * hd + h * hd * d
    kinds = c["layer_types"]
    n_attn = kinds.count("attention")
    return 2 * vocab_rows * d + d + len(kinds) * (2 * d + 3 * d * f) \
        + (len(kinds) - n_attn) * gdn + n_attn * attn
