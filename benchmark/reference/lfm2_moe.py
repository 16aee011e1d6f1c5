"""LFM2-MoE (``model_type: lfm2_moe``; LiquidAI/LFM2-8B-A1B's ``config.json``
and the catalog's ``described_as`` wherever ``config.json`` is silent)
forward pass, plain: ``jax.numpy`` only, no kernel, nothing of the program
imported. Written for the UNCUT layer; the share a chip holds (which
experts, which rows of the vocabulary) is the parameters' shapes and
``model_kwargs``.

d = ``hidden_size``. ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32
statistics, eps ``norm_eps``. Every layer:

    x <- x + operator(rms(x; w_op));   x <- x + ffn(rms(x; w_ffn))
    logits = rms(x_L; w_out) E^T       the head is the embedding (tied)

``conv`` operator (``layer_types`` ``conv``; ``conv_L_cache`` 3 taps,
``conv_bias`` false), u the normed input:

    B | C | x = u W_in                 three chunks of d IN THAT ORDER
    z = B * x
    c[t] = w[0] z[t-2] + w[1] z[t-1] + w[2] z[t]    per channel, zeros before
                                       the row, NO activation: three shifted
                                       sums, written out (``short_conv``)
    out = (C * c) W_out

``full_attention`` operator (``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of D = 64, no bias):

    q = u W_q [S, H, D]   k = u W_k [S, Hkv, D]   v = u W_v
    q <- rms_head(q; w_qn)   k <- rms_head(k; w_kn)      over D, BEFORE the
                                                         rotation
    rotate-half over all D channels: channel i with i + D / 2, angle
        position x rope_theta^(-2i / D)
    out = softmax(q k^T / sqrt(D) + causal) v  W_o, query head j on
        key/value head j // (H / Hkv), queries in blocks of ``Q_BLOCK``

dense MLP (layers below ``num_dense_layers``): W_2 (silu(W_1 u) * W_3 u).

expert layer (all others; ``use_expert_bias``, ``norm_topk_prob``,
``routed_scaling_factor``):

    s = sigmoid(u W_r)                 ALL ``num_experts``, float32
    chosen = top ``num_experts_per_tok`` of s + b      b a buffer, no gradient
    w = s[chosen] / sum s[chosen] * routed_scaling_factor
    out = sum_{e chosen} w_e W_2,e (silu(W_1,e u) * W_3,e u)     no shared
                                                                  expert

**The objective** (``losses``) is the next-token loss plus
``router_aux_coef`` times every expert layer's sequence-wise balancing term
(DeepSeek-V3, arXiv:2412.19437, eq. 17 to 20), a row at a time: sum_e f_e
P_e over ALL E experts, f_e = E / (k T) x the count of the row's tokens
whose top k (of s + b) names e (a count: no gradient), P_e the row's mean
of s_e / sum_j s_j; 1 a layer under a level router. The term reads the
router alone, which every chip holds whole, so a share states it as the
uncut model does.

Reads the parameter dict of ``ray_tpu.models.lfm2_moe.Lfm2Moe``
(``<run>.<kind>.<name>`` stacked over a run's layers, kinds
``<conv|attn>_<mlp|moe>``; the taps tap-major [K, d]).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share: the sum over chosen experts runs over the held ones
  only (``expert_offset`` and the leading axis of ``e_gate``);
* the vocabulary is a slice (ids 0-16383 of 65536): embedding, logits and
  loss over it;
* the selection bias is never updated (its published update needs a rate
  no ``config.json`` holds): it stays the buffer it is drawn as;
* the weights' sum is taken as it is (the public modelling code adds 1e-6
  to it by the issue writer's recollection: under float32's rounding of a
  sum near 2);
* what ``config.json`` does not give (the tied head, ``head_dim``, the
  weights' draw) is listed under ``assumed`` in the configuration's file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import Q_BLOCK, _gated, _rmsnorm
from benchmark.reference.granite_hybrid import _layers

__all__ = ["hidden", "head", "losses", "model_kwargs", "num_params",
           "conv_operator", "attention_operator", "routed_experts",
           "router_balance", "short_conv"]


def in_chunks(h):
    """u W_in [..., 3 d] -> (B, C, x), in that order."""
    b, c, x = jnp.split(h, 3, -1)
    return b, c, x


def tap_activation(c):
    """What stands between the taps' sum and the second gate: nothing."""
    return c


def short_conv(z, w):
    """z [B, S, d], w [3, d] -> c[t] = w[0] z[t-2] + w[1] z[t-1] + w[2]
    z[t], zeros before the row; float32."""
    zf, wf = z.astype(jnp.float32), w.astype(jnp.float32)
    zero = jnp.zeros_like(zf[:, :1])
    z1 = jnp.concatenate([zero, zf[:, :-1]], 1)               # z[t-1]
    z2 = jnp.concatenate([zero, zero, zf[:, :-2]], 1)         # z[t-2]
    return wf[0] * z2 + wf[1] * z1 + wf[2] * zf


def conv_operator(u, lp):
    """u [B, S, d] (normed) -> (C * conv3(B * x)) W_out."""
    b, c, x = in_chunks(u @ lp["w_in"])
    taps = tap_activation(short_conv(b * x, lp["conv_w"]))
    return (c.astype(jnp.float32) * taps).astype(u.dtype) @ lp["w_out"]


def head_norm(x, w, eps):
    """rms over a head's channels, a weight a channel."""
    return _rmsnorm(x, w, eps)


def rotate_half(x, base: float):
    """x [B, S, H, D]: channel i paired with i + D / 2, turned by position
    x base^(-2i / D); every channel of the head."""
    d = x.shape[-1]
    theta = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [D/2]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * theta[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    lo, hi = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                           -1).astype(x.dtype)


def attention_operator(u, lp, *, n_head, n_kv_head, rope_base, eps):
    """u [B, S, d] (normed) -> o W_o."""
    b, s, _ = u.shape
    hd = lp["q_norm"].shape[-1]
    q = (u @ lp["w_q"]).reshape(b, s, n_head, hd)
    k = (u @ lp["w_k"]).reshape(b, s, n_kv_head, hd)
    v = (u @ lp["w_v"]).reshape(b, s, n_kv_head, hd)
    q = rotate_half(head_norm(q, lp["q_norm"], eps), rope_base)
    k = rotate_half(head_norm(k, lp["k_norm"], eps), rope_base)
    share = n_head // n_kv_head
    # query head j = kv * share + i reads key/value head kv
    q = q.reshape(b, s, n_kv_head, share, hd)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    blk = min(Q_BLOCK, s)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1).astype(jnp.float32)
        sc = jnp.einsum("bqgid,bkgd->bgiqk", qb, kf) * scale
        seen = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("bgiqk,bkgd->bqgid", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, n_head * hd).astype(u.dtype)
    return o @ lp["w_o"]


def router_scores(u, lp):
    """-> s = sigmoid(u W_r) over ALL experts, float32."""
    return jax.nn.sigmoid(jnp.einsum("...d,de->...e", u, lp["w_router"],
                                     preferred_element_type=jnp.float32))


def choose(s, bias, top_k: int):
    """The top k of s + bias; the bias chooses and does not weigh."""
    return jax.lax.top_k(
        s + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)[1]


def weight_scores(s, bias):
    """The scores the weights are made of: s, WITHOUT the bias."""
    return s


def routed_experts(u, lp, *, top_k, routed_scale, expert_offset=0):
    """u [..., d] -> the part of sum_e w_e expert_e(u) that the experts in
    ``lp`` (those from ``expert_offset`` on) give."""
    s = router_scores(u, lp)
    chosen = choose(s, lp["router_bias"], top_k)
    picked = jnp.take_along_axis(weight_scores(s, lp["router_bias"]), chosen,
                                 -1)
    w = picked / picked.sum(-1, keepdims=True) * routed_scale   # [..., k]

    def add_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e + expert_offset, w, 0.0), -1)
        return out + w_e[..., None] * _gated(u, w_gate, w_up, w_down).astype(
            jnp.float32), None

    held = lp["e_gate"].shape[0]
    out, _ = jax.lax.scan(add_expert, jnp.zeros(u.shape, jnp.float32),
                          (jnp.arange(held), lp["e_gate"], lp["e_up"],
                           lp["e_down"]))
    return out.astype(u.dtype)


def router_balance(u, lp, *, top_k):
    """u [B, S, d] -> the layer's balancing term a row [B] f32: sum_e f_e
    P_e, f_e = E / (k S) x the tokens whose top k names e, P_e the row's
    mean of s_e / sum_j s_j."""
    s = router_scores(u, lp)
    chosen = choose(s, lp["router_bias"], top_k)
    n = s.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(chosen, n, dtype=jnp.float32), (-3, -2))
    f = counts * n / (top_k * s.shape[-2])                        # [B, E]
    p = jnp.mean(s / jnp.sum(s, -1, keepdims=True), -2)
    return jnp.sum(jax.lax.stop_gradient(f) * p, -1)


def _walk(params: dict, tokens: jax.Array, dtype, *, n_head, n_kv_head,
          rope_base, eps, top_k, routed_scale, expert_offset,
          router_aux_coef=0.0):
    """-> (final hidden states [B, S, d], the expert layers' balancing
    terms summed, a row [B] f32)."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    balance = jnp.zeros((tokens.shape[0],), jnp.float32)
    for kind, lp in _layers(p):
        operator, ffn = kind.split("_")
        u = _rmsnorm(x, lp["norm"], eps)
        if operator == "conv":
            x = x + conv_operator(u, lp)
        else:
            x = x + attention_operator(u, lp, n_head=n_head,
                                       n_kv_head=n_kv_head,
                                       rope_base=rope_base, eps=eps)
        u = _rmsnorm(x, lp["mlp_norm"], eps)
        if ffn == "mlp":
            x = x + _gated(u, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            balance = balance + router_balance(u, lp, top_k=top_k)
            x = x + routed_experts(u, lp, top_k=top_k,
                                   routed_scale=routed_scale,
                                   expert_offset=expert_offset)
    return _rmsnorm(x, p["out_norm"], eps), balance


def hidden(params: dict, tokens: jax.Array, dtype, **kw) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, d] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    return _walk(params, tokens, dtype, **kw)[0]


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., d] -> logits [..., V] in float32: the embedding,
    transposed."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["wte"].astype(dtype),
                      preferred_element_type=jnp.float32)


def losses(params: dict, tokens: jax.Array, dtype, **kw) -> jax.Array:
    """The objective's terms [B, S] f32, whose mean is the loss: each
    position's next-token term (the target of the last position is the
    row's first id, as the step rolls them) plus ``router_aux_coef`` times
    its row's balancing terms."""
    h, balance = _walk(params, tokens, dtype, **kw)
    logits = head(params, h, dtype)
    gold = jnp.take_along_axis(
        logits, jnp.roll(tokens, -1, axis=1)[..., None], -1)[..., 0]
    return jax.scipy.special.logsumexp(logits, -1) - gold \
        + kw.get("router_aux_coef", 0.0) * balance[:, None]


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "n_kv_head": c.n_kv_head,
            "rope_base": c.rope_base, "eps": c.rms_eps, "top_k": c.top_k,
            "routed_scale": c.routed_scale,
            "expert_offset": c.expert_offset,
            "router_aux_coef": c.router_aux_coef}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding that is also the head. The
    selection bias (``num_experts`` a layer) is a buffer the program holds
    among its parameters and is counted."""
    c = sizes
    d, hd = c["hidden_size"], c["head_dim"]
    conv = d * 3 * d + d * d + c["conv_L_cache"] * d
    attention = d * hd * (2 * c["num_attention_heads"]
                          + 2 * c["num_key_value_heads"]) + 2 * hd
    mlp = 3 * d * c["intermediate_size"]
    moe = d * c["num_experts"] + c["num_experts"] \
        + c["experts_held"] * 3 * d * c["moe_intermediate_size"]
    total = vocab_rows * d + d
    for i, kind in enumerate(c["layer_types"]):
        total += 2 * d + (attention if kind == "attention" else conv) \
            + (mlp if i < c["num_dense_layers"] else moe)
    return total
