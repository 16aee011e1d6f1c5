"""A DeepSeek-V3 shaped decoder whose residual is ``hc_mult`` streams mixed
by manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606), with the query bottleneck
(``q_lora_rank``) and YaRN positions: the forward pass of ``model_type:
xing4_0``, plain. ``jax.numpy`` only, no kernel, nothing of the program
imported; what ``reference/deepseek_v3.py`` already states and this model
leaves as it is (RMSNorm, the gated MLP, the shared expert, the routed
experts of a held share, the head) is taken from there.

Streams. X ∈ R^{n×d} a token, X₀[j] = Emb(t) for every j; after the last
layer x = Σ_j X[j], then the final norm and the head (arXiv:2409.19606).
Around every sublayer F (attention; dense MLP or shared + routed experts)
with its own Φ ∈ R^{nd×(2n+n²)}, gain g ∈ R^{nd}, b ∈ R^{2n+n²}, α ∈ R³,
written in the natural [tokens, n, n] form:

    u = vec(X) (stream-major);  ũ = g ⊙ u / sqrt(mean(u²) + eps)
    [p | q | r] = ũ Φ
    H_pre = σ(α₀ p + b_pre);  H_post = 2 σ(α₁ q + b_post)
    A = clip(α₂ r + b_res, lo, hi) as n × n;  M = exp(A)
    ``iters`` times: M ← M / (colsum M + hc_eps); M ← M / (rowsum M + hc_eps)
    z = Σ_j H_pre[j] X[j];  y = F(RMSNorm(z));  X'[i] = Σ_j M[i, j] X[j] + H_post[i] y

Attention: x̂ = RMSNorm(z); c_q = RMSNorm(x̂ W_qa); [q_nope | q_rope] =
c_q W_qb a head (without ``w_q_a`` in the parameters: x̂ W_q); [c | k_pe] =
x̂ W_kva; [k_nope | v] = RMSNorm(c) W_kvb; RoPE on neighbouring pairs of
q_rope and of the one k_pe with YaRN's frequencies

    f_i = θ^(−2i/dr);  low, high = floor, ceil of dr ln(L₀ / (β 2π)) / (2 ln θ) at β_fast, β_slow
    ramp_i = clip((i − low) / (high − low), 0, 1);  f'_i = f_i / factor · ramp_i + f_i (1 − ramp_i)

(cos and sin times m(mscale) / m(mscale_all_dim), m(k) = 0.1 k ln factor +
1); score (q_nope·k_nope + q_rope·k_pe) (dn + dr)^(−1/2) m(mscale_all_dim)²,
causal softmax, o = P v, out concat(o) W_o.

Departures from the published model, the program's and kept so that both
sides see the same function: those of ``reference/deepseek_v3.py`` (one
chip's share of the experts, ``n_group`` = ``topk_group`` = 1, a padded
vocabulary slice), and no multi-token-prediction module
(``num_nextn_predict_layers`` 0): the loss is the next-token loss alone.
Assumed, with the papers as source (the configuration's file lists them):
columns before rows in the Sinkhorn step, ``hc_eps`` added to each sum,
the streams' start and their sum at the end, RoPE on neighbouring pairs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import (  # noqa: F401 (re-exported)
    Q_BLOCK, _gated, _rmsnorm, head, routed_experts, shared_expert)


def yarn_frequencies(dr: int, base: float, yarn: dict):
    """-> (f' [dr/2], the factor on cos and sin, the factor on the softmax
    scale) of ``rope_scaling`` ``yarn`` in the DeepSeek-V3 convention."""
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    f = base ** (-2.0 * i / dr)
    factor = yarn["factor"]

    def pair(beta):
        return dr * math.log(yarn["original_max"] / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair(yarn["beta_slow"])), dr - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    m = lambda k: 0.1 * k * math.log(factor) + 1.0 if factor > 1 else 1.0  # noqa: E731
    all_dim = yarn["mscale_all_dim"]
    return (f / factor * ramp + f * (1.0 - ramp),
            m(yarn["mscale"]) / m(all_dim), m(all_dim) ** 2)


def rope_pairs(x, freqs, scale: float):
    """x [B, S, H, D]: the pair (x[2i], x[2i+1]) turned by the angle
    position * freqs[i], cos and sin times ``scale``."""
    b, s, h, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos = (jnp.cos(ang) * scale)[None, :, None]
    sin = (jnp.sin(ang) * scale)[None, :, None]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(b, s, h, d).astype(x.dtype)


def attention(xn, lp, *, n_head, rope_base, eps, yarn):
    """x̂ [B, S, D] -> concat_h(o_h) W_o."""
    b, s, _ = xn.shape
    dr = lp["w_k_rope"].shape[1]
    per_head = lambda t: t.reshape(b, s, n_head, -1)  # noqa: E731
    freqs, on_table, on_scale = yarn_frequencies(dr, rope_base, yarn)
    c = _rmsnorm(xn @ lp["w_kv_a"], lp["kv_norm"], eps)
    cq = _rmsnorm(xn @ lp["w_q_a"], lp["q_norm"], eps) \
        if "w_q_a" in lp else xn
    k_pe = rope_pairs((xn @ lp["w_k_rope"])[:, :, None, :], freqs, on_table)
    q = jnp.concatenate([per_head(cq @ lp["w_q_nope"]),
                         rope_pairs(per_head(cq @ lp["w_q_rope"]), freqs,
                                    on_table)], -1)
    k = jnp.concatenate([per_head(c @ lp["w_k_b"]),
                         jnp.broadcast_to(k_pe, (b, s, n_head, dr))], -1)
    v = per_head(c @ lp["w_v_b"])
    scale = on_scale / jnp.sqrt(jnp.float32(q.shape[-1]))
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


def hc_maps(x, hp, *, iters, hc_eps, clamp, eps):
    """x [B, S, n, D], one sublayer's set ``hp`` -> (H_pre [B, S, n],
    H_post [B, S, n], H_res [B, S, n, n]) in x's dtype."""
    b, s, n, d = x.shape
    u = x.reshape(b, s, n * d)
    pqr = _rmsnorm(u, hp["gain"], eps) @ hp["phi"]
    alpha, bias = hp["alpha"], hp["bias"]
    pre = jax.nn.sigmoid(alpha[0] * pqr[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[..., n:2 * n] + bias[n:2 * n])
    a = jnp.clip(alpha[2] * pqr[..., 2 * n:] + bias[2 * n:], *clamp)
    m = jnp.exp(a).reshape(b, s, n, n)
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + hc_eps)       # columns
        m = m / (m.sum(-1, keepdims=True) + hc_eps)       # rows
    return pre, post, m


def hc_sublayer(x, hp, f, **hc):
    """X' of the streams x [B, S, n, D] around ``f(z) -> y``; ``hc`` the
    keywords of ``hc_maps``."""
    pre, post, res = hc_maps(x, hp, **hc)
    y = f(jnp.einsum("bsj,bsjd->bsd", pre, x))
    return jnp.einsum("bsij,bsjd->bsid", res, x) \
        + post[..., None] * y[:, :, None, :]


def hidden(params: dict, tokens: jax.Array, dtype, *, n_head, rope_base,
           eps, top_k, routed_scale, expert_offset, yarn, hc_mult, hc_iters,
           hc_eps, hc_clamp) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    emb = p["wte"][tokens]
    x = jnp.broadcast_to(emb[:, :, None, :],
                         emb.shape[:2] + (hc_mult, emb.shape[-1]))
    attn = dict(n_head=n_head, rope_base=rope_base, eps=eps, yarn=yarn)
    hc = dict(iters=hc_iters, hc_eps=hc_eps, clamp=hc_clamp, eps=eps)

    def layers(kind):
        stacked = {k.split(".", 1)[1]: v for k, v in p.items()
                   if k.startswith(kind + ".")}
        n = next(iter(stacked.values())).shape[0]
        return [{k: v[i] for k, v in stacked.items()} for i in range(n)]

    def hc_set(lp, which):
        return {k.split(".", 1)[1]: v for k, v in lp.items()
                if k.startswith(which + ".")}

    def attn_sublayer(x, lp):
        return hc_sublayer(x, hc_set(lp, "hc_attn"), lambda z: attention(
            _rmsnorm(z, lp["attn_norm"], eps), lp, **attn), **hc)

    for lp in layers("dense"):
        x = attn_sublayer(x, lp)
        x = hc_sublayer(x, hc_set(lp, "hc_mlp"), lambda z: _gated(
            _rmsnorm(z, lp["mlp_norm"], eps), lp["w_gate"], lp["w_up"],
            lp["w_down"]), **hc)
    for lp in layers("moe"):
        x = attn_sublayer(x, lp)

        def experts(z, lp=lp):
            zn = _rmsnorm(z, lp["mlp_norm"], eps)
            return shared_expert(zn, lp) + routed_experts(
                zn, lp, top_k=top_k, routed_scale=routed_scale,
                expert_offset=expert_offset)

        x = hc_sublayer(x, hc_set(lp, "hc_mlp"), experts, **hc)
    return _rmsnorm(x.sum(2), p["out_norm"], eps)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "rope_base": c.rope_base, "eps": c.rms_eps,
            "top_k": c.top_k, "routed_scale": c.routed_scaling_factor,
            "expert_offset": c.expert_offset,
            "yarn": {"factor": c.rope_factor,
                     "original_max": c.rope_original_max,
                     "beta_fast": c.rope_beta_fast,
                     "beta_slow": c.rope_beta_slow, "mscale": c.rope_mscale,
                     "mscale_all_dim": c.rope_mscale_all_dim},
            "hc_mult": c.hc_mult, "hc_iters": c.hc_sinkhorn_iters,
            "hc_eps": c.hc_eps, "hc_clamp": tuple(c.hc_res_clamp)}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    q, n = c["q_lora_rank"], c["hc_mult"]
    attn = d * q + q + q * h * qk \
        + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d + c["kv_lora_rank"] + 2 * d   # three norms
    k = 2 * n + n * n
    attn += 2 * (n * d * k + n * d + k + 3)     # the two sublayers' sets
    dense = attn + 3 * d * c["intermediate_size"]
    f = c["moe_intermediate_size"]
    moe = attn + d * c["n_routed_experts"] + c["n_routed_experts"] \
        + 3 * d * c["n_shared_experts"] * f + c["experts_held"] * 3 * d * f
    lead = c["first_k_dense_replace"]
    return 2 * vocab_rows * d + d + lead * dense \
        + (c["num_hidden_layers"] - lead) * moe
