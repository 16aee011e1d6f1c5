"""Keye-VL-2.0's language model (``model_type: KeyeVL2``; ``config.json``,
the Qwen3-MoE modelling code of ``transformers`` whose keys that file
carries, and the public DeepSeek-V3.2-Exp inference code's ``Indexer`` with
``sa_config``'s sizes wherever ``config.json`` is silent) forward pass,
plain: ``jax.numpy`` only, no kernel, nothing of the program imported.
Written for the UNCUT layer; the share a chip holds (which experts, which
rows of the vocabulary) is the parameters' shapes and ``model_kwargs``.

d = ``hidden_size``. ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32
statistics, eps ``rms_norm_eps``. Positions ``pos`` [3, B, S] (temporal,
height, width); text: all three ``arange(S)``. Every layer, x̂ = rms(x; w_in):

    q = x̂ W_q [S, H, D]   k = x̂ W_k [S, Hkv, D]   v = x̂ W_v      no bias
    q <- rms_head(q; w_qn)   k <- rms_head(k; w_kn)              over D
    mRoPE: of a head's D / 2 frequency pairs (channel i with i + D / 2,
        angle pos * theta^(-2i / D)) pairs 0-15 take pos[0], 16-39 pos[1],
        40-63 pos[2]                                 (``mrope_section``)
    indexer (Hi heads of Di on ONE key of Di):
        qI = x̂ W_qI [S, Hi, Di]   kI = layernorm(x̂ W_kI) [S, Di]
        wI = x̂ W_w [S, Hi] * Hi^-1/2 * Di^-1/2                   float32
        the first Di / 2 channels of qI and kI rotated by pos[0] (Di / 4
        pairs, channel i with i + Di / 4, theta^(-i / (Di / 4))); the
        other Di / 2 as they are
        I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])          float32,
        the heads summed in their order
    S_t = the ``topk`` keys s <= t of largest I[t, s], by ``lax.top_k``
        (ties to the lower s); every s <= t where t < topk
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // G]
        / sqrt(D)) v[s, h // G]
    x <- x + o W_o
    x̂ = rms(x; w_post); p = softmax(x̂ W_r) over ALL experts in float32;
        the ``num_experts_per_tok`` largest; weights p / sum of the chosen p
    x <- x + sum_chosen weight_e W_down,e (silu(W_gate,e x̂) * W_up,e x̂)

    logits = rms(x_L; w_f) W_head

**The objective** (``losses``) is the next-token loss plus
``router_aux_coef`` times every layer's load-balancing term (Switch
Transformer, eq. 4 to 6, with a token's k choices all counted), a row at a
time: E sum_e f_e P_e over ALL E experts, f_e the share of the row's
(token, choice) pairs that name expert e (a count: no gradient), P_e the
row's mean of p_e; 1 a layer under a level router. The term reads the
router alone, which every chip holds whole, so a share states it as the
uncut model does.

**Queries are walked in blocks of ``Q_BLOCK``** so that a row of 16 384
fits: a block holds its [H, block, S] scores and its [block, S] index
scores, never [S, S].

Reads the parameter dict of ``ray_tpu.models.keye_vl2.KeyeVL2``
(``0.attn_moe.<name>`` stacked over the layers).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share: the sum over chosen experts runs over the held ones
  only (``expert_offset`` and the leading axis of ``e_gate``); there is no
  shared expert;
* the vocabulary is a slice; its rows are padded to a multiple of 128
  (18 992 -> 19 072) and the padded rows take part in the softmax;
* the inference code's Hadamard rotation and fp8 quantisation of qI and kI
  are inference numerics and are not built;
* the indexer's own training term (DeepSeek-V3.2-Exp's sparse-training
  stage: a KL from the main attention's head-summed distribution over S_t
  to softmax_{S_t}(I), the indexer's input detached) is a training option
  that is left off: under the next-token loss nothing reaches the
  indexer's parameters;
* the vision tower is not built (its sizes are not in the repository); the
  model takes the three position components its patches would bring;
* what ``config.json`` does not give (the per-head rms on q and k, plain-w
  norms, the indexer's inside) is listed under ``assumed`` in the
  configuration's file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import Q_BLOCK, _gated, _rmsnorm
from benchmark.reference.granite_hybrid import _layers

__all__ = ["hidden", "head", "losses", "model_kwargs", "num_params",
           "routed_experts", "router_balance", "sparse_attention",
           "index_parts", "index_scores", "top_keys"]


def _relu(x):
    return jnp.maximum(x, 0.0)


def index_key_norm(x, w, b, eps):
    """LayerNorm (weight and bias) over the ONE index key's channels."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def top_keys(scores, k: int):
    """scores [..., S] (-inf where a key is not causal) -> the positions of
    the k largest, ties to the lower position."""
    return jax.lax.top_k(scores, k)[1]


def _turn(x, angles):
    """x [B, S, H, R], channel i paired with i + R / 2; angles [B | 1, S,
    R / 2]."""
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    xf = x.astype(jnp.float32)
    lo, hi = jnp.split(xf, 2, -1)
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                           -1).astype(x.dtype)


def mrope_angles(pos, head_dim: int, base: float, sections):
    """pos [3, B, S] -> [B, S, head_dim / 2]: pair i under the component
    its section names."""
    half = head_dim // 2
    theta = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    comp = sum(([c] * n for c, n in enumerate(sections)), [])
    by_pair = jnp.stack([pos[c] for c in comp], -1).astype(jnp.float32)
    return by_pair * theta


def index_parts(xn, lp, pos, *, index_heads, rope_base, eps):
    """x̂ [B, S, d] -> (qI [B, S, Hi, Di], kI [B, S, Di], wI [B, S, Hi]
    float32 with both scales)."""
    b, s, _ = xn.shape
    di = lp["i_wk"].shape[-1]
    r = di // 2
    theta = rope_base ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    ang = pos[0].astype(jnp.float32)[..., None] * theta        # [B, S, r/2]
    part = lambda x: jnp.concatenate(                          # noqa: E731
        [_turn(x[..., :r], ang), x[..., r:]], -1)
    qi = part((xn @ lp["i_wq"]).reshape(b, s, index_heads, di))
    ki = index_key_norm(xn @ lp["i_wk"], lp["i_kn_w"], lp["i_kn_b"], eps)
    ki = part(ki[:, :, None])[:, :, 0]
    wi = jnp.einsum("bsd,dh->bsh", xn, lp["i_ww"],
                    preferred_element_type=jnp.float32) \
        * (index_heads ** -0.5 * di ** -0.5)
    return qi, ki, wi


def index_scores(qi, ki, wi):
    """qI [B, T, Hi, Di] of a block of queries, kI [B, S, Di], wI
    [B, T, Hi] -> I [B, T, S] float32."""
    out = jnp.zeros(qi.shape[:2] + ki.shape[1:2], jnp.float32)
    for j in range(qi.shape[2]):
        dots = jnp.einsum("btd,bsd->bts", qi[:, :, j], ki,
                          preferred_element_type=jnp.float32)
        out = out + wi[:, :, j, None] * _relu(dots)
    return out


def sparse_attention(xn, lp, pos, *, n_head, n_kv_head, rope_base,
                     mrope_section, index_heads, topk, eps):
    """x̂ [B, S, d] -> o W_o."""
    b, s, _ = xn.shape
    hd = lp["q_norm"].shape[-1]
    q = (xn @ lp["w_q"]).reshape(b, s, n_head, hd)
    k = (xn @ lp["w_k"]).reshape(b, s, n_kv_head, hd)
    v = (xn @ lp["w_v"]).reshape(b, s, n_kv_head, hd)
    ang = mrope_angles(pos, hd, rope_base, mrope_section)
    q = _turn(_rmsnorm(q, lp["q_norm"], eps), ang)
    k = _turn(_rmsnorm(k, lp["k_norm"], eps), ang)
    qi, ki, wi = index_parts(xn, lp, pos, index_heads=index_heads,
                             rope_base=rope_base, eps=eps)
    group = n_head // n_kv_head
    kf = jnp.repeat(k, group, 2).astype(jnp.float32)
    vf = jnp.repeat(v, group, 2).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    blk = min(Q_BLOCK, s)
    keep = min(topk, s)

    def rows(lo):
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, lo, blk, 1)  # noqa
        seen = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(seen[None], index_scores(cut(qi), ki, cut(wi)),
                           -jnp.inf)
        chosen = top_keys(scores, keep)                       # [B, blk, keep]
        picked = jnp.zeros((b, blk, s), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(blk)[None, :, None],
            chosen].set(True) & seen[None]
        sc = jnp.einsum("bqhd,bkhd->bhqk", cut(q).astype(jnp.float32),
                        kf) * scale
        sc = jnp.where(picked[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, n_head * hd).astype(xn.dtype)
    return o @ lp["w_o"]


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


def router_balance(xn, lp, *, top_k):
    """x̂ [B, S, D] -> the layer's load-balancing term a row [B] f32:
    E sum_e f_e P_e."""
    p = jax.nn.softmax(jnp.einsum("bsd,de->bse", xn, lp["w_router"],
                                  preferred_element_type=jnp.float32), -1)
    _, chosen = jax.lax.top_k(p, top_k)
    n = p.shape[-1]
    f = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, n, dtype=jnp.float32), -2),
                 -2) / top_k                                      # [B, E]
    return n * jnp.sum(jax.lax.stop_gradient(f) * jnp.mean(p, -2), -1)


def _walk(params: dict, tokens: jax.Array, dtype, *, n_head, n_kv_head,
          rope_base, mrope_section, index_heads, topk, eps, top_k,
          expert_offset, positions=None, router_aux_coef=0.0):
    """-> (final hidden states [B, S, D], the layers' balancing terms
    summed, a row [B] f32)."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    b, s = tokens.shape
    pos = positions if positions is not None else jnp.broadcast_to(
        jnp.arange(s)[None, None], (3, b, s))
    x, balance = p["wte"][tokens], jnp.zeros((b,), jnp.float32)
    for _, lp in _layers(p):
        x = x + sparse_attention(
            _rmsnorm(x, lp["norm"], eps), lp, pos, n_head=n_head,
            n_kv_head=n_kv_head, rope_base=rope_base,
            mrope_section=mrope_section, index_heads=index_heads, topk=topk,
            eps=eps)
        xn = _rmsnorm(x, lp["mlp_norm"], eps)
        balance = balance + router_balance(xn, lp, top_k=top_k)
        x = x + routed_experts(xn, lp, top_k=top_k,
                               expert_offset=expert_offset)
    return _rmsnorm(x, p["out_norm"], eps), balance


def hidden(params: dict, tokens: jax.Array, dtype, **kw) -> jax.Array:
    """tokens [B, S] (and ``positions`` [3, B, S]; None: text) -> final
    hidden states [B, S, D] in ``dtype``; with float32 the caller wraps the
    call in ``jax.default_matmul_precision("highest")``."""
    return _walk(params, tokens, dtype, **kw)[0]


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


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] -> logits [..., V_padded] in float32."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["lm_head"].astype(dtype),
                      preferred_element_type=jnp.float32)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "n_kv_head": c.n_kv_head,
            "rope_base": c.rope_base, "mrope_section": c.mrope_section,
            "index_heads": c.index_heads, "topk": c.index_topk,
            "eps": c.rms_eps, "top_k": c.top_k,
            "expert_offset": c.expert_offset,
            "router_aux_coef": c.router_aux_coef}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    hi, di = c["indexer_num_heads"], c["indexer_head_dim"]
    attn = 2 * d * h * hd + 2 * d * kv * hd + 2 * hd
    indexer = d * hi * di + d * di + d * hi + 2 * di
    moe = d * c["num_experts"] \
        + c["experts_held"] * 3 * d * c["moe_intermediate_size"]
    return 2 * vocab_rows * d + d \
        + c["num_hidden_layers"] * (2 * d + attn + indexer + moe)
