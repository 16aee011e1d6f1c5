"""MiniCPM-SALA (``model_type: minicpm_sala``; ``config.json``, Lightning
Attention-2 for the ``lightning_*`` keys, MiniCPM4's published
``sparse_config`` and InfLLM-v2 for the ``minicpm4`` layers, the MiniCPM
block for the muP constants) forward pass, plain: ``jax.numpy`` only, no
kernel, nothing of the program imported. Written for the UNCUT layer; the
share a chip holds (which heads, which key/value groups, which hidden units
of the MLP, which rows of the vocabulary) is the parameters' shapes and
``model_kwargs``. SiLU and the walk over ``<run>.<kind>.<name>`` parameters
(``reference/granite_hybrid.py``) and the query blocks' size
(``reference/deepseek_v3.py``) are used as they are.

d = ``hidden_size``, D = ``head_dim`` 128. ``rms(x; w) = x / sqrt(mean(x^2)
+ eps) * w``, float32 statistics, eps ``rms_norm_eps``; ``rms_head`` the
same over a head's D channels, ONE gain [D] for all heads. No bias.

    x_0 = scale_emb wte[ids]
    h   = x + s mixer(rms(x; w_1));   x' = h + s MLP(rms(h; w_2))
          s = scale_depth / sqrt(32), the PUBLISHED depth
    MLP(u) = (SiLU(u W_gate) * u W_up) W_down
    logits = (rms(x_L; w_f) / (d / dim_model_base)) W_head

``lightning`` layer (published head index h of H = ``lightning_nh``, layer
index l of 32), u = rms(x; w_1):

    q = rms_head(u W_q);  k = rms_head(u W_k);  v = u W_v     [heads, D]
    q, k rotated over all D channels: channel i with i + D / 2, angle
        t * rope_theta^(-i / (D / 2))
    lambda_h = exp(-2^(-8 (h + 1) / H) * (1 - l / 31 + 1e-5))
    per head, token by token, S [D, D] float32 from zero:
        S_t = lambda_h S_{t-1} + k_t v_t^T;   o_t = S_t^T q_t / sqrt(D)
    y = (rms_head(o; w_on) * sigmoid(u W_g)) W_o

**The recurrence is a ``lax.scan`` over the tokens**, the state [D, D]
float32 whatever ``dtype`` the matrices are in.

``attn`` layer (``minicpm4``: H query heads on Hkv key/value heads, G = H /
Hkv; no rotation), u = rms(x; w_1):

    q = rms_head(u W_q) [H, D];  k = rms_head(u W_k);  v = u W_v  [Hkv, D]
    a row of at most ``dense_len`` tokens (or of no more blocks than a query
    may take): o = causal softmax(q k^T / sqrt(D)) v. A longer row, per
    query t and key/value group g (blocks of ``block`` keys):
      Kc_g[j] = mean(k_g[stride j .. stride j + size - 1]), j = 0 ..
          S / stride - 2
      p_h[t, j] = softmax_j(q_h[t] . Kc_g[j] / sqrt(D)) over the j whose
          window ends at or before t, float32
      P_g[t, j] = sum over the group's heads of p_h[t, j]
      B_g[t, b] = max of P_g[t, j] over the windows that overlap block b
      the set: the first ``init_blocks`` blocks and the ``local_blocks``
          that end with the query's own FORCED (set above every score),
          with them the blocks of largest B_g up to ``blocks`` in all, by
          ``lax.top_k`` (ties to the lower block), never a block after the
          query's own
      o_h[t] = softmax over the keys s <= t of the set's blocks
          (q_h[t] . k_g[s] / sqrt(D)) v_g[s]
    y = (o * sigmoid(u W_g)) W_o

**Queries are walked in blocks of ``Q_BLOCK``** and the MLP and the head in
chunks of ``TOKEN_CHUNK`` tokens, so that a row of 32 768 fits beside the
job: a block holds its [H, block, S] scores, never [S, S].

Reads the parameter dict of ``ray_tpu.models.minicpm_sala.MiniCPMSALA``
(``<run>.<kind>.<name>`` stacked over a run's layers, kinds ``attn`` and
``lightning``).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share of a tensor-parallel pair: the parameters are those of
  the heads, key/value groups and hidden units held (``head_offset`` says
  which published heads they are: their decays); a sublayer's output is
  their part of ``o W_o`` (``W_down``), and that partial result is what the
  residual takes. Nothing computed spans the pair;
* the vocabulary is a slice, its rows a multiple of 128;
* no gradient passes the selection (InfLLM-v2 has no branch that feeds it
  to the output): its inputs are under ``stop_gradient``;
* what ``config.json`` does not give (the whole ``sparse_config``, the
  decay's rule, per-head norms, sigmoid gates, no feature map, no
  length-dependent logit scale) is listed under ``assumed`` in the
  configuration's file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.deepseek_v3 import Q_BLOCK
from benchmark.reference.granite_hybrid import _layers, _silu

__all__ = ["hidden", "head", "model_kwargs", "num_params", "lightning_mixer",
           "attention_mixer", "lightning_scan", "block_set", "log_decays"]

TOKEN_CHUNK = 4096


def rms(x, w, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x.astype(jnp.float32)))


def _by_tokens(fn, x):
    """fn over x [B, S, ..] in chunks of ``TOKEN_CHUNK`` tokens where S is
    several of them (fn works a token at a time)."""
    b, s = x.shape[:2]
    if s <= TOKEN_CHUNK or s % TOKEN_CHUNK:
        return fn(x)
    cut = jnp.moveaxis(x.reshape((b, s // TOKEN_CHUNK, TOKEN_CHUNK)
                                 + x.shape[2:]), 1, 0)
    out = jax.lax.map(fn, cut)
    return jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])


def rotate(x, base):
    """x [B, S, H, D]: channel i paired with i + D / 2, angle t *
    base^(-i / (D / 2))."""
    half = x.shape[-1] // 2
    theta = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * theta
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    lo, hi = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                           -1).astype(x.dtype)


def log_decays(heads, *, n_head, head_offset, layer, n_layer):
    """log lambda of the published heads ``head_offset`` .. at the published
    layer index ``layer``: -2^(-8 (h + 1) / n_head) (1 - l / (L - 1) +
    1e-5)."""
    h = jnp.arange(head_offset, head_offset + heads, dtype=jnp.float32)
    return -(2.0 ** (-8.0 * (h + 1.0) / n_head)) \
        * (1.0 - layer / (n_layer - 1) + 1e-5)


def lightning_scan(q, k, v, log_decay, scale):
    """q, k, v [B, T, H, D], log_decay [H] -> o [B, T, H, D]: the state
    [D, D] decayed, written and read once a token, float32 sums on the
    VPU."""
    b, t, h, d = q.shape
    lam = jnp.exp(log_decay.astype(jnp.float32))[None, :, None, None]
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731

    def token(s, tok):
        q_t, k_t, v_t = tok                                   # [B, H, D]
        s = lam * s + k_t[..., :, None] * v_t[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2) * scale

    _, o = jax.lax.scan(token, jnp.zeros((b, h, d, d), jnp.float32),
                        (f32(q), f32(k), f32(v)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def lightning_mixer(u, lp, log_decay, *, rope_base, eps):
    """u = rms(x; w_1) [B, T, d] -> the held heads' part of y W_o."""
    b, t, _ = u.shape
    d = lp["q_norm"].shape[-1]
    heads = lambda w: (u @ w).reshape(b, t, -1, d)             # noqa: E731
    q = rotate(rms(heads(lp["w_q"]), lp["q_norm"], eps), rope_base)
    k = rotate(rms(heads(lp["w_k"]), lp["k_norm"], eps), rope_base)
    o = lightning_scan(q, k, heads(lp["w_v"]), log_decay, d ** -0.5)
    o = rms(o, lp["o_norm"], eps).astype(jnp.float32) \
        * _sigmoid(heads(lp["w_g"]))
    return o.astype(u.dtype).reshape(b, t, -1) @ lp["w_o"]


def block_set(qb, kc, t, *, block, blocks, init_blocks, local_blocks, pool,
              scale):
    """qb [B, T, G, D] float32 (one group's queries at positions ``t`` [T]),
    kc [B, J, D] the group's pooled keys -> [B, T, S / block] bool: the
    blocks each query attends over."""
    size, stride = pool
    j = kc.shape[1]
    per = block // stride
    nb = (j + 1) // per
    ends = jnp.arange(j) * stride + size - 1
    seen_j = ends[None, :] <= t[:, None]                       # [T, J]
    sc = jnp.einsum("btgd,bjd->bgtj", qb, kc) * scale
    sc = jnp.where(seen_j, sc, -jnp.inf)
    e = jnp.where(seen_j, jnp.exp(sc - jnp.max(
        jnp.where(seen_j, sc, -1e30), -1, keepdims=True)), 0.0)
    p = jnp.sum(e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30), 1)
    # windows per b - 1 .. per b + per - 1 overlap block b; the window past
    # the row's end (j = J) does not exist
    p = jnp.pad(p, ((0, 0), (0, 0), (1, nb * per - j)))
    score = jnp.max(jnp.stack(
        [p[..., i:i + nb * per:per] for i in range(per + 1)], -1), -1)
    own = t // block
    cols = jnp.arange(nb)
    seen = cols[None, :] <= own[:, None]                       # [T, nb]
    forced = (cols[None, :] < init_blocks) \
        | (cols[None, :] > own[:, None] - local_blocks)
    score = jnp.where(seen, jnp.where(forced, jnp.inf, score), -jnp.inf)
    keep = min(blocks, nb)
    chosen = jax.lax.top_k(score, keep)[1]                     # [B, T, keep]
    bsz, n = score.shape[:2]
    picked = jnp.zeros(score.shape, bool).at[
        jnp.arange(bsz)[:, None, None], jnp.arange(n)[None, :, None],
        chosen].set(True)
    return picked & seen


def attention_mixer(u, lp, *, n_kv_held, block, blocks, init_blocks,
                    local_blocks, pool, dense_len, eps):
    """u = rms(x; w_1) [B, S, d] -> the held heads' part of y W_o."""
    b, s, _ = u.shape
    d = lp["q_norm"].shape[-1]
    heads = lambda w: (u @ w).reshape(b, s, -1, d)             # noqa: E731
    q = rms(heads(lp["w_q"]), lp["q_norm"], eps)
    k = rms(heads(lp["w_k"]), lp["k_norm"], eps)
    v = heads(lp["w_v"])
    h, kv = q.shape[2], n_kv_held
    g = h // kv
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    sparse = s > dense_len and s > blocks * block
    if sparse:
        size, stride = pool
        ks = jax.lax.stop_gradient(kf)
        window = stride * jnp.arange(s // stride - 1)[:, None] \
            + jnp.arange(size)[None, :]                        # [J, size]
        kc = ks[:, window].mean(2)                             # [B,J,Hkv,D]
    blk = min(Q_BLOCK, s)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1).astype(jnp.float32)
        t = lo + jnp.arange(blk)
        causal = t[:, None] >= jnp.arange(s)[None, :]          # [blk, S]
        out = []
        for grp in range(kv):
            qg = qb[:, :, grp * g:(grp + 1) * g]
            allowed = causal[None]
            if sparse:
                picked = block_set(
                    jax.lax.stop_gradient(qg), kc[:, :, grp], t, block=block,
                    blocks=blocks, init_blocks=init_blocks,
                    local_blocks=local_blocks, pool=pool, scale=scale)
                allowed = jnp.repeat(picked, block, -1) & causal[None]
            sc = jnp.einsum("bqgd,bkd->bgqk", qg, kf[:, :, grp]) * scale
            sc = jnp.where(allowed[:, None], sc, -jnp.inf)
            out.append(jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(sc, -1),
                                  vf[:, :, grp]))
        return jnp.concatenate(out, 2)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, d)
    o = (o * _sigmoid(heads(lp["w_g"]))).astype(u.dtype)
    return o.reshape(b, s, h * d) @ lp["w_o"]


def mlp(u, lp):
    return _by_tokens(
        lambda c: (_silu(c @ lp["w_gate"]) * (c @ lp["w_up"])) @ lp["w_down"],
        u)


def hidden(params: dict, tokens: jax.Array, dtype, *, n_head, n_kv_head,
           head_offset, n_layer, rope_base, scale_emb, scale_depth,
           dim_model_base, block, blocks, init_blocks, local_blocks, pool,
           dense_len, eps) -> jax.Array:
    """tokens [B, S] -> rms(x_L; w_f) / (d / dim_model_base) [B, S, d] in
    ``dtype`` (the head's input); with float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens] * jnp.asarray(scale_emb, dtype)
    s = jnp.asarray(scale_depth / n_layer ** 0.5, dtype)
    for layer, (kind, lp) in enumerate(_layers(p)):
        u = rms(x, lp["norm1"], eps)
        if kind == "lightning":
            heads = lp["w_q"].shape[-1] // lp["q_norm"].shape[-1]
            y = lightning_mixer(
                u, lp, log_decays(heads, n_head=n_head,
                                  head_offset=head_offset, layer=layer,
                                  n_layer=n_layer),
                rope_base=rope_base, eps=eps)
        else:
            held = lp["w_q"].shape[-1] // lp["q_norm"].shape[-1]
            y = attention_mixer(
                u, lp, n_kv_held=held * n_kv_head // n_head, block=block,
                blocks=blocks, init_blocks=init_blocks,
                local_blocks=local_blocks, pool=pool, dense_len=dense_len,
                eps=eps)
        x = x + s * y
        x = x + s * mlp(rms(x, lp["norm2"], eps), lp)
    x = rms(x, p["out_norm"], eps)
    return (x.astype(jnp.float32)
            * (dim_model_base / x.shape[-1])).astype(dtype)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [B, S, d] -> logits [B, S, V_padded] in float32, the tokens in
    chunks."""
    w = params["lm_head"].astype(dtype)
    return _by_tokens(lambda c: jnp.einsum(
        "...d,vd->...v", c, w, preferred_element_type=jnp.float32),
        h.astype(dtype))


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "n_kv_head": c.n_kv_head,
            "head_offset": c.head_offset, "n_layer": c.published_n_layer,
            "rope_base": c.rope_base, "scale_emb": c.scale_emb,
            "scale_depth": c.scale_depth,
            "dim_model_base": c.dim_model_base, "block": c.sparse_block,
            "blocks": c.sparse_blocks,
            "init_blocks": c.sparse_init_blocks,
            "local_blocks": c.sparse_window // c.sparse_block,
            "pool": tuple(c.sparse_pool), "dense_len": c.dense_len,
            "eps": c.rms_eps}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the embedding and in the head."""
    c = sizes
    d, f, hd = c["hidden_size"], c["intermediate_held"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = 3 * d * h * hd + 2 * d * kv * hd + 2 * hd
    light = 5 * d * c["lightning_nh"] * c["lightning_head_dim"] \
        + 3 * c["lightning_head_dim"]
    kinds = c["mixer_types"]
    n_attn = kinds.count("minicpm4")
    return 2 * vocab_rows * d + d + len(kinds) * (2 * d + 3 * d * f) \
        + n_attn * attn + (len(kinds) - n_attn) * light
