"""Granite-4.0-H (``model_type: granitemoehybrid``, no routed experts; the
``modeling_granitemoehybrid.py`` the published checkpoints name) forward
pass, plain: ``jax.numpy`` only, no kernel, nothing of the program
imported.

Per layer, with r = ``residual_multiplier`` and x̂ = RMSNorm(x):

    x = x + r * mixer(x̂);   x = x + r * W_down (silu(x̂ W_gate) * x̂ W_up)

* a ``mamba`` layer (Mamba-2, H heads of P, state N, G groups):
  [z | xBC | dt] = x̂ W_in; xBC = silu(conv(xBC) + b), a causal depthwise
  convolution (token t sees t-K+1 .. t, zeros before the sequence);
  [x | B | C] = xBC; dt = softplus(dt + dt_bias); A = -exp(A_log); then
  ONE TOKEN AT A TIME, a ``lax.scan`` over the sequence and no chunk:

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t     (h [P, N] a head)
      y_t = h_t C_t + D x_t

  y = RMSNorm(y * silu(z)) * w; out y W_out;
* an ``attention`` layer: q of ``n_head`` heads over ``n_kv_head``
  key/value heads (head j reads key/value head j // (n_head / n_kv_head)),
  no rotation, softmax(q k^T * attention_multiplier + causal) v, out W_o.

Embedding rows x ``embedding_multiplier``; logits = W_emb^T RMSNorm(x) /
``logits_scaling``, the head being the embedding.

Reads the parameter dict of ``ray_tpu.models.granite_hybrid.GraniteHybrid``:
``wte``, ``out_norm`` and ``<run>.<kind>.<name>`` stacked over the layers
of a run of like layers, runs in the order of their number. ``W_in`` comes
as its column groups ``w_z``, ``w_xbc``, ``w_dt``, which are put side by
side again here, and the convolution's weight tap-major [K, C].

Departures, the program's and kept so that both sides see one function:

* the vocabulary is a slice (one chip of several): embedding, logits and
  loss are over it;
* ``hidden`` returns the final hidden states ALREADY divided by
  ``logits_scaling``: the harness hands ``head`` no keyword, and a scalar
  commutes with the product (exactly so at the published 8, a power of
  two, in any precision).

In a dtype below float32 the operands of every product are rounded to it
(as the program's are to bf16) while dt, the decays and the state h stay
float32, which is what the configuration states. Attention runs one block
of queries at a time so that S = 4096 fits in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 256


def _rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


def _silu(x):
    xf = x.astype(jnp.float32)
    return (xf / (1.0 + jnp.exp(-xf))).astype(x.dtype)


def causal_conv(x, w, b):
    """x [B, T, C], w [K, C], b [C]: out_t = b + sum_k w[k] x_{t-K+1+k}."""
    taps, t = w.shape[0], x.shape[1]
    out = jnp.zeros(x.shape, jnp.float32) + b.astype(jnp.float32)
    for k in range(taps):
        back = taps - 1 - k                     # how far tap k looks back
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        out = out + shifted.astype(jnp.float32) * w[k].astype(jnp.float32)
    return out.astype(x.dtype)


def recurrence(x, dt, a_log, bm, cm, d):
    """x [B, T, H, P], dt [B, T, H] f32 (after its softplus), B and C
    [B, T, G, N] -> y [B, T, H, P]: the state updated and read once a
    token, float32."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    dtype = x.dtype
    a = -jnp.exp(a_log.astype(jnp.float32))                       # [H]
    to_heads = lambda v: jnp.repeat(v, h // g, axis=2)            # noqa: E731
    xd = (x.astype(jnp.float32) * dt[..., None]).astype(dtype)

    def token(state, tok):
        xd_t, dt_t, b_t, c_t = tok          # [B,H,P] [B,H] [B,H,N] [B,H,N]
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + xd_t.astype(jnp.float32)[..., None] \
            * b_t.astype(jnp.float32)[..., None, :]
        read = state.astype(dtype).astype(jnp.float32)
        y_t = jnp.sum(read * c_t.astype(jnp.float32)[..., None, :], -1)
        return state, y_t

    _, y = jax.lax.scan(
        token, jnp.zeros((b, h, p, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0)
              for v in (xd, dt, to_heads(bm), to_heads(cm))))
    y = jnp.moveaxis(y, 0, 1) + x.astype(jnp.float32) \
        * d.astype(jnp.float32)[:, None]
    return y.astype(dtype)


def mamba_mixer(xn, lp, *, heads, state, groups, eps):
    """x̂ [B, T, D] -> y W_out."""
    b, t, _ = xn.shape
    w_in = jnp.concatenate([lp["w_z"], lp["w_xbc"], lp["w_dt"]], axis=1)
    d_inner = lp["w_z"].shape[1]
    channels = lp["w_xbc"].shape[1]
    proj = xn @ w_in
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:d_inner + channels]
    dt = proj[..., d_inner + channels:]
    xbc = _silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    x = xbc[..., :d_inner].reshape(b, t, heads, d_inner // heads)
    bm = xbc[..., d_inner:d_inner + groups * state].reshape(
        b, t, groups, state)
    cm = xbc[..., d_inner + groups * state:].reshape(b, t, groups, state)
    dt = dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
    dt = jnp.logaddexp(dt, 0.0)                                  # softplus
    y = recurrence(x, dt, lp["A_log"], bm, cm, lp["D"]).reshape(
        b, t, d_inner)
    y = _rmsnorm((y.astype(jnp.float32) * _silu(z).astype(jnp.float32)
                  ).astype(y.dtype), lp["gate_norm"], eps)
    return y @ lp["w_out"]


def attention_mixer(xn, lp, *, n_head, n_kv_head, scale):
    """x̂ [B, S, D] -> concat_h(o_h) W_o, grouped-query, no positions."""
    b, s, _ = xn.shape
    q = (xn @ lp["w_q"]).reshape(b, s, n_head, -1)
    k = (xn @ lp["w_k"]).reshape(b, s, n_kv_head, -1)
    v = (xn @ lp["w_v"]).reshape(b, s, n_kv_head, -1)
    share = n_head // n_kv_head
    # query head j = kv * share + i reads key/value head kv
    q = q.reshape(b, s, n_kv_head, share, -1)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    blk = min(Q_BLOCK, s)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1).astype(jnp.float32)
        sc = jnp.einsum("bqgid,bkgd->bgiqk", qb, kf) * scale
        seen = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("bgiqk,bkgd->bqgid", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))          # [S/blk, B, blk, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, -1).astype(xn.dtype)
    return o @ lp["w_o"]


def _layers(p: dict):
    """[(kind, one layer's parameters)] in the order of the stack."""
    runs = {}
    for name, v in p.items():
        if name[0].isdigit():
            run, kind, leaf = name.split(".", 2)
            runs.setdefault((int(run), kind), {})[leaf] = v
    out = []
    for (_, kind), stacked in sorted(runs.items()):
        n = next(iter(stacked.values())).shape[0]
        out += [(kind, {k: v[i] for k, v in stacked.items()})
                for i in range(n)]
    return out


def hidden(params: dict, tokens: jax.Array, dtype, *, n_head, n_kv_head,
           mamba_heads, mamba_state, mamba_groups, eps, embedding_multiplier,
           residual_multiplier, attention_multiplier,
           logits_scaling) -> jax.Array:
    """tokens [B, S] -> RMSNorm(x) / logits_scaling [B, S, D] in ``dtype``
    (module docstring: why the division is here); with float32 the caller
    wraps the call in ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens] * jnp.asarray(embedding_multiplier, dtype)
    r = jnp.asarray(residual_multiplier, dtype)
    for kind, lp in _layers(p):
        xn = _rmsnorm(x, lp["norm"], eps)
        if kind == "mamba":
            x = x + r * mamba_mixer(xn, lp, heads=mamba_heads,
                                    state=mamba_state, groups=mamba_groups,
                                    eps=eps)
        else:
            x = x + r * attention_mixer(xn, lp, n_head=n_head,
                                        n_kv_head=n_kv_head,
                                        scale=attention_multiplier)
        xn = _rmsnorm(x, lp["mlp_norm"], eps)
        x = x + r * ((_silu(xn @ lp["w_gate"]) * (xn @ lp["w_up"]))
                     @ lp["w_down"])
    return (_rmsnorm(x, p["out_norm"], eps).astype(jnp.float32)
            / logits_scaling).astype(dtype)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] (divided by ``logits_scaling`` already) -> logits
    [..., V] in float32; the head is the embedding."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["wte"].astype(dtype),
                      preferred_element_type=jnp.float32)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"n_head": c.n_head, "n_kv_head": c.n_kv_head,
            "mamba_heads": c.mamba_n_heads, "mamba_state": c.mamba_d_state,
            "mamba_groups": c.mamba_n_groups, "eps": c.rms_eps,
            "embedding_multiplier": c.embedding_multiplier,
            "residual_multiplier": c.residual_multiplier,
            "attention_multiplier": c.attention_multiplier,
            "logits_scaling": c.logits_scaling}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the (tied) embedding."""
    c = sizes
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    mlp = 3 * d * f + d                                  # and its norm
    h = c["mamba_n_heads"]
    d_inner = h * c["mamba_d_head"]
    channels = d_inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    mamba = d * (d_inner + channels + h) + channels * (c["mamba_d_conv"] + 1) \
        + 3 * h + d_inner + d_inner * d + d + mlp
    hd = c["head_dim"]
    attention = d * hd * (2 * c["num_attention_heads"]
                          + 2 * c["num_key_value_heads"]) + d + mlp
    kinds = c["layer_types"]
    return vocab_rows * d + d + kinds.count("mamba") * mamba \
        + kinds.count("attention") * attention
