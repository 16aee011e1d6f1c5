"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``; SambaY with
differential attention, arXiv:2507.06607; the ``modeling_phi4flash.py`` the
published checkpoint names) forward pass, plain: ``jax.numpy`` only, no
kernel, nothing of the program imported.

32 published layers, l = 0..31; LN is LayerNorm with gain and bias, eps
``layer_norm_eps``; no rotary or other position term; embedding rows
unscaled, the head tied to the embedding, a final LN before it.

    x = x + Mixer_l(LN_a(x));   x = x + W_down (silu(u W_gate) * u W_up),
                                                       u = LN_b(x)

* l even, l <= 16, Mamba-1 (d_inner = 2 hidden, state 16, 4 taps, dt rank
  hidden / 16): [x' | z] = u W_in; x' = silu(conv(x') + b), causal
  depthwise (token t sees t-3 .. t, zeros before the sequence); [r | B | C]
  = x' W_x; dt = softplus(r W_dt + b_dt); A = -exp(A_log); then ONE TOKEN
  AT A TIME, a ``lax.scan`` over the sequence and no chunk:

      h_t = exp(dt_t A) h_{t-1} + (dt_t x'_t) B_t^T       (h [d_inner, 16])
      y_t = h_t C_t + D x'_t

  out (y * silu(z)) W_out. Layer 16 also hands on m = y.
* l odd, l <= 17, differential self-attention; window ``sliding_window``
  for l <= 15 (query i sees keys i - window < j <= i), full causal for l =
  17. q of 40 heads of 64, k and v of 20, with bias. Query heads 2i, 2i+1
  are (q1, q2) of differential head i (20 of them), key heads 2g, 2g+1 (k1,
  k2) of group g = i // 2 (10 of them), value heads 2g, 2g+1 side by side
  its V of 128:

      a = softmax(q1 k1^T / 8 + mask) V - lam softmax(q2 k2^T / 8 + mask) V
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
      lam_init = 0.8 - 0.6 exp(-0.3 l)
      a = RMSNorm_128(a) g (1 - lam_init)

  the 20 heads side by side, then W_o with bias. Layer 17 also hands on
  its k and v.
* l even, l >= 18, gated memory unit: (silu(u W_in) * m) W_out.
* l odd, l >= 19, differential cross-attention: q = u W_q + b alone; keys
  and values are layer 17's; full causal; the same differential form with
  its own lam vectors, sub-norm gain and W_o.

Each score map is formed ONCE here, against the value of 128 (the program
hands its kernels four heads of 64 a differential head).

Reads the parameter dict of ``ray_tpu.models.sambay.SambaY``: ``wte``,
``out_norm_g``, ``out_norm_b`` and ``<run>.<kind>.<name>`` stacked over the
periods of a run of like periods, runs in the order of their number, the
kinds of a period in the order of the layers; ``W_in`` comes as its column
groups ``w_in_x``, ``w_in_z`` and the convolution's weight tap-major [K, C].
Which published layer a layer is (``lam_init`` hangs on it) comes as
``layers``.

Departure, the program's and kept so that both sides see one function: the
vocabulary is a slice (one chip of several): embedding, logits and loss are
over it.

In a dtype below float32 the operands of every product are rounded to it
(as the program's are to bf16) while dt, the decays and the state h stay
float32, which is what the configuration states. Attention runs one block
of queries at a time so that S = 8192 fits in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256
F32 = jnp.float32


def _layernorm(x, g, b, eps):
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32)
            + b.astype(F32)).astype(x.dtype)


def _silu(x):
    xf = x.astype(F32)
    return (xf / (1.0 + jnp.exp(-xf))).astype(x.dtype)


def causal_conv(x, w, b):
    """x [B, T, C], w [K, C], b [C]: out_t = b + sum_k w[k] x_{t-K+1+k}."""
    taps, t = w.shape[0], x.shape[1]
    out = jnp.zeros(x.shape, F32) + b.astype(F32)
    for k in range(taps):
        back = taps - 1 - k                     # how far tap k looks back
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        out = out + shifted.astype(F32) * w[k].astype(F32)
    return out.astype(x.dtype)


def recurrence(x, dt, a_log, bm, cm, d):
    """x [B, T, C], dt [B, T, C] f32 (after its softplus), A_log [C, N], B
    and C [B, T, N], D [C] -> y [B, T, C]: the state updated and read once
    a token, float32."""
    dtype = x.dtype
    a = -jnp.exp(a_log.astype(F32))                              # [C, N]

    def token(state, tok):
        x_t, dt_t, b_t, c_t = tok           # [B, C] [B, C] [B, N] [B, N]
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * x_t.astype(F32))[..., None] \
            * b_t.astype(F32)[:, None, :]
        return state, jnp.sum(state * c_t.astype(F32)[:, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0],) + a.shape, F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    y = jnp.moveaxis(y, 0, 1) + x.astype(F32) * d.astype(F32)
    return y.astype(dtype)


def mamba_mixer(u, lp, *, state, dt_rank):
    """u = LN_a(x) [B, T, D] -> (the mixer's output, y before the gate)."""
    xs = u @ lp["w_in_x"]
    z = u @ lp["w_in_z"]
    xs = _silu(causal_conv(xs, lp["conv_w"], lp["conv_b"]))
    proj = xs @ lp["w_x"]
    r, bm, cm = (proj[..., :dt_rank], proj[..., dt_rank:dt_rank + state],
                 proj[..., dt_rank + state:])
    dt = (r @ lp["w_dt"]).astype(F32) + lp["b_dt"].astype(F32)
    dt = jnp.logaddexp(dt, 0.0)                                  # softplus
    y = recurrence(xs, dt, lp["A_log"], bm, cm, lp["D"])
    gated = (y.astype(F32) * _silu(z).astype(F32)).astype(u.dtype)
    return gated @ lp["w_out"], y


def differential_attention(q, k, v, lp, *, index, window, eps):
    """q [B, S, H, hd], k and v [B, S, KV, hd] -> [B, S, H * hd]: H / 2
    differential heads over KV / 2 groups, each score map once against its
    group's value of 2 hd, one block of queries at a time."""
    b, s, h, hd = q.shape
    nd, g = h // 2, k.shape[2] // 2
    share = nd // g
    # differential head i = group * share + j
    q = q.reshape(b, s, g, share, 2, hd).astype(F32)
    kf = k.reshape(b, s, g, 2, hd).astype(F32)
    vf = v.reshape(b, s, g, 2 * hd).astype(F32)
    blk = min(Q_BLOCK, s)
    scale = 1.0 / math.sqrt(hd)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 1)
        sc = jnp.einsum("bqgjpd,bkgpd->bgjpqk", qb, kf) * scale
        at = (lo + jnp.arange(blk))[:, None] - jnp.arange(s)[None, :]
        seen = at >= 0
        if window is not None:
            seen = seen & (at < window)
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("bgjpqk,bkge->bqgjpe", jax.nn.softmax(sc, -1), vf)

    o = jax.lax.map(rows, jnp.arange(0, s, blk))   # [S/blk, B, blk, g, j, 2, 2hd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, nd, 2, 2 * hd)
    f32 = lambda name: lp[name].astype(F32)                  # noqa: E731
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(f32("lam_q1") * f32("lam_k1"))) \
        - jnp.exp(jnp.sum(f32("lam_q2") * f32("lam_k2"))) + lam_init
    a = o[..., 0, :] - lam * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
    a = a * f32("subln_g") * (1.0 - lam_init)
    return a.astype(v.dtype).reshape(b, s, h * hd)


def attention_mixer(u, lp, *, head_dim, index, window, eps, kv=None):
    """u = LN_a(x) [B, S, D] -> (the mixer's output, k, v); ``kv`` given:
    a cross-decoder layer, which projects the query alone."""
    b, s, _ = u.shape
    proj = lambda w, bias: (u @ lp[w] + lp[bias]).reshape(   # noqa: E731
        b, s, -1, head_dim)
    q = proj("w_q", "b_q")
    k, v = kv if kv is not None else (proj("w_k", "b_k"), proj("w_v", "b_v"))
    a = differential_attention(q, k, v, lp, index=index, window=window,
                               eps=eps)
    return a @ lp["w_o"] + lp["b_o"], k, v


def _layers(p: dict):
    """[(kind, one layer's parameters)] in the order of the stack: the
    runs by their number, a run's periods in turn, a period's kinds in the
    order the program's walker holds them."""
    runs = {}
    for name, v in p.items():
        if name[0].isdigit():
            run, kind, leaf = name.split(".", 2)
            runs.setdefault(int(run), {}).setdefault(kind, {})[leaf] = v
    order = ("mamba", "swa", "mamba_m", "attn_kv", "gmu", "cross")
    out = []
    for _, kinds in sorted(runs.items()):
        period = sorted(kinds, key=order.index)
        n = next(iter(kinds[period[0]].values())).shape[0]
        out += [(kind, {k: v[i] for k, v in kinds[kind].items()})
                for i in range(n) for kind in period]
    return out


def hidden(params: dict, tokens: jax.Array, dtype, *, layers, head_dim,
           state, dt_rank, window, eps) -> jax.Array:
    """tokens [B, S] -> LN(x) [B, S, D] in ``dtype``; with float32 the
    caller wraps the call in ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    m = kv = None
    stack = _layers(p)
    assert len(stack) == len(layers)
    for index, (kind, lp) in zip(layers, stack):
        u = _layernorm(x, lp["norm_g"], lp["norm_b"], eps)
        if kind in ("mamba", "mamba_m"):
            out, y = mamba_mixer(u, lp, state=state, dt_rank=dt_rank)
            if kind == "mamba_m":
                m = y
        elif kind in ("swa", "attn_kv"):
            out, k, v = attention_mixer(
                u, lp, head_dim=head_dim, index=index, eps=eps,
                window=window if kind == "swa" else None)
            if kind == "attn_kv":
                kv = (k, v)
        elif kind == "gmu":
            out = (_silu(u @ lp["w_in"]) * m) @ lp["w_out"]
        else:
            out, _, _ = attention_mixer(u, lp, head_dim=head_dim,
                                        index=index, window=None, eps=eps,
                                        kv=kv)
        x = x + out
        u = _layernorm(x, lp["mlp_norm_g"], lp["mlp_norm_b"], eps)
        x = x + (_silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]
    return _layernorm(x, p["out_norm_g"], p["out_norm_b"], eps)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] -> logits [..., V] in float32; the head is the
    embedding."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["wte"].astype(dtype),
                      preferred_element_type=F32)


def model_kwargs(model_config) -> dict:
    c = model_config
    return {"layers": tuple(c.layers), "head_dim": c.head_dim,
            "state": c.mamba_d_state, "dt_rank": c.mamba_dt_rank,
            "window": c.sliding_window, "eps": c.ln_eps}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe, with
    ``vocab_rows`` rows in the (tied) embedding."""
    c = sizes
    d, f, hd = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    di, n = c["mamba_expand"] * d, c["mamba_d_state"]
    r, taps = c["mamba_dt_rank"], c["mamba_d_conv"]
    common = 3 * d * f + 4 * d                      # the MLP, two LayerNorms
    q = d * hd * c["num_attention_heads"]
    diff = 2 * q + hd * c["num_attention_heads"] + d + 4 * hd + 2 * hd
    kv = 2 * (d * hd + hd) * c["num_key_value_heads"]
    per = {
        "mamba": 2 * d * di + di * (taps + 1) + di * (r + 2 * n) + r * di
        + di + di * n + di + di * d,
        "attention": diff + kv, "gmu": 2 * d * di, "cross": diff}
    half = c["num_hidden_layers_published"] // 2
    total = vocab_rows * d + 2 * d
    for i in c["layers"]:
        kind = ("mamba" if i <= half else "gmu") if i % 2 == 0 else \
            ("attention" if i <= half + 1 else "cross")
        total += per[kind] + common
    return total
