"""Nemotron-H (``model_type: nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B's
``config.json`` and the family's public modelling code wherever
``config.json`` is silent) forward pass, plain: ``jax.numpy`` only, no
kernel, no chunk, nothing of the program imported. RMSNorm, SiLU, the
causal convolution, the token-by-token state-space recurrence, the blocked
grouped-query attention and the walk over ``<run>.<kind>.<name>`` parameters
are ``reference/granite_hybrid.py``'s, used as they are.

d = ``hidden_size``; x̂ = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, float32
statistics, eps ``layer_norm_epsilon``. A layer is ONE sublayer, by its
character of ``hybrid_override_pattern``:

    x = x + f(x̂);        logits = RMSNorm(x) W_head   (untied)

``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
state N = ``ssm_state_size``, G = ``n_groups`` groups of H / G heads, a
convolution of ``conv_kernel`` taps with bias, no projection bias):

    z | xBC | dt = x̂ W_in           H P, H P + 2 G N, H columns
    xBC = silu(conv(xBC) + b)       causal, depthwise
    x | B | C = xBC                 B, C [G, N]: a group's heads share them
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    per head, TOKEN BY TOKEN (a ``lax.scan``), h [P, N] float32 from zero:
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t
        y_t = h_t C_t + D x_t
    y = RMSNorm_group(y * silu(z)) * w     the gate first, then the norm,
                                           over each group's H P / G channels
    out = y W_out

``*``, attention (``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, query head j on
key/value head j // (H / Hkv), NO rotation, no bias):

    out = softmax(q k^T / sqrt(head_dim) + causal) v  W_o

``E``, experts in a latent of L = ``moe_latent_size``:

    s = sigmoid(x̂ W_r)              all ``n_routed_experts``, float32
    chosen = top ``num_experts_per_tok`` of s + bias      (bias a buffer, 0)
    w = s[chosen] / (sum s[chosen] + 1e-20) * ``routed_scaling_factor``
    u = x̂ W_fc1                     [L]
    r = sum_{e chosen} w_e W_down,e relu(u W_up,e)^2      [L]
    out = r W_fc2 + W_sdown relu(x̂ W_sup)^2   the shared expert on x̂ itself

**Written for the uncut layer and handed the share.** Every function here
takes the counts of what its parameters hold: ``mamba_mixer`` G groups and
H heads (the norm a group), ``attention_mixer`` the query and key/value
heads, ``routed_experts`` the experts in ``lp`` from ``expert_offset``. With
the whole layer's parameters and counts they compute the whole layer; with
one chip's slice (``model_kwargs`` reads the held counts off the program's
configuration) the part of the sum that chip gives: a group with its heads
is independent of the others up to ``W_out``, a query head up to ``W_o``, an
expert up to the weighted sum, and ``W_fc2`` is linear.

Reads the parameter dict of ``ray_tpu.models.nemotron_h.NemotronH``
(``<run>.<kind>.<name>`` stacked over a run's periods, kinds ``mamba``,
``attention``, ``moe``; ``W_in`` as its column groups ``w_z``, ``w_xbc``,
``w_dt``; the convolution tap-major [K, C]).

Departures from the published model, the program's and kept so that both
sides see the same function:

* one chip's share of every layer (above): the absent experts', groups' and
  heads' parts of each sum are left out and the partial result goes on;
* the vocabulary is a slice; embedding, logits and loss are over it;
* no multi-token-prediction layer (``num_nextn_predict_layers`` 0): its
  equations are not in ``config.json``;
* no rotation in attention: the family's public modelling code builds no
  rotary embedding in its attention (``rope_theta``,
  ``partial_rotary_factor`` are keys it does not read); dt is not clamped
  (``time_step_min`` / ``max`` / ``floor`` shape the initial ``dt_bias``
  only). Listed under ``assumed`` in the configuration's file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import (_layers, _rmsnorm, _silu,
                                                attention_mixer, causal_conv,
                                                recurrence)

__all__ = ["hidden", "head", "model_kwargs", "num_params", "mamba_mixer",
           "attention_mixer", "shared_expert", "routed_experts"]


def _relu2(x, w_up, w_down):
    return jnp.square(jnp.maximum(x @ w_up, 0)) @ w_down


def mamba_mixer(xn, lp, *, heads, state, groups, eps):
    """x̂ [B, T, D] -> y W_out, over the ``groups`` groups and ``heads``
    heads that ``lp`` holds."""
    b, t, _ = xn.shape
    d_inner = lp["w_z"].shape[1]
    z = xn @ lp["w_z"]
    xbc = _silu(causal_conv(xn @ lp["w_xbc"], lp["conv_w"], lp["conv_b"]))
    dt = (xn @ lp["w_dt"]).astype(jnp.float32) \
        + lp["dt_bias"].astype(jnp.float32)
    dt = jnp.logaddexp(dt, 0.0)                                  # softplus
    x = xbc[..., :d_inner].reshape(b, t, heads, d_inner // heads)
    bm = xbc[..., d_inner:d_inner + groups * state].reshape(
        b, t, groups, state)
    cm = xbc[..., d_inner + groups * state:].reshape(b, t, groups, state)
    y = recurrence(x, dt, lp["A_log"], bm, cm, lp["D"]).reshape(
        b, t, d_inner)
    gated = (y.astype(jnp.float32) * _silu(z).astype(jnp.float32)
             ).astype(y.dtype).reshape(b, t, groups, d_inner // groups)
    y = _rmsnorm(gated, lp["gate_norm"].reshape(groups, d_inner // groups),
                 eps).reshape(b, t, d_inner)
    return y @ lp["w_out"]


def shared_expert(xn, lp):
    return _relu2(xn, lp["s_up"], lp["s_down"])


def routed_experts(xn, lp, *, top_k, routed_scale, expert_offset=0):
    """x̂ [..., D] -> W_fc2 applied to the part of sum_e w_e expert_e(x̂
    W_fc1) that the experts in ``lp`` (those from ``expert_offset`` on)
    give."""
    s = jax.nn.sigmoid(jnp.einsum("...d,de->...e", xn, lp["w_router"],
                                  preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                              top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * routed_scale
    u = xn @ lp["w_fc1"]

    def add_expert(out, expert):
        e, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e + expert_offset, w, 0.0), -1)
        return out + w_e[..., None] * _relu2(u, w_up, w_down).astype(
            jnp.float32), None

    held = lp["e_up"].shape[0]
    out, _ = jax.lax.scan(add_expert, jnp.zeros(u.shape, jnp.float32),
                          (jnp.arange(held), lp["e_up"], lp["e_down"]))
    return out.astype(xn.dtype) @ lp["w_fc2"]


def hidden(params: dict, tokens: jax.Array, dtype, *, layer_types, n_head,
           n_kv_head, head_dim, mamba_heads, mamba_state, mamba_groups, eps,
           top_k, routed_scale, expert_offset) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``. ``layer_types`` is the
    stack's order of kinds (a run of the program's parameters holds a
    period of TWO kinds, so the names alone do not give it: each kind's
    layers are taken in the order of their run and place); the counts are
    of what ``params`` holds (module docstring)."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    x = p["wte"][tokens]
    of_kind = {}
    for kind, lp in _layers(p):
        of_kind.setdefault(kind, []).append(lp)
    for kind in layer_types:
        lp = of_kind[kind].pop(0)
        xn = _rmsnorm(x, lp["norm"], eps)
        if kind == "mamba":
            x = x + mamba_mixer(xn, lp, heads=mamba_heads, state=mamba_state,
                                groups=mamba_groups, eps=eps)
        elif kind == "attention":
            x = x + attention_mixer(xn, lp, n_head=n_head,
                                    n_kv_head=n_kv_head,
                                    scale=head_dim ** -0.5)
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
    """The share the program's configuration holds: the held counts."""
    c = model_config
    return {"layer_types": c.layer_types, "n_head": c.q_heads_held, "n_kv_head": c.kv_heads_held,
            "head_dim": c.head_dim, "mamba_heads": c.mamba_heads_held,
            "mamba_state": c.mamba_d_state, "mamba_groups": c.groups_held,
            "eps": c.rms_eps, "top_k": c.top_k,
            "routed_scale": c.routed_scale,
            "expert_offset": c.expert_offset}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters of the cut the configuration's ``sizes`` describe (the
    HELD counts of heads, groups and experts), with ``vocab_rows`` rows in
    the embedding and in the head."""
    c = sizes
    d = c["hidden_size"]
    h = c["mamba_n_heads"]
    d_inner = h * c["mamba_d_head"]
    channels = d_inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    mamba = d * (d_inner + channels + h) + channels * (c["mamba_d_conv"] + 1) \
        + 3 * h + d_inner + d_inner * d
    attention = d * c["head_dim"] * (2 * c["num_attention_heads"]
                                     + 2 * c["num_key_value_heads"])
    lat, e = c["moe_latent_size"], c["n_routed_experts"]
    moe = d * e + e + 2 * d * lat \
        + 2 * d * c["moe_shared_expert_intermediate_size"] \
        + c["experts_held"] * 2 * lat * c["moe_intermediate_size"]
    kinds = c["layer_types"]
    return 2 * vocab_rows * d + d + len(kinds) * d \
        + kinds.count("mamba") * mamba \
        + kinds.count("attention") * attention + kinds.count("moe") * moe
