"""GPT-2 (Radford et al. 2019) forward pass, plain.

Pre-LayerNorm blocks, learned positions, GELU (tanh approximation, as the
published checkpoints use), causal softmax attention, head tied to the
token embedding. Reads the parameter dict of ``ray_tpu.models.gpt.GPT``
(layers stacked on a leading axis) and nothing else of the program.

Departure from the published model, the program's and kept so that both
sides see the same function: the vocabulary is padded to a multiple of
128 and the padded rows take part in the softmax (random rows here, since
weights are random).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _layernorm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def hidden(params: dict, tokens: jax.Array, n_head: int, dtype) -> jax.Array:
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``; with
    float32 the caller wraps the call in
    ``jax.default_matmul_precision("highest")``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    B, S = tokens.shape
    D = p["wte"].shape[1]
    hd = D // n_head
    x = p["wte"][tokens] + p["wpe"][jnp.arange(S)][None]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def block(x, lp):
        h = _layernorm(x.astype(jnp.float32), lp["ln1_g"].astype(jnp.float32),
                       lp["ln1_b"].astype(jnp.float32)).astype(dtype)
        qkv = h @ lp["w_qkv"] + lp["b_qkv"]
        q, k, v = (t.reshape(B, S, n_head, hd) for t in
                   jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                       v.astype(jnp.float32)).astype(dtype)
        x = x + a.reshape(B, S, D) @ lp["w_proj"] + lp["b_proj"]
        h = _layernorm(x.astype(jnp.float32), lp["ln2_g"].astype(jnp.float32),
                       lp["ln2_b"].astype(jnp.float32)).astype(dtype)
        h = jax.nn.gelu(h @ lp["w_fc"] + lp["b_fc"], approximate=True)
        return x + h @ lp["w_out"] + lp["b_out"], None

    layers = {k: p[k] for k in ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_proj",
                                "b_proj", "ln2_g", "ln2_b", "w_fc", "b_fc",
                                "w_out", "b_out")}
    x, _ = jax.lax.scan(block, x, layers)
    return _layernorm(x.astype(jnp.float32), p["lnf_g"].astype(jnp.float32),
                      p["lnf_b"].astype(jnp.float32)).astype(dtype)


def head(params: dict, h: jax.Array, dtype) -> jax.Array:
    """hidden [..., D] -> logits [..., V_padded] in float32."""
    return jnp.einsum("...d,vd->...v", h.astype(dtype),
                      params["wte"].astype(dtype),
                      preferred_element_type=jnp.float32)


def model_kwargs(model_config) -> dict:
    return {"n_head": model_config.n_head}


def num_params(sizes: dict, vocab_rows: int) -> int:
    """Parameters a model of the configuration's ``sizes`` has when its
    embedding holds ``vocab_rows`` rows (the program pads the vocabulary):
    the arithmetic the FLOP counts rest on, so a run can check that the
    model it built is the one the sizes describe."""
    from benchmark.lib.flops import gpt_num_params

    return gpt_num_params(dict(sizes, vocab_size=vocab_rows))
