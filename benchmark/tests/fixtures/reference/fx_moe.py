"""Test fixture: the plain forward of a sparse mixture-of-experts
transformer (GPT-2 shaped attention; per layer a softmax router over E
GELU feed-forwards, a token's two largest probabilities renormalised to
sum to one). Every expert is computed for every token and the result
weighted: no capacity, no dispatch tensors, so it stands for the program
only where the program drops no token. Reads the parameter dict of
``ray_tpu.models.moe.MoE`` and nothing else of the program."""
import jax
import jax.numpy as jnp

from benchmark.reference.gpt import _layernorm, head  # noqa: F401  tied head


def _norm(x, g, b, dtype):
    f32 = jnp.float32
    return _layernorm(x.astype(f32), g.astype(f32), b.astype(f32)).astype(dtype)


def hidden(params, tokens, n_head, top_k, dtype):
    """tokens [B, S] -> final hidden states [B, S, D] in ``dtype``."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    B, S = tokens.shape
    D = p["wte"].shape[1]
    x = p["wte"][tokens] + p["wpe"][jnp.arange(S)][None]
    mask = jnp.tril(jnp.ones((S, S), bool))
    for i in range(p["w_qkv"].shape[0]):
        lp = {k: v[i] for k, v in p.items() if k not in (
            "wte", "wpe", "lnf_g", "lnf_b")}
        h = _norm(x, lp["ln1_g"], lp["ln1_b"], dtype)
        q, k, v = (t.reshape(B, S, n_head, D // n_head).astype(jnp.float32)
                   for t in jnp.split(h @ lp["w_qkv"] + lp["b_qkv"], 3, -1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
        s = jnp.where(mask[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + a.astype(dtype).reshape(B, S, D) @ lp["w_proj"] + lp["b_proj"]
        h = _norm(x, lp["ln2_g"], lp["ln2_b"], dtype)
        probs = jax.nn.softmax(h.astype(jnp.float32)
                               @ params["w_router"][i].astype(jnp.float32))
        kth = jax.lax.top_k(probs, top_k)[0][..., -1:]
        w = jnp.where(probs >= kth, probs, 0.0)
        w = (w / w.sum(-1, keepdims=True)).astype(dtype)          # [B,S,E]
        up = jax.nn.gelu(jnp.einsum("bsd,edf->bsef", h, lp["w_up"])
                         + lp["b_up"], approximate=True)
        out = jnp.einsum("bsef,efd->bsed", up, lp["w_down"]) + lp["b_down"]
        x = x + jnp.einsum("bse,bsed->bsd", w, out)
    return _norm(x, p["lnf_g"], p["lnf_b"], dtype)


def model_kwargs(model_config) -> dict:
    return {"n_head": model_config.n_head, "top_k": model_config.top_k}


def num_params(sizes: dict, vocab_rows: int) -> int:
    d, f, e = sizes["d_model"], sizes["d_ff"], sizes["num_experts"]
    block = (3 * d * d + 3 * d) + (d * d + d) + 4 * d + d * e \
        + e * (d * f + f + f * d + d)
    return vocab_rows * d + sizes["max_seq"] * d + sizes["n_layer"] * block \
        + 2 * d
