"""Sparse Mixture-of-Experts transformer LM (Mixtral-style) — the `ep`
mesh axis made real.

TPU-first design: routing uses the static dispatch/combine einsum
formulation (Shazeer et al. 2017; GShard) — top-k gating builds dense
[T, E, C] dispatch and combine tensors so every step compiles to fixed
shapes and large MXU einsums; no data-dependent gathers, no dynamic
shapes (XLA cannot tile those). Expert weights carry the "expert"
logical axis, which AxisRules maps onto the mesh's `ep` dimension —
with experts sharded over ep, XLA inserts the all-to-alls over ICI
exactly where the einsums demand them (the scaling-book recipe).

Reference capability note: the reference's MoE support lives in user
code atop torch; this is new TPU-native work per SURVEY.md §5. Attention
reuses the flash kernel (ops/flash_attention.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import flash_attention, gelu, layernorm


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_flash: bool = True

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @staticmethod
    def tiny(**kw) -> "MoEConfig":
        return MoEConfig(vocab_size=512, n_layer=2, n_head=4, d_model=64,
                         d_ff=128, num_experts=4, max_seq=128, **kw)

    @staticmethod
    def small(**kw) -> "MoEConfig":
        return MoEConfig(**kw)


class MoE:
    """init/apply pytree model in the house style (gpt.py/llama.py)."""

    def __init__(self, config: MoEConfig):
        self.config = config

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        c = self.config
        pd = c.param_dtype
        L, D, F, V = c.n_layer, c.d_model, c.d_ff, c.padded_vocab
        E = c.num_experts
        k = jax.random.split(rng, 12)
        std = 0.02
        res_std = std / math.sqrt(2 * L)
        return {
            "wte": jax.random.normal(k[0], (V, D), pd) * std,
            "wpe": jax.random.normal(k[1], (c.max_seq, D), pd) * std,
            "ln1_g": jnp.ones((L, D), pd), "ln1_b": jnp.zeros((L, D), pd),
            "w_qkv": jax.random.normal(k[2], (L, D, 3 * D), pd) * std,
            "b_qkv": jnp.zeros((L, 3 * D), pd),
            "w_proj": jax.random.normal(k[3], (L, D, D), pd) * res_std,
            "b_proj": jnp.zeros((L, D), pd),
            "ln2_g": jnp.ones((L, D), pd), "ln2_b": jnp.zeros((L, D), pd),
            # router + per-expert FFNs: the "expert" axis shards over ep
            "w_router": jax.random.normal(k[4], (L, D, E), pd) * std,
            "w_up": jax.random.normal(k[5], (L, E, D, F), pd) * std,
            "b_up": jnp.zeros((L, E, F), pd),
            "w_down": jax.random.normal(k[6], (L, E, F, D), pd) * res_std,
            "b_down": jnp.zeros((L, E, D), pd),
            "lnf_g": jnp.ones((D,), pd), "lnf_b": jnp.zeros((D,), pd),
        }

    @staticmethod
    def logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
        return {
            "wte": ("vocab", "embed"), "wpe": (None, "embed"),
            "ln1_g": (None, None), "ln1_b": (None, None),
            "w_qkv": (None, "embed", "heads"), "b_qkv": (None, "heads"),
            "w_proj": (None, "heads", "embed"), "b_proj": (None, None),
            "ln2_g": (None, None), "ln2_b": (None, None),
            "w_router": (None, "embed", None),
            "w_up": (None, "expert", "embed", "mlp"),
            "b_up": (None, "expert", "mlp"),
            "w_down": (None, "expert", "mlp", "embed"),
            "b_down": (None, "expert", "embed"),
            "lnf_g": (None,), "lnf_b": (None,),
        }

    def param_shardings(self, mesh, rules=None):
        from jax.sharding import NamedSharding

        from ..parallel.mesh import AxisRules

        rules = rules or AxisRules()
        return {n: NamedSharding(mesh, rules.mesh_axes(a))
                for n, a in self.logical_axes().items()}

    def num_params(self) -> int:
        return sum(int(v.size) for v in jax.eval_shape(
            self.init, jax.random.PRNGKey(0)).values())

    # -- MoE layer ---------------------------------------------------------

    def _moe_ffn(self, x: jax.Array, lp: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, jax.Array]:
        """x [B, S, D] -> (out [B, S, D], aux_loss scalar). Static-shape
        top-k dispatch: tokens over capacity are DROPPED (zero combine
        weight) and pass through the residual — standard GShard/Switch
        behavior that keeps shapes compile-time constant."""
        c = self.config
        B, S, D = x.shape
        T = B * S
        E, K = c.num_experts, c.top_k
        cap = max(1, int(c.capacity_factor * T * K / E))
        xt = x.reshape(T, D)
        logits = (xt @ lp["w_router"].astype(jnp.float32)
                  if lp["w_router"].dtype != jnp.float32
                  else xt.astype(jnp.float32) @ lp["w_router"])  # [T, E] f32
        probs = jax.nn.softmax(logits, axis=-1)
        # aux load-balancing loss (Switch Transformer eq. 4): mean prob x
        # mean assignment fraction per expert, scaled by E
        top_w, top_e = jax.lax.top_k(probs, K)           # [T, K]
        assign = jax.nn.one_hot(top_e, E, dtype=jnp.float32)  # [T, K, E]
        frac_tokens = assign.sum(axis=1).mean(axis=0)    # [E]
        frac_probs = probs.mean(axis=0)                  # [E]
        aux = c.aux_loss_coeff * E * jnp.sum(frac_tokens * frac_probs)
        # position of each (token, k) within its expert's capacity buffer
        pos = (jnp.cumsum(assign.reshape(T * K, E), axis=0)
               - assign.reshape(T * K, E)).reshape(T, K, E)
        pos = jnp.sum(pos * assign, axis=-1)             # [T, K]
        keep = (pos < cap) & (top_w > 0)
        top_w = jnp.where(keep, top_w, 0.0)
        # renormalize kept weights so each token's routes sum to 1
        denom = jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-9)
        top_w = top_w / denom
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                                dtype=jnp.float32)[..., :cap]  # [T, K, C]
        # combine [T, E, C] = sum_k weight_k * onehot(expert_k, pos_k)
        combine = jnp.einsum("tke,tkc,tk->tec", assign, pos_oh, top_w)
        dispatch = (combine > 0).astype(c.dtype)
        # expert compute: three big einsums, all static shapes
        ein = jnp.einsum("tec,td->ecd", dispatch, xt.astype(c.dtype))
        h = gelu(jnp.einsum("ecd,edf->ecf", ein, lp["w_up"].astype(c.dtype))
                 + lp["b_up"].astype(c.dtype)[:, None, :])
        eout = jnp.einsum("ecf,efd->ecd", h, lp["w_down"].astype(c.dtype)) \
            + lp["b_down"].astype(c.dtype)[:, None, :]
        out = jnp.einsum("tec,ecd->td", combine.astype(c.dtype), eout)
        return out.reshape(B, S, D), aux

    def _block(self, x: jax.Array, lp: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, jax.Array]:
        c = self.config
        B, S, D = x.shape
        h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
        qkv = (h @ lp["w_qkv"].astype(c.dtype)) + lp["b_qkv"].astype(c.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        H, hd = c.n_head, c.head_dim
        shp = lambda t: t.reshape(B, S, H, hd)  # noqa: E731
        if c.use_flash:
            attn = flash_attention(shp(q), shp(k), shp(v), causal=True)
        else:
            from ..ops import mha_reference

            attn = mha_reference(shp(q), shp(k), shp(v), causal=True)
        attn = attn.reshape(B, S, D)
        x = x + (attn @ lp["w_proj"].astype(c.dtype)) \
            + lp["b_proj"].astype(c.dtype)
        h = layernorm(x, lp["ln2_g"], lp["ln2_b"])
        ffn, aux = self._moe_ffn(h, lp)
        return x + ffn, aux

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """tokens [B, S] -> (logits [B, S, V] f32, aux_loss scalar)."""
        c = self.config
        B, S = tokens.shape
        x = params["wte"].astype(c.dtype)[tokens] \
            + params["wpe"].astype(c.dtype)[jnp.arange(S)][None, :]
        aux_total = jnp.float32(0.0)
        layer_params = {n: v for n, v in params.items()
                        if n not in ("wte", "wpe", "lnf_g", "lnf_b")}
        for i in range(c.n_layer):
            lp = {n: v[i] for n, v in layer_params.items()}
            x, aux = self._block(x, lp)
            aux_total = aux_total + aux
        x = layernorm(x, params["lnf_g"], params["lnf_b"])
        logits = jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(c.dtype),
                            preferred_element_type=jnp.float32)
        return logits, aux_total

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        from ..ops import cross_entropy_loss

        logits, aux = self.apply(params, tokens)
        return cross_entropy_loss(logits, targets) + aux
