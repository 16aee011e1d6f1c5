"""Llama-family decoder (rmsnorm + rope + swiglu + GQA).

Backs the BASELINE.md "Llama-2-7B pjit-sharded Serve inference" config.
Same scan-over-stacked-layers + logical-axis design as gpt.py; adds
grouped-query attention (n_kv_head < n_head) and a KV-cache decode path
for the Serve layer.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import (apply_rope, cross_entropy_loss, flash_attention,
                   mha_reference, rmsnorm, rope_cache)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32
    d_model: int = 4096
    d_ff: int = 11008
    max_seq: int = 4096
    rope_base: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    use_flash: bool = True

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=512, n_layer=2, n_head=4, n_kv_head=2,
                    d_model=64, d_ff=128, max_seq=128)
        base.update(kw)            # callers may stretch max_seq etc.
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(n_layer=40, n_head=40, n_kv_head=40, d_model=5120,
                           d_ff=13824, **kw)


class Llama:
    def __init__(self, config: LlamaConfig):
        self.config = config

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        c = self.config
        pd = c.param_dtype
        L, D, F, V = c.n_layer, c.d_model, c.d_ff, c.padded_vocab
        hd, H, KH = c.head_dim, c.n_head, c.n_kv_head
        k = jax.random.split(rng, 10)
        std = 0.02
        res_std = std / math.sqrt(2 * L)
        return {
            "wte": jax.random.normal(k[0], (V, D), pd) * std,
            "attn_norm": jnp.ones((L, D), pd),
            "w_q": jax.random.normal(k[1], (L, D, H * hd), pd) * std,
            "w_k": jax.random.normal(k[2], (L, D, KH * hd), pd) * std,
            "w_v": jax.random.normal(k[3], (L, D, KH * hd), pd) * std,
            "w_o": jax.random.normal(k[4], (L, H * hd, D), pd) * res_std,
            "mlp_norm": jnp.ones((L, D), pd),
            "w_gate": jax.random.normal(k[5], (L, D, F), pd) * std,
            "w_up": jax.random.normal(k[6], (L, D, F), pd) * std,
            "w_down": jax.random.normal(k[7], (L, F, D), pd) * res_std,
            "out_norm": jnp.ones((D,), pd),
            "lm_head": jax.random.normal(k[8], (V, D), pd) * std,
        }

    @staticmethod
    def logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
        return {
            "wte": ("vocab", "embed"),
            "attn_norm": (None, None),
            "w_q": (None, "embed", "heads"),
            "w_k": (None, "embed", "heads"),
            "w_v": (None, "embed", "heads"),
            "w_o": (None, "heads", "embed"),
            "mlp_norm": (None, None),
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed"),
            "out_norm": (None,),
            "lm_head": ("vocab", "embed"),
        }

    def param_shardings(self, mesh, rules=None):
        from jax.sharding import NamedSharding
        from ..parallel.mesh import AxisRules

        rules = rules or AxisRules()
        return {n: NamedSharding(mesh, rules.mesh_axes(a))
                for n, a in self.logical_axes().items()}

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(int(math.prod(s.shape)) for s in jax.tree.leaves(shapes))

    def flops_per_token(self, seq: Optional[int] = None) -> int:
        """Forward+backward matmul FLOPs per token (6N rule + attention),
        the same accounting as GPT.flops_per_token so MFU numbers are
        comparable across models.

        The attention score/value matmuls run at FULL head count even
        under GQA (k/v broadcast to n_head before QK^T / PV), so the
        attention term uses n_head * head_dim, not the smaller KV
        projection width: 6 * L * S * (H * hd), already halved for
        causal masking."""
        c = self.config
        s = c.max_seq if seq is None else seq
        n = self.num_params()
        attn = 6 * c.n_layer * c.n_head * c.head_dim * s
        return 6 * n + attn

    def _block(self, x, lp, cos, sin, positions):
        c = self.config
        B, S, D = x.shape
        H, KH, hd = c.n_head, c.n_kv_head, c.head_dim
        # named scopes as in models/gpt.py: embed, attn, mlp, lm_head, loss
        with jax.named_scope("attn"):
            h = rmsnorm(x, lp["attn_norm"], c.rms_eps)
            q = (h @ lp["w_q"].astype(c.dtype)).reshape(B, S, H, hd)
            k = (h @ lp["w_k"].astype(c.dtype)).reshape(B, S, KH, hd)
            v = (h @ lp["w_v"].astype(c.dtype)).reshape(B, S, KH, hd)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            if KH != H:  # GQA: broadcast kv heads to query heads
                rep = H // KH
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            if c.use_flash:
                attn = flash_attention(q, k, v, causal=True)
            else:
                attn = mha_reference(q, k, v, causal=True)
            x = x + attn.reshape(B, S, H * hd) @ lp["w_o"].astype(c.dtype)
        return self._mlp(x, lp)

    def apply(self, params, tokens, positions=None):
        c = self.config
        B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = self._embed(params, tokens)
        cos, sin = rope_cache(c.max_seq, c.head_dim, c.rope_base)
        lp_names = [n for n, a in self.logical_axes().items()
                    if a[0] is None and len(a) > 1 and n not in ("out_norm",)]
        layer_params = {n: params[n] for n in lp_names}

        def block_fn(x, lp):
            return self._block(x, lp, cos, sin, positions), None

        if c.remat:
            block_fn = jax.checkpoint(block_fn)
        x, _ = jax.lax.scan(block_fn, x, layer_params)
        return self._head(params, x, "bsd,vd->bsv")

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.config.dtype)[tokens]

    def _head(self, params, x, einsum: str):
        """Final norm and float32 logits, for every entry point."""
        with jax.named_scope("lm_head"):
            x = rmsnorm(x, params["out_norm"], self.config.rms_eps)
            return jnp.einsum(einsum, x.astype(jnp.float32),
                              params["lm_head"].astype(jnp.float32))

    def loss(self, params, tokens, targets):
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)

    # ---- paged-KV serving path (ray_tpu.serve.llm) ------------------------

    def init_paged_cache(self, num_blocks: int,
                         block_size: int) -> Dict[str, jax.Array]:
        """Block-pool KV cache: k/v [L, num_blocks, block_size, KH, hd]."""
        c = self.config
        shape = (c.n_layer, num_blocks, block_size, c.n_kv_head, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype),
                "v": jnp.zeros(shape, c.dtype)}

    _PAGED_LP = ("attn_norm", "w_q", "w_k", "w_v", "w_o", "mlp_norm",
                 "w_gate", "w_up", "w_down")

    def _mlp(self, x, lp):
        c = self.config
        with jax.named_scope("mlp"):
            h = rmsnorm(x, lp["mlp_norm"], c.rms_eps)
            gate = jax.nn.silu(h @ lp["w_gate"].astype(c.dtype))
            up = h @ lp["w_up"].astype(c.dtype)
            return x + (gate * up) @ lp["w_down"].astype(c.dtype)

    def paged_prefill(self, params, cache, tokens, length, block_row):
        """Prompt pass at a static bucket shape (see GPT.paged_prefill —
        same contract: tokens [1, S], length scalar, block_row [M] ->
        (last-token logits [V], cache))."""
        from ..ops import paged_write_prefill

        c = self.config
        S = tokens.shape[1]
        H, KH, hd = c.n_head, c.n_kv_head, c.head_dim
        x = self._embed(params, tokens)                        # [1, S, D]
        cos, sin = rope_cache(c.max_seq, hd, c.rope_base)
        kc, vc = cache["k"], cache["v"]
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = {n: params[n][li] for n in self._PAGED_LP}
            with jax.named_scope("attn"):
                h = rmsnorm(x, lp["attn_norm"], c.rms_eps)
                q = (h @ lp["w_q"].astype(c.dtype)).reshape(1, S, H, hd)
                k = (h @ lp["w_k"].astype(c.dtype)).reshape(1, S, KH, hd)
                v = (h @ lp["w_v"].astype(c.dtype)).reshape(1, S, KH, hd)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                with jax.named_scope("kv_write"):
                    new_k.append(paged_write_prefill(kc[li], block_row,
                                                     k[0], length))
                    new_v.append(paged_write_prefill(vc[li], block_row,
                                                     v[0], length))
                if KH != H:
                    rep = H // KH
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                with jax.named_scope("paged_attn"):
                    attn = mha_reference(q, k, v, causal=True)
                x = x + attn.reshape(1, S, H * hd) \
                    @ lp["w_o"].astype(c.dtype)
            x = self._mlp(x, lp)
        return self._last_logits(params, x[0], length), \
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    def _last_logits(self, params, x, length):
        """x [S, D] of one prefilled sequence -> logits [V] of its last
        real token."""
        last = jax.lax.dynamic_index_in_dim(
            x, jnp.maximum(length - 1, 0), axis=0, keepdims=False)
        return self._head(params, last, "d,vd->v")

    def paged_prefill_extend(self, params, cache, tokens, start, length,
                             block_row):
        """Suffix prefill over a cached prefix (see
        GPT.paged_prefill_extend — same contract: tokens [1, S] are the
        suffix only, RoPE'd at absolute positions start.., written into
        ``block_row`` at start.., attended over the full paged context
        incl. the reused [0, start) KV)."""
        from ..ops import paged_attention_prefill, paged_write_prefill

        c = self.config
        S = tokens.shape[1]
        H, KH, hd = c.n_head, c.n_kv_head, c.head_dim
        x = self._embed(params, tokens)                        # [1, S, D]
        cos, sin = rope_cache(c.max_seq, hd, c.rope_base)
        positions = (start + jnp.arange(S))[None]              # [1, S]
        kc, vc = cache["k"], cache["v"]
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = {n: params[n][li] for n in self._PAGED_LP}
            with jax.named_scope("attn"):
                h = rmsnorm(x, lp["attn_norm"], c.rms_eps)
                q = (h @ lp["w_q"].astype(c.dtype)).reshape(1, S, H, hd)
                k = (h @ lp["w_k"].astype(c.dtype)).reshape(1, S, KH, hd)
                v = (h @ lp["w_v"].astype(c.dtype)).reshape(1, S, KH, hd)
                q = apply_rope(q, cos, sin, positions)
                k = apply_rope(k, cos, sin, positions)
                with jax.named_scope("kv_write"):
                    kl = paged_write_prefill(kc[li], block_row, k[0],
                                             length, start)
                    vl = paged_write_prefill(vc[li], block_row, v[0],
                                             length, start)
                new_k.append(kl)
                new_v.append(vl)
                with jax.named_scope("paged_attn"):
                    attn = paged_attention_prefill(q[0], kl, vl, block_row,
                                                   start, length)
                x = x + attn.reshape(1, S, H * hd) \
                    @ lp["w_o"].astype(c.dtype)
            x = self._mlp(x, lp)
        return self._last_logits(params, x[0], length), \
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    def paged_decode_step(self, params, cache, tokens, positions,
                          block_rows, active):
        """One continuous-batching iteration at a fixed batch shape (see
        GPT.paged_decode_step — same contract)."""
        from ..ops import paged_attention_decode, paged_write_step

        c = self.config
        B = tokens.shape[0]
        H, KH, hd = c.n_head, c.n_kv_head, c.head_dim
        x = self._embed(params, tokens)                        # [B, D]
        cos, sin = rope_cache(c.max_seq, hd, c.rope_base)
        kc, vc = cache["k"], cache["v"]
        lengths = positions + 1
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = {n: params[n][li] for n in self._PAGED_LP}
            with jax.named_scope("attn"):
                h = rmsnorm(x, lp["attn_norm"], c.rms_eps)
                q = (h @ lp["w_q"].astype(c.dtype)).reshape(B, 1, H, hd)
                k = (h @ lp["w_k"].astype(c.dtype)).reshape(B, 1, KH, hd)
                v = (h @ lp["w_v"].astype(c.dtype)).reshape(B, 1, KH, hd)
                q = apply_rope(q, cos, sin, positions[:, None])
                k = apply_rope(k, cos, sin, positions[:, None])
                with jax.named_scope("kv_write"):
                    kl = paged_write_step(kc[li], block_rows, positions,
                                          k[:, 0], active)
                    vl = paged_write_step(vc[li], block_rows, positions,
                                          v[:, 0], active)
                new_k.append(kl)
                new_v.append(vl)
                with jax.named_scope("paged_attn"):
                    attn = paged_attention_decode(q[:, 0], kl, vl,
                                                  block_rows, lengths)
                x = x + attn.reshape(B, H * hd) @ lp["w_o"].astype(c.dtype)
            x = self._mlp(x, lp)
        return self._head(params, x, "bd,vd->bv"), \
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    # ---- decode path (Serve) ----------------------------------------------

    def init_cache(self, batch: int) -> Dict[str, jax.Array]:
        c = self.config
        shape = (c.n_layer, batch, c.max_seq, c.n_kv_head, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def decode_step(self, params, cache, tokens):
        """One autoregressive step. tokens [B, 1] -> (logits [B, V], cache).
        Dense-layer loop (no scan) so each layer can dynamic-update its
        cache slice; decode is bandwidth-bound anyway."""
        c = self.config
        B = tokens.shape[0]
        H, KH, hd = c.n_head, c.n_kv_head, c.head_dim
        pos = cache["pos"]                      # [B]
        x = params["wte"].astype(c.dtype)[tokens]  # [B, 1, D]
        cos, sin = rope_cache(c.max_seq, c.head_dim, c.rope_base)
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = {n: params[n][li] for n in
                  ("attn_norm", "w_q", "w_k", "w_v", "w_o", "mlp_norm",
                   "w_gate", "w_up", "w_down")}
            h = rmsnorm(x, lp["attn_norm"], c.rms_eps)
            q = (h @ lp["w_q"].astype(c.dtype)).reshape(B, 1, H, hd)
            k = (h @ lp["w_k"].astype(c.dtype)).reshape(B, 1, KH, hd)
            v = (h @ lp["w_v"].astype(c.dtype)).reshape(B, 1, KH, hd)
            q = apply_rope(q, cos, sin, pos[:, None])
            k = apply_rope(k, cos, sin, pos[:, None])
            # per-batch positions differ: scatter via one_hot multiply
            onehot = jax.nn.one_hot(pos, c.max_seq, dtype=c.dtype)  # [B, S]
            ck = cache["k"][li] * (1 - onehot[:, :, None, None]) \
                + onehot[:, :, None, None] * k
            cv = cache["v"][li] * (1 - onehot[:, :, None, None]) \
                + onehot[:, :, None, None] * v
            new_k.append(ck)
            new_v.append(cv)
            kk, vv = ck, cv
            if KH != H:
                rep = H // KH
                kk = jnp.repeat(kk, rep, axis=2)
                vv = jnp.repeat(vv, rep, axis=2)
            # masked attention over the cache
            scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                                kk.astype(jnp.float32)) / math.sqrt(hd)
            mask = (jnp.arange(c.max_seq)[None, :] <= pos[:, None])
            scores = jnp.where(mask[:, None, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs,
                              vv.astype(jnp.float32)).astype(c.dtype)
            x = x + attn.reshape(B, 1, H * hd) @ lp["w_o"].astype(c.dtype)
            h = rmsnorm(x, lp["mlp_norm"], c.rms_eps)
            gate = jax.nn.silu(h @ lp["w_gate"].astype(c.dtype))
            up = h @ lp["w_up"].astype(c.dtype)
            x = x + (gate * up) @ lp["w_down"].astype(c.dtype)
        x = rmsnorm(x, params["out_norm"], c.rms_eps)
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                            params["lm_head"].astype(jnp.float32))[:, 0]
        cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v), "pos": pos + 1}
        return logits, cache
