"""GPT-2 family — the flagship model (BASELINE.md north star: >50% MFU).

TPU-first choices:
- layer params stacked on a leading axis and driven by lax.scan: one
  compiled transformer block regardless of depth (fast compile, XLA
  pipelines the scan).
- vocab padded to a multiple of 128 so the embedding/LM-head matmuls tile
  the MXU exactly.
- flash-attention Pallas kernel on the hot path; jax.checkpoint around the
  block for rematerialisation.
- every parameter carries a logical-axis tuple (see `logical_axes`) that
  AxisRules maps to the dp/fsdp/tp/sp mesh — pure data parallel, ZeRO-3
  style fsdp, megatron tp, and sequence parallel all fall out of the same
  annotations.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import (chunked_head_nll, cross_entropy_loss, flash_attention,
                   gelu, layernorm)
from ..ops.ring_attention import ring_attention


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq: int = 1024
    dropout: float = 0.0          # inference/bench default; train sets >0
    dtype: Any = jnp.bfloat16     # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    use_flash: bool = True
    # scan_layers=True compiles one block body (fast compile, the right
    # default for deep models); False unrolls the layer loop — slower to
    # compile but removes the scan's per-layer residual-stacking
    # dynamic-update-slices (ROADMAP A3 decides between the two on the
    # chip)
    scan_layers: bool = True
    seq_axis: Optional[str] = None  # set to "sp" to use ring attention

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    # ---- presets ----------------------------------------------------------
    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        base = dict(vocab_size=512, n_layer=2, n_head=2, d_model=64,
                    d_ff=256, max_seq=128)
        base.update(kw)            # callers may stretch max_seq etc.
        return GPTConfig(**base)

    @staticmethod
    def small(**kw) -> "GPTConfig":      # GPT-2 124M
        return GPTConfig(**kw)

    @staticmethod
    def medium(**kw) -> "GPTConfig":     # 350M
        return GPTConfig(n_layer=24, n_head=16, d_model=1024, d_ff=4096, **kw)

    @staticmethod
    def large(**kw) -> "GPTConfig":      # 774M
        return GPTConfig(n_layer=36, n_head=20, d_model=1280, d_ff=5120, **kw)

    @staticmethod
    def xl(**kw) -> "GPTConfig":         # 1.5B
        return GPTConfig(n_layer=48, n_head=25, d_model=1600, d_ff=6400, **kw)


class GPT:
    """init/apply pair. Params are a flat dict of stacked arrays."""

    def __init__(self, config: GPTConfig):
        self.config = config

    # ---- parameters --------------------------------------------------------

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        c = self.config
        pd = c.param_dtype
        L, D, F, V, S = c.n_layer, c.d_model, c.d_ff, c.padded_vocab, c.max_seq
        k = jax.random.split(rng, 8)
        std = 0.02
        # residual-path projections scaled per GPT-2 (1/sqrt(2L))
        res_std = std / math.sqrt(2 * L)
        return {
            "wte": jax.random.normal(k[0], (V, D), pd) * std,
            "wpe": jax.random.normal(k[1], (S, D), pd) * std,
            "ln1_g": jnp.ones((L, D), pd), "ln1_b": jnp.zeros((L, D), pd),
            "w_qkv": jax.random.normal(k[2], (L, D, 3 * D), pd) * std,
            "b_qkv": jnp.zeros((L, 3 * D), pd),
            "w_proj": jax.random.normal(k[3], (L, D, D), pd) * res_std,
            "b_proj": jnp.zeros((L, D), pd),
            "ln2_g": jnp.ones((L, D), pd), "ln2_b": jnp.zeros((L, D), pd),
            "w_fc": jax.random.normal(k[4], (L, D, F), pd) * std,
            "b_fc": jnp.zeros((L, F), pd),
            "w_out": jax.random.normal(k[5], (L, F, D), pd) * res_std,
            "b_out": jnp.zeros((L, D), pd),
            "lnf_g": jnp.ones((D,), pd), "lnf_b": jnp.zeros((D,), pd),
        }

    @staticmethod
    def logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
        """Per-param logical axes; leading layer-stack axis is unsharded
        (scan carries it). Mapped to mesh axes by AxisRules."""
        return {
            "wte": ("vocab", "embed"),
            "wpe": (None, "embed"),
            "ln1_g": (None, None), "ln1_b": (None, None),
            "w_qkv": (None, "embed", "heads"),
            "b_qkv": (None, "heads"),
            "w_proj": (None, "heads", "embed"),
            "b_proj": (None, "embed"),
            "ln2_g": (None, None), "ln2_b": (None, None),
            "w_fc": (None, "embed", "mlp"),
            "b_fc": (None, "mlp"),
            "w_out": (None, "mlp", "embed"),
            "b_out": (None, "embed"),
            "lnf_g": (None,), "lnf_b": (None,),
        }

    def param_shardings(self, mesh, rules=None):
        from ..parallel.mesh import AxisRules
        from jax.sharding import NamedSharding

        rules = rules or AxisRules()
        return {
            name: NamedSharding(mesh, rules.mesh_axes(axes))
            for name, axes in self.logical_axes().items()
        }

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(int(math.prod(s.shape)) for s in jax.tree.leaves(shapes))

    def flops_per_token(self, seq: Optional[int] = None) -> int:
        """Forward+backward matmul FLOPs per token (6N rule + attention).

        Attention term: QK^T + PV are each 2·S·D MAC-FLOPs per token per
        layer forward (4·S·D), ×3 for fwd+bwd = 12·S·D, halved for causal
        masking → 6·L·S·D. The benchmark counts the same operations from
        a configuration's sizes (benchmark/lib/flops.py)."""
        c = self.config
        s = c.max_seq if seq is None else seq
        n = self.num_params()
        attn = 6 * c.n_layer * c.d_model * s
        return 6 * n + attn

    # ---- forward -----------------------------------------------------------

    def _dropout(self, x: jax.Array, key: jax.Array) -> jax.Array:
        rate = self.config.dropout
        keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
        return jnp.where(keep, x / jnp.asarray(1.0 - rate, x.dtype),
                         jnp.zeros_like(x))

    def _block(self, x: jax.Array, lp: Dict[str, jax.Array]) -> jax.Array:
        c = self.config
        B, S, D = x.shape
        H, hd = c.n_head, c.head_dim
        # per-layer dropout key rides in the (stacked) layer params so one
        # scanned block body serves every layer
        key = lp.get("_dropout_key")
        drop = c.dropout > 0.0 and key is not None
        if drop:
            k_attn, k_mlp = jax.random.split(key)
        # the two halves of a block are named scopes (metadata only):
        # a device trace charges every operation, forward, backward and
        # recomputed, to the scope in its op_name
        with jax.named_scope("attn"):
            h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
            qkv = (h @ lp["w_qkv"].astype(c.dtype)) \
                + lp["b_qkv"].astype(c.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, S, H, hd)
            k = k.reshape(B, S, H, hd)
            v = v.reshape(B, S, H, hd)
            if c.seq_axis is not None:
                attn = ring_attention(q, k, v, axis_name=c.seq_axis,
                                      causal=True)
            elif c.use_flash:
                attn = flash_attention(q, k, v, causal=True)
            else:
                from ..ops import mha_reference

                attn = mha_reference(q, k, v, causal=True)
            attn = attn.reshape(B, S, D)
            proj = (attn @ lp["w_proj"].astype(c.dtype)) \
                + lp["b_proj"].astype(c.dtype)
            if drop:
                proj = self._dropout(proj, k_attn)
            x = x + proj
        with jax.named_scope("mlp"):
            h = layernorm(x, lp["ln2_g"], lp["ln2_b"])
            h = gelu((h @ lp["w_fc"].astype(c.dtype))
                     + lp["b_fc"].astype(c.dtype))
            out = (h @ lp["w_out"].astype(c.dtype)) \
                + lp["b_out"].astype(c.dtype)
            if drop:
                out = self._dropout(out, k_mlp)
            return x + out

    @staticmethod
    def _remat_policy():
        """Save matmul outputs + flash-attention kernel outputs, recompute
        only the cheap elementwise chain in the backward — full-block remat
        costs +1/3 step FLOPs, which this policy avoids while still
        bounding activation memory."""
        cp = jax.checkpoint_policies
        policy = getattr(cp, "dots_with_no_batch_dims_saveable", None)
        names = getattr(cp, "save_only_these_names", None)
        both = getattr(cp, "save_from_both_policies", None)
        if policy and names and both:
            # see flash_attention._flash_vjp_fwd: saving these means the
            # backward never re-runs the forward kernel
            policy = both(policy, names("flash_out", "flash_lse"))
        return policy

    def _embed(self, wte: jax.Array, wpe: jax.Array, tokens: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
        """Token + position embedding — the single definition all paths
        (apply/loss, loss_pp, actor-pipeline stage 0) share."""
        c = self.config
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        with jax.named_scope("embed"):
            return wte.astype(c.dtype)[tokens] \
                + wpe.astype(c.dtype)[positions]

    def _lm_head(self, head_w: jax.Array, x: jax.Array) -> jax.Array:
        """Tied LM head in bf16 on the MXU fast path, f32 accumulation —
        a f32xf32 matmul here runs at 1/4 MXU rate and doubles HBM
        traffic on the [B,S,V] logits. Single definition for all paths."""
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x,
                              head_w.astype(self.config.dtype),
                              preferred_element_type=jnp.float32)

    def apply(self, params: Dict[str, jax.Array], tokens: jax.Array,
              positions: Optional[jax.Array] = None,
              rng: Optional[jax.Array] = None) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, padded_vocab] (f32)."""
        x = self._backbone(params, tokens, rng, positions=positions)
        return self._lm_head(params["wte"], x)

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array, rng: Optional[jax.Array] = None) -> jax.Array:
        logits = self.apply(params, tokens, rng=rng)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)

    def loss_chunked(self, params: Dict[str, jax.Array], tokens: jax.Array,
                     targets: jax.Array, rng: Optional[jax.Array] = None,
                     num_chunks: int = 8) -> jax.Array:
        """Cross-entropy without materializing the full [B,S,V] f32 logits:
        the LM head + logsumexp run per token-chunk under jax.checkpoint,
        so only per-chunk logits ever exist (fwd and bwd) — e.g. 3.3 GB of
        GPT-2-small logits at B=16,S=1024 become 8 × 412 MB transients.
        The only option once vocab*batch*seq logits stop fitting HBM."""
        x = self._backbone(params, tokens, rng)         # [B,S,D] bf16
        return self._chunked_head_nll(params["wte"], x, targets, num_chunks)

    def _chunked_head_nll(self, wte: jax.Array, x: jax.Array,
                          targets: jax.Array, num_chunks: int) -> jax.Array:
        """Head + token-mean NLL per chunk under jax.checkpoint — shared by
        loss_chunked and loss_pp so the no-full-logits property holds on
        every path (``ops.chunked_head_nll``, which other families reach
        too)."""
        return chunked_head_nll(wte.astype(self.config.dtype), x, targets,
                                num_chunks)

    def loss_pp(self, params: Dict[str, jax.Array], tokens: jax.Array,
                targets: jax.Array, mesh, num_microbatches: int = 0,
                pp_axis: str = "pp", rng: Optional[jax.Array] = None,
                num_chunks: int = 0) -> jax.Array:
        """Pipeline-parallel loss: the layer stack runs as a collective
        microbatch pipeline over the mesh's `pp` axis (see
        parallel/pipeline.py), embedding and LM head replicated across pp
        (their FLOPs are small next to the body; this is the standard
        praxis-style split). Differentiable — jax.grad through this gives
        the reverse pipeline automatically.

        The reference has no pipeline engine to cite; capability-new per
        SURVEY.md §5."""
        from ..parallel.pipeline import pipeline_spmd, stack_stages

        c = self.config
        P_ = mesh.shape[pp_axis]
        M = num_microbatches or max(P_, 2)
        B, S = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible into {M} microbatches")
        x = self._embed(params["wte"], params["wpe"], tokens)
        D = x.shape[-1]
        layer_params = {k: v for k, v in params.items()
                        if k not in ("wte", "wpe", "lnf_g", "lnf_b")}
        if c.dropout > 0.0 and rng is not None:
            # same regularization as the non-pp path: embedding dropout +
            # per-layer residual-branch dropout keys stacked onto the
            # layer params (they stage-split with everything else)
            emb_key, layers_key = jax.random.split(rng)
            x = self._dropout(x, emb_key)
            layer_params["_dropout_key"] = jax.random.split(
                layers_key, c.n_layer)
        stages = stack_stages(layer_params, P_)
        x_mb = x.reshape(M, B // M, S, D)

        def stage_fn(lp, xs):
            return self._run_layers(xs, lp, remat=c.remat, scan=True)

        y_mb = pipeline_spmd(stage_fn, stages, x_mb, mesh, pp_axis=pp_axis)
        x = y_mb.reshape(B, S, D)
        x = layernorm(x, params["lnf_g"], params["lnf_b"])
        # chunked head: pipeline parallelism exists for the large-model
        # regime where full [B,S,V] f32 logits can't live in HBM.
        # M divides B, so it always divides B*S — a safe default chunking.
        return self._chunked_head_nll(params["wte"], x, targets,
                                      num_chunks or M)

    # ---- paged-KV serving path (ray_tpu.serve.llm) -------------------------

    def init_paged_cache(self, num_blocks: int,
                         block_size: int) -> Dict[str, jax.Array]:
        """Block-pool KV cache shared by every resident sequence:
        k/v [L, num_blocks, block_size, H, hd] (GPT has no GQA: KH=H)."""
        c = self.config
        shape = (c.n_layer, num_blocks, block_size, c.n_head, c.head_dim)
        return {"k": jnp.zeros(shape, c.dtype),
                "v": jnp.zeros(shape, c.dtype)}

    def _paged_layer_params(self, params: Dict[str, jax.Array], li: int):
        return {n: params[n][li] for n in
                ("ln1_g", "ln1_b", "w_qkv", "b_qkv", "w_proj", "b_proj",
                 "ln2_g", "ln2_b", "w_fc", "b_fc", "w_out", "b_out")}

    def _paged_mlp(self, x: jax.Array, lp: Dict[str, jax.Array]) -> jax.Array:
        c = self.config
        with jax.named_scope("mlp"):
            h = layernorm(x, lp["ln2_g"], lp["ln2_b"])
            h = gelu((h @ lp["w_fc"].astype(c.dtype))
                     + lp["b_fc"].astype(c.dtype))
            return x + (h @ lp["w_out"].astype(c.dtype)) \
                + lp["b_out"].astype(c.dtype)

    def paged_prefill(self, params: Dict[str, jax.Array],
                      cache: Dict[str, jax.Array], tokens: jax.Array,
                      length: jax.Array, block_row: jax.Array
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Prompt pass at a static bucket shape. tokens [1, S] (padded to
        the bucket), length scalar int32 (true prompt length), block_row
        [M] — the sequence's block table. Writes the prompt's K/V into
        the paged cache and returns (last-real-token logits [V], cache).
        One XLA program per bucket size, not per request."""
        from ..ops import (mha_reference, paged_write_prefill)

        c = self.config
        S = tokens.shape[1]
        H, hd = c.n_head, c.head_dim
        x = self._embed(params["wte"], params["wpe"], tokens)   # [1, S, D]
        kc, vc = cache["k"], cache["v"]
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = self._paged_layer_params(params, li)
            with jax.named_scope("attn"):
                h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
                qkv = (h @ lp["w_qkv"].astype(c.dtype)) \
                    + lp["b_qkv"].astype(c.dtype)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(1, S, H, hd)
                k = k.reshape(1, S, H, hd)
                v = v.reshape(1, S, H, hd)
                with jax.named_scope("paged_attn"):
                    attn = mha_reference(q, k, v, causal=True)
                with jax.named_scope("kv_write"):
                    new_k.append(paged_write_prefill(kc[li], block_row,
                                                     k[0], length))
                    new_v.append(paged_write_prefill(vc[li], block_row,
                                                     v[0], length))
                x = x + attn.reshape(1, S, H * hd) \
                    @ lp["w_proj"].astype(c.dtype) \
                    + lp["b_proj"].astype(c.dtype)
            x = self._paged_mlp(x, lp)
        return self._paged_head(params, x[0], length), \
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    def _paged_head(self, params: Dict[str, jax.Array], x: jax.Array,
                    length: jax.Array) -> jax.Array:
        """Final norm and float32 logits of the last real token of one
        prefilled sequence: x [S, D] -> [V]."""
        with jax.named_scope("lm_head"):
            x = layernorm(x, params["lnf_g"], params["lnf_b"])
            last = jax.lax.dynamic_index_in_dim(
                x, jnp.maximum(length - 1, 0), axis=0, keepdims=False)
            return jnp.einsum("d,vd->v", last.astype(jnp.float32),
                              params["wte"].astype(jnp.float32))

    def paged_prefill_extend(self, params: Dict[str, jax.Array],
                             cache: Dict[str, jax.Array],
                             tokens: jax.Array, start: jax.Array,
                             length: jax.Array, block_row: jax.Array
                             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Suffix prefill over a cached prefix (prefix cache,
        docs/LLM_SERVE.md): positions [0, start) already sit in the
        blocks named by ``block_row`` (written by an earlier request
        that shared them); only the suffix ``tokens`` [1, S] (padded to
        the bucket, true length ``length``) is embedded, written at
        positions start.., and attended causally over the FULL paged
        context. Returns (last-real-token logits [V], cache) — exactly
        :meth:`paged_prefill` output, at suffix cost."""
        from ..ops import paged_attention_prefill, paged_write_prefill

        c = self.config
        S = tokens.shape[1]
        H, hd = c.n_head, c.head_dim
        positions = (start + jnp.arange(S))[None]               # [1, S]
        x = self._embed(params["wte"], params["wpe"], tokens, positions)
        kc, vc = cache["k"], cache["v"]
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = self._paged_layer_params(params, li)
            with jax.named_scope("attn"):
                h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
                qkv = (h @ lp["w_qkv"].astype(c.dtype)) \
                    + lp["b_qkv"].astype(c.dtype)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                with jax.named_scope("kv_write"):
                    kl = paged_write_prefill(kc[li], block_row,
                                             k.reshape(S, H, hd), length,
                                             start)
                    vl = paged_write_prefill(vc[li], block_row,
                                             v.reshape(S, H, hd), length,
                                             start)
                new_k.append(kl)
                new_v.append(vl)
                with jax.named_scope("paged_attn"):
                    attn = paged_attention_prefill(
                        q.reshape(S, H, hd), kl, vl, block_row, start,
                        length)
                x = x + attn.reshape(1, S, H * hd) \
                    @ lp["w_proj"].astype(c.dtype) \
                    + lp["b_proj"].astype(c.dtype)
            x = self._paged_mlp(x, lp)
        return self._paged_head(params, x[0], length), \
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    def paged_decode_step(self, params: Dict[str, jax.Array],
                          cache: Dict[str, jax.Array], tokens: jax.Array,
                          positions: jax.Array, block_rows: jax.Array,
                          active: jax.Array
                          ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """One continuous-batching iteration at a fixed batch shape.
        tokens/positions [B] (position = index the token is written at),
        block_rows [B, M], active [B] bool (padded slots write nothing).
        Returns (logits [B, V] f32, cache). Dense layer loop — each layer
        scatters its cache slice; decode is bandwidth-bound anyway."""
        from ..ops import paged_attention_decode, paged_write_step

        c = self.config
        B = tokens.shape[0]
        H, hd = c.n_head, c.head_dim
        x = self._embed(params["wte"], params["wpe"], tokens[:, None],
                        positions[:, None])[:, 0]              # [B, D]
        kc, vc = cache["k"], cache["v"]
        lengths = positions + 1           # attend over context incl. self
        new_k, new_v = [], []
        for li in range(c.n_layer):
            lp = self._paged_layer_params(params, li)
            with jax.named_scope("attn"):
                h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
                qkv = (h @ lp["w_qkv"].astype(c.dtype)) \
                    + lp["b_qkv"].astype(c.dtype)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                with jax.named_scope("kv_write"):
                    kl = paged_write_step(kc[li], block_rows, positions,
                                          k.reshape(B, H, hd), active)
                    vl = paged_write_step(vc[li], block_rows, positions,
                                          v.reshape(B, H, hd), active)
                new_k.append(kl)
                new_v.append(vl)
                with jax.named_scope("paged_attn"):
                    attn = paged_attention_decode(
                        q.reshape(B, H, hd), kl, vl, block_rows, lengths)
                x = x + attn.reshape(B, H * hd) \
                    @ lp["w_proj"].astype(c.dtype) \
                    + lp["b_proj"].astype(c.dtype)
            x = self._paged_mlp(x, lp)
        with jax.named_scope("lm_head"):
            x = layernorm(x, params["lnf_g"], params["lnf_b"])
            logits = jnp.einsum("bd,vd->bv", x.astype(jnp.float32),
                                params["wte"].astype(jnp.float32))
        return logits, {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    # ---- pipeline-stage slicing (train/pipeline_cgraph.py) -----------------

    def pipeline_stages(self, params: Dict[str, jax.Array],
                        num_chunks: int):
        """Split this GPT into ``num_chunks`` pipeline chunks for the
        actor-hosted engine: chunk 0 carries the embedding, the last
        chunk the final LN + tied LM head + loss, layer blocks divide
        evenly. Returns ``(chunk_fns, chunk_params, tied)`` — with
        ``num_chunks = P * virtual_stages`` the same entry point feeds
        both the plain and the interleaved engine."""
        return gpt_pipeline_stages(self, params, num_chunks)

    def _backbone(self, params: Dict[str, jax.Array], tokens: jax.Array,
                  rng: Optional[jax.Array] = None,
                  positions: Optional[jax.Array] = None) -> jax.Array:
        """Transformer stack up to the final layernorm ([B,S,D], no head)."""
        c = self.config
        B, S = tokens.shape
        x = self._embed(params["wte"], params["wpe"], tokens, positions)
        layer_params = {k: v for k, v in params.items()
                        if k not in ("wte", "wpe", "lnf_g", "lnf_b")}
        if c.dropout > 0.0 and rng is not None:
            # GPT-2 drops embeddings + each residual-branch output; the
            # per-layer keys stack onto the layer params so the scanned
            # body stays a single compiled block
            emb_key, layers_key = jax.random.split(rng)
            x = self._dropout(x, emb_key)
            layer_params["_dropout_key"] = jax.random.split(
                layers_key, c.n_layer)
        x = self._run_layers(x, layer_params, remat=c.remat,
                             scan=c.scan_layers)
        with jax.named_scope("lm_head"):     # the final norm goes with it
            return layernorm(x, params["lnf_g"], params["lnf_b"])

    def _run_layers(self, x: jax.Array, layer_params: Dict[str, jax.Array],
                    *, remat: bool, scan: bool) -> jax.Array:
        """Run ``x`` through the blocks whose parameters are stacked on the
        leading axis of ``layer_params``: the one place a stack of
        ``_block`` is looped over and checkpointed. ``scan`` compiles one
        block body; unrolled is slower to compile and leaves out the
        scan's stacking of each layer's saved residuals. ``remat`` saves
        what ``_remat_policy`` names and recomputes the rest in the
        backward."""
        def body(h, lp):
            return self._block(h, lp), None

        if remat:
            body = jax.checkpoint(body, policy=self._remat_policy())
        if scan:
            return jax.lax.scan(body, x, layer_params)[0]
        for i in range(jax.tree.leaves(layer_params)[0].shape[0]):
            x, _ = body(x, {k: v[i] for k, v in layer_params.items()})
        return x


# ---------------------------------------------------------------------------
# pipeline-stage slicing — the model side of the actor-hosted pipeline
# engine (train/pipeline_cgraph.py). Lives with the model because the
# split points (embedding / layer blocks / LN+head) are model knowledge,
# not engine knowledge.
# ---------------------------------------------------------------------------


def gpt_pipeline_stages(model: "GPT", params: Dict[str, jax.Array],
                        num_chunks: int):
    """Split a GPT into ``num_chunks`` pipeline chunks: chunk 0 carries
    the embedding, the last chunk carries the final LN + tied LM head +
    loss; layer blocks divide evenly. Returns
    ``(chunk_fns, chunk_params, tied)`` where chunk fns are
    ``fn(params, x) -> activation`` for every chunk but the last, which
    is ``fn(params, x, targets) -> scalar loss``; ``tied`` names the
    embedding/LM-head grad-exchange pair in GLOBAL chunk indices."""
    c = model.config
    L = c.n_layer
    if num_chunks < 2:
        raise ValueError("pipeline needs >= 2 chunks")
    if L % num_chunks:
        raise ValueError(
            f"{L} layers not divisible by {num_chunks} chunks")
    per = L // num_chunks
    layer_keys = [k for k in params
                  if k not in ("wte", "wpe", "lnf_g", "lnf_b")]

    def slice_layers(lo, hi):
        return {k: params[k][lo:hi] for k in layer_keys}

    chunk_params = []
    for i in range(num_chunks):
        sp = {"layers": slice_layers(i * per, (i + 1) * per)}
        if i == 0:
            sp["wte"] = params["wte"]
            sp["wpe"] = params["wpe"]
        if i == num_chunks - 1:
            sp["lnf_g"] = params["lnf_g"]
            sp["lnf_b"] = params["lnf_b"]
            if "wte" not in sp:
                sp["head"] = params["wte"]  # tied head needs its own copy
        chunk_params.append(sp)

    def run_layers(model, sp, x):
        # no checkpoint: the engine's own remat recomputes at the stage
        # boundary (train/pipeline_cgraph.py, _make_programs)
        return model._run_layers(x, sp["layers"], remat=False, scan=True)

    def make_first(model):
        def fn(sp, tokens):
            x = model._embed(sp["wte"], sp["wpe"], tokens)
            return run_layers(model, sp, x)
        return fn

    def make_mid(model):
        def fn(sp, x):
            return run_layers(model, sp, x)
        return fn

    def make_last(model):
        def fn(sp, x, targets):
            from ..ops import cross_entropy_loss, layernorm

            h = run_layers(model, sp, x)
            h = layernorm(h, sp["lnf_g"], sp["lnf_b"])
            head = sp.get("head", sp.get("wte"))
            return cross_entropy_loss(model._lm_head(head, h), targets)
        return fn

    chunk_fns = [make_first(model)]
    for _ in range(num_chunks - 2):
        chunk_fns.append(make_mid(model))
    chunk_fns.append(make_last(model))
    # the tied embedding/head copies must exchange grads every step
    tied = [(0, "wte", num_chunks - 1, "head")]
    return chunk_fns, chunk_params, tied
