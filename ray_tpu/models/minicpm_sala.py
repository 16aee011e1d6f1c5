"""MiniCPM-SALA shaped decoder (``model_type: minicpm_sala``), training path:
a dense stack whose layers differ in the MIXER, one InfLLM-v2 sparse
attention layer (``minicpm4``) to three Lightning linear-attention layers
(``lightning-attn``) in the published order, pre-norm, a gated MLP after
every mixer, muP scales. (The published order is not periodic: 8 attention
layers among 24, ``_PUBLISHED_MIXERS``; layers 0-3 are one to three.)

    x_0    = scale_emb * wte[ids]
    h      = x + s * mixer(rms(x; w_1)),   s = scale_depth / sqrt(32), the
    x'     = h + s * mlp(rms(h; w_2))      PUBLISHED depth whatever the cut
    mlp(u) = (silu(u W_gate) * u W_up) W_down
    logits = (rms(x_L; w_f) / (d_model / dim_model_base)) W_head    untied

``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32 statistics;
``rms_head`` the same over a head's channels with ONE gain [head_dim] for
all heads. No bias anywhere.

* ``lightning`` mixer (H heads of d; u = rms(x; w_1)):

      q = rms_head(u W_q),  k = rms_head(u W_k),  v = u W_v,  g = u W_g
      q, k rotated over all d channels (channel i with i + d / 2, angle
      t * rope_base^(-2 i / d))
      per head h, S [d, d] float32 from zero:
          S_t = lambda_h S_{t-1} + k_t v_t^T;   o_t = S_t^T q_t / sqrt(d)
      lambda_h = exp(-2^(-8 (h + 1) / H) * (1 - l / 31 + 1e-5)) for the
      PUBLISHED head index h of H and layer index l of 32: a constant of
      the head and layer, no parameter (``ops.lightning_attention``)
      y = (rms_head(o; w_on) * sigmoid(g)) W_o

* ``attn`` mixer (H query heads on Hkv key/value heads of d):

      q = rms_head(u W_q),  k = rms_head(u W_k),  v = u W_v,  g = u W_g
      no rotation. A row of at most ``dense_len`` tokens attends causally
      over everything; a longer one over the ``sparse_blocks`` blocks of
      ``sparse_block`` keys its key/value GROUP selects by its own heads'
      scores on mean-pooled keys (``ops.block_sparse_attention``; no
      parameter, no gradient through the selection)
      y = (o * sigmoid(g)) W_o

**A share of a tensor-parallel group** (the usual Megatron split).
``heads_held`` of each mixer's ``n_head`` heads from ``head_offset`` are
here, whole key/value groups of them (``heads_held`` a multiple of the
group), and ``ff_held`` of the MLP's ``d_ff`` hidden units from
``ff_offset``: the matching columns of ``W_q``, ``W_k``, ``W_v``, ``W_g``,
``W_gate``, ``W_up`` and rows of ``W_o``, ``W_down``; each held head keeps
its PUBLISHED decay. The norms, their gains and the model's width are whole.
A sublayer's output is then its own heads' (hidden units') part of ``o W_o``
(``W_down``): that partial result is what the residual takes and what goes
on, and nothing stands in for the other chip. Nothing a chip computes spans
the group: the q/k and output norms are a head's, the selection's sum over
heads a key/value group's. ``held_share`` cuts a whole model's parameters to
a share's.

``vocab_size`` is the vocabulary this chip holds: embedding, head, logits
and loss are over it. The head and the loss are walked in chunks of
``head_chunk`` tokens (``ops.chunked_head_nll``) wherever a row is several
chunks long: at 32 768 tokens x 9216 rows a whole row's float32 logits and
their cotangent are 2.4 GB.

The stack is walked by ``models/stack.py``: kinds ``attn`` and
``lightning`` cut into runs of like layers (1, then 3 scanned), parameters
one flat dict: ``wte``, ``lm_head``, ``out_norm`` and
``<run>.<kind>.<name>`` stacked over the run's layers; a Lightning layer's
decays are its entry of the walker's ``layer_xs``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import (apply_rope, block_sparse_attention, chunked_head_nll,
                   cross_entropy_loss, lightning_attention, rmsnorm,
                   rmsnorm_then_gate, rope_cache)
from .stack import draw_params, period_runs, vocab_row_shardings, walk_stack

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``: the attention layer the selection (one byte a query
# and key block), the masked kernels' output and row statistics, and on a
# short row the flash kernels' (Keye's keep-set: neither the selection nor
# the forward kernel runs again); a Lightning layer its input alone.
_REMAT_SAVE = {
    "attn": ("sparse_mask", "sparse_out", "sparse_lse", "flash_out",
             "flash_lse"),
    "lightning": ()}

# ``mixer_types`` of the published config.json: a for ``minicpm4``, l for
# ``lightning-attn``; 8 to 24, in no fixed period
_PUBLISHED_MIXERS = "a" + "l" * 8 + "a" + "l" * 6 + "aa" + "l" * 4 + "a" \
    + "l" * 6 + "aaa"
_PUBLISHED_N_LAYER = len(_PUBLISHED_MIXERS)        # 32


def _layer_types(n_layer: int) -> Tuple[str, ...]:
    """The first ``n_layer`` layers of the published order."""
    return tuple({"a": "attn", "l": "lightning"}[m]
                 for m in _PUBLISHED_MIXERS[:n_layer])


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448           # the ids held here
    layer_types: Tuple[str, ...] = _layer_types(_PUBLISHED_N_LAYER)
    published_n_layer: int = _PUBLISHED_N_LAYER   # s and the decays read it
    d_model: int = 4096
    d_ff: int = 16384
    ff_held: Optional[int] = None     # hidden units of the MLP here; None: all
    ff_offset: int = 0
    # heads of a WHOLE layer, both mixers, and the share of them held here
    n_head: int = 32
    n_kv_head: int = 2                # the attention layer's, a whole layer's
    heads_held: Optional[int] = None  # None: all
    head_offset: int = 0
    head_dim: int = 128
    rope_base: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # InfLLM-v2's selection (MiniCPM4's published ``sparse_config``)
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    sparse_pool: Tuple[int, int] = (32, 16)   # kernel_size, kernel_stride
    dense_len: int = 8192
    lightning_chunk: int = 256
    head_chunk: int = 4096            # tokens a chunk of the head and loss
    rms_eps: float = 1e-6
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.layer_types) - {"attn", "lightning"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.n_head % self.n_kv_head or self.heads % self.group:
            raise ValueError(
                f"{self.heads} of {self.n_head} heads on {self.n_kv_head} "
                "key/value heads: a share holds whole groups")
        if self.head_offset % self.group \
                or not 0 <= self.head_offset <= self.n_head - self.heads:
            raise ValueError(
                f"heads {self.head_offset}..{self.head_offset + self.heads} "
                f"of {self.n_head} in groups of {self.group}")
        if not 0 <= self.ff_offset <= self.d_ff - self.ff:
            raise ValueError(
                f"hidden units {self.ff_offset}..{self.ff_offset + self.ff} "
                f"of {self.d_ff}")

    @property
    def group(self) -> int:
        """Query heads to a key/value head."""
        return self.n_head // self.n_kv_head

    @property
    def heads(self) -> int:
        """Heads of each mixer held here."""
        return self.n_head if self.heads_held is None else self.heads_held

    @property
    def kv_heads(self) -> int:
        """Key/value heads of the attention layer held here."""
        return self.heads // self.group

    @property
    def ff(self) -> int:
        """Hidden units of the MLP held here."""
        return self.d_ff if self.ff_held is None else self.ff_held

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_n_layer)

    @property
    def sparse_blocks(self) -> int:
        """Blocks a query attends over: ``topk`` beside the window's."""
        return self.sparse_topk + self.sparse_window // self.sparse_block

    def log_decays(self, layer: int) -> np.ndarray:
        """log lambda of the heads HELD, at the published layer index
        ``layer``: -2^(-8 (h + 1) / n_head) (1 - l / (L - 1) + 1e-5)."""
        h = np.arange(self.head_offset, self.head_offset + self.heads,
                      dtype=np.float64)
        slope = 2.0 ** (-8.0 * (h + 1.0) / self.n_head)
        return (-slope * (1.0 - layer / (self.published_n_layer - 1) + 1e-5)
                ).astype(np.float32)

    @staticmethod
    def tiny(**kw) -> "MiniCPMSALAConfig":
        """Published head size (128 shapes both kernel routes), everything
        else small: one period, four heads on two key/value heads, a
        selection that decides something on a row of 256 (blocks of 16, 6
        of them, a window of 2 blocks, pooling 8 / 4, dense up to 64); the
        head's input divided by 4, as the published 16."""
        base = dict(vocab_size=512, d_model=64, d_ff=128, n_head=4,
                    n_kv_head=2, layer_types=_layer_types(4),
                    sparse_block=16, sparse_topk=4, sparse_window=32,
                    sparse_pool=(8, 4), dense_len=64, lightning_chunk=128,
                    head_chunk=64, dim_model_base=16)
        base.update(kw)
        return MiniCPMSALAConfig(**base)

    @staticmethod
    def minicpm_sala_9b(n_layer: Optional[int] = None,
                        **kw) -> "MiniCPMSALAConfig":
        """openbmb/MiniCPM-SALA ``config.json``, every published width;
        ``n_layer`` keeps the first layers of the published order."""
        return MiniCPMSALAConfig(
            layer_types=_layer_types(n_layer or _PUBLISHED_N_LAYER), **kw)


# The columns (axis -1) or rows (axis -2) of a layer's parameters that belong
# to heads ("h") or to the MLP's hidden units ("ff"), by the parameter's
# name; the attention layer's ``w_k`` and ``w_v`` belong to key/value heads.
_HELD_AXES = {
    "w_q": (-1, "h"), "w_k": (-1, "h"), "w_v": (-1, "h"), "w_g": (-1, "h"),
    "w_o": (-2, "h"),
    "w_gate": (-1, "ff"), "w_up": (-1, "ff"), "w_down": (-2, "ff")}


class MiniCPMSALA:
    """init / loss pytree model in the house style (olmo_hybrid.py)."""

    def __init__(self, config: MiniCPMSALAConfig):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            config.layer_types)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones)."""
        c = self.config
        d, f, hd = c.d_model, c.ff, c.head_dim
        hw, kw = c.heads * hd, c.kv_heads * hd
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        mlp = {"norm1": ((d,), None), "norm2": ((d,), None),
               "w_gate": ((d, f), std), "w_up": ((d, f), std),
               "w_down": ((f, d), res)}
        kinds = {
            "attn": dict({
                "w_q": ((d, hw), std), "w_k": ((d, kw), std),
                "w_v": ((d, kw), std), "w_g": ((d, hw), std),
                "q_norm": ((hd,), None), "k_norm": ((hd,), None),
                "w_o": ((hw, d), res)}, **mlp),
            "lightning": dict({
                "w_q": ((d, hw), std), "w_k": ((d, hw), std),
                "w_v": ((d, hw), std), "w_g": ((d, hw), std),
                "q_norm": ((hd,), None), "k_norm": ((hd,), None),
                "o_norm": ((hd,), None), "w_o": ((hw, d), res)}, **mlp),
        }
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), None)}
        for i, ((kind,), n) in enumerate(self.runs):
            for name, (shape, how) in kinds[kind].items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, every norm's gain 1."""
        return draw_params(self._shapes(), rng, self.config.param_dtype, 0)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    def held_share(self, whole: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """The parameters of this share (``heads_held`` heads from
        ``head_offset`` with their key/value heads, ``ff_held`` hidden units
        from ``ff_offset``) out of those of the whole layer: the held
        columns (rows of ``W_o``, ``W_down``), everything else as it is."""
        c = self.config
        hd = c.head_dim
        spans = {"h": (c.head_offset * hd, c.heads * hd),
                 "kv": (c.head_offset // c.group * hd, c.kv_heads * hd),
                 "ff": (c.ff_offset, c.ff)}

        def cut(name, x):
            _, _, leaf = name.rpartition(".")
            if leaf not in _HELD_AXES or "." not in name:
                return x
            axis, what = _HELD_AXES[leaf]
            if ".attn." in name and leaf in ("w_k", "w_v"):
                what = "kv"
            start, width = spans[what]
            return jax.lax.slice_in_dim(x, start, start + width,
                                        axis=x.ndim + axis)

        return {n: cut(n, x) for n, x in whole.items()}

    # -- layers ------------------------------------------------------------

    def _heads(self, u, lp, name: str, heads: int, norm: Optional[str]):
        """u W_<name> as [B, S, heads, head_dim], normed a head where
        ``norm`` names the gain."""
        c = self.config
        b, s, _ = u.shape
        t = (u @ lp[name].astype(c.dtype)).reshape(b, s, heads, c.head_dim)
        return t if norm is None else rmsnorm(t, lp[norm], c.rms_eps)

    def _lightning_mixer(self, x, lp, log_decay, rope):
        """The residual x -> the held heads' part of the Lightning
        sublayer's output."""
        c = self.config
        b, s, _ = x.shape
        h, hd = c.heads, c.head_dim
        with jax.named_scope("mixer"):
            u = rmsnorm(x, lp["norm1"], c.rms_eps)
            q = apply_rope(self._heads(u, lp, "w_q", h, "q_norm"), *rope)
            k = apply_rope(self._heads(u, lp, "w_k", h, "k_norm"), *rope)
            v = self._heads(u, lp, "w_v", h, None)
            gate = self._heads(u, lp, "w_g", h, None)
        with jax.named_scope("scan"):
            o = lightning_attention(q, k, v, log_decay, scale=hd ** -0.5,
                                    chunk=c.lightning_chunk)
        with jax.named_scope("mixer"):
            o = rmsnorm_then_gate(o, gate, lp["o_norm"], c.rms_eps)
            return o.reshape(b, s, h * hd) @ lp["w_o"].astype(c.dtype)

    def _attn_mixer(self, x, lp):
        """-> the held heads' part of the attention sublayer's output."""
        c = self.config
        b, s, _ = x.shape
        h, kv, hd = c.heads, c.kv_heads, c.head_dim
        with jax.named_scope("attn"):
            u = rmsnorm(x, lp["norm1"], c.rms_eps)
            q = self._heads(u, lp, "w_q", h, "q_norm")
            k = self._heads(u, lp, "w_k", kv, "k_norm")
            v = self._heads(u, lp, "w_v", kv, None)
            gate = self._heads(u, lp, "w_g", h, None)
            o = block_sparse_attention(
                q, k, v, block=c.sparse_block, blocks=c.sparse_blocks,
                init_blocks=c.sparse_init_blocks,
                local_blocks=c.sparse_window // c.sparse_block,
                pool=c.sparse_pool, dense_len=c.dense_len)
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(c.dtype)
            return o.reshape(b, s, h * hd) @ lp["w_o"].astype(c.dtype)

    def _mlp(self, x, lp):
        """-> the held hidden units' part of the MLP sublayer's output."""
        c, dt = self.config, self.config.dtype
        u = rmsnorm(x, lp["norm2"], c.rms_eps)
        return (jax.nn.silu(u @ lp["w_gate"].astype(dt))
                * (u @ lp["w_up"].astype(dt))) @ lp["w_down"].astype(dt)

    def _block(self, kind: str, x, lp, log_decay, rope):
        """One layer: pre-norm, both sublayers' outputs times the depth
        scale."""
        c, dt = self.config, self.config.dtype
        s = jnp.asarray(c.residual_scale, dt)
        y = self._attn_mixer(x, lp) if kind == "attn" else \
            self._lightning_mixer(x, lp, log_decay, rope)
        with jax.named_scope("attn" if kind == "attn" else "mixer"):
            x = x + s * y
        with jax.named_scope("mlp"):
            return x + s * self._mlp(x, lp)

    def _layer_xs(self):
        """The walker's constants a layer: a Lightning layer's log decays
        [heads held], by its published layer index."""
        out, at = [], 0
        for (kind,), n in self.runs:
            out.append({kind: jnp.asarray(np.stack(
                [self.config.log_decays(at + i) for i in range(n)]))})
            at += n
        return out

    def hidden(self, params: Dict[str, jax.Array],
               tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> the final norm's output [B, S, D], divided by
        ``d_model / dim_model_base`` (the head's input)."""
        c = self.config
        with jax.named_scope("embed"):
            x = params["wte"].astype(c.dtype)[tokens] \
                * jnp.asarray(c.scale_emb, c.dtype)
        with jax.named_scope("mixer"):
            rope = rope_cache(tokens.shape[1], c.head_dim, c.rope_base)
        x, _ = walk_stack(
            x, self.runs, params,
            lambda kind, h, p, side, decay: (
                self._block(kind, h, p, decay, rope), {}),
            [_REMAT_SAVE[period[0]] for period, _ in self.runs],
            model="minicpm_sala", layer_xs=self._layer_xs(),
            facts={"heads": [c.heads, c.n_head],
                   "kv_heads": [c.kv_heads, c.n_kv_head],
                   "head_offset": c.head_offset,
                   "ff": [c.ff, c.d_ff], "ff_offset": c.ff_offset})
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = rmsnorm(x, params["out_norm"], c.rms_eps)
            return (x.astype(jnp.float32)
                    * (c.dim_model_base / c.d_model)).astype(c.dtype)

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        x = self.hidden(params, tokens)
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x,
                              params["lm_head"].astype(self.config.dtype),
                              preferred_element_type=jnp.float32)

    def head_chunks(self, tokens: int) -> int:
        """Chunks the head and the loss are walked in: whole chunks of
        ``head_chunk`` tokens, 1 where the tokens are no several of them."""
        chunk = self.config.head_chunk
        return tokens // chunk if tokens % chunk == 0 else 1

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        """The bare next-token loss over the vocabulary held here; the head
        and the loss in chunks of tokens where there are several."""
        chunks = self.head_chunks(tokens.size)
        if chunks > 1:
            return chunked_head_nll(
                params["lm_head"].astype(self.config.dtype),
                self.hidden(params, tokens), targets, chunks)
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)
