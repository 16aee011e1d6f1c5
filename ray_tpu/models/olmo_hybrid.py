"""Olmo-Hybrid shaped decoder (``model_type: olmo_hybrid``), training path:
a dense stack whose layers differ in the MIXER (Gated DeltaNet with key
heads of ``gdn_key_dim`` and value heads of ``gdn_value_dim``, the correction
allowed past the key, or softmax attention without positions) in a
published order (three Gated DeltaNet layers to one attention layer), a
gated MLP after every mixer, and POST-norm residuals: a sublayer reads the
residual stream itself and its OUTPUT is normed before it is added,

    h   = x + rms(mixer(x); w_1)
    out = h + rms(mlp(h); w_2),     mlp(h) = (silu(h W_gate) * h W_up) W_down

``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32 statistics, w from
one; logits are ``rms(x; w_out) W_head``, untied. No bias anywhere.

* Gated DeltaNet mixer (H heads, a key head a value head; d_k =
  ``gdn_key_dim``, d_v = ``gdn_value_dim``):

      q | k | v | gate = x W_qkvg        (H d_k, H d_k, H d_v, H d_v columns)
      b | a            = x W_ba          (H, H)
      q, k, v <- silu(conv(q | k | v))   causal, depthwise, ``gdn_d_conv``
                                         taps, no bias (three convolutions
                                         side by side are one over their
                                         channels)
      q, k l2-normalised a head, q times 1 / sqrt(d_k)
      beta = 2 sigmoid(b)                in (0, 2): I - beta k k^T has the
                                         eigenvalue 1 - beta in (-1, 1)
      g = -exp(A_log) * softplus(a + dt_bias)    float32, a head and token
      per head, S [d_k, d_v] float32 from zero:
          S <- exp(g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
          o_t = S^T q_t                  (``ops.gdn_gated_scan``)
      y = (o / sqrt(mean_head(o^2) + eps) * w_norm * silu(gate)) W_o

* attention mixer (H heads of ``head_dim``, a key/value head a query head):

      q = rms(x W_q; w_q),  k = rms(x W_k; w_k),  v = x W_v
      each norm over ALL of the layer's q (k) channels at once, not a head
      o = causal softmax(q k^T / sqrt(head_dim)) v      no rotation
      y = o W_o

**A share of the heads.** ``heads_held`` of each mixer's heads from
``head_offset`` are here (tensor parallel over the heads; the MLP, the
norms and every width whole): the matching columns of ``W_qkvg``, ``W_ba``,
``W_q``, ``W_k``, ``W_v``, convolution channels, ``A_log``, ``dt_bias`` and
q/k gains, and the matching rows of ``W_o``. A mixer's output is then its
own heads' part of ``o W_o``; that partial result is what the post-norm
reads and what goes on, and nothing stands in for the other chips.
``held_share`` cuts a whole model's parameters to a share's. The one
statistic that spans heads, the q/k norm's mean of squares, runs over the
channels held here (``_qk_norm``: the number a tensor-parallel group would
all-reduce).

``vocab_size`` is the vocabulary this chip holds: embedding, head, logits
and loss are over it.

The stack is walked by ``models/stack.py``: kinds ``gdn`` and ``attn`` in
their published order cut into runs of like layers (3 scanned, then 1),
parameters one flat dict: ``wte``, ``lm_head``, ``out_norm`` and
``<run>.<kind>.<name>`` stacked over the run's layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import (causal_conv1d_silu, cross_entropy_loss, flash_attention,
                   gdn_gated_scan, rmsnorm, rmsnorm_then_gate)
from .stack import draw_params, period_runs, vocab_row_shardings, walk_stack

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name`` (``qwen3_next.py``'s choice, for its reasons): the
# attention layer the flash kernels' output and row statistics, a Gated
# DeltaNet layer its input alone.
_REMAT_SAVE = {"attn": ("flash_out", "flash_lse"), "gdn": ()}

_PUBLISHED_N_LAYER = 32
_PUBLISHED_PERIOD = 4       # layer i is attention where (i + 1) % 4 == 0


def _layer_types(n_layer: int) -> Tuple[str, ...]:
    return tuple("attn" if (i + 1) % _PUBLISHED_PERIOD == 0 else "gdn"
                 for i in range(n_layer))


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352          # the ids held here
    layer_types: Tuple[str, ...] = _layer_types(_PUBLISHED_N_LAYER)
    d_model: int = 3840
    d_ff: int = 11008
    # heads of a WHOLE layer, both mixers (a key/value head a query head, a
    # value head a key head), and the share of them held here
    n_head: int = 30
    heads_held: Optional[int] = None  # None: all
    head_offset: int = 0
    head_dim: int = 128               # attention
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    gdn_d_conv: int = 4
    rms_eps: float = 1e-6
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.layer_types) - {"gdn", "attn"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if not 0 <= self.head_offset <= self.n_head - self.heads:
            raise ValueError(
                f"heads {self.head_offset}..{self.head_offset + self.heads} "
                f"of {self.n_head}")

    @property
    def heads(self) -> int:
        """Heads of each mixer held here."""
        return self.n_head if self.heads_held is None else self.heads_held

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @staticmethod
    def tiny(**kw) -> "OlmoHybridConfig":
        """Published head sizes (96 and 192 shape the scan's route, 128 the
        flash kernels'), everything else small: one period, three heads."""
        base = dict(vocab_size=512, d_model=64, d_ff=128, n_head=3,
                    layer_types=_layer_types(4))
        base.update(kw)
        return OlmoHybridConfig(**base)

    @staticmethod
    def olmo_hybrid_7b(n_layer: Optional[int] = None,
                       **kw) -> "OlmoHybridConfig":
        """allenai/Olmo-Hybrid-7B ``config.json``, every published width;
        ``n_layer`` keeps the first layers of the published order."""
        return OlmoHybridConfig(
            layer_types=_layer_types(n_layer or _PUBLISHED_N_LAYER), **kw)


def _head_blocks(
        c: OlmoHybridConfig) -> Dict[str, Tuple[int, Tuple[int, ...]]]:
    """The columns (axis -1) or rows (axis -2) of a layer's parameters that
    belong to heads: ``<kind>.<name>`` -> (axis, the blocks' widths a head,
    in order)."""
    dk, dv, hd = c.gdn_key_dim, c.gdn_value_dim, c.head_dim
    return {
        "gdn.w_qkvg": (-1, (dk, dk, dv, dv)), "gdn.w_ba": (-1, (1, 1)),
        "gdn.conv": (-1, (dk, dk, dv)), "gdn.A_log": (-1, (1,)),
        "gdn.dt_bias": (-1, (1,)), "gdn.w_o": (-2, (dv,)),
        "attn.w_q": (-1, (hd,)), "attn.w_k": (-1, (hd,)),
        "attn.w_v": (-1, (hd,)), "attn.q_norm": (-1, (hd,)),
        "attn.k_norm": (-1, (hd,)), "attn.w_o": (-2, (hd,))}


class OlmoHybrid:
    """init / loss pytree model in the house style (qwen3_next.py)."""

    def __init__(self, config: OlmoHybridConfig):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            config.layer_types)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, or the name of a rule of ``stack.draw_params``)."""
        c = self.config
        d, f, h, hd = c.d_model, c.d_ff, c.heads, c.head_dim
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        gk, gv = h * c.gdn_key_dim, h * c.gdn_value_dim
        mlp = {"mix_norm": ((d,), None), "mlp_norm": ((d,), None),
               "w_gate": ((d, f), std), "w_up": ((d, f), std),
               "w_down": ((f, d), res)}
        kinds = {
            "gdn": dict({
                "w_qkvg": ((d, 2 * gk + 2 * gv), std),
                "w_ba": ((d, 2 * h), std),
                "conv": ((c.gdn_d_conv, 2 * gk + gv), "conv"),
                "A_log": ((h,), "A_log"), "dt_bias": ((h,), "dt_bias"),
                "o_norm": ((c.gdn_value_dim,), None),
                "w_o": ((gv, d), res)}, **mlp),
            "attn": dict({
                "w_q": ((d, h * hd), std), "w_k": ((d, h * hd), std),
                "w_v": ((d, h * hd), std),
                "q_norm": ((h * hd,), None), "k_norm": ((h * hd,), None),
                "w_o": ((h * hd, d), res)}, **mlp),
        }
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), None)}
        for i, ((kind,), n) in enumerate(self.runs):
            for name, (shape, how) in kinds[kind].items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, every norm's gain 1, and the three rules of a
        delta-rule layer that the config's file lists as assumed
        (``stack.draw_params``: ``A_log``, ``dt_bias``, the convolution)."""
        c = self.config
        return draw_params(self._shapes(), rng, c.param_dtype, c.gdn_d_conv)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    def held_share(self, whole: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """The parameters of this share (``heads_held`` heads from
        ``head_offset``) out of those of the whole layer's ``n_head``: of
        every block of a head-wise parameter the held heads' columns (rows
        of ``W_o``), everything else as it is."""
        c = self.config
        blocks = _head_blocks(c)

        def cut(name, x):
            axis, widths = blocks.get(name.partition(".")[2], (None, ()))
            if axis is None:
                return x
            parts, at = [], 0
            for w in widths:
                parts.append(jax.lax.slice_in_dim(
                    x, at + c.head_offset * w,
                    at + (c.head_offset + c.heads) * w, axis=x.ndim + axis))
                at += c.n_head * w
            return jnp.concatenate(parts, axis)

        return {n: cut(n, x) for n, x in whole.items()}

    # -- layers ------------------------------------------------------------

    def _gdn_mixer(self, x, lp):
        """The residual x -> the held heads' part of the Gated DeltaNet
        sublayer's output (before the post-norm)."""
        c = self.config
        b, s, _ = x.shape
        h, dk, dv, dt = c.heads, c.gdn_key_dim, c.gdn_value_dim, c.dtype
        gk, gv = h * dk, h * dv
        heads = lambda t: t.reshape(b, s, h, dv)             # noqa: E731
        with jax.named_scope("mixer"):
            qkvg = x @ lp["w_qkvg"].astype(dt)
            qkv, gate = qkvg[..., :2 * gk + gv], qkvg[..., 2 * gk + gv:]
            ba = x @ lp["w_ba"].astype(dt)
        with jax.named_scope("conv"):
            qkv = causal_conv1d_silu(qkv, lp["conv"])
        with jax.named_scope("scan"):
            beta = 2.0 * jax.nn.sigmoid(ba[..., :h].astype(jnp.float32))
            o = gdn_gated_scan(
                qkv[..., :gk], qkv[..., gk:2 * gk], qkv[..., 2 * gk:],
                ba[..., h:], lp["A_log"], lp["dt_bias"], beta,
                scale=dk ** -0.5, key_heads=h, beta_max=2.0)
        with jax.named_scope("mixer"):
            o = rmsnorm_then_gate(heads(o), heads(gate), lp["o_norm"],
                                  c.rms_eps, activation=jax.nn.silu)
            return o.reshape(b, s, gv) @ lp["w_o"].astype(dt)

    def _qk_norm(self, t, w):
        """The norm over ALL of the layer's q (k) channels held here, t [B,
        S, heads * head_dim]: the mean of squares is over the held heads'
        channels, the one number of the model that a tensor-parallel group
        would complete by an exchange."""
        return rmsnorm(t, w, self.config.rms_eps)

    def _attn_mixer(self, x, lp):
        """-> the held heads' part of the attention sublayer's output."""
        c = self.config
        b, s, _ = x.shape
        h, hd, dt = c.heads, c.head_dim, c.dtype
        with jax.named_scope("attn"):
            q = self._qk_norm(x @ lp["w_q"].astype(dt), lp["q_norm"])
            k = self._qk_norm(x @ lp["w_k"].astype(dt), lp["k_norm"])
            v = x @ lp["w_v"].astype(dt)
            o = flash_attention(*(t.reshape(b, s, h, hd) for t in (q, k, v)),
                                causal=True)
            return o.reshape(b, s, h * hd) @ lp["w_o"].astype(dt)

    def _block(self, kind: str, x, lp):
        """One layer: both sublayers read the stream un-normed, and each
        one's output is normed before it is added."""
        c, dt = self.config, self.config.dtype
        y = (self._gdn_mixer if kind == "gdn" else self._attn_mixer)(x, lp)
        with jax.named_scope("mixer" if kind == "gdn" else "attn"):
            x = x + rmsnorm(y, lp["mix_norm"], c.rms_eps)
        with jax.named_scope("mlp"):
            y = (jax.nn.silu(x @ lp["w_gate"].astype(dt))
                 * (x @ lp["w_up"].astype(dt))) @ lp["w_down"].astype(dt)
            return x + rmsnorm(y, lp["mlp_norm"], c.rms_eps)

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        c = self.config
        with jax.named_scope("embed"):
            x = params["wte"].astype(c.dtype)[tokens]
        x, _ = walk_stack(
            x, self.runs, params,
            lambda kind, h, p, side, _: (self._block(kind, h, p), {}),
            [_REMAT_SAVE[period[0]] for period, _ in self.runs],
            model="olmo_hybrid",
            facts={"heads": [c.heads, c.n_head],
                   "head_offset": c.head_offset})
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = rmsnorm(x, params["out_norm"], c.rms_eps)
            return jnp.einsum("bsd,vd->bsv", x,
                              params["lm_head"].astype(c.dtype),
                              preferred_element_type=jnp.float32)

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        """The bare next-token loss over the vocabulary held here."""
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)
