"""LFM2-MoE shaped decoder (``model_type: lfm2_moe``), training path: a stack
whose layers differ in the OPERATOR that mixes tokens (a double-gated short
convolution, or grouped-query softmax attention) in a published order
(``layer_types``: about three ``conv`` to one ``full_attention``) and in the
feed-forward part (a dense gated MLP in the first ``num_dense_layers``
layers, sigmoid-routed experts with a selection bias and NO shared expert
in all others), of whose routed experts and vocabulary this chip may hold a
share.

``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32 statistics, w from
one. Every layer is ``x = x + operator(rms(x)); x = x + ffn(rms(x))``;
logits are ``rms(x; w_out) E^T``: the head is the embedding (tied).

* ``conv`` operator, x̂ the normed input, d the model width:

      B | C | u = x̂ W_in             three chunks of d IN THAT ORDER, no bias
      z = B * u
      c[t] = w[0] z[t-2] + w[1] z[t-1] + w[2] z[t]     per channel, zeros
                                       before the row, NO activation
      y = (C * c) W_out               (``ops.short_conv.in_proj_short_conv``)

* ``full_attention`` operator (H query heads over Hkv key/value heads of
  ``head_dim``, no bias): q and k rms-normalised a head over their
  ``head_dim`` channels (weights of their own) BEFORE the rotation; every
  channel of a head rotated (channel i pairs with i + head_dim / 2, angle
  position x theta^(-2i / head_dim)); causal softmax at head_dim^-1/2 in
  the flash kernels, query head j on key/value head j // (H / Hkv) (k and
  v repeated to the query heads before them, ROADMAP B19(a)); then W_o.
* dense MLP: ``W_down (silu(W_gate x̂) * W_up x̂)``.
* expert layer (``ops.expert_layer.held_expert_layer``, ``score``
  ``sigmoid``): s = sigmoid(x̂ W_r) over ALL experts in float32; the
  ``top_k`` experts are the top k of s + bias (``use_expert_bias``: the
  bias a buffer at zero that no gradient reaches and nothing updates);
  weights s / sum of the chosen s (``norm_topk_prob``) times
  ``routed_scale``; each expert the gated MLP above; no shared expert;
  dropless; ``experts_held`` of ``n_routed_experts`` from
  ``expert_offset``.

Where ``router_aux_coef`` is not 0 the model's ``loss`` adds that times
every expert layer's sequence-wise balancing term
(``ops.expert_layer.balance_term``, ``score`` ``sigmoid``; 1 a layer under
a level router), summed over the layers, the mean over the sequences.

``vocab_size`` is the vocabulary this chip holds: embedding, head, logits
and loss are over it. Nothing stands in for the absent chips: a partial
result goes on.

The stack is walked by ``models/stack.py``: kinds ``<operator>_<ffn>``
(``conv_mlp``, ``attn_mlp``, ``conv_moe``, ``attn_moe``) in their order cut
into runs of like layers, every layer rematerialised, parameters one flat
dict: ``wte``, ``out_norm`` and ``<run>.<kind>.<name>`` stacked over the
run's layers. What is held of what is the event
``rtpu.models.lfm2_moe.share``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import apply_rope, cross_entropy_loss, flash_attention, rmsnorm, \
    rope_cache
from ..ops.expert_layer import balance_term, held_expert_layer
from ..ops.short_conv import in_proj_short_conv
from ..perf.recorder import record as _record
from .stack import (draw_params, period_runs, run_params,
                    vocab_row_shardings, walk_stack)

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``: an attention layer the flash kernels' output and row
# statistics (its backward never runs the forward kernel again: 2 x 32
# heads x 8192 x 64 bf16 = 67 MB and the statistics, once), as the other
# families on the walker keep them. A conv layer keeps its input alone: its
# projections are made again and the convolution's backward makes z and the
# taps again from b, c, x inside its kernel, so nothing of it is saved.
_REMAT_SAVE = {"attn": ("flash_out", "flash_lse"), "conv": ()}

_OPERATORS = {"conv": "conv", "full_attention": "attn"}

# LiquidAI/LFM2-8B-A1B ``config.json``'s ``layer_types``: 18 conv, 6 attention
_PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536           # the ids held here
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2         # the first layers' MLP is dense
    d_model: int = 2048
    # the conv operator
    conv_taps: int = 3                # conv_L_cache
    # the attention operator
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    rope_base: float = 1000000.0
    max_seq: int = 128000             # positions the model is built for
    # feed-forward
    d_ff: int = 7168                  # the dense layers' gated MLP
    d_expert: int = 1792              # one routed expert's
    n_routed_experts: int = 32        # the router's width
    experts_held: Optional[int] = None         # None: all
    expert_offset: int = 0
    top_k: int = 4
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    init_std: float = 0.02
    # residual projections are drawn at init_std / sqrt(2 x init_layers):
    # the depth of the model these layers are layers OF (None: the layers
    # built)
    init_layers: Optional[int] = None
    # times each expert layer's balancing term, added to the loss; 0: none
    router_aux_coef: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(_OPERATORS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if not self.layer_types:
            raise ValueError("no layer is built")
        if self.n_head % self.n_kv_head or self.head_dim % 2:
            raise ValueError("query heads are a multiple of key/value "
                             "heads, a head's channels turn in pairs")
        if not 0 < self.n_experts_held <= self.n_routed_experts \
                - self.expert_offset or self.expert_offset < 0:
            raise ValueError("experts held of the routed experts")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``<operator>_<ffn>`` a layer, in order."""
        return tuple(
            f"{_OPERATORS[t]}_{'mlp' if i < self.num_dense_layers else 'moe'}"
            for i, t in enumerate(self.layer_types))

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def n_experts_held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    def share(self) -> Dict[str, Any]:
        """What this chip holds, and of how many."""
        return {"experts": [self.n_experts_held, self.n_routed_experts],
                "expert_offset": self.expert_offset,
                "vocab_rows": self.padded_vocab, "kinds": list(self.kinds)}

    @staticmethod
    def tiny(**kw) -> "Lfm2MoeConfig":
        """The published head size (the flash kernels are shaped by it) and
        taps, everything else small: a dense conv layer, an attention layer
        and two conv layers before experts, everything held."""
        base = dict(vocab_size=512, d_model=128, n_head=4, n_kv_head=2,
                    d_ff=256, d_expert=64, n_routed_experts=8, top_k=3,
                    max_seq=1024, num_dense_layers=1,
                    layer_types=("conv", "full_attention", "conv", "conv"))
        base.update(kw)
        return Lfm2MoeConfig(**base)

    @staticmethod
    def lfm2_8b_a1b(**kw) -> "Lfm2MoeConfig":
        """LiquidAI/LFM2-8B-A1B ``config.json``, every published width;
        ``layer_types`` and ``num_dense_layers`` choose the layers that are
        built."""
        return Lfm2MoeConfig(**kw)


class Lfm2Moe:
    """init / loss pytree model in the house style (qwen3_next.py)."""

    def __init__(self, config: Lfm2MoeConfig):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            config.kinds)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, 0.0 for zeros, ``conv`` for the taps' rule of
        ``draw_params``)."""
        c = self.config
        d, h, kv, hd = c.d_model, c.n_head, c.n_kv_head, c.head_dim
        std = c.init_std
        res = std / math.sqrt(2 * (c.init_layers or c.n_layer))
        g = c.n_experts_held
        parts = {
            "conv": {
                "norm": ((d,), None), "w_in": ((d, 3 * d), std),
                "conv_w": ((c.conv_taps, d), "conv"),
                "w_out": ((d, d), res)},
            "attn": {
                "norm": ((d,), None),
                "w_q": ((d, h * hd), std),
                "w_k": ((d, kv * hd), std), "w_v": ((d, kv * hd), std),
                "q_norm": ((hd,), None), "k_norm": ((hd,), None),
                "w_o": ((h * hd, d), res)},
            "mlp": {
                "mlp_norm": ((d,), None),
                "w_gate": ((d, c.d_ff), std), "w_up": ((d, c.d_ff), std),
                "w_down": ((c.d_ff, d), res)},
            "moe": {
                "mlp_norm": ((d,), None),
                "w_router": ((d, c.n_routed_experts), std),
                "router_bias": ((c.n_routed_experts,), 0.0),
                "e_gate": ((g, d, c.d_expert), std),
                "e_up": ((g, d, c.d_expert), std),
                "e_down": ((g, c.d_expert, d), res)},
        }
        out = {"wte": ((c.padded_vocab, d), std), "out_norm": ((d,), None)}
        for i, ((kind,), n) in enumerate(self.runs):
            operator, ffn = kind.split("_")
            for name, (shape, how) in dict(parts[operator],
                                           **parts[ffn]).items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, norm gains 1, the selection bias 0, the taps
        uniform in +-1/sqrt(taps) (a depthwise conv1d's default)."""
        c = self.config
        return draw_params(self._shapes(), rng, c.param_dtype, c.conv_taps)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- layers ------------------------------------------------------------

    def _conv_operator(self, x, lp):
        c = self.config
        dt = c.dtype
        with jax.named_scope("mixer"):
            bcx = rmsnorm(x, lp["norm"], c.rms_eps) @ lp["w_in"].astype(dt)
        with jax.named_scope("conv"):
            # the one array, its chunks indexed inside the kernels: a
            # chunk sliced out here would be copied to feed a kernel
            y = in_proj_short_conv(bcx, lp["conv_w"])
        with jax.named_scope("mixer"):
            return x + y @ lp["w_out"].astype(dt)

    def _attn_operator(self, x, lp, cos, sin):
        c = self.config
        b, s, _ = x.shape
        h, kv, hd, dt = c.n_head, c.n_kv_head, c.head_dim, c.dtype
        with jax.named_scope("attn"):
            xn = rmsnorm(x, lp["norm"], c.rms_eps)
            q = (xn @ lp["w_q"].astype(dt)).reshape(b, s, h, hd)
            k = (xn @ lp["w_k"].astype(dt)).reshape(b, s, kv, hd)
            v = (xn @ lp["w_v"].astype(dt)).reshape(b, s, kv, hd)
            q = apply_rope(rmsnorm(q, lp["q_norm"], c.rms_eps), cos, sin)
            k = apply_rope(rmsnorm(k, lp["k_norm"], c.rms_eps), cos, sin)
            # grouped-query: key/value heads to the query heads
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
            o = flash_attention(q, k, v, causal=True)
            return x + o.reshape(b, s, h * hd) @ lp["w_o"].astype(dt)

    def _mlp(self, x, lp):
        c = self.config
        dt = c.dtype
        with jax.named_scope("mlp"):
            xn = rmsnorm(x, lp["mlp_norm"], c.rms_eps)
            hidden = jax.nn.silu(xn @ lp["w_gate"].astype(dt)) \
                * (xn @ lp["w_up"].astype(dt))
            return x + hidden @ lp["w_down"].astype(dt)

    def _moe(self, x, lp, balance: bool):
        """-> (x + the layer's experts, the rows its held experts worked,
        the router's balancing term a sequence [B] or None)."""
        c = self.config
        b, s, d = x.shape
        with jax.named_scope("router"):     # the norm goes with the router
            xn = rmsnorm(x, lp["mlp_norm"], c.rms_eps).reshape(b * s, d)
            aux = balance_term(
                xn, lp["w_router"], top_k=c.top_k, groups=b, score="sigmoid",
                bias=lp["router_bias"]) if balance else None
        y, rows = held_expert_layer(
            xn, lp, experts_held=c.n_experts_held,
            expert_offset=c.expert_offset, top_k=c.top_k,
            routed_scale=c.routed_scale, score="sigmoid")
        return x + y.reshape(b, s, d), rows, aux

    def _block(self, kind: str, x, lp, rope, balance: bool = False):
        """One layer of kind ``<operator>_<ffn>`` -> (x, held rows or None,
        balancing term or None)."""
        operator, ffn = kind.split("_")
        x = self._conv_operator(x, lp) if operator == "conv" \
            else self._attn_operator(x, lp, *rope)
        if ffn == "mlp":
            return self._mlp(x, lp), None, None
        return self._moe(x, lp, balance)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.config.dtype)[tokens]

    def _rope(self, seq: int):
        c = self.config
        if seq > c.max_seq:
            raise ValueError(f"{seq} positions, max_seq is {c.max_seq}")
        return rope_cache(seq, c.head_dim, c.rope_base)

    def forward(self, params: Dict[str, jax.Array], tokens: jax.Array, *,
                balance: bool = False):
        """-> (logits [B, S, padded_vocab] f32, the expert layers' balancing
        terms summed, a sequence [B] f32, or None). With ``balance`` the
        walker's carry is the pair (x, the sum so far): a scanned run has
        no output a layer."""
        c = self.config
        _record("rtpu.models.lfm2_moe.share", "held", c.share())
        rope = self._rope(tokens.shape[1])

        def block(kind, h, p, side, _):
            if not balance:
                return self._block(kind, h, p, rope)[0], {}
            x, _, aux = self._block(kind, h[0], p, rope, True)
            return (x, h[1] if aux is None else h[1] + aux), {}

        x = self._embed(params, tokens)
        h, _ = walk_stack(
            (x, jnp.zeros(x.shape[:1], jnp.float32)) if balance else x,
            self.runs, params, block,
            [_REMAT_SAVE[period[0].split("_")[0]] for period, _ in self.runs],
            model="lfm2_moe")
        x, aux = h if balance else (h, None)
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = rmsnorm(x, params["out_norm"], c.rms_eps)
            return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(c.dtype),
                              preferred_element_type=jnp.float32), aux

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        return self.forward(params, tokens)[0]

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        """The next-token loss over the vocabulary held here and, where
        ``router_aux_coef`` is not 0, that times the expert layers'
        balancing terms (summed over the layers, the mean over the
        sequences)."""
        coef = self.config.router_aux_coef
        logits, aux = self.forward(params, tokens, balance=coef != 0)
        with jax.named_scope("loss"):
            loss = cross_entropy_loss(logits, targets)
            return loss if aux is None else loss + coef * jnp.mean(aux)

    def routing_stats(self, params: Dict[str, jax.Array],
                      tokens: jax.Array) -> jax.Array:
        """(token, choice) pairs that name a held expert, one count an
        expert layer in order: the rows its grouped product works. A
        forward of its own, layer by layer (the walker's scanned runs have
        no output a layer). Jit it; it is no part of a train step."""
        rope = self._rope(tokens.shape[1])
        x, rows = self._embed(params, tokens), []
        for i, ((kind,), n) in enumerate(self.runs):
            lp = run_params(params, i)[kind]
            for j in range(n):
                x, held, _ = self._block(
                    kind, x, {name: v[j] for name, v in lp.items()}, rope)
                if held is not None:
                    rows.append(held)
        return jnp.stack(rows)
