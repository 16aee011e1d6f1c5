"""Qwen3-Next shaped decoder (``model_type: qwen3_next``), training path: a
stack whose layers differ in the MIXER (Gated DeltaNet, a linear-attention
layer with a matrix state a value head and ONE decay a head and token, or
gated grouped-query softmax attention) in a published order (three Gated
DeltaNet layers to one attention layer), every layer before a
softmax-routed expert layer with a gated shared expert, of whose routed
experts this chip may hold a share.

``zrms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``, float32 statistics, w
from zero: every norm of the model but the one inside a Gated DeltaNet
layer. Every layer is ``x = x + mixer(zrms(x)); x = x + ffn(zrms(x))``;
logits are ``zrms(x) W_head``, untied.

* Gated DeltaNet mixer (Hk key heads, Hv value heads, every head d =
  ``gdn_head_dim``), x̂ the normed input:

      q | k | v | z = x̂ W_qkvz     (Hk d, Hk d, Hv d, Hv d columns)
      b | a         = x̂ W_ba       (Hv, Hv)
      q, k, v <- silu(conv(q | k | v))     ONE causal depthwise convolution
                                           of ``gdn_d_conv`` taps, no bias
      q, k l2-normalised a key head, q times 1 / sqrt(d); value head j
      reads key head j // (Hv / Hk)
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   float32,
                                           one a VALUE head and token
      per value head, S [d, d] float32 from zero:
          S <- exp(g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
          o_t = S^T q_t                    (``ops.gdn_gated_scan``)
      y = (o / sqrt(mean_head(o^2) + eps) * w_norm * silu(z)) W_o

  The norms of q and k and g are the scan's to make, as in
  ``kimi_linear.py``; the output norm comes first and then the SiLU gate
  (``ops.layers.rmsnorm_then_gate``), ``w_norm`` [d] from one.
* attention mixer (H query heads over Hkv key/value heads, every head
  ``head_dim``):

      q | gate = x̂ W_q      (a head's head_dim of q beside its head_dim of
                             gate);  k = x̂ W_k;  v = x̂ W_v
      q <- zrms_head(q), k <- zrms_head(k); the first ``rotary_dim``
      channels of each head of q and k rotated (channel i pairs with i +
      rotary_dim / 2), the others as they are
      o = causal softmax(q k^T / sqrt(head_dim)) v, query head j on
      key/value head j // (H / Hkv)
      y = (o * sigmoid(gate)) W_o

  k and v are repeated to the query heads before ``ops.flash_attention``
  (ROADMAP B19(a)). No bias anywhere.
* expert feed-forward (``ops.expert_layer.held_expert_layer``, ``score``
  ``softmax``): p = softmax(x̂ W_r) over all experts in float32, the
  ``top_k`` largest, weights p over the chosen k, no selection bias, no
  scaling; the shared expert one more gated MLP times ``sigmoid(x̂ w_sg)``;
  dropless; ``experts_held`` of ``n_routed_experts`` from ``expert_offset``.

``vocab_size`` is the vocabulary this chip holds: embedding, head, logits
and loss are over it. The published checkpoint's multi-token-prediction
layer is not built (``config.json`` has no key for it).

The stack is walked by ``models/stack.py``: kinds ``gdn_moe`` and
``attn_moe`` in their published order cut into runs of like layers (3
scanned, then 1), parameters one flat dict: ``wte``, ``lm_head``,
``out_norm`` and ``<run>.<kind>.<name>`` stacked over the run's layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import (apply_rope, causal_conv1d_silu, cross_entropy_loss,
                   flash_attention, gdn_gated_scan, rmsnorm,
                   rmsnorm_then_gate, rope_cache)
from ..ops.expert_layer import held_expert_layer
from .stack import (draw_params, period_runs, run_params,
                    vocab_row_shardings, walk_stack)

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``. The attention layer keeps the flash kernels' output
# and row statistics (the backward never runs the forward kernel again:
# 2 x 16 heads x 8192 x 256 bf16 = 134 MB and the statistics, once). A
# Gated DeltaNet layer keeps its input alone, as a KDA layer does
# (``kimi_linear.py``): its projections, convolution and gates are made
# again and ``gdn_chunk_fwd`` runs a second time in the layer's backward.
# Decided by ``benchmark/scratch/describe_compile.py`` on the cell's step
# (PERF.md, PR 52).
_REMAT_SAVE = {"attn": ("flash_out", "flash_lse"), "gdn": ()}

_PUBLISHED_N_LAYER = 48
_PUBLISHED_FULL_ATTENTION_INTERVAL = 4


def _layer_types(n_layer: int, interval: int) -> Tuple[str, ...]:
    return tuple("attn" if (i + 1) % interval == 0 else "gdn"
                 for i in range(n_layer))


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936          # the ids held here
    layer_types: Tuple[str, ...] = _layer_types(
        _PUBLISHED_N_LAYER, _PUBLISHED_FULL_ATTENTION_INTERVAL)
    d_model: int = 2048
    # gated softmax attention
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_base: float = 10000000.0
    max_seq: int = 262144             # positions the model is built for
    # Gated DeltaNet (the ``linear_*`` keys)
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_head_dim: int = 128           # keys and values
    gdn_d_conv: int = 4
    # feed-forward
    d_expert: int = 512               # one routed expert's gated MLP
    d_shared: int = 512               # the shared expert's
    n_routed_experts: int = 512       # the router's width
    experts_held: int = 512           # experts on this chip ...
    expert_offset: int = 0            # ... from this one
    top_k: int = 10
    rms_eps: float = 1e-6
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.layer_types) - {"gdn", "attn"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.n_head % self.n_kv_head \
                or self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError("query heads are a multiple of key/value heads, "
                             "value heads of key heads")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``<mixer>_moe`` a layer, in order."""
        return tuple(f"{m}_moe" for m in self.layer_types)

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """Published head sizes (the scan and the flash kernels are shaped
        by them), everything else small: one period, three Gated DeltaNet
        layers of 4 value heads over 2 key heads, one attention layer of 4
        query heads over 2 key/value heads."""
        base = dict(vocab_size=512, d_model=64, n_head=4, n_kv_head=2,
                    gdn_key_heads=2, gdn_value_heads=4, d_expert=32,
                    d_shared=32, n_routed_experts=8, experts_held=8, top_k=3,
                    max_seq=1024, layer_types=_layer_types(4, 4))
        base.update(kw)
        return Qwen3NextConfig(**base)

    @staticmethod
    def qwen3_next_80b_a3b(n_layer: Optional[int] = None,
                           **kw) -> "Qwen3NextConfig":
        """Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``, every published
        width; ``n_layer`` keeps the first layers of the published order
        (``full_attention_interval`` 4)."""
        return Qwen3NextConfig(layer_types=_layer_types(
            n_layer or _PUBLISHED_N_LAYER,
            _PUBLISHED_FULL_ATTENTION_INTERVAL), **kw)


class Qwen3Next:
    """init / loss pytree model in the house style (kimi_linear.py)."""

    def __init__(self, config: Qwen3NextConfig):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            config.kinds)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, 0.0 for zeros, or the name of a rule of ``init``)."""
        c = self.config
        d, h, kv, hd = c.d_model, c.n_head, c.n_kv_head, c.head_dim
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        gk, gv = (c.gdn_key_heads * c.gdn_head_dim,
                  c.gdn_value_heads * c.gdn_head_dim)
        mixers = {
            "gdn": {
                "norm": ((d,), 0.0),
                "w_qkvz": ((d, 2 * gk + 2 * gv), std),
                "w_ba": ((d, 2 * c.gdn_value_heads), std),
                "conv": ((c.gdn_d_conv, 2 * gk + gv), "conv"),
                "A_log": ((c.gdn_value_heads,), "A_log"),
                "dt_bias": ((c.gdn_value_heads,), "dt_bias"),
                "o_norm": ((c.gdn_head_dim,), None),
                "w_o": ((gv, d), res)},
            "attn": {
                "norm": ((d,), 0.0),
                "w_q": ((d, h * 2 * hd), std),
                "w_k": ((d, kv * hd), std), "w_v": ((d, kv * hd), std),
                "q_norm": ((hd,), 0.0), "k_norm": ((hd,), 0.0),
                "w_o": ((h * hd, d), res)},
        }
        g = c.experts_held
        moe = {
            "mlp_norm": ((d,), 0.0),
            "w_router": ((d, c.n_routed_experts), std),
            "s_gate": ((d, c.d_shared), std), "s_up": ((d, c.d_shared), std),
            "s_down": ((c.d_shared, d), res), "s_gate_w": ((d, 1), std),
            "e_gate": ((g, d, c.d_expert), std),
            "e_up": ((g, d, c.d_expert), std),
            "e_down": ((g, c.d_expert, d), res)}
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), 0.0)}
        for i, ((kind,), n) in enumerate(self.runs):
            mixer = kind.split("_")[0]
            for name, (shape, how) in dict(mixers[mixer], **moe).items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, the ``1 + w`` norms' weights 0, a Gated DeltaNet
        layer's output norm 1, and the three rules the config's file lists
        as assumed (``kimi_linear.py``'s): ``A_log`` the log of a uniform
        draw in [1, 16] a head, ``dt_bias`` the inverse softplus of a dt
        drawn log-uniformly in [0.001, 0.1] (the decays exp(g) run from
        0.2 to 0.999 a token), the convolution uniform in +-1/sqrt(taps)."""
        c = self.config
        return draw_params(self._shapes(), rng, c.param_dtype, c.gdn_d_conv)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- layers ------------------------------------------------------------

    def _norm(self, x, w):
        return rmsnorm(x, w, self.config.rms_eps, plus_one=True)

    def _gdn_mixer(self, x, lp):
        """The Gated DeltaNet sublayer of the residual x, norm first ->
        x + y."""
        c = self.config
        b, s, _ = x.shape
        hv, d, dt = c.gdn_value_heads, c.gdn_head_dim, c.dtype
        gk, gv = c.gdn_key_heads * d, hv * d
        heads = lambda t: t.reshape(b, s, hv, d)             # noqa: E731
        with jax.named_scope("mixer"):
            xn = self._norm(x, lp["norm"])
            qkvz = xn @ lp["w_qkvz"].astype(dt)
            qkv, z = qkvz[..., :2 * gk + gv], qkvz[..., 2 * gk + gv:]
            ba = xn @ lp["w_ba"].astype(dt)
        with jax.named_scope("conv"):
            qkv = causal_conv1d_silu(qkv, lp["conv"])
        with jax.named_scope("scan"):
            beta = jax.nn.sigmoid(ba[..., :hv].astype(jnp.float32))
            o = gdn_gated_scan(
                qkv[..., :gk], qkv[..., gk:2 * gk], qkv[..., 2 * gk:],
                ba[..., hv:], lp["A_log"], lp["dt_bias"], beta,
                scale=d ** -0.5)
        with jax.named_scope("mixer"):
            o = rmsnorm_then_gate(heads(o), heads(z), lp["o_norm"], c.rms_eps,
                                  activation=jax.nn.silu).reshape(b, s, gv)
            return x + o @ lp["w_o"].astype(dt)

    def _attn_mixer(self, x, lp, cos, sin):
        c = self.config
        b, s, _ = x.shape
        h, kv, hd, dt = c.n_head, c.n_kv_head, c.head_dim, c.dtype
        with jax.named_scope("attn"):
            xn = self._norm(x, lp["norm"])
            qg = (xn @ lp["w_q"].astype(dt)).reshape(b, s, h, 2 * hd)
            q, gate = qg[..., :hd], qg[..., hd:]
            k = (xn @ lp["w_k"].astype(dt)).reshape(b, s, kv, hd)
            v = (xn @ lp["w_v"].astype(dt)).reshape(b, s, kv, hd)
            q = apply_rope(self._norm(q, lp["q_norm"]), cos, sin)
            k = apply_rope(self._norm(k, lp["k_norm"]), cos, sin)
            # grouped-query: key/value heads to the query heads
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
            o = flash_attention(q, k, v, causal=True)
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
            return x + o.reshape(b, s, h * hd) @ lp["w_o"].astype(dt)

    def _moe_ffn(self, x, lp):
        """-> (x + the layer's experts, the rows its held experts worked)."""
        c = self.config
        b, s, d = x.shape
        with jax.named_scope("router"):     # the norm goes with the router
            xn = self._norm(x, lp["mlp_norm"]).reshape(b * s, d)
        y, rows = held_expert_layer(
            xn, lp, experts_held=c.experts_held,
            expert_offset=c.expert_offset, top_k=c.top_k, routed_scale=1.0,
            score="softmax")
        return x + y.reshape(b, s, d), rows

    def _block(self, kind: str, x, lp, rope):
        """One layer of kind ``<mixer>_moe`` -> (x, held rows)."""
        if kind.startswith("gdn"):
            x = self._gdn_mixer(x, lp)
        else:
            x = self._attn_mixer(x, lp, *rope)
        return self._moe_ffn(x, lp)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.config.dtype)[tokens]

    def _rope(self, seq: int):
        c = self.config
        if seq > c.max_seq:
            raise ValueError(f"{seq} positions, max_seq is {c.max_seq}")
        return rope_cache(seq, c.rotary_dim, c.rope_base)

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        rope = self._rope(tokens.shape[1])
        x, _ = walk_stack(
            self._embed(params, tokens), self.runs, params,
            lambda kind, h, p, side, _: (self._block(kind, h, p, rope)[0],
                                         {}),
            [_REMAT_SAVE[period[0].split("_")[0]] for period, _ in self.runs],
            model="qwen3_next")
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = self._norm(x, params["out_norm"])
            return jnp.einsum("bsd,vd->bsv", x,
                              params["lm_head"].astype(self.config.dtype),
                              preferred_element_type=jnp.float32)

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        """The bare next-token loss over the vocabulary held here."""
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)

    def routing_stats(self, params: Dict[str, jax.Array],
                      tokens: jax.Array) -> jax.Array:
        """(token, choice) pairs that name a held expert, one count a layer
        in order: the rows its grouped product works. A forward of its own,
        layer by layer (the walker's scanned runs have no output a layer).
        Jit it; it is no part of a train step."""
        rope = self._rope(tokens.shape[1])
        x, rows = self._embed(params, tokens), []
        for i, ((kind,), n) in enumerate(self.runs):
            lp = run_params(params, i)[kind]
            for j in range(n):
                x, held = self._block(
                    kind, x, {name: v[j] for name, v in lp.items()}, rope)
                rows.append(held)
        return jnp.stack(rows)
