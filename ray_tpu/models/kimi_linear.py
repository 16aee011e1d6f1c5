"""Kimi-Linear shaped decoder (``model_type: kimi_linear``,
arXiv:2510.26692), training path: a stack whose layers differ in the MIXER
(Kimi Delta Attention, a linear-attention layer with a matrix state a
head, or multi-head latent attention WITHOUT positions) and in the
feed-forward (a dense gated MLP in the leading layers, then shared + routed
experts of which this chip may hold a share), in a published order (three
KDA layers to one latent-attention layer).

Every layer is ``x = x + mixer(RMSNorm(x)); x = x + ffn(RMSNorm(x))``.

* KDA mixer (H heads, d_k = d_v = ``kda_head_dim``), x̂ the normed input:

      q = l2norm_head(silu(conv(x̂ W_q)));  k likewise;  v = silu(conv(x̂ W_v))
      g = -exp(A_log)[head] * softplus((x̂ W_fa) W_fb + dt_bias)    float32
      beta = sigmoid(x̂ W_beta)                                      a head
      per head, S [d_k, d_v] float32 from zero:
          S <- diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
          o_t = S^T (q_t / sqrt(d_k))              (``ops.kda_gated_scan``)

  The norms of q and k and g are the scan's to make: the layer hands
  ``ops.kda_gated_scan`` what its convolutions and its gate projection
  left, and at the published head size (128 x 128: the kernel route) they
  are made inside the scan's kernels, g never written; at any other size
  by ``l2norm`` and the softplus, in float32, before the plain scan.
      y = (RMSNorm_head(o) w_norm * sigmoid((x̂ W_ga) W_gb)) W_o

  ``conv`` a causal depthwise convolution of ``kda_d_conv`` taps without
  bias, its weight held tap-major [K, C]; g one number a key channel a
  token, <= 0; no bias anywhere.
* latent-attention mixer: ``deepseek_v3.latent_attention`` without a query
  bottleneck and without rotation (``mla_use_nope``): the ``qk_rope_head_dim``
  columns of q and the one shared key stay, as the projections made them.
* dense feed-forward: ``W_down (silu(x̂ W_gate) * x̂ W_up)``.
* expert feed-forward: ``deepseek_v3.held_expert_sublayer`` (sigmoid scores,
  top k of score + selection bias, weights normalised over the chosen k and
  scaled, shared expert added; dropless; ``experts_held`` of
  ``n_routed_experts`` from ``expert_offset``).

``vocab_size`` is the vocabulary this chip holds: embedding, head, logits
and loss are over it. The head is untied.

The stack is walked by ``models/stack.py``: a layer's kind is
``<mixer>_<ffn>`` (``kda_dense``, ``kda_moe``, ``mla_moe``), the kinds in
their published order cut into runs of like layers, each run one
rematerialised body over its own stacked parameters, a run of several
layers that body scanned. Parameters are one flat dict: ``wte``,
``lm_head``, ``out_norm`` and ``<run>.<kind>.<name>`` stacked over the
run's layers. Which runs a trace walked is the event
``rtpu.models.stack.runs``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import (causal_conv1d_silu, cross_entropy_loss, kda_gated_scan,
                   rmsnorm, sigmoid_gated_rmsnorm)
from .deepseek_v3 import held_expert_sublayer, latent_attention
from .stack import (draw_params, period_runs, run_params,
                    vocab_row_shardings, walk_stack)

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``: in a latent-attention layer the flash kernels' output
# and row statistics and q as the kernels read it (``deepseek_v3``'s set:
# the backward never runs the forward kernel again). A KDA layer keeps its
# input alone: its projections, convolutions and gates are made again, and
# ``kda_chunk_fwd`` runs a second time in the layer's backward. The kernel
# names what would spare that ("kda_out", "kda_states": 0.67 GB a layer),
# and the described-chip compile of the cell's step decided against it
# (PERF.md, PR 50): with nothing kept the compiler makes 2 instructions
# again on its own, with one layer's kept 20, with all four 45 (accepted,
# temporaries 8.76 -> 10.17 GB). Since the kernels make the norms and the
# gate (PR 51) the step with nothing kept reads 7.95 GB and 0: what a next
# census of the keep-set starts from.
_REMAT_SAVE = {"mla": ("flash_out", "flash_lse", "attn_q"), "kda": ()}

# config.json's 1-based lists
_PUBLISHED_FULL_ATTN = (4, 8, 12, 16, 20, 24, 27)
_PUBLISHED_N_LAYER = 27
_PUBLISHED_LAYER_TYPES = tuple(
    "mla" if i + 1 in _PUBLISHED_FULL_ATTN else "kda"
    for i in range(_PUBLISHED_N_LAYER))


def _mlp_layer_types(n_layer: int, first_k_dense: int) -> Tuple[str, ...]:
    return tuple("dense" if i < first_k_dense else "moe"
                 for i in range(n_layer))


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840          # the ids held here
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYER_TYPES      # the mixers
    mlp_layer_types: Tuple[str, ...] = _mlp_layer_types(
        _PUBLISHED_N_LAYER, 1)                                 # the ffns
    d_model: int = 2304
    # latent attention
    n_head: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    # Kimi Delta Attention (linear_attn_config)
    kda_n_heads: int = 32
    kda_head_dim: int = 128           # keys and values
    kda_d_conv: int = 4
    kda_gate_rank: int = 128          # W_fa, W_ga: d -> rank -> H * head_dim
    # feed-forward
    d_ff: int = 9216                  # the dense layers' gated MLP
    d_expert: int = 1024              # one routed expert's gated MLP
    n_routed_experts: int = 256       # the router's width
    experts_held: int = 256           # experts on this chip ...
    expert_offset: int = 0            # ... from this one
    n_shared_experts: int = 1
    top_k: int = 8
    routed_scaling_factor: float = 2.446
    rms_eps: float = 1e-5
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        unknown = (set(self.layer_types) - {"kda", "mla"}) \
            | (set(self.mlp_layer_types) - {"dense", "moe"})
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``<mixer>_<ffn>`` a layer, in order."""
        return tuple(f"{m}_{f}" for m, f in
                     zip(self.layer_types, self.mlp_layer_types))

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def kda_width(self) -> int:
        return self.kda_n_heads * self.kda_head_dim

    @staticmethod
    def tiny(**kw) -> "KimiLinearConfig":
        """Published head sizes (the scan and the flash kernels are shaped
        by them), everything else small: dense + KDA, two KDA + experts,
        latent + experts, KDA + experts."""
        base = dict(vocab_size=512, d_model=64, n_head=2, kv_lora_rank=32,
                    kda_n_heads=2, kda_gate_rank=16, d_ff=128, d_expert=32,
                    n_routed_experts=8, experts_held=8, top_k=3,
                    layer_types=_PUBLISHED_LAYER_TYPES[:5],
                    mlp_layer_types=_mlp_layer_types(5, 1))
        base.update(kw)
        return KimiLinearConfig(**base)

    @staticmethod
    def kimi_linear_48b_a3b(n_layer: Optional[int] = None,
                            **kw) -> "KimiLinearConfig":
        """moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``, every
        published width; ``n_layer`` keeps the first layers of the
        published order (``first_k_dense_replace`` 1)."""
        n = n_layer or _PUBLISHED_N_LAYER
        return KimiLinearConfig(layer_types=_PUBLISHED_LAYER_TYPES[:n],
                                mlp_layer_types=_mlp_layer_types(n, 1), **kw)


class KimiLinear:
    """init / loss pytree model in the house style (deepseek_v3.py,
    granite_hybrid.py)."""

    def __init__(self, config: KimiLinearConfig):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            config.kinds)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, 0.0 for zeros, or the name of a rule of ``init``)."""
        c = self.config
        d, h, r = c.d_model, c.n_head, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        kh, kw, gr = c.kda_n_heads, c.kda_width, c.kda_gate_rank
        taps = (c.kda_d_conv, kw)
        mixers = {
            "kda": {
                "norm": ((d,), None), "w_q": ((d, kw), std),
                "w_k": ((d, kw), std), "w_v": ((d, kw), std),
                "conv_q": (taps, "conv"), "conv_k": (taps, "conv"),
                "conv_v": (taps, "conv"), "A_log": ((kh,), "A_log"),
                "dt_bias": ((kw,), "dt_bias"),
                "w_f_a": ((d, gr), std), "w_f_b": ((gr, kw), std),
                "w_beta": ((d, kh), std),
                "w_g_a": ((d, gr), std), "w_g_b": ((gr, kw), std),
                "o_norm": ((c.kda_head_dim,), None), "w_o": ((kw, d), res)},
            "mla": {
                "attn_norm": ((d,), None),
                "w_q_nope": ((d, h * dn), std), "w_q_rope": ((d, h * dr), std),
                "w_kv_a": ((d, r), std), "w_k_rope": ((d, dr), std),
                "kv_norm": ((r,), None),
                "w_k_b": ((r, h * dn), std), "w_v_b": ((r, h * dv), std),
                "w_o": ((h * dv, d), res)},
        }
        fs, g = c.n_shared_experts * c.d_expert, c.experts_held
        ffns = {
            "dense": {
                "mlp_norm": ((d,), None), "w_gate": ((d, c.d_ff), std),
                "w_up": ((d, c.d_ff), std), "w_down": ((c.d_ff, d), res)},
            "moe": {
                "mlp_norm": ((d,), None),
                "w_router": ((d, c.n_routed_experts), std),
                "router_bias": ((c.n_routed_experts,), 0.0),
                "s_gate": ((d, fs), std), "s_up": ((d, fs), std),
                "s_down": ((fs, d), res),
                "e_gate": ((g, d, c.d_expert), std),
                "e_up": ((g, d, c.d_expert), std),
                "e_down": ((g, c.d_expert, d), res)},
        }
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), None)}
        for i, ((kind,), n) in enumerate(self.runs):
            mixer, ffn = kind.split("_")
            for name, (shape, how) in dict(mixers[mixer], **ffns[ffn]).items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, norm gains 1, the selection bias 0, and the three
        rules the config's file lists as assumed: ``A_log`` the log of a
        uniform draw in [1, 16] a head, ``dt_bias`` the inverse softplus of
        a dt drawn log-uniformly in [0.001, 0.1] (the family's public
        initialisation: the decays exp(g) run from 0.2 to 0.999 a token),
        the convolutions uniform in +-1/sqrt(taps) (a depthwise conv1d's
        default)."""
        c = self.config
        return draw_params(self._shapes(), rng, c.param_dtype, c.kda_d_conv)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- layers ------------------------------------------------------------

    def _kda_mixer(self, x, lp):
        """The KDA sublayer of the residual x, norm first -> x + y."""
        c = self.config
        b, s, _ = x.shape
        h, dk, dt = c.kda_n_heads, c.kda_head_dim, c.dtype
        w = lambda name: lp[name].astype(dt)                 # noqa: E731
        heads = lambda t: t.reshape(b, s, h, dk)             # noqa: E731
        merged = lambda t: t.reshape(b, s, h * dk)           # noqa: E731
        with jax.named_scope("mixer"):
            xn = rmsnorm(x, lp["norm"], c.rms_eps)
            q, k, v = (xn @ w(n) for n in ("w_q", "w_k", "w_v"))
            step = (xn @ w("w_f_a")) @ w("w_f_b")
            gate = (xn @ w("w_g_a")) @ w("w_g_b")
            write = xn @ w("w_beta")
        with jax.named_scope("conv"):
            q, k, v = (causal_conv1d_silu(t, lp[n]) for t, n in
                       ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
        with jax.named_scope("scan"):
            beta = jax.nn.sigmoid(write.astype(jnp.float32))
            o = kda_gated_scan(q, k, v, step, lp["A_log"], lp["dt_bias"],
                               beta, scale=dk ** -0.5)
        with jax.named_scope("mixer"):
            o = merged(sigmoid_gated_rmsnorm(heads(o), heads(gate),
                                             lp["o_norm"], c.rms_eps))
            return x + o @ w("w_o")

    def _mla_mixer(self, x, lp):
        c = self.config
        y = latent_attention(x, lp, n_head=c.n_head, dtype=c.dtype,
                             eps=c.rms_eps)
        with jax.named_scope("attn"):
            return x + y

    def _dense_ffn(self, x, lp):
        c = self.config
        with jax.named_scope("mlp"):
            xn = rmsnorm(x, lp["mlp_norm"], c.rms_eps)
            hid = jax.nn.silu(xn @ lp["w_gate"].astype(c.dtype)) \
                * (xn @ lp["w_up"].astype(c.dtype))
            return x + hid @ lp["w_down"].astype(c.dtype), None

    def _moe_ffn(self, x, lp):
        """-> (x + the layer's experts, the rows its held experts worked)."""
        c = self.config
        y, rows = held_expert_sublayer(
            x, lp, eps=c.rms_eps, experts_held=c.experts_held,
            expert_offset=c.expert_offset, top_k=c.top_k,
            routed_scale=c.routed_scaling_factor)
        return x + y, rows

    def _block(self, kind: str, x, lp):
        """One layer of kind ``<mixer>_<ffn>`` -> (x, held rows or None)."""
        mixer, ffn = kind.split("_")
        x = (self._kda_mixer if mixer == "kda" else self._mla_mixer)(x, lp)
        return (self._dense_ffn if ffn == "dense" else self._moe_ffn)(x, lp)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.config.dtype)[tokens]

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        c = self.config
        x, _ = walk_stack(
            self._embed(params, tokens), self.runs, params,
            lambda kind, h, p, side, _: (self._block(kind, h, p)[0], {}),
            [_REMAT_SAVE[period[0].split("_")[0]] for period, _ in self.runs],
            model="kimi_linear")
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

    def routing_stats(self, params: Dict[str, jax.Array],
                      tokens: jax.Array) -> jax.Array:
        """(token, choice) pairs that name a held expert, one count an
        expert layer in order: the rows its grouped product works. A
        forward of its own, layer by layer (the walker's scanned runs have
        no output a layer). Jit it; it is no part of a train step."""
        x, rows = self._embed(params, tokens), []
        for i, ((kind,), n) in enumerate(self.runs):
            lp = run_params(params, i)[kind]
            for j in range(n):
                x, held = self._block(
                    kind, x, {name: v[j] for name, v in lp.items()})
                if held is not None:
                    rows.append(held)
        return jnp.stack(rows)
