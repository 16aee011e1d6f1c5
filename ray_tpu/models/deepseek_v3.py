"""DeepSeek-V3 shaped decoder (``model_type: deepseek_v3`` and the models
that keep its keys), training path: multi-head latent attention with or
without a query bottleneck (``q_lora_rank``), RoPE plain or YaRN-scaled,
``first_k_dense`` leading layers with a dense gated MLP and then layers of
routed + shared experts, of which this chip may hold a share; the
residual one stream, or ``hc_mult`` streams mixed around every sublayer
by manifold-constrained hyper-connections (``ops/hyper_connection.py``).

Per sublayer, x̂ = RMSNorm(x) (x the residual, or with ``hc_mult`` > 1 the
streams' weighted sum z):

* attention: per head ``[q_nope | q_rope] = x̂ W_q``, or with
  ``q_lora_rank`` ``RMSNorm(x̂ W_qa) W_qb``; ``[c | k_pe] =
  x̂ W_kva``; ``[k_nope | v] = RMSNorm(c) W_kvb``; RoPE (interleaved pairs)
  on every head's ``q_rope`` and on the ONE ``k_pe`` all heads share;
  ``score_h = (q_nope_h·k_nope_h + q_rope_h·k_pe) / sqrt(dn + dr)`` (times
  YaRN's m² where ``rope_factor`` > 1: ``ops.layers.yarn_rope_cache``), causal
  softmax, ``o_h = P v_h``; out ``concat_h(o_h) W_o``. The attention runs
  in the latent flash kernels (``ops/flash_attention.py``), which take the
  two parts of the score apart, so the projections are kept as separate
  matrices (``w_q_nope``, ``w_q_rope``, ``w_kv_a``, ``w_k_rope``, ``w_k_b``,
  ``w_v_b``): the published matrices with their columns sorted by kind.
* expert layers: ``ops.expert_layer.held_expert_layer`` (sigmoid scores,
  top k of score + selection bias, weights normalised over the chosen k
  and scaled, shared experts added; dropless; ``experts_held`` of
  ``n_routed_experts`` from ``expert_offset``).

``vocab_size`` is the vocabulary this chip holds: embedding, head, logits
and loss are over it (a sliced vocabulary is a smaller vocabulary).

The stack: the dense layers one by one, then the expert layers through
one scanned runner; every layer is rematerialised, saving what
``_REMAT_SAVE`` names. The carry is the residual [B, S, d], or the tuple
of its ``hc_mult`` streams.

Not here: the multi-token-prediction module (``num_nextn_predict_layers``)
of the checkpoints that have one, and its second loss term.
"""
from __future__ import annotations

import math
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (apply_rope, cross_entropy_loss, flash_attention, rmsnorm,
                   rope_cache)
from ..ops.expert_layer import held_expert_layer
from ..ops.hyper_connection import HC_PARAMS, hc_mix, hc_param_shapes
from ..ops.layers import yarn_rope_cache, yarn_softmax_scale
from ..perf.recorder import record as _record


# What a rematerialised layer keeps for its backward, by
# ``checkpoint_name``: the latent flash kernels' output and row statistics
# (so the backward never re-runs the forward kernel) and q as the kernels
# read it. Everything else is computed again, k and v included: at 16 384
# tokens a step and five layers, keeping them too is 88 MB more than a v5e
# holds beside 576 M parameters' adamw state (PERF.md, PR 33).
_REMAT_SAVE = ("flash_out", "flash_lse", "attn_q")
# Behind a query bottleneck q is two products of a ``q_lora_rank``-wide row
# and is made again. Four residual streams of d 3584 at 8192 tokens a step
# make a layer's input 235 MB; with q kept too (101 MB a layer) a v5e
# refused five layers beside 759 M parameters' adamw state by 1.73 MB
# (PERF.md, PR 45).
_REMAT_SAVE_BOTTLENECK = ("flash_out", "flash_lse")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256          # the ids held here
    n_layer: int = 48
    first_k_dense: int = 1
    d_model: int = 2048
    n_head: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None  # the query bottleneck, if any
    d_ff: int = 6144                  # the dense layers' gated MLP
    d_expert: int = 768               # one routed expert's gated MLP
    n_routed_experts: int = 128       # the router's width
    experts_held: int = 128           # experts on this chip ...
    expert_offset: int = 0            # ... from this one
    n_shared_experts: int = 2
    top_k: int = 6
    routed_scaling_factor: float = 2.448
    max_seq: int = 32768
    rope_base: float = 1000000.0
    # YaRN (``rope_scaling``, the DeepSeek-V3 convention); factor 1: plain
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # hyper-connections: residual streams (1: the plain residual)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    num_nextn_predict_layers: int = 0
    rms_eps: float = 1e-6
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_nextn_predict_layers != 0:
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: "
                "this model has no multi-token-prediction module (one more "
                "expert layer over [h | Emb(next)] W_proj sharing the "
                "embedding and the head, and its weighted second loss "
                "term); build with 0, which is the next-token model")

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)

    @staticmethod
    def tiny(**kw) -> "DeepseekV3Config":
        base = dict(vocab_size=512, n_layer=3, d_model=64, n_head=2,
                    kv_lora_rank=32, d_ff=128, d_expert=32,
                    n_routed_experts=8, experts_held=8, n_shared_experts=2,
                    top_k=3, max_seq=128)
        base.update(kw)
        return DeepseekV3Config(**base)

    @staticmethod
    def kanana2_30b_a3b(**kw) -> "DeepseekV3Config":
        """kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``."""
        return DeepseekV3Config(**kw)

    @staticmethod
    def xing4_29b_a4b(**kw) -> "DeepseekV3Config":
        """XingChen-AGI/Xing4.0-29B-A4B ``config.json`` (``model_type``
        ``xing4_0``), every published width; its prediction module
        (``num_nextn_predict_layers`` 1) is not built."""
        base = dict(vocab_size=131072, n_layer=40, first_k_dense=2,
                    d_model=3584, n_head=32, kv_lora_rank=512,
                    q_lora_rank=768, d_ff=9216, d_expert=1024,
                    n_routed_experts=64, experts_held=64, n_shared_experts=1,
                    top_k=4, routed_scaling_factor=2.0, max_seq=262144,
                    rope_base=10000.0, rope_factor=64.0,
                    rope_original_max=4096, rope_beta_fast=32.0,
                    rope_beta_slow=1.0, rope_mscale=1.0,
                    rope_mscale_all_dim=1.0, hc_mult=4, hc_sinkhorn_iters=20,
                    hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0))
        base.update(kw)
        return DeepseekV3Config(**base)


def latent_attention(x, lp, *, n_head: int, dtype, eps: float, rope=None,
                     sm_scale=None):
    """The latent-attention sublayer of its input x [B, S, d], norm first,
    without the residual, for any model that holds the layer's parameters
    under these names (``attn_norm``, ``w_kv_a``, ``kv_norm``, ``w_q_nope``,
    ``w_q_rope``, ``w_k_rope``, ``w_k_b``, ``w_v_b``, ``w_o``; behind a
    query bottleneck ``w_q_a`` and ``q_norm`` too). ``rope`` = (cos, sin)
    turns every head's ``q_rope`` and the one ``k_rope``; None leaves them
    as the projections made them (a layer without positions). ``sm_scale``
    None is 1 / sqrt(dn + dr)."""
    b, s, _ = x.shape
    h, dt = n_head, dtype
    turn = (lambda t: t) if rope is None else (
        lambda t: _rope_interleaved(t, *rope))
    with jax.named_scope("attn"):
        xn = rmsnorm(x, lp["attn_norm"], eps)
        latent = rmsnorm(xn @ lp["w_kv_a"].astype(dt), lp["kv_norm"], eps)
        heads = lambda t: t.reshape(b, s, h, -1)  # noqa: E731
        q_in = xn if "w_q_a" not in lp else rmsnorm(
            xn @ lp["w_q_a"].astype(dt), lp["q_norm"], eps)
        q_nope = q_in @ lp["w_q_nope"].astype(dt)
        q_rope = turn(heads(q_in @ lp["w_q_rope"].astype(dt))
                      ).reshape(b, s, -1)
        k_rope = turn((xn @ lp["w_k_rope"].astype(dt))[:, :, None, :]
                      )[:, :, 0, :]
        k_nope = latent @ lp["w_k_b"].astype(dt)
        v = latent @ lp["w_v_b"].astype(dt)
        # named in the merged [B, S, H*d] form the kernels read (a
        # 64-wide minor dimension would be kept padded to 128 lanes)
        q_nope, q_rope = (checkpoint_name(t, "attn_q")
                          for t in (q_nope, q_rope))
        o = flash_attention(
            heads(q_nope), heads(k_nope), heads(v), causal=True,
            q_rope=heads(q_rope), k_rope=k_rope, sm_scale=sm_scale)
        return o.reshape(b, s, -1) @ lp["w_o"].astype(dt)


def held_expert_sublayer(z, lp, *, eps: float, experts_held: int,
                         expert_offset: int, top_k: int,
                         routed_scale: float):
    """The expert sublayer of its input z [B, S, d], norm first (it goes
    with the router), without the residual -> (shared experts + the held
    experts' part of the routed sum, the rows the held experts worked)."""
    b, s, d = z.shape
    with jax.named_scope("router"):
        xn = rmsnorm(z, lp["mlp_norm"], eps).reshape(b * s, d)
    y, rows = held_expert_layer(
        xn, lp, experts_held=experts_held, expert_offset=expert_offset,
        top_k=top_k, routed_scale=routed_scale)
    return y.reshape(b, s, d), rows


class DeepseekV3:
    """init / loss pytree model in the house style (gpt.py, llama.py).
    Parameters are one flat dict: ``wte``, ``lm_head``, ``out_norm``,
    ``dense.<name>`` stacked over the leading dense layers and
    ``moe.<name>`` stacked over the expert layers."""

    def __init__(self, config: DeepseekV3Config):
        self.config = config

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Optional[float]]]:
        """name -> (shape, std of its normal init; None: ones, 0: zeros,
        ("fill", v): the constant v)."""
        c = self.config
        d, h, r = c.d_model, c.n_head, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        q_in = c.q_lora_rank or d       # what the head projections read
        attn = {
            "attn_norm": ((d,), None),
            "w_q_nope": ((q_in, h * dn), std),
            "w_q_rope": ((q_in, h * dr), std),
            "w_kv_a": ((d, r), std), "w_k_rope": ((d, dr), std),
            "kv_norm": ((r,), None),
            "w_k_b": ((r, h * dn), std), "w_v_b": ((r, h * dv), std),
            "w_o": ((h * dv, d), res), "mlp_norm": ((d,), None),
        }
        if c.q_lora_rank:
            attn.update({"w_q_a": ((d, c.q_lora_rank), std),
                         "q_norm": ((c.q_lora_rank,), None)})
        if c.hc_mult > 1:
            # one set a sublayer: Φ ~ N(0, init_std), gain 1, the biases
            # N(0, 1) so that no map starts degenerate, every α 0.01
            how = {"phi": std, "gain": None, "bias": 1.0,
                   "alpha": ("fill", 0.01)}
            attn.update({f"{sub}.{name}": (shape, how[name])
                         for sub in ("hc_attn", "hc_mlp") for name, shape
                         in hc_param_shapes(c.hc_mult, d).items()})
        fs = c.n_shared_experts * c.d_expert
        g = c.experts_held
        kinds = {
            "dense": (c.first_k_dense, dict(attn, **{
                "w_gate": ((d, c.d_ff), std), "w_up": ((d, c.d_ff), std),
                "w_down": ((c.d_ff, d), res)})),
            "moe": (c.n_layer - c.first_k_dense, dict(attn, **{
                "w_router": ((d, c.n_routed_experts), std),
                "router_bias": ((c.n_routed_experts,), 0.0),
                "s_gate": ((d, fs), std), "s_up": ((d, fs), std),
                "s_down": ((fs, d), res),
                "e_gate": ((g, d, c.d_expert), std),
                "e_up": ((g, d, c.d_expert), std),
                "e_down": ((g, c.d_expert, d), res)})),
        }
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), None)}
        for kind, (layers, shapes) in kinds.items():
            for name, (shape, s) in shapes.items():
                out[f"{kind}.{name}"] = ((layers,) + shape, s)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        pd = self.config.param_dtype
        shapes = self._shapes()
        keys = jax.random.split(rng, len(shapes))
        return {n: (jnp.ones(shape, pd) if std is None else
                    jnp.full(shape, std[1], pd) if isinstance(std, tuple)
                    else jax.random.normal(k, shape, pd) * std)
                for k, (n, (shape, std)) in zip(keys, shapes.items())}

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows: this model is one
        chip's share of an expert-parallel job (the experts it holds are
        its own), so no axis of the mesh cuts a layer."""
        from jax.sharding import NamedSharding

        from ..parallel.mesh import AxisRules

        rules = rules or AxisRules()
        return {n: NamedSharding(mesh, rules.mesh_axes(
            ("vocab", "embed") if n in ("wte", "lm_head")
            else (None,) * len(shape)))
            for n, (shape, _) in self._shapes().items()}

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- layers ------------------------------------------------------------

    def _residual(self, x, lp, which, scope, f):
        """The one residual path around a sublayer ``f(its input) -> (y,
        aux)``. One stream: ``x + y``, the sum under the sublayer's own
        ``scope`` as ever. ``hc_mult`` streams (x their tuple): the set
        ``which`` of the layer's hyper-connection parameters reads them
        into f's input and mixes f's output back into them. -> (x', aux)"""
        c = self.config
        if c.hc_mult == 1:
            y, aux = f(x)
            with jax.named_scope(scope) if scope else nullcontext():
                return x + y, aux
        return hc_mix(
            x, {name: lp[f"{which}.{name}"] for name in HC_PARAMS}, f,
            iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
            clamp=tuple(c.hc_res_clamp), rms_eps=c.rms_eps)

    def _attention(self, x, lp, cos, sin):
        """``latent_attention`` with this model's positions and scale."""
        c = self.config
        return latent_attention(
            x, lp, n_head=c.n_head, dtype=c.dtype, eps=c.rms_eps,
            rope=(cos, sin),
            sm_scale=None if c.rope_factor == 1.0 else yarn_softmax_scale(
                c.qk_nope_head_dim + c.qk_rope_head_dim, c.rope_factor,
                c.rope_mscale_all_dim)), None

    def _attention_sublayer(self, x, lp, cos, sin):
        return self._residual(
            x, lp, "hc_attn", "attn",
            lambda z: self._attention(z, lp, cos, sin))[0]

    def _dense_block(self, x, lp, cos, sin):
        c = self.config

        def mlp(z):
            with jax.named_scope("mlp"):
                xn = rmsnorm(z, lp["mlp_norm"], c.rms_eps)
                hid = jax.nn.silu(xn @ lp["w_gate"].astype(c.dtype)) \
                    * (xn @ lp["w_up"].astype(c.dtype))
                return hid @ lp["w_down"].astype(c.dtype), None

        x = self._attention_sublayer(x, lp, cos, sin)
        return self._residual(x, lp, "hc_mlp", "mlp", mlp)[0]

    def _moe_block(self, x, lp, cos, sin):
        """-> (the layer's output, the rows its held experts worked)."""
        c = self.config

        def experts(z):
            return held_expert_sublayer(
                z, lp, eps=c.rms_eps, experts_held=c.experts_held,
                expert_offset=c.expert_offset, top_k=c.top_k,
                routed_scale=c.routed_scaling_factor)

        x = self._attention_sublayer(x, lp, cos, sin)
        return self._residual(x, lp, "hc_mlp", None, experts)

    def _run_layers(self, x, params, cos, sin):
        """The one place the stack is walked: the dense layers, then the
        expert layers through one scanned body, each rematerialised. ->
        (x, held rows of each expert layer)."""
        c = self.config
        policy = jax.checkpoint_policies.save_only_these_names(
            *(_REMAT_SAVE_BOTTLENECK if c.q_lora_rank else _REMAT_SAVE))
        group = lambda kind: {n.split(".", 1)[1]: v  # noqa: E731
                              for n, v in params.items()
                              if n.startswith(kind + ".")}
        dense = jax.checkpoint(
            lambda h, lp: self._dense_block(h, lp, cos, sin), policy=policy)
        for i in range(c.first_k_dense):
            x = dense(x, {n: v[i] for n, v in group("dense").items()})

        return jax.lax.scan(
            jax.checkpoint(lambda h, lp: self._moe_block(h, lp, cos, sin),
                           policy=policy), x, group("moe"))

    def _backbone(self, params, tokens):
        c = self.config
        with jax.named_scope("embed"):
            x = params["wte"].astype(c.dtype)[tokens]
        if c.rope_factor == 1.0:
            cos, sin = rope_cache(tokens.shape[1], c.qk_rope_head_dim,
                                  c.rope_base)
        else:
            cos, sin = yarn_rope_cache(
                tokens.shape[1], c.qk_rope_head_dim, c.rope_base,
                factor=c.rope_factor, original_max=c.rope_original_max,
                beta_fast=c.rope_beta_fast, beta_slow=c.rope_beta_slow,
                mscale=c.rope_mscale, mscale_all_dim=c.rope_mscale_all_dim)
        # what one rematerialised layer keeps of its input for its backward
        _record("rtpu.models.deepseek_v3.residual", "streams", {
            "hc_streams": c.hc_mult,
            "hc_sublayers": 2 * c.n_layer if c.hc_mult > 1 else 0,
            "residual_stream_bytes":
                c.hc_mult * math.prod(x.shape) * x.dtype.itemsize})
        if c.hc_mult > 1:
            # every stream starts as the embedding; the head reads their sum
            x = (x,) * c.hc_mult
        x, rows = self._run_layers(x, params, cos, sin)
        if c.hc_mult > 1:
            with jax.named_scope("mhc"):
                x = sum(xj.astype(jnp.float32) for xj in x).astype(c.dtype)
        with jax.named_scope("lm_head"):     # the final norm goes with it
            return rmsnorm(x, params["out_norm"], c.rms_eps), rows

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        x, _ = self._backbone(params, tokens)
        with jax.named_scope("lm_head"):
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
        """(token, choice) pairs that name a held expert, one count an
        expert layer [n_layer - first_k_dense]: the rows its grouped
        product works. Jit it; it is no part of a train step."""
        return self._backbone(params, tokens)[1]


def _rope_interleaved(x, cos, sin):
    """RoPE on x [B, S, H, D] whose rotated pairs are NEIGHBOURS
    (x0, x1), (x2, x3), ... (``rope_interleave``): the pairs are first
    sorted into halves, as the published code does, then rotated in the
    half-split form. q and k get the same order, so the score is that of
    the pairwise rotation."""
    b, s, h, d = x.shape
    halves = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
    return apply_rope(halves, cos, sin)
