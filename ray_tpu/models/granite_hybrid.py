"""Granite-4.0-H shaped decoder (``model_type: granitemoehybrid`` with no
routed experts), training path: a stack whose layers are of two kinds in
a published order (``layer_types``), Mamba-2 state-space layers and
grouped-query attention layers without positions, each followed by the
same gated MLP, and four scalar multipliers.

Per layer, with r = ``residual_multiplier`` and x̂ = RMSNorm(x):

    x = x + r * mixer(x̂);   x = x + r * W_down (silu(x̂ W_gate) * x̂ W_up)

* Mamba-2 mixer (H heads of P, state N, one group): ``[z | xBC | dt] =
  x̂ W_in``; ``xBC = silu(conv(xBC) + b)``, a causal depthwise convolution
  of ``mamba_d_conv`` taps; ``[x | B | C] = xBC``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence ``h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t C_t + D x_t``
  (``ops.ssd_scan``); ``y = RMSNorm(y * silu(z)) * w``; out ``y W_out``.
  ``W_in`` is held as its column groups ``w_z``, ``w_xbc``, ``w_dt``, and
  the convolution's weight tap-major [K, C]: the same parameters, laid
  out so that no activation is cut and no minor dimension is 4 wide.
* attention mixer: q of ``n_head`` heads over ``n_kv_head`` key/value
  heads, no rotation (``position_embedding_type`` nope), ``softmax(q k^T *
  attention_multiplier + causal) v`` in the flash kernels with the
  key/value heads repeated to the query heads.

Embedding rows x ``embedding_multiplier``; logits = ``W_emb^T RMSNorm(x) /
logits_scaling`` (the head is the embedding). ``vocab_size`` is the
vocabulary this chip holds: embedding, logits and loss are over it.

The stack runs ``layer_types`` as RUNS of like layers (``stack_runs``):
each run one scanned, rematerialised body over its own stacked
parameters, a run of one a plain call, walked by ``models/stack.py`` (the
walker ``sambay.py`` shares; a run of like layers is a run of periods of
one kind). Parameters are one flat dict: ``wte``, ``out_norm`` and
``<run>.<kind>.<name>`` stacked over the run's layers. Which runs a trace
walked is the event ``rtpu.models.stack.runs``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (causal_conv1d_silu, cross_entropy_loss, flash_attention,
                   gated_rmsnorm, rmsnorm, ssd_scan)
from .stack import period_runs, walk_stack

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``. Every layer: the flash kernels' output and row
# statistics, so that the attention layer's backward does not run the
# forward kernel again. Every run but the FIRST: the gated MLP's two input
# products too (``mlp_gate``, ``mlp_up``: 268 MB a layer at 8192 tokens of
# ``d_ff`` 8192 in bf16), so that the backward does not run the layer's two
# largest matmuls a second time. The backward walks the runs last to first:
# what a later run keeps is freed before the step's peak, which is in the
# first run's backward beside every gradient made by then. Asked of the
# compiler for a described v5e (the benchmark's step: runs of 5, 1, 4, batch
# 2 x 4096; PERF.md, PR 37): 1.34 GB kept in the runs of 1 and 4 costs 61 MB
# of temporaries (8.65 GB) and nothing is made again. With the first run
# keeping them too the step is still accepted (10.86 GB) but only because
# the compiler's own rematerialisation has made it fit: the head's logits
# and the mixers' products made again, 49.8 T matmul operations a step
# where nothing kept is 47.4 T and this is 44.7 T, and 4 % slower on the
# chip than nothing kept. The next supersets: + the mixers' ``w_z`` product
# 11.82 GB, + ``w_xbc`` refused ("Used 16.25G of 15.75G hbm"); batch 3 with
# every layer keeping both refused ("Used 17.15G").
_REMAT_SAVE = ("flash_out", "flash_lse")
_REMAT_SAVE_LATER_RUNS = _REMAT_SAVE + ("mlp_gate", "mlp_up")

_PUBLISHED_LAYER_TYPES = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


def stack_runs(layer_types) -> List[Tuple[str, int]]:
    """``layer_types`` as runs of like layers: [(kind, length), ...]."""
    return [(period[0], n) for period, n in period_runs(layer_types)]


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352          # the ids held here
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYER_TYPES
    d_model: int = 2048
    d_ff: int = 8192                  # shared_intermediate_size
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256       # how the scan is cut, not what it is
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def d_conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        base = dict(vocab_size=512, d_model=64, d_ff=128, n_head=4,
                    n_kv_head=2, mamba_n_heads=4, mamba_chunk_size=128,
                    layer_types=("mamba", "mamba", "attention", "mamba"))
        base.update(kw)
        return GraniteHybridConfig(**base)

    @staticmethod
    def granite4_h_micro(n_layer: Optional[int] = None,
                         **kw) -> "GraniteHybridConfig":
        """ibm-granite/granite-4.0-h-micro ``config.json``; ``n_layer``
        keeps the first layers of the published order."""
        return GraniteHybridConfig(
            layer_types=_PUBLISHED_LAYER_TYPES[:n_layer], **kw)


class GraniteHybrid:
    """init / loss pytree model in the house style (gpt.py, llama.py,
    deepseek_v3.py)."""

    def __init__(self, config: GraniteHybridConfig):
        self.config = config
        self.runs = stack_runs(config.layer_types)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, or the name of a rule of ``init``)."""
        c = self.config
        d, f = c.d_model, c.d_ff
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        mlp = {"mlp_norm": ((d,), None), "w_gate": ((d, f), std),
               "w_up": ((d, f), std), "w_down": ((f, d), res)}
        h, di, ch = c.mamba_n_heads, c.d_inner, c.d_conv_channels
        kinds = {
            "mamba": dict({
                "norm": ((d,), None), "w_z": ((d, di), std),
                "w_xbc": ((d, ch), std), "w_dt": ((d, h), std),
                "conv_w": ((c.mamba_d_conv, ch), "conv"),
                "conv_b": ((ch,), "conv"), "dt_bias": ((h,), "dt_bias"),
                "A_log": ((h,), "A_log"), "D": ((h,), None),
                "gate_norm": ((di,), None), "w_out": ((di, d), res)}, **mlp),
            "attention": dict({
                "norm": ((d,), None),
                "w_q": ((d, c.n_head * c.head_dim), std),
                "w_k": ((d, c.n_kv_head * c.head_dim), std),
                "w_v": ((d, c.n_kv_head * c.head_dim), std),
                "w_o": ((c.n_head * c.head_dim, d), res)}, **mlp),
        }
        out = {"wte": ((c.padded_vocab, d), std), "out_norm": ((d,), None)}
        for i, (kind, n) in enumerate(self.runs):
            for name, (shape, how) in kinds[kind].items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, norm gains and D at 1, and the three rules the
        config's file lists as assumed: ``A_log`` = log(1..H) by head,
        ``dt_bias`` the inverse softplus of a dt drawn log-uniformly in
        [0.001, 0.1] (both the family's public initialisation, which
        keeps the decays exp(dt A) between 0.002 and 0.999 a token), the
        convolution uniform in +-1/sqrt(taps) (a depthwise conv1d's
        default)."""
        c, pd = self.config, self.config.param_dtype
        shapes = self._shapes()
        keys = jax.random.split(rng, len(shapes))

        def draw(key, shape, how):
            if how is None:
                return jnp.ones(shape, pd)
            if how == "A_log":
                return jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[-1] + 1, dtype=pd)), shape)
            if how == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    key, shape, pd, math.log(1e-3), math.log(0.1)))
                return dt + jnp.log(-jnp.expm1(-dt))
            if how == "conv":
                bound = 1.0 / math.sqrt(c.mamba_d_conv)
                return jax.random.uniform(key, shape, pd, -bound, bound)
            return jax.random.normal(key, shape, pd) * how

        return {n: draw(k, shape, how)
                for k, (n, (shape, how)) in zip(keys, shapes.items())}

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows: this model is one
        pipeline stage's chip, and no axis of the mesh cuts a layer."""
        from jax.sharding import NamedSharding

        from ..parallel.mesh import AxisRules

        rules = rules or AxisRules()
        return {n: NamedSharding(mesh, rules.mesh_axes(
            ("vocab", "embed") if n == "wte" else (None,) * len(shape)))
            for n, (shape, _) in self._shapes().items()}

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- layers ------------------------------------------------------------

    def _mamba_mixer(self, x, lp):
        c = self.config
        b, s, _ = x.shape
        h, p, g, n = (c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups,
                      c.mamba_d_state)
        di, dt = c.d_inner, c.dtype
        with jax.named_scope("mixer"):
            xn = rmsnorm(x, lp["norm"], c.rms_eps)
            z = xn @ lp["w_z"].astype(dt)
            xbc = xn @ lp["w_xbc"].astype(dt)
            step = xn @ lp["w_dt"].astype(dt)
        with jax.named_scope("conv"):
            xbc = causal_conv1d_silu(xbc, lp["conv_w"], lp["conv_b"])
        with jax.named_scope("mixer"):
            xs = xbc[..., :di].reshape(b, s, h, p)
            bm = xbc[..., di:di + g * n].reshape(b, s, g, n)
            cm = xbc[..., di + g * n:].reshape(b, s, g, n)
        with jax.named_scope("scan"):
            step = jax.nn.softplus(step.astype(jnp.float32)
                                   + lp["dt_bias"].astype(jnp.float32))
            y = ssd_scan(xs, step, -jnp.exp(lp["A_log"].astype(jnp.float32)),
                         bm, cm, lp["D"], chunk=c.mamba_chunk_size)
            y = gated_rmsnorm(y.reshape(b, s, di), z, lp["gate_norm"],
                              c.rms_eps)
        with jax.named_scope("mixer"):
            return x + c.residual_multiplier * (y @ lp["w_out"].astype(dt))

    def _attention_mixer(self, x, lp):
        c = self.config
        b, s, _ = x.shape
        h, kh, hd, dt = c.n_head, c.n_kv_head, c.head_dim, c.dtype
        with jax.named_scope("attn"):
            xn = rmsnorm(x, lp["norm"], c.rms_eps)
            q = (xn @ lp["w_q"].astype(dt)).reshape(b, s, h, hd)
            k = (xn @ lp["w_k"].astype(dt)).reshape(b, s, kh, hd)
            v = (xn @ lp["w_v"].astype(dt)).reshape(b, s, kh, hd)
            if kh != h:   # grouped-query: key/value heads to the query heads
                k = jnp.repeat(k, h // kh, axis=2)
                v = jnp.repeat(v, h // kh, axis=2)
            o = flash_attention(q, k, v, causal=True,
                                sm_scale=c.attention_multiplier)
            return x + c.residual_multiplier * (
                o.reshape(b, s, h * hd) @ lp["w_o"].astype(dt))

    def _block(self, kind: str, x, lp):
        c = self.config
        x = (self._mamba_mixer if kind == "mamba"
             else self._attention_mixer)(x, lp)
        with jax.named_scope("mlp"):
            xn = rmsnorm(x, lp["mlp_norm"], c.rms_eps)
            gate = checkpoint_name(xn @ lp["w_gate"].astype(c.dtype),
                                   "mlp_gate")
            up = checkpoint_name(xn @ lp["w_up"].astype(c.dtype), "mlp_up")
            return x + c.residual_multiplier * (
                (jax.nn.silu(gate) * up) @ lp["w_down"].astype(c.dtype))

    def _run_layers(self, x, params):
        """The stack, run after run of like layers, each layer
        rematerialised (``stack.walk_stack``)."""
        c = self.config
        kept = [_REMAT_SAVE if i == 0 else _REMAT_SAVE_LATER_RUNS
                for i in range(len(self.runs))]
        products = 2 * math.prod(x.shape[:-1]) * c.d_ff \
            * jnp.dtype(c.dtype).itemsize        # mlp_gate and mlp_up
        x, _ = walk_stack(
            x, [((kind,), n) for kind, n in self.runs], params,
            lambda kind, h, p, side, _: (self._block(kind, h, p), {}),
            kept, model="granite_hybrid",
            facts={"kept_bytes_per_layer": products,
                   "kept_bytes": products * sum(n for _, n in self.runs[1:])})
        return x

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        c = self.config
        with jax.named_scope("embed"):
            x = params["wte"].astype(c.dtype)[tokens] * jnp.asarray(
                c.embedding_multiplier, c.dtype)
        x = self._run_layers(x, params)
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = rmsnorm(x, params["out_norm"], c.rms_eps)
            return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(c.dtype),
                              preferred_element_type=jnp.float32) \
                / c.logits_scaling

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        """The bare next-token loss over the vocabulary held here."""
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)
