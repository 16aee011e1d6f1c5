"""Nemotron-H shaped decoder (``model_type: nemotron_h``), training path: a
stack whose every layer is ONE sublayer, a mixer or a feed-forward part
alone, in a published order (``hybrid_override_pattern``: ``M`` a Mamba-2
state-space layer, ``*`` grouped-query attention without positions, ``E``
an expert layer whose routed experts work in a latent), of which this chip
may hold a SHARE: some of the routed experts, some of the Mamba-2 groups
with their heads, some of the query heads with the key/value heads they
read, a slice of the vocabulary.

Every layer, with x̂ = RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w:

    x = x + f(x̂)

* ``mamba`` (H heads of P, state N, G groups of H / G heads, a convolution
  of ``mamba_d_conv`` taps with bias): ``[z | xBC | dt] = x̂ W_in``; ``xBC =
  silu(conv(xBC) + b)``; ``[x | B | C] = xBC``, B and C [G, N] shared by a
  group's heads; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t C_t + D
  x_t`` (``ops.ssd_scan``); ``y = RMSNorm_group(y * silu(z)) * w``, the norm
  over each group's H P / G channels; out ``y W_out``. Held here:
  ``mamba_groups_held`` groups from ``mamba_group_offset``: their heads'
  columns of ``W_in`` (held as ``w_z``, ``w_xbc`` = x | B | C, ``w_dt``),
  convolution channels, ``w``, and rows of ``W_out``. The output is those
  heads' part of ``y W_out``.
* ``attention`` (``n_head`` query heads over ``n_kv_head`` key/value
  heads, no rotation, no bias): ``softmax(q k^T / sqrt(head_dim) + causal)
  v`` in the flash kernels, then ``W_o``. Held here: ``heads_held`` query
  heads from ``head_offset`` and the key/value heads they read; the output
  is their part of ``o W_o``.
* ``moe`` (``ops.expert_layer.held_expert_layer``, ``score`` ``sigmoid``,
  ``expert`` ``relu2``, a latent): ``s = sigmoid(x̂ W_r)`` over all experts
  in f32, the top ``top_k`` of ``s + bias`` (the bias a buffer at zero),
  weights ``s / sum of the chosen s * routed_scale``; ``u = x̂ W_fc1``;
  ``r`` = the held experts' part of ``sum_e w_e W_down,e relu(u W_up,e)^2``
  in the latent; out ``r W_fc2 + W_sdown relu(x̂ W_sup)^2``, the shared
  expert on x̂ itself and whole on every chip.

Logits are ``RMSNorm(x) W_head``, untied, over the ``vocab_size`` rows held
here. Nothing stands in for the absent chips: a partial result goes on.
The published checkpoint's multi-token-prediction layer is not built.

The stack is walked by ``models/stack.py``: the kinds in their order cut
into runs of like periods of up to two kinds (``EMEMEMEMEM*`` is five
scanned (``moe``, ``mamba``) periods and a run of one), parameters one flat
dict: ``wte``, ``lm_head``, ``out_norm`` and ``<run>.<kind>.<name>``
stacked over the run's periods. What is held of what is the event
``rtpu.models.nemotron_h.share``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import (causal_conv1d_silu, cross_entropy_loss, flash_attention,
                   gated_rmsnorm, rmsnorm, ssd_scan)
from ..ops.expert_layer import held_expert_layer
from ..perf.recorder import record as _record
from .stack import (draw_params, period_runs, run_params,
                    vocab_row_shardings, walk_stack)

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``: the attention layer the flash kernels' output and row
# statistics (its backward never runs the forward kernel again); a Mamba-2
# or an expert layer its input alone.
_REMAT_SAVE = {"attention": ("flash_out", "flash_lse"), "mamba": (),
               "moe": ()}

_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}

# NVIDIA-Nemotron-3-Super-120B-A12B ``hybrid_override_pattern``: 88 layers
_PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072          # the ids held here
    pattern: str = _PUBLISHED_PATTERN     # hybrid_override_pattern, whole
    first_layer: int = 0              # the layers built: n_layer characters
    n_layer: Optional[int] = None     # of ``pattern`` from this one
    d_model: int = 4096
    # Mamba-2, of the whole layer ...
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256       # how the scan is cut, not what it is
    # ... and the groups (with their heads) this chip holds
    mamba_groups_held: Optional[int] = None    # None: all
    mamba_group_offset: int = 0
    # attention, of the whole layer ...
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    # ... and the query heads (with the key/value heads they read) held
    heads_held: Optional[int] = None           # None: all
    head_offset: int = 0
    # experts
    d_latent: int = 1024              # the routed experts' width in and out
    d_expert: int = 2688
    d_shared: int = 5376              # the shared expert's, on d_model
    n_routed_experts: int = 512       # the router's width
    experts_held: Optional[int] = None         # None: all
    expert_offset: int = 0
    top_k: int = 22
    routed_scale: float = 5.0
    rms_eps: float = 1e-5
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        unknown = set(self.pattern) - set(_KINDS)
        if unknown:
            raise ValueError(f"unknown layer characters {sorted(unknown)}")
        if not self.layer_types:
            raise ValueError("no layer of the pattern is built")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.n_head % self.n_kv_head:
            raise ValueError("heads are a multiple of groups, query heads "
                             "of key/value heads")
        g, off = self.groups_held, self.mamba_group_offset
        if not 0 < g <= self.mamba_n_groups - off or off < 0:
            raise ValueError(f"groups {off}..{off + g} of "
                             f"{self.mamba_n_groups}")
        h, off, per_kv = self.q_heads_held, self.head_offset, \
            self.n_head // self.n_kv_head
        if not 0 < h <= self.n_head - off or off < 0:
            raise ValueError(f"query heads {off}..{off + h} of {self.n_head}")
        # whole key/value heads, or a share of one's readers
        if (h % per_kv or off % per_kv) and off // per_kv \
                != (off + h - 1) // per_kv:
            raise ValueError(
                f"query heads {off}..{off + h} read parts of several "
                f"key/value heads ({per_kv} readers a head)")
        if not 0 < self.n_experts_held <= self.n_routed_experts \
                - self.expert_offset:
            raise ValueError("experts held of the routed experts")

    # -- the layers built --------------------------------------------------

    @property
    def layer_types(self) -> Tuple[str, ...]:
        chars = self.pattern[self.first_layer:][:self.n_layer]
        return tuple(_KINDS[c] for c in chars)

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    # -- the share ---------------------------------------------------------

    @property
    def groups_held(self) -> int:
        return self.mamba_n_groups if self.mamba_groups_held is None \
            else self.mamba_groups_held

    @property
    def mamba_heads_held(self) -> int:
        return self.mamba_n_heads // self.mamba_n_groups * self.groups_held

    @property
    def mamba_head_offset(self) -> int:
        return self.mamba_n_heads // self.mamba_n_groups \
            * self.mamba_group_offset

    @property
    def d_inner(self) -> int:
        """Channels of the heads held."""
        return self.mamba_heads_held * self.mamba_d_head

    @property
    def d_conv_channels(self) -> int:
        return self.d_inner + 2 * self.groups_held * self.mamba_d_state

    @property
    def q_heads_held(self) -> int:
        return self.n_head if self.heads_held is None else self.heads_held

    @property
    def kv_heads_held(self) -> int:
        per_kv = self.n_head // self.n_kv_head
        return (self.head_offset + self.q_heads_held - 1) // per_kv \
            - self.head_offset // per_kv + 1

    @property
    def n_experts_held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    def share(self) -> Dict[str, Any]:
        """What this chip holds, and of how many."""
        return {
            "mamba_groups": [self.groups_held, self.mamba_n_groups],
            "mamba_group_offset": self.mamba_group_offset,
            "mamba_heads": [self.mamba_heads_held, self.mamba_n_heads],
            "query_heads": [self.q_heads_held, self.n_head],
            "head_offset": self.head_offset,
            "kv_heads": [self.kv_heads_held, self.n_kv_head],
            "experts": [self.n_experts_held, self.n_routed_experts],
            "expert_offset": self.expert_offset,
            "vocab_rows": self.padded_vocab}

    @staticmethod
    def tiny(**kw) -> "NemotronHConfig":
        """Published head sizes and state (the scan and the flash kernels
        are shaped by them), everything else small: one period of five
        layers, every kind in it, everything held."""
        base = dict(vocab_size=512, pattern="EMEM*", d_model=64,
                    mamba_n_heads=4, mamba_n_groups=2, mamba_chunk_size=128,
                    n_head=4, n_kv_head=2, d_latent=32, d_expert=48,
                    d_shared=64, n_routed_experts=8, top_k=3)
        base.update(kw)
        return NemotronHConfig(**base)

    @staticmethod
    def nemotron_3_super_120b_a12b(**kw) -> "NemotronHConfig":
        """nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 ``config.json``,
        every published width; ``first_layer`` and ``n_layer`` choose the
        characters of the published pattern that are built."""
        return NemotronHConfig(**kw)


class NemotronH:
    """init / loss pytree model in the house style (qwen3_next.py)."""

    def __init__(self, config: NemotronHConfig):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            config.layer_types, max_period=2)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, 0.0 for zeros or the name of a rule of ``draw_params``;
        ``A_log`` is set by ``init``)."""
        c = self.config
        d, hd = c.d_model, c.head_dim
        std, res = c.init_std, c.init_std / math.sqrt(
            2 * len(c.layer_types))
        h, di, ch = c.mamba_heads_held, c.d_inner, c.d_conv_channels
        g, lat, f = c.n_experts_held, c.d_latent, c.d_expert

        kinds = {
            "mamba": {
                "norm": ((d,), None), "w_z": ((d, di), std),
                "w_xbc": ((d, ch), std), "w_dt": ((d, h), std),
                "conv_w": ((c.mamba_d_conv, ch), "conv"),
                "conv_b": ((ch,), "conv"), "dt_bias": ((h,), "dt_bias"),
                "A_log": ((h,), None), "D": ((h,), None),
                "gate_norm": ((di,), None), "w_out": ((di, d), res)},
            "attention": {
                "norm": ((d,), None),
                "w_q": ((d, c.q_heads_held * hd), std),
                "w_k": ((d, c.kv_heads_held * hd), std),
                "w_v": ((d, c.kv_heads_held * hd), std),
                "w_o": ((c.q_heads_held * hd, d), res)},
            "moe": {
                "norm": ((d,), None),
                "w_router": ((d, c.n_routed_experts), std),
                "router_bias": ((c.n_routed_experts,), 0.0),
                "w_fc1": ((d, lat), std), "w_fc2": ((lat, d), res),
                "s_up": ((d, c.d_shared), std),
                "s_down": ((c.d_shared, d), res),
                "e_up": ((g, lat, f), std), "e_down": ((g, f, lat), res)},
        }
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), None)}
        for i, (period, n) in enumerate(self.runs):
            for kind in period:
                for name, (shape, how) in kinds[kind].items():
                    out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, norm gains and D at 1, the selection bias 0, and
        the three rules the config's file lists as assumed: ``A_log`` =
        log(head number) counted over the whole layer's heads, ``dt_bias``
        the inverse softplus of a dt drawn log-uniformly in [0.001, 0.1],
        the convolution uniform in +-1/sqrt(taps)."""
        c = self.config
        params = draw_params(self._shapes(), rng, c.param_dtype,
                             c.mamba_d_conv)
        return {n: jnp.broadcast_to(self._a_log(), v.shape)
                if n.endswith(".mamba.A_log") else v
                for n, v in params.items()}

    def _a_log(self) -> jax.Array:
        """log(head number), the held heads numbered in the WHOLE layer."""
        c = self.config
        return jnp.log(jnp.arange(1, c.mamba_heads_held + 1,
                                  dtype=c.param_dtype) + c.mamba_head_offset)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- layers ------------------------------------------------------------

    def _mamba(self, x, lp):
        c = self.config
        b, s, _ = x.shape
        h, p, g, n = (c.mamba_heads_held, c.mamba_d_head, c.groups_held,
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
            # the norm over each group's channels
            groups = lambda t: t.reshape(b, s, g, di // g)      # noqa: E731
            y = gated_rmsnorm(groups(y), groups(z),
                              lp["gate_norm"].reshape(g, di // g),
                              c.rms_eps).reshape(b, s, di)
        with jax.named_scope("mixer"):
            return x + y @ lp["w_out"].astype(dt)

    def _attention(self, x, lp):
        c = self.config
        b, s, _ = x.shape
        h, kh, hd, dt = c.q_heads_held, c.kv_heads_held, c.head_dim, c.dtype
        with jax.named_scope("attn"):
            xn = rmsnorm(x, lp["norm"], c.rms_eps)
            q = (xn @ lp["w_q"].astype(dt)).reshape(b, s, h, hd)
            k = (xn @ lp["w_k"].astype(dt)).reshape(b, s, kh, hd)
            v = (xn @ lp["w_v"].astype(dt)).reshape(b, s, kh, hd)
            if kh != h:   # grouped-query: key/value heads to the query heads
                k = jnp.repeat(k, h // kh, axis=2)
                v = jnp.repeat(v, h // kh, axis=2)
            o = flash_attention(q, k, v, causal=True)
            return x + o.reshape(b, s, h * hd) @ lp["w_o"].astype(dt)

    def _moe(self, x, lp):
        """-> (x + the layer's experts, the rows its held experts worked)."""
        c = self.config
        b, s, d = x.shape
        with jax.named_scope("router"):     # the norm goes with the router
            xn = rmsnorm(x, lp["norm"], c.rms_eps).reshape(b * s, d)
        y, rows = held_expert_layer(
            xn, lp, experts_held=c.n_experts_held,
            expert_offset=c.expert_offset,
            top_k=c.top_k, routed_scale=c.routed_scale, score="sigmoid",
            expert="relu2")
        return x + y.reshape(b, s, d), rows

    def _block(self, kind: str, x, lp):
        """One layer -> (x, held rows or None)."""
        if kind == "moe":
            return self._moe(x, lp)
        return (self._mamba if kind == "mamba" else self._attention)(
            x, lp), None

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.config.dtype)[tokens]

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        c = self.config
        _record("rtpu.models.nemotron_h.share", "held", c.share())
        x, _ = walk_stack(
            self._embed(params, tokens), self.runs, params,
            lambda kind, h, p, side, _: (self._block(kind, h, p)[0], {}),
            [tuple(name for kind in period for name in _REMAT_SAVE[kind])
             for period, _ in self.runs], model="nemotron_h")
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
        for i, (period, n) in enumerate(self.runs):
            lp = run_params(params, i)
            for j in range(n):
                for kind in period:
                    x, held = self._block(
                        kind, x, {k: v[j] for k, v in lp[kind].items()})
                    if held is not None:
                        rows.append(held)
        return jnp.stack(rows)
