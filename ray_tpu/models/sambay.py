"""Phi-4-mini-flash-reasoning shaped decoder (``model_type: phi4flash``:
SambaY with differential attention, arXiv:2507.06607), training path: a
decoder-hybrid-decoder. The first half of the stack (the self-decoder)
alternates Mamba-1 state-space layers and differential attention under a
sliding window; its last two layers, a Mamba-1 layer and a FULL attention
layer, also hand on what they made: the scan's output ``m`` and the keys
and values ``k``, ``v``. The second half (the cross-decoder) makes neither
a scan nor keys of its own: its even layers are gated memory units that
multiply by ``m``, its odd layers differential CROSS-attention to ``k`` and
``v``. Every layer is followed by the same gated MLP.

With l the published layer index of ``n_published`` = 32, LN a LayerNorm
with gain and bias, no position term anywhere:

    x = x + Mixer_l(LN_a(x));   x = x + W_down (silu(u W_gate) * u W_up),
                                                       u = LN_b(x)

* ``mamba`` (l even, l < 16) and ``mamba_m`` (l = 16): ``[x' | z] = u
  W_in``; ``x' = silu(conv(x') + b)``, causal depthwise; ``[r | B | C] = x'
  W_x``; ``dt = softplus(r W_dt + b_dt)`` float32; ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) h_{t-1} + (dt_t x'_t) B_t^T``, ``y_t = h_t C_t + D
  x'_t`` (``ops.selective_scan``); out ``(y * silu(z)) W_out``.
  ``mamba_m`` writes ``m = y`` to the side state.
* ``swa`` (l odd, l < 16: window ``sliding_window``) and ``attn_kv`` (l =
  17: full causal): q of ``n_head`` heads, k and v of ``n_kv_head``, with
  bias. The heads pair into ``n_head / 2`` differential heads over
  ``n_kv_head / 2`` groups: query heads 2i and 2i+1 are (q1, q2) of
  differential head i, key heads 2g and 2g+1 (k1, k2) of group g = i //
  (n_head / n_kv_head), value heads 2g and 2g+1 side by side its V of twice
  the width. ``a = softmax(q1 k1^T s + mask) V - lam softmax(q2 k2^T s +
  mask) V``, ``lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init``, ``lam_init =
  0.8 - 0.6 exp(-0.3 l)``; ``a = RMSNorm(a) g (1 - lam_init)``; the heads
  side by side, then ``W_o`` with bias. ``attn_kv`` writes ``k`` and ``v``.
* ``gmu`` (l even, l > 16): ``(silu(u W_in) * m) W_out``.
* ``cross`` (l odd, l > 17): ``q = u W_q + b`` alone; keys and values are
  the side state's; full causal; the same differential form with its own
  ``lam`` vectors, sub-norm gain and ``W_o``.

Each score map is formed ONCE: ``flash_attention`` is handed the pair of
score heads (q1, q2), (k1, k2) of 64 and the ONE value [v1 | v2] of 128 as
the projections made them, and its paired streamed kernels
(``ops/flash_attention.py``) multiply each map with all 128 value lanes
(the published code makes four products of 64 in four calls). Heads the
paired kernels do not tile (the ``tiny`` preset's 16) are expanded inside
``flash_attention`` to four heads a differential head. A key/value group
is still repeated to its differential heads here (ROADMAP B19).

``layers`` are the published indices held here (all 32, or a pipeline
stage's cut; a cut that holds a reader holds the writers), ``vocab_size``
the ids held: embedding (unscaled rows, tied head), logits and loss are
over them. The stack is walked by ``models/stack.py`` as runs of like
periods: whole, (mamba, swa) x 8, mamba_m, attn_kv, (gmu, cross) x 7.
Parameters are one flat dict: ``wte``, ``out_norm_g``, ``out_norm_b`` and
``<run>.<kind>.<name>`` stacked over the run's periods.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (causal_conv1d, cross_entropy_loss, flash_attention,
                   layernorm, selective_scan)
from .stack import period_runs, walk_stack

# What a rematerialised layer keeps for its backward beside its input and
# the side state, by ``checkpoint_name``: the flash kernels' output and row
# statistics and the scan kernel's output and chunk states, so that no
# layer's backward runs a forward kernel again; the gated MLP's two input
# products (``mlp_gate``, ``mlp_up``: 0.34 GB a layer at 8192 tokens of
# ``d_ff`` 10 240) and the mixers' input projections (``mixer_in``: x' and z
# of a Mamba-1 layer, q, k, v, a gated memory unit's gate; 0.04 to 0.17 GB a
# layer), so that the backward does not run those matmuls a second time.
# EVERY run keeps them. Asked of the compiler for a described v5e (the
# benchmark's step, six runs of one, 1 x 8192; PERF.md, PR 43): nothing
# kept is 42.0 T matmul operations a step and 5.230 GB of temporaries; the
# MLP's products in every run 36.9 T and 5.246 GB (2.0 GB kept costs 16 MB:
# the step's peak is at the end of the backward, beside every gradient,
# when what the layers kept is freed); with ``mixer_in`` 35.3 T and 5.454
# GB; the compiler makes nothing again on its own in any of them. On the
# chip the MLP's products are 451.8 against 479.1 ms a step. What is still
# made again is cheap: norms, the convolution, silu and gates, the x- and
# dt-projections, the groups' repeat around the flash kernels.
# (``granite_hybrid``'s first run cannot keep the products: its peak is in
# that run's backward.)
_REMAT_SAVE = ("flash_out", "flash_lse", "selscan_out", "selscan_states",
               "mlp_gate", "mlp_up", "mixer_in")

_PARAM_KIND = {"mamba": "mamba", "mamba_m": "mamba", "swa": "attention",
               "attn_kv": "attention", "gmu": "gmu", "cross": "cross"}
_READS = {"gmu": ("m",), "cross": ("k", "v")}
_WRITES = {"mamba_m": ("m",), "attn_kv": ("k", "v")}


def layer_kind(index: int, n_published: int = 32) -> str:
    """The kind of published layer ``index``."""
    half = n_published // 2
    if index % 2 == 0:
        return "mamba" if index < half else \
            "mamba_m" if index == half else "gmu"
    return "swa" if index < half else \
        "attn_kv" if index == half + 1 else "cross"


@dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064          # the ids held here
    layers: Tuple[int, ...] = tuple(range(32))   # published indices held
    n_published: int = 32             # num_hidden_layers as published
    d_model: int = 2560
    d_ff: int = 10240
    n_head: int = 40
    n_kv_head: int = 20
    head_dim: int = 64
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160          # d_model / 16
    scan_chunk: int = 128             # how the scan is cut, not what it is
    ln_eps: float = 1e-5
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    lambda_std: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        if list(self.layers) != sorted(set(self.layers)) or not self.layers \
                or not 0 <= self.layers[0] <= self.layers[-1] \
                < self.n_published:
            raise ValueError(f"layers {self.layers}: ascending published "
                             f"indices below {self.n_published}")
        kinds = self.layer_kinds
        for reader, entries in _READS.items():
            writer = next(k for k, w in _WRITES.items() if w == entries)
            if reader in kinds and writer not in kinds:
                raise ValueError(
                    f"the cut {self.layers} holds a {reader} layer but not "
                    f"the {writer} layer that makes {entries}")
        if self.n_head % 2 or self.n_kv_head % 2 \
                or (self.n_head // 2) % (self.n_kv_head // 2):
            raise ValueError("differential attention pairs the heads")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(layer_kind(i, self.n_published) for i in self.layers)

    @property
    def n_layer(self) -> int:
        return len(self.layers)

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @staticmethod
    def tiny(**kw) -> "SambaYConfig":
        """Six layers, one of each kind, tiny widths."""
        base = dict(vocab_size=512, layers=(0, 1, 4, 5, 6, 7), n_published=8,
                    d_model=64, d_ff=128, n_head=4, n_kv_head=2,
                    sliding_window=48, mamba_dt_rank=8, scan_chunk=32)
        base.update(kw)
        return SambaYConfig(**base)

    @staticmethod
    def phi4_mini_flash(**kw) -> "SambaYConfig":
        """microsoft/Phi-4-mini-flash-reasoning ``config.json``; ``layers``
        and ``vocab_size`` are what is held here."""
        return SambaYConfig(**kw)


class SambaY:
    """init / loss pytree model in the house style (gpt.py, llama.py,
    deepseek_v3.py, granite_hybrid.py)."""

    def __init__(self, config: SambaYConfig):
        self.config = config
        self.runs = period_runs(config.layer_kinds, max_period=2)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, 0.0 for zeros, or the name of a rule of ``init``)."""
        c = self.config
        d, f, di, n = c.d_model, c.d_ff, c.d_inner, c.mamba_d_state
        r, hd = c.mamba_dt_rank, c.head_dim
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        common = {"norm_g": ((d,), None), "norm_b": ((d,), 0.0),
                  "mlp_norm_g": ((d,), None), "mlp_norm_b": ((d,), 0.0),
                  "w_gate": ((d, f), std), "w_up": ((d, f), std),
                  "w_down": ((f, d), res)}
        diff = {"w_q": ((d, c.n_head * hd), std),
                "b_q": ((c.n_head * hd,), std),
                "w_o": ((c.n_head * hd, d), res), "b_o": ((d,), std),
                "lam_q1": ((hd,), c.lambda_std),
                "lam_k1": ((hd,), c.lambda_std),
                "lam_q2": ((hd,), c.lambda_std),
                "lam_k2": ((hd,), c.lambda_std),
                "subln_g": ((2 * hd,), None)}
        kinds = {
            "mamba": {
                "w_in_x": ((d, di), std), "w_in_z": ((d, di), std),
                "conv_w": ((c.mamba_d_conv, di), "conv"),
                "conv_b": ((di,), "conv"), "w_x": ((di, r + 2 * n), std),
                "w_dt": ((r, di), std), "b_dt": ((di,), "dt_bias"),
                "A_log": ((di, n), "A_log"), "D": ((di,), None),
                "w_out": ((di, d), res)},
            "attention": dict({
                "w_k": ((d, c.n_kv_head * hd), std),
                "b_k": ((c.n_kv_head * hd,), std),
                "w_v": ((d, c.n_kv_head * hd), std),
                "b_v": ((c.n_kv_head * hd,), std)}, **diff),
            "gmu": {"w_in": ((d, di), std), "w_out": ((di, d), res)},
            "cross": diff,
        }
        out = {"wte": ((c.padded_vocab, d), std),
               "out_norm_g": ((d,), None), "out_norm_b": ((d,), 0.0)}
        for i, (period, reps) in enumerate(self.runs):
            for kind in period:
                for name, (shape, how) in dict(
                        kinds[_PARAM_KIND[kind]], **common).items():
                    out[f"{i}.{kind}.{name}"] = ((reps,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights and biases, LayerNorm gains 1 and biases 0, D at 1,
        and the three rules the config's file lists as assumed: ``A_log`` =
        log(1..N) by state, ``b_dt`` the inverse softplus of a dt drawn
        log-uniformly in [0.001, 0.1] (the decays exp(dt A) then lie between
        0.2 and 0.999 a token), the convolution uniform in +-1/sqrt(taps)."""
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

    def _layer_xs(self):
        """The published index of every layer, stacked like its run's
        parameters: ``lam_init`` is a function of it."""
        it = iter(self.config.layers)
        out = []
        for period, reps in self.runs:
            idx = [[next(it) for _ in period] for _ in range(reps)]
            out.append({kind: jnp.asarray([row[j] for row in idx],
                                          jnp.float32)
                        for j, kind in enumerate(period)})
        return out

    # -- layers ------------------------------------------------------------

    def _mamba_mixer(self, u, lp):
        """LN_a(x) -> (the mixer's output, y before the gate)."""
        c = self.config
        n, r, dt = c.mamba_d_state, c.mamba_dt_rank, c.dtype
        with jax.named_scope("mixer"):
            xs = checkpoint_name(u @ lp["w_in_x"].astype(dt), "mixer_in")
            z = checkpoint_name(u @ lp["w_in_z"].astype(dt), "mixer_in")
        with jax.named_scope("conv"):
            xs = jax.nn.silu(causal_conv1d(xs, lp["conv_w"], lp["conv_b"]))
        with jax.named_scope("mixer"):
            proj = xs @ lp["w_x"].astype(dt)
            step = proj[..., :r] @ lp["w_dt"].astype(dt)
            bm, cm = proj[..., r:r + n], proj[..., r + n:]
        with jax.named_scope("scan"):
            step = jax.nn.softplus(step.astype(jnp.float32)
                                   + lp["b_dt"].astype(jnp.float32))
            y = selective_scan(xs, step,
                               -jnp.exp(lp["A_log"].astype(jnp.float32)),
                               bm, cm, lp["D"], chunk=c.scan_chunk)
            gated = (y.astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
        with jax.named_scope("mixer"):
            return gated @ lp["w_out"].astype(dt), y

    def _differential(self, q, k, v, lp, index, window):
        """q [B, S, n_head, hd], k and v [B, S, n_kv_head, hd] -> the
        differential heads side by side [B, S, n_head * hd]."""
        c = self.config
        b, s, h, hd = q.shape
        nd, g = h // 2, k.shape[2] // 2
        share = nd // g
        # a key/value group to its differential heads; the pair (k1, k2)
        # and the value [v1 | v2] of 2 hd stay as projected
        k = jnp.repeat(k.reshape(b, s, g, 2 * hd), share, axis=2)
        v = jnp.repeat(v.reshape(b, s, g, 2 * hd), share, axis=2)
        # a pair of score heads of hd a value of 2 hd: head 2i of o is
        # softmax(q1 k1^T)[v1|v2], head 2i+1 softmax(q2 k2^T)[v1|v2], each
        # map formed once where the paired kernels tile the call
        o = flash_attention(q, k.reshape(b, s, h, hd), v, causal=True,
                            sm_scale=1.0 / math.sqrt(hd), window=window)
        o = o.reshape(b, s, nd, 2, 2 * hd).astype(jnp.float32)
        f32 = lambda name: lp[name].astype(jnp.float32)       # noqa: E731
        lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * index)
        lam = jnp.exp(jnp.sum(f32("lam_q1") * f32("lam_k1"))) \
            - jnp.exp(jnp.sum(f32("lam_q2") * f32("lam_k2"))) + lam_init
        a = o[..., 0, :] - lam * o[..., 1, :]
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + c.ln_eps)
        a = a * f32("subln_g") * (1.0 - lam_init)
        return a.astype(c.dtype).reshape(b, s, h * hd)

    def _attention_mixer(self, u, lp, index, window, kv=None):
        """LN_a(x) -> (the mixer's output, k, v); ``kv`` given: the
        cross-decoder's layer, which projects the query alone."""
        c = self.config
        b, s, _ = u.shape
        hd, dt = c.head_dim, c.dtype
        proj = lambda w, bias: checkpoint_name(               # noqa: E731
            u @ lp[w].astype(dt) + lp[bias].astype(dt),
            "mixer_in").reshape(b, s, -1, hd)
        q = proj("w_q", "b_q")
        k, v = kv if kv is not None else (proj("w_k", "b_k"),
                                          proj("w_v", "b_v"))
        a = self._differential(q, k, v, lp, index, window)
        return a @ lp["w_o"].astype(dt) + lp["b_o"].astype(dt), k, v

    def _block(self, kind, x, lp, side, index):
        c = self.config
        dt = c.dtype
        writes = {}
        scope = {"gmu": "gmu", "cross": "cross_attn", "swa": "attn",
                 "attn_kv": "attn"}.get(kind, "mixer")
        with jax.named_scope(scope):
            u = layernorm(x, lp["norm_g"], lp["norm_b"], c.ln_eps)
        if kind in ("mamba", "mamba_m"):
            out, y = self._mamba_mixer(u, lp)
            if kind == "mamba_m":
                writes["m"] = y
        elif kind in ("swa", "attn_kv"):
            with jax.named_scope("attn"):
                out, k, v = self._attention_mixer(
                    u, lp, index,
                    c.sliding_window if kind == "swa" else None)
            if kind == "attn_kv":
                writes.update(k=k, v=v)
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                gate = jax.nn.silu(checkpoint_name(
                    u @ lp["w_in"].astype(dt), "mixer_in"))
                out = (gate * side["m"]) @ lp["w_out"].astype(dt)
        else:
            with jax.named_scope("cross_attn"):
                out, _, _ = self._attention_mixer(
                    u, lp, index, None, kv=(side["k"], side["v"]))
        with jax.named_scope(scope):
            x = x + out
        with jax.named_scope("mlp"):
            u = layernorm(x, lp["mlp_norm_g"], lp["mlp_norm_b"], c.ln_eps)
            gate = checkpoint_name(u @ lp["w_gate"].astype(dt), "mlp_gate")
            up = checkpoint_name(u @ lp["w_up"].astype(dt), "mlp_up")
            x = x + (jax.nn.silu(gate) * up) @ lp["w_down"].astype(dt)
        return x, writes

    def apply(self, params: Dict[str, jax.Array],
              tokens: jax.Array) -> jax.Array:
        """tokens [B, S] -> logits [B, S, padded_vocab] f32."""
        c = self.config
        with jax.named_scope("embed"):
            x = params["wte"].astype(c.dtype)[tokens]
        x, _ = walk_stack(x, self.runs, params, self._block,
                          [_REMAT_SAVE] * len(self.runs), model="sambay",
                          layer_xs=self._layer_xs())
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = layernorm(x, params["out_norm_g"], params["out_norm_b"],
                          c.ln_eps)
            return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(c.dtype),
                              preferred_element_type=jnp.float32)

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        """The bare next-token loss over the vocabulary held here."""
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            return cross_entropy_loss(logits, targets)
