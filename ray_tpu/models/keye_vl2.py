"""Keye-VL-2.0's language model (``model_type: KeyeVL2``), training path: a
stack of like layers, each grouped-query attention over the keys a learned
INDEXER selects for each query (``sa_config``: DeepSeek-Sparse-Attention's
mechanism on a Qwen3-MoE-shaped block) before a softmax-routed expert layer
WITHOUT a shared expert, of whose routed experts this chip may hold a
share.

``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, float32 statistics, w from
one. Positions are three components a token, ``positions`` [3, B, S]
(temporal, height, width; text: all three ``arange(S)``). Every layer, x̂ =
rms(x; w_in):

    q = x̂ W_q [S, H, hd]   k = x̂ W_k [S, Hkv, hd]   v = x̂ W_v    no bias
    q <- rms_head(q; w_qn)   k <- rms_head(k; w_kn)
    mRoPE: of a head's hd / 2 frequency pairs (channel i with i + hd / 2,
        angle pos * theta^(-2 i / hd)) the first ``mrope_section[0]`` take
        positions[0], the next ``mrope_section[1]`` positions[1], the rest
        positions[2]
    indexer: qI = x̂ W_qI [S, Hi, Di]   kI = layernorm(x̂ W_kI) [S, Di]
        wI = x̂ W_w [S, Hi] * Hi^-1/2 * Di^-1/2, float32
        the first Di / 2 channels of qI and kI rotated by positions[0]
        (channel i with i + Di / 4), the others as they are
        I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s])          float32
    S_t = the ``index_topk`` keys s <= t of largest I[t, s] (ties to the
        lower s); every s <= t where t < index_topk
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // G]
        / sqrt(hd)) v[s, h // G]                (``ops.sparse_attention``)
    x <- x + o W_o
    x̂ = rms(x; w_post); p = softmax(x̂ W_r) over all experts in float32,
        the ``top_k`` largest, weights p over the chosen k
    x <- x + the held experts' part of sum_chosen weight_e SwiGLU_e(x̂)
                                 (``ops.expert_layer.held_expert_layer``)

Logits are ``rms(x; w_f) W_head``, untied, over the ``vocab_size`` rows
held here. No gradient passes the selection or the index scores: under the
next-token loss the indexer's parameters have gradient exactly zero (the
indexer's own training term, a KL from the main attention's distribution,
is not built). Nothing stands in for the absent chips: a partial result
goes on. The vision tower is not built; the model takes the three position
components its patches would bring.

The stack is walked by ``models/stack.py`` as ONE scanned run of like
layers (kind ``attn_moe``), every layer rematerialised; parameters one flat
dict: ``wte``, ``lm_head``, ``out_norm`` and ``0.attn_moe.<name>`` stacked
over the layers. What is held of what is the event
``rtpu.models.keye_vl2.share``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import cross_entropy_loss, layernorm, rmsnorm
from ..ops.expert_layer import balance_term, held_expert_layer
from ..ops.sparse_attention import sparse_attention
from ..perf.recorder import record as _record
from .stack import (draw_params, period_runs, run_params,
                    vocab_row_shardings, walk_stack)

# What a rematerialised layer keeps for its backward beside its input, by
# ``checkpoint_name``: the selection (one byte a pair), the attention
# kernels' output and row statistics; where S <= index_topk the flash
# kernels' own. Its backward then runs neither the indexer, the selection
# nor a forward attention kernel again (PERF.md, PR 59).
_REMAT_SAVE = ("sparse_mask", "sparse_out", "sparse_lse", "flash_out",
               "flash_lse")

_KIND = "attn_moe"


@dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936          # the ids held here
    n_layer: int = 48
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    rope_base: float = 10000000.0
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    max_seq: int = 262144             # positions the model is built for
    # the indexer (``sa_config``)
    index_heads: int = 16
    index_dim: int = 64               # of a head and of the ONE key
    index_topk: int = 2048
    index_q_chunk: int = 512          # queries a block of index scores
    # experts
    d_expert: int = 768               # one routed expert's gated MLP
    n_routed_experts: int = 128       # the router's width
    experts_held: Optional[int] = None         # None: all
    expert_offset: int = 0
    top_k: int = 8
    rms_eps: float = 1e-6
    init_std: float = 0.02            # residual projections: / sqrt(2 L)
    # times each layer's load-balancing term (``ops.expert_layer
    # .balance_term``, a sequence at a time), added to the loss; 0: none
    router_aux_coef: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError("query heads are a multiple of key/value heads")
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(f"mrope_section {self.mrope_section} is not "
                             f"the {self.head_dim // 2} pairs of a head")
        if self.index_dim % 4:
            raise ValueError("half of the index channels turn, in pairs")
        if not 0 < self.n_experts_held <= self.n_routed_experts \
                - self.expert_offset or self.expert_offset < 0:
            raise ValueError("experts held of the routed experts")

    @property
    def padded_vocab(self) -> int:
        return (self.vocab_size + 127) // 128 * 128

    @property
    def index_rope_dim(self) -> int:
        """Channels of an index head and of the index key that turn."""
        return self.index_dim // 2

    @property
    def n_experts_held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    def share(self) -> Dict[str, Any]:
        """What this chip holds, and of how many."""
        return {"experts": [self.n_experts_held, self.n_routed_experts],
                "expert_offset": self.expert_offset,
                "vocab_rows": self.padded_vocab, "layers": self.n_layer}

    @staticmethod
    def tiny(**kw) -> "KeyeVL2Config":
        """The published head size (the kernels are shaped by it) and the
        indexer's, everything else small: two layers, a selection of 64."""
        base = dict(vocab_size=512, n_layer=2, d_model=64, n_head=4,
                    n_kv_head=2, index_heads=4, index_topk=64,
                    index_q_chunk=64, d_expert=32, n_routed_experts=8,
                    top_k=3, max_seq=1024)
        base.update(kw)
        return KeyeVL2Config(**base)

    @staticmethod
    def keye_vl2_30b_a3b(**kw) -> "KeyeVL2Config":
        """Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``'s language model,
        every published width."""
        return KeyeVL2Config(**kw)


def _rotate(x, cos, sin):
    """x [B, S, H, R], channel i paired with i + R / 2; cos and sin
    [B | 1, S, R / 2] float32."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


class KeyeVL2:
    """init / loss pytree model in the house style (qwen3_next.py)."""

    def __init__(self, config: KeyeVL2Config):
        self.config = config
        self.runs: List[Tuple[Tuple[str, ...], int]] = period_runs(
            (_KIND,) * config.n_layer)

    # -- parameters --------------------------------------------------------

    def _shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """name -> (shape, how it is drawn: a std of its normal init, None
        for ones, 0.0 for zeros)."""
        c = self.config
        d, h, kv, hd = c.d_model, c.n_head, c.n_kv_head, c.head_dim
        hi, di, g = c.index_heads, c.index_dim, c.n_experts_held
        std, res = c.init_std, c.init_std / math.sqrt(2 * c.n_layer)
        layer = {
            "norm": ((d,), None),
            "w_q": ((d, h * hd), std),
            "w_k": ((d, kv * hd), std), "w_v": ((d, kv * hd), std),
            "q_norm": ((hd,), None), "k_norm": ((hd,), None),
            "w_o": ((h * hd, d), res),
            # the indexer: its queries, its one key (under a LayerNorm
            # with weight and bias) and its head weights
            "i_wq": ((d, hi * di), std), "i_wk": ((d, di), std),
            "i_ww": ((d, hi), std),
            "i_kn_w": ((di,), None), "i_kn_b": ((di,), 0.0),
            "mlp_norm": ((d,), None),
            "w_router": ((d, c.n_routed_experts), std),
            "e_gate": ((g, d, c.d_expert), std),
            "e_up": ((g, d, c.d_expert), std),
            "e_down": ((g, c.d_expert, d), res)}
        out = {"wte": ((c.padded_vocab, d), std),
               "lm_head": ((c.padded_vocab, d), std),
               "out_norm": ((d,), None)}
        for i, ((kind,), n) in enumerate(self.runs):
            for name, (shape, how) in layer.items():
                out[f"{i}.{kind}.{name}"] = ((n,) + shape, how)
        return out

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Normal weights, norm gains 1, the index key's LayerNorm bias 0."""
        return draw_params(self._shapes(), rng, self.config.param_dtype, 1)

    def param_shardings(self, mesh, rules=None):
        """Replicated but for the vocabulary's rows
        (``stack.vocab_row_shardings``)."""
        return vocab_row_shardings(self._shapes(), mesh, rules)

    def num_params(self) -> int:
        return sum(math.prod(shape) for shape, _ in self._shapes().values())

    # -- positions ---------------------------------------------------------

    def _angles(self, positions, seq: int):
        """-> ((cos, sin) of the main heads' hd / 2 pairs, each pair under
        its component of the positions; (cos, sin) of the indexer's
        index_rope_dim / 2 pairs under the first): [B | 1, S, pairs]
        float32."""
        c = self.config
        if seq > c.max_seq:
            raise ValueError(f"{seq} positions, max_seq is {c.max_seq}")
        if positions is None:
            pos = jnp.arange(seq, dtype=jnp.float32)[None, None]   # [1,1,S]
            pos = jnp.broadcast_to(pos, (3, 1, seq))
        else:
            pos = positions.astype(jnp.float32)
        half = c.head_dim // 2
        with jax.named_scope("attn"):    # a fusion may carry these names
            freqs = c.rope_base ** (
                -jnp.arange(half, dtype=jnp.float32) / half)
            component = np.repeat(np.arange(3), c.mrope_section)
            # pair i under component[i]: [B, S, half]
            main = jnp.moveaxis(pos, 0, -1)[..., component] * freqs
            main = jnp.cos(main), jnp.sin(main)
            with jax.named_scope("indexer"):
                ih = c.index_rope_dim // 2
                index = pos[0][..., None] * c.rope_base ** (
                    -jnp.arange(ih, dtype=jnp.float32) / ih)
                index = jnp.cos(index), jnp.sin(index)
        return main, index

    # -- layers ------------------------------------------------------------

    def _indexer(self, xn, lp, index_rope):
        """The normed input -> (qI [B, S, Hi, Di], kI [B, S, Di], wI
        [B, S, Hi] float32, scaled). Nothing of it is differentiated:
        its input and its parameters are under ``stop_gradient``."""
        c = self.config
        xn = jax.lax.stop_gradient(xn)
        lp = {n: jax.lax.stop_gradient(v) for n, v in lp.items()
              if n.startswith("i_")}
        b, s, _ = xn.shape
        hi, di, r, dt = c.index_heads, c.index_dim, c.index_rope_dim, c.dtype
        part = lambda x: jnp.concatenate(                     # noqa: E731
            [_rotate(x[..., :r], *index_rope), x[..., r:]], axis=-1)
        qi = part((xn @ lp["i_wq"].astype(dt)).reshape(b, s, hi, di))
        ki = layernorm(xn @ lp["i_wk"].astype(dt), lp["i_kn_w"],
                       lp["i_kn_b"], c.rms_eps)
        ki = part(ki[:, :, None, :])[:, :, 0, :]
        wi = jnp.dot(xn, lp["i_ww"].astype(dt),
                     preferred_element_type=jnp.float32) \
            * (hi ** -0.5 * di ** -0.5)
        return qi, ki, wi

    def _attn(self, x, lp, rope, index_rope):
        c = self.config
        b, s, _ = x.shape
        h, kv, hd, dt = c.n_head, c.n_kv_head, c.head_dim, c.dtype
        with jax.named_scope("attn"):
            xn = rmsnorm(x, lp["norm"], c.rms_eps)
            q = (xn @ lp["w_q"].astype(dt)).reshape(b, s, h, hd)
            k = (xn @ lp["w_k"].astype(dt)).reshape(b, s, kv, hd)
            v = (xn @ lp["w_v"].astype(dt)).reshape(b, s, kv, hd)
            q = _rotate(rmsnorm(q, lp["q_norm"], c.rms_eps), *rope)
            k = _rotate(rmsnorm(k, lp["k_norm"], c.rms_eps), *rope)
            with jax.named_scope("indexer"):
                qi, ki, wi = self._indexer(xn, lp, index_rope)
            o = sparse_attention(q, k, v, qi, ki, wi, topk=c.index_topk,
                                 q_chunk=c.index_q_chunk)
            return x + o.reshape(b, s, h * hd) @ lp["w_o"].astype(dt)

    def _moe_ffn(self, x, lp, balance: bool = False):
        """-> (x + the layer's experts, the rows its held experts worked,
        the router's balancing term a sequence [B] or None)."""
        c = self.config
        b, s, d = x.shape
        with jax.named_scope("router"):     # the norm goes with the router
            xn = rmsnorm(x, lp["mlp_norm"], c.rms_eps).reshape(b * s, d)
            aux = balance_term(xn, lp["w_router"], top_k=c.top_k,
                               groups=b) if balance else None
        y, rows = held_expert_layer(
            xn, lp, experts_held=c.n_experts_held,
            expert_offset=c.expert_offset, top_k=c.top_k, routed_scale=1.0,
            score="softmax")
        return x + y.reshape(b, s, d), rows, aux

    def _block(self, x, lp, rope, index_rope, balance: bool = False):
        """One layer -> (x, held rows, balancing term or None)."""
        return self._moe_ffn(self._attn(x, lp, rope, index_rope), lp,
                             balance)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return params["wte"].astype(self.config.dtype)[tokens]

    def forward(self, params: Dict[str, jax.Array], tokens: jax.Array,
                positions: Optional[jax.Array] = None, *,
                balance: bool = False):
        """-> (logits [B, S, padded_vocab] f32, the layers' balancing terms
        summed, a sequence [B] f32, or None). With ``balance`` the walker's
        carry is the pair (x, the sum so far): a scanned run has no output
        a layer."""
        c = self.config
        _record("rtpu.models.keye_vl2.share", "held", c.share())
        rope, index_rope = self._angles(positions, tokens.shape[1])

        def block(kind, h, p, side, _):
            if not balance:
                return self._block(h, p, rope, index_rope)[0], {}
            x, _, aux = self._block(h[0], p, rope, index_rope, True)
            return (x, h[1] + aux), {}

        x = self._embed(params, tokens)
        h, _ = walk_stack(
            (x, jnp.zeros(x.shape[:1], jnp.float32)) if balance else x,
            self.runs, params, block,
            [_REMAT_SAVE for _ in self.runs], model="keye_vl2")
        x, aux = h if balance else (h, None)
        with jax.named_scope("lm_head"):     # the final norm goes with it
            x = rmsnorm(x, params["out_norm"], c.rms_eps)
            return jnp.einsum("bsd,vd->bsv", x,
                              params["lm_head"].astype(c.dtype),
                              preferred_element_type=jnp.float32), aux

    def apply(self, params: Dict[str, jax.Array], tokens: jax.Array,
              positions: Optional[jax.Array] = None) -> jax.Array:
        """tokens [B, S], positions [3, B, S] (None: text, all three
        ``arange(S)``) -> logits [B, S, padded_vocab] f32."""
        return self.forward(params, tokens, positions)[0]

    def loss(self, params: Dict[str, jax.Array], tokens: jax.Array,
             targets: jax.Array,
             positions: Optional[jax.Array] = None) -> jax.Array:
        """The next-token loss over the vocabulary held here and, where
        ``router_aux_coef`` is not 0, that times the layers' balancing
        terms (summed over the layers, the mean over the sequences)."""
        coef = self.config.router_aux_coef
        logits, aux = self.forward(params, tokens, positions,
                                   balance=coef != 0)
        with jax.named_scope("loss"):
            loss = cross_entropy_loss(logits, targets)
            return loss if aux is None else loss + coef * jnp.mean(aux)

    def routing_stats(self, params: Dict[str, jax.Array],
                      tokens: jax.Array) -> jax.Array:
        """(token, choice) pairs that name a held expert, one count a layer
        in order: the rows its grouped product works. A forward of its own,
        layer by layer (the walker's scanned run has no output a layer).
        Jit it; it is no part of a train step."""
        rope, index_rope = self._angles(None, tokens.shape[1])
        x, rows = self._embed(params, tokens), []
        for i, ((kind,), n) in enumerate(self.runs):
            lp = run_params(params, i)[kind]
            for j in range(n):
                x, held, _ = self._block(
                    x, {name: v[j] for name, v in lp.items()}, rope,
                    index_rope)
                rows.append(held)
        return jnp.stack(rows)
