"""ray_tpu.ops — TPU kernels (Pallas) and the models' numerics.

The compute-hot path of the framework. The reference has no first-party
kernels (its CUDA appears only through torch/NCCL deps — SURVEY.md §2
legend); for a TPU-native framework the hot ops are first-party:

- flash_attention: tiled online-softmax attention on the MXU (Pallas);
  ``window=`` keeps a causal call to the key blocks of its band.
- sparse_attention: grouped-query attention over the keys a learned indexer
  selects for each query: index scores a block of queries at a time, an
  exact top k with no sort, masked flash kernels of their own (one
  key/value head and its group of query heads a program) with a backward
  written by hand (Pallas).
  block_sparse_attention: the same kernel pair over the BLOCKS of keys a
  key/value group selects for each query by its own heads' scores on
  mean-pooled keys (no indexer, no parameter).
- lightning_attention: linear attention whose every head has its own q and
  k and a constant decay (groups == heads), a chunked scan with the decay's
  powers as tables: forward and backward kernels, a head's state in VMEM
  (Pallas).
- ring_attention: context-parallel attention over the `sp` mesh axis —
  K/V blocks rotate the ring via ppermute while compute overlaps.
- ssd_scan: Mamba-2's state-space recurrence as a chunked scan, forward
  and backward kernels with the state carried in VMEM (Pallas).
- kda_scan: Kimi Delta Attention's recurrence (a gated delta rule with a
  decay for every key channel; a matrix state a head), chunked in the WY
  form with every exponent <= 0: a Pallas kernel pair where a head is one
  128-lane tile, else plain jnp under one ``lax.scan``. kda_gated_scan:
  the same from what a KDA layer's convolutions and gate projection made
  (the l2 norms of q and k and the gate's softplus inside the kernels).
  gated_delta_scan: the delta rule with ONE decay a head and several
  value heads to a key head (Gated DeltaNet), the plain chunked form with
  the decay factored out; gdn_gated_scan: a Gated DeltaNet layer's call,
  through KDA's kernel pair where a head is one 128-lane tile.
- selective_scan: Mamba-1's recurrence (a decay for every channel AND
  state), walked in chunks on the VPU with the state in VMEM, forward and
  backward kernels (Pallas).
- hyper_connection: several residual streams read, written and mixed
  around a sublayer by maps made doubly stochastic by Sinkhorn
  iterations (mHC); a Pallas kernel pair with a backward of its own, the
  plain jnp form for shapes its tile cannot take; every coefficient
  tokens-minor.
- short_conv: a double-gated causal convolution of a few taps as a token
  mixer of its own, ``c * conv(b * x)``: a Pallas kernel pair that reads
  b, c, x (and dy) once a block with a halo of rows, the backward written
  by hand, and the plain route beside it.
- layers: rmsnorm/layernorm/gelu/rope (plain and YaRN)/cross-entropy,
  the causal depthwise convolution, the gated norms (the gate before the
  norm, or after it under sigmoid or SiLU) and a head's l2 norm
  in plain jnp, shaped so XLA fuses them into the adjacent matmuls.
- paged_attention: reads and writes of the serving engine's block-pool
  KV cache.

Everything here runs in Pallas interpret mode on CPU (tests) and compiled
on TPU.
"""
from .attention import mha_reference
from .flash_attention import flash_attention
from .ring_attention import ring_attention
from .sparse_attention import block_sparse_attention, sparse_attention
from .lightning_attention import lightning_attention
from .layers import (chunked_head_nll, cross_entropy_loss, gelu, layernorm, rmsnorm,
                     rope_cache, apply_rope, causal_conv1d,
                     causal_conv1d_silu, gated_rmsnorm, l2norm,
                     rmsnorm_then_gate, sigmoid_gated_rmsnorm)
from .ssd_scan import ssd_scan
from .kda_scan import (gated_delta_scan, gdn_gated_scan, kda_gated_scan,
                       kda_scan)
from .selective_scan import selective_scan
from .short_conv import in_proj_short_conv
from .hyper_connection import hc_coefficients, hc_mix, hc_post, hc_pre
from .paged_attention import (paged_attention_decode,
                              paged_attention_prefill, paged_gather_kv,
                              paged_write_prefill, paged_write_step)

__all__ = [
    "flash_attention", "ring_attention", "mha_reference", "sparse_attention",
    "block_sparse_attention", "lightning_attention",
    "rmsnorm", "layernorm", "gelu", "rope_cache", "apply_rope",
    "cross_entropy_loss", "chunked_head_nll", "causal_conv1d", "causal_conv1d_silu",
    "gated_rmsnorm", "l2norm",
    "rmsnorm_then_gate", "sigmoid_gated_rmsnorm", "ssd_scan", "kda_scan",
    "kda_gated_scan", "gated_delta_scan", "gdn_gated_scan", "selective_scan",
    "in_proj_short_conv",
    "hc_coefficients", "hc_pre", "hc_post", "hc_mix",
    "paged_attention_decode", "paged_attention_prefill",
    "paged_gather_kv", "paged_write_prefill",
    "paged_write_step",
]
