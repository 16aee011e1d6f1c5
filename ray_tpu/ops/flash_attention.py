"""Flash attention for TPU (Pallas) — forward AND backward kernels.

Tiled online-softmax attention. Layout [B,S,H,D] -> [B*H, S, D]; the grid
streams Q and K/V blocks so nothing larger than a block is VMEM-resident
(the round-1 kernel kept whole K/V per head in VMEM, capping sequence
length). bf16 inputs feed the MXU directly (preferred_element_type=f32
accumulate); all softmax state is f32 on the VPU — the standard TPU recipe
(pallas_guide.md: MXU matmuls with preferred_element_type; min tile
(16,128) for bf16).

Forward saves the logsumexp per row; backward is two Pallas kernels that
recompute probabilities from (q, k, lse) inside the kernel — dq in one
pass over K blocks, dk/dv in one pass over Q blocks — with f32 scratch
accumulators. Causal masking skips fully-masked blocks via a predicate on
the grid position, halving FLOPs for autoregressive models.

Reference capability (not design): the reference has no first-party
attention kernels at all (torch/NCCL stack); this is new TPU-native work
per SURVEY.md §5.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .attention import mha_reference

_NEG_INF = -1e30

# The names of the five kernels, as a device trace and the compiled HLO
# show them (``name=`` on ``pl.pallas_call``; each stays a custom call to
# ``tpu_custom_call``). Part of the measurement: pinned in
# tests/test_tracing_names.py.
KERNEL_NAMES = {
    "fwd_single": "flash_fwd_single",   # forward, all of K/V in one block
    "fwd": "flash_fwd",                 # forward, K/V streamed in blocks
    "bwd_fused": "flash_bwd_fused",     # dq, dk, dv in one pass
    "bwd_dq": "flash_bwd_dq",           # dq, one pass over K blocks
    "bwd_dkv": "flash_bwd_dkv",         # dk and dv, one pass over Q blocks
}


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fit_block(block: int, seq: int) -> int:
    """Largest multiple of 128 that is <= block and divides seq. The
    kernel path requires seq % 128 == 0 (flash_attention routes anything
    else to mha_reference), so a 128-multiple divisor always exists —
    sub-128 blocks would lower to illegal / silently padded Mosaic tiles
    on real TPU."""
    block = min(block, seq)
    if seq % block == 0:
        return block
    for b in range(block - block % 128, 127, -128):
        if seq % b == 0:
            return b
    return 128


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                sm_scale: float, causal: bool,
                block_q: int, block_k: int, num_kb: int):
    """Grid: (B*H, num_q_blocks, num_k_blocks); K innermost so the f32
    scratch (m, l, acc) carries across K iterations for one Q block."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: the block [qi*bq, qi*bq+bq) x [kb*bk, kb*bk+bk) intersects the
    # lower triangle iff its last row can see its first column.
    run = (qi * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[...]  # (block_q, d) input dtype — MXU fast path
        k = k_ref[...]
        v = v_ref[...]
        # scale the (block_q, d) tile, not the (block_q, block_k) s matrix
        s = jax.lax.dot_general(
            q * jnp.asarray(sm_scale, q.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       sm_scale: float, causal: bool, block_q: int,
                       block_k: int):
    """Single-K-block forward (S <= block_k): direct one-shot softmax, no
    online-softmax scratch carry / rescale passes."""
    qi = pl.program_id(1)
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q * jnp.asarray(sm_scale, q.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    acc = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(l)).T


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    """[B*H, S, D] in -> (out [B*H, S, D], lse [B*H, S])."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q = _fit_block(block_q, seq_q)
    block_k = _fit_block(block_k, seq_k)
    num_kb = seq_k // block_k
    from jax.experimental.pallas import tpu as pltpu

    if num_kb == 1:
        kernel = functools.partial(
            _fwd_single_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k)
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, seq_q // block_q),
            in_specs=[
                pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, block_k, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((None, block_k, d), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
                # [bh, 1, S]: q-positions on the LANE axis. A trailing
                # singleton dim ([bh, S, 1]) would tile-pad 128x in HBM
                # (1.5 MB -> 192 MB per layer) and dominate the step in
                # residual-stacking copies; this layout pads 8x only.
                jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name=KERNEL_NAMES["fwd_single"],
            interpret=_use_interpret(),
            cost_estimate=pl.CostEstimate(
                flops=4 * bh * seq_q * seq_k * d // (2 if causal else 1),
                bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
                transcendentals=bh * seq_q * seq_k,
            ),
        )(q, k, v)
        return out, lse

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kb=num_kb)
    grid = (bh, seq_q // block_q, num_kb)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            # [bh, 1, S]: see _fwd_single_kernel's out_shape comment
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # batch*head and q-block grid dims are independent — marking them
        # parallel lets Mosaic pipeline the next block's DMA under compute;
        # only the K dim (scratch carry) is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=KERNEL_NAMES["fwd"],
        interpret=_use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * seq_q * seq_k * d // (2 if causal else 1),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      sm_scale: float, causal: bool,
                      block_q: int, block_k: int, num_qb: int):
    """Single-pass backward for the num_kb == 1 case (S <= block_k): one
    (b, qi) instance computes s/p ONCE and emits dq directly plus dk/dv
    scratch accumulation — versus the two-pass scheme which recomputes
    the s matrix, causal mask, and exp in both the dq and dkv kernels.
    Grid: (B*H, 1, num_q_blocks); qi minor so dk/dv carry in scratch."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[...].T    # stored [1, block_q]; rows here are q-positions
    delta = delta_ref[...].T
    # scale on the (block_q, d) tile — 16x cheaper than scaling the
    # (block_q, block_k) s matrix
    qs = q * jnp.asarray(sm_scale, q.dtype)
    s = jax.lax.dot_general(
        qs, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    p = jnp.exp(s - lse)
    pt = p.astype(do.dtype)
    dv_scr[...] += jax.lax.dot_general(
        pt, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # ds = dL/ds; the sm_scale factor of s = (q·scale)·kᵀ routes into both
    # dq and dk, so fold it once here
    dsc = (p * (dp - delta) * sm_scale).astype(k.dtype)
    dq_ref[...] = jax.lax.dot_general(
        dsc, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_scr[...] += jax.lax.dot_general(
        dsc, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale: float, causal: bool,
                   block_q: int, block_k: int, num_kb: int):
    """Grid: (B*H, num_q_blocks, num_k_blocks); accumulates dq over K."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (qi * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...].T
        delta = delta_ref[...].T
        s = jax.lax.dot_general(
            q * jnp.asarray(sm_scale, q.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk) f32, exactly softmax(s)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale: float, causal: bool,
                    block_q: int, block_k: int, num_qb: int):
    """Grid: (B*H, num_k_blocks, num_q_blocks); accumulates dk/dv over Q."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (qi * block_q + block_q - 1 >= kb * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...].T
        delta = delta_ref[...].T
        s = jax.lax.dot_general(
            q * jnp.asarray(sm_scale, q.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        pt = p.astype(do.dtype)
        dv_scr[...] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, sm_scale, causal, block_q, block_k):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q = _fit_block(block_q, seq_q)
    block_k = _fit_block(block_k, seq_k)
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    # delta_i = rowsum(dO_i * O_i): cheap elementwise reduce — jnp/XLA.
    # [bh, 1, S] like lse (a trailing dim would tile-pad 128x in HBM).
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]

    interp = _use_interpret()
    from jax.experimental.pallas import tpu as pltpu

    if num_kb == 1:
        # single K block: one fused pass computes s/p once and emits
        # dq + dk + dv together (the two-pass scheme below recomputes the
        # s matrix, mask, and exp in each kernel)
        qb_spec = pl.BlockSpec((None, block_q, d), lambda b, j, i: (b, i, 0))
        rowb_spec = pl.BlockSpec((None, 1, block_q),
                                 lambda b, j, i: (b, 0, i))
        kb_spec = pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
                block_q=block_q, block_k=block_k, num_qb=num_qb),
            grid=(bh, 1, num_qb),
            in_specs=[qb_spec, kb_spec, kb_spec, qb_spec, rowb_spec,
                      rowb_spec],
            out_specs=[qb_spec, kb_spec, kb_spec],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
                jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
                jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            name=KERNEL_NAMES["bwd_fused"],
            interpret=interp,
            cost_estimate=pl.CostEstimate(
                flops=10 * bh * seq_q * seq_k * d // (2 if causal else 1),
                bytes_accessed=(q.size * 2 + k.size * 2 + v.size * 2)
                * q.dtype.itemsize,
                transcendentals=bh * seq_q * seq_k,
            ),
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    q_spec = pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i))
    k_spec = pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_kb=num_kb),
        grid=(bh, num_qb, num_kb),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=KERNEL_NAMES["bwd_dq"],
        interpret=interp,
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * seq_q * seq_k * d // (2 if causal else 1),
            bytes_accessed=(q.size * 2 + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(q, k, v, g, lse, delta)

    # dk/dv: Q streams in the minor grid dim.
    qb_spec = pl.BlockSpec((None, block_q, d), lambda b, j, i: (b, i, 0))
    rowb_spec = pl.BlockSpec((None, 1, block_q), lambda b, j, i: (b, 0, i))
    kb_spec = pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_qb=num_qb),
        grid=(bh, num_kb, num_qb),
        in_specs=[qb_spec, kb_spec, kb_spec, qb_spec, rowb_spec, rowb_spec],
        out_specs=[kb_spec, kb_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=KERNEL_NAMES["bwd_dkv"],
        interpret=interp,
        cost_estimate=pl.CostEstimate(
            flops=8 * bh * seq_q * seq_k * d // (2 if causal else 1),
            bytes_accessed=(q.size * 2 + k.size * 2 + v.size * 2)
            * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP — boundary carries MERGED [B, S, H*D] tensors
# ---------------------------------------------------------------------------
# Residuals cross the fwd/bwd boundary in merged form on purpose: a
# [B*H, S, 64] tensor tile-pads its 64-lane minor dim to 128 in HBM (2x
# memory AND 2x traffic every time the remat machinery stacks it into the
# per-layer residual buffers). [B, S, 768] is unpadded; the padded kernel
# layout exists only transiently inside the fwd/bwd computations.


def _to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _merged_to_bhsd(x, h):
    b, s, hd = x.shape
    return _to_bhsd(x.reshape(b, s, h, hd // h))


def _bhsd_to_merged(x, b, h):
    s, d = x.shape[1:]
    return _from_bhsd(x, b, h).reshape(b, s, h * d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qm, km, vm, h, sm_scale, causal, block_q, block_k):
    out, _ = _flash_fwd(_merged_to_bhsd(qm, h), _merged_to_bhsd(km, h),
                        _merged_to_bhsd(vm, h), sm_scale, causal,
                        block_q, block_k)
    return _bhsd_to_merged(out, qm.shape[0], h)


def _flash_vjp_fwd(qm, km, vm, h, sm_scale, causal, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(_merged_to_bhsd(qm, h), _merged_to_bhsd(km, h),
                          _merged_to_bhsd(vm, h), sm_scale, causal,
                          block_q, block_k)
    # Named so a remat policy can choose to SAVE these residuals: pallas
    # outputs are not dots, so a dots-saveable policy would otherwise
    # re-run the forward kernel inside the backward pass.
    out_m = checkpoint_name(_bhsd_to_merged(out, qm.shape[0], h), "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out_m, (qm, km, vm, out_m, lse)


def _flash_vjp_bwd(h, sm_scale, causal, block_q, block_k, res, g):
    qm, km, vm, out_m, lse = res
    b = qm.shape[0]
    dq, dk, dv = _flash_bwd(
        _merged_to_bhsd(qm, h), _merged_to_bhsd(km, h),
        _merged_to_bhsd(vm, h), _merged_to_bhsd(out_m, h), lse,
        _merged_to_bhsd(g, h), sm_scale, causal, block_q, block_k)
    return (_bhsd_to_merged(dq, b, h), _bhsd_to_merged(dk, b, h),
            _bhsd_to_merged(dv, b, h))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024) -> jax.Array:
    """Flash attention. q/k/v: [batch, seq, heads, head_dim] -> same shape.

    head_dim should be a multiple of 128 for MXU efficiency (pads are the
    caller's job — model dims are chosen MXU-friendly instead)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if causal and q.shape[1] != k.shape[1]:
        # The kernels' diagonal masks assume square attention; the reference
        # formulation applies a (seq_k - seq_q) offset this path does not.
        raise ValueError(
            f"causal flash_attention requires seq_q == seq_k, got "
            f"{q.shape[1]} != {k.shape[1]}; use mha_reference for "
            "offset-causal decode")
    if q.shape[1] % 128 != 0 or k.shape[1] % 128 != 0:
        # Mosaic's minimum tile is (8, 128): sub-128 sequence blocks lower
        # to illegal or silently padded tiles on real TPU. Pads are the
        # caller's job; unpadded odd shapes go to the XLA reference.
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    b, s, h, d = q.shape
    merge = lambda x: x.reshape(x.shape[0], x.shape[1], h * d)  # noqa: E731
    out = _flash(merge(q), merge(k), merge(v), h, sm_scale, causal,
                 block_q, block_k)
    return out.reshape(b, s, h, d)
