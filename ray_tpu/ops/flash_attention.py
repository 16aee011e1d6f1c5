"""Flash attention for TPU (Pallas) — forward AND backward kernels.

Tiled online-softmax attention. The kernels work on MERGED arrays
[B, S, H*D], the layout the model's projections produce and consume: the
grid runs over (batch, column block, sequence blocks) and each program
gets [block, W] tiles of q, k, v (o, dO) by index map, W lanes wide.

The routes, chosen by what a call shows (``PATH_COUNTS``, the event
``rtpu.ops.flash.path``; no argument or configuration selects one):

* ``merged``: one head size D for q, k and v, D of 64 or 128 and H*D a
  multiple of 128 (``_heads_per_block``). W is 128 and a column block
  holds 128 // D whole heads, so every load and store is a dense 128-lane
  tile and nothing is transposed outside the kernels. Inside a program
  the heads of a block are worked one after the other on full-width tiles
  whose other lanes are zeroed (``_head_lanes``): a product with an exact
  zero adds an exact zero in the float32 accumulator, so each head's
  numbers are those of a kernel that saw its D lanes alone.
* ``paired``: a call that shows a PAIR of score heads a value: q and k
  [B, S, H, 64], v [B, S, H/2, 128]; score heads 2i and 2i+1 share value
  i, and the call returns [B, S, H, 128], head 2i softmax(q_2i k_2i^T) V_i
  and head 2i+1 softmax(q_2i+1 k_2i+1^T) V_i (differential attention
  before its subtraction). In the merged layout the pair (q_2i | q_2i+1)
  is ONE 128-lane column block, the pair of keys another, and V_i a third:
  one program of the STREAMED kernels holds all three, forms each of the
  two maps once (the other head's lanes of q zeroed, as on the ``merged``
  route) and multiplies it with all 128 value lanes; o and dO are two
  128-lane blocks a program, dv the one value's with both maps summed
  into it. The streamed kernels whatever S (one block is a grid of one
  step); a branch of their bodies taken at trace time by the widths of
  the tiles (``_value_lanes``). A paired call whose shapes do not tile (S
  no multiple of 128, score heads not of 64) is expanded here to four
  heads a pair, (q1 k1 v1) (q1 k1 v2) (q2 k2 v1) (q2 k2 v2), and takes the
  routes of a call with one head size (``_paired_attention``).
* ``relayout``: every other single head size (32, 96, 192, 256, ..., or a
  merged width that is no multiple of 128) is first transposed to
  [B*H, S, D] (``_to_bhsd``) and runs the same kernels as B*H batches of
  one head, W = D: a 64-wide minor dimension would pad to 128 lanes in
  HBM, a 32-wide one to four times its size, which is why the merged form
  is what crosses the custom-VJP boundary either way.
* ``latent``: a call that brings ``q_rope`` [B, S, H, dr] and ONE
  ``k_rope`` [B, S, dr] for all heads: the score is
  (q·k + q_rope·k_rope) * scale and v may have another head size than q
  and k (multi-head latent attention: 128 + 64 against 128). dr of 64 or
  128, the q/k and v head sizes multiples of 128 (``_latent_ok``):
  kernels of their own (``LATENT_KERNEL_NAMES``), 128 // dr heads to a
  program, the shared key never copied to the heads: a forward and ONE
  backward kernel that makes the score and dP once a block pair, for
  CAUSAL calls in square blocks (``_latent_attention``). See the section
  "latent attention" below.
* ``reference`` / ``latent_reference``: S no multiple of 128, latent
  head sizes that do not tile the lanes, or a latent call that is not
  causal: ``mha_reference``, no kernel.

The grid streams Q and K/V blocks so nothing larger than a block is
VMEM-resident (but the float32 dq of one head block, which the latent
route's backward keeps for the whole sequence). bf16 inputs feed the MXU
directly (preferred_element_type=f32 accumulate); all softmax state is
f32 on the VPU — the standard TPU recipe (pallas_guide.md: MXU matmuls
with preferred_element_type; min tile (16,128) for bf16).

Forward saves the logsumexp per row. The backward of the ONE-PART score
(``merged``, ``paired``, ``relayout``) is two Pallas kernels that
recompute probabilities from (q, k, lse) inside the kernel — dq in one
pass over K blocks, dk/dv in one pass over Q blocks — with f32 scratch
accumulators, or one fused kernel where K/V are a single block; the
latent route's is one kernel at every S (below). delta = rowsum(dO * O)
is reduced inside them too (``_row_delta``).

Causal calls do not compute what the mask would throw away, in one of two
ways. The STREAMED kernels (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``: S larger than a block) skip fully-masked blocks by a
predicate on the grid position, and so do the LATENT kernels, which also
band the blocks the diagonal crosses (below). The SINGLE-BLOCK kernels
(``flash_fwd_single``, ``flash_bwd_fused``), where one program holds the
whole S x S square of a column block, cut its rows into bands of a
quarter of the sequence (``_band_height``) and work band r on the columns
[0, (r+1)*h) it can see: a Python loop over static slices of tiles that
are in VMEM already, no predicate and no second call, 62.5 % of the
square's matmuls and softmax at four bands. A masked score gave
exp(-1e30 - m) = 0 to its row's sum and an exact zero to every product,
so each band's numbers are the square's, summed over the non-zero terms.
Non-causal calls and a single-block call with several q blocks work the
whole block as one band. The LATENT kernels do the same inside every
block the diagonal crosses, whatever S, in bands of a quarter of the
BLOCK (``_latent_band``; the section "latent attention" below; ``bands``
in the event: the bands of such a step).

A causal call with a ``window`` (query i sees the keys i - window < j <= i)
takes the streamed kernels whatever S, and their innermost grid dimension
runs over the blocks of the band alone (``_band_blocks``): forward and dq
over the key blocks a query block's window reaches, dk/dv over the query
blocks whose windows reach a key block; the blocks the band's two edges
cross are masked, a block wholly outside is never fetched. At S = 8192 in
blocks of 1024 and a window of 512 that is 2 key blocks a query block (15
of the 36 a causal call visits).

Reference capability (not design): the reference has no first-party
attention kernels at all (torch/NCCL stack); this is new TPU-native work
per SURVEY.md §5.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..perf.recorder import record as _record
from . import kernel_common
from .attention import mha_reference
from .kernel_common import AB, ABT, ATB, LANES, NEG_INF, dot, fit_block

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

# The two kernels of a call whose score has a second part against one
# shared key (latent attention), at any S; pinned in the same test. The
# backward makes all five gradients and is launched as
# ``flash_latent_bwd_dkv``: the benchmark's reader
# (``benchmark/layer_metrics/mla_attention_roofline.py``) knows that name,
# and under another the time would leave the metric while the operations
# stayed in its count.
LATENT_KERNEL_NAMES = {
    "fwd": "flash_latent_fwd",
    "bwd_dkv": "flash_latent_bwd_dkv",      # dq_nope, dq_rope, dk_nope,
                                            # d(shared key), dv
}

# Traced calls of flash_attention by the layout each took: "merged" (the
# kernels index [B, S, H*D] as it stands), "paired" (merged, two score
# heads of 64 and their one value of 128 to a program), "relayout"
# (transposed to [B*H, S, D] around the kernels), "reference"
# (mha_reference, no kernel). Counted where the choice is made, once per trace; the same
# choice is the flight-recorder event ``rtpu.ops.flash.path``.
PATH_COUNTS: collections.Counter = collections.Counter()

# The same traced calls by the number of causal row bands a program works
# (``_band_height``): 4 at S=1024, 1 where nothing is banded (non-causal,
# streamed, S=128), 0 on the reference route; of a LATENT call the bands
# of a step the diagonal crosses (``_latent_band``: 4 at blocks of 1024,
# 2 at 256, 1 at 128). ``bands`` in the event's data.
BAND_COUNTS: collections.Counter = collections.Counter()


def _band_height(seq: int, causal: bool, block_q: int, block_k: int) -> int:
    """Height of the causal row bands of a call that one program holds
    whole (seq <= both blocks), or 0 where nothing is banded: a non-causal
    call, or one streamed in blocks, whose kernels skip masked blocks by
    predicate. Band r owns rows [r*h, (r+1)*h) and computes the columns
    [0, (r+1)*h) its rows can see, all slices static: of the square's
    area (nb + 1) / (2 nb) is issued. The height is a quarter of the
    sequence in whole 128-lane tiles. At S=1024 on a v5e (PERF.md, PR 31)
    four bands of 256 (62.5 % of the area) took 64.5 % of the square's
    time, two of 512 75.3 %, and eight of 128 (56 %) 61.9 %: fastest, but
    a band is unrolled Python, traced and lowered at every start of a
    process, and eight bands cost the step's build 0.6 s against 0.15 s
    for four."""
    if not causal or fit_block(block_q, seq) != seq \
            or fit_block(block_k, seq) != seq:
        return 0
    return fit_block(max(LANES, seq // 4 // LANES * LANES), seq)


def _row_bands(rows: int, cols: int, band: int):
    """(first row, rows, columns computed) of each band of one program's
    [rows, cols] scores: the whole block where nothing is banded."""
    if not band:
        return [(0, rows, cols)]
    return [(r, band, r + band) for r in range(0, rows, band)]


def _one_ahead(units, first, banded):
    """(unit, first(unit)) of each (band, head) unit of a program, in
    order. In a banded program the first stage of unit i+1 (its scores'
    matmuls) is written before unit i's is handed out: Mosaic schedules
    close to the order of the program, and with a unit's matmuls behind
    the softmax of the one before, the MXU waited for the VPU between
    bands (forward at S=1024: 80 % of the square's time so, 67 % one
    ahead; PERF.md, PR 31). The square's two heads are left in the
    parent's order: it gains nothing there, and the backward's second
    [1024, 1024] set of tiles does not fit VMEM."""
    if not banded:
        for u in units:
            yield u, first(u)
        return
    nxt = first(units[0])
    for i, u in enumerate(units):
        cur, nxt = nxt, (first(units[i + 1]) if i + 1 < len(units) else None)
        yield u, cur


def _heads_per_block(heads: int, d: int) -> int:
    """Whole heads in one 128-lane column block of [B, S, H*D], or 0 where
    the merged layout cannot be cut that way (heads narrower than 64 would
    be worked four or more to a block at a quarter of the MXU's width;
    wider than 128 or not dividing it do not tile the lanes)."""
    if d >= 64 and LANES % d == 0 and (heads * d) % LANES == 0:
        return LANES // d
    return 0


def _head_lanes(x, j: int, d: int):
    """The tile with every lane outside head j's D zeroed; the tile itself
    where it holds one head."""
    if x.shape[-1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane >= j * d) & (lane < (j + 1) * d), x,
                     jnp.zeros_like(x))


def _value_lanes(x, j: int, d: int, paired: bool):
    """What head j of a program reads of a tile on the value's side (v, o,
    dO). One head size: head j's D lanes, the others zeroed. A PAIRED
    program (two score maps of D against ONE value of 128): the whole tile
    where it is that value (v), map j's 128 lanes of a tile that holds one
    block a map (o, dO): a static slice on a lane-tile boundary."""
    if not paired:
        return _head_lanes(x, j, d)
    if x.shape[-1] == LANES:
        return x
    return x[:, j * LANES:(j + 1) * LANES]


def _scores(q, k, sm_scale, causal, row0, col0, window=None):
    """(q * scale) @ k^T in f32, causal-masked (and, with a ``window``,
    masked where the key lies ``window`` or more before the query);
    row0/col0 are the block's first q and k positions."""
    # scale the (block_q, d) tile, not the (block_q, block_k) s matrix
    s = dot(q * jnp.asarray(sm_scale, q.dtype), k, ABT)
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = rows >= cols
        if window is not None:
            seen = seen & (rows - cols < window)
        s = jnp.where(seen, s, NEG_INF)
    return s


# A window (causal, query i sees keys i - window < j <= i) in the streamed
# kernels: the grid's innermost dimension runs over the blocks of the BAND
# alone, ``_band_blocks`` of them, block ``first + step`` where ``first`` is
# the first block the outer block's band reaches. A step past the band's
# last block is skipped by the causal predicate the kernels have, and its
# index maps hold still at that last block, so nothing is fetched for it.


def _band_first(outer, block_outer: int, block_inner: int, back: int):
    """The first inner block that the band of outer block ``outer``
    reaches: its first position less ``back``, not before 0."""
    return jnp.maximum(outer * block_outer - back, 0) // block_inner


def _band_last(outer, block_outer: int, block_inner: int, ahead: int,
               n_inner: int):
    """The last inner block that band reaches."""
    return jnp.minimum((outer * block_outer + block_outer - 1 + ahead)
                       // block_inner, n_inner - 1)


def _band_counts(n_outer: int, block_outer: int, block_inner: int, back: int,
                 ahead: int, n_inner: int) -> list:
    """The inner blocks each outer block's band touches. Query blocks over
    key blocks look ``window - 1`` back and 0 ahead; key blocks over query
    blocks 0 back and ``window - 1`` ahead."""
    return [min((o * block_outer + block_outer - 1 + ahead) // block_inner,
                n_inner - 1)
            - max(o * block_outer - back, 0) // block_inner + 1
            for o in range(n_outer)]


def _band_blocks(*band) -> int:
    """The most inner blocks any outer block's band touches: the steps of
    the grid's innermost dimension."""
    return max(_band_counts(*band))


def _window_vmem(window) -> dict:
    """The window's second comparison is one more [block_q, block_k] value
    in a program: at blocks of 1024 the dk/dv kernel then asks 16.6 MB of
    the compiler's default 16 MB of scoped VMEM. A windowed call asks for
    32 (of a v5e's 128); a call without a window asks for nothing, as
    before."""
    return {} if window is None else {"vmem_limit_bytes": 32 * 1024 * 1024}


def _band_of_keys(num_qb, num_kb, block_q, block_k, window):
    """-> (index map (query block, step) -> key block, steps a query
    block) of a windowed call's kernels that walk the keys innermost."""
    def key_block(i, j):
        return jnp.minimum(_band_first(i, block_q, block_k, window - 1) + j,
                           _band_last(i, block_q, block_k, 0, num_kb))

    return key_block, _band_blocks(num_qb, block_q, block_k, window - 1, 0,
                                   num_kb)


def _band_of_queries(num_qb, num_kb, block_q, block_k, window):
    """-> (index map (key block, step) -> query block, steps a key block)
    of the dk/dv kernel, which walks the queries innermost."""
    def query_block(j, i):
        return jnp.minimum(_band_first(j, block_k, block_q, 0) + i,
                           _band_last(j, block_k, block_q, window - 1,
                                      num_qb))

    return query_block, _band_blocks(num_kb, block_k, block_q, 0,
                                     window - 1, num_qb)


def _units(rows: int, cols: int, band: int, heads: int):
    """The (band, head) units of a single-block program, in the order
    they are worked: (first row, rows, columns computed, head)."""
    return [(r0, h, end, j) for r0, h, end in _row_bands(rows, cols, band)
            for j in range(heads)]


def _rows(x, r0: int, h: int):
    """Rows [r0, r0 + h) of a tile held as a value (a static, tile-aligned
    slice: no data moves); the tile itself where that is all of it."""
    if h == x.shape[0]:
        return x
    return jax.lax.slice(x, (r0, 0), (r0 + h, x.shape[1]))


def _visible(row0, rows: int, cols: int):
    """([rows, cols] of row0 + i >= c, as many of -1e30): the causal mask
    of a tile whose first row is row0 columns below its first column, and
    what ``_masked`` puts where it is False."""
    return (row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1),
            jnp.full((rows, cols), NEG_INF, jnp.float32))


def _masked(s, visible):
    """s with its LAST visible.shape[1] columns causal-masked; the columns
    before them are visible to every row (a row band's columns before its
    diagonal tile). The mask is made once a program and shared by its
    units: every band's diagonal tile has the same one."""
    if visible is None:
        return s
    visible, neg = visible
    free = s.shape[1] - visible.shape[1]
    if not free:
        return jax.lax.select(visible, s, neg)
    return jax.lax.concatenate(
        [jax.lax.slice(s, (0, 0), (s.shape[0], free)),
         jax.lax.select(visible, jax.lax.slice(s, (0, free), s.shape), neg)],
        1)


def _col(x):
    """Row-wise reduction result [rows] -> a column [rows, 1]."""
    return jax.lax.expand_dims(x, (1,))


def _along(col, like):
    """A column [rows, 1] broadcast along the lanes of ``like``."""
    return jax.lax.broadcast_in_dim(col, like.shape, (0, 1))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                sm_scale: float, causal: bool, d: int,
                block_q: int, block_k: int, num_kb: int, window=None):
    """Grid: (B, column blocks, num_q_blocks, num_k_blocks); K innermost
    so the f32 scratch (m, l, acc: one of each per head of the block)
    carries across K iterations for one Q block. With a ``window`` the K
    dimension is the ``num_kb`` steps of the band (``_band_blocks``). A
    PAIRED program (o's tile twice as wide as q's) holds two score heads
    of ``d`` and one value of 128: each map is multiplied with the whole v
    tile and leaves through its own 128 lanes of o."""
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    heads = lse_ref.shape[0]
    paired = o_ref.shape[-1] != q_ref.shape[-1]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the key block of this step
    col = kb if window is None else \
        kb + _band_first(qi, block_q, block_k, window - 1)
    # Causal: the block [qi*bq, qi*bq+bq) x [col*bk, col*bk+bk) intersects
    # the lower triangle iff its last row can see its first column.
    run = (qi * block_q + block_q - 1 >= col * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[...]  # (block_q, W) input dtype — MXU fast path
        k = k_ref[...]
        v = v_ref[...]
        for j in range(heads):
            s = _scores(_head_lanes(q, j, d), k, sm_scale, causal,
                        qi * block_q, col * block_k, window)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[j] = l_scr[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[j] = acc_scr[j] * alpha + dot(
                p.astype(v.dtype), _value_lanes(v, j, d, paired), AB)
            m_scr[j] = m_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        out = None
        for j in range(heads):
            l = jnp.maximum(l_scr[j], 1e-30)
            o = acc_scr[j] / l   # zero outside head j's lanes
            if paired:           # all 128 lanes are map j's
                o_ref[:, j * LANES:(j + 1) * LANES] = o.astype(o_ref.dtype)
            else:
                out = o if out is None else out + o
            lse_ref[j] = (m_scr[j] + jnp.log(l)).T
        if not paired:
            o_ref[...] = out.astype(o_ref.dtype)


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       sm_scale: float, causal: bool, d: int, block_q: int,
                       band: int):
    """Single-K-block forward (S <= block_k): direct one-shot softmax, no
    online-softmax scratch carry / rescale passes. Where the call is
    banded (``_band_height``) each row band computes only the columns it
    can see: a band's rows have all their columns before them, so the
    softmax stays one-shot. What does not depend on the band (the heads'
    zeroed and scaled tiles, the diagonal tile's mask) is made once a
    program: the body below is traced and lowered once per unit, at every
    start of a process. Grid: (B, column blocks, num_q_blocks)."""
    heads = lse_ref.shape[0]
    block_k = k_ref.shape[0]
    # scale the (block_q, d) tile, not the (block_q, block_k) s matrix
    q = q_ref[...] * jnp.asarray(sm_scale, q_ref.dtype)
    v = v_ref[...]
    qs = [_head_lanes(q, j, d) for j in range(heads)]
    vs = [_head_lanes(v, j, d) for j in range(heads)]
    visible = None
    if causal:
        visible = _visible(0, band, band) if band else _visible(
            pl.program_id(2) * block_q, block_q, block_k)

    def scores(u):
        r0, h, end, j = u
        return _masked(dot(_rows(qs[j], r0, h), k_ref[:end, :], ABT),
                       visible)

    out = None
    for (r0, h, end, j), s in _one_ahead(
            _units(block_q, block_k, band, heads), scores, band):
        # jax.lax, not jnp, in a unit's body: a jnp call costs several
        # times as much to trace, and every unit is traced at every start
        m = _col(jax.lax.reduce_max(s, (1,)))
        p = jax.lax.exp(jax.lax.sub(s, _along(m, s)))
        l = jax.lax.max(_col(jax.lax.reduce_sum(p, (1,))), 1e-30)
        # zero outside head j's lanes, so the heads' tiles add up exactly
        o = dot(jax.lax.convert_element_type(p, v.dtype),
                _rows(vs[j], 0, end), AB)
        o = jax.lax.div(o, _along(l, o))
        out = o if j == 0 else jax.lax.add(out, o)
        lse_ref[j, :, r0:r0 + h] = jax.lax.transpose(
            jax.lax.add(m, jax.lax.log(l)), (1, 0))
        if j == heads - 1:
            o_ref[r0:r0 + h, :] = out.astype(o_ref.dtype)


def _cut(q, k, heads, hpb, causal, block_q, block_k, paired=False):
    """How [B, S, heads*D] is cut into programs -> (d, width of a column
    block, column blocks, block_q, block_k, height of a causal row band
    inside a program or 0, lanes of o and dO a program holds, the head
    size the cost estimates count: in a ``paired`` call two 128-lane
    blocks and the mean of the score's 64 and the value's 128)."""
    d = q.shape[-1] // heads
    w = hpb * d
    return (d, w, heads // hpb, fit_block(block_q, q.shape[1]),
            fit_block(block_k, k.shape[1]),
            _band_height(q.shape[1], causal, block_q, block_k),
            *((2 * w, (d + w) // 2) if paired else (w, d)))


def _flash_fwd(q, k, v, heads, hpb, sm_scale, causal, block_q, block_k,
               window=None, paired=False):
    """[B, S, heads*D] in, ``hpb`` heads to a column block ->
    (out [B, S, heads*D], lse [B*heads, 1, S]). A call with a ``window``
    takes the streamed kernel whatever S, and so does a ``paired`` one:
    v [B, S, heads/2 * 128], the ONE value of each pair of score heads of
    64 that a column block of q and k holds -> out [B, S, heads*128], a
    128-lane block a score head."""
    b, seq_q, _ = q.shape
    seq_k = k.shape[1]
    d, w, ncb, block_q, block_k, band, wo, dd = _cut(
        q, k, heads, hpb, causal, block_q, block_k, paired)
    num_kb = seq_k // block_k
    from jax.experimental.pallas import tpu as pltpu

    out_shape = [
        jax.ShapeDtypeStruct((b, seq_q, ncb * wo), q.dtype),
        # [B*heads, 1, S]: q-positions on the LANE axis. A trailing
        # singleton dim ([bh, S, 1]) would tile-pad 128x in HBM
        # (1.5 MB -> 192 MB per layer) and dominate the step in
        # residual-stacking copies; this layout pads 8x only.
        jax.ShapeDtypeStruct((b * heads, 1, seq_q), jnp.float32),
    ]
    cost = pl.CostEstimate(
        flops=4 * b * heads * seq_q * seq_k * dd // (2 if causal else 1),
        bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
        transcendentals=b * heads * seq_q * seq_k,
    )

    if num_kb == 1 and window is None and not paired:
        q_spec = pl.BlockSpec((None, block_q, w), lambda b, c, i: (b, i, c))
        kv_spec = pl.BlockSpec((None, block_k, w), lambda b, c, i: (b, 0, c))
        return pl.pallas_call(
            functools.partial(
                _fwd_single_kernel, sm_scale=sm_scale, causal=causal, d=d,
                block_q=block_q, band=band),
            grid=(b, ncb, seq_q // block_q),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((hpb, 1, block_q),
                             lambda b, c, i: (b * ncb + c, 0, i)),
            ],
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name=KERNEL_NAMES["fwd_single"],
            interpret=kernel_common.use_interpret(),
            cost_estimate=cost,
        )(q, k, v)

    # the key block of step j of query block i: every block in turn, or,
    # with a window, the blocks of the band alone
    key_block, steps = (lambda i, j: j), num_kb
    if window is not None:
        key_block, steps = _band_of_keys(seq_q // block_q, num_kb, block_q,
                                         block_k, window)
        cost = pl.CostEstimate(
            flops=4 * b * heads * seq_q * min(window, seq_k) * dd,
            bytes_accessed=cost.bytes_accessed,
            transcendentals=b * heads * seq_q * min(window, seq_k))
    q_spec = pl.BlockSpec((None, block_q, w), lambda b, c, i, j: (b, i, c))
    kv_spec = pl.BlockSpec((None, block_k, w),
                           lambda b, c, i, j: (b, key_block(i, j), c))
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, d=d,
            block_q=block_q, block_k=block_k, num_kb=steps, window=window),
        grid=(b, ncb, seq_q // block_q, steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((None, block_q, wo), lambda b, c, i, j: (b, i, c)),
            pl.BlockSpec((hpb, 1, block_q),
                         lambda b, c, i, j: (b * ncb + c, 0, i)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((hpb, block_q, 1), jnp.float32),
            pltpu.VMEM((hpb, block_q, 1), jnp.float32),
            pltpu.VMEM((hpb, block_q, w), jnp.float32),
        ],
        # batch, column-block and q-block grid dims are independent —
        # marking them parallel lets Mosaic pipeline the next block's DMA
        # under compute; only the K dim (scratch carry) is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"), **_window_vmem(window)),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=cost,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_head(q, k, v, do, lse, delta, j, *, d, sm_scale, causal, row0,
              col0, window=None):
    """What head j of the block gives one (q block, k block) pair:
    (p, ds, q_j, do_j), p the recomputed probabilities and ds = dL/ds with
    the sm_scale of s = (q·scale)·kᵀ folded in once (it routes into both
    dq and dk), both in the inputs' dtype for the MXU. In a paired program
    (dO's tile twice as wide as q's) do_j is map j's 128 lanes of dO, and
    dP runs over all 128 lanes of the one value."""
    qj = _head_lanes(q, j, d)
    doj = _value_lanes(do, j, d, do.shape[-1] != q.shape[-1])
    p = jnp.exp(_scores(qj, k, sm_scale, causal, row0, col0, window) - lse)
    dp = dot(doj, v, ABT)
    ds = p * (dp - delta) * sm_scale
    return p.astype(do.dtype), ds.astype(k.dtype), qj, doj


def _row_delta(do, o, j, d, paired=False):
    """delta_i = rowsum(dO_i * O_i) over head j's lanes (a paired
    program: over map j's 128) -> (block_q, 1) f32. Computed where dO and
    O are already in VMEM: left to XLA, the reduction over 64 of 1024
    lanes made it keep dO with S on the lanes and copy it back for the
    kernel."""
    if paired:
        o = _value_lanes(o, j, d, True)
    return jnp.sum(_value_lanes(do, j, d, paired).astype(jnp.float32)
                   * o.astype(jnp.float32), axis=-1, keepdims=True)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      sm_scale: float, causal: bool, d: int,
                      block_q: int, num_qb: int, band: int):
    """Single-pass backward for the num_kb == 1 case (S <= block_k): one
    (b, column block, qi) instance computes s/p ONCE per head and emits dq
    directly plus dk/dv scratch accumulation — versus the two-pass scheme
    which recomputes the s matrix, causal mask, and exp in both the dq and
    dkv kernels. A banded call (``_band_height``) works its row bands one
    after the other, each on the columns it can see, and adds into the
    same rows of the dk/dv scratch; what does not depend on the band is
    made once a program, as in the forward. The numbers of a unit are
    ``_bwd_head``'s. Grid: (B, column blocks, num_q_blocks); qi minor so
    dk/dv carry in scratch."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    heads = lse_ref.shape[0]
    block_k = k_ref.shape[0]
    q, k, do, o = q_ref[...], k_ref[...], do_ref[...], o_ref[...]
    qm = [_head_lanes(q, j, d) for j in range(heads)]
    qs = [x * jnp.asarray(sm_scale, q.dtype) for x in qm]
    ks = [_head_lanes(k, j, d) for j in range(heads)]
    dos = [_head_lanes(do, j, d) for j in range(heads)]
    # lse is stored [1, block_q]; rows here are q-positions
    lse = [lse_ref[j].T for j in range(heads)]
    delta = [_row_delta(do, o, j, d) for j in range(heads)]
    visible = None
    if causal:
        visible = _visible(0, band, band) if band else _visible(
            qi * block_q, block_q, block_k)

    def head(u):
        r0, h, end, j = u
        doj = _rows(dos[j], r0, h)
        s = _masked(dot(_rows(qs[j], r0, h), k_ref[:end, :], ABT), visible)
        # jax.lax in a unit's body, as in the forward
        p = jax.lax.exp(jax.lax.sub(s, _along(_rows(lse[j], r0, h), s)))
        dp = dot(doj, v_ref[:end, :], ABT)
        ds = jax.lax.mul(jax.lax.mul(p, jax.lax.sub(
            dp, _along(_rows(delta[j], r0, h), dp))), sm_scale)
        return (jax.lax.convert_element_type(p, do.dtype),
                jax.lax.convert_element_type(ds, k.dtype), doj)

    dq = None
    for (r0, h, end, j), (p, ds, doj) in _one_ahead(
            _units(block_q, block_k, band, heads), head, band):
        # each product is zero outside head j's lanes: the heads add up
        dv_scr[:end, :] += dot(p, doj, ATB)
        dk_scr[:end, :] += dot(ds, _rows(qm[j], r0, h), ATB)
        dqj = dot(ds, _rows(ks[j], 0, end), AB)
        dq = dqj if j == 0 else jax.lax.add(dq, dqj)
        if j == heads - 1:
            dq_ref[r0:r0 + h, :] = dq.astype(dq_ref.dtype)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, delta_ref, dq_scr, *,
                   sm_scale: float, causal: bool, d: int,
                   block_q: int, block_k: int, num_kb: int, window=None):
    """Grid: (B, column blocks, num_q_blocks, num_k_blocks); accumulates
    dq over K (with a ``window``, over the band's ``num_kb`` steps). Also
    emits delta [B*heads, 1, S] (``_row_delta``), which it needs itself and
    the dk/dv kernel reads."""
    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for j in range(lse_ref.shape[0]):
            delta_ref[j] = _row_delta(
                do_ref[...], o_ref[...], j, d,
                do_ref.shape[-1] != q_ref.shape[-1]).T

    col = kb if window is None else \
        kb + _band_first(qi, block_q, block_k, window - 1)
    run = (qi * block_q + block_q - 1 >= col * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        for j in range(lse_ref.shape[0]):
            _, ds, _, _ = _bwd_head(
                q, k, v, do, lse_ref[j].T, delta_ref[j].T, j, d=d,
                sm_scale=sm_scale, causal=causal, row0=qi * block_q,
                col0=col * block_k, window=window)
            dq_scr[...] += dot(ds, _head_lanes(k, j, d), AB)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale: float, causal: bool, d: int,
                    block_q: int, block_k: int, num_qb: int, window=None,
                    seq_qb: int = 0):
    """Grid: (B, column blocks, num_k_blocks, num_q_blocks); accumulates
    dk/dv over Q (with a ``window``, over the ``num_qb`` steps of the band
    of query blocks that see this key block, of ``seq_qb`` in all)."""
    kb = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if window is None:
        row = qi
        run = (qi * block_q + block_q - 1 >= kb * block_k) if causal else True
    else:
        # the query block of this step: from the first that holds a row at
        # or past this key block's first key; none past the last whose
        # window still reaches its last key
        row = qi + _band_first(kb, block_k, block_q, 0)
        run = row <= _band_last(kb, block_k, block_q, window - 1, seq_qb)

    @pl.when(run)
    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        for j in range(lse_ref.shape[0]):
            p, ds, qj, doj = _bwd_head(
                q, k, v, do, lse_ref[j].T, delta_ref[j].T, j, d=d,
                sm_scale=sm_scale, causal=causal, row0=row * block_q,
                col0=kb * block_k, window=window)
            dv_scr[...] += dot(p, doj, ATB)
            dk_scr[...] += dot(ds, qj, ATB)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, heads, hpb, sm_scale, causal, block_q,
               block_k, window=None, paired=False):
    """[B, S, heads*D] q, k, v, o, dO and lse [B*heads, 1, S] ->
    dq, dk, dv [B, S, heads*D]. A call with a ``window`` takes the two
    streamed kernels whatever S, each over its band of blocks alone; so
    does a ``paired`` one (``_flash_fwd``), whose o and dO are
    [B, S, heads*128] and whose dv is the one value's, both maps summed."""
    b, seq_q, _ = q.shape
    seq_k = k.shape[1]
    d, w, ncb, block_q, block_k, band, wo, dd = _cut(
        q, k, heads, hpb, causal, block_q, block_k, paired)
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k
    interp = kernel_common.use_interpret()
    from jax.experimental.pallas import tpu as pltpu

    bh = b * heads
    half = 2 if causal else 1
    bytes_qkv2 = (q.size * 2 + k.size * 2 + v.size * 2) * q.dtype.itemsize

    if num_kb == 1 and window is None and not paired:
        # single K block: one fused pass computes s/p once and emits
        # dq + dk + dv together (the two-pass scheme below recomputes the
        # s matrix, mask, and exp in each kernel)
        q_spec = pl.BlockSpec((None, block_q, w), lambda b, c, i: (b, i, c))
        row_spec = pl.BlockSpec((hpb, 1, block_q),
                                lambda b, c, i: (b * ncb + c, 0, i))
        kv_spec = pl.BlockSpec((None, block_k, w), lambda b, c, i: (b, 0, c))
        return pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, sm_scale=sm_scale, causal=causal, d=d,
                block_q=block_q, num_qb=num_qb, band=band),
            grid=(b, ncb, num_qb),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, w), jnp.float32),
                pltpu.VMEM((block_k, w), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name=KERNEL_NAMES["bwd_fused"],
            interpret=interp,
            cost_estimate=pl.CostEstimate(
                flops=10 * bh * seq_q * seq_k * d // half,
                bytes_accessed=bytes_qkv2,
                transcendentals=bh * seq_q * seq_k,
            ),
        )(q, k, v, o, g, lse)

    # the key block of step j of query block i and the query block of step
    # i of key block j: every block in turn, or, with a window, the blocks
    # of the band alone
    key_block, k_steps = (lambda i, j: j), num_kb
    query_block, q_steps = (lambda j, i: i), num_qb
    if window is not None:
        key_block, k_steps = _band_of_keys(num_qb, num_kb, block_q, block_k,
                                           window)
        query_block, q_steps = _band_of_queries(num_qb, num_kb, block_q,
                                                block_k, window)
        half = max(1, seq_k // min(window, seq_k))

    q_spec = pl.BlockSpec((None, block_q, w), lambda b, c, i, j: (b, i, c))
    o_spec = pl.BlockSpec((None, block_q, wo), lambda b, c, i, j: (b, i, c))
    row_spec = pl.BlockSpec((hpb, 1, block_q),
                            lambda b, c, i, j: (b * ncb + c, 0, i))
    kv_spec = pl.BlockSpec((None, block_k, w),
                           lambda b, c, i, j: (b, key_block(i, j), c))
    parallel3 = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        **_window_vmem(window))

    dq, delta = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, d=d,
            block_q=block_q, block_k=block_k, num_kb=k_steps, window=window),
        grid=(b, ncb, num_qb, k_steps),
        in_specs=[q_spec, kv_spec, kv_spec, o_spec, o_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, w), jnp.float32)],
        compiler_params=parallel3,
        name=KERNEL_NAMES["bwd_dq"],
        interpret=interp,
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * seq_q * seq_k * dd // half,
            bytes_accessed=(q.size * 2 + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(q, k, v, o, g, lse)

    # dk/dv: Q streams in the minor grid dim.
    qb_spec = pl.BlockSpec((None, block_q, w),
                           lambda b, c, j, i: (b, query_block(j, i), c))
    dob_spec = pl.BlockSpec((None, block_q, wo),
                            lambda b, c, j, i: (b, query_block(j, i), c))
    rowb_spec = pl.BlockSpec(
        (hpb, 1, block_q),
        lambda b, c, j, i: (b * ncb + c, 0, query_block(j, i)))
    kb_spec = pl.BlockSpec((None, block_k, w), lambda b, c, j, i: (b, j, c))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, d=d,
            block_q=block_q, block_k=block_k, num_qb=q_steps, window=window,
            seq_qb=num_qb),
        grid=(b, ncb, num_kb, q_steps),
        in_specs=[qb_spec, kb_spec, kb_spec, dob_spec, rowb_spec, rowb_spec],
        out_specs=[kb_spec, kb_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, w), jnp.float32),
            pltpu.VMEM((block_k, w), jnp.float32),
        ],
        compiler_params=parallel3,
        name=KERNEL_NAMES["bwd_dkv"],
        interpret=interp,
        cost_estimate=pl.CostEstimate(
            flops=8 * bh * seq_q * seq_k * dd // half,
            bytes_accessed=bytes_qkv2,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP — boundary carries MERGED [B, S, H*D] tensors
# ---------------------------------------------------------------------------
# Residuals cross the fwd/bwd boundary in merged form, and where
# ``_heads_per_block`` allows it (hpb > 0) nothing else exists: the
# kernels read q, k, v, o, dO and write o, dq, dk, dv in that form, no
# transpose or reshape stands around them. Elsewhere (hpb == 0: heads of
# 32, 96, 256, ...) the kernels get [B*H, S, D] copies made here, which
# exist only transiently inside the fwd/bwd computations: a [B*H, S, 64]
# tensor tile-pads its 64-lane minor dim to 128 in HBM (2x memory AND 2x
# traffic every time the remat machinery stacks it into the per-layer
# residual buffers), [B, S, 768] is unpadded.


def _to_bhsd(x, h):
    b, s, hd = x.shape
    return x.reshape(b, s, h, hd // h).transpose(0, 2, 1, 3).reshape(
        b * h, s, hd // h)


def _from_bhsd(x, b, h):
    s, d = x.shape[1:]
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _fwd_any(qm, km, vm, h, hpb, sm_scale, causal, block_q, block_k,
             window, paired):
    if hpb:
        return _flash_fwd(qm, km, vm, h, hpb, sm_scale, causal, block_q,
                          block_k, window, paired)
    out, lse = _flash_fwd(_to_bhsd(qm, h), _to_bhsd(km, h), _to_bhsd(vm, h),
                          1, 1, sm_scale, causal, block_q, block_k, window)
    return _from_bhsd(out, qm.shape[0], h), lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(qm, km, vm, h, hpb, sm_scale, causal, block_q, block_k,
           window=None, paired=False):
    """``paired`` (always with ``hpb`` 2): vm is [B, S, h/2 * 128], the
    one value of each pair of score heads, and the output [B, S, h * 128]
    (``_flash_fwd``); the merged widths of km and vm are then the same, so
    the arrays alone do not say it."""
    return _fwd_any(qm, km, vm, h, hpb, sm_scale, causal, block_q,
                    block_k, window, paired)[0]


def _flash_vjp_fwd(qm, km, vm, h, hpb, sm_scale, causal, block_q, block_k,
                   window, paired):
    from jax.ad_checkpoint import checkpoint_name

    out_m, lse = _fwd_any(qm, km, vm, h, hpb, sm_scale, causal, block_q,
                          block_k, window, paired)
    # Named so a remat policy can choose to SAVE these residuals: pallas
    # outputs are not dots, so a dots-saveable policy would otherwise
    # re-run the forward kernel inside the backward pass.
    out_m = checkpoint_name(out_m, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out_m, (qm, km, vm, out_m, lse)


def _flash_vjp_bwd(h, hpb, sm_scale, causal, block_q, block_k, window,
                   paired, res, g):
    qm, km, vm, out_m, lse = res
    if hpb:
        return _flash_bwd(qm, km, vm, out_m, lse, g, h, hpb, sm_scale,
                          causal, block_q, block_k, window, paired)
    b = qm.shape[0]
    grads = _flash_bwd(
        _to_bhsd(qm, h), _to_bhsd(km, h), _to_bhsd(vm, h),
        _to_bhsd(out_m, h), lse, _to_bhsd(g, h), 1, 1, sm_scale, causal,
        block_q, block_k, window)
    return tuple(_from_bhsd(x, b, h) for x in grads)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# latent attention: a score in two parts, the second against ONE shared key
# ---------------------------------------------------------------------------
# score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) * scale, o_h = P v_h
# (DeepSeek-V2's multi-head latent attention after the up-projection): the
# q/k head is dn + dr wide, the value dv, and k_rope [B, S, dr] is one
# vector a position that every head shares. Everything stays on 128-lane
# tiles and the shared key is never broadcast to the heads: a program
# holds the 128 // dr heads whose rope parts fill one 128-lane block of
# q_rope [B, S, H*dr]; their nope and value parts are whole 128-lane
# tiles of [B, S, H*dn] and [B, S, H*dv] (dn, dv multiples of 128), cut
# by static slices; the rope part of head j is the q_rope tile with the
# other heads' lanes zeroed (``_head_lanes``) against k_rope repeated to
# 128 lanes (``kr``), so that the zeroed lanes add exact zeros. The
# gradient of ``kr`` comes out of the backward summed over every head of
# the batch row: one accumulator carried across the head blocks. One set
# of kernels serves every S (a single block is a grid of one step); blocks
# above the diagonal are skipped by predicate and fetch nothing (their
# index maps point at the block before), and only blocks the diagonal
# crosses are masked. Such a DIAGONAL step works causal row bands, not
# the masked square, where the blocks are square and a multiple of 256
# (``_latent_band``; both kernels, by ``_latent_band_scores`` and
# ``_latent_bwd_bands``): band r of a quarter of the block takes the
# rows [r*h, (r+1)*h) of q, o, dO and the row statistics and the columns
# [0, (r+1)*h) of k_nope, the shared key and v, masks the last [h, h]
# tile alone and adds into static slices of the scratch: 10 of a block's
# 16 band tiles issued; at S 4096 in blocks of 1024 four of a head
# block's ten computed steps are diagonal, at S 8192 eight of 36. A call
# in blocks of 128 works the whole block under the mask. The backward is
# ONE kernel (``_latent_bwd_fused_kernel``): the score, dP, the mask and
# exp of a block pair are made once and feed all five gradients, 8 passes
# of a 128-deep contraction a pair and head (a dq kernel beside a dk/dv
# kernel issued 5 + 6; PERF.md, PR 34), for causal calls in square
# blocks: ``_latent_attention`` hands the kernels nothing else.


def _latent_cut(qn, qr, v, heads, block_q, block_k):
    """-> (dn, dr, dv, heads to a program, programs a batch row, block_q,
    block_k) of a latent call on merged arrays."""
    dn, dr, dv = (x.shape[-1] // heads for x in (qn, qr, v))
    hpb = LANES // dr
    return (dn, dr, dv, hpb, heads // hpb, fit_block(block_q, qn.shape[1]),
            fit_block(block_k, qn.shape[1]))


def _latent_ok(heads: int, dn: int, dr: int, dv: int) -> bool:
    """Whether the latent kernels can cut these heads into 128-lane
    tiles."""
    return (dr in (64, LANES) and dn % LANES == 0 and dv % LANES == 0
            and heads % (LANES // dr) == 0)


def _lanes(x, j: int, d: int):
    """Head j's d lanes of a tile of whole 128-lane head tiles."""
    return x[:, j * d:(j + 1) * d]


def _causal_steps(causal, qi, kb, block_q, block_k, body):
    """Run ``body(masked)`` for the (q block, k block) pair: not at all
    where the causal mask hides the whole block, unmasked where every
    column is visible to every row."""
    if not causal:
        body(False)
        return
    first_row, last_row = qi * block_q, qi * block_q + block_q - 1
    first_col, last_col = kb * block_k, kb * block_k + block_k - 1
    pl.when(first_row >= last_col)(lambda: body(False))
    pl.when((last_row >= first_col) & (first_row < last_col))(
        lambda: body(True))


def _latent_scores(qn, qr, kn, kr, j, dn, dr, sm_scale, masked, row0, col0):
    """Head j's [block_q, block_k] scores in f32: both parts on the MXU,
    q scaled on its (block_q, d) tiles."""
    scale = jnp.asarray(sm_scale, qn.dtype)
    s = dot(_lanes(qn, j, dn) * scale, _lanes(kn, j, dn), ABT) \
        + dot(_head_lanes(qr, j, dr) * scale, kr, ABT)
    if masked:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    return s


def _latent_band(causal: bool, block_q: int, block_k: int) -> int:
    """Height of the causal row bands a latent program works in a step
    the diagonal crosses, or 0 where such a step works the whole block
    under the mask: a non-causal call, unequal blocks (the diagonal then
    cuts no aligned triangle out of a block), a block that is no multiple
    of 256 (one band of 128 is the block). ``_band_height``'s rule
    applied to the BLOCK: a quarter of it in whole 128-lane tiles, 256 at
    blocks of 1024, 10 of a diagonal block's 16 band tiles issued."""
    if not causal or block_q != block_k or block_q % (2 * LANES):
        return 0
    return _band_height(block_q, True, block_q, block_k)


def _latent_band_scores(band, qn_ref, qr_ref, kn_ref, kr_ref, heads, dn, dr,
                        sm_scale):
    """-> scores((first row, rows, columns, head)): the [rows, columns]
    scores in f32 of one (band, head) unit of a DIAGONAL step, its last
    [band, band] tile masked. What does not depend on the band (the
    heads' scaled tiles of q, the one mask every band's diagonal tile
    has) is made here, once a program (``_fwd_single_kernel``'s reason);
    the keys a band can see are static slices of the tiles in VMEM."""
    scale = jnp.asarray(sm_scale, qn_ref.dtype)
    qn, qr = qn_ref[...] * scale, qr_ref[...] * scale
    qns = [_lanes(qn, j, dn) for j in range(heads)]
    qrs = [_head_lanes(qr, j, dr) for j in range(heads)]
    visible = _visible(0, band, band)

    def scores(u):
        r0, h, end, j = u
        return _masked(jax.lax.add(
            dot(_rows(qns[j], r0, h), kn_ref[:end, j * dn:(j + 1) * dn],
                ABT),
            dot(_rows(qrs[j], r0, h), kr_ref[:end, :], ABT)), visible)

    return scores


def _latent_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, sm_scale, causal, dn, dr,
                       dv, block_q, block_k, num_kb, band):
    """Grid (B, head blocks, q blocks, k blocks), K innermost: online
    softmax with one (m, l, acc) a head of the block. In a banded call
    (``_latent_band``) the step the diagonal crosses works row bands,
    band r on the keys [0, (r+1)*band) of the block, and carries the
    online softmax on at the band's rows of the scratch."""
    qi, kb = pl.program_id(2), pl.program_id(3)
    heads = lse_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def bands():
        scores = _latent_band_scores(band, qn_ref, qr_ref, kn_ref, kr_ref,
                                     heads, dn, dr, sm_scale)
        for (r0, h, end, j), s in _one_ahead(
                _units(block_q, block_k, band, heads), scores, True):
            # jax.lax in a unit's body, as in the single-block kernels
            rows = slice(r0, r0 + h)
            m_prev = m_scr[j, rows, :]
            m_new = jax.lax.max(m_prev, _col(jax.lax.reduce_max(s, (1,))))
            p = jax.lax.exp(jax.lax.sub(s, _along(m_new, s)))
            alpha = jax.lax.exp(jax.lax.sub(m_prev, m_new))
            l_scr[j, rows, :] = jax.lax.add(
                jax.lax.mul(l_scr[j, rows, :], alpha),
                _col(jax.lax.reduce_sum(p, (1,))))
            pv = dot(jax.lax.convert_element_type(p, v_ref.dtype),
                     v_ref[:end, j * dv:(j + 1) * dv], AB)
            acc_scr[j, rows, :] = jax.lax.add(
                jax.lax.mul(acc_scr[j, rows, :], _along(alpha, pv)), pv)
            m_scr[j, rows, :] = m_new

    def compute(masked):
        if masked and band:
            return bands()
        qn, qr, kn, kr, v = (qn_ref[...], qr_ref[...], kn_ref[...],
                             kr_ref[...], v_ref[...])
        for j in range(heads):
            s = _latent_scores(qn, qr, kn, kr, j, dn, dr, sm_scale, masked,
                               qi * block_q, kb * block_k)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[j] = l_scr[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[j] = acc_scr[j] * alpha + dot(
                p.astype(v.dtype), _lanes(v, j, dv), AB)
            m_scr[j] = m_new

    _causal_steps(causal, qi, kb, block_q, block_k, compute)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        for j in range(heads):
            l = jnp.maximum(l_scr[j], 1e-30)
            o_ref[:, j * dv:(j + 1) * dv] = (acc_scr[j] / l).astype(
                o_ref.dtype)
            lse_ref[j] = (m_scr[j] + jnp.log(l)).T


def _latent_bwd_head(qn, qr, kn, kr, v, do, lse, delta, j, *, dn, dr, dv,
                     sm_scale, masked, row0, col0):
    """(p, ds) of head j for one (q block, k block) pair, in the inputs'
    dtype for the MXU; ds carries the score's scale (``_bwd_head``)."""
    s = _latent_scores(qn, qr, kn, kr, j, dn, dr, sm_scale, masked, row0,
                       col0)
    p = jnp.exp(s - lse)
    dp = dot(_lanes(do, j, dv), _lanes(v, j, dv), ABT)
    ds = p * (dp - delta) * sm_scale
    return p.astype(do.dtype), ds.astype(kn.dtype)


def _latent_delta(do, o, j, dv):
    """delta_i = rowsum(dO_i * O_i) of head j -> [block, 1] f32, from the
    tiles at hand (``_row_delta``'s reason)."""
    return jnp.sum(_lanes(do, j, dv).astype(jnp.float32)
                   * _lanes(o, j, dv).astype(jnp.float32),
                   axis=-1, keepdims=True)


def _latent_bwd_bands(band, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                      lse_ref, delta, *, dn, dr, dv, sm_scale, dkv, dq):
    """The DIAGONAL step of a banded call's backward (``_latent_band``):
    ``_latent_bwd_head``'s (p, ds) of each (band, head) unit, [band,
    columns the band sees], and the products they feed, added into static
    slices of what the kernel keeps: ``dkv`` = (dk_nope, d(shared key),
    dv) accumulators of the key block at the band's COLUMNS, ``dq`` =
    (dq_nope, dq_rope) of the q block at the band's ROWS. ``delta`` is one
    [block, 1] column a head. Unit i+1's scores, exp, dP and ds are
    written before unit i's products (``_one_ahead``)."""
    heads = lse_ref.shape[0]
    block = qn_ref.shape[0]
    # lse is stored [1, block]; rows here are q-positions
    lse = [lse_ref[j].T for j in range(heads)]
    scores = _latent_band_scores(band, qn_ref, qr_ref, kn_ref, kr_ref, heads,
                                 dn, dr, sm_scale)
    qn, do = qn_ref[...], do_ref[...]
    # head j's lanes of q_rope (for the shared key's gradient) and of the
    # repeated key (for dq_rope), the other heads' zeroed: once a program
    qrs = [_head_lanes(qr_ref[...], j, dr) for j in range(heads)]
    krs = [_head_lanes(kr_ref[...], j, dr) for j in range(heads)]
    dkn_acc, dkr_acc, dv_acc = dkv
    dqn_acc, dqr_acc = dq

    def head(u):
        r0, h, end, j = u
        s = scores(u)
        doj = _rows(_lanes(do, j, dv), r0, h)
        p = jax.lax.exp(jax.lax.sub(s, _along(_rows(lse[j], r0, h), s)))
        dp = dot(doj, v_ref[:end, j * dv:(j + 1) * dv], ABT)
        ds = jax.lax.mul(jax.lax.mul(p, jax.lax.sub(
            dp, _along(_rows(delta[j], r0, h), dp))), sm_scale)
        return (jax.lax.convert_element_type(p, do.dtype),
                jax.lax.convert_element_type(ds, kn_ref.dtype), doj)

    for (r0, h, end, j), (p, ds, doj) in _one_ahead(
            _units(block, block, band, heads), head, True):
        rows, nope, val = (slice(r0, r0 + h), slice(j * dn, (j + 1) * dn),
                           slice(j * dv, (j + 1) * dv))
        dv_acc[:end, val] += dot(p, doj, ATB)
        dkn_acc[:end, nope] += dot(ds, _rows(_lanes(qn, j, dn), r0, h), ATB)
        dkr_acc[:end, :] += dot(ds, _rows(qrs[j], r0, h), ATB)
        dqn_acc[rows, nope] += dot(ds, kn_ref[:end, nope], AB)
        dqr_acc[rows, :] += dot(ds, _rows(krs[j], 0, end), AB)


def _latent_bwd_fused_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                             do_ref, lse_ref, dqn_ref, dqr_ref, dkn_ref,
                             dkr_ref, dv_ref, dqn_scr, dqr_scr, dkn_scr,
                             dkr_scr, dv_scr, *, sm_scale, dn, dr, dv, block,
                             num_qb, num_cb, band):
    """The whole backward of a causal call in one kernel, blocks square:
    grid (B, head blocks, k blocks, q blocks). ``_latent_bwd_head``'s
    (p, ds) are made once a (q block, k block) pair and head and feed all
    five gradients. dk_nope and dv accumulate over Q for one head block.
    dq_nope and dq_rope accumulate over the key blocks in VMEM for the
    WHOLE sequence of one head block
    ([q blocks, block, width] f32): rows of q block i are complete at the
    diagonal step (kb = i, qi = i), the first computed step of key block
    i, and are written there; their output block is (b, kb, c), which
    holds still through the inner loop, so each goes to HBM once. The
    shared key's gradient accumulates for the whole sequence too, across
    the head blocks, and leaves in the last one. delta = rowsum(dO * o)
    is made from the tiles at hand (``_row_delta``'s reason)."""
    cb, kb, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    heads = lse_ref.shape[0]

    @pl.when(qi == 0)
    def _init():
        dkn_scr[...] = jnp.zeros_like(dkn_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when((qi == 0) & (cb == 0))
    def _init_shared():
        dkr_scr[kb] = jnp.zeros(dkr_scr.shape[1:], dkr_scr.dtype)

    @pl.when(kb == 0)
    def _init_dq():
        dqn_scr[qi] = jnp.zeros(dqn_scr.shape[1:], dqn_scr.dtype)
        dqr_scr[qi] = jnp.zeros(dqr_scr.shape[1:], dqr_scr.dtype)

    def bands():    # the diagonal step of a banded call
        do, o = do_ref[...], o_ref[...]
        _latent_bwd_bands(
            band, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
            [_latent_delta(do, o, j, dv) for j in range(heads)], dn=dn,
            dr=dr, dv=dv,
            sm_scale=sm_scale, dkv=(dkn_scr, dkr_scr.at[kb], dv_scr),
            dq=(dqn_scr.at[qi], dqr_scr.at[qi]))
        dqn_ref[...] = dqn_scr[qi].astype(dqn_ref.dtype)
        dqr_ref[...] = dqr_scr[qi].astype(dqr_ref.dtype)

    def compute(masked):
        if masked and band:
            return bands()
        qn, qr, kn, kr, v, o, do = (
            qn_ref[...], qr_ref[...], kn_ref[...], kr_ref[...], v_ref[...],
            o_ref[...], do_ref[...])
        for j in range(heads):
            doj = _lanes(do, j, dv)
            delta = _latent_delta(do, o, j, dv)
            p, ds = _latent_bwd_head(
                qn, qr, kn, kr, v, do, lse_ref[j].T, delta, j, dn=dn, dr=dr,
                dv=dv, sm_scale=sm_scale, masked=masked, row0=qi * block,
                col0=kb * block)
            dv_scr[:, j * dv:(j + 1) * dv] += dot(p, doj, ATB)
            dkn_scr[:, j * dn:(j + 1) * dn] += dot(ds, _lanes(qn, j, dn),
                                                   ATB)
            dkr_scr[kb] += dot(ds, _head_lanes(qr, j, dr), ATB)
            dqn_scr[qi, :, j * dn:(j + 1) * dn] += dot(
                ds, _lanes(kn, j, dn), AB)
            dqr_scr[qi] += dot(ds, _head_lanes(kr, j, dr), AB)
        if masked:   # the diagonal step: the last key block these rows see
            dqn_ref[...] = dqn_scr[qi].astype(dqn_ref.dtype)
            dqr_ref[...] = dqr_scr[qi].astype(dqr_ref.dtype)

    _causal_steps(True, qi, kb, block, block, compute)

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dkn_ref[...] = dkn_scr[...].astype(dkn_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((qi == num_qb - 1) & (cb == num_cb - 1))
    def _finalize_shared():
        dkr_ref[...] = dkr_scr[kb].astype(dkr_ref.dtype)


# v5e's default scoped VMEM (16 MiB) does not hold three [1024, 1024] f32
# tiles of scores beside double-buffered operands of two heads; the chip
# has 128 MiB.
_LATENT_VMEM_BYTES = 96 * 1024 * 1024


def _latent_specs(q_major: bool, causal, block_q, block_k, hpb, ncb, widths):
    """Block specs of the latent kernels for a grid (B, head block, q
    block, k block) (``q_major``: the forward) or (B, head block, k block,
    q block) (the backward): (q_nope, q_rope, o / dO, k_nope, v, shared
    key, row statistics). A skipped causal step names the block of the
    last computed step, so nothing is fetched for it."""
    wn, wv = widths

    def at(pick):
        def index_map(b, c, x, y):
            i, j = (x, y) if q_major else (y, x)
            if causal and q_major:   # k blocks past the diagonal: skipped
                j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
            elif causal:             # q blocks before the diagonal: skipped
                i = jnp.maximum(i, j * block_k // block_q)
            return pick(b, c, i, j)
        return index_map

    def q_side(w):
        return pl.BlockSpec((None, block_q, w), at(lambda b, c, i, j: (b, i, c)))

    def k_side(w):
        return pl.BlockSpec((None, block_k, w), at(lambda b, c, i, j: (b, j, c)))

    shared = pl.BlockSpec((None, block_k, LANES),
                          at(lambda b, c, i, j: (b, j, 0)))
    rows = pl.BlockSpec((hpb, 1, block_q),
                        at(lambda b, c, i, j: (b * ncb + c, 0, i)))
    return q_side(wn), q_side(LANES), q_side(wv), k_side(wn), k_side(wv), \
        shared, rows


def _latent_fwd(qn, qr, kn, kr, v, heads, sm_scale, causal, block_q,
                block_k):
    """Merged q_nope, k_nope [B, S, H*dn], q_rope [B, S, H*dr], the shared
    key repeated to 128 lanes kr [B, S, 128], v [B, S, H*dv] ->
    (o [B, S, H*dv], lse [B*H, 1, S])."""
    from jax.experimental.pallas import tpu as pltpu

    b, seq, _ = qn.shape
    dn, dr, dv, hpb, ncb, block_q, block_k = _latent_cut(
        qn, qr, v, heads, block_q, block_k)
    num_kb = seq // block_k
    qn_s, qr_s, qv_s, kn_s, kv_s, kr_s, row_s = _latent_specs(
        True, causal, block_q, block_k, hpb, ncb, (hpb * dn, hpb * dv))
    half = 2 if causal else 1
    return pl.pallas_call(
        functools.partial(
            _latent_fwd_kernel, sm_scale=sm_scale, causal=causal, dn=dn,
            dr=dr, dv=dv, block_q=block_q, block_k=block_k, num_kb=num_kb,
            band=_latent_band(causal, block_q, block_k)),
        grid=(b, ncb, seq // block_q, num_kb),
        in_specs=[qn_s, qr_s, kn_s, kr_s, kv_s],
        out_specs=[qv_s, row_s],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b * heads, 1, seq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((hpb, block_q, 1), jnp.float32),
            pltpu.VMEM((hpb, block_q, 1), jnp.float32),
            pltpu.VMEM((hpb, block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM_BYTES),
        name=LATENT_KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * heads * seq * seq * (dn + dr + dv) // half,
            bytes_accessed=(qn.size + qr.size + kn.size + kr.size
                            + 2 * v.size) * qn.dtype.itemsize,
            transcendentals=b * heads * seq * seq // half),
    )(qn, qr, kn, kr, v)


def _latent_bwd_vmem(seq: int, block: int, wn: int, wv: int,
                     itemsize: int) -> int:
    """Bytes of VMEM the backward kernel asks for: what it keeps of the
    whole sequence beside a block pair's tiles (``_latent_attention``
    refuses a call over ``_LATENT_VMEM_BYTES``). At S=8192 and two heads
    of 128 to a block: 16.8 MB of whole-sequence accumulators + 16.8 MB of
    score tiles + 2 MB + 10.5 MB of operands: 46 of 96; S 34 816 is the
    longest that fits at these widths."""
    whole = seq * (wn + 2 * LANES) * 4         # dq_nope, dq_rope, d(kr)
    tiles = block * block * (3 * 4 + 2 * itemsize)      # s, dP, ds; p, ds
    dkv = block * (wn + wv) * 4
    # q_nope, q_rope, o, dO, k_nope, kr, v in; the five gradients out;
    # each double-buffered; a q side and a k side of one block each
    operands = 2 * 2 * itemsize * (2 * block * (wn + wv + LANES))
    return whole + tiles + dkv + operands


def _latent_bwd(qn, qr, kn, kr, v, o, lse, g, heads, sm_scale, causal,
                block_q, block_k):
    """-> dq_nope, dq_rope, dk_nope, d(kr) [B, S, 128] (the heads of a
    batch row summed; lane block j holds the heads whose rope part reads
    it), dv, of a CAUSAL call in square blocks: one kernel, launched under
    the name the benchmark's reader knows (``LATENT_KERNEL_NAMES``)."""
    from jax.experimental.pallas import tpu as pltpu

    b, seq, _ = qn.shape
    dn, dr, dv, hpb, ncb, block, block_k = _latent_cut(
        qn, qr, v, heads, block_q, block_k)
    if not causal or block != block_k:
        # a q block's dq is complete at ITS diagonal step: no other order
        raise ValueError("the latent backward takes causal calls in square "
                         f"blocks, got causal={causal}, {block} x {block_k}")
    nb = seq // block
    wn, wv = hpb * dn, hpb * dv
    pairs = b * heads * seq * seq // 2
    qn_s, qr_s, qv_s, kn_s, kv_s, kr_s, row_s = _latent_specs(
        False, True, block, block, hpb, ncb, (wn, wv))
    # dq's blocks leave by key block (square blocks: k_nope's spec fits
    # dq_nope); the shared key's gradient leaves in the last head block
    # and names one block until then, so that nothing is written before
    dqr_s = pl.BlockSpec((None, block, LANES), lambda b, c, j, i: (b, j, c))
    dkr_s = pl.BlockSpec(
        (None, block, LANES),
        lambda b, c, j, i: (b, jnp.where(c == ncb - 1, j, 0), 0))
    return pl.pallas_call(
        functools.partial(
            _latent_bwd_fused_kernel, sm_scale=sm_scale, dn=dn, dr=dr, dv=dv,
            block=block, num_qb=nb, num_cb=ncb,
            band=_latent_band(True, block, block)),
        grid=(b, ncb, nb, nb),
        in_specs=[qn_s, qr_s, kn_s, kr_s, kv_s, qv_s, qv_s, row_s],
        out_specs=[kn_s, dqr_s, kn_s, dkr_s, kv_s],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (qn, qr, kn, kr, v)],
        scratch_shapes=[pltpu.VMEM((nb, block, wn), jnp.float32),
                        pltpu.VMEM((nb, block, LANES), jnp.float32),
                        pltpu.VMEM((block, wn), jnp.float32),
                        pltpu.VMEM((nb, block, LANES), jnp.float32),
                        pltpu.VMEM((block, wv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM_BYTES),
        name=LATENT_KERNEL_NAMES["bwd_dkv"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * pairs * (3 * (dn + dr) + 2 * dv),
            bytes_accessed=(2 * qn.size + 2 * qr.size + 2 * kn.size
                            + 2 * kr.size + 4 * v.size) * qn.dtype.itemsize,
            transcendentals=pairs),
    )(qn, qr, kn, kr, v, o, g, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_latent(qn, qr, kn, kr, v, h, sm_scale, block):
    return _latent_fwd(qn, qr, kn, kr, v, h, sm_scale, True, block, block)[0]


def _flash_latent_vjp_fwd(qn, qr, kn, kr, v, h, sm_scale, block):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _latent_fwd(qn, qr, kn, kr, v, h, sm_scale, True, block, block)
    out = checkpoint_name(out, "flash_out")    # as ``_flash_vjp_fwd``
    lse = checkpoint_name(lse, "flash_lse")
    return out, (qn, qr, kn, kr, v, out, lse)


def _flash_latent_vjp_bwd(h, sm_scale, block, res, g):
    qn, qr, kn, kr, v, out, lse = res
    return _latent_bwd(qn, qr, kn, kr, v, out, lse, g, h, sm_scale, True,
                       block, block)


_flash_latent.defvjp(_flash_latent_vjp_fwd, _flash_latent_vjp_bwd)


def _note_path(layout: str, hpb: int, d: int, seq: int, bands: int,
               **more) -> None:
    PATH_COUNTS[layout] += 1
    BAND_COUNTS[bands] += 1
    _record("rtpu.ops.flash.path", layout,
            dict({"layout": layout, "heads_per_block": hpb, "hd": d,
                  "S": seq, "bands": bands}, **more))


def _latent_attention(q, k, v, q_rope, k_rope, causal, sm_scale, block_q,
                      block_k):
    """The call with a second, shared part of the score: q, k
    [B, S, H, dn], v [B, S, H, dv], q_rope [B, S, H, dr], k_rope
    [B, S, dr] -> [B, S, H, dv]. The route is decided here, once, from
    what the call shows: a causal call whose sizes tile takes the kernels,
    in square blocks (the smaller of the two asked for); a call that is
    not causal, or whose sizes do not tile, ``mha_reference``; a causal
    call too long for what the backward keeps in VMEM is refused."""
    b, s, h, dn = q.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / ((dn + dr) ** 0.5)
    if k.shape[1] != s:
        raise ValueError("latent flash_attention requires seq_q == seq_k, "
                         f"got {s} != {k.shape[1]}")
    facts = {"hd_qk": dn + dr, "hd_v": dv, "shared_key": dr}
    if s % 128 != 0 or not _latent_ok(h, dn, dr, dv) or not causal:
        _note_path("latent_reference", 0, dn + dr, s, 0, **facts)
        shared = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))
        return mha_reference(jnp.concatenate([q, q_rope], -1),
                             jnp.concatenate([k, shared], -1), v,
                             causal=causal, sm_scale=sm_scale)
    hpb = LANES // dr
    # square blocks: the smaller of the two fitted ones divides S too
    block = min(fit_block(block_q, s), fit_block(block_k, s))
    need = _latent_bwd_vmem(s, block, hpb * dn, hpb * dv, q.dtype.itemsize)
    if need > _LATENT_VMEM_BYTES:
        # the reference would make S x S scores a head: no route to fall to
        raise ValueError(
            f"latent flash_attention at S={s}: the backward keeps {need} "
            f"bytes in VMEM, the limit is {_LATENT_VMEM_BYTES}")
    band = _latent_band(True, block, block)
    _note_path("latent", hpb, dn + dr, s, block // band if band else 1,
               **facts)
    merge = lambda x: x.reshape(b, s, -1)  # noqa: E731
    out = _flash_latent(merge(q), merge(q_rope), merge(k),
                        jnp.tile(k_rope, (1, 1, hpb)), merge(v), h,
                        sm_scale, block)
    return out.reshape(b, s, h, dv)


def _window_facts(window, s: int, block_q: int, block_k: int) -> dict:
    """What the event of a windowed call says beside the layout: the
    blocks its grids visit and those a causal call would; nothing for a
    call without a window."""
    if window is None:
        return {}
    bq, bk = fit_block(block_q, s), fit_block(block_k, s)
    over = (s // bq, bq, bk)
    return {"window": window, "block_q": bq, "block_k": bk,
            "blocks_visited": sum(_band_counts(*over, window - 1, 0,
                                               s // bk)),
            "blocks_causal": sum(_band_counts(*over, s, 0, s // bk))}


def _paired_attention(q, k, v, causal, sm_scale, block_q, block_k, window):
    """The call that shows a PAIR of score heads a value: q, k
    [B, S, H, d], v [B, S, H/2, 2d] -> [B, S, H, 2d], head 2i
    softmax(q_2i k_2i^T) V_i and head 2i+1 softmax(q_2i+1 k_2i+1^T) V_i.
    Where the shapes tile (d of 64, S a multiple of 128) the streamed
    kernels, whatever S: one program holds the pair's 128 lanes of q and
    of k and the value's 128 and forms each map once. Every other call is
    expanded to four heads of d a pair, (q1 k1 v1) (q1 k1 v2) (q2 k2 v1)
    (q2 k2 v2), and takes the routes of a call with one head size."""
    b, s, h, d = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if s % 128 or sk % 128 or _heads_per_block(h, d) != 2 \
            or (causal and s != sk):
        halves = jnp.tile(v.reshape(b, sk, h // 2, 1, 2 * d),
                          (1, 1, 1, 2, 1)).reshape(b, sk, 2 * h, d)
        out = flash_attention(jnp.repeat(q, 2, axis=2),
                              jnp.repeat(k, 2, axis=2), halves, causal,
                              sm_scale, block_q, block_k, window=window)
        return out.reshape(b, s, h, 2 * d)
    _note_path("paired", 2, d, s, 1, hd_v=2 * d,
               **_window_facts(window, s, block_q, block_k))
    merge = lambda x: x.reshape(b, x.shape[1], h * d)  # noqa: E731
    out = _flash(merge(q), merge(k), merge(v), h, 2, sm_scale, causal,
                 block_q, block_k, window, True)
    return out.reshape(b, s, h, 2 * d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    q_rope: Optional[jax.Array] = None,
                    k_rope: Optional[jax.Array] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention. q/k/v: [batch, seq, heads, head_dim] -> v's shape
    (a paired call: [batch, seq, heads, 2 * head_dim]).

    Heads of 64 or 128 whose merged width heads*head_dim is a multiple of
    128 run with no copy around the kernels; other head sizes are
    transposed to and fro, and sequences that are no multiple of 128 go
    to ``mha_reference`` (module docstring; ``PATH_COUNTS``).

    A PAIRED call is known by its shapes: v has half as many heads as q
    and k and twice their head size, [batch, seq, heads / 2, 2 * head_dim].
    Score heads 2i and 2i+1 share value i; the result is
    [batch, seq, heads, 2 * head_dim], head j softmax(q_j k_j^T) V_(j // 2):
    each score map formed once against the whole value, in one program of
    the streamed kernels where the score heads are of 64, and through the
    four-head expansion otherwise (``layout`` ``paired`` with ``hd_v`` in
    the event, or the expansion's own).

    A call that brings ``q_rope`` [batch, seq, heads, dr] and ONE
    ``k_rope`` [batch, seq, dr] for all heads has a score in two parts,
    (q·k + q_rope·k_rope) * sm_scale (default 1/sqrt(head_dim + dr)), and
    v may be of another head size than q and k: the latent kernels, where
    the call is causal and the sizes tile 128 lanes (``_latent_ok``).

    ``window`` (causal calls of the one-part score): query i sees the keys
    i - window < j <= i, itself and the window - 1 before it. The streamed
    kernels then run, whatever S, over the key blocks of each query
    block's band alone (``window``, ``blocks_visited`` and
    ``blocks_causal`` in the event)."""
    if window is not None and (q_rope is not None or not causal
                               or window < 1):
        raise ValueError("a window takes a causal call of the one-part "
                         f"score and at least one key, got window={window}")
    if q_rope is not None:
        return _latent_attention(q, k, v, q_rope, k_rope, causal, sm_scale,
                                 block_q, block_k)
    b, s, h, d = q.shape
    if k.shape[2:] == (h, d) and (2 * v.shape[2], v.shape[3]) == (h, 2 * d):
        return _paired_attention(q, k, v, causal, sm_scale, block_q,
                                 block_k, window)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if causal and s != k.shape[1]:
        # The kernels' diagonal masks assume square attention; the reference
        # formulation applies a (seq_k - seq_q) offset this path does not.
        raise ValueError(
            f"causal flash_attention requires seq_q == seq_k, got "
            f"{s} != {k.shape[1]}; use mha_reference for "
            "offset-causal decode")
    if s % 128 != 0 or k.shape[1] % 128 != 0:
        # Mosaic's minimum tile is (8, 128): sub-128 sequence blocks lower
        # to illegal or silently padded tiles on real TPU. Pads are the
        # caller's job; unpadded odd shapes go to the XLA reference.
        _note_path("reference", 0, d, s, 0,
                   **({} if window is None else {"window": window}))
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window)
    hpb = _heads_per_block(h, d)
    merge = lambda x: x.reshape(x.shape[0], x.shape[1], h * d)  # noqa: E731
    bands = 1
    if window is None:
        band = _band_height(s, causal, block_q, block_k)
        bands = s // band if band else 1
    _note_path("merged" if hpb else "relayout", hpb, d, s, bands,
               **_window_facts(window, s, block_q, block_k))
    out = _flash(merge(q), merge(k), merge(v), h, hpb, sm_scale, causal,
                 block_q, block_k, window)
    return out.reshape(b, s, h, d)
