"""Mamba-2's state-space recurrence (SSD) for TPU: a chunked scan with its
backward, as two Pallas kernels.

Per head (state h [P, N], one scalar decay a token):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t
    y_t = h_t C_t + D x_t

x [B, T, H, P], dt [B, T, H] (positive: the step after its softplus),
A [H] (negative), B and C [B, T, G, N] shared by the H / G heads of a
group, D [H].

Chunked form. With a_t = dt_t A and c_t its cumulative sum INSIDE a chunk
of Q tokens, for rows t and columns s of one chunk and the state h_0 the
chunk starts from:

    y_t   = sum_{s<=t} (C_t.B_s) exp(c_t - c_s) dt_s x_s + exp(c_t) h_0 C_t
    h_end = exp(c_Q) h_0 + sum_s exp(c_Q - c_s) dt_s x_s (outer) B_s

Every exponent is a difference of cumulative sums with t >= s, so it is
<= 0: nothing overflows however strong the decay (a factorised
exp(c_t) exp(-c_s) would). Chunking is not part of the mathematics; any
chunk gives the recurrence's numbers up to rounding.

The routes, chosen by what a call shows (``PATH_COUNTS``, the event
``rtpu.ops.ssd.path``; no argument or configuration selects one):

* ``kernel``: heads of 64 or 128 whose merged width H*P is a multiple of
  128, one group, a state that is a multiple of 128, a chunk that is a
  multiple of 128 and divides T. The kernels index the model's merged
  [B, T, H*P] arrays (two heads of 64 to a 128-lane tile), grid (batch,
  chunk, head block), the chunks in order and the head blocks inside a
  chunk: the state of every head stays in ONE float32 VMEM scratch
  [H*P, N] from chunk to chunk (2 MB at 64 heads of 64 x 128), the group's
  C B^T [Q, Q] is made once a chunk and shared by its head blocks, and
  each head's decay matrix exp(c_t - c_s) [Q, Q] exists only in VMEM. The
  forward also writes the state each chunk starts from ([B, T/Q, H*P, N]
  float32); the backward walks the chunks in reverse, carries the state's
  gradient in the same kind of scratch and accumulates dB and dC over the
  head blocks in their resident output block. No array of shape
  [.., chunks, heads, Q, Q] reaches HBM, forward or backward
  (tests/test_chip_compile.py holds that).

  How a program (one chunk of one block of up to 16 heads) lays out its
  work, one body for heads of 64 and of 128 (``per_tile`` 2 or 1). A
  quantity that is one number a head and token is never held as a
  [Q, 1] column, which fills one lane of 128 and costs a register an
  eighth of a row whatever is done to it (ISSUE 40; the ablation that
  found two thirds of the backward there is in PERF.md section 7):
  - once a program: everything that is a function of the cumulative sum
    alone (exp(c), exp(c_Q - c), exp(c_Q)) on the dense [heads, Q] rows
    the kernel receives, and those rows and dt turned to columns by
    ``_down``: each head's row spread over the sublanes of an aligned
    [128, Q] tile and turned by the XLU, so that it comes out down the
    rows in EVERY lane that is the head's (all 128 for the decay's c_t,
    its own P of the merged width for what multiplies x and dy);
  - once a tile (two heads of 64, or one of 128): x dt, the two
    (backward: five) products with the state, and in the backward the
    sums over a head's lanes that become d(dt) and d(cumulative sum): the
    tile turned once, a head's lanes then being sublanes, added register
    by register into ROWS [heads, Q], which are kept in values and stored
    once a program (``_head_rows``); what row t of d(exponent) gains is
    folded into the tile's sums first, what column s loses is a row
    already;
  - once a head: the [Q, Q] chain alone: the decay, the two (forward:
    one) big products, d(C B^T) and d(exponent).
  The unrolled bodies are ``jax.lax`` primitives (``jnp`` calls on a
  tracer are jitted helpers traced again at every use: ROADMAP A11).
* ``reference``: every other shape (several groups, T no multiple of the
  chunk, a chunk of 1, heads of 32): the same chunked form in plain
  ``jnp``, differentiated by jax; T is padded to whole chunks with dt = 0
  (a step that neither decays nor writes the state).

Precision: matrix products take their operands in x's dtype (bf16 in a
model) and accumulate in float32; dt, the cumulative sums, every decay
and the state are float32 throughout.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_common
from .kernel_common import (AB, ABT, ATB, LANES, VMEM_BYTES, dot, lane_sum,
                            pad_tokens, record_path, spread)

# The names of the two kernels, as a device trace and the compiled HLO
# show them (``name=`` on ``pl.pallas_call``). Part of the measurement:
# pinned in tests/test_tracing_names.py; the benchmark's
# ``ssd_scan_roofline`` finds the kernels' time by them.
KERNEL_NAMES = {
    "fwd": "ssd_chunk_fwd",     # y and the state each chunk starts from
    "bwd": "ssd_chunk_bwd",     # dx, d(dt), d(cumulative sum), dB, dC
}

# Traced calls of ssd_scan by the route each took ("kernel", "reference");
# the same choice is the flight-recorder event ``rtpu.ops.ssd.path``.
PATH_COUNTS: collections.Counter = collections.Counter()

_MAX_HEADS_PER_BLOCK = 16     # 8 tiles of two heads unrolled in a program


def _heads_per_block(heads: int, p: int) -> int:
    """Heads a program works, or 0 where the merged layout cannot be cut:
    whole 128-lane tiles of [.., H*P], and rows of the [H, T] arrays of dt
    that tile the sublanes (a multiple of 8, or all of them)."""
    if p not in (64, LANES) or (heads * p) % LANES:
        return 0
    per_tile = LANES // p
    for hpb in range(min(heads, _MAX_HEADS_PER_BLOCK), 0, -1):
        if heads % hpb == 0 and hpb % per_tile == 0 \
                and (hpb % 8 == 0 or hpb == heads):
            return hpb
    return 0


# ---------------------------------------------------------------------------
# what both kernels share
# ---------------------------------------------------------------------------

_F32 = jnp.float32


def _group_scores(c, b):
    """C B^T of one chunk [Q, Q] in f32, zero above the diagonal."""
    g = dot(c, b, ABT)
    rows = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    return jnp.where(rows >= cols, g, 0.0)


# The unrolled bodies below are written in ``jax.lax`` primitives: each
# ``jnp`` call or operator on a tracer is a jitted helper traced again at
# every use (ROADMAP A11), thousands in a model's step.
_mul, _add, _sub = jax.lax.mul, jax.lax.add, jax.lax.sub


def _tile(v, i: int):
    """Lanes [128 i, 128 (i + 1)) of v."""
    return jax.lax.slice(v, (0, i * LANES), (v.shape[0], (i + 1) * LANES))


def _row_is(sub, k: int):
    """Where the row index ``sub`` (an iota) is k."""
    return jax.lax.eq(sub, jax.lax.full_like(sub, k))


def _only(x, j: int, p: int, first):
    """``_head_lanes`` with the mask at hand: the tile with every lane
    outside head j's P zeroed (``first``: the lanes of the tile's first
    head); the tile itself where it holds one head."""
    if first is None:
        return x
    zero = jax.lax.full_like(x, 0)
    return jax.lax.select(first, x, zero) if j == 0 else \
        jax.lax.select(first, zero, x)


def _decay(cc, cr):
    """exp(c_t - c_s) [Q, Q] from c down the rows (every lane of [Q, 128]
    holding it) and along the lanes [1, Q]; the exponent is <= 0 wherever
    t >= s, and the rest is thrown away by the zeros of ``_group_scores``."""
    q = cr.shape[1]
    d = _sub(jax.lax.concatenate([cc] * (q // LANES), 1),
             spread(cr, (q, q)))
    return jax.lax.exp(jax.lax.min(d, jax.lax.full_like(d, 0)))


def _down(rows, heads: int):
    """Per-head rows [hpb, Q] -> [Q, (hpb / heads) x 128]: every group of
    ``heads`` rows turned down the rows of one 128-lane tile, each head's
    row in every one of its own 128 / heads lanes. The row is spread over
    its lanes' worth of sublanes first (a sublane broadcast is one cheap
    pass) and the [128, Q] float32 tile turned by the XLU, aligned."""
    hpb, q = rows.shape
    return jax.lax.concatenate([jax.lax.transpose(jax.lax.concatenate(
        [spread(jax.lax.slice(rows, (k, 0), (k + 1, q)),
                (LANES // heads, q)) for k in range(k0, k0 + heads)], 0),
        (1, 0)) for k0 in range(0, hpb, heads)], 1)


def _last_entry(cum):
    """The chunk's last cumulative sum of every head [hpb, 1], by a masked
    sum: a cut from lane Q-1 is a layout Mosaic cannot broadcast down the
    rows of a state tile; a sum's is in every lane."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1)
    return jnp.sum(jnp.where(lane == cum.shape[1] - 1, cum, 0.0), axis=1,
                   keepdims=True)


def _last_rows(last, k0: int, per_tile: int, p: int, n: int):
    """exp(c_Q) of the block's heads [hpb, 1] -> [128, n], each head's P
    rows of tile k0's state holding its value."""
    return jax.lax.concatenate(
        [spread(jax.lax.slice(last, (k, 0), (k + 1, 1)), (p, n))
         for k in range(k0, k0 + per_tile)], 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref,
                h_scr, g_scr, *, p: int):
    """Grid (B, chunks, head blocks), head blocks innermost. ``h_scr``
    [head blocks, W, N] f32 is every head's state, carried over the
    chunks; ``g_scr`` the chunk's C B^T."""
    ci, hb = pl.program_id(1), pl.program_id(2)
    per_tile = LANES // p
    dtype = x_ref.dtype
    hpb, q = cum_ref.shape
    n = b_ref.shape[1]

    @pl.when(ci == 0)
    def _first_chunk():
        h_scr[hb] = jnp.zeros(h_scr.shape[1:], h_scr.dtype)

    @pl.when(hb == 0)
    def _first_block():
        g_scr[...] = _group_scores(c_ref[...], b_ref[...])

    st_ref[...] = h_scr[hb]
    g = g_scr[...]
    bm, cm = b_ref[...], c_ref[...]
    cum, dt = cum_ref[...], dt_ref[...]
    cq = _last_entry(cum)
    cc = _down(cum, 1)
    into, carry, step = (_down(v, per_tile) for v in (
        jnp.exp(cum), jnp.exp(cq - cum) * dt, dt))
    last = jnp.exp(cq)
    first = None if per_tile == 1 else \
        jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1) < p
    for i in range(x_ref.shape[1] // LANES):
        lanes = pl.ds(i * LANES, LANES)
        xf = x_ref[:, lanes].astype(_F32)
        h0 = h_scr[hb, lanes, :]
        xd = _mul(xf, _tile(step, i)).astype(dtype)
        y = None
        for j in range(per_tile):
            k = i * per_tile + j
            decay = _decay(_tile(cc, k), jax.lax.slice(cum, (k, 0),
                                                       (k + 1, q)))
            part = dot(_mul(g, decay).astype(dtype), _only(xd, j, p, first),
                       AB)
            y = part if y is None else _add(y, part)
        y = _add(y, _mul(_tile(into, i), dot(cm, h0.astype(dtype), ABT)))
        xw = _mul(xf, _tile(carry, i)).astype(dtype)
        h_scr[hb, lanes, :] = _add(
            _mul(_last_rows(last, i * per_tile, per_tile, p, n), h0),
            dot(xw, bm, ATB))
        y_ref[:, lanes] = y.astype(y_ref.dtype)


def _specs(b, t, h, p, n, chunk, hpb, reverse: bool):
    """Block specs of the merged arrays on the grid (B, chunks, head
    blocks); ``reverse`` walks the chunks from the last to the first."""
    nc = t // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    w = hpb * p
    return {
        "x": pl.BlockSpec((None, chunk, w), lambda b, c, k: (b, at(c), k)),
        "dt": pl.BlockSpec((None, hpb, chunk),
                           lambda b, c, k: (b, k, at(c))),
        "bc": pl.BlockSpec((None, chunk, n), lambda b, c, k: (b, at(c), 0)),
        "state": pl.BlockSpec((None, None, w, n),
                              lambda b, c, k: (b, at(c), k, 0)),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES)


def _ssd_fwd(x, dt_t, cum_t, bm, cm, p, chunk, hpb):
    """x [B, T, H*P], dt and its cumulative sum [B, H, T] f32, B and C
    [B, T, N] -> (y [B, T, H*P], states [B, T/Q, H*P, N] f32: the state
    each chunk starts from)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hp = x.shape
    h, n, nc = hp // p, bm.shape[-1], t // chunk
    s = _specs(b, t, h, p, n, chunk, hpb, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(b, nc, h // hpb),
        in_specs=[s["x"], s["dt"], s["dt"], s["bc"], s["bc"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, hp, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // hpb, hpb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * (h * p * (chunk + 2 * n) + n * chunk),
            bytes_accessed=2 * x.size * x.dtype.itemsize + 4 * b * nc * hp * n,
            transcendentals=b * t * h * chunk),
    )(x, dt_t, cum_t, bm, cm)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _head_rows(vals, k0: int, hpb: int, p: int):
    """Sums over each head's P lanes of [Q, 128] float32 tiles, as ROWS:
    -> [hpb, Q] whose row k0 + j holds head j's sums and whose other rows
    are zero. ``vals`` is a list of (tile, swapped): a swapped tile gives
    its upper lanes to the first head (two heads a tile only).

    The tile is turned once by the XLU; a head's lanes are then sublanes,
    their sum is plain adds of registers, and it comes out with the tokens
    along the lanes, which is how d(dt) and d(cumulative sum) leave."""
    per_tile = LANES // p
    q = vals[0][0].shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, (hpb, q), 0)
    out = jnp.zeros((hpb, q), _F32)
    for v, swapped in vals:
        vt = jax.lax.transpose(v, (1, 0))
        for j in range(per_tile):
            jj = per_tile - 1 - j if swapped else j
            row = lane_sum(
                jax.lax.slice(vt, (jj * p, 0), ((jj + 1) * p, q)), 0)
            out = _add(out, jax.lax.select(
                _row_is(sub, k0 + j), spread(row, (hpb, q)),
                jax.lax.full_like(out, 0)))
    return out


def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, b_ref, c_ref, st_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                dh_scr, g_scr, dg_scr, *, p: int):
    """Grid (B, chunks from the last, head blocks). ``dh_scr`` is the
    gradient of the state the chunk ENDS in, carried back over the chunks;
    ``dg_scr`` the gradient of the chunk's C B^T, summed over its heads;
    dB and dC accumulate in their output block, which holds still over the
    head blocks. d(dt) here is through dt's own uses only; what reaches dt
    and A through the cumulative sum leaves as d(cumulative sum)."""
    ci, hb = pl.program_id(1), pl.program_id(2)
    per_tile = LANES // p
    dtype = x_ref.dtype
    hpb, q = cum_ref.shape
    n = b_ref.shape[1]

    @pl.when(ci == 0)
    def _last_chunk():
        dh_scr[hb] = jnp.zeros(dh_scr.shape[1:], dh_scr.dtype)

    @pl.when(hb == 0)
    def _first_block():
        g_scr[...] = _group_scores(c_ref[...], b_ref[...])
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    g = g_scr[...]
    bm, cm = b_ref[...], c_ref[...]
    cum, dt = cum_ref[...], dt_ref[...]
    cq = _last_entry(cum)
    cc = _down(cum, 1)
    into, carry, step = (_down(v, per_tile) for v in (
        jnp.exp(cum), jnp.exp(cq - cum), dt))
    last = jnp.exp(cq)
    sub = jax.lax.broadcasted_iota(jnp.int32, (hpb, q), 0)
    end = jax.lax.broadcasted_iota(jnp.int32, (hpb, q), 1) == q - 1
    first = None if per_tile == 1 else \
        jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1) < p
    zeros = jnp.zeros((hpb, q), _F32)
    # d(cumulative sum) and d(dt) of the block, the tokens along the lanes
    dcum = ddt = zeros
    dg = None
    for i in range(x_ref.shape[1] // LANES):
        lanes = pl.ds(i * LANES, LANES)
        dyt = dy_ref[:, lanes]
        xf, dyf = x_ref[:, lanes].astype(_F32), dyt.astype(_F32)
        h0, dh = st_ref[lanes, :], dh_scr[hb, lanes, :]
        ec, sw, dcl = _tile(into, i), _tile(carry, i), _tile(step, i)
        xd = _mul(xf, dcl).astype(dtype)
        dxd, across = None, []
        for j in range(per_tile):
            k = i * per_tile + j
            decay = _decay(_tile(cc, k), jax.lax.slice(cum, (k, 0),
                                                       (k + 1, q)))
            dyj = _only(dyt, j, p, first)
            # d(C B^T) of this head, unmasked
            u = _mul(dot(dyj, _only(xd, j, p, first), ABT), decay)
            dg = u if dg is None else _add(dg, u)
            w = _mul(u, g)                               # d(exponent) [t, s]
            # what row t gains is summed over the lanes below, with the
            # tile's other sums; what column s loses is a row already
            gain = _tile(w, 0)
            for m in range(1, q // LANES):
                gain = _add(gain, _tile(w, m))
            across.append(gain)
            dcum = _sub(dcum, jax.lax.select(
                _row_is(sub, k), spread(lane_sum(w, 0), (hpb, q)), zeros))
            part = dot(_mul(g, decay).astype(dtype), dyj, ATB)
            dxd = part if dxd is None else _add(dxd, part)
        eq = _last_rows(last, i * per_tile, per_tile, p, n)
        h0m, dhm = h0.astype(dtype), dh.astype(dtype)
        e = _mul(dyf, ec)
        em = e.astype(dtype)
        xw = _mul(_mul(xf, sw), dcl)
        dc_ref[...] += dot(em, h0m, AB)
        db_ref[...] += dot(xw.astype(dtype), dhm, AB)
        dxw = dot(bm, dhm, ABT)
        dh_scr[hb, lanes, :] = _add(dot(em, cm, ATB), _mul(eq, dh))
        dxs = _add(dxd, _mul(dxw, sw))
        dx_ref[:, lanes] = _mul(dxs, dcl).astype(dx_ref.dtype)
        written = _mul(dxw, xw)               # d(h_end) . (what s wrote)
        # dy . (what the state gave y) less what s wrote: d(c_t) of both
        local = _sub(_mul(e, dot(cm, h0m, ABT)), written)
        kept = _mul(_mul(dh, eq), h0)         # d(h_end) . (what was kept)
        if per_tile == 1:
            sums = [(_add(local, across[0]), False)]
        else:
            sums = [(_add(local, jax.lax.select(first, *across)), False),
                    (jax.lax.select(first, *across[::-1]), True)]
        dcum = _add(dcum, _head_rows(sums, i * per_tile, hpb, p))
        ddt = _add(ddt, _head_rows([(_mul(dxs, xf), False)], i * per_tile,
                                   hpb, p))
        wrote = lane_sum(written, 0)                               # [1, 128]
        for j in range(per_tile):
            k = i * per_tile + j
            dcq = _add(
                lane_sum(_only(wrote, j, p, None if first is None else
                               jax.lax.slice(first, (0, 0), (1, LANES))), 1),
                lane_sum(lane_sum(jax.lax.slice(
                    kept, (j * p, 0), ((j + 1) * p, n)), 0), 1))
            dcum = _add(dcum, jax.lax.select(
                _row_is(sub, k) & end, spread(dcq, (hpb, q)), zeros))
    dg_scr[...] += dg
    dcum_ref[...] = dcum
    ddt_ref[...] = ddt

    @pl.when(hb == pl.num_programs(2) - 1)
    def _last_block():
        rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        dgm = jnp.where(rows >= cols, dg_scr[...], 0.0).astype(dtype)
        dc_ref[...] += dot(dgm, bm, AB)
        db_ref[...] += dot(dgm, cm, ATB)


def _ssd_bwd(x, dy, dt_t, cum_t, bm, cm, states, p, chunk, hpb):
    """-> dx [B, T, H*P], d(dt) and d(cumulative sum) [B, H, T] f32, dB
    and dC [B, T, N] f32."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hp = x.shape
    h, n, nc = hp // p, bm.shape[-1], t // chunk
    s = _specs(b, t, h, p, n, chunk, hpb, reverse=True)
    rows = jax.ShapeDtypeStruct(dt_t.shape, jnp.float32)
    shared = jax.ShapeDtypeStruct(bm.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(b, nc, h // hpb),
        in_specs=[s["x"], s["x"], s["dt"], s["dt"], s["bc"], s["bc"],
                  s["state"]],
        out_specs=[s["x"], s["dt"], s["dt"], s["bc"], s["bc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), rows, rows,
                   shared, shared],
        scratch_shapes=[pltpu.VMEM((h // hpb, hpb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["bwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * (h * p * (2 * chunk + 5 * n) + 3 * n * chunk),
            bytes_accessed=3 * x.size * x.dtype.itemsize + 4 * b * nc * hp * n,
            transcendentals=b * t * h * chunk),
    )(x, dy, dt_t, cum_t, bm, cm, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd_kernels(x, dt_t, cum_t, bm, cm, p, chunk, hpb):
    return _ssd_fwd(x, dt_t, cum_t, bm, cm, p, chunk, hpb)[0]


def _ssd_vjp_fwd(x, dt_t, cum_t, bm, cm, p, chunk, hpb):
    y, states = _ssd_fwd(x, dt_t, cum_t, bm, cm, p, chunk, hpb)
    return y, (x, dt_t, cum_t, bm, cm, states)


def _ssd_vjp_bwd(p, chunk, hpb, res, dy):
    x, dt_t, cum_t, bm, cm, states = res
    dx, ddt, dcum, db, dc = _ssd_bwd(x, dy, dt_t, cum_t, bm, cm, states, p,
                                     chunk, hpb)
    return dx, ddt, dcum, db.astype(bm.dtype), dc.astype(cm.dtype)


_ssd_kernels.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


# ---------------------------------------------------------------------------
# the plain route
# ---------------------------------------------------------------------------


def _ssd_chunked(x, dt, a, bm, cm, chunk: int):
    """The chunked form in plain ``jnp`` for any shape: x [B, T, H, P], dt
    and a = dt A [B, T, H] f32, B and C [B, T, G, N] -> y [B, T, H, P].
    Holds [B, chunks, Q, Q, H] arrays: small shapes only."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    (x, dt, a, bm, cm), pad = pad_tokens((x, dt, a, bm, cm), chunk)
    nc = (t + pad) // chunk
    cut = lambda v: v.reshape((b, nc, chunk) + v.shape[2:])  # noqa: E731
    x, dt, a, bm, cm = map(cut, (x, dt, a, bm, cm))
    ein = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
    cum = jnp.cumsum(a, axis=2)                              # [b, c, Q, h]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    decay = jnp.where(seen, jnp.exp(jnp.minimum(
        cum[:, :, :, None] - cum[:, :, None], 0.0)), 0.0)    # [b,c,t,s,h]
    scores = jnp.repeat(ein("bctgn,bcsgn->bctsg", cm, bm), h // g, axis=-1)
    xd = (x.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    y = ein("bctsh,bcshp->bcthp", (scores * decay).astype(x.dtype), xd)
    xw = (xd.astype(jnp.float32) * jnp.exp(cum[:, :, -1:] - cum)[..., None]
          ).astype(x.dtype)
    heads = lambda v: jnp.repeat(v, h // g, axis=3)          # noqa: E731
    wrote = ein("bcshp,bcshn->bchpn", xw, heads(bm))         # [b,c,h,p,n]
    keep = jnp.exp(cum[:, :, -1])                            # [b, c, h]

    def chunk_step(state, c):
        kept, added = c
        return kept[..., None, None] * state + added, state

    _, starts = jax.lax.scan(
        chunk_step, jnp.zeros((b, h, p, n), jnp.float32),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(wrote, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                      # [b,c,h,p,n]
    y = y + jnp.exp(cum)[..., None] * ein(
        "bcthn,bchpn->bcthp", heads(cm), starts.astype(x.dtype))
    return y.reshape(b, nc * chunk, h, p)[:, :t].astype(x.dtype)


# ---------------------------------------------------------------------------
# the call
# ---------------------------------------------------------------------------


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int = 256) -> jax.Array:
    """Mamba-2's recurrence. x [batch, seq, heads, head_dim], dt [batch,
    seq, heads] (positive), A [heads] (negative), B and C [batch, seq,
    groups, state], D [heads] -> y of x's shape and dtype. Differentiable
    in all six. ``chunk`` is how the work is cut, not what is computed."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    chunk = min(chunk, t)
    dt = dt.astype(jnp.float32)
    a = dt * A.astype(jnp.float32)
    hpb = _heads_per_block(h, p)
    kernel = bool(hpb) and g == 1 and n % LANES == 0 \
        and chunk % LANES == 0 and t % chunk == 0
    route = "kernel" if kernel else "reference"
    record_path("rtpu.ops.ssd.path", PATH_COUNTS, route,
                {"chunk": chunk, "heads": h, "head_dim": p, "state": n,
                 "groups": g, "chunks": -(-t // chunk)})
    if kernel:
        cum = jnp.cumsum(a.reshape(b, t // chunk, chunk, h), axis=2)
        rows = lambda v: jnp.swapaxes(v.reshape(b, t, h), 1, 2)  # noqa: E731
        y = _ssd_kernels(x.reshape(b, t, h * p), rows(dt), rows(cum),
                         B.reshape(b, t, n), C.reshape(b, t, n), p, chunk,
                         hpb).reshape(b, t, h, p)
    else:
        y = _ssd_chunked(x, dt, a, B, C, chunk)
    skip = x.astype(jnp.float32) * D.astype(jnp.float32)[:, None]
    return (y.astype(jnp.float32) + skip).astype(x.dtype)
