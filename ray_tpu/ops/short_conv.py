"""A double-gated short convolution as a token mixer of its own (LFM2's
``conv`` operator): over [B, T, D], per channel and causal,

    z = b * x
    conv[t] = sum_k w[k] z[t - (K - 1) + k]        zeros before the row, NO
                                                   activation, tap K - 1 on
                                                   the token itself
    y = c * conv

``in_proj_short_conv(bcx, w)`` takes the in-projection's ONE array
[B, T, 3 D] (the chunks B | C | x in that order, a conv operator's
``u W_in``), has a gradient written by hand and two routes that compute the
same function:

* ``plain``: ``ops.layers.causal_conv1d`` between two products, XLA's
  fusions. Every shifted slice of the padded z is a read of the whole array
  (``layers.py``, ``_conv_silu_bwd``), and z is written once to be read K
  times;
* ``kernel``: a Pallas pair that reads b, c, x (and dy) ONCE a block of
  rows and channels, with a HALO: the ``taps - 1`` rows before the block
  (after it, for the backward's dy and c) come from one more small block of
  the same arrays, ``_halo_rows`` rows of them, so no program depends on
  another and z never leaves VMEM. The forward keeps nothing: the backward
  makes z and the convolution again from b, c, x and gives db, dc, dx and
  the float32 dw [K, D], summed over rows and batch inside the call.

The kernels index the chunks as column blocks of the one array and the
backward writes ONE cotangent [B, T, 3 D], so no chunk is copied out to
feed a kernel (an operand of a custom call is an array of its own: fed
three slices, the cell's ``train_conv_ms`` read 11.07 against the plain
route's 8.64 and this entry's 6.26) and no three padded cotangents are
added up behind it.

Inside the kernels everything between the loads and the stores is float32
(the plain route rounds z and the convolution to the inputs' dtype, as its
two products and ``causal_conv1d`` do), so the routes agree to the inputs'
rounding, not to the bit. The kernel takes a shape whose rows are whole
halos and whose channels are whole lanes; anything else takes the plain
route. Which route a traced call took is the flight-recorder event
``rtpu.ops.short_conv`` (tokens, channels, taps, route), once a traced
call, and ``PATH_COUNTS``.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_common
from .kernel_common import LANES, VMEM_BYTES
from .layers import causal_conv1d

# Names of the two Pallas calls as a device trace shows them; part of the
# measurement (tests/test_tracing_names.py).
KERNEL_NAMES = {"fwd": "short_conv_fwd", "bwd": "short_conv_bwd"}

# Traced calls by route.
PATH_COUNTS: collections.Counter = collections.Counter()

# Rows and channels of a block of the forward, and rows of a block of the
# backward, whose programs hold the whole width of a chunk (a block of the
# ONE cotangent is one place of one array). scripts/short_conv_chip.py times
# the cuts at the cell's shape.
BLOCK_T = 512
BLOCK_D = 512
BWD_BLOCK_T = 256


def _halo_rows(dtype) -> int:
    """Rows of the smallest block of ``dtype`` along the sublanes: what a
    halo is read as (8 rows of 32 bits, 16 of 16)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _fit(block: int, size: int, unit: int) -> int:
    """The largest multiple of ``unit`` that is <= block and divides size
    (size is a multiple of unit)."""
    for b in range(min(block, size) // unit * unit, unit, -unit):
        if size % b == 0:
            return b
    return unit


def kernel_takes(shape, dtype, taps: int) -> bool:
    """Whether the Pallas pair takes a [B, T, D] call: rows whole halos,
    channels whole lanes, the taps inside one halo."""
    _, t, d = shape
    halo = _halo_rows(dtype)
    return t % halo == 0 and d % LANES == 0 and 1 < taps <= halo + 1


# ---------------------------------------------------------------------------
# the plain route
# ---------------------------------------------------------------------------


def _plain_fwd(bcx, w):
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * causal_conv1d(b * x, w)


def _plain_bwd(bcx, w, dy):
    """The transposed convolution in ``causal_conv1d``'s own form (the
    cotangent padded at the END, K shifted slices each widened where it is
    used), the float32 sums for dw from the same z."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    taps, t = w.shape[0], x.shape[1]
    wf = w.astype(jnp.float32)
    z = b * x
    dc = dy * causal_conv1d(z, w)
    g = dy * c
    gp = jnp.pad(g, ((0, 0), (0, taps - 1), (0, 0)))
    dz = sum(gp[:, taps - 1 - k:taps - 1 - k + t].astype(jnp.float32) * wf[k]
             for k in range(taps)).astype(x.dtype)
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    gf = g.astype(jnp.float32)
    dw = jnp.stack([jnp.sum(zp[:, k:k + t].astype(jnp.float32) * gf,
                            axis=(0, 1)) for k in range(taps)])
    return (jnp.concatenate([dz * x, dc, dz * b], axis=-1),
            dw.astype(w.dtype))


# ---------------------------------------------------------------------------
# the kernel route
# ---------------------------------------------------------------------------


def _rows_off(v, halo, n: int):
    """v[t - n] over a block's rows [bt, bd]. n > 0: the block rolled down
    by n, its first n rows the last n of ``halo`` (the rows before the
    block); n < 0: v[t + |n|], rolled up, its last rows the first of
    ``halo`` (the rows after it)."""
    from jax.experimental.pallas import tpu as pltpu

    bt, h = v.shape[0], halo.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    out = pltpu.roll(v, n % bt, 0)
    for r in range(abs(n)):
        at, src = (r, h - n + r) if n > 0 else (bt + n + r, r)
        out = jnp.where(row == at, halo[src:src + 1, :], out)
    return out


def _f32(ref):
    return ref[...].astype(jnp.float32)


def _z_and_conv(b, x, hb_ref, hx_ref, w, first):
    """-> (z, the convolution) of a block, float32; ``first``: the block
    starts its row (zeros before it)."""
    taps = w.shape[0]
    z = b * x
    zh = jnp.where(first, 0.0, _f32(hb_ref) * _f32(hx_ref))
    conv = w[taps - 1:taps, :] * z
    for n in range(1, taps):
        conv = conv + w[taps - 1 - n:taps - n, :] * _rows_off(z, zh, n)
    return z, conv


def _fwd_kernel(b_ref, c_ref, x_ref, hb_ref, hx_ref, w_ref, y_ref):
    _, conv = _z_and_conv(_f32(b_ref), _f32(x_ref), hb_ref, hx_ref,
                          _f32(w_ref), pl.program_id(2) == 0)
    y_ref[...] = (_f32(c_ref) * conv).astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, x_ref, dy_ref, hb_ref, hx_ref, hc_ref, hdy_ref,
                w_ref, dbcx_ref, dw_ref):
    """``dbcx_ref``: a block [bt, 3 D] of the ONE cotangent of b | c | x."""
    ti = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (ti == 0))
    def _first_of_these_channels():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = _f32(w_ref)
    taps = w.shape[0]
    b, x, dy = _f32(b_ref), _f32(x_ref), _f32(dy_ref)
    z, conv = _z_and_conv(b, x, hb_ref, hx_ref, w, ti == 0)
    dc = dy * conv
    # g = the convolution's cotangent; z[t] fed conv[t + n] by w[K - 1 - n]
    g = dy * _f32(c_ref)
    gh = jnp.where(ti == pl.num_programs(2) - 1, 0.0,
                   _f32(hdy_ref) * _f32(hc_ref))
    dz = w[taps - 1:taps, :] * g
    dw_ref[taps - 1:taps, :] += jnp.sum(z * g, axis=0, keepdims=True)
    for n in range(1, taps):
        later = _rows_off(g, gh, -n)
        dz = dz + w[taps - 1 - n:taps - n, :] * later
        dw_ref[taps - 1 - n:taps - n, :] += jnp.sum(z * later, axis=0,
                                                    keepdims=True)
    d = b.shape[1]
    for k, v in enumerate((dz * x, dc, dz * b)):
        dbcx_ref[:, k * d:(k + 1) * d] = v.astype(dbcx_ref.dtype)


def _specs(shape, dtype, block_t: int, block_d: int):
    """(B, T, D), D the width of ONE chunk -> (grid, the specs of a block of
    b, c and x, of the halo before a block of each, of the halo after it,
    the spec of a block of a [B, T, D] array, of the halo after it, a
    block's channels). The grid is (channel blocks, batch, row blocks), so
    that dw's block stays where it is while its sums come in. b, c and x
    are the column blocks j, j + D / bd and j + 2 D / bd of the one array
    [B, T, 3 D]."""
    bsz, t, d = shape
    halo = _halo_rows(dtype)
    bt, bd = _fit(block_t, t, halo), _fit(block_d, d, LANES)
    per, last, chunk = bt // halo, t // halo - 1, d // bd

    def rows(kind, k):
        row = {"block": lambda i: i,
               "before": lambda i: jnp.maximum(i * per - 1, 0),
               "after": lambda i: jnp.minimum((i + 1) * per, last)}[kind]
        return pl.BlockSpec((None, bt if kind == "block" else halo, bd),
                            lambda j, b, i: (b, row(i), j + k * chunk))

    of = lambda kind: [rows(kind, k) for k in range(3)]      # noqa: E731
    return ((d // bd, bsz, t // bt), of("block"), of("before"), of("after"),
            rows("block", 0), rows("after", 0), bd)


def _taps_spec(taps: int, bd: int):
    return pl.BlockSpec((taps, bd), lambda j, b, i: (0, j))


def _params(semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def _kernel_fwd(bcx, w):
    shape = bcx.shape[:2] + w.shape[1:]
    grid, (sb, sc, sx), (hb, _, hx), _, out, _, bd = _specs(
        shape, bcx.dtype, BLOCK_T, BLOCK_D)
    return pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[sb, sc, sx, hb, hx, _taps_spec(w.shape[0], bd)],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct(shape, bcx.dtype),
        compiler_params=_params(("parallel", "parallel", "parallel")),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
    )(bcx, bcx, bcx, bcx, bcx, w)


def _kernel_bwd(bcx, w, dy):
    """-> (the cotangent of bcx, dw)."""
    grid, (sb, sc, sx), (hb, _, hx), (_, hc, _), one, hdy, bd = _specs(
        dy.shape, bcx.dtype, BWD_BLOCK_T, dy.shape[2])
    taps = _taps_spec(w.shape[0], bd)
    dbcx, dw = pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[sb, sc, sx, one, hb, hx, hc, hdy, taps],
        out_specs=[pl.BlockSpec((None, one.block_shape[1], 3 * bd),
                                lambda j, b, i: (b, i, 0)), taps],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        compiler_params=_params(("parallel", "arbitrary", "arbitrary")),
        name=KERNEL_NAMES["bwd"],
        interpret=kernel_common.use_interpret(),
    )(bcx, bcx, bcx, dy, bcx, bcx, bcx, dy, w)
    return dbcx, dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# the call
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(bcx, w, route):
    return _kernel_fwd(bcx, w) if route == "kernel" else _plain_fwd(bcx, w)


def _conv_fwd(bcx, w, route):
    # nothing is kept that the backward can make from b, c, x
    return _conv(bcx, w, route), (bcx, w)


def _conv_bwd(route, res, dy):
    return (_kernel_bwd if route == "kernel" else _plain_bwd)(*res, dy)


_conv.defvjp(_conv_fwd, _conv_bwd)


def _routed(bcx, w, route: Optional[str]):
    """``in_proj_short_conv`` by a route asked for (``kernel`` | ``plain``;
    None: the kernel where it takes the shape): the tests' and
    scripts/short_conv_chip.py's way to either route."""
    if route not in (None, "kernel", "plain"):
        raise ValueError(f"route is kernel or plain, got {route!r}")
    if bcx.ndim != 3 or w.ndim != 2 or bcx.shape[-1] != 3 * w.shape[1]:
        raise ValueError(f"bcx {bcx.shape} is not [B, T, three chunks of "
                         f"w's {w.shape[1:]} channels]")
    shape = bcx.shape[:2] + w.shape[1:]
    takes = kernel_takes(shape, bcx.dtype, w.shape[0])
    if route == "kernel" and not takes:
        raise ValueError(f"the kernel does not take {shape} {bcx.dtype} "
                         f"with {w.shape[0]} taps")
    route = route or ("kernel" if takes else "plain")
    kernel_common.record_path(
        "rtpu.ops.short_conv", PATH_COUNTS, route,
        {"tokens": shape[0] * shape[1], "channels": shape[2],
         "taps": w.shape[0]})
    return _conv(bcx, w, route)


def in_proj_short_conv(bcx: jax.Array, w: jax.Array) -> jax.Array:
    """bcx [B, T, 3 D], the chunks b | c | x in that order (a conv
    operator's in-projection's output), w [K, D] (tap K - 1 on the token
    itself: ``causal_conv1d``'s layout) -> ``c * conv_K(b * x)`` [B, T, D],
    by the kernel where it takes the shape (``kernel_takes``)."""
    return _routed(bcx, w, None)
