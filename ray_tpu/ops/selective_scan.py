"""Mamba-1's selective scan for TPU: the recurrence walked in chunks with
the state in VMEM, forward and backward, as two Pallas kernels.

Per channel c of ``d_inner`` and state n of ``d_state`` (a decay of its own
for every pair and token, which is what sets it apart from Mamba-2's one
scalar a head, ``ops.ssd_scan``):

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]

x and dt [B, T, C] (dt positive: the step after its softplus), A [C, N]
(negative), B and C [B, T, N], D [C].

There is no matrix product in it: one row of 8192 tokens has 8192 x C x N
decays (2.7 GB in float32 at C = 5120, N = 16), so neither they nor the
states may reach HBM, and the work is the VPU's. The kernels hold the state
of a block of channels as ONE float32 value [N, W] (the states down the
sublanes, the channels along the lanes), walk a chunk of Q tokens one token
at a time (eight to a loop iteration, so that every load and store of a row
is one of an aligned [8, W] tile), and keep it from chunk to chunk in a
VMEM scratch, grid (batch, chunk, channel block), the chunks in order.

What a token brings to every channel, B_t[n] and C_t[n], has to stand down
the sublanes and be the same in every lane. The call hands the kernels B
and C spread over 128 lanes ([B, T, N, 128] float32, made by XLA: 67 MB
each at T = 8192), of which ``ref[t]`` is the aligned [N, 128] tile of
token t; a channel block reuses it for each of its lane tiles and, because
its block index does not depend on the channel block, it is fetched once a
chunk. The gradient of that spread array is what the backward writes
(summed over the channels that share a lane); XLA's transpose of the
broadcast sums the 128 lanes.

The forward also writes the state each chunk starts from ([B, T/Q, N, C]
float32). The backward walks the chunks from the last, makes a chunk's
states again from that start (kept in VMEM, [Q, N, W]), then walks its
tokens from the last carrying the gradient of the state; d(A) leaves as one
[N, C] a chunk and is summed outside.

The routes, chosen by what a call shows (``PATH_COUNTS``, the event
``rtpu.ops.selscan.path``; no argument or configuration selects one):

* ``kernel``: C a multiple of 128 and N a multiple of 8. T is padded to
  whole chunks with dt = 0 and x = 0, a step that neither decays nor writes
  the state.
* ``reference``: every other shape: ``selective_scan_reference``, a
  ``lax.scan`` over the tokens, differentiated by jax.

Precision: dt, every decay, the state and all sums are float32 whatever
x's dtype; x, B and C are widened where they are read; y leaves in x's
dtype.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_common
from .kernel_common import LANES, VMEM_BYTES, record_path

# The names of the two kernels, as a device trace and the compiled HLO
# show them (``name=`` on ``pl.pallas_call``). Part of the measurement:
# pinned in tests/test_tracing_names.py; the benchmark's
# ``selective_scan_roofline`` finds the kernels' time by them.
KERNEL_NAMES = {
    "fwd": "selscan_chunk_fwd",   # y and the state each chunk starts from
    "bwd": "selscan_chunk_bwd",   # dx, d(dt), d(A) a chunk, dB, dC
}

# Traced calls of selective_scan by the route each took ("kernel",
# "reference"); the same choice is the event ``rtpu.ops.selscan.path``.
PATH_COUNTS: collections.Counter = collections.Counter()

_STEPS = 8                    # tokens a loop iteration: one [8, W] tile
_MAX_BLOCK = 512              # channels a program works
_F32 = jnp.float32


def _block_width(channels: int) -> int:
    """Channels a program works: the widest multiple of 128 up to
    ``_MAX_BLOCK`` that divides them, or 0 where there is none."""
    for w in range(min(channels, _MAX_BLOCK) // LANES * LANES, 0, -LANES):
        if channels % w == 0:
            return w
    return 0


def _wide(tile, width: int):
    """One token's [N, 128] tile of B or C for every lane tile of a block
    [N, width]."""
    reps = width // LANES
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _fold(v):
    """[N, W] summed over its lane tiles -> [N, 128]."""
    out = v[:, :LANES]
    for i in range(1, v.shape[1] // LANES):
        out = out + v[:, i * LANES:(i + 1) * LANES]
    return out


def _over_states(v):
    """[N, W] summed over the states -> [1, W]."""
    return jnp.sum(v, axis=0, keepdims=True)


def _put_row(rows, j: int, row):
    """``rows`` [8, W] with row j replaced by ``row`` [1, W]: the eight
    rows of a loop iteration leave in one aligned store."""
    sub = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.where(sub == j, row, rows)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                h_scr, x_scr, y_scr):
    """Grid (B, chunks, channel blocks), channel blocks innermost.
    ``h_scr`` [blocks, N, W] f32 is every channel's state, carried over the
    chunks; ``x_scr`` the chunk's x widened once, ``y_scr`` its y before it
    is narrowed."""
    ci, kb = pl.program_id(1), pl.program_id(2)
    q, w = x_scr.shape

    @pl.when(ci == 0)
    def _first_chunk():
        h_scr[kb] = jnp.zeros(h_scr.shape[1:], _F32)

    h0 = h_scr[kb]
    st_ref[...] = h0
    x_scr[...] = x_ref[...].astype(_F32)
    a = a_ref[...]

    def eight(g, h):
        t0 = pl.multiple_of(g * _STEPS, _STEPS)
        dt8 = dt_ref[pl.ds(t0, _STEPS), :]
        x8 = x_scr[pl.ds(t0, _STEPS), :]
        ys = jnp.zeros((_STEPS, w), _F32)
        for j in range(_STEPS):
            dt_t = dt8[j:j + 1, :]
            h = jnp.exp(dt_t * a) * h \
                + (dt_t * x8[j:j + 1, :]) * _wide(b_ref[t0 + j], w)
            ys = _put_row(ys, j, _over_states(h * _wide(c_ref[t0 + j], w)))
        y_scr[pl.ds(t0, _STEPS), :] = ys
        return h

    h_scr[kb] = jax.lax.fori_loop(0, q // _STEPS, eight, h0)
    y_ref[...] = y_scr[...].astype(y_ref.dtype)


def _specs(t, n, chunk, w, reverse: bool):
    """Block specs on the grid (B, chunks, channel blocks); ``reverse``
    walks the chunks from the last to the first."""
    nc = t // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    return {
        "x": pl.BlockSpec((None, chunk, w), lambda b, c, k: (b, at(c), k)),
        "a": pl.BlockSpec((n, w), lambda b, c, k: (0, k)),
        "bc": pl.BlockSpec((None, chunk, n, LANES),
                           lambda b, c, k: (b, at(c), 0, 0)),
        "state": pl.BlockSpec((None, None, n, w),
                              lambda b, c, k: (b, at(c), 0, k)),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES)


def _scan_fwd(x, dt, a_t, bw, cw, chunk, w):
    """x [B, T, C], dt [B, T, C] f32, A^T [N, C] f32, B and C spread over
    the lanes [B, T, N, 128] f32 -> (y [B, T, C], states [B, T/Q, N, C]
    f32: the state each chunk starts from)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, ch = x.shape
    n, nc = a_t.shape[0], t // chunk
    s = _specs(t, n, chunk, w, reverse=False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, nc, ch // w),
        in_specs=[s["x"], s["x"], s["a"], s["bc"], s["bc"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, n, ch), _F32)],
        scratch_shapes=[pltpu.VMEM((ch // w, n, w), _F32),
                        pltpu.VMEM((chunk, w), _F32),
                        pltpu.VMEM((chunk, w), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=7 * b * t * ch * n,
            bytes_accessed=x.size * (2 * x.dtype.itemsize + 4)
            + 2 * bw.size * 4 + 4 * b * nc * n * ch,
            transcendentals=b * t * ch * n),
    )(x, dt, a_t, bw, cw)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, dy_ref, dt_ref, a_ref, b_ref, c_ref, st_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                dh_scr, hs_scr, x_scr, dy_scr, dx_scr):
    """Grid (B, chunks from the last, channel blocks). ``dh_scr`` is the
    gradient of the state the chunk ENDS in, carried back over the chunks;
    ``hs_scr`` [Q, N, W] the state BEFORE each token of the chunk, made
    again from the state the chunk starts from; dB and dC (spread over the
    lanes, as B and C come) accumulate in their output block, which holds
    still over the channel blocks."""
    ci, kb = pl.program_id(1), pl.program_id(2)
    q, w = x_scr.shape
    n = a_ref.shape[0]

    @pl.when(ci == 0)
    def _last_chunk():
        dh_scr[kb] = jnp.zeros(dh_scr.shape[1:], _F32)

    @pl.when(kb == 0)
    def _first_block():
        db_ref[...] = jnp.zeros(db_ref.shape, _F32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, _F32)

    x_scr[...] = x_ref[...].astype(_F32)
    dy_scr[...] = dy_ref[...].astype(_F32)
    a = a_ref[...]

    def again(g, h):
        t0 = pl.multiple_of(g * _STEPS, _STEPS)
        dt8 = dt_ref[pl.ds(t0, _STEPS), :]
        x8 = x_scr[pl.ds(t0, _STEPS), :]
        for j in range(_STEPS):
            hs_scr[t0 + j] = h
            dt_t = dt8[j:j + 1, :]
            h = jnp.exp(dt_t * a) * h \
                + (dt_t * x8[j:j + 1, :]) * _wide(b_ref[t0 + j], w)
        return h

    jax.lax.fori_loop(0, q // _STEPS, again, st_ref[...])

    def eight(i, carry):
        dh, da = carry
        t0 = pl.multiple_of((q // _STEPS - 1 - i) * _STEPS, _STEPS)
        dt8 = dt_ref[pl.ds(t0, _STEPS), :]
        x8 = x_scr[pl.ds(t0, _STEPS), :]
        dy8 = dy_scr[pl.ds(t0, _STEPS), :]
        dxs = jnp.zeros((_STEPS, w), _F32)
        ddts = jnp.zeros((_STEPS, w), _F32)
        for j in reversed(range(_STEPS)):
            dt_t, x_t, dy_t = (v[j:j + 1, :] for v in (dt8, x8, dy8))
            bt, ct = _wide(b_ref[t0 + j], w), _wide(c_ref[t0 + j], w)
            before = hs_scr[t0 + j]
            decay = jnp.exp(dt_t * a)
            wrote = dt_t * x_t
            h = decay * before + wrote * bt
            dh = dh + dy_t * ct                 # all of d(h_t)
            dc_ref[t0 + j] += _fold(dy_t * h)
            db_ref[t0 + j] += _fold(dh * wrote)
            dwrote = _over_states(dh * bt)      # d(dt_t x_t) [1, W]
            ddecay = dh * before * decay        # d(dt_t A) [N, W]
            da = da + ddecay * dt_t
            ddts = _put_row(ddts, j, _over_states(ddecay * a) + dwrote * x_t)
            dxs = _put_row(dxs, j, dwrote * dt_t)
            dh = decay * dh                     # d(h_{t-1}) through h_t
        dx_scr[pl.ds(t0, _STEPS), :] = dxs
        ddt_ref[pl.ds(t0, _STEPS), :] = ddts
        return dh, da

    dh, da = jax.lax.fori_loop(0, q // _STEPS, eight,
                               (dh_scr[kb], jnp.zeros((n, w), _F32)))
    dh_scr[kb] = dh
    da_ref[...] = da
    dx_ref[...] = dx_scr[...].astype(dx_ref.dtype)


def _scan_bwd(x, dy, dt, a_t, bw, cw, states, chunk, w):
    """-> dx [B, T, C], d(dt) [B, T, C] f32, d(A^T) a chunk [B, T/Q, N, C]
    f32, dB and dC spread over the lanes [B, T, N, 128] f32."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, ch = x.shape
    n, nc = a_t.shape[0], t // chunk
    s = _specs(t, n, chunk, w, reverse=True)
    spread = jax.ShapeDtypeStruct(bw.shape, _F32)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, nc, ch // w),
        in_specs=[s["x"], s["x"], s["x"], s["a"], s["bc"], s["bc"],
                  s["state"]],
        out_specs=[s["x"], s["x"], s["state"], s["bc"], s["bc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(states.shape, _F32), spread, spread],
        scratch_shapes=[pltpu.VMEM((ch // w, n, w), _F32),
                        pltpu.VMEM((chunk, n, w), _F32),
                        pltpu.VMEM((chunk, w), _F32),
                        pltpu.VMEM((chunk, w), _F32),
                        pltpu.VMEM((chunk, w), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["bwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=18 * b * t * ch * n,
            bytes_accessed=x.size * (3 * x.dtype.itemsize + 8)
            + 4 * bw.size * 4 + 8 * b * nc * n * ch,
            transcendentals=2 * b * t * ch * n),
    )(x, dy, dt, a_t, bw, cw, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(x, dt, a_t, bw, cw, chunk, w):
    return _scan_fwd(x, dt, a_t, bw, cw, chunk, w)[0]


def _scan_vjp_fwd(x, dt, a_t, bw, cw, chunk, w):
    from jax.ad_checkpoint import checkpoint_name

    y, states = _scan_fwd(x, dt, a_t, bw, cw, chunk, w)
    # Named, as the flash kernels' outputs are, so that a remat policy can
    # choose to SAVE them: the backward of a rematerialised layer then
    # makes the kernel's arguments again but does not run it again.
    y = checkpoint_name(y, "selscan_out")
    states = checkpoint_name(states, "selscan_states")
    return y, (x, dt, a_t, bw, cw, states)


def _scan_vjp_bwd(chunk, w, res, dy):
    x, dt, a_t, bw, cw, states = res
    dx, ddt, da, db, dc = _scan_bwd(x, dy, dt, a_t, bw, cw, states, chunk, w)
    return dx, ddt, da.sum((0, 1)), db, dc


_scan_kernels.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


# ---------------------------------------------------------------------------
# the plain route
# ---------------------------------------------------------------------------


def selective_scan_reference(x, dt, A, B, C, D):
    """The recurrence one token at a time, float32, in plain ``jnp``: what
    the kernels are held to, and the route of shapes they do not take."""
    a = A.astype(_F32)

    def token(h, tok):
        x_t, dt_t, b_t, c_t = tok           # [B, C] [B, C] [B, N] [B, N]
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    b, _, ch = x.shape
    xf = x.astype(_F32)
    _, y = jax.lax.scan(
        token, jnp.zeros((b, ch, A.shape[1]), _F32),
        tuple(jnp.moveaxis(v.astype(_F32), 1, 0) for v in (xf, dt, B, C)))
    return (jnp.moveaxis(y, 0, 1) + xf * D.astype(_F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# the call
# ---------------------------------------------------------------------------


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array, chunk: int = 128) -> jax.Array:
    """Mamba-1's recurrence. x and dt [batch, seq, channels] (dt positive),
    A [channels, state] (negative), B and C [batch, seq, state], D
    [channels] -> y of x's shape and dtype. Differentiable in all six.
    ``chunk`` is how the work is cut, not what is computed."""
    b, t, ch = x.shape
    n = A.shape[1]
    w = _block_width(ch) if n % 8 == 0 else 0
    route = "kernel" if w else "reference"
    chunk = -(-min(chunk, t) // _STEPS) * _STEPS     # whole loop iterations
    record_path("rtpu.ops.selscan.path", PATH_COUNTS, route,
                {"chunk": chunk, "channels": ch, "state": n,
                 "block_channels": w, "chunks": -(-t // chunk),
                 "steps_per_iteration": _STEPS})
    if not w:
        return selective_scan_reference(x, dt, A, B, C, D)
    dt = dt.astype(_F32)
    # not ``kernel_common.pad_tokens``: it pads nothing where T is whole
    # chunks, these pads are traced all the same, and phi4flash's compiled
    # step numbers its instructions after them (ROADMAP C26(d))
    pad = -t % chunk
    rows = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
    spread = lambda v: jnp.broadcast_to(                      # noqa: E731
        rows(v.astype(_F32))[..., None], (b, t + pad, n, LANES))
    y = _scan_kernels(rows(x), rows(dt), A.astype(_F32).T, spread(B),
                      spread(C), chunk, w)[:, :t]
    skip = x.astype(_F32) * D.astype(_F32)
    return (y.astype(_F32) + skip).astype(x.dtype)
