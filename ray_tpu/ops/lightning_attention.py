"""Lightning Attention's recurrence for TPU: linear attention whose every
head has its OWN q and k and a decay that is a CONSTANT of the head, as a
chunked scan with its backward, two Pallas kernels.

Per head (state S [N, P] float32 from zero, lambda = exp(a), a < 0 one
number a head, no parameter):

    S_t = lambda S_{t-1} + k_t (outer) v_t
    o_t = scale * S_t^T q_t

q, k [B, T, H, N], v [B, T, H, P], a = log lambda [H]. It is the
state-space form of ``ssd_scan`` with dt = 1, A = a, B = k, C = q, D = 0 and
AS MANY GROUPS AS HEADS, the one shape that file's kernel pair does not
take: its pair makes C B^T once a chunk for all heads, carries dt, its
cumulative sums and their gradients as rows a head, and sums dB and dC over
a block of heads. None of that exists here: the scores q k^T are a head's
own [Q, Q] product, dq and dk leave a head, and the decay is a table of the
head that no gradient reaches. So this is a pair of its own, a third of that
one's length, and ``ssd_scan``'s stays what it was (PERF.md, PR 69).

Chunked form. For rows t and columns s of one chunk of Q tokens and the
state S_0 the chunk starts from:

    o_t   = scale (sum_{s<=t} (q_t.k_s) lambda^(t-s) v_s
                   + lambda^(t+1) S_0^T q_t)
    S_end = lambda^Q S_0 + sum_s lambda^(Q-1-s) k_s (outer) v_s

Every power of lambda has an exponent >= 0 (t >= s inside a chunk), so it is
<= 1: nothing overflows however fast the head forgets (the published
fastest head decays by e^-215 over a chunk of 256; a factorised
lambda^t lambda^-s would overflow). The powers are FOUR TABLES of the head
(``_tables``: [Q, Q] under the diagonal, two [Q] columns spread over a
tile's lanes, lambda^Q), made by plain ``jnp`` outside the kernels from the
[H] decays, which may be traced (a layer's own constant inside a scanned run
of layers): the kernels hold no ``exp`` at all.

The routes, chosen by what a call shows (``PATH_COUNTS``, the event
``rtpu.ops.lightning.path``; no argument or configuration selects one):

* ``kernel``: heads of 128 on a state of 128, a chunk that is a multiple of
  128 and divides T. Grid (batch, head, chunk), the chunks in order; a
  program is one chunk of one head: its q, k, v tiles [Q, 128] out of the
  model's merged [B, T, H * 128] arrays, the head's state [128, 128] float32
  in a VMEM scratch from chunk to chunk. The forward also writes the state
  each chunk starts from ([B, H, T / Q, 128, 128] float32); the backward
  walks the chunks in reverse and carries the state's gradient in the same
  kind of scratch. Forward: 2 [Q, Q] products and 2 with the state;
  backward: 5 and 4.
* ``reference``: every other shape: the same chunked form in plain ``jnp``
  under one ``lax.scan`` over the chunks, differentiated by jax; T is padded
  to whole chunks with zeros (a zero key writes nothing).

Precision: matrix products take their operands in q's dtype (bf16 in a
model) and accumulate in float32; the tables and the state are float32
throughout.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_common
from .kernel_common import (AB, ABT, ATB, LANES, VMEM_BYTES, dot, pad_tokens,
                            record_path, spread)

# The names of the two kernels, as a device trace and the compiled HLO show
# them. Part of the measurement: pinned in tests/test_tracing_names.py; the
# benchmark's ``lightning_scan_roofline`` finds the kernels' time by them.
KERNEL_NAMES = {
    "fwd": "lightning_chunk_fwd",   # o and the state each chunk starts from
    "bwd": "lightning_chunk_bwd",   # dq, dk, dv
}

# Traced calls by the route each took ("kernel", "reference"); the same
# choice is the flight-recorder event ``rtpu.ops.lightning.path``.
PATH_COUNTS: collections.Counter = collections.Counter()

_F32 = jnp.float32


def _tables(log_decay, chunk: int, scale: float, lanes: int):
    """log lambda [H] f32 -> the powers a chunk needs, float32:
    ``local`` [H, Q, Q] scale lambda^(t-s) where t >= s, else 0;
    ``into`` [H, Q, lanes] scale lambda^(t+1) (what the state gives row t);
    ``out`` [H, Q, lanes] lambda^(Q-1-s) (what row s leaves in the state);
    ``keep`` [H, 1, lanes] lambda^Q. Every exponent is >= 0 times a < 0."""
    a = log_decay.astype(_F32)
    idx = jnp.arange(chunk, dtype=_F32)
    ahead = idx[:, None] - idx[None, :]
    local = jnp.where(ahead >= 0,
                      jnp.exp(a[:, None, None] * jnp.maximum(ahead, 0.0)),
                      0.0) * scale
    over = lambda x: jnp.broadcast_to(                         # noqa: E731
        x[..., None], x.shape + (lanes,))
    into = over(jnp.exp(a[:, None] * (idx + 1.0)) * scale)
    out = over(jnp.exp(a[:, None] * (chunk - 1.0 - idx)))
    keep = over(jnp.exp(a * chunk)[:, None])
    return local, into, out, keep


# ---------------------------------------------------------------------------
# the kernels: q, k, v, o and the gradients [B, T, H * 128]; grid (B, H, T/Q)
# ---------------------------------------------------------------------------


def _weighted(x, w, dtype):
    """x [Q, 128] times the float32 column table w, back in ``dtype``."""
    return (x.astype(_F32) * w).astype(dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, local_ref, into_ref, out_ref, keep_ref,
                o_ref, st_ref, s_scr):
    """``s_scr`` [N, P] f32 is the head's state, carried over the chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_scr[...] = jnp.zeros_like(s_scr)

    dtype = q_ref.dtype
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    s0 = s_scr[...]
    st_ref[...] = s0
    scores = (dot(q, k, ABT) * local_ref[...]).astype(dtype)
    o = dot(scores, v, AB) + into_ref[...] * dot(q, s0.astype(dtype), AB)
    s_scr[...] = spread(keep_ref[...], s0.shape) * s0 \
        + dot(_weighted(k, out_ref[...], dtype), v, ATB)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, st_ref, local_ref, into_ref,
                out_ref, keep_ref, dq_ref, dk_ref, dv_ref, ds_scr):
    """Chunks from the last. ``ds_scr`` is the gradient of the state the
    chunk ENDS in, carried back over the chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    dtype = q_ref.dtype
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    local, into, out = local_ref[...], into_ref[...], out_ref[...]
    s0, ds = st_ref[...].astype(dtype), ds_scr[...]
    dsm = ds.astype(dtype)
    scores = (dot(q, k, ABT) * local).astype(dtype)            # [t, s]
    dscores = (dot(do, v, ABT) * local).astype(dtype)          # [t, s]
    dq = dot(dscores, k, AB) + dot(_weighted(do, into, dtype), s0, ABT)
    dk = dot(dscores, q, ATB) + dot(_weighted(v, out, dtype), dsm, ABT)
    dv = dot(scores, do, ATB) + dot(_weighted(k, out, dtype), dsm, AB)
    ds_scr[...] = spread(keep_ref[...], ds.shape) * ds \
        + dot(_weighted(q, into, dtype), do, ATB)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _specs(t: int, d: int, chunk: int, reverse: bool):
    """Block specs on the grid (B, heads, chunks); ``reverse`` walks the
    chunks from the last to the first."""
    nc = t // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    table = lambda rows: pl.BlockSpec(                         # noqa: E731
        (None, rows, d), lambda b, h, c: (h, 0, 0))
    return {
        "x": pl.BlockSpec((None, chunk, d), lambda b, h, c: (b, at(c), h)),
        "state": pl.BlockSpec((None, None, None, d, d),
                              lambda b, h, c: (b, h, at(c), 0, 0)),
        "local": pl.BlockSpec((None, chunk, chunk),
                              lambda b, h, c: (h, 0, 0)),
        "column": table(chunk), "keep": table(1),
    }


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES)


def _lightning_fwd(q, k, v, tables, heads: int, chunk: int):
    """-> (o [B, T, H * 128], states [B, H, T / Q, 128, 128] f32: the state
    each chunk starts from)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = q.shape
    d, nc = hd // heads, t // chunk
    s = _specs(t, d, chunk, reverse=False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, heads, nc),
        in_specs=[s["x"], s["x"], s["x"], s["local"], s["column"],
                  s["column"], s["keep"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, nc, d, d), _F32)],
        scratch_shapes=[pltpu.VMEM((d, d), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * hd * (2 * chunk + 2 * d),
            bytes_accessed=4 * q.size * q.dtype.itemsize
            + 4 * b * heads * nc * d * d,
            transcendentals=0),
    )(q, k, v, *tables)


def _lightning_bwd(q, k, v, do, states, tables, heads: int, chunk: int):
    """-> dq, dk, dv [B, T, H * 128]."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = q.shape
    d, nc = hd // heads, t // chunk
    s = _specs(t, d, chunk, reverse=True)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, heads, nc),
        in_specs=[s["x"], s["x"], s["x"], s["x"], s["state"], s["local"],
                  s["column"], s["column"], s["keep"]],
        out_specs=[s["x"], s["x"], s["x"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((d, d), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["bwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * hd * (5 * chunk + 4 * d),
            bytes_accessed=7 * q.size * q.dtype.itemsize
            + 4 * b * heads * nc * d * d,
            transcendentals=0),
    )(q, k, v, do, states, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lightning_kernels(q, k, v, log_decay, heads, chunk, scale):
    tables = _tables(log_decay, chunk, scale, LANES)
    return _lightning_fwd(q, k, v, tables, heads, chunk)[0]


def _lightning_vjp_fwd(q, k, v, log_decay, heads, chunk, scale):
    tables = _tables(log_decay, chunk, scale, LANES)
    o, states = _lightning_fwd(q, k, v, tables, heads, chunk)
    return o, (q, k, v, log_decay, states)


def _lightning_vjp_bwd(heads, chunk, scale, res, do):
    q, k, v, log_decay, states = res
    tables = _tables(log_decay, chunk, scale, LANES)
    return _lightning_bwd(q, k, v, do, states, tables, heads, chunk) \
        + (jnp.zeros_like(log_decay),)        # a constant: no gradient


_lightning_kernels.defvjp(_lightning_vjp_fwd, _lightning_vjp_bwd)


# ---------------------------------------------------------------------------
# the plain route
# ---------------------------------------------------------------------------


def _lightning_chunked(q, k, v, log_decay, chunk: int, scale: float):
    """The chunked form in plain ``jnp`` for any shape: q, k [B, T, H, N],
    v [B, T, H, P] -> o [B, T, H, P]. Holds [B, chunks, H, Q, Q] arrays:
    small shapes only."""
    b, t, h, _ = q.shape
    (q, k, v), pad = pad_tokens((q, k, v), chunk)
    nc = (t + pad) // chunk
    cut = lambda x: x.reshape((b, nc, chunk) + x.shape[2:])    # noqa: E731
    q, k, v = map(cut, (q, k, v))
    local, into, out, keep = _tables(log_decay, chunk, scale, 1)
    rows = lambda x: jnp.swapaxes(x, 0, 1)[None, None]         # noqa: E731
    ein = functools.partial(jnp.einsum, preferred_element_type=_F32)
    scores = ein("bcthn,bcshn->bchts", q, k) * local
    o = ein("bchts,bcshp->bcthp", scores.astype(q.dtype), v)
    kw = (k.astype(_F32) * rows(out)).astype(q.dtype)
    wrote = ein("bcshn,bcshp->bchnp", kw, v)

    def chunk_step(state, added):
        return keep[None] * state + added, state

    _, starts = jax.lax.scan(
        chunk_step, jnp.zeros((b, h) + wrote.shape[-2:], _F32),
        jnp.moveaxis(wrote, 1, 0))
    starts = jnp.moveaxis(starts, 0, 1)                       # [b,c,h,n,p]
    o = o + rows(into) * ein(
        "bcthn,bchnp->bcthp", q, starts.astype(q.dtype))
    return o.reshape((b, nc * chunk) + o.shape[3:])[:, :t].astype(v.dtype)


# ---------------------------------------------------------------------------
# the call
# ---------------------------------------------------------------------------


def lightning_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        log_decay: jax.Array, *, scale: float,
                        chunk: int = 256) -> jax.Array:
    """q, k [batch, seq, heads, state], v [batch, seq, heads, head_dim],
    ``log_decay`` [heads] (negative: the log of each head's constant decay;
    no gradient reaches it) -> o of v's shape and dtype: the recurrence of
    the module docstring. Differentiable in q, k and v. ``chunk`` is how the
    work is cut, not what is computed."""
    b, t, h, n = q.shape
    p = v.shape[-1]
    chunk = min(chunk, t)
    log_decay = jax.lax.stop_gradient(log_decay.astype(_F32))
    kernel = n == LANES and p == LANES and chunk % LANES == 0 \
        and t % chunk == 0
    route = "kernel" if kernel else "reference"
    record_path("rtpu.ops.lightning.path", PATH_COUNTS, route,
                {"chunk": chunk, "heads": h, "groups": h, "head_dim": p,
                 "state": n, "decay": "constant",
                 "chunks": -(-t // chunk)})
    if kernel:
        merged = lambda x: x.reshape(b, t, h * LANES)          # noqa: E731
        return _lightning_kernels(merged(q), merged(k), merged(v), log_decay,
                                  h, chunk, float(scale)).reshape(v.shape)
    return _lightning_chunked(q, k, v, log_decay, chunk, float(scale))
