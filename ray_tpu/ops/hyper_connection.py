"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections arXiv:2409.19606): a layer's residual is n streams
X ∈ R^{n×d} a token, and around every sublayer F three maps made from the
token's own row u = vec(X) (stream-major, n·d wide) say how the streams
are read, written and mixed:

    ũ = g ⊙ u / sqrt(mean(u²) + rms_eps);  [p | q | r] = ũ Φ   (n, n, n² wide)
    H_pre  = σ(α_pre p + b_pre)             H_post = 2 σ(α_post q + b_post)
    A = clip(α_res r + b_res, lo, hi) as n × n;  M = exp(A)
    iters times: M ← M / (colsum M + eps); M ← M / (rowsum M + eps);  H_res = M
    z = Σ_j H_pre[j] X[j];  y = F(RMSNorm(z));  X'[i] = Σ_j H_res[i, j] X[j] + H_post[i] y

Layout. X is a tuple of its n streams, each an ordinary activation
[..., d] (stacked, the n mixed streams a sublayer writes would be copied
once more into the stack: 235 MB read and written again a sublayer at
8192 tokens of d 3584), and every coefficient is tokens-minor: ``H_pre``,
``H_post`` [n, tokens], ``H_res`` [n, n, tokens], float32. Written
[tokens, n, n] the 16 values a token would be padded to an (8, 128) tile,
64 times their bytes, through 2 x iters normalisations and their backward.
X stays in the compute dtype; the statistic, the coefficients, the
Sinkhorn iterations and the sums over streams are float32, and ũΦ runs on
the MXU (operands in X's dtype, float32 sums). Everything runs under the
scope ``mhc``.

Route (``hc_mix``, the one entry a model calls; chosen from the input's
shape alone, told by the event ``rtpu.ops.hyper_connection`` and counted in
``ROUTE_COUNTS``): the Pallas kernel pair with a backward of its own
(``KERNEL_NAMES``) where d is a multiple of 128, the tokens a multiple of
``TOKEN_TILE``, n at most 8 and a program's tiles fit its VMEM (streams
of d 3584 do in bfloat16), on any backend (interpreted off the chip);
the plain ``jax.numpy`` form under autodiff (``hc_coefficients``,
``hc_pre``, ``hc_post``) otherwise. One algorithm either way.

The kernels walk the tokens in tiles of ``TOKEN_TILE`` (128: the fourth
kernel's 3 n + 1 stream tiles in two buffers each and its float32 scratch
come to 39 MB of VMEM at d 3584; 256 is refused), and within a tile in
blocks of ``_ROWS`` tokens by whole rows of d, so that nothing of a
tile's size is ever held in float32. Φ with the gain folded in stays in
VMEM, transposed and its 2n + n² rows spread to groups of 8
([n, 8 (2 + n), d]), so that ũΦ comes out tokens-minor with every group
of coefficients on whole (8, 128) tiles; a token's coefficients reach the
tokens-major streams, and the reductions over d come back, by turning one
[tile, 128] float32 tile on the XLU. In units of one stream's activation
[tokens, d]:

* ``mhc_pre_fwd`` reads the n streams once: Σu², ũΦ (MXU), sigmoids, clip,
  exp, the Sinkhorn iterations on [n][8, tile] in registers, z. Writes z
  and the coefficients (float32, tokens-minor) with the raw ũΦ and the
  inverse RMS the backward starts from.
* ``mhc_post_fwd`` reads the n streams, y and the coefficients, writes the
  n mixed streams: 3 n + 2 units a sublayer with the first.
* ``mhc_post_bwd`` reads X, dX', y: writes dy and the per-token dH_post
  and dH_res as multiply-reduces over d of tiles that stay tokens-major.
* ``mhc_pre_bwd`` reads X, dX', dz: dH_pre, the Sinkhorn iterations made
  again (kept in VMEM) and walked back, clip, sigmoids, d(raw); dΦ summed
  over the tiles in float32 in VMEM (MXU), and dX (mixing, ũΦ and RMS
  terms) written once, in dX''s place: 5 n + 3 units with the third. The
  gradients of ``gain``, ``phi``, ``bias`` and ``alpha`` follow outside
  from dΦ and d(logits).

The second kernel's cotangent for the streams is dX' itself, handed on to
the fourth through the first's pass-through output: the pair is one
function cut in two by the sublayer between, and is private to ``hc_mix``.

Measured alone on a v5e, one sublayer at 8192 tokens of 4 x 3584 in
bfloat16 (PERF.md, PR 47; ``benchmark/scratch/mhc_kernel_chip.py``; the
plain form in brackets): forward 1.15 ms (1.61), 712 GB/s of the 0.82 GB
it needs; forward + backward with X' made 3.92 ms (6.96), 554 GB/s of
2.17 GB, a copy of dX' included that only a caller whose dX' is an
argument pays. In ``xing4_train_s4096``'s step the four read 0.45, 0.80,
0.72 and 1.18 ms a call: 650, 660, 815 and 645 GB/s of their bytes.

Parameters of one sublayer's set (``HC_PARAMS``): ``phi`` [n·d, 2n + n²],
``gain`` [n·d], ``bias`` [2n + n²], ``alpha`` [3] (pre, post, res).
"""
from __future__ import annotations

import collections
import functools
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..perf.recorder import record as _record
from . import kernel_common
from .kernel_common import LANES, VMEM_BYTES

HC_PARAMS = ("phi", "gain", "bias", "alpha")
SCOPE = "mhc"

# The names of the four kernels, as a device trace and the compiled HLO
# show them (``name=`` on ``pl.pallas_call``). Part of the measurement:
# pinned in tests/test_tracing_names.py.
KERNEL_NAMES = {
    "pre_fwd": "mhc_pre_fwd",     # Σu², ũΦ, coefficients, z
    "post_fwd": "mhc_post_fwd",   # X'
    "post_bwd": "mhc_post_bwd",   # dy, dH_post, dH_res
    "pre_bwd": "mhc_pre_bwd",     # dH_pre, Sinkhorn backward, dΦ, dX
}

# Traced sublayers by the route each took ("kernel", "plain"); the same
# choice is the event ``rtpu.ops.hyper_connection``.
ROUTE_COUNTS: collections.Counter = collections.Counter()

TOKEN_TILE = 128              # tokens a program works
_GROUP = 8                    # rows a group of coefficients takes
_ROWS = 16                    # tokens a loop iteration mixes
_F32 = jnp.float32
# what the coefficients are held in between the kernels (a test rounds
# them to show that its limits would see it)
_COEF_DTYPE = jnp.float32


def hc_param_shapes(n: int, d: int) -> Dict[str, Tuple[int, ...]]:
    k = 2 * n + n * n
    return {"phi": (n * d, k), "gain": (n * d,), "bias": (k,), "alpha": (3,)}


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m [n, n, tokens] positive -> doubly stochastic a token: ``iters``
    times every column (axis 0 summed) and then every row (axis 1) is
    divided by its sum + eps. Rows sum to 1 / (1 + eps); columns as near
    as the iterations bring them."""
    def step(_, m):
        m = m / (m.sum(0, keepdims=True) + eps)
        return m / (m.sum(1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, step, m)


def _folded_phi(p: Dict[str, jax.Array], n: int, dtype) -> jax.Array:
    """Φ with the gain folded in, in the streams' dtype: [n, d, 2n + n²]."""
    g_phi = p["gain"].astype(_F32)[:, None] * p["phi"].astype(_F32)
    return g_phi.astype(dtype).reshape(n, g_phi.shape[0] // n, -1)


def hc_coefficients(x: Sequence[jax.Array], p: Dict[str, jax.Array], *,
                    iters: int, eps: float, clamp: Tuple[float, float],
                    rms_eps: float):
    """x: n streams [..., d]; p: one set of ``HC_PARAMS`` -> (H_pre
    [n, tokens], H_post [n, tokens], H_res [n, n, tokens]), float32,
    tokens the flattened leading axes."""
    n, d = len(x), x[0].shape[-1]
    with jax.named_scope(SCOPE):
        u = [xj.reshape(-1, d) for xj in x]
        f32 = jnp.float32
        ss = sum(jnp.sum(jnp.square(uj.astype(f32)), -1) for uj in u)
        inv = jax.lax.rsqrt(ss / (n * d) + rms_eps)              # [tokens]
        g_phi = _folded_phi(p, n, u[0].dtype)
        raw = sum(jnp.einsum("dc,td->ct", g_phi[j], u[j],
                             preferred_element_type=f32)
                  for j in range(n)) * inv                       # [2n+n², t]
        alpha, bias = p["alpha"].astype(f32), p["bias"].astype(f32)[:, None]
        pre = jax.nn.sigmoid(alpha[0] * raw[:n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[n:2 * n] + bias[n:2 * n])
        a = jnp.clip(alpha[2] * raw[2 * n:] + bias[2 * n:], *clamp)
        res = sinkhorn(jnp.exp(a).reshape(n, n, -1), iters, eps)
        return pre, post, res


def _over_d(h: jax.Array, like: jax.Array) -> jax.Array:
    """A coefficient [tokens] against an activation [..., d] of those
    tokens."""
    return h.reshape(like.shape[:-1] + (1,))


def hc_pre(x: Sequence[jax.Array], h_pre: jax.Array) -> jax.Array:
    """n streams [..., d], H_pre [n, tokens] -> z = Σ_j H_pre[j] x[j]."""
    with jax.named_scope(SCOPE):
        z = sum(_over_d(h_pre[j], xj) * xj.astype(jnp.float32)
                for j, xj in enumerate(x))
        return z.astype(x[0].dtype)


def hc_post(x: Sequence[jax.Array], y: jax.Array, h_post: jax.Array,
            h_res: jax.Array) -> Tuple[jax.Array, ...]:
    """n streams [..., d] and the sublayer's y [..., d] -> the n streams
    X'[i] = Σ_j H_res[i, j] x[j] + H_post[i] y."""
    with jax.named_scope(SCOPE):
        xs = [xj.astype(jnp.float32) for xj in x]
        yf = y.astype(jnp.float32)
        return tuple(
            (sum(_over_d(h_res[i, j], y) * xj for j, xj in enumerate(xs))
             + _over_d(h_post[i], y) * yf).astype(y.dtype)
            for i in range(len(x)))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------
#
# Rows of the coefficients' arrays, [8 (2 + n), tokens]: H_pre[j] at j,
# H_post[i] at 8 + i, H_res[i, j] at 16 + 8 i + j; the rows between are 0.


def _each_rows(tt: int, body: Callable) -> None:
    """``body(rows)`` for every block of ``_ROWS`` tokens of a tile."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS))
        return carry

    jax.lax.fori_loop(0, tt // _ROWS, step, 0)


def _to_columns(rows, col_ref) -> None:
    """Tokens-minor rows [r <= 128, tile] -> ``col_ref`` [tile, 128],
    float32: row c down lane c, a token's values side by side, as the
    tokens-major streams need them."""
    r, tt = rows.shape
    full = jnp.concatenate(
        [rows.astype(_F32), jnp.zeros((LANES - r, tt), _F32)], axis=0)
    col_ref[...] = full.T


def _lane(block, c: int):
    """Lane c of a [rows, 128] block as a column [rows, 1]."""
    return block[:, c:c + 1]


def _put_lane(block, c: int, col):
    """``block`` [rows, 128] with lane c replaced by ``col`` [rows, 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.where(lane == c, col, block)


def _fold(v):
    """[rows, W] summed over its lane tiles and lanes -> [rows, 1]."""
    out = v[:, :LANES]
    for i in range(1, v.shape[1] // LANES):
        out = out + v[:, i * LANES:(i + 1) * LANES]
    return jnp.sum(out, axis=1, keepdims=True)


def _valid(n: int, tt: int):
    """The rows of a group [8, tile] that hold a value."""
    return jax.lax.broadcasted_iota(jnp.int32, (_GROUP, tt), 0) < n


def _maps(raw, sb, n: int, clamp):
    """The raw ũΦ / rms [8 (2 + n), tile] and (scale, bias) [.., 1] each ->
    H_pre [8, tile], H_post [8, tile], the logits of H_res before the
    clip as n groups [8, tile] and exp of the clipped, 0 on the rows that
    hold nothing."""
    scale, bias = sb
    tt = raw.shape[1]
    ok = _valid(n, tt)
    logit = raw * scale + bias
    pre = jnp.where(ok, jax.nn.sigmoid(logit[:_GROUP]), 0.0)
    post = jnp.where(ok, 2.0 * jax.nn.sigmoid(logit[_GROUP:2 * _GROUP]), 0.0)
    a = [logit[(2 + i) * _GROUP:(3 + i) * _GROUP] for i in range(n)]
    m = [jnp.where(ok, jnp.exp(jnp.clip(ai, *clamp)), 0.0) for ai in a]
    return pre, post, a, m


def _sinkhorn_rows(m, ok, iters: int, eps: float, keep_ref=None):
    """``sinkhorn`` on the n rows of M, each [8, tile] (its columns down
    the sublanes, 0 past n, where ``ok`` is false and the column's sum is
    taken as 1: a compiler that folds the chain of divisions into one
    would otherwise divide 0 by eps to the power of 2 x iters) -> H_res's
    rows. ``keep_ref`` [iters, 2 n + 1, 8, tile] takes what each
    iteration's backward needs: the columns' sums, M after the columns'
    step and after the rows'."""
    n = len(m)

    def step(it, m):
        c = jnp.where(ok, sum(m[1:], m[0]) + eps, 1.0)
        nrm = [mi / c for mi in m]
        out = tuple(ni / (jnp.sum(ni, axis=0, keepdims=True) + eps)
                    for ni in nrm)
        if keep_ref is not None:
            keep_ref[it, 0] = c
            for i in range(n):
                keep_ref[it, 1 + i] = nrm[i]
                keep_ref[it, 1 + n + i] = out[i]
        return out

    return list(jax.lax.fori_loop(0, iters, step, tuple(m)))


def _sinkhorn_rows_bwd(dm, keep_ref, ok, iters: int, eps: float):
    """The cotangent of H_res's rows walked back through the iterations
    to that of M."""
    n = len(dm)

    def step(back, dm):
        it = iters - 1 - back
        c = keep_ref[it, 0]
        nrm = [keep_ref[it, 1 + i] for i in range(n)]
        dn = []
        for i in range(n):
            p, s = keep_ref[it, 1 + n + i], \
                jnp.sum(nrm[i], axis=0, keepdims=True) + eps
            dn.append(jnp.where(
                ok, (dm[i] - jnp.sum(dm[i] * p, axis=0, keepdims=True)) / s,
                0.0))
        t = [dni * ni for dni, ni in zip(dn, nrm)]
        t = sum(t[1:], t[0])
        return tuple((dni - t) / c for dni in dn)

    return list(jax.lax.fori_loop(0, iters, step, tuple(dm)))


def _scale_bias(sb_ref):
    sb = sb_ref[...]
    return sb[:, 0:1], sb[:, 1:2]


def _f32(ref, rows):
    """A block of a stream's rows, whole rows of d, in float32."""
    return ref[rows, :].astype(_F32)


def _pre_fwd_kernel(*refs, n, d, iters, eps, clamp, rms_eps):
    x = refs[:n]
    phi_ref, sb_ref, z_ref, coef_ref, raw_ref, col_ref = refs[n:]
    tt = z_ref.shape[0]
    nt = (((1,), (1,)), ((), ()))
    s = sum(jax.lax.dot_general(phi_ref[j], x[j][...], nt,
                                preferred_element_type=_F32)
            for j in range(n))                          # [8 (2 + n), tile]

    def statistic(rows):
        sq = [jnp.square(_f32(x[j], rows)) for j in range(n)]
        col_ref[rows, :] = jnp.broadcast_to(_fold(sum(sq[1:], sq[0])),
                                            (_ROWS, LANES))

    _each_rows(tt, statistic)
    ss = col_ref[...].T[0:1, :]                         # [1, tile]
    inv = jax.lax.rsqrt(ss / (n * d) + rms_eps)
    raw = s * inv
    pre, post, _, m = _maps(raw, _scale_bias(sb_ref), n, clamp)
    res = _sinkhorn_rows(m, _valid(n, tt), iters, eps)
    coef_ref[...] = jnp.concatenate([pre, post] + res, 0).astype(
        coef_ref.dtype)
    raw_ref[...] = jnp.concatenate(
        [raw, jnp.broadcast_to(inv, (_GROUP, tt))], 0)
    _to_columns(pre, col_ref)

    def mix(rows):
        h = col_ref[rows, :]
        z = [_lane(h, j) * _f32(x[j], rows) for j in range(n)]
        z_ref[rows, :] = sum(z[1:], z[0]).astype(z_ref.dtype)

    _each_rows(tt, mix)


def _post_fwd_kernel(*refs, n):
    x = refs[:n]
    y_ref, coef_ref = refs[n:n + 2]
    out = refs[n + 2:2 * n + 2]
    col_ref = refs[2 * n + 2]
    _to_columns(coef_ref[...], col_ref)

    def mix(rows):
        h = col_ref[rows, :]
        xf, yf = [_f32(x[j], rows) for j in range(n)], _f32(y_ref, rows)
        for i in range(n):
            o = _lane(h, _GROUP + i) * yf
            for j in range(n):
                o = o + _lane(h, (2 + i) * _GROUP + j) * xf[j]
            out[i][rows, :] = o.astype(out[i].dtype)

    _each_rows(y_ref.shape[0], mix)


def _post_bwd_kernel(*refs, n):
    x, g = refs[:n], refs[n:2 * n]
    y_ref, coef_ref, dy_ref, dcoef_ref, col_ref, dcol_ref = refs[2 * n:]
    _to_columns(coef_ref[_GROUP:2 * _GROUP, :], col_ref)    # H_post

    def reduce(rows):
        h = col_ref[rows, :]
        xf, yf = [_f32(x[j], rows) for j in range(n)], _f32(y_ref, rows)
        block = jnp.zeros((_ROWS, LANES), _F32)
        dy = None
        for i in range(n):
            gf = _f32(g[i], rows)
            t = _lane(h, i) * gf
            dy = t if dy is None else dy + t
            block = _put_lane(block, _GROUP + i, _fold(gf * yf))
            for j in range(n):
                block = _put_lane(block, (2 + i) * _GROUP + j,
                                  _fold(gf * xf[j]))
        dy_ref[rows, :] = dy.astype(dy_ref.dtype)
        dcol_ref[rows, :] = block

    _each_rows(y_ref.shape[0], reduce)
    dcoef_ref[...] = dcol_ref[...].T[:dcoef_ref.shape[0], :]


def _pre_bwd_kernel(*refs, n, d, iters, eps, clamp):
    x, g = refs[:n], refs[n:2 * n]
    dz_ref, raw_ref, dcoef_ref, phi_ref, sb_ref = refs[2 * n:2 * n + 5]
    dx = refs[2 * n + 5:3 * n + 5]
    dlogit_ref, dphi_ref, col_ref, dcol_ref, mm_ref, keep_ref = \
        refs[3 * n + 5:]
    tt = dz_ref.shape[0]
    k = (2 + n) * _GROUP

    def reduce(rows):                                   # dH_pre
        dzf = _f32(dz_ref, rows)
        block = jnp.zeros((_ROWS, LANES), _F32)
        for j in range(n):
            block = _put_lane(block, j, _fold(dzf * _f32(x[j], rows)))
        dcol_ref[rows, :] = block

    _each_rows(tt, reduce)
    dcoef = dcoef_ref[...].astype(_F32)
    dpre = dcol_ref[...].T[:_GROUP, :] + dcoef[:_GROUP]
    dpost = dcoef[_GROUP:2 * _GROUP]
    scale, bias = _scale_bias(sb_ref)
    raw_inv = raw_ref[...]
    raw, inv = raw_inv[:k], raw_inv[k:k + 1]
    pre, post, a, m = _maps(raw, (scale, bias), n, clamp)
    ok = _valid(n, tt)
    res = _sinkhorn_rows(m, ok, iters, eps, keep_ref)
    dm = _sinkhorn_rows_bwd(
        [dcoef[(2 + i) * _GROUP:(3 + i) * _GROUP] for i in range(n)],
        keep_ref, ok, iters, eps)
    lo, hi = clamp
    da = [jnp.where((ai > lo) & (ai < hi), dmi * mi, 0.0)
          for ai, dmi, mi in zip(a, dm, m)]
    dlogit = jnp.concatenate(
        [dpre * pre * (1.0 - pre), dpost * post * (1.0 - 0.5 * post)] + da,
        0)
    dlogit_ref[...] = dlogit
    draw = dlogit * scale
    rms = -jnp.sum(draw * raw, axis=0, keepdims=True) * inv * inv / (n * d)
    ds = (draw * inv).astype(phi_ref.dtype)             # [8 (2 + n), tile]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    tn = (((0,), (0,)), ((), ()))
    for j in range(n):
        dphi_ref[j] += jnp.dot(ds, x[j][...], preferred_element_type=_F32)
        mm_ref[j] = jax.lax.dot_general(ds, phi_ref[j], tn,
                                        preferred_element_type=_F32)
    # a token's coefficients side by side: H_pre at 0, the RMS term at 8,
    # H_res from 16 on
    _to_columns(jnp.concatenate(
        [pre, jnp.broadcast_to(rms, (_GROUP, tt))] + res, 0), col_ref)

    def mix(rows):
        h = col_ref[rows, :]
        gf, dzf = [_f32(g[i], rows) for i in range(n)], _f32(dz_ref, rows)
        for j in range(n):
            o = mm_ref[j, rows, :] + _lane(h, j) * dzf \
                + _lane(h, _GROUP) * _f32(x[j], rows)
            for i in range(n):
                o = o + _lane(h, (2 + i) * _GROUP + j) * gf[i]
            dx[j][rows, :] = o.astype(dx[j].dtype)

    _each_rows(tt, mix)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------


def _call(kernel, name, tokens, grid_in, out_specs, out_shape, scratch,
          order=("parallel",), aliases=None):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=(tokens // TOKEN_TILE,), in_specs=grid_in,
        out_specs=out_specs, out_shape=out_shape,
        input_output_aliases=aliases or {},
        scratch_shapes=[pltpu.VMEM(s, _F32) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order, vmem_limit_bytes=VMEM_BYTES),
        name=KERNEL_NAMES[name], interpret=kernel_common.use_interpret())


def _stream_spec(d):
    return pl.BlockSpec((TOKEN_TILE, d), lambda t: (t, 0))


def _coef_spec(rows):
    return pl.BlockSpec((rows, TOKEN_TILE), lambda t: (0, t))


def _whole(shape):
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape))


def _cols():
    return (TOKEN_TILE, LANES)


def _cut():
    """What a kernel's trace reads from this module beside its arguments."""
    return (TOKEN_TILE, _ROWS, jnp.dtype(_COEF_DTYPE).name,
            kernel_common.use_interpret())


def _traced_once(fn):
    """``fn(*arrays, **static)`` under ``jax.jit``. A step calls every
    kernel at four sublayers, as the primal, under jvp and in the
    rematerialised backward, and each call would trace the kernel's body
    anew (half a second each); a jitted call's trace is found again by
    its shapes, ``static`` and ``_cut``. The one trace serves every
    caller, so it names its scope itself: the caller's is not on it."""
    def scoped(cut, static, *a):
        with jax.named_scope(SCOPE):
            return fn(*a, **dict(static))

    jitted = jax.jit(scoped, static_argnums=(0, 1))

    def call(*arrays, **static):
        return jitted(_cut(), tuple(sorted(static.items())), *arrays)

    return functools.wraps(fn)(call)


@_traced_once
def _pre_fwd(x, phi_t, sb, **static):
    n, (t, d) = len(x), x[0].shape
    k = phi_t.shape[1]
    sd = jax.ShapeDtypeStruct
    return _call(
        functools.partial(_pre_fwd_kernel, n=n, d=d, **static), "pre_fwd", t,
        [_stream_spec(d)] * n + [_whole(phi_t.shape), _whole(sb.shape)],
        [_stream_spec(d), _coef_spec(k), _coef_spec(k + _GROUP)],
        [sd((t, d), x[0].dtype), sd((k, t), _COEF_DTYPE),
         sd((k + _GROUP, t), _F32)],
        [_cols()])(*x, phi_t, sb)


@_traced_once
def _post_fwd(x, y, coef):
    n, (t, d) = len(x), x[0].shape
    return _call(
        functools.partial(_post_fwd_kernel, n=n), "post_fwd", t,
        [_stream_spec(d)] * (n + 1) + [_coef_spec(coef.shape[0])],
        [_stream_spec(d)] * n,
        [jax.ShapeDtypeStruct((t, d), y.dtype)] * n, [_cols()])(*x, y, coef)


@_traced_once
def _post_bwd(x, g, y, coef):
    n, (t, d) = len(x), x[0].shape
    k = coef.shape[0]
    return _call(
        functools.partial(_post_bwd_kernel, n=n), "post_bwd", t,
        [_stream_spec(d)] * (2 * n + 1) + [_coef_spec(k)],
        [_stream_spec(d), _coef_spec(k)],
        [jax.ShapeDtypeStruct((t, d), y.dtype),
         jax.ShapeDtypeStruct((k, t), _F32)],
        [_cols(), _cols()])(*x, *g, y, coef)


@_traced_once
def _pre_bwd(x, g, dz, raw, dcoef, phi_t, sb, *, iters, eps, clamp):
    n, (t, d) = len(x), x[0].shape
    k = dcoef.shape[0]
    sd = jax.ShapeDtypeStruct
    return _call(
        functools.partial(_pre_bwd_kernel, n=n, d=d, iters=iters, eps=eps,
                          clamp=clamp), "pre_bwd", t,
        [_stream_spec(d)] * (2 * n + 1)
        + [_coef_spec(k + _GROUP), _coef_spec(k),
           _whole(phi_t.shape), _whole(sb.shape)],
        [_stream_spec(d)] * n + [_coef_spec(k), _whole(phi_t.shape)],
        [sd((t, d), x[0].dtype)] * n + [sd((k, t), _F32),
                                        sd(phi_t.shape, _F32)],
        [_cols(), _cols(), (n, TOKEN_TILE, d),
         (iters, 2 * n + 1, _GROUP, TOKEN_TILE)],
        order=("arbitrary",),
        # dX[j] takes dX'[j]'s place: a program has read its rows of every
        # dX' before it writes any, and nothing reads dX' afterwards
        aliases={n + j: j for j in range(n)})(
            *x, *g, dz, raw, dcoef, phi_t, sb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _read(static, x, phi_t, scale, bias):
    """The first half of a sublayer's pair: n streams [tokens, d] -> (z,
    the coefficients, the streams handed on to ``_write``)."""
    return _read_fwd(static, x, phi_t, scale, bias)[0]


def _scale_bias_lanes(scale, bias):
    sb = jnp.stack([scale, bias], 1).astype(_F32)
    return jnp.pad(sb, ((0, 0), (0, LANES - 2)))


def _read_fwd(static, x, phi_t, scale, bias):
    sb = _scale_bias_lanes(scale, bias)
    with jax.named_scope(SCOPE):
        z, coef, raw = _pre_fwd(x, phi_t, sb, **dict(static))
    return (z, coef, x), (x, phi_t, sb, raw)


def _read_bwd(static, kept, cts):
    x, phi_t, sb, raw = kept
    dz, dcoef, g = cts
    n = len(x)
    with jax.named_scope(SCOPE):
        kw = dict(static)
        del kw["rms_eps"]       # the statistic is kept (``raw``), not remade
        out = _pre_bwd(x, g, dz, raw, dcoef.astype(_F32), phi_t, sb, **kw)
        dx, dlogit, dphi = tuple(out[:n]), out[n], out[n + 1]
        dscale = jnp.sum(dlogit * raw[:dlogit.shape[0]], axis=1)
        dbias = jnp.sum(dlogit, axis=1)
    return dx, dphi.astype(phi_t.dtype), dscale, dbias


_read.defvjp(_read_fwd, _read_bwd)


@jax.custom_vjp
def _write(x, y, coef):
    """The second half: the streams ``_read`` handed on, the sublayer's y
    and the coefficients -> the n mixed streams. Its cotangent for the
    streams is dX' as it came: ``_read``'s backward mixes it (module
    docstring), so the two are only ever used as the pair ``hc_mix``
    makes of them."""
    with jax.named_scope(SCOPE):
        return tuple(_post_fwd(x, y, coef))


def _write_fwd(x, y, coef):
    return _write(x, y, coef), (x, y, coef)


def _write_bwd(kept, g):
    x, y, coef = kept
    with jax.named_scope(SCOPE):
        dy, dcoef = _post_bwd(x, g, y, coef)
    return tuple(g), dy, dcoef.astype(coef.dtype)


_write.defvjp(_write_fwd, _write_bwd)


def _spread(v: jax.Array, n: int, axis: int) -> jax.Array:
    """The 2n + n² coefficients of the parameters' order (pre, post, res
    row by row: 2 + n groups of n) along ``axis`` -> the kernels' 8 (2 + n)
    rows, every group followed by 8 - n zeros."""
    v = jnp.moveaxis(v, axis, -1)
    g = v.reshape(v.shape[:-1] + (2 + n, n))
    g = jnp.pad(g, [(0, 0)] * (g.ndim - 1) + [(0, _GROUP - n)])
    return jnp.moveaxis(g.reshape(v.shape[:-1] + (-1,)), -1, axis)


def _kernel_operands(p: Dict[str, jax.Array], n: int, dtype):
    """One set of ``HC_PARAMS`` -> (Φ with the gain folded in, transposed
    and spread [n, 8 (2 + n), d]; every row's α; every row's bias)."""
    phi_t = _spread(jnp.swapaxes(_folded_phi(p, n, dtype), 1, 2), n, 1)
    alpha = p["alpha"].astype(_F32)
    scale = jnp.concatenate([jnp.broadcast_to(alpha[g], (m,)) for g, m
                             in enumerate((n, n, n * n))])
    return phi_t, _spread(scale, n, 0), _spread(p["bias"].astype(_F32), n, 0)


def _vmem_bytes(n: int, d: int, itemsize: int) -> int:
    """What the largest of the four programs (``mhc_pre_bwd``) holds in
    VMEM: 3 n + 1 stream tiles and Φ in two buffers each, dΦ, the ũΦ term
    and the Sinkhorn iterations in float32."""
    tile, k = TOKEN_TILE * d, (2 + n) * _GROUP
    return 2 * ((3 * n + 1) * tile + n * k * d) * itemsize \
        + 4 * (n * tile + 2 * n * k * d)


def _route(n: int, d: int, tokens: int, itemsize: int) -> str:
    fits = d % LANES == 0 and tokens % TOKEN_TILE == 0 and n <= _GROUP \
        and _vmem_bytes(n, d, itemsize) <= 0.9 * VMEM_BYTES
    return "kernel" if fits else "plain"


def hc_mix(x: Sequence[jax.Array], p: Dict[str, jax.Array], f: Callable, *,
           iters: int, eps: float, clamp: Tuple[float, float],
           rms_eps: float):
    """One sublayer under its hyper-connections. x: n streams [..., d]; p:
    one set of ``HC_PARAMS``; ``f(z) -> (y, aux)`` the sublayer of its
    input z [..., d] -> (the n mixed streams, aux)."""
    n, shape = len(x), x[0].shape
    d = shape[-1]
    tokens = x[0].size // d
    route = _route(n, d, tokens, x[0].dtype.itemsize)
    ROUTE_COUNTS[route] += 1
    _record("rtpu.ops.hyper_connection", "route",
            {"route": route, "streams": n, "d": d, "tokens": tokens,
             "tile": TOKEN_TILE})
    if route == "plain":
        pre, post, res = hc_coefficients(x, p, iters=iters, eps=eps,
                                         clamp=clamp, rms_eps=rms_eps)
        y, aux = f(hc_pre(x, pre))
        return hc_post(x, y, post, res), aux
    static = (("iters", iters), ("eps", eps), ("clamp", tuple(clamp)),
              ("rms_eps", rms_eps))
    with jax.named_scope(SCOPE):
        phi_t, scale, bias = _kernel_operands(p, n, x[0].dtype)
    z, coef, handed = _read(static, tuple(xj.reshape(-1, d) for xj in x),
                            phi_t, scale, bias)
    y, aux = f(z.reshape(shape))
    out = _write(handed, y.reshape(-1, d), coef)
    return tuple(o.reshape(shape) for o in out), aux
