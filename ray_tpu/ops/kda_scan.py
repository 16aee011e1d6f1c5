"""Kimi Delta Attention's recurrence (KDA: a gated delta rule with a decay
for every key channel) as a chunked scan, in plain ``jax.numpy``.

Per head (state S [d_k, d_v] float32 from zero; q_t, k_t [d_k], v_t [d_v],
g_t [d_k] <= 0 the log of the token's decay a key channel, beta_t in (0, 1)
how much of the correction is written):

    S   <- diag(exp(g_t)) S                      every key channel decays
    S   <- S + beta_t k_t (v_t - S^T k_t)^T      the delta rule: what S holds
                                                 for k_t is corrected to v_t
    o_t  = S^T (scale q_t)

Chunked form (the WY form of a product of the rank-one corrections). Inside
a chunk of C tokens with G the INCLUSIVE cumulative sum of g over the
chunk's tokens (a key channel) and S_0 the state the chunk starts from,
rows i and columns j of one chunk:

    A_ij  = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)      i > j, else 0
    T     = (I + A)^-1                                   unit lower triangular
    W     = T (beta k exp(G)),   U = T (beta v)
    V'    = U - W S_0                                    what each token writes
    O     = scale ((q exp(G)) S_0 + tril(Q_ij) V'),  Q_ij = sum_c q_ic k_jc
            exp(G_ic - G_jc), i >= j
    S_end = diag(exp(G_C)) S_0 + (k exp(G_C - G))^T V'

(``v'_t = beta_t (v_t - (diag(exp(g_t)) S_{t-1})^T k_t)`` unrolled over the
chunk: ``(I + A) V' = beta V - (beta K exp(G)) S_0``.) Chunking is no part
of the mathematics: any chunk gives the recurrence's numbers up to rounding.

**Every exponent is a later cumulative sum less an earlier one, so <= 0**
(``ssd_scan.py``'s rule): nothing overflows however strong the decay, and
``exp(-G)`` alone is never formed. The decay does not factor out of the
contraction over c (it is a vector a token, not a scalar), so the score
matrices A and Q are made in sub-blocks of ``_SUB`` = 8 rows:

* block row I against an EARLIER block column: rows scaled by
  ``exp(G_i - G_I0)`` and columns by ``exp(G_I0 - G_j)``, I0 the block
  row's first token (i >= I0 > j: both <= 0), then one matrix product;
* a diagonal sub-block: the [8, 8, d_k] differences ``G_i - G_j`` (i >= j)
  themselves, a multiply-add on the VPU. (Sub-blocks of 8 / 16 / 32 rows
  cost a layer and step 100 / 117 / 163 ms on a v5e: the differences are
  the larger part, and under 8 the scaled copies of k outgrow them.)

``T``: the 8 x 8 diagonal blocks of I + A by the Neumann product
``(I - A)(I + A^2)(I + A^4)`` (A strictly lower: A^8 = 0), then pairs of
blocks merged three times, ``[[T11, 0], [-T22 A21 T11, T22]]``, in float32
at matmul precision "highest". The product over the whole 64 x 64 block
would be as many products, but its terms grow as C(63, n) a^n before they
cancel: with neighbouring keys alike (k_i . k_j near 0.8) it is wrong by
thirteen orders of magnitude, blocks of 32 by a tenth, of 16 by 5e-5, of 8
by 1e-6 (``tests/test_kda_scan.py``). The solve's backward is its own
(``dA = -T^T dT T^T``), not autodiff's through the products.

How the work is cut: ONE ``lax.scan`` over the chunks carries the state; a
turn makes its chunk's matrices (A, Q, T, W, U) for every batch row and
head at once, reads and updates the state. The turn's body is
rematerialised: autodiff keeps the state each chunk starts from and the
inputs, and makes a chunk's matrices again in the backward, so no array of
[tokens, 16, d_k] or [tokens, C] a head outlives its chunk. The backward is
jax's, through the scan. (Measured on a v5e at the benchmark cell's shape,
PERF.md PR 49: with the local matrices of 1 / 2 / 4 / 8 / 32 chunks made at
once a layer costs a step 113 / 138 / 166 / 193 / 276 ms, one forward and one
forward + backward: one chunk's arrays are small enough for the compiler to
keep in VMEM.)

One route today (``PATH_COUNTS``, the event ``rtpu.ops.kda.path``):
``chunked_jnp``. T is padded to whole chunks with zeros (g = 0 does not
decay, beta = 0 and k = 0 write nothing).

Precision: matrix products take their operands in q's dtype (bf16 in a
model) and accumulate in float32; the gates, their cumulative sums, every
decay, the triangular solve and the state are float32 throughout.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from .scan_common import pad_tokens, record_path

# Traced calls of kda_scan by route; the same choice is the flight-recorder
# event ``rtpu.ops.kda.path``.
PATH_COUNTS: collections.Counter = collections.Counter()

_SUB = 8      # rows of a sub-block: of the decayed score matrices, and of
#               the diagonal blocks the solve inverts by a Neumann product
_F32 = jnp.float32

_ein = functools.partial(jnp.einsum, preferred_element_type=_F32)
_exact = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _inverse_by_blocks(a):
    n = a.shape[-1]
    if n <= _SUB or n % 2:
        eye = jnp.eye(n, dtype=a.dtype)
        t, p, m = eye - a, a, 2
        while m < n:                  # p = a^(m/2) -> a^m; a^n = 0
            p = _exact(p, p)
            t = _exact(t, eye + p)
            m *= 2
        return t
    h = n // 2
    t11, t22 = _inverse_by_blocks(
        jnp.stack([a[..., :h, :h], a[..., h:, h:]]))
    t21 = -_exact(_exact(t22, a[..., h:, :h]), t11)
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(t11)], -1),
        jnp.concatenate([t21, t22], -1)], -2)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """T = (I + a)^-1 for a [..., n, n] strictly lower triangular, float32.
    Its backward is the inverse's own, dA = -T^T dT T^T from T alone: two
    products, where autodiff through the blocks' products makes twenty."""
    return _inverse_by_blocks(a)


def _inverse_fwd(a):
    t = _inverse_by_blocks(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_exact(_exact(tt, dt), tt),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _decayed_scores(q, k, cum):
    """q, k [..., C, d], cum [..., C, d] f32 (inclusive cumulative gates of
    the chunk) -> (Q, K) [..., C, C] f32 with X_ij = sum_c x_ic k_jc
    exp(cum_ic - cum_jc) for i >= j and 0 above the diagonal."""
    *lead, c, d = q.shape
    r = _SUB if c % _SUB == 0 else c
    nb = c // r
    dt = q.dtype
    sub = lambda x: x.reshape(*lead, nb, r, d)               # noqa: E731
    qk = jnp.stack([q, k], -3)                               # [.., 2, C, d]
    cs = sub(cum)
    # diagonal sub-blocks: the differences themselves
    seen = jnp.tril(jnp.ones((r, r), bool))[..., None]
    decay = jnp.exp(jnp.where(
        seen, cs[..., :, None, :] - cs[..., None, :, :], -jnp.inf))
    kj = sub(k).astype(_F32)[..., None, :, :] * decay        # [.., nb, r, r, d]
    xi = qk.reshape(*lead, 2, nb, r, d).astype(_F32)
    diag = (xi[..., :, None, :] * kj[..., None, :, :, :, :]).sum(-1)
    eye = jnp.eye(nb, dtype=_F32)[:, None, :, None]          # [nb, 1, nb, 1]
    out = (diag[..., :, :, None, :] * eye).reshape(*lead, 2, c, c)
    if nb == 1:
        return out[..., 0, :, :], out[..., 1, :, :]
    # block rows 1.. against the columns before them: both sides scaled
    # against the block row's first token
    first = cs[..., 1:, :1, :]                               # [.., nb-1, 1, d]
    rows = (xi[..., 1:, :, :]
            * jnp.exp(cs[..., 1:, :, :] - first)[..., None, :, :, :]
            ).astype(dt)                                     # [.., 2, nb-1, r, d]
    early = cum[..., None, :c - r, :]                        # [.., 1, C-r, d]
    cols = (k.astype(_F32)[..., None, :c - r, :]
            * jnp.exp(jnp.minimum(first - early, 0.0))).astype(dt)
    off = _ein("...xird,...ijd->...xirj", rows, cols)        # [.., 2, nb-1, r, C-r]
    before = (jnp.arange(c - r)[None, None, :]
              < (jnp.arange(1, nb) * r)[:, None, None])      # [nb-1, 1, C-r]
    off = jnp.where(before, off, 0.0).reshape(*lead, 2, c - r, c - r)
    out = out + jnp.pad(off, [(0, 0)] * (len(lead) + 1) + [(r, 0), (0, r)])
    return out[..., 0, :, :], out[..., 1, :, :]


def _chunk_body(state, xs, *, scale: float):
    """One chunk. state [B, H, d_k, d_v] f32; xs = (q, k [B, H, C, d_k], v
    [B, H, C, d_v], g [B, H, C, d_k] f32, beta [B, H, C] f32) -> (the state
    after the chunk, o [B, H, C, d_v])."""
    q, k, v, g, beta = xs
    dt = q.dtype
    chunk = q.shape[2]
    # the inclusive cumulative sum as ONE product with a triangle of ones
    # (``jnp.cumsum`` lowers to a window reduction, 9 ms a step more at the
    # benchmark cell's shape: PERF.md, PR 49)
    cum = jnp.einsum("ij,bhjd->bhid", jnp.tril(jnp.ones((chunk, chunk), _F32)),
                     g, precision=jax.lax.Precision.HIGHEST)
    sq, sk = _decayed_scores(q, k, cum)                      # [b, h, C, C]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    t = _unit_lower_inverse(
        jnp.where(strict, sk * beta[..., None], 0.0)).astype(dt)
    kept, held = jnp.exp(cum), state.astype(dt)    # decay since the chunk began
    bk = (k.astype(_F32) * beta[..., None] * kept).astype(dt)
    bv = (v.astype(_F32) * beta[..., None]).astype(dt)
    w = _ein("bhij,bhjd->bhid", t, bk).astype(dt)
    u = _ein("bhij,bhjd->bhid", t, bv)                       # f32
    wrote = (u - _ein("bhid,bhde->bhie", w, held)).astype(dt)
    q_in = (q.astype(_F32) * kept).astype(dt)
    o = _ein("bhid,bhde->bhie", q_in, held) \
        + _ein("bhij,bhje->bhie", sq.astype(dt), wrote)
    last = cum[:, :, -1:, :]                                 # [b, h, 1, d]
    k_end = (k.astype(_F32) * jnp.exp(last - cum)).astype(dt)
    state = kept[:, :, -1, :, None] * state + _ein(
        "bhid,bhie->bhde", k_end, wrote)
    return state, (o * scale).astype(dt)


def _chunked(q, k, v, g, beta, heads: int, chunk: int, scale: float):
    """Whole chunks of merged [B, T, H*d] arrays -> o [B, T, H*d_v]."""
    b, t, _ = q.shape

    def chunks_first(x):      # [B, T, H*d] or [B, T, H] -> [chunks, B, H, C, ..]
        x = x.reshape(b, t // chunk, chunk, heads, -1)
        return jnp.moveaxis(x, (1, 3), (0, 2))

    xs = tuple(map(chunks_first, (q, k, v, g, beta)))
    xs = xs[:4] + (xs[4][..., 0],)
    dk, dv = xs[1].shape[-1], xs[2].shape[-1]
    body = jax.checkpoint(functools.partial(_chunk_body, scale=scale))
    _, o = jax.lax.scan(body, jnp.zeros((b, heads, dk, dv), _F32), xs)
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t, heads * dv)


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, *, scale: float, chunk: int = 64) -> jax.Array:
    """The gated delta rule with a decay a key channel. q, k [batch, seq,
    heads * d_k] and v [batch, seq, heads * d_v] in the model's merged
    layout, g [batch, seq, heads * d_k] (<= 0, the log decay, float32),
    beta [batch, seq, heads] -> o [batch, seq, heads * d_v] in q's dtype,
    o_t = S_t^T (scale q_t). Differentiable in all five. ``chunk`` is how
    the work is cut, not what is computed."""
    b, t, _ = q.shape
    heads = beta.shape[-1]
    chunk = min(chunk, t)
    g, beta = g.astype(_F32), beta.astype(_F32)
    (q, k, v, g, beta), pad = pad_tokens((q, k, v, g, beta), chunk)
    record_path("rtpu.ops.kda.path", PATH_COUNTS, "chunked_jnp",
                {"chunk": chunk, "tokens": t, "padded_tokens": pad,
                 "heads": heads, "d_k": k.shape[-1] // heads,
                 "d_v": v.shape[-1] // heads,
                 "chunks": (t + pad) // chunk})
    return _chunked(q, k, v, g, beta, heads, chunk, float(scale))[:, :t]
