"""Kimi Delta Attention's recurrence (KDA: a gated delta rule with a decay
for every key channel) as a chunked scan: a Pallas kernel pair, and the
plain ``jax.numpy`` form that defines it; and Gated DeltaNet's (the same
rule with ONE decay a head and several value heads to a key head): its own
plain form, and the same kernel pair with a body of its own.

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

**One decay a head (Gated DeltaNet).** Where g_t is one number a head the
decay is a scalar a token, e^{G_i - G_j} comes out of the contraction over
c, and the chunk's matrices are (G [C] a value head, K K^T and Q K^T made
ONCE a key head, R value heads reading it):

    A_ij  = beta_i (k_i . k_j) exp(G_i - G_j)            i > j, else 0
    Q_ij  = (q_i . k_j) exp(G_i - G_j)                   i >= j
    T, W, U, V', O as above with exp(G) a scalar a row
    S_end = exp(G_C) S_0 + (k exp(G_C - G))^T V'

one product a chunk and a [C, C] table of decays a value head in place of
the sub-blocks of 8 rows: ``_head_decay_chunk_body``, the state [Hk, R,
d_k, d_v]. Every exponent is still a later cumulative sum less an earlier
one. ``gated_delta_scan(q, k, v, g, beta)`` is that definition (g and beta
[B, T, Hv], q and k [B, T, Hk x d]); it always takes the plain route.

Which entry a layer calls. A KDA layer (``models/kimi_linear.py``):
``kda_gated_scan``. A Gated DeltaNet layer (``models/qwen3_next.py``):
``gdn_gated_scan(q, k, v, a, a_log, dt_bias, beta)``, which on the plain
route is ``l2norm`` a key head, g = -exp(a_log) softplus(a + dt_bias) a
value head and ``gated_delta_scan``, and on the kernel route is the kernel
pair with the body for one decay a head (``gdn_chunk_fwd`` /
``gdn_chunk_bwd``; below). The two rules' needs conflict (the general body
cannot use a factored table, the scalar body is wrong for a vector decay),
so they are separate paths, reached by the entry alone: no argument, flag
or name chooses, and KDA's traced program knows nothing of the other.
``kda_scan`` and ``gated_delta_scan`` are the two definitions everything is
tested against. The event's facts ``decay`` (``channel`` or ``head``),
``body`` (``channel_decay`` or ``head_decay``: which program ran) and
``key_heads`` say which rule a call was.

KDA's two entries. ``kda_scan(q, k, v, g, beta)`` is the recurrence above,
q and k as the caller normalised them and g ready: the definition everything
here is tested against. ``kda_gated_scan(q, k, v, step, a_log, dt_bias, beta)``
is a KDA layer's call: q and k as its convolutions' SiLU left them, ``step``
as its gate projection left it, and

    q_n = q / sqrt(sum over a head's channels of q^2 + eps),  k_n likewise
    g   = -exp(a_log)[head] * softplus(step + dt_bias)           float32
    o   = kda_scan(q_n, k_n, v, g, beta)

which on the plain route is literally that (``layers.l2norm``, the
softplus, ``kda_scan``) and on the kernel route is the SAME kernel pair
with a prologue: each program makes q_n, k_n and g on its own tiles in
VMEM, and the backward kernel carries the chain rule on (below), so no
float32 [B, T, H x 128] array is written or read on the way in or out.
Who made them is the event's fact ``prologue``: ``in_kernel``, or ``jnp``
(the plain route, and every ``kda_scan`` call).

Two routes, chosen by what a call shows (``PATH_COUNTS``, the event
``rtpu.ops.kda.path``; no argument or configuration selects one). T is
padded to whole chunks with zeros on both (g = 0 does not decay, beta = 0
and k = 0 write nothing; through the prologue a padded token's g is
softplus(dt_bias)'s and not 0, which decays a state nothing reads any
more).

* ``kernel``: heads of 128 key and 128 value channels (a head is one
  128-lane tile of the model's merged [B, T, H x 128] arrays: nothing is
  transposed or copied on the way in or out) and a chunk of 64. A Pallas
  pair under one ``custom_vjp`` (``KERNEL_NAMES``), grid (batch, head
  block, chunk), the chunks in order on the last, sequential axis; a
  program is one chunk of a block of ``_MAX_HEADS_PER_BLOCK`` heads.
  - ``kda_chunk_fwd`` carries each head's state, TRANSPOSED [d_v, d_k], in
    a float32 VMEM scratch from chunk to chunk (what decays it is one number
    a key channel: with the key channels along the lanes that is a row
    spread down the sublanes, never a [d_k, 1] column) and writes o and the
    state each chunk STARTS from ([B, T/C, H x 128, 128] float32), the
    backward's one residual beside the inputs.
  - ``kda_chunk_bwd`` walks the chunks from the last, carries dS in the
    same kind of scratch, makes the chunk's matrices AGAIN from q, k, v, g,
    beta and the saved start state (one body, ``_chunk_forward``, serves
    both kernels), and writes dq, dk, dv (q's dtype), dg and dbeta
    (float32). For a decayed score X_ij = sum_c x_ic k_jc exp(G_ic - G_jc):
    dx_ic = sum_j dX_ij k_jc exp(.), dk_jc = sum_i dX_ij x_ic exp(.), and
    the gates' share is elementwise, dG_ic += x_ic dx_ic, dG_jc -= k_jc
    dk_jc (``_scores_bwd``; the reference of a block row drops out); dA =
    -T^T dT T^T; dV' = scale Q^T dO + k_end dS_end; dS_0 = scale (q e^G)^T
    dO + diag(e^G_C) dS_end - W^T dV'; dg is the triangle's product with dG
    reversed. All held to ``jax.vjp`` of the plain route in the tests.
  - the prologue (``kda_gated_scan``; ``_unit``, ``_gate``: pure functions
    of tiles, skipped when g came ready, a static fact of the trace). A
    program reads q, k and step in the model's dtype and two float32 rows
    [2, H x 128] (A_log, a head's over its 128 channels, and dt_bias). The
    norm is a sum over the lanes and a rsqrt a row, the gate an exp and a
    log1p an element (softplus as max(x, 0) + log1p(exp(-|x|)): nothing
    overflows), all float32; q_n and k_n stay float32 (the plain code
    rounds them to q's dtype first: the kernels are the nearer to the
    recurrence). ``kda_chunk_bwd`` makes them again and writes dq and dk of
    the RAW q and k (d = r (dq_n - q_n sum over the lanes of dq_n q_n), r
    the same rsqrt), ``dstep = dg (-exp(A_log)) sigmoid(step + dt_bias)``
    in step's dtype in place of dg, and the rows' gradients as float32
    partial sums over the chunks it walks, in an output block [2, W] a
    (batch row, head block) that stays in VMEM over the sequential axis
    (sum dg g a channel for A_log, dg / dA_log being g itself; sum dstep a
    channel for dt_bias); the caller adds them over the batch, and
    autodiff over a head's lanes.
  How a program lays out its work:
  - once a program: the gate (the prologue) and the cumulative gates of
    the whole block (the triangle of ones times g, g in three bfloat16
    pieces: three exact passes);
  - once a program, for its whole block of heads (``_forward_of`` /
    ``_backward_of``: pure functions of the block's tiles under
    ``jax.jit``, so that the nine kernel instances of a train step all get
    the body traced ONCE; ``setup_s`` pays for every equation traced): the
    solve, two heads' A side by side [C, 2 C] against block-diagonal
    right-hand sides (``_solve``: the MXU's time is the rows that stream
    through it, so two heads cost one); its ten products at
    ``Precision.HIGHEST``, which Mosaic honours (on the chip o is the plain
    route's to a rounding of bf16, PERF.md PR 50), are a chain in which
    each waits for the last, so the block's two pairs are solved in lock
    step (``_solve_heads``) and every later stage of the heads' chunks is
    issued for all of them before the next (``_staged``): the same products
    on the same numbers, side by side in the MXU (on a v5e at the cell's
    shape a layer's forward kernel 11.0 -> 6.8 ms and its backward 19.0 ->
    12.9 by the order of issue alone, every output equal to the last bit:
    PERF.md PR 66; Gated DeltaNet's body below had it from PR 53). A head's
    scores and their backward, two thirds of a head's equations and no part
    of those chains, are jit's too (``_scores``, ``_scores_bwd``): traced
    once for the four heads, so the body of four costs the step's trace what
    the body of two did;
  - once a head: beta as a [C, 128] column by a product with ones (beta
    comes in head-major, dense [heads, C] rows: a [C, 1] column costs a
    register a sublane whatever is done to it, ISSUE 40), the block rows
    against earlier columns (seven small products), W and U in one product
    (T against [beta k e^G | beta v]), W S_0 and (q e^G) S_0 in one (both
    against the state), Q V', the state's update;
  - once a column of the diagonal sub-blocks, for all eight sub-blocks at
    once ([8 blocks, 8 rows, 128] values: a block's rows are the sublanes
    of its own tile): the differences, their exp, two sums over the lanes.
  The bodies are ``jax.lax`` primitives (ROADMAP A11).
  One decay a head (``_gdn_chunk``, ``_gdn_forward_of`` /
  ``_gdn_backward_of``; the grid, the state's layout, the residual, the
  scratch, the ``custom_vjp`` and the once-traced pure bodies are the
  pair's above, the body here a program's whole block). A program is one
  chunk of a block of at most four VALUE heads that holds whole key heads
  (``_gdn_heads_per_block``: four value heads over two key heads at
  Qwen3-Next's two to one; more value heads to a key head than a block
  holds are the plain route's):
  - q and k are read ONCE a key head, from the model's merged [B, T, Hk x
    128] arrays by a block spec of their own; v, o and the state a value
    head. Nothing is repeated to the value heads and no copy of ``a`` over
    a head's lanes exists. ``a`` and beta come in head-major ([B, head
    blocks, chunks, heads a block, C] float32: dense rows), A_log and
    dt_bias as rows a head block.
  - the gate is a NUMBER a value head and token: g = -exp(A_log) softplus(
    a + dt_bias), its cumulative sums over the chunk (one exact product
    with the triangle of ones) and, in the backward, the whole chain rule
    back to ``a`` are worked on one [8, C] tile a program (``_head_gate``:
    the block's heads its first rows). A row becomes a [C, 128] column only
    where a product's operand needs one, for beta and for G (``_columns``:
    ``_beta_column`` for all of a block's rows in one product; exact, the
    column is the row's own float32 numbers).
  - once a key head: the l2 norms (the prologue, as KDA's), and Q K^T over
    K K^T in ONE product [2C, 128] x [128, C] of the stacked, normalised q
    and k rounded to the model's dtype, shared by the key head's value
    heads.
  - once a value head: the table exp(G_i - G_j) [C, C] laid over both
    score matrices on the VPU (the differences themselves, every one used
    <= 0; 4096 exps where the general body makes 65 536 on the diagonal
    sub-blocks alone), the solve (``_solve_heads``, shared and unchanged:
    blocks of 8, ``Precision.HIGHEST``, a key head's two value heads side
    by side), and ONE product of the stacked q and k with the state: with a
    scalar decay (q e^G) S_0 = e^G (q S_0), and what the tokens write is
    V' = T (beta (v - e^G (k S_0))), one [C, C] x [C, 128] product after
    the solve and no W of the keys' own.
  - ``gdn_chunk_bwd`` makes that again and writes dq and dk of the RAW q
    and k ALREADY SUMMED over a key head's value heads (they are in one
    program), [B, T, Hk x 128] in q's dtype; dv; da and dbeta head-major
    float32; A_log's and dt_bias's gradients as partial sums a (batch row,
    head block), as KDA's ``drows``. The gates' gradients are numbers a
    row: with P = dQ o Q + dK o K (the decayed scores times their
    gradients) dG_i gains the sums of P's row i and loses those of its
    column i, e^G's and e^(G_C - G)'s shares are sums over a row's lanes
    (all of a head's in ONE product with ones, the tokens along its
    lanes), dg is the sums from each token to the chunk's end.
  - the heads' work stands SIDE BY SIDE in the program: a head's chunk is
    a chain of products each waiting for the one before (the solve alone
    ten at ``Precision.HIGHEST``), the chains of different heads are
    independent, and issued a stage at a time for all of the block's heads
    (``_solve`` handed the block's pairs as a list; ``_staged``) they
    overlap in the MXU. The same products on the same numbers: on a v5e at
    the cell's shape a layer's forward 9.6 -> 5.9 ms and its backward 14.7
    -> 8.8 by the order of issue alone (PERF.md, PR 53).
  Shared with KDA's body: ``_solve`` / ``_solve_heads``, ``_unit`` /
  ``_unit_bwd``, ``_three``, ``_decay``, the specs' builder. Not
  called here: ``_scores``, ``_against_earlier``, ``_diagonal``, their
  backward twins, ``_gate``, ``_triangle_sums``. (On a v5e at the cell's
  shape, PERF.md PR 53: a layer 6.1 ms forward and 15.5 forward + backward
  where PR 52's route through KDA's body took 14.1 and 36.9; the solve,
  two pairs in lock step, is 3.4 ms of either pass.)
* ``chunked_jnp``: every other shape, and the definition the kernels are
  tested against: ONE ``lax.scan`` over the chunks carries the state; a
  turn makes its chunk's matrices (A, Q, T, W, U) for every batch row and
  head at once, reads and updates the state. The turn's body is
  rematerialised: autodiff keeps the state each chunk starts from and the
  inputs, and makes a chunk's matrices again in the backward. The backward
  is jax's, through the scan. (Measured on a v5e at the benchmark cell's
  shape, PERF.md PR 49 and 50: a layer 20.7 ms forward and 65.8 forward +
  backward, where the kernels take 11.3 and 30.7.)

Precision: matrix products take their operands in q's dtype (bf16 in a
model) and accumulate in float32; the norms' statistics, the gates, their
cumulative sums, every decay, the triangular solve and the state are
float32 throughout.
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
from .layers import l2norm

# The names of the two kernels, as a device trace and the compiled HLO
# show them (``name=`` on ``pl.pallas_call``); pinned in
# tests/test_tracing_names.py.
KERNEL_NAMES = {
    "fwd": "kda_chunk_fwd",     # o and the state each chunk starts from
    "bwd": "kda_chunk_bwd",     # dq, dk, dv, dg (or dstep, drows), dbeta
    # the same pair with the body for ONE decay a head (Gated DeltaNet)
    "gdn_fwd": "gdn_chunk_fwd",
    "gdn_bwd": "gdn_chunk_bwd",     # dq, dk a KEY head; dv, da, drows, dbeta
}

# Traced calls of either entry by route; the same choice is the
# flight-recorder event ``rtpu.ops.kda.path``.
PATH_COUNTS: collections.Counter = collections.Counter()

_SUB = 8      # rows of a sub-block: of the decayed score matrices, and of
#               the diagonal blocks the solve inverts by a Neumann product
_F32 = jnp.float32

_ein = functools.partial(jnp.einsum, preferred_element_type=_F32)
_exact = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _solve_block(beta_max: float) -> int:
    """Rows of the diagonal blocks the solve inverts by the Neumann product,
    by how large the caller says beta gets. A block's terms grow as C(r - 1,
    n) a^n before they cancel, a an entry of A = beta k_i . k_j: with
    neighbouring keys alike (k_i . k_j near 0.8) blocks of 8 are off by
    9e-7 of T's largest entry at beta 0.9 and by 3.3e-5 at beta 2, where the
    correction I - beta k k^T has the eigenvalue 1 - beta < 0 (Gated
    DeltaNet with ``allow_neg_eigval``); blocks of 4 read 2.9e-6 there, and
    blocks of 2 no less (``tests/test_kda_scan.py``). As many products
    either way: one doubling less in the block, one merge more."""
    return _SUB if beta_max <= 1.0 else _SUB // 2


def _inverse_by_blocks(a, r: int):
    n = a.shape[-1]
    if n <= r or n % 2:
        eye = jnp.eye(n, dtype=a.dtype)
        t, p, m = eye - a, a, 2
        while m < n:                  # p = a^(m/2) -> a^m; a^n = 0
            p = _exact(p, p)
            t = _exact(t, eye + p)
            m *= 2
        return t
    h = n // 2
    t11, t22 = _inverse_by_blocks(
        jnp.stack([a[..., :h, :h], a[..., h:, h:]]), r)
    t21 = -_exact(_exact(t22, a[..., h:, :h]), t11)
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(t11)], -1),
        jnp.concatenate([t21, t22], -1)], -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, r):
    """T = (I + a)^-1 for a [..., n, n] strictly lower triangular, float32,
    the diagonal blocks of ``r`` rows by the Neumann product. Its backward
    is the inverse's own, dA = -T^T dT T^T from T alone: two products, where
    autodiff through the blocks' products makes twenty."""
    return _inverse_by_blocks(a, r)


def _inverse_fwd(a, r):
    t = _inverse_by_blocks(a, r)
    return t, t


def _inverse_bwd(r, t, dt):
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
        jnp.where(strict, sk * beta[..., None], 0.0), _SUB).astype(dt)
    last = cum[:, :, -1:, :]                                 # [b, h, 1, d]
    return _chunk_tail(state, t, sq, q, k, v, beta[..., None], jnp.exp(cum),
                       jnp.exp(last - cum), jnp.exp(last)[:, :, 0, :, None],
                       scale)


def _chunk_tail(state, t, sq, q, k, v, beta, kept, to_end, at_end,
                scale: float):
    """What a chunk does once its solve T and its decayed query-key scores
    ``sq`` are made, whatever the decay's shape: W, U, what each token
    writes, the output and the state after the chunk. ``kept`` = exp(G)
    (the decay since the chunk began) and ``to_end`` = exp(G_C - G), [..,
    C, d_k] for a decay a key channel or [.., C, 1] for one a head;
    ``at_end`` = exp(G_C), [.., d_k, 1] or [.., 1, 1]; beta [.., C, 1]; q
    and k broadcast against them. -> (state, o [.., C, d_v])."""
    dt = q.dtype
    held = state.astype(dt)
    kf, qf = k.astype(_F32), q.astype(_F32)
    bk = (kf * beta * kept).astype(dt)
    bv = (v.astype(_F32) * beta).astype(dt)
    w = _ein("...ij,...jd->...id", t, bk).astype(dt)
    u = _ein("...ij,...jd->...id", t, bv)                    # f32
    wrote = (u - _ein("...id,...de->...ie", w, held)).astype(dt)
    q_in = (qf * kept).astype(dt)
    o = _ein("...id,...de->...ie", q_in, held) \
        + _ein("...ij,...je->...ie", sq.astype(dt), wrote)
    k_end = (kf * to_end).astype(dt)
    state = at_end * state + _ein("...id,...ie->...de", k_end, wrote)
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


def _head_decay_chunk_body(state, xs, *, scale: float, r: int):
    """One chunk of the delta rule with ONE decay a value head and token.
    state [B, Hk, R, d_k, d_v] f32 (R value heads to a key head); xs = (q,
    k [B, Hk, C, d_k], v [B, Hk, R, C, d_v], g, beta [B, Hk, R, C] f32) ->
    (the state after the chunk, o [B, Hk, R, C, d_v]). The decay factors
    out of the contraction over the key channels: the scores are ONE
    product a key head and a [C, C] table of decays a value head."""
    q, k, v, g, beta = xs
    dt = q.dtype
    chunk = q.shape[2]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    cum = jnp.einsum("ij,bhrj->bhri", lower.astype(_F32), g,
                     precision=jax.lax.Precision.HIGHEST)
    decay = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    sk = _ein("bhid,bhjd->bhij", k, k)[:, :, None] * decay   # [b, h, r, C, C]
    sq = _ein("bhid,bhjd->bhij", q, k)[:, :, None] * decay
    t = _unit_lower_inverse(jnp.where(
        jnp.tril(lower, -1), sk * beta[..., None], 0.0), r).astype(dt)
    last = cum[..., -1:]                                     # [b, h, r, 1]
    return _chunk_tail(state, t, sq, q[:, :, None], k[:, :, None], v,
                       beta[..., None], jnp.exp(cum)[..., None],
                       jnp.exp(last - cum)[..., None],
                       jnp.exp(last)[..., None], scale)


def _head_decay_chunked(q, k, v, g, beta, key_heads: int, chunk: int,
                        scale: float, r: int):
    """Whole chunks of merged arrays, q, k [B, T, Hk*d_k], v [B, T,
    Hv*d_v], g, beta [B, T, Hv] -> o [B, T, Hv*d_v]; value head j reads
    key head j // (Hv / Hk)."""
    b, t, _ = q.shape
    group = g.shape[-1] // key_heads

    def chunks_first(x, *heads):     # [B, T, ..] -> [chunks, B, *heads, C, ..]
        n = len(heads)
        x = x.reshape(b, t // chunk, chunk, *heads, -1)
        return jnp.moveaxis(x, (1,) + tuple(range(3, 3 + n)),
                            (0,) + tuple(range(2, 2 + n)))

    xs = (chunks_first(q, key_heads), chunks_first(k, key_heads),
          chunks_first(v, key_heads, group),
          chunks_first(g, key_heads, group)[..., 0],
          chunks_first(beta, key_heads, group)[..., 0])
    dk, dv = xs[1].shape[-1], xs[2].shape[-1]
    body = jax.checkpoint(functools.partial(_head_decay_chunk_body,
                                            scale=scale, r=r))
    _, o = jax.lax.scan(
        body, jnp.zeros((b, key_heads, group, dk, dv), _F32), xs)
    # [chunks, B, Hk, R, C, d_v] -> [B, T, Hv * d_v]
    return jnp.moveaxis(o, (0, 4), (1, 2)).reshape(
        b, t, key_heads * group * dv)


# ---------------------------------------------------------------------------
# the kernel route: what both kernels share
# ---------------------------------------------------------------------------
#
# A program is one chunk of one block of heads; a head is one 128-lane tile
# of the merged arrays. The state is held TRANSPOSED, St [d_v, d_k] (and so
# is the residual the forward writes): what decays it, exp(G_C), is one
# number a key channel, and with the key channels along the lanes that is a
# row spread down the sublanes, never a [d_k, 1] column.

_MAX_HEADS_PER_BLOCK = 4
_HEAD_ROWS = 8    # the sublanes of the tile on which a block's numbers a
#                   head and token are worked (one decay a head): its heads
#                   the first rows

_mul, _add, _minus = jax.lax.mul, jax.lax.add, jax.lax.sub


def _exact_dot(a, b, contract):
    """A float32 product at full float32 precision."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _heads_per_block(heads: int) -> int:
    """Heads a program works: as many as divide the heads, an even number
    (the solve takes them in pairs) unless the heads are odd."""
    return max(n for n in range(1, _MAX_HEADS_PER_BLOCK + 1)
               if heads % n == 0 and (n % 2 == 0 or heads % 2))


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _rows(v, lo: int, n: int):
    return jax.lax.slice(v, (lo, 0), (lo + n, v.shape[1]))


def _cols(v, lo: int, n: int):
    return jax.lax.slice(v, (0, lo), (v.shape[0], lo + n))


def _zeros_like(v):
    return jax.lax.full_like(v, 0)


def _full(shape, value, dtype=_F32):
    return jax.lax.full(shape, value, dtype)


_eq, _ge, _gt, _lt = jax.lax.eq, jax.lax.ge, jax.lax.gt, jax.lax.lt


def _is(v, n: int):
    """Where the integer array v is n."""
    return _eq(v, jax.lax.full_like(v, n))


def _below(v, n: int):
    """Where the integer array v is under n."""
    return _lt(v, jax.lax.full_like(v, n))


def _decay(d):
    """exp of a difference of cumulative gates that is <= 0 wherever it is
    used; where it is not (and is masked later) it is held to 1."""
    return jax.lax.exp(jax.lax.min(d, _zeros_like(d)))


def _block_pieces(qf, kf, cum, lo: int, r: int):
    return _rows(qf, lo, r), _rows(kf, lo, r), _rows(cum, lo, r)


def _against_earlier(qb, kb, gb, kf, cum, dt):
    """A block row against the columns before it, both sides scaled against
    the block row's first token -> (rows [2r, d] of q then k, their scale,
    cols [C, d], their scale); columns at or after the block row hold
    garbage <= |k| and are masked by the caller."""
    r, d = gb.shape
    first = _rows(gb, 0, 1)
    up = jax.lax.exp(_minus(gb, spread(first, (r, d))))
    up = jax.lax.concatenate([up, up], 0)
    down = _decay(_minus(spread(first, cum.shape), cum))
    rows = _mul(jax.lax.concatenate([qb, kb], 0), up).astype(dt)
    return rows, up, _mul(kf, down).astype(dt), down


def _blocks(x, r: int):
    """[C, w] -> [C / r, r, w]: the sub-blocks apart, a block's rows the
    sublanes of its own tiles (no data moves at r = 8)."""
    c, w = x.shape
    return jax.lax.reshape(x, (c // r, r, w))


def _own_column(shape, j: int):
    """Where the lane of [blocks, r, C] is column j of the block's own
    diagonal sub-block."""
    r = shape[1]
    return _is(_minus(_iota(shape, 2), _mul(
        _iota(shape, 0), _full(shape, r, jnp.int32))), j)


def _row_of_blocks(x3, j: int):
    """Row j of every block of [blocks, r, w], down the block's rows."""
    n, _, w = x3.shape
    return jax.lax.broadcast_in_dim(
        jax.lax.slice(x3, (0, j, 0), (n, j + 1, w)), x3.shape, (0, 1, 2))


def _diagonal(qf, kf, cum, r: int):
    """The diagonal sub-blocks of every block row at once: the [r, r, d]
    differences themselves, column by column on the VPU -> (Q, K) [C, C],
    zero outside the diagonal sub-blocks (and unmasked inside them)."""
    c, d = qf.shape
    q3, k3, g3 = (_blocks(x, r) for x in (qf, kf, cum))
    shape = (c // r, r, c)
    sq = sk = _full(shape, 0)
    along = lambda x: jax.lax.broadcast_in_dim(              # noqa: E731
        lane_sum(x, 2), shape, (0, 1, 2))
    for j in range(r):
        kj = _mul(_row_of_blocks(k3, j),
                  _decay(_minus(g3, _row_of_blocks(g3, j))))
        here = _own_column(shape, j)
        sq = jax.lax.select(here, along(_mul(q3, kj)), sq)
        sk = jax.lax.select(here, along(_mul(k3, kj)), sk)
    return jax.lax.reshape(sq, (c, c)), jax.lax.reshape(sk, (c, c))


def _diagonal_bwd(qf, kf, cum, dsq, dsk, r: int):
    """``_diagonal``'s gradients: dsq, dsk [C, C] the scores' gradients
    (only the diagonal sub-blocks are read) -> (dq, dk of the rows, dk of
    the tokens as columns) [C, d]."""
    c, d = qf.shape
    q3, k3, g3 = (_blocks(x, r) for x in (qf, kf, cum))
    dq3, dk3 = _blocks(dsq, r), _blocks(dsk, r)
    zero = _zeros_like(dq3)
    own = lambda x, here: jax.lax.broadcast_in_dim(          # noqa: E731
        lane_sum(jax.lax.select(here, x, zero), 2), q3.shape, (0, 1, 2))
    sub = _iota(q3.shape, 1)
    dq = dk = dc = _full(q3.shape, 0)
    for j in range(r):
        e = _decay(_minus(g3, _row_of_blocks(g3, j)))
        kj = _mul(_row_of_blocks(k3, j), e)
        here = _own_column(dq3.shape, j)
        cq, ck = own(dq3, here), own(dk3, here)
        dq = _add(dq, _mul(cq, kj))
        dk = _add(dk, _mul(ck, kj))
        got = lane_sum(_mul(_add(_mul(cq, q3), _mul(ck, k3)), e), 1)
        dc = jax.lax.select(_is(sub, j), jax.lax.broadcast_in_dim(
            got, q3.shape, (0, 1, 2)), dc)
    return tuple(jax.lax.reshape(x, (c, d)) for x in (dq, dk, dc))


# A head's scores and their backward are two thirds of the equations of a
# head's chunk and are no part of the chain the heads' stages overlap in:
# under ``jax.jit`` each is traced ONCE for all of a block's heads and for
# both kernels' bodies (they are lowered inline, where they are called).


@functools.partial(jax.jit, static_argnums=(3, 4))
def _scores(qf, kf, cum, dt, r: int):
    """q, k [C, d] float32, cum [C, d] -> (Q, K) [C, C] float32 as
    ``_decayed_scores`` makes them: Q_ij for i >= j, K_ij for i > j."""
    c, d = qf.shape
    lane = _iota((2 * r, c), 1)
    bands = [_full((2 * r, c), 0)]
    for b in range(1, c // r):
        rows, _, cols, _ = _against_earlier(
            *_block_pieces(qf, kf, cum, b * r, r), kf, cum, dt)
        off = dot(rows, cols, ABT)                           # [2r, C]
        bands.append(jax.lax.select(_below(lane, b * r), off,
                                    _zeros_like(off)))
    sq, sk = _diagonal(qf, kf, cum, r)
    sq = _add(sq, jax.lax.concatenate([_rows(x, 0, r) for x in bands], 0))
    sk = _add(sk, jax.lax.concatenate([_rows(x, r, r) for x in bands], 0))
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    return (jax.lax.select(_ge(row, col), sq, _zeros_like(sq)),
            jax.lax.select(_gt(row, col), sk, _zeros_like(sk)))


@functools.partial(jax.jit, static_argnums=(5, 6))
def _scores_bwd(qf, kf, cum, dsq, dsk, dt, r: int):
    """The gradients of ``_scores``: dsq, dsk [C, C] (zero where the score
    is masked) -> (dq, dk as a row's key, dk as a column's key) [C, d].
    The gates' share is elementwise from these (the caller's): dG_i +=
    x_i dx_i for the rows, dG_j -= k_j dk_j for the columns."""
    c, d = qf.shape
    lane = _iota((2 * r, c), 1)
    dq, dk, dcol = _diagonal_bwd(qf, kf, cum, dsq, dsk, r)
    bands_q, bands_k = [_full((r, d), 0)], [_full((r, d), 0)]
    for b in range(1, c // r):
        lo = b * r
        rows, up, cols, down = _against_earlier(
            *_block_pieces(qf, kf, cum, lo, r), kf, cum, dt)
        both = jax.lax.concatenate([_rows(dsq, lo, r), _rows(dsk, lo, r)], 0)
        both = jax.lax.select(_below(lane, lo), both, _zeros_like(both)
                              ).astype(dt)
        drows = _mul(dot(both, cols, AB), up)                # [2r, d]
        bands_q.append(_rows(drows, 0, r))
        bands_k.append(_rows(drows, r, r))
        dcol = _add(dcol, _mul(dot(both, rows, ATB), down))
    return (_add(dq, jax.lax.concatenate(bands_q, 0)),
            _add(dk, jax.lax.concatenate(bands_k, 0)), dcol)


def _same_block(shape, size: int):
    """Where row and column of side-by-side [C, C] matrices [C, n C] lie in
    one block of ``size`` (a power of two) of their own matrix."""
    shift = _full(shape, size.bit_length() - 1, jnp.int32)
    col = jax.lax.bitwise_and(
        _iota(shape, 1), _full(shape, shape[0] - 1, jnp.int32))
    return _eq(jax.lax.shift_right_logical(_iota(shape, 0), shift),
               jax.lax.shift_right_logical(col, shift))


def _apart(y):
    """Side-by-side matrices [C, n C] -> the block-diagonal [n C, n C] that
    multiplies side-by-side matrices from the right each by its own."""
    c, w = y.shape
    if w == c:
        return y
    first, zero = _below(_iota(y.shape, 1), c), _zeros_like(y)
    return jax.lax.concatenate([jax.lax.select(first, y, zero),
                                jax.lax.select(first, zero, y)], 0)


def _solve(a, r: int):
    """``_inverse_by_blocks`` of one [C, C] matrix or of two side by side,
    [C, 2 C] (two heads a product: the MXU's time is the rows that stream
    through it, and a [C, 2 C] x [2 C, 2 C] product streams as many as a
    [C, C] x [C, C] one): the r x r diagonal blocks by the Neumann product
    (the products of block-diagonal matrices are the blocks' products), then
    the blocks merged pair by pair, T <- T - T L T with L the part of a that
    joins the pair.

    ``a`` may be a LIST of such matrices of one shape -> the list of their
    solves, made in lock step: a solve is a chain of ten products, each
    waiting for the one before, and the chains of different matrices are
    independent, so side by side in the program they overlap in the MXU
    (the same products on the same numbers, in another order of issue)."""
    if not isinstance(a, (list, tuple)):
        return _solve([a], r)[0]
    shape = a[0].shape
    c = shape[0]
    eye = _same_block(shape, 1).astype(_F32)
    zero = _zeros_like(a[0])
    inside = _same_block(shape, r)
    p = [jax.lax.select(inside, x, zero) for x in a]
    t, m = [_minus(eye, x) for x in p], 2
    while m < r:
        p = [_exact_dot(x, _apart(x), AB) for x in p]
        t = [_exact_dot(y, _apart(_add(eye, x)), AB) for x, y in zip(p, t)]
        m *= 2
    while r < c:
        inside, around = _same_block(shape, r), _same_block(shape, 2 * r)
        joined = [_exact_dot(y, _apart(jax.lax.select(
            inside, zero, jax.lax.select(around, x, zero))), AB)
            for x, y in zip(a, t)]
        t = [_minus(y, _exact_dot(j, _apart(y), AB))
             for j, y in zip(joined, t)]
        r *= 2
    return t


def _solve_heads(a, r: int):
    """[T of each A in ``a``], the heads two to a product and the pairs in
    lock step (``_solve``)."""
    c = a[0].shape[0]
    pairs = [jax.lax.concatenate(a[i:i + 2], 1)
             for i in range(0, len(a) - 1, 2)]
    solved = _solve(pairs, r) if len(pairs) > 1 \
        else [_solve(x, r) for x in pairs]
    out = [part for t in solved
           for part in (_cols(t, 0, c), _cols(t, c, c))]
    return out + [_solve(x, r) for x in a[len(out):]]


def _staged(heads, **stages):
    """``f[name] = stage(f)`` for every head's dict f, a stage at a time:
    a head's chunk is a chain of products each waiting for the last, the
    heads' chains are independent, and side by side in the program they
    overlap in the MXU (as the pairs' solves do, ``_solve``)."""
    for name, stage in stages.items():
        for f in heads:
            f[name] = stage(f)


def _chunk_forward(heads, dt):
    """A block's heads from their scores and solves up to what they write,
    a stage at a time for all of them (``_staged``). Each head a dict: qf,
    kf, vf [C, d] float32, cum [C, d], bcol [C, d] (beta_i in every lane),
    st [d_v, d_k] float32 the state the chunk starts from, transposed, sq
    and t [C, C]; it gains what the forward and the backward both use."""
    c, d = heads[0]["qf"].shape
    _staged(
        heads,
        kept=lambda f: jax.lax.exp(f["cum"]),
        bkv=lambda f: jax.lax.concatenate(
            [_mul(_mul(f["kf"], f["bcol"]), f["kept"]),
             _mul(f["vf"], f["bcol"])], 1).astype(dt),           # [C, 2d]
        wu=lambda f: dot(f["t"].astype(dt), f["bkv"], AB),
        w=lambda f: _cols(f["wu"], 0, d).astype(dt),
        q_in=lambda f: _mul(f["qf"], f["kept"]).astype(dt),
        held=lambda f: f["st"].astype(dt),
        from_state=lambda f: dot(
            jax.lax.concatenate([f["w"], f["q_in"]], 0), f["held"], ABT),
        wrote=lambda f: _minus(_cols(f["wu"], d, d),
                               _rows(f["from_state"], 0, c)).astype(dt),
        to_end=lambda f: jax.lax.exp(_minus(
            spread(_rows(f["cum"], c - 1, 1), (c, d)), f["cum"])),
        k_end=lambda f: _mul(f["kf"], f["to_end"]).astype(dt),
        q_state=lambda f: _rows(f["from_state"], c, c),
        at_end=lambda f: jax.lax.exp(_rows(f["cum"], c - 1, 1)))


def _three(x):
    """Float32 -> three bfloat16 pieces whose sum is x to 2^-24 of it: a
    product of x with ones and zeros is then three passes of the MXU, each
    exact, where ``_exact_dot`` splits both sides and makes six."""
    pieces = []
    for _ in range(3):
        pieces.append(x.astype(jnp.bfloat16))
        x = _minus(x, pieces[-1].astype(_F32))
    return pieces


def _triangle_sums(x, contract):
    """The triangle of ones times x [C, w] (``AB``: the inclusive
    cumulative sum over the chunk's tokens) or its transpose times x
    (``ATB``: the sums from each token to the chunk's end), float32."""
    c = x.shape[0]
    tri = _ge(_iota((c, c), 0), _iota((c, c), 1)).astype(jnp.bfloat16)
    a, b, e = (dot(tri, piece, contract) for piece in _three(x))
    return _add(_add(a, b), e)


def _beta_column(row):
    """A head's dense row of beta [1, C] -> [C, 128] with beta_i down the
    rows in every lane: the row on the diagonals of three [C, C] pieces
    times ones, on the MXU (a [C, 1] column costs a register a sublane
    whatever is done to it)."""
    c = row.shape[1]
    row = spread(row, (c, c))
    on = _eq(_iota((c, c), 0), _iota((c, c), 1))
    pieces = _three(jax.lax.select(on, row, _zeros_like(row)))
    return dot(jax.lax.concatenate(pieces, 1),
               _full((3 * c, LANES), 1, jnp.bfloat16), AB)


def _heads_scores(heads, dt, r: int):
    """What the solve needs of a block's heads, each (q, k, v [C, 128] in
    the model's dtype, its cumulative gates, its row of beta, the start
    state, ...) -> a dict a head: q, k, v in float32, the gates, beta's
    column, the state, Q, K and T, the heads' solves in lock step
    (``_solve_heads``)."""
    out = []
    for q, k, v, cum, beta, st, *_ in heads:
        qf, kf = q.astype(_F32), k.astype(_F32)
        sq, sk = _scores(qf, kf, cum, dt, r)
        out.append(dict(qf=qf, kf=kf, vf=v.astype(_F32), cum=cum,
                        bcol=_beta_column(beta), st=st, sq=sq, sk=sk))
    c = cum.shape[0]
    solved = _solve_heads(
        [_mul(_cols(f["bcol"], 0, c), f["sk"]) for f in out], r)
    for f, t in zip(out, solved):
        f["t"] = t
    return out


# The prologue: what ``kda_gated_scan``'s caller left to the kernels. Pure
# functions of tiles that are in VMEM already; ``kda_scan``'s caller made q,
# k and g itself and the bodies skip them (``eps`` None: a fact of the
# trace).


def _unit(x, eps: float):
    """A head's tile x [C, 128] float32 -> (x / sqrt(sum over the lanes of
    x^2 + eps): ``layers.l2norm``, and that reciprocal root in every
    lane)."""
    inv = jax.lax.rsqrt(_add(lane_sum(_mul(x, x), 1),
                             _full((x.shape[0], 1), eps)))
    inv = spread(inv, x.shape)
    return _mul(x, inv), inv


def _unit_bwd(dy, y, inv):
    """``_unit``'s gradient from its two results: inv (dy - y sum over the
    lanes of dy y)."""
    return _mul(inv, _minus(dy, _mul(y, spread(lane_sum(_mul(dy, y), 1),
                                               y.shape))))


def _normed(heads, eps):
    """Each head's q and k (its first two tiles) of unit length in float32
    and the heads' (r_q, r_k), the reciprocal roots the backward's chain
    rule takes; with ``eps`` None the heads as the caller normalised
    them."""
    if eps is None:
        return heads, [None] * len(heads)
    out, inv = [], []
    for q, k, *rest in heads:
        (q, rq), (k, rk) = (_unit(x.astype(_F32), eps) for x in (q, k))
        out.append((q, k, *rest))
        inv.append((rq, rk))
    return out, inv


def _gate(g_ref, rows_ref=None, *, slope: bool = False):
    """A program's g [C, W] float32 -> (g, d g / d step where ``slope``
    asks for it and the prologue made g, else None). ``g_ref`` alone holds
    g as the caller made it; with ``rows_ref`` [2, W] float32 (A_log, a
    head's over its 128 channels, and dt_bias) it holds the gate
    projection's step and g = -exp(A_log) softplus(step + dt_bias), the
    softplus as max(x, 0) + log1p(exp(-|x|)): nothing overflows."""
    if rows_ref is None:
        return g_ref[...], None
    x = g_ref[...].astype(_F32)
    rows = rows_ref[...]
    x = _add(x, spread(_rows(rows, 1, 1), x.shape))
    neg_a = spread(jax.lax.neg(jax.lax.exp(_rows(rows, 0, 1))), x.shape)
    soft = _add(jax.lax.max(x, _zeros_like(x)),
                jax.lax.log1p(jax.lax.exp(jax.lax.neg(jax.lax.abs(x)))))
    return _mul(neg_a, soft), (
        _mul(neg_a, jax.lax.logistic(x)) if slope else None)


def _tiles(refs, h: int):
    """Head h's [C, 128] tile of each merged ref."""
    return tuple(x[:, pl.ds(h * LANES, LANES)] for x in refs)


# A program's whole block of heads as a PURE function of values under
# ``jax.jit``: a train step holds nine instances of these kernels (three
# runs of the walker x forward sweep, rematerialised forward, backward);
# jit's cache hands every one of them the body traced ONCE, and ``setup_s``
# pays for every equation traced. All of the block's heads in one body, and
# not a pair at a time: a head's chunk is a chain of products each waiting
# for the last (the solve's ten at ``Precision.HIGHEST`` first of all), and
# the body issues every stage for all the heads before the next, the two
# pairs' solves in lock step (``_solve_heads``, ``_staged``).


@functools.partial(jax.jit, static_argnames=("scale", "r", "eps"))
def _forward_of(heads, *, scale: float, r: int, eps):
    """``heads``: a block's (q, k, v, cumulative gates, beta's row, the
    start state [d_v, d_k]) -> per head (o scaled in q's dtype, the state
    the chunk ends in). ``eps``: the l2 norm's, where q and k come as the
    convolutions left them; None where the caller normalised them."""
    dt, d = heads[0][0].dtype, LANES
    heads = _heads_scores(_normed(heads, eps)[0], dt, r)
    _chunk_forward(heads, dt)
    _staged(
        heads,
        o=lambda f: _add(f["q_state"],
                         dot(f["sq"].astype(dt), f["wrote"], AB)),
        ended=lambda f: _add(_mul(spread(f["at_end"], (d, d)), f["st"]),
                             dot(f["wrote"], f["k_end"], ATB)))
    return [(_mul(f["o"], jax.lax.full_like(f["o"], scale)).astype(dt),
             f["ended"]) for f in heads]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale: float, r: int, eps):
    """Grid (B, head blocks, chunks), the chunks in order. ``refs``: g (or
    the prologue's step and rows, ``_gate``), beta, then o, the chunk's
    start state and ``s_scr`` [heads x d_v, d_k] f32: the block's states,
    transposed, carried over the chunks."""
    *gate, beta_ref, o_ref, st_ref, s_scr = refs
    d = LANES

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_scr[...] = _full(s_scr.shape, 0)

    st_ref[...] = s_scr[...]
    cum_all = _triangle_sums(_gate(*gate)[0], AB)
    done = _forward_of(tuple(
        _tiles((q_ref, k_ref, v_ref), h)
        + (_cols(cum_all, h * d, d), beta_ref[pl.ds(h, 1), :],
           s_scr[pl.ds(h * d, d), :]) for h in range(q_ref.shape[1] // d)),
        scale=scale, r=r, eps=eps)
    for h, (o, st) in enumerate(done):
        o_ref[:, pl.ds(h * d, d)] = o
        s_scr[pl.ds(h * d, d), :] = st


def _specs(t: int, chunk: int, hpb: int, reverse: bool, key_heads: int = 0):
    """Block specs on the grid (B, head blocks, chunks); ``reverse`` walks
    the chunks from the last to the first. ``key_heads``: the key heads a
    block of ``hpb`` value heads reads, for the body with one decay a
    head."""
    nc = t // chunk
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    w = hpb * LANES
    specs = {
        "x": pl.BlockSpec((None, chunk, w), lambda b, k, c: (b, at(c), k)),
        "beta": pl.BlockSpec((None, None, None, hpb, chunk),
                             lambda b, k, c: (b, k, at(c), 0, 0)),
        # the prologue's two rows, and their gradients' partial sums a
        # batch row: one block over the whole sequential axis
        "rows": pl.BlockSpec((2, w), lambda b, k, c: (0, k)),
        "drows": pl.BlockSpec((None, 2, w), lambda b, k, c: (b, 0, k)),
        "state": pl.BlockSpec((None, None, w, LANES),
                              lambda b, k, c: (b, at(c), k, 0)),
    }
    if key_heads:
        specs.update({
            "keys": pl.BlockSpec((None, chunk, key_heads * LANES),
                                 lambda b, k, c: (b, at(c), k)),
            # A_log over 8 rows and dt_bias over 8 more, a block's heads
            # the first of each (``_head_gate``), and their gradients'
            # partial sums a batch row and token of the chunk
            "head_rows": pl.BlockSpec((None, 2 * _HEAD_ROWS, chunk),
                                      lambda b, k, c: (k, 0, 0)),
            "head_drows": pl.BlockSpec((None, None, 2 * _HEAD_ROWS, chunk),
                                       lambda b, k, c: (b, k, 0, 0)),
        })
    return specs


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES)


def _gate_specs(s, gate):
    """The block specs of ``gate``: (g,), or the prologue's (step, rows)."""
    return [s["x"]] + [s["rows"]] * (len(gate) - 1)


def _gate_bytes(gate) -> int:
    """What a kernel reads of ``gate`` (the backward writes as much)."""
    return sum(x.size * x.dtype.itemsize for x in gate)


def _kda_fwd(q, k, v, gate, beta_t, scale, hpb, eps):
    """q, k, v [B, T, H*128], ``gate`` (g,) or the prologue's (step [B, T,
    H*128], rows [2, H*128]), beta_t [B, H/hpb, T/C, hpb, C] -> (o [B, T,
    H*128], states [B, T/C, H*128, 128] f32: the state each chunk starts
    from, transposed)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = q.shape
    chunk = beta_t.shape[-1]
    nc, h = t // chunk, hd // LANES
    s = _specs(t, chunk, hpb, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, r=_SUB, eps=eps),
        grid=(b, h // hpb, nc),
        in_specs=[s["x"]] * 3 + _gate_specs(s, gate) + [s["beta"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, nc, hd, LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((hpb * LANES, LANES), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * hd * (4 * chunk + 3 * LANES),
            bytes_accessed=q.size * 4 * q.dtype.itemsize + _gate_bytes(gate)
            + 4 * b * nc * hd * LANES,
            transcendentals=b * t * hd * (_SUB + 10 + 2 * (len(gate) - 1))),
    )(q, k, v, *gate, beta_t)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "r", "eps"))
def _backward_of(heads, *, scale: float, r: int, eps):
    """``heads``: a block's (q, k, v, cumulative gates, beta's row, the
    start state, dO, the END state's gradient) -> per head (dq, dk, dv in
    q's dtype, d(cumulative gates) [C, 128], dbeta's row [1, C], the START
    state's gradient). The chunk's matrices are made again first; with
    ``eps`` (``_forward_of``) dq and dk are the RAW q's and k's, through
    the norm. A stage at a time for all the heads, as the forward."""
    dt, d = heads[0][0].dtype, LANES
    c = heads[0][0].shape[0]
    given, norms = _normed(heads, eps)
    heads = _heads_scores(given, dt, r)
    _chunk_forward(heads, dt)
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    seen, earlier = _ge(row, col), _gt(row, col)
    zero = _full((c, c), 0)
    ones = _full((8, d + c), 1)
    last_row = _is(_iota((c, d), 0), c - 1)
    for f, (*_, do, dst) in zip(heads, given):
        do = do.astype(_F32)
        f.update(do=_mul(do, jax.lax.full_like(do, scale)).astype(dt),
                 dst=dst, dsd=dst.astype(dt), bc=_cols(f["bcol"], 0, c))
    _staged(
        heads,
        # what each token wrote: through o and through the end state
        dwrote=lambda f: _add(dot(f["sq"].astype(dt), f["do"], ATB),
                              dot(f["k_end"], f["dsd"], ABT)),    # [C, d_v]
        dwd=lambda f: f["dwrote"].astype(dt),
        dsq=lambda f: jax.lax.select(
            seen, dot(f["do"], f["wrote"], ABT), zero),
        dq_in=lambda f: dot(f["do"], f["held"], AB),            # [C, d_k]
        dk_end=lambda f: dot(f["wrote"], f["dsd"], AB),
        dwu=lambda f: jax.lax.concatenate(
            [jax.lax.neg(dot(f["dwd"], f["held"], AB)), f["dwrote"]],
            1).astype(dt),
        d_t=lambda f: dot(f["dwu"], f["bkv"], ABT),             # [C, C]
        dbkv=lambda f: dot(f["t"].astype(dt), f["dwu"], ATB),   # [C, 2d]
        dbk=lambda f: _cols(f["dbkv"], 0, d),
        dbv=lambda f: _cols(f["dbkv"], d, d),
        # the solve's own backward, dA = -T^T dT T^T
        half=lambda f: _exact_dot(f["t"], f["d_t"], ATB),
        da=lambda f: jax.lax.select(
            earlier, jax.lax.neg(_exact_dot(f["half"], f["t"], ABT)), zero),
        dsk=lambda f: _mul(f["da"], f["bc"]),
        # the state the chunk starts from
        decayed=lambda f: _mul(f["dst"], spread(f["at_end"], (d, d))),
        dst_start=lambda f: _minus(
            _add(f["decayed"], dot(f["do"], f["q_in"], ATB)),
            dot(f["dwd"], f["w"], ATB)),
        d_last=lambda f: lane_sum(_mul(f["decayed"], f["st"]), 0),  # [1, d]
        # beta: through A's rows, beta k exp(G) and beta v; a row of sums
        # over the lanes by a product with ones, the tokens along the lanes
        k_kept=lambda f: _mul(f["kf"], f["kept"]),
        sums=lambda f: jax.lax.concatenate(
            [_add(_mul(f["dbk"], f["k_kept"]), _mul(f["dbv"], f["vf"])),
             _mul(f["da"], f["sk"])], 1),
        dbeta=lambda f: _rows(_exact_dot(ones, f["sums"], ABT), 0, 1),
        # the scores: (dq, dk as a row's key, dk as a column's key)
        dscores=lambda f: _scores_bwd(f["qf"], f["kf"], f["cum"], f["dsq"],
                                      f["dsk"], dt, r),
        ended=lambda f: _mul(f["dk_end"], _mul(f["kf"], f["to_end"])),
        dq=lambda f: _add(_mul(f["dq_in"], f["kept"]), f["dscores"][0]),
        dk=lambda f: _add(
            _add(_mul(_mul(f["dbk"], f["bcol"]), f["kept"]),
                 _mul(f["dk_end"], f["to_end"])),
            _add(f["dscores"][1], f["dscores"][2])),
        at_last=lambda f: _add(f["d_last"], lane_sum(f["ended"], 0)),
        dc=lambda f: _add(
            _add(_minus(_add(_mul(f["dbk"], _mul(f["k_kept"], f["bcol"])),
                             _mul(f["dq_in"], _mul(f["qf"], f["kept"]))),
                        f["ended"]),
                 _add(_mul(f["qf"], f["dscores"][0]),
                      _mul(f["kf"], _minus(f["dscores"][1],
                                           f["dscores"][2])))),
            jax.lax.select(last_row, spread(f["at_last"], (c, d)),
                           _full((c, d), 0))))
    out = []
    for f, inv in zip(heads, norms):
        dq, dk = f["dq"], f["dk"]
        if inv is not None:
            dq = _unit_bwd(dq, f["qf"], inv[0])
            dk = _unit_bwd(dk, f["kf"], inv[1])
        out.append((dq.astype(dt), dk.astype(dt),
                    _mul(f["dbv"], f["bcol"]).astype(dt), f["dc"],
                    f["dbeta"], f["dst_start"]))
    return out


def _bwd_kernel(q_ref, k_ref, v_ref, *refs, scale: float, r: int, eps):
    """Grid (B, head blocks, chunks from the last). ``refs``: g (or the
    prologue's step and rows), beta, the chunks' start states, dO; then dq,
    dk, dv, dg (or dstep in step's dtype and ``drows`` [2, W] f32, the
    rows' gradients summed over the chunks walked so far: the block stays
    in VMEM over the sequential axis), dbeta; and ``ds_scr``, the gradient
    of the state the chunk ENDS in, transposed, carried back over the
    chunks. The chunk's matrices are made again from the inputs and the
    saved start state."""
    n = 1 if eps is None else 2   # g ready, or the prologue's step and rows
    gate = refs[:n]
    (beta_ref, st_ref, do_ref, dq_ref, dk_ref, dv_ref, dgate_ref,
     *drows_ref, dbeta_ref, ds_scr) = refs[n:]
    d = LANES

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = _full(ds_scr.shape, 0)
        for ref in drows_ref:
            ref[...] = _full(ref.shape, 0)

    g, slope = _gate(*gate, slope=True)
    cum_all = _triangle_sums(g, AB)
    done = _backward_of(tuple(
        _tiles((q_ref, k_ref, v_ref), h)
        + (_cols(cum_all, h * d, d), beta_ref[pl.ds(h, 1), :],
           st_ref[pl.ds(h * d, d), :])
        + _tiles((do_ref,), h) + (ds_scr[pl.ds(h * d, d), :],)
        for h in range(q_ref.shape[1] // d)), scale=scale, r=r, eps=eps)
    for h, (dq, dk, dv, _, dbeta, dst) in enumerate(done):
        lanes = pl.ds(h * d, d)
        dq_ref[:, lanes], dk_ref[:, lanes], dv_ref[:, lanes] = dq, dk, dv
        dbeta_ref[pl.ds(h, 1), :] = dbeta
        ds_scr[pl.ds(h * d, d), :] = dst
    # dg from d(cumulative sum): the triangle's product, reversed
    dg = _triangle_sums(
        jax.lax.concatenate([dc for _, _, _, dc, _, _ in done], 1), ATB)
    if slope is None:
        dgate_ref[...] = dg
        return
    # the gate's chain rule: dstep = dg dg/dstep, dA_log = sum dg g (dg /
    # dA_log is g itself), ddt_bias = sum dstep, the sums in float32
    dstep = _mul(dg, slope)
    dgate_ref[...] = dstep.astype(dgate_ref.dtype)
    drows_ref[0][...] = _add(drows_ref[0][...], jax.lax.concatenate(
        [lane_sum(_mul(dg, g), 0), lane_sum(dstep, 0)], 0))


def _kda_bwd(q, k, v, gate, beta_t, states, do, scale, hpb, eps):
    """-> [dq, dk, dv [B, T, H*128] (q's dtype), dg f32 (or the
    prologue's dstep in step's dtype and drows [B, 2, H*128] f32),
    dbeta_t f32]."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = q.shape
    chunk = beta_t.shape[-1]
    nc, h = t // chunk, hd // LANES
    s = _specs(t, chunk, hpb, reverse=True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    drows = [jax.ShapeDtypeStruct((b,) + x.shape, x.dtype) for x in gate[1:]]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, r=_SUB, eps=eps),
        grid=(b, h // hpb, nc),
        in_specs=[s["x"]] * 3 + _gate_specs(s, gate)
        + [s["beta"], s["state"], s["x"]],
        out_specs=[s["x"]] * 4 + [s["drows"]] * len(drows) + [s["beta"]],
        out_shape=[like(q), like(k), like(v), like(gate[0])] + drows
        + [like(beta_t)],
        scratch_shapes=[pltpu.VMEM((hpb * LANES, LANES), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["bwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * hd * (10 * chunk + 8 * LANES),
            bytes_accessed=q.size * 7 * q.dtype.itemsize
            + 2 * _gate_bytes(gate) + 4 * b * nc * hd * LANES,
            transcendentals=b * t * hd * (2 * _SUB + 20
                                          + 3 * (len(gate) - 1))),
    )(q, k, v, *gate, beta_t, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernels(q, k, v, gate, beta_t, scale, hpb, eps):
    return _kda_fwd(q, k, v, gate, beta_t, scale, hpb, eps)[0]


def _kda_vjp_fwd(q, k, v, gate, beta_t, scale, hpb, eps):
    from jax.ad_checkpoint import checkpoint_name

    o, states = _kda_fwd(q, k, v, gate, beta_t, scale, hpb, eps)
    # named so a layer's remat policy can keep them (as ``flash_out``):
    # with both kept the backward does not run the forward kernel again
    o = checkpoint_name(o, "kda_out")
    states = checkpoint_name(states, "kda_states")
    return o, (q, k, v, gate, beta_t, states)


def _kda_vjp_bwd(scale, hpb, eps, res, do):
    dq, dk, dv, *dgate, dbeta = _kda_bwd(*res, do, scale, hpb, eps)
    # the rows' partial sums a batch row -> the rows' gradient
    dgate[1:] = [x.sum(0) for x in dgate[1:]]
    return dq, dk, dv, tuple(dgate), dbeta


_kda_kernels.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)


def _head_major(x, chunk: int, hpb: int):
    """A number a token and head [B, T, H] -> [B, head blocks, chunks, heads
    a block, C]: a program sees dense rows."""
    b, t, heads = x.shape
    return x.reshape(b, t // chunk, chunk, heads // hpb, hpb
                     ).transpose(0, 3, 1, 4, 2)


def _kernel_route(q, k, v, gate, beta, heads: int, chunk: int,
                  scale: float, hpb: int, eps):
    """Whole chunks of merged arrays through the kernel pair; beta goes in
    head-major (``_head_major``)."""
    return _kda_kernels(q, k, v, gate, _head_major(beta, chunk, hpb), scale,
                        hpb, eps)


# ---------------------------------------------------------------------------
# one decay a head (Gated DeltaNet): the body of the same kernel pair
# ---------------------------------------------------------------------------
#
# The module docstring's "One decay a head" block as the kernels' body. A
# program is one chunk of a block of VALUE heads and of the key heads they
# read. What is a [C, 128] tile of gates in KDA's body is a row [1, C] a
# value head here (head-major, as beta comes in): the gate, its cumulative
# sum and the chain rule back to ``a`` are worked on ONE [8, C] tile a
# program, and a row becomes a [C, 128] column (``_columns``) only where a
# product's operand needs one.


def _gdn_heads_per_block(heads: int, group: int):
    """Value heads a program works where ``group`` of them read one key
    head: whole key heads (dq and dk leave the backward kernel summed over a
    key head's value heads, so those are in one program), as many as divide
    the heads, an even number where there is one (the solve takes them in
    pairs). None where no block of ``_MAX_HEADS_PER_BLOCK`` holds a key
    head's value heads: the plain route's."""
    fit = [n for n in range(group, _MAX_HEADS_PER_BLOCK + 1, group)
           if heads % n == 0]
    return max([n for n in fit if n % 2 == 0] or fit, default=None)


def _head_tile(rows):
    """Rows [1, C], one a head -> the [8, C] tile with head h in row h and
    zeros under the heads."""
    shape = (_HEAD_ROWS, rows[0].shape[1])
    sub, out = _iota(shape, 0), _full(shape, 0)
    for h, row in enumerate(rows):
        out = jax.lax.select(_is(sub, h), spread(row, shape), out)
    return out


def _lower_ones(c: int):
    """The [C, C] float32 triangle of ones, row >= column: against a
    head-major tile's tokens, ``ABT`` is the inclusive cumulative sum over
    the chunk and ``AB`` the sums from each token to the chunk's end."""
    return _ge(_iota((c, c), 0), _iota((c, c), 1)).astype(_F32)


def _head_gate(a_ref, rows_ref):
    """A block's ``a`` [heads, C] float32 and its rows [16, C] (A_log of
    head h in row h, dt_bias in row 8 + h, each in every lane) -> (g =
    -exp(A_log) softplus(a + dt_bias) [8, C], d g / d a, the inclusive
    cumulative sums of g over the chunk's tokens): a number a head and
    token, the softplus as ``_gate`` makes it."""
    c = a_ref.shape[1]
    x = _add(_head_tile([a_ref[pl.ds(h, 1), :]
                         for h in range(a_ref.shape[0])]),
             rows_ref[pl.ds(_HEAD_ROWS, _HEAD_ROWS), :])
    neg_a = jax.lax.neg(jax.lax.exp(rows_ref[pl.ds(0, _HEAD_ROWS), :]))
    soft = _add(jax.lax.max(x, _zeros_like(x)),
                jax.lax.log1p(jax.lax.exp(jax.lax.neg(jax.lax.abs(x)))))
    g = _mul(neg_a, soft)
    return (g, _mul(neg_a, jax.lax.logistic(x)),
            _exact_dot(g, _lower_ones(c), ABT))


def _columns(rows):
    """Rows [1, C] -> each one's [C, 128] column as ``_beta_column`` makes
    it (the row's own numbers, exactly), all of them in ONE product."""
    c = rows[0].shape[1]
    on = _eq(_iota((c, c), 0), _iota((c, c), 1))
    zero = _full((c, c), 0)
    diagonals = jax.lax.concatenate([jax.lax.concatenate(_three(
        jax.lax.select(on, spread(row, (c, c)), zero)), 1) for row in rows],
        0)                                                    # [n C, 3 C]
    out = dot(diagonals, _full((3 * c, LANES), 1, jnp.bfloat16), AB)
    return [_rows(out, i * c, c) for i in range(len(rows))]


def _lane_sums(x):
    """x [n, w] float32 -> the sums over its lanes as a ROW [1, n] (the
    tokens along the lanes): three exact passes against ones."""
    ones = _full((_HEAD_ROWS, x.shape[1]), 1, jnp.bfloat16)
    a, b, e = (dot(ones, piece, ABT) for piece in _three(x))
    return _rows(_add(_add(a, b), e), 0, 1)


def _gdn_chunk(keys, values, dt, r: int, eps: float):
    """What both kernels make of a block's chunk. ``keys``: (q, k [C, 128])
    a key head, as the convolution left them; ``values``: (v [C, 128], the
    cumulative gates' row [1, C], beta's row [1, C], the start state [d_v,
    d_k], ...) a value head, ``len(values) // len(keys)`` of them to a key
    head -> (a dict a key head, a dict a value head with the key head's
    under ``key``).

    A key head: q and k of unit length (float32, with the reciprocal
    roots), stacked and rounded to the model's dtype, and Q K^T over K K^T
    in ONE product [2C, 128] x [128, C]. A value head lays its table
    exp(G_i - G_j) over them (of the differences themselves, every one that
    is used <= 0; the column G_i is the row's own numbers, so the diagonal
    is exp(0)), solves, and reads the state once for q and k both: with a
    scalar decay (q e^G) S_0 = e^G (q S_0) and what the tokens write is T
    (beta (v - e^G (k S_0))), no W of the keys' own."""
    c, d = keys[0][0].shape[0], LANES
    group = len(values) // len(keys)
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    seen, earlier = _ge(row, col), _gt(row, col)
    zero = _full((c, c), 0)
    keyed = []
    for q, k in keys:
        (qn, rq), (kn, rk) = (_unit(x.astype(_F32), eps) for x in (q, k))
        qk = jax.lax.concatenate([qn, kn], 0).astype(dt)        # [2C, 128]
        kd = _rows(qk, c, c)
        keyed.append(dict(qn=qn, kn=kn, rq=rq, rk=rk, qk=qk, kd=kd,
                          scores=dot(qk, kd, ABT)))
    heads = []
    columns = _columns([row for _, grow, beta, *_ in values
                        for row in (beta, grow)])
    for i, (v, grow, beta, st, *_) in enumerate(values):
        key = keyed[i // group]
        bcol, gcol = columns[2 * i:2 * i + 2]
        table = _decay(_minus(_cols(gcol, 0, c), spread(grow, (c, c))))
        last = _rows(gcol, c - 1, 1)                            # [1, 128]
        heads.append(dict(
            key=key, vf=v.astype(_F32), st=st, bcol=bcol, table=table,
            sq=jax.lax.select(seen, _mul(_rows(key["scores"], 0, c), table),
                              zero),
            sk=jax.lax.select(earlier, _mul(_rows(key["scores"], c, c),
                                            table), zero),
            kept=jax.lax.exp(gcol), at_end=jax.lax.exp(last),
            to_end=jax.lax.exp(_minus(spread(last, (c, d)), gcol))))
    solved = _solve_heads(
        [_mul(_cols(f["bcol"], 0, c), f["sk"]) for f in heads], r)
    for f, t in zip(heads, solved):
        f.update(t=t, held=f["st"].astype(dt),
                 k_end=_mul(f["key"]["kn"], f["to_end"]).astype(dt))
    _staged(
        heads,
        # q S_0 over k S_0 [2C, d_v]
        from_state=lambda f: dot(f["key"]["qk"], f["held"], ABT),
        q_state=lambda f: _rows(f["from_state"], 0, c),
        k_state=lambda f: _rows(f["from_state"], c, c),
        inner=lambda f: _minus(f["vf"], _mul(f["kept"], f["k_state"])),
        rhs=lambda f: _mul(f["bcol"], f["inner"]).astype(dt),
        wrote=lambda f: dot(f["t"].astype(dt), f["rhs"], AB).astype(dt))
    return keyed, heads


@functools.partial(jax.jit, static_argnames=("scale", "r", "eps"))
def _gdn_forward_of(keys, values, *, scale: float, r: int, eps: float):
    """A block's chunk (``_gdn_chunk``'s arguments) -> a value head (o
    scaled in q's dtype, the state the chunk ends in)."""
    dt, d = keys[0][0].dtype, LANES
    heads = _gdn_chunk(keys, values, dt, r, eps)[1]
    _staged(
        heads,
        o=lambda f: _add(_mul(f["kept"], f["q_state"]),
                         dot(f["sq"].astype(dt), f["wrote"], AB)),
        ended=lambda f: _add(_mul(spread(f["at_end"], (d, d)), f["st"]),
                             dot(f["wrote"], f["k_end"], ATB)))
    return [(_mul(f["o"], jax.lax.full_like(f["o"], scale)).astype(dt),
             f["ended"]) for f in heads]


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, a_ref, rows_ref, beta_ref, o_ref,
                    st_ref, s_scr, *, scale: float, r: int, eps: float):
    """Grid (B, head blocks, chunks), the chunks in order. q and k hold the
    block's KEY heads, v, ``a`` and beta (head-major) its value heads;
    ``s_scr`` [value heads x d_v, d_k] f32 carries their states,
    transposed, over the chunks."""
    d = LANES
    hpb = v_ref.shape[1] // d

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_scr[...] = _full(s_scr.shape, 0)

    st_ref[...] = s_scr[...]
    cum = _head_gate(a_ref, rows_ref)[2]
    done = _gdn_forward_of(
        tuple(_tiles((q_ref, k_ref), h) for h in range(q_ref.shape[1] // d)),
        tuple(_tiles((v_ref,), h)
              + (_rows(cum, h, 1), beta_ref[pl.ds(h, 1), :],
                 s_scr[pl.ds(h * d, d), :]) for h in range(hpb)),
        scale=scale, r=r, eps=eps)
    for h, (o, st) in enumerate(done):
        o_ref[:, pl.ds(h * d, d)] = o
        s_scr[pl.ds(h * d, d), :] = st


def _gdn_fwd(q, k, v, a_t, rows, beta_t, scale, eps, r):
    """q, k [B, T, Hk*128], v [B, T, Hv*128], a_t and beta_t [B, Hv/hpb,
    T/C, hpb, C] float32, rows [Hv/hpb, 16, C] (``_head_gate``) -> (o [B,
    T, Hv*128], states [B, T/C, Hv*128, 128] f32: the state each chunk
    starts from, transposed)."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = v.shape
    hpb, chunk = beta_t.shape[-2:]
    nc, h = t // chunk, hd // LANES
    keys = hpb * q.shape[-1] // hd
    s = _specs(t, chunk, hpb, reverse=False, key_heads=keys)
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, scale=scale, r=r, eps=eps),
        grid=(b, h // hpb, nc),
        in_specs=[s["keys"]] * 2 + [s["x"], s["beta"], s["head_rows"],
                                    s["beta"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, nc, hd, LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((hpb * LANES, LANES), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["gdn_fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * (hd * (8 * chunk + 4 * LANES)
                               + 2 * q.shape[-1] * chunk),
            bytes_accessed=(2 * q.size + 2 * v.size) * q.dtype.itemsize
            + 8 * a_t.size + 4 * b * nc * hd * LANES,
            transcendentals=b * t * h * (chunk + 3 * LANES)),
    )(q, k, v, a_t, rows, beta_t)


def _summed(parts, onto=None):
    """A key head's value heads' shares added up (onto the key head's
    own)."""
    return functools.reduce(_add, parts if onto is None else [onto] + parts)


@functools.partial(jax.jit, static_argnames=("scale", "r", "eps"))
def _gdn_backward_of(keys, values, *, scale: float, r: int, eps: float):
    """``keys``: (q, k) a key head; ``values``: (v, the cumulative gates'
    row, beta's row, the start state, dO, the END state's gradient) a value
    head -> ([(dq, dk) a key head: the RAW q's and k's, through the norm,
    summed over the key head's value heads, in q's dtype], [(dv, d(the
    cumulative gates)'s row [1, C], dbeta's row [1, C], the START state's
    gradient) a value head]). The chunk's matrices are made again first.

    The gates' gradients are numbers a row: with P = dQ Q + dA-side K (the
    two decayed score matrices times their gradients, elementwise) dG_i
    gains the sums of P's row i and loses those of its column i; e^G's and
    e^(G_C - G)'s shares are sums over a row's lanes. Every sum over the
    lanes is one product with ones, the tokens along its lanes."""
    dt, d = keys[0][0].dtype, LANES
    c = keys[0][0].shape[0]
    keyed, heads = _gdn_chunk(keys, values, dt, r, eps)
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    seen, earlier = _ge(row, col), _gt(row, col)
    zero = _full((c, c), 0)
    at_last = _is(_iota((1, c), 1), c - 1)
    for f, (*_, do, dst) in zip(heads, values):
        do = do.astype(_F32)
        do = _mul(do, jax.lax.full_like(do, scale))
        f.update(do=do, dod=do.astype(dt), dst=dst, dsd=dst.astype(dt),
                 tt=f["t"].astype(dt), bc=_cols(f["bcol"], 0, c),
                 decayed=_mul(dst, spread(f["at_end"], (d, d))))
    _staged(
        heads,
        # what each token wrote: through o and through the end state
        dwd=lambda f: _add(dot(f["sq"].astype(dt), f["dod"], ATB),
                           dot(f["k_end"], f["dsd"], ABT)).astype(dt),
        dsq=lambda f: jax.lax.select(
            seen, dot(f["dod"], f["wrote"], ABT), zero),
        dk_end=lambda f: dot(f["wrote"], f["dsd"], AB),        # [C, d_k]
        d_t=lambda f: dot(f["dwd"], f["rhs"], ABT),            # [C, C]
        drhs=lambda f: dot(f["tt"], f["dwd"], ATB),            # [C, d_v]
        # the solve's own backward, dA = -T^T dT T^T
        half=lambda f: _exact_dot(f["t"], f["d_t"], ATB),
        da=lambda f: jax.lax.select(
            earlier, jax.lax.neg(_exact_dot(f["half"], f["t"], ABT)), zero),
        dsk=lambda f: _mul(f["da"], f["bc"]),
        dinner=lambda f: _mul(f["drhs"], f["bcol"]),             # dv
        # the state: q S_0 and k S_0 were one product, and so are these
        dfs=lambda f: jax.lax.concatenate(
            [_mul(f["do"], f["kept"]),
             jax.lax.neg(_mul(f["dinner"], f["kept"]))], 0).astype(dt),
        dst_start=lambda f: _add(f["decayed"],
                                 dot(f["dfs"], f["key"]["qk"], ATB)),
        dstate=lambda f: dot(f["dfs"], f["held"], AB),         # [2C, d_k]
        ended=lambda f: _mul(f["dk_end"], _mul(f["key"]["kn"], f["to_end"])),
        # the sums over a row's lanes, the tokens along the lanes: the
        # gates' (e^G's share less e^(G_C - G)'s, and P's rows) and beta's
        p=lambda f: _add(_mul(f["dsq"], f["sq"]), _mul(f["dsk"], f["sk"])),
        gates=lambda f: _minus(_mul(f["kept"], _minus(
            _mul(f["do"], f["q_state"]), _mul(f["dinner"], f["k_state"]))),
            f["ended"]),
        sums=lambda f: _lane_sums(jax.lax.concatenate([
            jax.lax.concatenate([f["gates"], f["p"]], 1),
            jax.lax.concatenate([_mul(f["drhs"], f["inner"]),
                                 _mul(f["da"], f["sk"])], 1)], 0)),  # [1, 2C]
        # G_C's own: the end state's decay, and every e^(G_C - G_i)
        end=lambda f: _add(
            lane_sum(lane_sum(_mul(f["decayed"], f["st"]), 0), 1),
            lane_sum(lane_sum(f["ended"], 0), 1)),                   # [1, 1]
        dcum=lambda f: _add(
            _minus(_cols(f["sums"], 0, c), lane_sum(f["p"], 0)),
            jax.lax.select(at_last, spread(f["end"], (1, c)),
                           _full((1, c), 0))))
    out = [(f["dinner"].astype(dt), f["dcum"], _cols(f["sums"], c, c),
            f["dst_start"]) for f in heads]
    dkeys = []
    for key in keyed:     # what its value heads add up for the key head
        mine = [f for f in heads if f["key"] is key]
        dscores = _summed([jax.lax.concatenate(
            [_mul(f["dsq"], f["table"]), _mul(f["dsk"], f["table"])], 0)
            for f in mine]).astype(dt)                           # [2C, C]
        dqk = _summed([f["dstate"] for f in mine],
                      dot(dscores, key["kd"], AB))             # [2C, d_k]
        dk = _summed([_mul(f["dk_end"], f["to_end"]) for f in mine], _add(
            _rows(dqk, c, c), dot(dscores, key["qk"], ATB)))
        dkeys.append((
            _unit_bwd(_rows(dqk, 0, c), key["qn"], key["rq"]).astype(dt),
            _unit_bwd(dk, key["kn"], key["rk"]).astype(dt)))
    return dkeys, out


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, a_ref, rows_ref, beta_ref, st_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, da_ref, drows_ref,
                    dbeta_ref, ds_scr, *, scale: float, r: int, eps: float):
    """Grid (B, head blocks, chunks from the last). dq and dk are the
    block's key heads'; da and dbeta head-major, float32; ``drows`` [16, C]
    the partial sums of A_log's gradient (rows 0.., sum dg g) and dt_bias's
    (rows 8.., sum da) a token of the chunk over the chunks walked so far
    (the block stays in VMEM over the sequential axis); ``ds_scr`` the
    gradient of the states the chunk ENDS in, transposed."""
    d = LANES
    hpb = v_ref.shape[1] // d

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[...] = _full(ds_scr.shape, 0)
        drows_ref[...] = _full(drows_ref.shape, 0)

    g, slope, cum = _head_gate(a_ref, rows_ref)
    dkeys, done = _gdn_backward_of(
        tuple(_tiles((q_ref, k_ref), h) for h in range(q_ref.shape[1] // d)),
        tuple(_tiles((v_ref,), h)
              + (_rows(cum, h, 1), beta_ref[pl.ds(h, 1), :],
                 st_ref[pl.ds(h * d, d), :])
              + _tiles((do_ref,), h) + (ds_scr[pl.ds(h * d, d), :],)
              for h in range(hpb)), scale=scale, r=r, eps=eps)
    for h, (dq, dk) in enumerate(dkeys):
        dq_ref[:, pl.ds(h * d, d)], dk_ref[:, pl.ds(h * d, d)] = dq, dk
    for h, (dv, _, dbeta, dst) in enumerate(done):
        dv_ref[:, pl.ds(h * d, d)] = dv
        dbeta_ref[pl.ds(h, 1), :] = dbeta
        ds_scr[pl.ds(h * d, d), :] = dst
    # dg from d(cumulative sum): the sums from each token to the chunk's
    # end; then the gate's chain rule, all on the one [8, C] tile
    dg = _exact_dot(_head_tile([dc for _, dc, _, _ in done]),
                    _lower_ones(a_ref.shape[1]), AB)
    da = _mul(dg, slope)
    for h in range(hpb):
        da_ref[pl.ds(h, 1), :] = _rows(da, h, 1)
    drows_ref[...] = _add(drows_ref[...],
                          jax.lax.concatenate([_mul(dg, g), da], 0))


def _gdn_bwd(q, k, v, a_t, rows, beta_t, states, do, scale, eps, r):
    """-> [dq, dk [B, T, Hk*128], dv [B, T, Hv*128] (q's dtype), da_t f32,
    drows [B, Hv/hpb, 16, C] f32, dbeta_t f32]."""
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = v.shape
    hpb, chunk = beta_t.shape[-2:]
    nc, h = t // chunk, hd // LANES
    keys = hpb * q.shape[-1] // hd
    s = _specs(t, chunk, hpb, reverse=True, key_heads=keys)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, scale=scale, r=r, eps=eps),
        grid=(b, h // hpb, nc),
        in_specs=[s["keys"]] * 2 + [s["x"], s["beta"], s["head_rows"],
                                    s["beta"], s["state"], s["x"]],
        out_specs=[s["keys"]] * 2 + [s["x"], s["beta"], s["head_drows"],
                                     s["beta"]],
        out_shape=[like(q), like(k), like(v), like(a_t),
                   jax.ShapeDtypeStruct((b,) + rows.shape, _F32),
                   like(beta_t)],
        scratch_shapes=[pltpu.VMEM((hpb * LANES, LANES), _F32)],
        compiler_params=_params(),
        name=KERNEL_NAMES["gdn_bwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * (hd * (16 * chunk + 10 * LANES)
                               + 6 * q.shape[-1] * chunk),
            bytes_accessed=(4 * q.size + 3 * v.size) * q.dtype.itemsize
            + 16 * a_t.size + 4 * b * nc * hd * LANES,
            transcendentals=b * t * h * (chunk + 3 * LANES)),
    )(q, k, v, a_t, rows, beta_t, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _gdn_kernels(q, k, v, a_t, rows, beta_t, scale, eps, r):
    return _gdn_fwd(q, k, v, a_t, rows, beta_t, scale, eps, r)[0]


def _gdn_vjp_fwd(q, k, v, a_t, rows, beta_t, scale, eps, r):
    from jax.ad_checkpoint import checkpoint_name

    o, states = _gdn_fwd(q, k, v, a_t, rows, beta_t, scale, eps, r)
    o = checkpoint_name(o, "kda_out")             # as ``_kda_vjp_fwd``
    states = checkpoint_name(states, "kda_states")
    return o, (q, k, v, a_t, rows, beta_t, states)


def _gdn_vjp_bwd(scale, eps, r, res, do):
    dq, dk, dv, da, drows, dbeta = _gdn_bwd(*res, do, scale, eps, r)
    # the rows' partial sums a batch row -> the rows' gradient (a token of
    # the chunk still: autodiff adds those, the rows being a broadcast)
    return dq, dk, dv, da, drows.sum(0), dbeta


_gdn_kernels.defvjp(_gdn_vjp_fwd, _gdn_vjp_bwd)


def _gdn_lanes(d_k: int, d_v: int):
    """(the lanes a head's keys occupy, the lanes its values occupy) in
    what the Gated DeltaNet kernels read. The delta rule is separable over
    the value channels and a zero key channel changes no product, so a head
    whose sizes are not whole 128-lane tiles takes the pair's body as it
    is: its keys zero-padded to one tile, its values to whole tiles, each
    tile a value head of 128 on the one key head with the head's own decay
    and beta (a [96, 192] state is two [128, 128] ones side by side, a
    quarter of each zero). Taken where more than half of what is read is
    the model's; None else: the plain route's."""
    lanes_v = -(-d_v // LANES) * LANES
    if d_k <= LANES < 2 * d_k and lanes_v < 2 * d_v:
        return LANES, lanes_v
    return None


def _lane_padded(x, heads: int, lanes: int):
    """[B, T, heads * d] -> [B, T, heads * lanes], zeros after a head's d
    channels; as it is where d is ``lanes``."""
    b, t, hd = x.shape
    if hd == heads * lanes:
        return x
    return jnp.pad(x.reshape(b, t, heads, hd // heads), (
        (0, 0), (0, 0), (0, 0), (0, lanes - hd // heads))).reshape(
        b, t, heads * lanes)


def _gdn_kernel_route(q, k, v, a, a_log, dt_bias, beta, *, key_heads: int,
                      hpb: int, scale: float, eps: float, chunk: int,
                      lanes: tuple, r: int):
    """A Gated DeltaNet layer's call through the pair with the body for one
    decay a head: q and k [B, T, Hk*128] as they are (heads of fewer
    channels zero-padded to the tile, ``_gdn_lanes``; zeros change neither
    the l2 norm nor a product); v in tiles of 128, a head of more value
    channels as that many value heads on its key head, each with the
    head's ``a``, beta, A_log and dt_bias; ``a`` and beta go in head-major
    (``_head_major``) in float32, A_log and dt_bias as rows a head block."""
    t, heads = q.shape[1], beta.shape[-1]
    d_k, d_v = k.shape[-1] // key_heads, v.shape[-1] // heads
    tiles = lanes[1] // LANES
    q, k = (_lane_padded(x, key_heads, lanes[0]) for x in (q, k))
    v = _lane_padded(v, heads, lanes[1])
    if tiles > 1:
        a, beta = (jnp.repeat(x, tiles, -1) for x in (a, beta))
        a_log, dt_bias = (jnp.repeat(x, tiles) for x in (a_log, dt_bias))
    (q, k, v, a, beta), pad = pad_tokens(
        (q, k, v, a.astype(_F32), beta.astype(_F32)), chunk)
    facts = _path_facts(chunk, t, pad, heads, d_k, d_v, eps, key_heads,
                        lanes=lanes, r=r)
    facts.update(_block_facts(hpb))
    record_path("rtpu.ops.kda.path", PATH_COUNTS, "kernel", facts)
    blocks = heads * tiles // hpb
    rows = jnp.pad(jnp.stack([a_log, dt_bias]).reshape(2, blocks, hpb),
                   ((0, 0), (0, 0), (0, _HEAD_ROWS - hpb)))
    rows = jnp.broadcast_to(
        rows.transpose(1, 0, 2).reshape(blocks, 2 * _HEAD_ROWS, 1),
        (blocks, 2 * _HEAD_ROWS, chunk))
    o = _gdn_kernels(q, k, v, _head_major(a, chunk, hpb), rows,
                     _head_major(beta, chunk, hpb), float(scale),
                     float(eps), r)[:, :t]
    if lanes[1] == d_v:
        return o
    return o.reshape(*o.shape[:2], heads, lanes[1])[..., :d_v].reshape(
        *o.shape[:2], heads * d_v)


def _route(d_k: int, d_v: int, chunk: int) -> str:
    """The route a call takes, by what it shows: the kernel pair where a
    head is one 128-lane tile of keys and of values and the chunk is 64."""
    return "kernel" if d_k == d_v == LANES and chunk == 64 else "chunked_jnp"


def _path_facts(chunk, tokens, pad, heads, d_k, d_v, eps, key_heads,
                lanes=None, r=None):
    """The facts of ``rtpu.ops.kda.path`` that every entry states. ``d_k``
    and ``d_v`` are the MODEL's head sizes; ``lanes_k`` and ``lanes_v`` the
    lanes a head's keys and values occupy in what the route reads (the head
    sizes themselves but on the kernel route of heads that are not whole
    tiles, ``_gdn_lanes``: what the padding costs is (d_k + d_v) / (lanes_k
    + lanes_v)); ``solve_block`` the rows of the solve's Neumann blocks
    (``_solve_block``).
    ``key_heads`` None: a decay a key channel, a key head a value head, and
    the body that makes the decayed scores in sub-blocks; else the body
    with the head's one decay factored out of them (``body``: which
    program a run measured; PR 52 ran ``decay: head`` through
    ``channel_decay``)."""
    return {"chunk": chunk, "tokens": tokens, "padded_tokens": pad,
            "heads": heads, "d_k": d_k, "d_v": d_v,
            "lanes_k": (lanes or (d_k, d_v))[0],
            "lanes_v": (lanes or (d_k, d_v))[1],
            "chunks": (tokens + pad) // chunk, "solve_block": r or _SUB,
            "prologue": "jnp" if eps is None else "in_kernel",
            "decay": "channel" if key_heads is None else "head",
            "body": "channel_decay" if key_heads is None else "head_decay",
            "key_heads": key_heads or heads}


def _block_facts(hpb: int):
    """The kernel route's facts of a program's block of heads:
    ``pairs_in_step`` is how many pairs' solves a program issues side by
    side (``_solve_heads``: 2 at four heads, one pair alone at two or three,
    none at one head)."""
    return {"heads_per_block": hpb, "pairs_in_step": hpb // 2}


def _scan(q, k, v, gate, beta, *, scale: float, chunk: int, eps):
    """What KDA's two entries share: the route by what the call shows,
    whole chunks, the path event. ``gate``: (g,) with ``eps`` None, or the
    prologue's (step, rows) on the kernel route."""
    b, t, _ = q.shape
    heads = beta.shape[-1]
    d_k, d_v = k.shape[-1] // heads, v.shape[-1] // heads
    route = _route(d_k, d_v, chunk)
    if route != "kernel":
        chunk = min(chunk, t)
    (q, k, v, lead, beta), pad = pad_tokens(
        (q, k, v, gate[0], beta.astype(_F32)), chunk)
    gate = (lead,) + tuple(gate[1:])
    facts = _path_facts(chunk, t, pad, heads, d_k, d_v, eps, None)
    if route == "kernel":
        hpb = _heads_per_block(heads)
        facts.update(_block_facts(hpb))
    record_path("rtpu.ops.kda.path", PATH_COUNTS, route, facts)
    if route == "kernel":
        o = _kernel_route(q, k, v, gate, beta, heads, chunk, float(scale),
                          hpb, eps)
    else:
        o = _chunked(q, k, v, *gate, beta, heads, chunk, float(scale))
    return o[:, :t]


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, *, scale: float, chunk: int = 64) -> jax.Array:
    """The gated delta rule with a decay a key channel. q, k [batch, seq,
    heads * d_k] and v [batch, seq, heads * d_v] in the model's merged
    layout, g [batch, seq, heads * d_k] (<= 0, the log decay, float32),
    beta [batch, seq, heads] -> o [batch, seq, heads * d_v] in q's dtype,
    o_t = S_t^T (scale q_t). Differentiable in all five. ``chunk`` is how
    the work is cut, not what is computed."""
    return _scan(q, k, v, (g.astype(_F32),), beta, scale=scale, chunk=chunk,
                 eps=None)


def kda_gated_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                   step: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
                   beta: jax.Array, *, scale: float, eps: float = 1e-6,
                   chunk: int = 64) -> jax.Array:
    """A KDA layer's scan from what its convolutions and its gate
    projection made: ``kda_scan`` of q and k each normalised to unit length
    a head (``layers.l2norm`` with ``eps``) and of

        g = -exp(a_log)[head] * softplus(step + dt_bias)        float32

    q, k, step [batch, seq, heads * d_k], a_log [heads], dt_bias [heads *
    d_k]. Differentiable in all seven arrays. That sentence is this
    function on the plain route, literally; on the kernel route the kernels
    make the norms and the gate on their own tiles (the module's docstring:
    the prologue) and no g is written."""
    heads = beta.shape[-1]
    d_k = k.shape[-1] // heads
    a_log, dt_bias = a_log.astype(_F32), dt_bias.astype(_F32)
    if _route(d_k, v.shape[-1] // heads, chunk) == "kernel":
        rows = jnp.stack([jnp.repeat(a_log, d_k), dt_bias])
        return _scan(q, k, v, (step, rows), beta, scale=scale, chunk=chunk,
                     eps=float(eps))
    unit = lambda x: l2norm(                                 # noqa: E731
        x.reshape(*x.shape[:2], heads, d_k), eps).reshape(x.shape)
    g = -jnp.repeat(jnp.exp(a_log), d_k) * jax.nn.softplus(
        step.astype(_F32) + dt_bias)
    return kda_scan(unit(q), unit(k), v, g, beta, scale=scale, chunk=chunk)


def _gdn_head_sizes(q, k, v, beta, key_heads):
    """(value heads, key heads, d_k, d_v) of merged arrays; the key heads as
    stated or, where keys and values share one head size, known from it."""
    heads = beta.shape[-1]
    d_v = v.shape[-1] // heads
    key_heads = key_heads or k.shape[-1] // d_v
    if heads % key_heads or q.shape[-1] != k.shape[-1] \
            or k.shape[-1] % key_heads:
        raise ValueError(f"{heads} value heads over {key_heads} key heads "
                         f"of {d_v}: q {q.shape}, k {k.shape}, v {v.shape}")
    return heads, key_heads, k.shape[-1] // key_heads, d_v


def gated_delta_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, *, scale: float, chunk: int = 64,
                     key_heads: int = None,
                     beta_max: float = 1.0) -> jax.Array:
    """The gated delta rule with ONE decay a head (Gated DeltaNet): the
    plain definition, in the chunked form with the decay factored out
    (the module's docstring). q, k [batch, seq, key_heads * d_k] as the
    caller normalised them, v [batch, seq, value_heads * d_v], g (<= 0, the
    log decay, float32) and beta [batch, seq, value_heads], beta as the
    caller made it (a sigmoid, or twice one where the correction may turn
    a key's component round: say so with ``beta_max``, which sizes the
    solve's blocks, ``_solve_block``, and changes nothing else). Keys and
    values may differ in head size where ``key_heads`` is stated; left out,
    they share one size d, which is how the key heads are then known from
    merged arrays. value_heads is a multiple of key_heads and value head j
    reads key head j // (value_heads / key_heads) -> o [batch, seq,
    value_heads * d_v] in q's dtype. Differentiable in all five. Always the
    route ``chunked_jnp``; ``chunk`` is how the work is cut, not what is
    computed."""
    t = q.shape[1]
    heads, key_heads, d_k, d_v = _gdn_head_sizes(q, k, v, beta, key_heads)
    chunk = min(chunk, t)
    r = _solve_block(beta_max)
    (q, k, v, g, beta), pad = pad_tokens(
        (q, k, v, g.astype(_F32), beta.astype(_F32)), chunk)
    record_path("rtpu.ops.kda.path", PATH_COUNTS, "chunked_jnp",
                _path_facts(chunk, t, pad, heads, d_k, d_v, None, key_heads,
                            r=r))
    return _head_decay_chunked(q, k, v, g, beta, key_heads, chunk,
                               float(scale), r)[:, :t]


def gdn_gated_scan(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array,
                   a_log: jax.Array, dt_bias: jax.Array, beta: jax.Array, *,
                   scale: float, eps: float = 1e-6, chunk: int = 64,
                   key_heads: int = None,
                   beta_max: float = 1.0) -> jax.Array:
    """A Gated DeltaNet layer's scan from what its convolution and its
    ``b | a`` projection made: ``gated_delta_scan`` of q and k each
    normalised to unit length a key head (``layers.l2norm`` with ``eps``)
    and of

        g = -exp(a_log) * softplus(a + dt_bias)      float32, a value head

    q, k [batch, seq, key_heads * d_k], v [batch, seq, value_heads * d_v],
    a and beta [batch, seq, value_heads], a_log and dt_bias [value_heads];
    ``key_heads`` and ``beta_max`` as ``gated_delta_scan`` takes them.
    Differentiable in all seven arrays. That sentence is this function on
    the plain route, literally. On the kernel route (a chunk of 64, heads
    whose keys fill more than half of one 128-lane tile and whose values
    more than half of whole tiles, ``_gdn_lanes``, a block of at most four
    value tiles that holds whole key heads) it is the kernel pair with the
    body for one decay a head (the module's docstring): a program reads q
    and k ONCE a key head from these arrays as they are, makes the norms,
    the gate and its cumulative sums itself (a number a value head and
    token: no float32 g, no copy of ``a`` over a head's lanes is written),
    one product of scores a key head and a [C, C] table of decays a value
    head over it; dq and dk come back summed over a key head's value
    heads."""
    heads, key_heads, d_k, d_v = _gdn_head_sizes(q, k, v, beta, key_heads)
    a_log, dt_bias = a_log.astype(_F32), dt_bias.astype(_F32)
    lanes = _gdn_lanes(d_k, d_v)
    tiles = lanes[1] // LANES if lanes else 0
    hpb = lanes and _gdn_heads_per_block(heads * tiles,
                                         heads // key_heads * tiles)
    if hpb and _route(lanes[0], LANES, chunk) == "kernel":
        return _gdn_kernel_route(q, k, v, a, a_log, dt_bias, beta,
                                 key_heads=key_heads, hpb=hpb, scale=scale,
                                 eps=eps, chunk=chunk, lanes=lanes,
                                 r=_solve_block(beta_max))
    unit = lambda x: l2norm(                                 # noqa: E731
        x.reshape(*x.shape[:2], key_heads, d_k), eps).reshape(x.shape)
    g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(_F32) + dt_bias)
    return gated_delta_scan(unit(q), unit(k), v, g, beta, scale=scale,
                            chunk=chunk, key_heads=key_heads,
                            beta_max=beta_max)
