"""A dropless mixture-of-experts layer for ONE chip's share of the experts,
and the grouped matrix product (Pallas) it rests on.

``held_expert_layer`` is told which experts live here (``experts_held``,
``expert_offset``), scores every token against ALL experts, takes the top
k of all of them, normalises over the k chosen whether held or not, and
returns the shared experts' output (where the layer has shared experts)
plus the part of the routed sum that the held experts give. What the
absent experts would add is left out (it is another chip's to compute;
nothing here stands in for them or for their exchange). With every expert
held it is the whole layer.

No token is dropped whatever the routing, and no shape or trip count
depends on it: the (token, choice) pairs that name a held expert are
sorted by expert into a row buffer of static size that covers the worst
case (every token choosing held experts only), each expert's rows
starting on a tile of ``ROW_TILE`` rows, so that a tile belongs to one
expert. The grouped product walks the buffer's tiles with the tile's
expert as a prefetched scalar; tiles past the last used one are skipped
by predicate and fetch nothing, so the time follows the rows that are
there while the grid does not. Rows move by gathers in both directions
(forward and backward each know token -> row and row -> token), never by
a scatter; they are made for the whole buffer, so that only the time of
``experts`` (the grouped products and, since PR 68, the kernel pair of the
first half) follows the routing.

A padding row of the buffer (the ragged end of an expert's last tile) is
NOT zeros: it holds whatever the gather's clamped index brought, a copy of
a real token's row, because a select over [rows, D] is a pass of its own.
The zero of a padding row is its WEIGHT, one scalar (``pairs_to_rows``),
and every product over the buffer carries it (``_mlp``'s ``row_weight``):
the row's hidden activation is finite x 0 = 0, so it adds 0 to ``dW_down``
(h^T dy), its hidden cotangent is again times 0, so it adds 0 to
``dW_gate`` and ``dW_up`` (x^T d) whatever x holds and its own cotangent is
0; its output row and that cotangent are never read (``rows_to_tokens``,
forward and as ``tokens_to_rows``' vjp, gathers held pairs' rows alone),
and ``pairs_to_rows``' vjp drops its weight's gradient. The sums gain
exact zeros where they gained exact zeros.

A token's k choices are k DIFFERENT experts, so a token holds at most
min(k, held) rows; the pair domain is that, the buffer already was. Where
``top_k > experts_held`` (two static arguments of the call) a token's held
choices are compacted to ``experts_held`` slots before the sort
(``compact_held``), and the sort, the row gathers and the weights' gather
are sized by tokens x slots; where it is not, the compaction is not
traced. The trace-time event ``rtpu.ops.expert_layer`` / ``held`` says
which (``pair_slots`` beside ``top_k``).

Where a token's slots do not fill whole tiles of the chip's second-minor
dimension (``slot_axis``: slots that are not a multiple of ``SLOT_TILE``, 8)
the pair domain is laid out CHOICE-MAJOR: a pair's id is ``choice * T +
token`` and ``sort_rows``' tables over the pairs are [k, T], a token's k
slots down the LEADING axis. The rows gathered back for the sum over a
token's slots are then [k, T, D], a bitcast of the gather's [k * T, D], and
the sum adds k whole [T, D] slices; with the slots in the second-minor
dimension ([T, k, D]) the chip's tiling of 8 rows makes that a full copy of
the layer's largest array, twice a layer (PERF.md, PR 65: four cells paid
it, at 4, 6 and 10 slots). Only the NAMES of the pairs change: the sort runs
over the token-major keys either way, so a row of the buffer holds the token
it held. Where the slots ARE whole tiles (8 in three cells) [T, k, D] costs
no copy and the pairs stay token-major, ``token * k + choice`` and [T, k]:
the program those cells had, kept because choice-major there moved what the
compiler schedules beside the gathers and read 0.3 to 2.9 % slower
(PERF.md, PR 65). The choice is one static fact of the call, as the
compaction's is, and the event's ``slot_axis`` states it.

The routed experts' first half is a kernel PAIR (``expert_hidden``, PR 68):
``h = silu(x W_gate) * (x W_up) * row_weight`` (``swiglu``) or ``relu(x
W_up)^2 * row_weight`` (``relu2``) over the buffer's tiles in ONE call, on
the grouped product's pattern (grid over the row tiles, ``tile_expert`` and
``n_used`` prefetched). The forward kernel KEEPS in VMEM the f32 sums of a
tile's product(s) with its expert's matrices, rounds them where the grouped
product rounds, applies the activation and the row's weight there in f32 and
WRITES h alone, in the buffer's dtype: no pre-activation of the worst-case
buffer goes to HBM, comes back, is widened or weighted by a pass of its own.
The backward kernel takes x, the rows' weights and h's cotangent (``e_down``'s
transposed grouped product), makes the pre-activations AGAIN in VMEM (two
products a tile) and WRITES their cotangents in the buffer's dtype (what
``grouped_matmul_dw`` reads for ``dW_gate`` / ``dW_up``), the rows' weights'
cotangent and ``dx = dg W_gate^T + du W_up^T`` summed in f32 in VMEM and
rounded once: no add over [rows, D]. Both SKIP a tile past ``n_used`` as the
grouped product does (its step names the block that is already there:
nothing fetched, computed or written), so the whole of ``experts`` follows
the rows that are there. Where an expert's matrices do not fit the kernels'
fast memory beside their second buffers (``hidden_block``, from the widths
and ``VMEM_BYTES``) the grid takes a second axis over blocks of F: outside
the tiles forward (the activation is elementwise in F; a block of weights
stays put over its expert's tiles), inside them backward (dx and the rows'
weights' cotangent are sums over F, made in scratch). ``e_down``'s product,
``grouped_matmul_dw`` and the shared expert (``_gated`` / ``_relu2`` over
``jnp.dot``: the tokens themselves, no padding) are as they were. The event
says ``mlp_in`` ``kernel`` and the block of F (``mlp_in_block``).

``balance_term`` is a router's load-balancing term (a softmax router's or,
``score="sigmoid"``, a sigmoid router's with its selection bias), for a
model that adds it to its loss: it reads the router alone (every chip holds
it whole), so a share states it as the uncut layer does.

Scopes (``jax.named_scope``, pinned in tests/test_tracing_names.py):
``router`` (scores, top-k, the sort, the gather into the buffer and the
weighted sum back), ``experts`` (the kernel pair of the first half and the
grouped products), ``shared_expert``
(where the layer has one), ``latent_proj`` (the projections into and out
of the experts' latent, where the layer has one).
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..perf.recorder import record as _record
from . import kernel_common
from .kernel_common import VMEM_BYTES

# Names of the Pallas calls as a device trace shows them; part of the
# measurement (tests/test_tracing_names.py).
KERNEL_NAMES = {
    "rows": "grouped_matmul",        # y[tile] = x[tile] @ w[expert(tile)]
    "weights": "grouped_matmul_dw",  # dw[e] = sum over e's tiles x^T dy
    # h[tile] = act(x[tile] @ w_gate[e], x[tile] @ w_up[e]) * row_weight
    "hidden": "expert_hidden_fwd",
    # its cotangents: of the pre-activations, of the rows' weights, of x
    "hidden_bwd": "expert_hidden_bwd",
}

# Rows of a tile. An expert's rows are padded to whole tiles (at least
# one), so a tile multiplies one expert's weights: up to ROW_TILE - 1
# padding rows an expert are the price.
ROW_TILE = 256

# Traced calls of the layer by (experts held, experts in all).
LAYER_COUNTS: collections.Counter = collections.Counter()


def buffer_rows(tokens: int, top_k: int, experts_held: int,
                tile: int = ROW_TILE) -> int:
    """Rows of the static buffer: every token choosing held experts only,
    each expert's last tile ragged, and one tile for an expert with no
    row."""
    worst = tokens * min(top_k, experts_held)
    return (-(-worst // tile) + experts_held) * tile


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------


def _rows_kernel(tile_expert, n_used, x_ref, w_ref, o_ref, *, transposed):
    @pl.when(pl.program_id(0) < n_used[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...],
            (((1,), (1,) if transposed else (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _weights_kernel(tile_expert, n_used, x_ref, dy_ref, dw_ref, acc, *,
                    n_tiles):
    i = pl.program_id(0)
    used = i < n_used[0]
    here = tile_expert[i]

    @pl.when(used & ((i == 0) | (tile_expert[jnp.maximum(i - 1, 0)] != here)))
    def _first_of_expert():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _():
        acc[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(used & ((i == n_used[0] - 1)
                     | (tile_expert[jnp.minimum(i + 1, n_tiles - 1)] != here)))
    def _last_of_expert():
        dw_ref[...] = acc[...].astype(dw_ref.dtype)


def _last_used(i, n_used):
    """Tile i, or the last used tile past it: a skipped step names the
    block that is already there, so nothing is fetched or written."""
    return jnp.minimum(i, n_used[0] - 1)


def _rows_call(x, w, tile_expert, n_used, transposed, tile):
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    n_tiles = rows // tile
    return pl.pallas_call(
        functools.partial(_rows_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda i, te, nu: (_last_used(i, nu), 0)),
                pl.BlockSpec((None,) + w.shape[1:],
                             lambda i, te, nu: (te[_last_used(i, nu)], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (tile, n), lambda i, te, nu: (_last_used(i, nu), 0))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        name=KERNEL_NAMES["rows"],
        interpret=kernel_common.use_interpret(),
    )(tile_expert, n_used, x, w)


def _weights_call(x, dy, tile_expert, n_used, experts, dtype, tile):
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = dy.shape[1]
    n_tiles = rows // tile
    return pl.pallas_call(
        functools.partial(_weights_kernel, n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda i, te, nu: (_last_used(i, nu), 0)),
                pl.BlockSpec((tile, n),
                             lambda i, te, nu: (_last_used(i, nu), 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, k, n),
                lambda i, te, nu: (te[_last_used(i, nu)], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((experts, k, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        name=KERNEL_NAMES["weights"],
        interpret=kernel_common.use_interpret(),
    )(tile_expert, n_used, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_expert, n_used, tile=ROW_TILE):
    """x [rows, K] @ w[expert of the row's tile] [K, N] -> [rows, N].

    ``tile_expert`` [rows // tile] int32 names each tile's expert,
    ``n_used`` [1] int32 how many leading tiles hold rows; every expert
    owns at least one used tile. Rows of tiles past ``n_used`` are not
    written (whatever the buffer held stays there): the caller masks what
    it reads from them."""
    return _rows_call(x, w, tile_expert, n_used, False, tile)


def _grouped_fwd(x, w, tile_expert, n_used, tile):
    return (_rows_call(x, w, tile_expert, n_used, False, tile),
            (x, w, tile_expert, n_used))


def _grouped_bwd(tile, res, dy):
    x, w, tile_expert, n_used = res
    dx = _rows_call(dy, w, tile_expert, n_used, True, tile)
    dw = _weights_call(x, dy, tile_expert, n_used, w.shape[0], w.dtype, tile)
    return dx, dw, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


# ---------------------------------------------------------------------------
# the experts' first half: products, activation and the rows' weights, a tile
# where the tile is (module docstring)
# ---------------------------------------------------------------------------


def _hidden(expert: str, weight, *pre):
    """The hidden activation of a tile in f32 from the rows' weights
    [tile, 1] f32 and its pre-activations (the gate's and the up product
    ``swiglu``, the up product ``relu2``; in the buffer's dtype, as the
    products are rounded): ``_gated`` / ``_relu2``'s own expressions."""
    if expert == "swiglu":
        gate, up = (v.astype(jnp.float32) for v in pre)
        return jax.nn.silu(gate) * up * weight
    # (the chip compares in f32: relu after the widening, the same values)
    return jnp.square(jax.nn.relu(pre[0].astype(jnp.float32))) * weight


def _column(row):
    """The rows' weights of a tile as the kernels hold them, [1, tile] along
    the lanes, as a column [tile, 1] (a value a row of the tile). The weights
    travel lane-major because a [rows, 1] f32 array is tiled (8, 128) on the
    chip: 512 bytes a row, a pass of 50 to 90 MB for every operation that
    makes or reads it."""
    return jnp.transpose(jnp.broadcast_to(row, (SLOT_TILE, row.shape[1])))[
        :, :1]


def _lanes(column):
    """``_column``'s inverse: [tile, 1] -> [1, tile]."""
    return jnp.transpose(jnp.broadcast_to(
        column, (column.shape[0], SLOT_TILE)))[:1, :]


def _pre(x, w_refs, dtype):
    """x [tile, K] times each of the expert's matrices [K, block of F],
    summed in f32 and rounded where the grouped product rounds."""
    return [kernel_common.dot(x, w[...], kernel_common.AB).astype(dtype)
            for w in w_refs]


def _hidden_kernel(tile_expert, n_used, x_ref, weight_ref, *refs, expert):
    *w_refs, h_ref = refs

    @pl.when(pl.program_id(1) < n_used[0])
    def _():
        pre = _pre(x_ref[...], w_refs, h_ref.dtype)
        h_ref[...] = _hidden(expert, _column(weight_ref[...]), *pre).astype(
            h_ref.dtype)


def _hidden_bwd_kernel(tile_expert, n_used, x_ref, weight_ref, dh_ref, *refs,
                       expert, mats, f_blocks):
    w_refs, d_refs = refs[:mats], refs[mats:2 * mats]
    dx_ref, dweight_ref = refs[2 * mats:2 * mats + 2]
    j = pl.program_id(1)    # read here: interpret mode has none in a branch

    @pl.when(pl.program_id(0) < n_used[0])
    def _():
        pre = _pre(x_ref[...], w_refs, dx_ref.dtype)
        _, pull = jax.vjp(functools.partial(_hidden, expert),
                          _column(weight_ref[...]), *pre)
        d_weight, *d_pre = pull(dh_ref[...].astype(jnp.float32))
        for d_ref, d in zip(d_refs, d_pre):
            d_ref[...] = d
        # dx = dg W_gate^T + du W_up^T, summed here: no pass over [rows, K]
        dx = sum(kernel_common.dot(d, w[...], kernel_common.ABT)
                 for d, w in zip(d_pre, w_refs))
        if f_blocks == 1:
            dx_ref[...] = dx.astype(dx_ref.dtype)
            dweight_ref[...] = _lanes(d_weight)
            return
        dx_acc, dweight_acc = refs[2 * mats + 2:]

        @pl.when(j == 0)
        def _first_block():
            dx_acc[...] = jnp.zeros_like(dx_acc)
            dweight_acc[...] = jnp.zeros_like(dweight_acc)

        dx_acc[...] += dx
        dweight_acc[...] += d_weight

        @pl.when(j == f_blocks - 1)
        def _last_block():
            dx_ref[...] = dx_acc[...].astype(dx_ref.dtype)
            dweight_ref[...] = _lanes(dweight_acc[...])


def hidden_block(k: int, f: int, mats: int, itemsize: int,
                 tile: int = ROW_TILE) -> int:
    """The block of F the kernel pair works at a time: all of F where the
    backward kernel (the larger of the two) fits its fast memory, else the
    largest whole share of F in whole lanes that does. Counted: the
    expert's ``mats`` matrices [K, block] and their second buffers, the
    tile's rows in and out twice ([tile, K] x and dx, [tile, block] the
    hidden cotangent and the pre-activations'), and in f32 dx and what the
    activation's cotangents take, [tile, block] a value; an eighth of the
    memory is left to the compiler. By that count every cell of the
    benchmark works all of F (``lfm2moe``'s 2048 x 1792 and ``xing4``'s
    3584 x 1024 at 52 and 56 MB of 64; timed in halves, both were 2 to 4 %
    slower forward + backward: PERF.md, PR 68)."""
    def fits(block):
        weights = 2 * mats * k * block * itemsize
        rows = 2 * tile * (2 * k + (1 + mats) * block) * itemsize
        values = 4 * tile * (2 * k + (2 + 3 * mats) * block)
        return weights + rows + values <= VMEM_BYTES * 7 // 8

    for n in range(1, f // kernel_common.LANES + 1):
        if f % (n * kernel_common.LANES) == 0 and fits(f // n):
            return f // n
    return f if f % kernel_common.LANES else kernel_common.LANES


def _hidden_call(x, w, weight, tile_expert, n_used, expert, tile):
    """The forward kernel. Its grid walks the blocks of F OUTSIDE the tiles,
    so that an expert's block of weights stays where it is over the expert's
    tiles (x [tile, K], a matrix's 256th, is what comes again)."""
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    f = w[0].shape[2]
    block = hidden_block(k, f, len(w), x.dtype.itemsize, tile)
    row = lambda j, i, te, nu: (_last_used(i, nu), 0)        # noqa: E731
    return pl.pallas_call(
        functools.partial(_hidden_kernel, expert=expert),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // block, rows // tile),
            in_specs=[pl.BlockSpec((tile, k), row),
                      pl.BlockSpec((None, 1, tile), lambda j, i, te, nu: (
                          _last_used(i, nu), 0, 0))] + [
                pl.BlockSpec(
                    (None, k, block),
                    lambda j, i, te, nu: (te[_last_used(i, nu)], 0, j))
                for _ in w],
            out_specs=pl.BlockSpec(
                (tile, block),
                lambda j, i, te, nu: (_last_used(i, nu), j))),
        out_shape=jax.ShapeDtypeStruct((rows, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        name=KERNEL_NAMES["hidden"],
        interpret=kernel_common.use_interpret(),
    )(tile_expert, n_used, x, weight.reshape(-1, 1, tile), *w)


def _hidden_bwd_call(x, w, weight, dh, tile_expert, n_used, expert, tile):
    """The backward kernel -> (the pre-activations' cotangents [rows, F]
    each, dx [rows, K], the rows' weights' cotangent [rows] f32). dx and the
    weights' cotangent are sums over F, so here the blocks of F are the
    INNER axis and the two are summed in scratch."""
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    f = w[0].shape[2]
    block = hidden_block(k, f, len(w), x.dtype.itemsize, tile)
    f_blocks = f // block
    row = lambda i, j, te, nu: (_last_used(i, nu), 0)        # noqa: E731
    lanes = pl.BlockSpec((None, 1, tile),
                         lambda i, j, te, nu: (_last_used(i, nu), 0, 0))
    # a skipped step names the last block of the last used tile
    last_block = lambda i, j, nu: jnp.where(  # noqa: E731
        i < nu[0], j, f_blocks - 1)
    wide = pl.BlockSpec(
        (tile, block), lambda i, j, te, nu: (
            _last_used(i, nu), last_block(i, j, nu)))
    out = pl.pallas_call(
        functools.partial(_hidden_bwd_kernel, expert=expert, mats=len(w),
                          f_blocks=f_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile, f_blocks),
            in_specs=[pl.BlockSpec((tile, k), row), lanes, wide] + [
                pl.BlockSpec(
                    (None, k, block), lambda i, j, te, nu: (
                        te[_last_used(i, nu)], 0, last_block(i, j, nu)))
                for _ in w],
            out_specs=[wide for _ in w] + [
                pl.BlockSpec((tile, k), row), lanes],
            scratch_shapes=[] if f_blocks == 1 else [
                pltpu.VMEM((tile, k), jnp.float32),
                pltpu.VMEM((tile, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, f), x.dtype) for _ in w] + [
            jax.ShapeDtypeStruct((rows, k), x.dtype),
            jax.ShapeDtypeStruct((rows // tile, 1, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        name=KERNEL_NAMES["hidden_bwd"],
        interpret=kernel_common.use_interpret(),
    )(tile_expert, n_used, x, weight.reshape(-1, 1, tile), dh, *w)
    return out[:len(w)], out[-2], out[-1].reshape(rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def expert_hidden(x, w, row_weight, tile_expert, n_used, expert: str,
                  tile=ROW_TILE):
    """The hidden activation of the row buffer x [rows, K] under each tile's
    expert -> [rows, F] in x's dtype: ``_gated`` / ``_relu2``'s first half
    over ``grouped_matmul``, made a tile at a time where the tile is. ``w``:
    the held experts' (``e_gate``, ``e_up``) ``swiglu``, (``e_up``,)
    ``relu2``, each [held, K, F] in x's dtype; ``row_weight`` [rows] f32
    (``pairs_to_rows``: the 0 of a padding row); ``tile_expert``, ``n_used``
    as ``grouped_matmul``'s, and as there rows of tiles past ``n_used`` are
    not written, forward (h) or backward (dx, the weights' cotangent).
    The products are summed in f32 and rounded to x's dtype, the activation,
    the square and the weight are applied in f32 and h is rounded once, as
    the unfused route rounds; backward the pre-activations are made again
    from x (two products a tile), their cotangents kept in f32 until they
    are written, and dx is the sum of the two transposed products in f32,
    rounded once (unfused: each rounded, then added and rounded)."""
    return _hidden_call(x, w, row_weight, tile_expert, n_used, expert, tile)


def _expert_hidden_fwd(x, w, row_weight, tile_expert, n_used, expert, tile):
    return (_hidden_call(x, w, row_weight, tile_expert, n_used, expert, tile),
            (x, w, row_weight, tile_expert, n_used))


def _expert_hidden_bwd(expert, tile, res, dh):
    x, w, row_weight, tile_expert, n_used = res
    d_pre, dx, d_weight = _hidden_bwd_call(x, w, row_weight, dh, tile_expert,
                                           n_used, expert, tile)
    dw = tuple(_weights_call(x, d, tile_expert, n_used, m.shape[0], m.dtype,
                             tile) for d, m in zip(d_pre, w))
    return dx, dw, d_weight, None, None


expert_hidden.defvjp(_expert_hidden_fwd, _expert_hidden_bwd)


# ---------------------------------------------------------------------------
# rows in and out of the buffer: gathers both ways
# ---------------------------------------------------------------------------
# ``at`` (``sort_rows``) knows pair -> row and row -> pair, so tokens go to
# rows and rows come back to tokens by gathers, and each is the other's
# transpose: no scatter in either direction, and nothing but copies of bf16
# rows (the routing weights are applied to the rows inside the buffer,
# ``held_expert_layer``). A pair is ``choice * T + token`` and the tables
# over the pairs [k, T] where ``at.slot_axis`` is 0, ``token * k + choice``
# and [T, k] where it is 1 (``slot_axis``).

# Rows of a tile of the chip's second-minor dimension.
SLOT_TILE = 8


def slot_axis(slots: int) -> int:
    """The axis of the pairs' tables a token's ``slots`` lie along: 1
    ([T, k], token-major) where they are whole tiles of the second-minor
    dimension, so that the rows gathered back as [T, k, D] tile as they
    are; else 0 ([k, T], choice-major), where [T, k, D] would be a padded
    copy of [T * k, D] and [k, T, D] is a bitcast of it."""
    return 1 if slots % SLOT_TILE == 0 else 0


class PairTables(dict):
    """``sort_rows``' tables, and with them the one static fact their
    readers need: ``slot_axis``, the axis of ``pair_row`` / ``pair_held``
    a token's slots lie along. It rides in the pytree's structure, so it
    is a Python int inside ``jit`` and ``custom_vjp`` too."""

    def __init__(self, tables, slot_axis: int):
        super().__init__(tables)
        self.slot_axis = slot_axis


jax.tree_util.register_pytree_node(
    PairTables,
    lambda at: (tuple(at.values()), (tuple(at), at.slot_axis)),
    lambda aux, leaves: PairTables(zip(aux[0], leaves), aux[1]))


def _rows_of(values, at):
    """values [n, *width], one a token (n = T) or one a pair (n = pairs,
    in the order of the pairs' ids) -> [rows, *width]: each row its pair's
    value AS GATHERED (a pair's token is its id modulo T choice-major, its
    id over k token-major). A padding row (``row_pair`` past the
    last pair) holds a copy of the last value, not zeros: a select over
    the gathered array is a pass of its own over [rows, D], three a layer
    (PERF.md, PR 62), so the zero of a padding row is kept where it is one
    scalar a row, in ``pairs_to_rows``. Every row of the buffer is made,
    used or not: made only as far as the used tiles reach (a sixteenth of
    the buffer at a time, the rest skipped by predicate) the step was
    2.9 % shorter, and its length followed the routing: six seeds then
    spread by 0.5 % where they spread by 0.15 % so (PERF.md, PR 33)."""
    pairs = at["pair_row"].size
    pair = jnp.minimum(at["row_pair"], pairs - 1)
    n = values.shape[0]
    return values[pair % n if at.slot_axis == 0 else pair // (pairs // n)]


def _rows_to_tokens_impl(y, at):
    # choice-major [k, T, D]: k whole [T, D] slices added, no relayout;
    # token-major [T, k, D], where k rows are whole tiles
    picked = jnp.where(at["pair_held"][..., None], y[at["pair_row"]],
                       jnp.zeros((), y.dtype))
    return jnp.sum(picked.astype(jnp.float32),
                   axis=at.slot_axis).astype(y.dtype)


def _pairs_to_rows_impl(w, at):
    # [T, k] scalars in the order of the pairs' ids
    flat = (w.T if at.slot_axis == 0 else w).reshape(-1)
    return jnp.where(at["row_pair"] < flat.shape[0], _rows_of(flat, at), 0)


def _pairs_to_rows_bwd(at, g):
    dw = jnp.where(at["pair_held"], g[at["pair_row"]], 0.0)
    return (dw.T if at.slot_axis == 0 else dw), None


@jax.custom_vjp
def tokens_to_rows(x, at):
    """x [T, D] -> the buffer [rows, D]: each row its pair's token; a
    padding row holds a copy of the last token's row, which nothing reads:
    ``pairs_to_rows`` gives the row the weight 0, so its hidden activation
    and with it its share of every weight gradient and its own cotangent
    are 0 (``_mlp``), and ``rows_to_tokens`` gathers the rows of held
    pairs alone, in the forward and (as this function's vjp) backward."""
    return _rows_of(x, at)


@jax.custom_vjp
def rows_to_tokens(y, at):
    """The buffer y [rows, D] -> [T, D]: each token the sum (in f32) of
    the rows of its held pairs. A pair that is not held reads row 0, which
    is real data: it is masked here, over the pair domain."""
    return _rows_to_tokens_impl(y, at)


tokens_to_rows.defvjp(
    lambda x, at: (_rows_of(x, at), at),
    lambda at, g: (_rows_to_tokens_impl(g, at), None))
rows_to_tokens.defvjp(
    lambda y, at: (_rows_to_tokens_impl(y, at), at),
    lambda at, g: (_rows_of(g, at), None))


@jax.custom_vjp
def pairs_to_rows(w, at):
    """A value a pair, w [T, k] -> a value a row [rows], 0 for padding:
    THE zero of a padding row (``tokens_to_rows``). The weights come and
    their gradient goes back [T, k], as ``route`` makes them: where the
    pairs' tables are [k, T] the transposition is of scalars, and is made
    here."""
    return _pairs_to_rows_impl(w, at)


pairs_to_rows.defvjp(lambda w, at: (_pairs_to_rows_impl(w, at), at),
                     _pairs_to_rows_bwd)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def route(x, w_router, bias, *, top_k: int, routed_scale: float,
          score: str = "sigmoid"):
    """The router, by how it scores (a static fact of the model's
    configuration). ``sigmoid``: DeepSeek-V3's without group limiting
    (``n_group`` 1): s = sigmoid(x W) in f32; the k experts are the top k
    of s + bias (the selection bias is a buffer: no gradient reaches it);
    ``deepseek_v3.py`` (and ``kimi_linear.py`` through its sublayer) and
    ``nemotron_h.py`` call with it. ``softmax``: s = softmax(x W) over ALL
    experts in f32, the k largest, no bias (``bias`` None);
    ``qwen3_next.py`` and ``keye_vl2.py`` call with it. Either way their
    weights are s over the chosen k, summing to 1, times ``routed_scale``.
    x [T, D] -> (weights [T, k] f32, experts [T, k] int32)."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, top_k)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    else:
        raise ValueError(f"score is sigmoid or softmax, got {score!r}")
    # the chosen scores by comparison, not by index: the gradient of a
    # gather is a scatter into [T, E]
    s = jnp.sum(jnp.where(
        chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :],
        scores[:, None, :], 0.0), axis=-1)
    return s / jnp.sum(s, axis=1, keepdims=True) * routed_scale, chosen


def balance_term(x, w_router, *, top_k: int, groups: int = 1,
                 score: str = "softmax", bias=None):
    """A router's load-balancing term, a sequence at a time: E sum_e f_e
    P_e, f_e the share of the sequence's (token, choice) pairs that name
    expert e (a count: no gradient), P_e the sequence's mean of a token's
    share p_e of the scores, in f32. 1 under a level router, E / k times
    the chosen experts' mean share where every token makes the same k
    choices. ``softmax``: Switch Transformer's (eq. 4 to 6, with a token's
    k choices counted as GShard counts them), p = softmax(x W).
    ``sigmoid``: DeepSeek-V3's sequence-wise term (arXiv:2412.19437, eq. 17
    to 20), the form stated for a sigmoid router with a selection bias: the
    k choices are the top k of s + ``bias`` (``route``'s own), p_e = s_e /
    sum_j s_j over ALL experts, s = sigmoid(x W). The scores and the top k
    are ``route``'s own expressions on the same operands, so XLA makes them
    once. x [T, D], the rows of ``groups`` sequences one after another ->
    [groups] f32."""
    logits = jnp.dot(x, w_router.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, top_k)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        scores = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        raise ValueError(f"score is sigmoid or softmax, got {score!r}")
    e = scores.shape[1]
    named = jnp.sum(chosen[:, :, None] == jnp.arange(e)[None, None, :],
                    axis=1, dtype=jnp.float32)                 # [T, E]
    f = jnp.mean(named.reshape(groups, -1, e), axis=1) / top_k
    p = jnp.mean(scores.reshape(groups, -1, e), axis=1)
    return e * jnp.sum(jax.lax.stop_gradient(f) * p, axis=-1)


def sort_rows(chosen, experts_held: int, expert_offset: int, rows: int,
              tile: int = ROW_TILE):
    """Where each (token, choice) pair lies in the row buffer. chosen
    [T, k] int32 over all experts (k the choices, or the slots of
    ``compact_held``, an empty one -1) -> dict of

    ``pair_held`` bool: the pair's expert lives here;
    ``pair_row`` int32: its row (0 where not held);
    ``row_pair`` [rows] int32: the pair of each row, T * k for padding;
    ``tile_expert`` [rows // tile], ``n_used`` [1]: ``grouped_matmul``'s;
    ``held_rows``: pairs held, a scalar (the counter of the layer),

    a ``PairTables`` whose ``slot_axis`` (of k: ``slot_axis``) says how the
    pairs are named and their two tables laid out: 1, ``token * k +
    choice`` and [T, k]; 0, ``choice * T + token`` and [k, T] (module
    docstring). The SORT runs over the keys token-major either way, as
    ``chosen`` lies, so that an expert's rows stand in the order of their
    tokens and the row buffer is the same buffer; choice-major its pairs
    are renamed after it (scalars: [T, k] int32 transposed, the sorted
    [T * k] int32 renumbered)."""
    t, k = chosen.shape
    pairs = t * k
    n_tiles = rows // tile
    local = chosen - expert_offset
    held = (local >= 0) & (local < experts_held)
    key = jnp.where(held, local, experts_held).reshape(pairs)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)   # pairs by expert
    counts = jnp.sum(key[:, None] == jnp.arange(experts_held)[None, :],
                     axis=0, dtype=jnp.int32)                 # [held]
    first_pair = jnp.cumsum(counts) - counts     # in the sorted order
    tiles = jnp.maximum(-(-counts // tile), 1)
    last_tile = jnp.cumsum(tiles)
    first_row = (last_tile - tiles) * tile

    def of_expert(table, e):
        """table[e] for e [n] over the few held experts, by comparison:
        a gather of one scalar an index is the slow way on this chip."""
        return jnp.sum(jnp.where(
            e[:, None] == jnp.arange(experts_held)[None, :], table[None, :],
            0), axis=1)

    # pair -> row: its rank among its expert's pairs, from the sort
    rank = jnp.argsort(order).astype(jnp.int32) - of_expert(first_pair, key)
    pair_row = jnp.where(held.reshape(pairs),
                         of_expert(first_row, key) + rank, 0).reshape(t, k)
    axis = slot_axis(k)
    if axis == 0:
        order = order % k * t + order // k  # token * k + choice, renamed
        held, pair_row = held.T, pair_row.T
    # row -> pair: the row's tile names its expert
    n_used = last_tile[-1:]
    tile_expert = jnp.minimum(
        jnp.searchsorted(last_tile, jnp.arange(n_tiles, dtype=jnp.int32),
                         side="right"), experts_held - 1).astype(jnp.int32)

    e = jnp.repeat(tile_expert, tile)
    r = jnp.arange(rows, dtype=jnp.int32) - of_expert(first_row, e)
    row_pair = jnp.where(
        r < of_expert(counts, e),
        order[jnp.clip(of_expert(first_pair, e) + r, 0, pairs - 1)], pairs)
    return PairTables(
        {"pair_held": held, "pair_row": pair_row, "row_pair": row_pair,
         "tile_expert": tile_expert, "n_used": n_used,
         "held_rows": jnp.sum(counts)}, axis)


def compact_held(weights, chosen, experts_held: int, expert_offset: int):
    """A token's held choices in its first slots. weights [T, k] f32, chosen
    [T, k] int32 with k > ``experts_held`` -> ([T, held], [T, held]): the
    held choices in their order of choice, then empty slots (expert -1,
    which no chip holds, weight 0). By comparison, as ``of_expert``: no
    gather, no scatter, no sort, and the weights' gradient comes back
    through the same mask."""
    local = chosen - expert_offset
    held = (local >= 0) & (local < experts_held)
    # a held pair's slot: the held pairs before it in its token
    slot = jnp.cumsum(held, axis=1, dtype=jnp.int32) - 1
    into = held[:, :, None] & (
        slot[:, :, None] == jnp.arange(experts_held)[None, None, :])
    return (jnp.sum(jnp.where(into, weights[:, :, None], 0.0), axis=1),
            jnp.sum(jnp.where(into, chosen[:, :, None] + 1, 0), axis=1) - 1)


def _gated(x, w_gate, w_up, w_down, matmul, row_weight=None):
    """The gated SiLU MLP; ``row_weight`` [rows] (f32) scales a row's
    hidden activation, i.e. its output. A call over the row buffer MUST
    carry it: its 0 is all that keeps a padding row (a copy of a real
    token, ``tokens_to_rows``) out of the weights' gradients."""
    h = jax.nn.silu(matmul(x, w_gate)) * matmul(x, w_up)
    if row_weight is not None:
        h = (h.astype(jnp.float32) * row_weight[:, None]).astype(h.dtype)
    return matmul(h, w_down)


def _relu2(x, w_up, w_down, matmul, row_weight=None):
    """The squared-ReLU MLP of two matrices, ``W_down relu(x W_up)^2``, the
    square (and ``row_weight``, as in ``_gated``: a call over the row
    buffer MUST carry it) in f32."""
    h = jax.nn.relu(matmul(x, w_up))
    hf = jnp.square(h.astype(jnp.float32))
    if row_weight is not None:
        hf = hf * row_weight[:, None]
    return matmul(hf.astype(h.dtype), w_down)


def _mlp(expert: str, x, p, prefix: str, matmul, row_weight=None):
    """The MLP of kind ``expert`` over ``p``'s ``<prefix>_gate`` (a gated
    one alone), ``<prefix>_up`` and ``<prefix>_down`` in x's dtype. Over
    the row buffer (``matmul`` the grouped product) ``row_weight`` MUST be
    ``pairs_to_rows``' weights: the buffer's padding rows are not zeros,
    and the 0 of their weight on the hidden activation is what makes them
    add nothing to any gradient (module docstring). Over the tokens
    themselves (the shared expert) there is no padding and none is given."""
    w = lambda name: p[f"{prefix}_{name}"].astype(x.dtype)    # noqa: E731
    if expert == "swiglu":
        return _gated(x, w("gate"), w("up"), w("down"), matmul, row_weight)
    return _relu2(x, w("up"), w("down"), matmul, row_weight)


def _held_mlp(expert: str, buf, p, row_weight, at, tile: int):
    """``_mlp`` over the row buffer under ``p``'s ``e_*``: the first half
    (both products, the activation, ``row_weight``: ``pairs_to_rows``',
    whose 0 keeps a padding row out of every gradient) in the kernel pair
    ``expert_hidden``, then ``e_down``'s grouped product."""
    w = lambda name: p[f"e_{name}"].astype(buf.dtype)         # noqa: E731
    first = (w("gate"), w("up")) if expert == "swiglu" else (w("up"),)
    h = expert_hidden(buf, first, row_weight, at["tile_expert"],
                      at["n_used"], expert, tile)
    return grouped_matmul(h, w("down"), at["tile_expert"], at["n_used"],
                          tile)


def held_expert_layer(x, p, *, experts_held: int, expert_offset: int,
                      top_k: int, routed_scale: float, tile: int = ROW_TILE,
                      score: str = "sigmoid", expert: str = "swiglu"):
    """x [T, D] (normalised) -> (shared(x) + the held experts' part of
    sum_e w_e expert_e(x), [T, D] in x's dtype; the (token, choice) pairs
    that named a held expert, i.e. the rows the grouped product worked).
    The shared experts are OPTIONAL: where ``p`` holds no ``s_up`` nothing
    of the shared path is traced and the routed part alone is returned.

    ``p``: ``w_router`` [D, E] over ALL E experts and, where ``score`` is
    ``sigmoid``, ``router_bias`` [E] (``route``);
    ``e_gate``, ``e_up`` [held, D, F], ``e_down`` [held, F, D] of the
    experts ``expert_offset`` .. ``expert_offset + experts_held``;
    where the layer has shared experts, ``s_gate``, ``s_up`` [D, Fs],
    ``s_down`` [Fs, D] (side by side, one gated MLP); where it holds
    ``s_gate_w`` [D, 1] the shared experts' output is times
    ``sigmoid(x s_gate_w)``, f32.

    ``expert`` is the kind of every MLP of the layer, routed and shared:
    ``swiglu`` the gated SiLU MLP of three matrices, ``relu2`` ``W_down
    relu(x W_up)^2`` of two (no ``e_gate``, ``s_gate``). Where ``p`` holds
    ``w_fc1`` [D, L] and ``w_fc2`` [L, D] the routed experts work in a
    LATENT of width L: the router scores x, ``x w_fc1`` is what is gathered
    into the row buffer, ``e_up`` is [held, L, F] and ``e_down`` [held, F,
    L], the weighted sum comes back in L and ``w_fc2`` lifts it (scope
    ``latent_proj``, both projections); the shared experts read x itself.
    Both are static facts of the call."""
    if expert not in ("swiglu", "relu2"):   # before anything is traced: a
        # layer without shared experts reaches its first MLP late
        raise ValueError(f"expert is swiglu or relu2, got {expert!r}")
    t, d = x.shape
    dt = x.dtype
    n_experts = p["w_router"].shape[1]
    latent = p["w_fc1"].shape[1] if "w_fc1" in p else 0
    rows = buffer_rows(t, top_k, experts_held, tile)
    LAYER_COUNTS[(experts_held, n_experts)] += 1
    _record("rtpu.ops.expert_layer", "held",
            {"experts_held": experts_held, "of": n_experts, "top_k": top_k,
             "pair_slots": min(top_k, experts_held),
             # the axis of the pairs' tables a token's slots lie along
             "slot_axis": slot_axis(min(top_k, experts_held)),
             "expert_offset": expert_offset, "tokens": t, "row_buffer": rows,
             "row_tile": tile, "score": score,
             "shared": "s_up" in p, "shared_gate": "s_gate_w" in p,
             "expert": expert,
             "latent": latent,
             # the experts' first half is the kernel pair ``expert_hidden``,
             # worked this block of F at a time (``hidden_block``)
             "mlp_in": "kernel",
             "mlp_in_block": hidden_block(
                 latent or d, p["e_up"].shape[2], 1 + (expert == "swiglu"),
                 dt.itemsize, tile)})
    shared = None
    if "s_up" in p:
        with jax.named_scope("shared_expert"):
            shared = _mlp(expert, x, p, "s", jnp.dot)
            if "s_gate_w" in p:
                opened = jax.nn.sigmoid(jnp.dot(
                    x, p["s_gate_w"].astype(dt),
                    preferred_element_type=jnp.float32))
                shared = (shared.astype(jnp.float32) * opened).astype(dt)
    with jax.named_scope("router"):
        weights, chosen = route(x, p["w_router"], p.get("router_bias"),
                                top_k=top_k, routed_scale=routed_scale,
                                score=score)
        if top_k > experts_held:
            weights, chosen = compact_held(weights, chosen, experts_held,
                                           expert_offset)
        at = sort_rows(chosen, experts_held, expert_offset, rows, tile)
        held_rows = at.pop("held_rows")
    if latent:
        with jax.named_scope("latent_proj"):
            x = jnp.dot(x, p["w_fc1"].astype(dt))
    with jax.named_scope("router"):
        buf = tokens_to_rows(x, at)
        row_weight = pairs_to_rows(weights, at)
    with jax.named_scope("experts"):
        y = _held_mlp(expert, buf, p, row_weight, at, tile)
    with jax.named_scope("router"):
        routed = rows_to_tokens(y, at)
    if latent:
        with jax.named_scope("latent_proj"):
            routed = jnp.dot(routed, p["w_fc2"].astype(dt))
    if shared is None:
        return routed, held_rows
    with jax.named_scope("router"):
        return shared + routed, held_rows
