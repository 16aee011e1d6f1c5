"""Grouped-query attention over the keys a learned INDEXER selects for each
query (DeepSeek-Sparse-Attention's mechanism): a small head scores every
causal pair, the ``topk`` best keys of a query are its set, and the main
attention's softmax runs over that set alone. Training path, forward and a
backward written by hand.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          float32
    S_t     = the topk keys s <= t of largest I[t, s], ties to the lower s
              (``lax.top_k``'s rule); every s <= t where t < topk
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // G]
              * sm_scale) v[s, h // G]

Three steps, each under a ``jax.named_scope`` of its own (the innermost
scope is what a trace's reader attributes to):

* ``indexer`` / ``index_scores``: the scores of ONE block of queries
  (``q_chunk`` 512, the published tile) against the keys, a head at a time
  into a float32 [chunk, S] accumulator, bf16 operands. Never [S, S]: the
  blocks are walked by ``lax.map``, in up to four groups whose keys end
  where the group's last query does (62.5 % of the square at four).
* ``select`` / ``select``: the exact set, with no sort. The scores' bits
  are mapped to integers of the same order and the k-th largest of a row
  is found bit by bit, 32 counts over the block (``_kth_largest``): the
  largest v with count(score >= v) >= topk. The set is the scores above
  it and, of the scores equal to it, the first ``topk - count above`` by
  position (a prefix count, made only where some row of the block has
  more ties than it needs). That is ``lax.top_k``'s set; ``approx_max_k``
  or a threshold that keeps more or fewer is another result. The order
  is the total order ``lax.top_k`` sorts by: -0.0 below +0.0. The block
  leaves as one BYTE a pair, [chunk, S] int8 (268 MB a layer at S 16 384,
  where the indices [S, 2048] int32 would be 134 MB: a mask is what a
  block-wise kernel reads, indices what a gather reads).
* the attention (the caller's scope): flash kernels of their own
  (``KERNEL_NAMES``) that take the mask's tile beside q, k and v and work
  a GROUP at a time: one program holds one key/value head's block and the
  G query heads that read it, so k, v and the mask's tile are fetched once
  a group, and dk and dv leave summed over the group's query heads and
  over the queries that selected a key. Blocks above the diagonal are
  skipped by predicate and fetch nothing; a block under it is worked
  whole, however few of its pairs are selected (under random weights the
  selected keys are scattered evenly and no block is empty: what the
  mechanism saves in arithmetic this route does not collect; PERF.md,
  PR 59, has the gather route's cost beside it). Arrays cross the
  kernels head-major, [B, H, S, D], so a head is a leading index. The
  backward is ONE kernel on the forward's grid: it makes a block pair's
  probabilities again from q, k, the mask and the saved row statistics,
  ONCE, and feeds them to dq, dk and dv (5 products a pair and query
  head). dq accumulates over a query block's key blocks; dk and dv
  accumulate in VMEM for the whole sequence of a key/value head and leave
  in the last query block's row, so a call whose sequence they cannot
  hold is refused (``_bwd_vmem``).

No gradient passes the selection or the index scores: the indexer's three
inputs are under ``stop_gradient`` on entry, so no backward of it is traced.

Under a rematerialised layer the mask, the kernels' output and the row
statistics carry ``checkpoint_name``s (``sparse_mask``, ``sparse_out``,
``sparse_lse``): a policy that saves them runs neither the indexer, the
selection nor the forward kernel again in the layer's backward.

Where ``S <= topk`` every causal key is selected: the call repeats k and v
to the query heads and is ``flash_attention(causal=True)``, the routes the
other models take. Shapes the kernels cannot tile (S no multiple of 128, a
head that is no multiple of 128 lanes) take the plain masked form, which
holds [S, S] floats and is for small shapes alone.

What a call did is the trace-time event ``rtpu.ops.sparse_attention`` /
``selected`` and ``CALL_COUNTS``.

**A second selector** (``block_sparse_attention``, PR 69): BLOCKS of keys,
not keys, and no indexer: the layer's own query heads score mean-pooled
keys, a softmax a head, summed over a key/value group, max-pooled to blocks
of keys; the first block and a local window are forced; ONE set a group and
query. It leaves as one byte a (query, key block) and goes through the same
masked kernel pair, the group's query heads a program, in blocks the
backward's accumulators admit at the row's length.
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
from .flash_attention import flash_attention
from .kernel_common import (AB, ABT, ATB, LANES, NEG_INF, VMEM_BYTES, dot,
                            fit_block)

# Names of the two Pallas calls as a device trace shows them; part of the
# measurement (tests/test_tracing_names.py).
KERNEL_NAMES = {
    "fwd": "sparse_attn_fwd",
    # the whole backward: dq, and dk and dv summed over the group. The name
    # is the one the benchmark's reader looks for
    "bwd": "sparse_attn_bwd_dkv",
}

# Traced calls by route: "masked_flash" (the kernels here), "causal_flash"
# (S <= topk), "masked_reference" (no kernel).
CALL_COUNTS: collections.Counter = collections.Counter()

# Groups of query blocks whose keys end with the group: more groups trace
# and compile more copies of the block's program for less of the square.
_KEY_GROUPS = 4


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a row of ``seq`` tokens selects:
    sum_t min(t + 1, topk)."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


# ---------------------------------------------------------------------------
# the indexer's scores and the selection, a block of queries at a time
# ---------------------------------------------------------------------------


def index_scores(q_idx, k_idx, w_idx):
    """q_idx [C, Hi, Di], k_idx [S, Di] (bf16 in training), w_idx [C, Hi]
    float32 with every scale folded in -> I [C, S] float32: the heads one
    after the other into one accumulator, in their order."""
    def head(acc, qw):
        qj, wj = qw
        dots = jax.lax.dot_general(qj, k_idx, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return acc + wj[:, None] * jnp.maximum(dots, 0.0), None

    acc = jnp.zeros((q_idx.shape[0], k_idx.shape[0]), jnp.float32)
    return jax.lax.scan(head, acc, (jnp.swapaxes(q_idx, 0, 1),
                                    w_idx.astype(jnp.float32).T))[0]


def _sortable(x):
    """float32 -> uint32 of the same order (-inf lowest, above 0)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _kth_largest(keys, k: int):
    """keys [C, S] uint32 -> [C]: the largest v with count(keys >= v) >= k,
    i.e. the k-th largest key of a row; 0 where a row has fewer than k keys
    above 0. One bit a pass, the highest first."""
    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, thr)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(keys.shape[:1], jnp.uint32))


def select(scores, topk: int, row0=0):
    """scores [C, S] float32 of the queries ``row0`` .. ``row0 + C`` against
    the keys 0 .. S -> [C, S] int8, 1 where key s is one of the ``topk``
    keys s <= t of largest score, ties to the lower s: the set
    ``lax.top_k`` takes of the causal part of the row, all of it where it
    has no more than ``topk`` keys."""
    c, s = scores.shape
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (c, s), 0)
    causal = jax.lax.broadcasted_iota(jnp.int32, (c, s), 1) <= rows
    keys = jnp.where(causal, _sortable(scores), jnp.uint32(0))
    thr = _kth_largest(keys, topk)[:, None]
    above = keys > thr
    tie = (keys == thr) & causal
    need = topk - jnp.sum(above, axis=1, dtype=jnp.int32)
    # more ties than places: the first ``need`` of them by position
    tie = jax.lax.cond(
        jnp.any(jnp.sum(tie, axis=1, dtype=jnp.int32) > need),
        lambda: tie & (jnp.cumsum(tie, axis=1, dtype=jnp.int32)
                       <= need[:, None]),
        lambda: tie)
    return (above | tie).astype(jnp.int8)


def _top_set(keys, allowed, topk: int):
    """``select``'s set from any keys: keys [C, S] uint32, 0 where a column
    is not ``allowed`` -> [C, S] int8: the ``topk`` largest keys of a row,
    ties to the lower column; all that are allowed where a row has no more
    than ``topk``. (``select`` keeps its own lines: the names of its
    closures stand in the metadata of a cell's compiled program, which
    ``scripts/train_step_hlo.py --compare`` holds to the letter.)"""
    thr = _kth_largest(keys, topk)[:, None]
    above = keys > thr
    tie = (keys == thr) & allowed
    need = topk - jnp.sum(above, axis=1, dtype=jnp.int32)
    # more ties than places: the first ``need`` of them by position
    tie = jax.lax.cond(
        jnp.any(jnp.sum(tie, axis=1, dtype=jnp.int32) > need),
        lambda: tie & (jnp.cumsum(tie, axis=1, dtype=jnp.int32)
                       <= need[:, None]),
        lambda: tie)
    return (above | tie).astype(jnp.int8)


def selection_mask(q_idx, k_idx, w_idx, *, topk: int, q_chunk: int = 512):
    """q_idx [B, S, Hi, Di], k_idx [B, S, Di], w_idx [B, S, Hi] (scaled)
    -> [B, S, S] int8, 1 where query t selects key s. A block of
    ``q_chunk`` queries at a time (``index_scores`` then ``select``); the
    float32 scores of a block never leave it."""
    b, s, hi, di = q_idx.shape
    chunk = _chunk_of(s, q_chunk)
    n = s // chunk
    groups = min(_KEY_GROUPS, n)
    bounds = [n * g // groups for g in range(groups + 1)]
    q_idx = q_idx.reshape(b, n, chunk, hi, di)
    w_idx = w_idx.astype(jnp.float32).reshape(b, n, chunk, hi)
    parts = []
    for first, last in zip(bounds, bounds[1:]):
        keys = k_idx[:, :last * chunk]

        def block(args, keys=keys):
            qc, wc, row, at = args
            with jax.named_scope("indexer"):
                scores = index_scores(qc, keys[at], wc)
            with jax.named_scope("select"):
                return select(scores, topk, row * chunk)

        m = last - first
        flat = lambda x: x[:, first:last].reshape(             # noqa: E731
            (b * m,) + x.shape[2:])
        part = jax.lax.map(block, (
            flat(q_idx), flat(w_idx),
            jnp.tile(jnp.arange(first, last, dtype=jnp.int32), b),
            jnp.repeat(jnp.arange(b, dtype=jnp.int32), m)))
        with jax.named_scope("select"):
            parts.append(jnp.pad(
                part.reshape(b, m * chunk, last * chunk),
                ((0, 0), (0, 0), (0, s - last * chunk))))
    with jax.named_scope("select"):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)


def _chunk_of(seq: int, q_chunk: int) -> int:
    """The largest divisor of ``seq`` that is no more than ``q_chunk``."""
    chunk = min(q_chunk, seq)
    while seq % chunk:
        chunk -= 1
    return chunk


# ---------------------------------------------------------------------------
# the second selector: BLOCKS of keys, chosen by the main heads' own scores
# on mean-pooled keys, one set a key/value group and query (InfLLM-v2)
# ---------------------------------------------------------------------------


def block_selected_pairs(seq: int, block: int, blocks: int) -> int:
    """(query, key) pairs a row of ``seq`` tokens selects where a query
    takes ``blocks`` blocks of ``block`` keys, its own among them and of
    that the causal part; every causal key where it sees no more blocks."""
    total = 0
    for own in range(-(-seq // block)):
        rows = min(block, seq - own * block)
        total += rows * min(own, blocks - 1) * block + rows * (rows + 1) // 2
    return total


def pooled_keys(k, size: int, stride: int):
    """k [B, S, Hkv, D] -> [B, S / stride, Hkv, D] in k's dtype: entry j the
    float32 mean of keys stride j .. stride j + size - 1 (size = 2 stride);
    the last entry, whose window would pass the row's end, is zero and no
    query ever sees it."""
    b, s, kv, d = k.shape
    halves = jnp.sum(k.astype(jnp.float32).reshape(
        b, s // stride, stride, kv, d), axis=2)
    means = (halves[:, :-1] + halves[:, 1:]) / size
    return jnp.pad(means, ((0, 0), (0, 1), (0, 0), (0, 0))).astype(k.dtype)


def pooled_scores(q, kc, row0, *, size: int, stride: int, sm_scale: float):
    """q [C, G, D] of the queries ``row0`` .. and ONE group's pooled keys kc
    [J, D] -> P [C, J] float32: each head's softmax over the pooled keys
    whose window ends at or before its query (stride j + size - 1 <= t;
    zero elsewhere, and a zero row where there is none), summed over the
    group's heads in their order."""
    c, j = q.shape[0], kc.shape[0]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (c, j), 0)
    seen = jax.lax.broadcasted_iota(jnp.int32, (c, j), 1) * stride \
        + (size - 1) <= rows

    def head(acc, qh):
        s = jax.lax.dot_general(qh, kc, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(seen, s, NEG_INF)
        e = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=1, keepdims=True)),
                      0.0)
        return acc + e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True),
                                     1e-30), None

    return jax.lax.scan(head, jnp.zeros((c, j), jnp.float32),
                        jnp.swapaxes(q, 0, 1))[0]


def block_scores(p, per_block: int):
    """P [C, J] -> [C, J / per_block]: block b's score the largest P of the
    pooled windows that overlap it, j = per_block b - 1 .. per_block b +
    per_block - 1 (a max-pool of per_block + 1, stride per_block, padding
    1)."""
    c, j = p.shape
    cut = p.reshape(c, j // per_block, per_block)
    before = jnp.pad(cut[:, :-1, -1], ((0, 0), (1, 0)))
    return jnp.maximum(jnp.max(cut, axis=2), before)


def select_blocks(scores, *, blocks: int, block: int, init_blocks: int,
                  local_blocks: int, row0=0):
    """scores [C, S / block] float32 of the queries ``row0`` .. -> [C,
    S / block] int8, 1 where the query selects the block: the first
    ``init_blocks`` and the ``local_blocks`` that end with the query's own
    are FORCED (they stand above every score), with them the blocks of
    largest score up to ``blocks`` in all, ties to the lower block, never a
    block after the query's own; all it sees where that is no more than
    ``blocks``."""
    c, n = scores.shape
    own = (row0 + jax.lax.broadcasted_iota(jnp.int32, (c, n), 0)) // block
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, n), 1)
    seen = cols <= own
    forced = (cols < init_blocks) | (cols > own - local_blocks)
    keys = jnp.where(seen, jnp.where(forced, jnp.uint32(0xFFFFFFFF),
                                     _sortable(scores)), jnp.uint32(0))
    return _top_set(keys, seen, blocks)


def block_selection(q, k, *, block: int, blocks: int, init_blocks: int,
                    local_blocks: int, pool, sm_scale: float,
                    q_chunk: int = 512):
    """q [B, S, H, D], k [B, S, Hkv, D] -> [B, Hkv, S, S / block] int8, 1
    where a query of key/value group g selects key block b. A block of
    ``q_chunk`` queries and one group at a time (``pooled_scores`` under
    the scope ``indexer``, then ``block_scores`` and ``select_blocks`` under
    ``select``); the float32 scores of a block never leave it."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    size, stride = pool
    chunk = _chunk_of(s, q_chunk)
    n = s // chunk
    with jax.named_scope("indexer"):
        kc = jnp.moveaxis(pooled_keys(k, size, stride), 2, 1)  # [B,Hkv,J,D]
        kc = kc.reshape(b * kv, s // stride, d)
        # [B * Hkv * n, chunk, G, D]: a group's block of queries an entry
        qg = jnp.moveaxis(q.reshape(b, n, chunk, kv, h // kv, d), 3, 1)
        qg = qg.reshape(b * kv * n, chunk, h // kv, d)

    def one(args):
        qc, row, at = args
        with jax.named_scope("indexer"):
            p = pooled_scores(qc, kc[at], row * chunk, size=size,
                              stride=stride, sm_scale=sm_scale)
        with jax.named_scope("select"):
            return select_blocks(
                block_scores(p, block // stride), blocks=blocks, block=block,
                init_blocks=init_blocks, local_blocks=local_blocks,
                row0=row * chunk)

    picked = jax.lax.map(one, (
        qg, jnp.tile(jnp.arange(n, dtype=jnp.int32), b * kv),
        jnp.repeat(jnp.arange(b * kv, dtype=jnp.int32), n)))
    with jax.named_scope("select"):
        return picked.reshape(b, kv, s, s // block)


def expand_blocks(picked, block: int):
    """[B, S, S / block] int8 -> [B, S, S] int8: one byte a (query, key)
    pair, what the masked kernels read: a selected block's keys, of the
    query's own block those up to the query."""
    b, s, _ = picked.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    return jnp.where(cols <= rows, jnp.repeat(picked, block, axis=2),
                     jnp.int8(0))


# ---------------------------------------------------------------------------
# the kernels: q, o, dO, dq [B, H, S, D]; k, v, dk, dv [B, Hkv, S, D];
# mask [B, S, S] int8; lse [B * H, 1, S] float32
# ---------------------------------------------------------------------------


def _selected(mask_ref):
    """The mask's tile as a predicate, made once a program for the group's
    heads (the tile is widened first: the VPU compares 32-bit lanes)."""
    return mask_ref[...].astype(jnp.int32) != 0


def _masked_scores(q, k, sel, sm_scale):
    return jnp.where(sel, dot(q * jnp.asarray(sm_scale, q.dtype), k, ABT),
                     NEG_INF)


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, block_q, block_k,
                num_kb, group):
    """Grid (B, key/value heads, query blocks, key blocks), keys innermost:
    the float32 scratch (m, l, acc, one of each a query head of the group)
    carries over a query block's key blocks."""
    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(qi * block_q + block_q - 1 >= kb * block_k)
    def _compute():
        sel = _selected(mask_ref)
        k = k_ref[...]
        v = v_ref[...]

        def head(h, carry):
            s = _masked_scores(q_ref[h], k, sel, sm_scale)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + dot(p.astype(v.dtype), v, AB)
            m_scr[h] = m_new
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        for h in range(group):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[h] = (acc_scr[h] / l).astype(o_ref.dtype)
            lse_ref[h] = (m_scr[h] + jnp.log(l)).T


def _bwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, delta_scr, dk_scr, dv_scr, *,
                sm_scale, block_q, block_k, num_qb, num_kb, group):
    """The whole backward, grid as the forward's: the probabilities and
    dL/ds of a block pair and query head are made ONCE (the ``sm_scale`` of
    s = (q scale) k^T folded in once) and feed dq, dk and dv: 5 products.
    dq accumulates over a query block's key blocks and leaves at the last.
    dk and dv accumulate in VMEM for the WHOLE sequence of the program's
    one key/value head ([key blocks, block_k, D] f32 each), summed over
    the group's query heads: a key block is complete in the LAST query
    block's row, which computes every key block, and is written there;
    until then its output names one block and holds still, so each goes to
    HBM once. delta = rowsum(dO * O) a head is made at the row's first step
    from the tiles at hand and never leaves VMEM."""
    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_scr[kb] = jnp.zeros(dk_scr.shape[1:], dk_scr.dtype)
        dv_scr[kb] = jnp.zeros(dv_scr.shape[1:], dv_scr.dtype)

    @pl.when(kb == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for h in range(group):
            delta_scr[h] = jnp.sum(
                do_ref[h].astype(jnp.float32) * o_ref[h].astype(jnp.float32),
                axis=-1, keepdims=True).T

    @pl.when(qi * block_q + block_q - 1 >= kb * block_k)
    def _compute():
        # keys down, queries across: the row statistics broadcast as they
        # are stored, [1, block_q], and of the three gradients' products
        # only dq's takes a transposed operand (PERF.md, PR 60: 3 % faster)
        sel = mask_ref[...].astype(jnp.float32).T != 0
        k = k_ref[...]
        v = v_ref[...]

        def head(h, carry):
            q = q_ref[h]
            do = do_ref[h]
            s = jnp.where(
                sel, dot(k, q * jnp.asarray(sm_scale, q.dtype), ABT),
                NEG_INF)
            p = jnp.exp(s - lse_ref[h])
            ds = (p * (dot(v, do, ABT) - delta_scr[h])
                  * sm_scale).astype(k.dtype)
            p = p.astype(do.dtype)
            dq_scr[h] += dot(ds, k, ATB)
            dv_scr[kb] += dot(p, do, AB)
            dk_scr[kb] += dot(ds, q, AB)
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    @pl.when(kb == num_kb - 1)
    def _dq_out():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(qi == num_qb - 1)
    def _dkv_out():
        dk_ref[...] = dk_scr[kb].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[kb].astype(dv_ref.dtype)


def _blocks(seq: int, block_q: int, block_k: int):
    return fit_block(block_q, seq), fit_block(block_k, seq)


def _specs(group: int, kv: int, d: int, block_q: int, block_k: int):
    """Block specs of the grid (B, key/value heads, query blocks, key
    blocks). A step the causal predicate skips names the last key block its
    query block sees, which its neighbour fetched, so nothing is fetched
    for it."""
    def spec(shape, where):
        return pl.BlockSpec(shape, lambda b, g, i, j: where(
            b, g, i, jnp.minimum(j, (i * block_q + block_q - 1) // block_k)))

    return {
        "mask": spec((None, block_q, block_k), lambda b, g, q, k: (b, q, k)),
        "q": spec((None, group, block_q, d), lambda b, g, q, k: (b, g, q, 0)),
        "kv": spec((None, None, block_k, d), lambda b, g, q, k: (b, g, k, 0)),
        "row": spec((group, 1, block_q),
                    lambda b, g, q, k: (b * kv + g, 0, q)),
    }


def _params(blocks: str):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", blocks, "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES)


def _masked_fwd(q, k, v, mask, sm_scale, block_q, block_k):
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv
    block_q, block_k = _blocks(s, block_q, block_k)
    num_kb = s // block_k
    sp = _specs(group, kv, d, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_kb=num_kb, group=group),
        grid=(b, kv, s // block_q, num_kb),
        in_specs=[sp["mask"], sp["q"], sp["kv"], sp["kv"]],
        out_specs=[sp["q"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, d), jnp.float32)],
        compiler_params=_params("parallel"),
        name=KERNEL_NAMES["fwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * s * s * d // 2,
            bytes_accessed=(2 * q.size + k.size + v.size) * q.dtype.itemsize
            + mask.size // 2,
            transcendentals=b * h * s * s // 2),
    )(mask, q, k, v)


def _bwd_vmem(seq: int, block_q: int, block_k: int, group: int, d: int,
              itemsize: int) -> int:
    """Bytes of VMEM the backward kernel asks for: what it keeps of the
    whole sequence beside a block pair's tiles (``sparse_attention``
    refuses a call over ``VMEM_BYTES``). At S 16 384 in blocks of 1024 x
    1024, 8 query heads of 128 to a key/value head, bf16: 16.8 MB of dk and
    dv + 16.8 MB of score tiles + 4.2 MB of dq + 21 MB of operands: 58.7
    of 67.1; S 24 576 is the longest that fits in these blocks, S 49 152 in
    blocks of 512 x 512."""
    whole = 2 * seq * d * 4                                 # dk, dv
    tiles = block_q * block_k * (3 * 4 + 2 * itemsize)      # s, dP, dS; p, dS
    dq = group * block_q * d * 4
    # q, o, dO in and dq out a group; k, v in and dk, dv out; the mask's
    # tile; each double-buffered
    operands = 2 * (itemsize * 4 * d * (group * block_q + block_k)
                    + block_q * block_k)
    return whole + tiles + dq + operands


def _masked_bwd(q, k, v, mask, o, lse, g, sm_scale, block_q, block_k):
    """-> dq, dk, dv: ONE kernel, launched under the name the benchmark's
    reader knows (``KERNEL_NAMES``)."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv
    block_q, block_k = _blocks(s, block_q, block_k)
    num_qb, num_kb = s // block_q, s // block_k
    sp = _specs(group, kv, d, block_q, block_k)
    # dk and dv leave in the last query block's row and name one block
    # until then, so that nothing is written before
    dkv = pl.BlockSpec(
        (None, None, block_k, d),
        lambda b, g, i, j: (b, g, jnp.where(i == num_qb - 1, j, 0), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_qb=num_qb, num_kb=num_kb,
                          group=group),
        grid=(b, kv, num_qb, num_kb),
        in_specs=[sp["mask"], sp["q"], sp["kv"], sp["kv"], sp["q"], sp["q"],
                  sp["row"]],
        out_specs=[sp["q"], dkv, dkv],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32),
                        pltpu.VMEM((group, 1, block_q), jnp.float32),
                        pltpu.VMEM((num_kb, block_k, d), jnp.float32),
                        pltpu.VMEM((num_kb, block_k, d), jnp.float32)],
        compiler_params=_params("arbitrary"),
        name=KERNEL_NAMES["bwd"],
        interpret=kernel_common.use_interpret(),
        cost_estimate=pl.CostEstimate(
            flops=10 * b * h * s * s * d // 2,
            bytes_accessed=(4 * q.size + 2 * k.size + 2 * v.size)
            * q.dtype.itemsize + mask.size // 2,
            transcendentals=b * h * s * s // 2),
    )(mask, q, k, v, o, g, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked_gqa(q, k, v, mask, sm_scale, block_q, block_k):
    return _masked_fwd(q, k, v, mask, sm_scale, block_q, block_k)[0]


def _masked_gqa_fwd(q, k, v, mask, sm_scale, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _masked_fwd(q, k, v, mask, sm_scale, block_q, block_k)
    o = checkpoint_name(o, "sparse_out")
    lse = checkpoint_name(lse, "sparse_lse")
    return o, (q, k, v, mask, o, lse)


def _masked_gqa_bwd(sm_scale, block_q, block_k, res, g):
    q, k, v, mask, o, lse = res
    return _masked_bwd(q, k, v, mask, o, lse, g, sm_scale, block_q,
                       block_k) + (None,)


_masked_gqa.defvjp(_masked_gqa_fwd, _masked_gqa_bwd)


def masked_attention_reference(q, k, v, mask, sm_scale):
    """The plain form, float32, [S, S] scores a head: q [B, S, H, D], k and
    v [B, S, Hkv, D], mask [B, S, S] -> [B, S, H, D]. Small shapes alone."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    qg = q.astype(jnp.float32).reshape(b, s, kv, h // kv, d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg,
                        k.astype(jnp.float32)) * sm_scale
    scores = jnp.where(mask[:, None, None] != 0, scores, -jnp.inf)
    out = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(scores, axis=-1),
                     v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_idx: jax.Array, k_idx: jax.Array, w_idx: jax.Array,
                     *, topk: int, sm_scale: Optional[float] = None,
                     q_chunk: int = 512, block_q: int = 1024,
                     block_k: int = 1024) -> jax.Array:
    """q [B, S, H, D], k and v [B, S, Hkv, D] (H a multiple of Hkv; query
    head h reads key/value head h // (H / Hkv)); the indexer's q_idx
    [B, S, Hi, Di], its ONE key k_idx [B, S, Di] and head weights w_idx
    [B, S, Hi] with every scale folded in -> [B, S, H, D]: each query's
    softmax over the ``topk`` causal keys of largest index score (module
    docstring). No gradient reaches the indexer's three inputs."""
    from jax.ad_checkpoint import checkpoint_name

    b, s, h, d = q.shape
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"{h} query heads over {kv} key/value heads")
    if sm_scale is None:
        sm_scale = d ** -0.5
    kernels = s % LANES == 0 and d % LANES == 0
    route = "causal_flash" if s <= topk else \
        "masked_flash" if kernels else "masked_reference"
    fused = route == "masked_flash"
    if fused:
        need = _bwd_vmem(s, *_blocks(s, block_q, block_k), h // kv, d,
                         q.dtype.itemsize)
        if need > VMEM_BYTES:
            # the plain form would make S x S floats a head: no route to
            # fall to
            raise ValueError(
                f"sparse_attention at S={s}: the backward keeps {need} bytes "
                f"in VMEM, the limit is {VMEM_BYTES}; smaller blocks than "
                f"{block_q} x {block_k} keep less")
    CALL_COUNTS[route] += 1
    _record("rtpu.ops.sparse_attention", "selected", {
        "seq": s, "topk": topk, "index_heads": q_idx.shape[2],
        "index_dim": q_idx.shape[3], "heads": h, "kv_heads": kv,
        "head_dim": d, "route": route,
        "backward": "fused" if fused else "none",
        "bwd_products": 5 if fused else 0,
        "saved": "none" if s <= topk else "mask_int8",
        "q_chunk": _chunk_of(s, q_chunk),
        "selected_pairs": selected_pairs(s, topk),
        "causal_pairs": s * (s + 1) // 2})
    if route == "causal_flash":
        # every causal key is selected: the indexer decides nothing
        return flash_attention(q, jnp.repeat(k, h // kv, axis=2),
                               jnp.repeat(v, h // kv, axis=2), causal=True,
                               sm_scale=sm_scale)
    q_idx, k_idx, w_idx = jax.lax.stop_gradient((q_idx, k_idx, w_idx))
    mask = checkpoint_name(
        selection_mask(q_idx, k_idx, w_idx, topk=topk, q_chunk=q_chunk),
        "sparse_mask")
    if route == "masked_reference":
        return masked_attention_reference(q, k, v, mask, sm_scale)
    major = lambda x: jnp.swapaxes(x, 1, 2)                   # noqa: E731
    return major(_masked_gqa(major(q), major(k), major(v), mask, sm_scale,
                             block_q, block_k))


def _fitting_blocks(seq: int, group: int, d: int, itemsize: int):
    """The largest square blocks whose backward ``_bwd_vmem`` admits at this
    length, or None where none of 128 or more does."""
    for side in (1024, 512, 256, 128):
        fitted = _blocks(seq, side, side)
        if _bwd_vmem(seq, *fitted, group, d, itemsize) <= VMEM_BYTES:
            return fitted
    return None


def block_sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           block: int = 64, blocks: int = 96,
                           init_blocks: int = 1, local_blocks: int = 32,
                           pool=(32, 16), dense_len: int = 8192,
                           sm_scale: Optional[float] = None,
                           q_chunk: int = 512) -> jax.Array:
    """q [B, S, H, D], k and v [B, S, Hkv, D] -> [B, S, H, D]: grouped-query
    attention over the ``blocks`` blocks of ``block`` keys that each query's
    key/value GROUP selects (InfLLM-v2's mechanism: no parameter; the
    group's own query heads score mean-pooled keys, ``pooled_scores``,
    ``block_scores``, ``select_blocks``), causal inside the query's own
    block. A row of at most ``dense_len`` tokens attends causally over
    everything. No gradient passes the selection. The selection leaves as
    one byte a (query, key block), [B, Hkv, S, S / block] int8 under the
    ``checkpoint_name`` ``sparse_mask``, and is expanded to the byte a pair
    the masked kernels read one key/value head at a time (``expand_blocks``:
    made again in a rematerialised layer's backward, never kept); the kernel
    pair's blocks are the largest its backward's accumulators admit at the
    length (``_fitting_blocks``)."""
    from jax.ad_checkpoint import checkpoint_name

    b, s, h, d = q.shape
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"{h} query heads over {kv} key/value heads")
    size, stride = pool
    if size != 2 * stride or block % stride:
        raise ValueError(f"pooling {pool} over blocks of {block}: windows of "
                         "two strides and whole strides a block are built")
    if sm_scale is None:
        sm_scale = d ** -0.5
    dense = s <= dense_len or s <= blocks * block
    if not dense and s % block:
        raise ValueError(f"a row of {s} is not whole blocks of {block}")
    tiled = not dense and s % LANES == 0 and d % LANES == 0
    fits = _fitting_blocks(s, h // kv, d, q.dtype.itemsize) if tiled else None
    if tiled and not fits:
        # the plain form would make S x S floats a head: no route to fall to
        raise ValueError(
            f"block_sparse_attention at S={s}: the backward keeps "
            f"{_bwd_vmem(s, LANES, LANES, h // kv, d, q.dtype.itemsize)} "
            f"bytes in VMEM in its smallest blocks, the limit is "
            f"{VMEM_BYTES}")
    route = "causal_flash" if dense else \
        "masked_flash" if fits else "masked_reference"
    CALL_COUNTS[route] += 1
    _record("rtpu.ops.sparse_attention", "selected", {
        "seq": s, "select_by": "block", "block": block, "blocks": blocks,
        "init_blocks": init_blocks, "local_blocks": local_blocks,
        "pool": list(pool), "dense_len": dense_len, "heads": h,
        "kv_heads": kv, "head_dim": d, "route": route,
        "kernel_blocks": list(fits) if fits else None,
        "backward": "fused" if fits else "none",
        "bwd_products": 5 if fits else 0,
        "saved": "none" if dense else "block_mask_int8",
        "q_chunk": _chunk_of(s, q_chunk),
        "selected_pairs": s * (s + 1) // 2 if dense
        else block_selected_pairs(s, block, blocks),
        "causal_pairs": s * (s + 1) // 2})
    if dense:
        return flash_attention(q, jnp.repeat(k, h // kv, axis=2),
                               jnp.repeat(v, h // kv, axis=2), causal=True,
                               sm_scale=sm_scale)
    picked = checkpoint_name(block_selection(
        *jax.lax.stop_gradient((q, k)), block=block, blocks=blocks,
        init_blocks=init_blocks, local_blocks=local_blocks, pool=pool,
        sm_scale=sm_scale, q_chunk=q_chunk), "sparse_mask")
    group = h // kv
    major = lambda x: jnp.swapaxes(x, 1, 2)                   # noqa: E731
    outs = []
    for g in range(kv):        # a group's selection is its own: a call each
        with jax.named_scope("select"):
            mask = expand_blocks(picked[:, g], block)
        qg = q[:, :, g * group:(g + 1) * group]
        kg, vg = k[:, :, g:g + 1], v[:, :, g:g + 1]
        outs.append(
            major(_masked_gqa(major(qg), major(kg), major(vg), mask, sm_scale,
                              *fits)) if fits else
            masked_attention_reference(qg, kg, vg, mask, sm_scale))
    return outs[0] if kv == 1 else jnp.concatenate(outs, axis=2)
