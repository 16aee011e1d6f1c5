"""ISSUE 69: the second selector of ``ops/sparse_attention.py``: BLOCKS of
keys chosen by the layer's own query heads on mean-pooled keys, one set a
key/value group and query (``block_sparse_attention``), against a plain
per-query implementation written out here in numpy float64 (pooling, each
head's softmax over the pooled windows that end at or before the query, the
group's sum, the max-pool to blocks, the forced blocks, ties to the lower
block, "all of them" while a query sees no more blocks than it may take),
and the attention over the selection against ``masked_attention_reference``
at a group of 16 query heads, the kernels interpreted.

Tolerances. Everything is float32 here. The pooled probabilities differ
from the float64 form by the order of their sums: 3e-7 was read on rows that
sum to 4 (4 heads' softmaxes); the limit is 2e-6. The SETS
are compared exactly: on these seeds the nearest two block scores at a
threshold lie 1e-4 apart, far above that noise (``test_select_blocks...``
holds the tie rule itself on scores that DO tie). The kernels' output
differs from the plain masked form by the online softmax's order: 6e-7 of
the largest entry read, gradients 1.5e-6; the limit is 1e-5.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the module, not the function of its name that the package exports
sa = importlib.import_module("ray_tpu.ops.sparse_attention")

SMALL = dict(block=16, blocks=6, init_blocks=1, local_blocks=2, pool=(8, 4))


def plain_scores(q, k, t, *, block, pool, scale):
    """One query t of one group: q [G, D], k [S, D] float64 -> (P [J] the
    group's summed probabilities over the pooled windows, B [S / block] the
    block scores)."""
    size, stride = pool
    s = k.shape[0]
    windows = [j for j in range(s // stride - 1) if stride * j + size - 1 <= t]
    p = np.zeros(s // stride)
    if windows:
        kc = np.stack([k[stride * j:stride * j + size].mean(0)
                       for j in windows])
        for qh in q:
            e = np.exp(kc @ qh * scale - (kc @ qh * scale).max())
            p[windows] += e / e.sum()
    per = block // stride
    b = np.array([max(p[j] for j in range(per * i - 1, per * i + per)
                      if j >= 0) for i in range(s // block)])
    return p, b


def plain_set(b, t, *, block, blocks, init_blocks, local_blocks):
    """The set of query t from its block scores: forced blocks first, then
    by score, ties to the lower block; never a block after its own."""
    own = t // block
    seen = list(range(own + 1))
    forced = [i for i in seen if i < init_blocks or i > own - local_blocks]
    rest = sorted((i for i in seen if i not in forced),
                  key=lambda i: (-b[i], i))
    picked = np.zeros(len(b), np.int8)
    picked[(forced + rest)[:blocks]] = 1
    return picked


def draw(seed, s, h, kv, d, b=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.float32)
    k, v = (jax.random.normal(kk, (b, s, kv, d), jnp.float32)
            for kk in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], (b, s, h, d), jnp.float32)


def test_pooled_and_block_scores_equal_the_plain_form():
    s, g, d = 128, 4, 32
    q, k, _, _ = draw(1, s, g, 1, d)
    scale = d ** -0.5
    kc = sa.pooled_keys(k, 8, 4)[0, :, 0]
    assert kc.shape == (s // 4, d) and not np.asarray(kc[-1]).any()
    p = sa.pooled_scores(q[0, 32:96], kc, 32, size=8, stride=4,
                         sm_scale=scale)
    blk = sa.block_scores(p, 4)
    q64 = np.asarray(q[0], np.float64)
    k64 = np.asarray(k[0, :, 0], np.float64)
    for i, t in enumerate(range(32, 96)):
        want_p, want_b = plain_scores(q64[t], k64, t, block=16, pool=(8, 4),
                                      scale=scale)
        assert np.abs(np.asarray(p[i]) - want_p).max() < 2e-6
        assert np.abs(np.asarray(blk[i]) - want_b).max() < 2e-6
    # each row sums to its heads' count: 4 softmaxes
    assert np.allclose(np.asarray(p.sum(1)), 4.0, atol=1e-5)
    # the first queries see no pooled window at all: a zero row, no nan
    first = sa.pooled_scores(q[0, :8], kc, 0, size=8, stride=4,
                             sm_scale=scale)
    assert not np.asarray(first[:7]).any() and np.isfinite(first).all()


@pytest.mark.parametrize("scores_of", ["normal", "tied", "all_of_them"])
def test_select_blocks_is_the_plain_set(scores_of):
    """Forced blocks stand above every score (+inf among them), the rest by
    score with ties to the LOWER block, never a block after the query's
    own, and every block it sees where that is no more than ``blocks``."""
    n, block, blocks = 48, 4, 12
    rng = np.random.default_rng(7)
    scores = rng.normal(size=(n * block, n)).astype(np.float32)
    if scores_of == "tied":
        # dozens of ties a threshold; + 0.0: no -0.0, which the total order
        # puts below +0.0 (block scores are sums of probabilities)
        scores = np.round(scores * 2) / 2 + 0.0
        scores[5::7] = np.inf     # a score no forced block stands under
    row0 = 8
    rows = slice(0, blocks * block) if scores_of == "all_of_them" \
        else slice(0, n * block - row0)
    kw = dict(blocks=blocks, block=block, init_blocks=2, local_blocks=3)
    got = np.asarray(sa.select_blocks(jnp.asarray(scores[rows]), row0=row0,
                                      **kw))
    for i in range(got.shape[0]):
        want = plain_set(scores[rows][i], row0 + i, **kw)
        assert (got[i] == want).all(), (i, got[i], want)
    t = row0 + np.arange(got.shape[0])
    assert (got.sum(1) == np.minimum(t // block + 1, blocks)).all()
    if scores_of == "all_of_them":
        assert (got.sum(1) == t // block + 1)[:(blocks - 3) * block].all()


@pytest.mark.parametrize("kv", [1, 2])
def test_the_selection_equals_the_plain_per_query_form(kv):
    s, g, d = 256, 4, 32
    q, k, _, _ = draw(2 + kv, s, g * kv, kv, d)
    scale = d ** -0.5
    picked = np.asarray(sa.block_selection(
        q, k, sm_scale=scale, q_chunk=64, **SMALL))
    assert picked.shape == (1, kv, s, s // 16) and picked.dtype == np.int8
    q64, k64 = np.asarray(q[0], np.float64), np.asarray(k[0], np.float64)
    decided = 0
    for grp in range(kv):
        for t in range(s):
            _, b = plain_scores(q64[t, grp * g:(grp + 1) * g], k64[:, grp], t,
                                block=16, pool=(8, 4), scale=scale)
            want = plain_set(b, t, block=16, blocks=6, init_blocks=1,
                             local_blocks=2)
            assert (picked[0, grp, t] == want).all(), (grp, t)
            decided += int(want.sum() < t // 16 + 1)
    assert decided > kv * s // 2          # the scores decided something
    if kv == 2:                           # a group's set is its own
        assert (picked[0, 0] != picked[0, 1]).any()


def test_expanded_blocks_are_causal_inside_the_own_block():
    picked = jnp.zeros((1, 64, 4), jnp.int8).at[:, :, 0].set(1).at[
        0, 40:, 2].set(1)
    mask = np.asarray(sa.expand_blocks(picked, 16))[0]
    assert mask.shape == (64, 64)
    assert mask[5, :6].all() and not mask[5, 6:].any()
    assert mask[45, 32:46].all() and not mask[45, 46:].any()
    assert mask[45, :16].all() and not mask[45, 16:32].any()


def _events(since):
    from ray_tpu.perf import recorder

    return [dict(e["data"]) for e in recorder.get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.sparse_attention" and e["ts"] >= since]


def test_the_kernels_at_group_16_equal_the_plain_masked_attention():
    """One key/value head and its 16 query heads a program, heads of 128,
    three key blocks of 128 a row, a selection that leaves blocks out:
    forward and ``jax.vjp`` against ``masked_attention_reference`` over the
    same selection."""
    s, h, d = 384, 16, 128
    q, k, v, do = draw(11, s, h, 1, d)
    kw = dict(block=32, blocks=6, init_blocks=1, local_blocks=2,
              pool=(16, 8), dense_len=128)
    since = time.time()
    o, vjp = jax.vjp(lambda q, k, v: sa.block_sparse_attention(
        q, k, v, **kw), q, k, v)
    (event,) = _events(since)
    assert event["route"] == "masked_flash"
    assert (event["select_by"], event["block"], event["blocks"],
            event["init_blocks"], event["local_blocks"], event["pool"],
            event["heads"], event["kv_heads"], event["saved"]) == (
        "block", 32, 6, 1, 2, [16, 8], 16, 1, "block_mask_int8")
    assert event["selected_pairs"] == sa.block_selected_pairs(s, 32, 6) \
        < event["causal_pairs"] == s * (s + 1) // 2
    picked = sa.block_selection(q, k, sm_scale=d ** -0.5, **{
        n: kw[n] for n in SMALL})
    mask = sa.expand_blocks(picked[:, 0], 32)
    assert int(mask.sum()) == event["selected_pairs"]
    want, ref_vjp = jax.vjp(lambda q, k, v: sa.masked_attention_reference(
        q, k, v, mask, d ** -0.5), q, k, v)
    rel = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())  # noqa
    assert rel(o, want) < 1e-5
    for got, ref in zip(vjp(do), ref_vjp(do)):
        assert rel(got, ref) < 1e-5
    # and it is NOT causal attention: the selection left keys out
    dense = sa.masked_attention_reference(
        q, k, v, jnp.tril(jnp.ones((1, s, s), jnp.int8)), d ** -0.5)
    assert rel(o, dense) > 1e-2


def test_a_short_row_attends_over_everything():
    s, h, d = 256, 4, 128
    q, k, v, _ = draw(13, s, h, 2, d)
    since = time.time()
    o = sa.block_sparse_attention(q, k, v, dense_len=256)
    (event,) = _events(since)
    assert event["route"] == "causal_flash" and event["saved"] == "none"
    assert event["selected_pairs"] == event["causal_pairs"]
    want = sa.masked_attention_reference(
        q, k, v, jnp.tril(jnp.ones((1, s, s), jnp.int8)), d ** -0.5)
    assert float(jnp.abs(o - want).max()) < 1e-5


@pytest.mark.parametrize("seq, group, blocks", [
    (32768, 16, (512, 512)),      # the cell's: 1024 x 1024 is refused
    (16384, 8, (1024, 1024)),     # a row of 16 384 at a group of 8 keeps them
    (49152, 16, (256, 256)),
    (384, 16, (384, 384)),        # a short row: the row itself
    (131072, 16, None),           # no block of 128 or more holds it
])
def test_the_blocks_are_chosen_by_the_length(seq, group, blocks):
    """ROADMAP B25(h): the masked pair's blocks are the largest squares
    whose backward's accumulators (dk and dv of the whole row beside a block
    pair's tiles) ``_bwd_vmem`` admits, heads of 128 in bf16."""
    assert sa._fitting_blocks(seq, group, 128, 2) == blocks
    if blocks:
        assert sa._bwd_vmem(seq, *blocks, group, 128, 2) <= sa.VMEM_BYTES


def test_the_cells_row_by_the_numbers():
    """At 32 768 tokens and a group of 16: 512 x 512 asks 60.3 of 67.1 MB,
    1024 x 1024 is refused; 81.3 % of the queries select and 33.8 % of the
    causal pairs are needed (61 % at 16 384)."""
    assert sa._bwd_vmem(32768, 512, 512, 16, 128, 2) == 60_293_120
    assert sa._bwd_vmem(32768, 1024, 1024, 16, 128, 2) > sa.VMEM_BYTES
    pairs = sa.block_selected_pairs(32768, 64, 96)
    assert pairs == 181_616_640
    assert round(pairs / (32768 * 32769 // 2), 3) == 0.338
    assert round(sa.block_selected_pairs(16384, 64, 96)
                 / (16384 * 16385 // 2), 2) == 0.61
    # while a query sees no more blocks than it takes, every causal pair
    assert sa.block_selected_pairs(6144, 64, 96) == 6144 * 6145 // 2


@pytest.mark.parametrize("bad, words", [
    (dict(pool=(32, 8)), "windows of two strides"),
    (dict(block=24), "whole strides a block"),
    (dict(dense_len=64, blocks=2, block=48, pool=(32, 16)), "whole blocks"),
])
def test_a_selection_that_cannot_be_built_is_refused(bad, words):
    q, k, v, _ = draw(17, 128, 2, 1, 32)
    with pytest.raises(ValueError, match=words):
        sa.block_sparse_attention(q, k, v, **bad)


@pytest.mark.parametrize("size, stride", [(8, 4), (32, 16), (2, 1)])
def test_pooled_keys_are_the_windows_means(size, stride):
    s = 16 * stride
    k = jax.random.normal(jax.random.PRNGKey(size), (2, s, 2, 8), jnp.float32)
    got = np.asarray(sa.pooled_keys(k, size, stride))
    assert got.shape == (2, s // stride, 2, 8)
    for j in range(s // stride - 1):
        want = np.asarray(k[:, stride * j:stride * j + size]).mean(1)
        assert np.abs(got[:, j] - want).max() < 1e-6
    assert not got[:, -1].any()        # the window past the row's end


@pytest.mark.parametrize("per_block", [2, 4])
def test_a_blocks_score_is_the_largest_of_the_windows_that_overlap_it(
        per_block):
    """Windows per b - 1 .. per b + per - 1 (a max-pool of per + 1, stride
    per, padding 1): a peak in the window just BEFORE a block counts for it
    and for the block it ends, a block's first window for that block alone."""
    n = 5
    p = np.zeros((1, n * per_block), np.float32)
    p[0, 2 * per_block - 1] = 3.0        # the last window of block 1
    p[0, 3 * per_block] = 2.0            # the first window of block 3
    got = np.asarray(sa.block_scores(jnp.asarray(p), per_block))[0]
    want = np.zeros(n, np.float32)
    want[1] = want[2] = 3.0
    want[3] = 2.0
    assert (got == want).all(), got
