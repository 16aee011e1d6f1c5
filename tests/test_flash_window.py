"""ISSUE 43: ``flash_attention(..., window=w)``, a causal call in which
query i sees the keys i - w < j <= i, against ``mha_reference`` with the
same mask: the output and the three gradients. The streamed one-part
kernels (interpret mode) walk the blocks of the band alone; the cases put
the window under, at and over a block, off every multiple of it, over the
whole sequence, with unlike block sizes for queries and keys, and through
the relayout route.

Tolerance: float32 arguments, so both sides are float32 and differ in the
order of their sums: 2e-5 of the output's largest entry or of 1, the scale
of the arguments, where the output is smaller (a window of one has a zero
dq and dk, which the kernels give to 3e-6); the worst measured is 4e-6."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention, mha_reference

fa = importlib.import_module("ray_tpu.ops.flash_attention")


def _qkv(seed, s, h, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (1, s, h, d)) for k in ks)


def _both(fn, q, k, v, do):
    out, grads = jax.jit(jax.value_and_grad(
        lambda q, k, v: (fn(q, k, v) * do).sum(), argnums=(0, 1, 2),
        has_aux=False))(q, k, v)
    return (fn(q, k, v),) + grads


# (S, heads, head_dim, window, block_q, block_k)
CASES = [
    pytest.param(512, 2, 64, 100, 128, 128, id="under-a-block"),
    pytest.param(512, 2, 64, 128, 128, 128, id="a-block"),
    pytest.param(512, 2, 64, 300, 128, 128, id="over-two-blocks-no-multiple"),
    pytest.param(512, 2, 64, 64, 256, 128, id="query-blocks-twice-the-keys"),
    pytest.param(512, 2, 64, 200, 128, 256, id="key-blocks-twice-the-queries"),
    pytest.param(256, 2, 64, 1, 128, 128, id="window-of-one"),
    pytest.param(256, 2, 64, 1000, 1024, 1024, id="window-over-S-one-block"),
    pytest.param(384, 1, 32, 150, 128, 128, id="relayout-three-blocks"),
    pytest.param(200, 2, 64, 50, 128, 128, id="reference-route"),
]


@pytest.mark.parametrize("s,h,d,window,bq,bk", CASES)
def test_a_window_is_the_masked_reference(s, h, d, window, bq, bk):
    q, k, v, do = _qkv(s + window, s, h, d)
    got = _both(lambda q, k, v: flash_attention(
        q, k, v, window=window, block_q=bq, block_k=bk), q, k, v, do)
    want = _both(lambda q, k, v: mha_reference(q, k, v, window=window),
                 q, k, v, do)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() \
            < 2e-5 * max(np.abs(np.asarray(w)).max(), 1.0), name
    # the mask is the window's: a key `window` back changes nothing
    if window < s:
        k2 = k.at[:, 0].add(1.0)
        o2 = flash_attention(q, k2, v, window=window, block_q=bq, block_k=bk)
        assert np.array_equal(np.asarray(o2[:, window:]),
                              np.asarray(got[0][:, window:]))


def test_a_window_over_everything_is_the_streamed_causal_call():
    """Where the band is the whole triangle the window's grid, index maps
    and masks give the causal call's numbers bit for bit, output and
    gradients: the streamed kernels without a window are the parent's
    (``scripts/train_step_hlo.py --compare`` holds the three cells' steps
    to the parent's programs; PERF.md, PR 43)."""
    q, k, v, do = _qkv(3, 512, 2, 64)
    plain = _both(lambda q, k, v: flash_attention(
        q, k, v, block_q=128, block_k=128), q, k, v, do)
    banded = _both(lambda q, k, v: flash_attention(
        q, k, v, block_q=128, block_k=128, window=512), q, k, v, do)
    for a, b in zip(plain, banded):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_call_without_a_window_leaves_the_parents_event():
    """No window: the event has the keys the parent's had and no other."""
    from ray_tpu.perf.recorder import get_recorder

    q, k, v, _ = _qkv(1, 256, 2, 64)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, block_q=128, block_k=128), q, k, v)
        event = [e for e in rec.snapshot()
                 if e["kind"] == "rtpu.ops.flash.path"][-1]
    finally:
        rec.enabled = was
    assert event["data"] == {"layout": "merged", "heads_per_block": 2,
                             "hd": 64, "S": 256, "bands": 1}


def test_the_band_is_what_is_visited():
    """At the benchmark cell's shape (S 8192, blocks of 1024, window 512)
    a query block visits 2 key blocks, 15 in all where a causal call
    visits 36; the event says so."""
    from ray_tpu.perf.recorder import get_recorder

    assert fa._band_blocks(8, 1024, 1024, 511, 0, 8) == 2
    assert fa._band_blocks(8, 1024, 1024, 0, 511, 8) == 2
    assert fa._band_blocks(4, 128, 128, 299, 0, 4) == 4
    assert fa._band_blocks(2, 256, 128, 63, 0, 4) == 3
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        qkv = jax.ShapeDtypeStruct((1, 8192, 4, 64), jnp.bfloat16)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, window=512),
                       qkv, qkv, qkv)
        event = [e for e in rec.snapshot()
                 if e["kind"] == "rtpu.ops.flash.path"][-1]
    finally:
        rec.enabled = was
    assert event["data"] == {
        "layout": "merged", "heads_per_block": 2, "hd": 64, "S": 8192,
        "bands": 1, "window": 512, "block_q": 1024, "block_k": 1024,
        "blocks_visited": 15, "blocks_causal": 36}


@pytest.mark.parametrize("kw", [dict(causal=False), dict(window=0)])
def test_a_window_takes_a_causal_call(kw):
    q, k, v, _ = _qkv(0, 128, 2, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, **dict({"window": 16}, **kw))
