"""ISSUE 44: a PAIRED call of ``flash_attention``: q and k [B, S, H, 64], v
[B, S, H/2, 128]; score heads 2i and 2i+1 share value i, and the call
returns [B, S, H, 128], head 2i softmax(q_2i k_2i^T) V_i and head 2i+1
softmax(q_2i+1 k_2i+1^T) V_i (differential attention before its
subtraction). The streamed one-part kernels (interpret mode here) hold the
pair and the whole value in one program and form each map once. Held to:

* the four-head expansion through the routes a call with one head size
  takes, (q1 k1 v1) (q1 k1 v2) (q2 k2 v1) (q2 k2 v2): what
  ``models/sambay.py`` handed the kernels before, and what
  ``flash_attention`` makes itself of a paired call that does not tile;
* ``mha_reference`` on the same expanded heads,

the output and the three gradients, the value's summed over both maps.

Tolerance, as in ``tests/test_flash_window.py``: float32 arguments, both
sides float32 and unlike only in the order of their sums: 2e-5 of the
largest entry or of 1. The worst read on these seeds is 5e-6; the output
against the expansion's is equal in every element."""
import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention, mha_reference

fa = importlib.import_module("ray_tpu.ops.flash_attention")


def _pair(seed, s, h, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, s, h, d)),
            jax.random.normal(ks[1], (1, s, h, d)),
            jax.random.normal(ks[2], (1, s, h // 2, 2 * d)),
            jax.random.normal(ks[3], (1, s, h, 2 * d)))


def _expanded(q, k, v):
    """Four heads of d a pair: (q1 k1 v1) (q1 k1 v2) (q2 k2 v1) (q2 k2 v2)."""
    b, s, h, d = q.shape
    halves = jnp.tile(v.reshape(b, s, h // 2, 1, 2 * d),
                      (1, 1, 1, 2, 1)).reshape(b, s, 2 * h, d)
    return jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), halves


def _both(fn, q, k, v, do):
    out, grads = jax.jit(jax.value_and_grad(
        lambda q, k, v: (fn(q, k, v) * do).sum(), argnums=(0, 1, 2),
        has_aux=False))(q, k, v)
    return (fn(q, k, v),) + grads


def _last_event(trace):
    from ray_tpu.perf.recorder import get_recorder

    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        trace()
        return [e for e in rec.snapshot()
                if e["kind"] == "rtpu.ops.flash.path"][-1]["data"]
    finally:
        rec.enabled = was


# (S, score heads, score head size, causal, window, block_q, block_k, route)
CASES = [
    pytest.param(256, 4, 64, True, None, 128, 128, "paired", id="causal"),
    pytest.param(512, 2, 64, True, None, 128, 256, "paired",
                 id="causal-key-blocks-twice-the-queries"),
    pytest.param(256, 2, 64, False, None, 128, 128, "paired",
                 id="not-causal"),
    pytest.param(128, 2, 64, True, None, 1024, 1024, "paired",
                 id="one-block-is-a-grid-of-one-step"),
    pytest.param(512, 2, 64, True, 100, 128, 128, "paired",
                 id="window-under-a-block"),
    pytest.param(512, 2, 64, True, 128, 128, 128, "paired",
                 id="window-a-block"),
    pytest.param(512, 2, 64, True, 300, 128, 128, "paired",
                 id="window-over-two-blocks-no-multiple"),
    pytest.param(512, 2, 64, True, 64, 256, 128, "paired",
                 id="window-query-blocks-twice-the-keys"),
    pytest.param(256, 4, 64, True, 1, 128, 128, "paired",
                 id="window-of-one"),
    pytest.param(256, 2, 64, True, 1000, 1024, 1024, "paired",
                 id="window-over-S-one-block"),
    # shapes the paired kernels do not tile: expanded inside the call
    pytest.param(200, 2, 64, True, None, 128, 128, "reference",
                 id="falls-back-S-no-multiple-of-128"),
    pytest.param(256, 4, 16, True, 48, 128, 128, "relayout",
                 id="falls-back-score-heads-of-16"),
    pytest.param(256, 2, 128, True, None, 128, 128, "merged",
                 id="falls-back-score-heads-of-128"),
]


@pytest.mark.parametrize("s,h,d,causal,window,bq,bk,route", CASES)
def test_a_pair_of_score_heads_against_one_value(s, h, d, causal, window, bq,
                                                 bk, route):
    q, k, v, do = _pair(s + (window or 0), s, h, d)
    kw = dict(causal=causal, window=window, block_q=bq, block_k=bk)
    before = collections.Counter(fa.PATH_COUNTS)
    got = _both(lambda q, k, v: flash_attention(q, k, v, **kw), q, k, v, do)
    took = collections.Counter(fa.PATH_COUNTS) - before
    assert set(took) == {route}, took
    assert got[0].shape == (1, s, h, 2 * d)
    four = _both(lambda q, k, v: flash_attention(
        *_expanded(q, k, v), **kw).reshape(1, s, h, 2 * d), q, k, v, do)
    want = _both(lambda q, k, v: mha_reference(
        *_expanded(q, k, v), causal=causal, window=window).reshape(
            1, s, h, 2 * d), q, k, v, do)
    assert np.array_equal(np.asarray(got[0]), np.asarray(four[0]))
    for name, g, f, w in zip(("o", "dq", "dk", "dv"), got, four, want):
        scale = 2e-5 * max(np.abs(np.asarray(w)).max(), 1.0)
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < scale, name
        assert np.abs(np.asarray(g) - np.asarray(f)).max() < scale, name
    if route != "paired":
        # the fallback IS the expansion: its numbers in every element
        for g, f in zip(got, four):
            assert np.array_equal(np.asarray(g), np.asarray(f))


def test_both_maps_reach_the_one_value():
    """dv of value i is the sum over its two maps: with the second map's
    cotangent zeroed it is the first map's alone, and the two add up."""
    q, k, v, do = _pair(7, 256, 2, 64)
    kw = dict(block_q=128, block_k=128)

    def dv(do):
        return jax.grad(lambda v: (flash_attention(q, k, v, **kw)
                                   * do).sum())(v)

    first, second = do.at[:, :, 1].set(0.0), do.at[:, :, 0].set(0.0)
    assert np.abs(np.asarray(dv(first))).max() > 0.1
    assert np.abs(np.asarray(dv(second))).max() > 0.1
    assert np.abs(np.asarray(dv(first) + dv(second) - dv(do))).max() < 2e-5


@pytest.mark.parametrize("window,more", [
    (None, {}),
    (512, {"window": 512, "block_q": 1024, "block_k": 1024,
           "blocks_visited": 15, "blocks_causal": 36})],
    ids=["full", "window-512"])
def test_the_event_says_paired_at_the_benchmark_cells_shape(window, more):
    """``phi4flash_train_s8192``'s calls: 40 score heads of 64 against 20
    values of 128 at S 8192; under the window the blocks the windowed
    event has."""
    qk = jax.ShapeDtypeStruct((1, 8192, 40, 64), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 20, 128), jnp.bfloat16)
    before = fa.PATH_COUNTS["paired"]
    data = _last_event(lambda: jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, window=window), qk, qk, v))
    assert fa.PATH_COUNTS["paired"] == before + 1
    assert data == dict({"layout": "paired", "heads_per_block": 2, "hd": 64,
                         "S": 8192, "bands": 1, "hd_v": 128}, **more)


def _kernel_equations(jaxpr, out):
    """Equations of every Pallas kernel's body by kernel name, bodies under
    a body (``pl.when``) counted in."""
    def size(j):
        n = 0
        for e in j.eqns:
            n += 1
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += size(sub)
        return n

    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            info = e.params.get("name_and_src_info") or e.params.get("name")
            out[str(getattr(info, "name", info))] = size(e.params["jaxpr"])
            continue
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _kernel_equations(j, out)
    return out


def _grad_jaxpr(q, k, v, **kw):
    return jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, **kw).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr


# equations of the three streamed kernels' bodies as the PARENT of ISSUE 44
# traced them (two heads of 64 a program, blocks of 128): counted there
PARENTS_KERNELS = {
    None: {"flash_fwd": 141, "flash_bwd_dq": 156, "flash_bwd_dkv": 123},
    100: {"flash_fwd": 163, "flash_bwd_dq": 178, "flash_bwd_dkv": 158},
}


@pytest.mark.parametrize("window", [None, 100], ids=["causal", "window"])
def test_a_call_with_one_head_size_is_the_parents(window):
    """The paired branches are taken at trace time by what the call
    shows: a call with one head size leaves the parent's event (``merged``,
    no ``hd_v``) and kernels of the parent's bodies, equation for equation
    (the whole jaxpr of such calls was read equal to the parent's text when
    this was written, and ``scripts/train_step_hlo.py --compare`` holds the
    other cells' compiled steps to the parent's; PERF.md, PR 44), while a
    paired call of the same score heads traces other bodies under the same
    three names."""
    q = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)
    v2 = jax.ShapeDtypeStruct((1, 256, 1, 128), jnp.bfloat16)
    kw = dict(block_q=128, block_k=128, window=window)
    data = _last_event(lambda: jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, **kw), q, q, q))
    assert data["layout"] == "merged" and "hd_v" not in data
    assert set(data) - {"window", "block_q", "block_k", "blocks_visited",
                        "blocks_causal"} \
        == {"layout", "heads_per_block", "hd", "S", "bands"}
    assert _kernel_equations(_grad_jaxpr(q, q, q, **kw), {}) \
        == PARENTS_KERNELS[window]
    paired = _kernel_equations(_grad_jaxpr(q, q, v2, **kw), {})
    assert set(paired) == set(PARENTS_KERNELS[window])
    assert all(paired[n] != PARENTS_KERNELS[window][n] for n in paired)
