"""ISSUE 64: ``ops/short_conv.py``'s ``in_proj_short_conv(b | c | x, w) =
c * conv3(b * x)``: both routes (the plain one, ``causal_conv1d`` between
two products; the Pallas pair with a halo of rows, interpreted here)
against the sums written out in float32, forward and every gradient (db,
dc, dx, dw: the ONE cotangent's thirds, through the concatenation), over
several row blocks, a row shorter than a block, and a halo that crosses a
block's edge.

Tolerances. In float32 the routes differ from the written-out sums by the
order of a few additions: a few float32 steps at values of a few units
(read: 1.9e-6 forward, 1.5e-5 on dw, a sum over 256 rows). In bfloat16 an
output is rounded once by the kernel and up to three times by the plain
route (z, the taps' sum, the product), each half a step of 2^-8: the limit
is 4 steps of the written-out sums' largest entry (0.0156), and dw, a
float32 sum of rounded products, a hundredth of its largest entry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import causal_conv1d
from ray_tpu.ops import short_conv as sc


def written_out(b, c, x, w):
    """c[t] * (w[0] z[t-2] + w[1] z[t-1] + w[2] z[t]), z = b x, zeros
    before the row, everything float32."""
    b, c, x, w = (v.astype(jnp.float32) for v in (b, c, x, w))
    z = b * x
    zero = jnp.zeros_like(z[:, :1])
    z1 = jnp.concatenate([zero, z[:, :-1]], 1)
    z2 = jnp.concatenate([zero, zero, z[:, :-2]], 1)
    return c * (w[0] * z2 + w[1] * z1 + w[2] * z)


def _inputs(shape, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, c, x = (jax.random.normal(k, shape, jnp.float32).astype(dtype)
               for k in ks[:3])
    w = jax.random.normal(ks[3], (3, shape[2]), jnp.float32)
    dy = jax.random.normal(ks[4], shape, jnp.float32)
    return b, c, x, w, dy


def _value_and_grads(f, b, c, x, w, dy):
    def loss(b, c, x, w):
        y = f(b, c, x, w)
        return jnp.sum(y.astype(jnp.float32) * dy), y
    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                       has_aux=True)(b, c, x, w)
    return (y,) + grads


# (B, T, D), rows a block: several row blocks whose edges the taps cross;
# a row shorter than a block; two channel blocks; one block of everything;
# three channel blocks of the forward under ONE of the backward; two rows
# of two blocks each (the second row starts from zeros)
SHAPES = [((2, 64, 256), 16), ((1, 48, 128), 512), ((2, 128, 256), 32),
          ((1, 32, 128), 32), ((1, 32, 384), 16), ((2, 32, 128), 16)]


def _of_chunks(route):
    """The op as a function of b, c and x: its gradients come back through
    the concatenation, the one cotangent's thirds."""
    return lambda b, c, x, w: sc._routed(
        jnp.concatenate([b, c, x], -1), w, route)


@pytest.fixture
def blocks(monkeypatch):
    """Sets the module's block sizes: ``rows`` a block of either kernel, 128
    channels a block of the forward."""
    def set_rows(rows):
        monkeypatch.setattr(sc, "BLOCK_T", rows)
        monkeypatch.setattr(sc, "BWD_BLOCK_T", rows)
        monkeypatch.setattr(sc, "BLOCK_D", 128)
    return set_rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("route", ["kernel", "plain"])
@pytest.mark.parametrize("shape,block_t", SHAPES,
                         ids=[f"{s[1]}x{s[2]}-rows{b}" for s, b in SHAPES])
def test_both_routes_equal_the_written_out_sums(route, dtype, shape, block_t,
                                                blocks):
    blocks(block_t)
    b, c, x, w, dy = _inputs(shape, dtype)
    f = _of_chunks(route)
    got = jax.jit(lambda *a: _value_and_grads(f, *a))(b, c, x, w, dy)
    want = _value_and_grads(written_out, b, c, x, w, dy)
    for name, g, r in zip(("y", "db", "dc", "dx", "dw"), got, want):
        assert g.shape == r.shape, name
        assert g.dtype == (jnp.float32 if name == "dw" else dtype), name
        top = float(jnp.abs(r).max())
        step = 2.0 ** -8 if dtype == jnp.bfloat16 else 2.0 ** -23
        limit = (0.01 if dtype == jnp.bfloat16 else 4e-6) * top \
            if name == "dw" else 4 * step * top
        assert float(jnp.abs(g.astype(jnp.float32) - r).max()) < limit, name


def test_the_halo_is_what_crosses_a_blocks_edge(blocks):
    """The rows before a block reach it (the forward's z, the backward's
    dc) and the rows after it do (the backward's cotangent): one impulse
    just before an edge and one just after, blocks of 16 rows."""
    blocks(16)
    kernel = _of_chunks("kernel")
    shape = (1, 64, 128)
    one = jnp.ones(shape, jnp.float32)
    w = jnp.stack([jnp.full((128,), 100.0), jnp.full((128,), 10.0),
                   jnp.ones((128,))])
    x = jnp.zeros(shape).at[0, 15].set(1.0)      # the last row of block 0
    f = lambda x: kernel(one, one, x, w)                    # noqa: E731
    y = f(x)
    assert np.asarray(y[0, :, 0]).tolist() == \
        [0.0] * 15 + [1.0, 10.0, 100.0] + [0.0] * 46
    # and the other way: y[16] and y[17] (block 1) pull on x[15] (block 0)
    dy = jnp.zeros(shape).at[0, 16].set(1.0).at[0, 17].set(1.0)
    dx = jax.grad(lambda x: jnp.sum(f(x) * dy))(x)
    assert np.asarray(dx[0, :, 0]).tolist() == \
        [0.0] * 14 + [100.0, 110.0, 11.0, 1.0] + [0.0] * 46
    # a row starts from zeros: nothing of row 0's end reaches row 1
    two = jnp.zeros((2, 32, 128)).at[0, 31].set(1.0)
    ones2 = jnp.ones((2, 32, 128))
    out = kernel(ones2, ones2, two, w)
    assert not np.asarray(out[1]).any()


def test_the_route_is_the_kernel_where_it_takes_the_shape_and_is_recorded():
    from ray_tpu.perf import get_recorder

    assert sc.kernel_takes((2, 8192, 2048), jnp.bfloat16, 3)
    assert sc.kernel_takes((1, 8, 128), jnp.float32, 3)
    assert not sc.kernel_takes((1, 8, 128), jnp.bfloat16, 3)   # half a halo
    assert not sc.kernel_takes((1, 64, 96), jnp.float32, 3)    # lanes
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        before = dict(sc.PATH_COUNTS)
        for shape in ((1, 64, 128), (1, 60, 96)):
            b, c, x, w, _ = _inputs(shape, jnp.float32)
            jax.eval_shape(sc.in_proj_short_conv,
                           jnp.concatenate([b, c, x], -1), w)
        events = [e for e in rec.snapshot()
                  if e["kind"] == "rtpu.ops.short_conv"][-2:]
    finally:
        rec.enabled = was
    assert [e["label"] for e in events] == ["kernel", "plain"]
    assert events[0]["data"] == {"route": "kernel", "tokens": 64,
                                 "channels": 128, "taps": 3}
    assert sc.PATH_COUNTS["kernel"] == before.get("kernel", 0) + 1
    assert sc.PATH_COUNTS["plain"] == before.get("plain", 0) + 1
    b, c, x, w, _ = _inputs((1, 60, 96), jnp.float32)
    bcx = jnp.concatenate([b, c, x], -1)
    with pytest.raises(ValueError, match="does not take"):
        sc._routed(bcx, w, "kernel")
    with pytest.raises(ValueError, match="route is"):
        sc._routed(bcx, w, "fast")
    with pytest.raises(ValueError, match="three chunks"):
        sc.in_proj_short_conv(jnp.concatenate([b, c], -1), w)


def test_the_plain_route_is_causal_conv1d_between_two_products():
    b, c, x, w, _ = _inputs((2, 40, 96), jnp.bfloat16, seed=3)
    want = c * causal_conv1d(b * x, w)
    assert jnp.array_equal(
        sc.in_proj_short_conv(jnp.concatenate([b, c, x], -1), w), want)
