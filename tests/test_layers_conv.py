"""ISSUE 55: ``causal_conv1d_silu`` is ``silu(causal_conv1d(...))`` to the
bit, and its hand-written gradient is autodiff's (dx, dw, db) and the
definition's, one token at a time. Plain ``jax.numpy`` on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.layers import causal_conv1d, causal_conv1d_silu

# (batch, tokens, channels, taps): the cells' four taps and two; as many
# tokens as taps - 1 and one alone (shorter than the taps)
SHAPES = [(2, 12, 8, 4), (3, 9, 8, 2), (2, 3, 8, 4), (2, 1, 8, 4)]
SHAPE_IDS = ["k4", "k2", "t_is_k_minus_1", "t1"]


def _silu_of_conv(x, w, b=None):
    return jax.nn.silu(causal_conv1d(x, w, b))


def _draw(shape, bias, dtype=jnp.float32, seed=0):
    b, t, c, k = shape
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, t, c), np.float32), dtype)
    w = jnp.asarray(rng.uniform(-1, 1, (k, c)).astype(np.float32) * k ** -0.5)
    bv = (jnp.asarray(0.3 * rng.standard_normal(c, np.float32)) if bias
          else None)
    dy = jnp.asarray(rng.standard_normal((b, t, c), np.float32), dtype)
    return x, w, bv, dy


def _grads(f, x, w, b, dy):
    """(dx, dw, db) of sum(f * dy); db None without a bias."""
    def loss(x, w, b):
        return jnp.sum(f(x, w, b).astype(jnp.float32)
                       * dy.astype(jnp.float32))
    if b is None:
        return jax.jit(jax.grad(lambda x, w: loss(x, w, None), (0, 1)))(
            x, w) + (None,)
    return jax.jit(jax.grad(loss, (0, 1, 2)))(x, w, b)


def _by_token(x, w, b, dy):
    """y and (dx, dw, db) from the definition in float64, a token at a time:
    y[n, t] = silu(sum_k w[k] x[n, t - (K-1-k)] + b), zeros before token 0
    of EVERY batch row, nothing read across rows."""
    x, w, dy = (np.asarray(a, np.float64) for a in (x, w, dy))
    bias = np.zeros(x.shape[2]) if b is None else np.asarray(b, np.float64)
    taps = w.shape[0]
    y, dx, dw, db = (np.zeros_like(x), np.zeros_like(x), np.zeros_like(w),
                     np.zeros_like(bias))
    for n in range(x.shape[0]):
        for t in range(x.shape[1]):
            reach = [(k, t - (taps - 1 - k)) for k in range(taps)
                     if t - (taps - 1 - k) >= 0]
            pre = bias + sum(w[k] * x[n, u] for k, u in reach)
            s = 1.0 / (1.0 + np.exp(-pre))
            y[n, t] = pre * s
            dpre = dy[n, t] * s * (1.0 + pre * (1.0 - s))
            db += dpre
            for k, u in reach:
                dx[n, u] += w[k] * dpre
                dw[k] += x[n, u] * dpre
    return y, (dx, dw, None if b is None else db)


def _close(got, want, rel):
    want = np.asarray(want, np.float64)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0,
                               atol=rel * max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_the_forward_is_the_expression_to_the_bit(shape, bias, dtype):
    """Like with like, one compiled program against one compiled program
    (a compiler may contract a product and a sum that op-by-op dispatch
    rounds apart): the plain call, and the forward of a differentiated
    one."""
    x, w, b, dy = _draw(shape, bias, dtype)
    pairs = [
        (jax.jit(causal_conv1d_silu)(x, w, b),
         jax.jit(_silu_of_conv)(x, w, b)),
        (jax.jit(lambda *a: jax.vjp(causal_conv1d_silu, *a)[0])(x, w, b),
         jax.jit(lambda *a: jax.vjp(_silu_of_conv, *a)[0])(x, w, b))]
    for got, want in pairs:
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("shape", SHAPES, ids=SHAPE_IDS)
def test_the_hand_gradient_is_autodiffs_and_the_definitions(shape, bias):
    x, w, b, dy = _draw(shape, bias)
    got = _grads(causal_conv1d_silu, x, w, b, dy)
    auto = _grads(_silu_of_conv, x, w, b, dy)
    y, defined = _by_token(x, w, b, dy)
    _close(jax.jit(causal_conv1d_silu)(x, w, b), y, 1e-5)
    for g, a, d in zip(got, auto, defined):
        if d is None:
            assert g is None and a is None
            continue
        assert g.dtype == a.dtype and g.shape == a.shape
        _close(g, a, 1e-5)
        _close(g, d, 1e-5)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("shape", SHAPES[:2], ids=SHAPE_IDS[:2])
def test_the_hand_gradient_in_bf16_is_within_dpres_rounding(shape, bias):
    """dpre is rounded to bf16 once (autodiff's is too, after its own bf16
    arithmetic): dx within a few bf16 steps of the definition's largest
    entry, the float32 sums dw and db nearer than autodiff's."""
    x, w, b, dy = _draw(shape, bias, jnp.bfloat16)
    got = _grads(causal_conv1d_silu, x, w, b, dy)
    auto = _grads(_silu_of_conv, x, w, b, dy)
    _, defined = _by_token(x, w, b, dy)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    for g, a, d in zip(got, auto, defined):
        if d is None:
            continue
        # the forward's pre-activation is bf16 in both: 2^-8 a rounding
        _close(g, d, 2e-2)
        _close(g, a, 2e-2)
        err = lambda v: np.abs(np.asarray(v, np.float64) - d).max()  # noqa
        assert err(g) <= 1.5 * err(a) + 1e-6


def test_no_row_reads_its_neighbours():
    """Zeros before token 0 of EVERY batch row, and the last K - 1 rows of
    dx see only their own later tokens: a batch row's gradient is what it
    is alone, whatever stands in the rows beside it."""
    shape = (3, 7, 8, 4)
    x, w, b, dy = _draw(shape, True)
    dx, dw, db = _grads(causal_conv1d_silu, x, w, b, dy)
    x, dy = np.asarray(x), np.asarray(dy)
    for n in range(shape[0]):
        alone = _grads(causal_conv1d_silu, x[n:n + 1], w, b, dy[n:n + 1])
        np.testing.assert_allclose(dx[n], alone[0][0], rtol=0, atol=1e-6)
    # the last token's dx is its own tap alone
    pre = np.asarray(jax.jit(causal_conv1d)(x, w, b))
    s = 1 / (1 + np.exp(-pre))
    dpre = dy * s * (1 + pre * (1 - s))
    np.testing.assert_allclose(dx[:, -1], dpre[:, -1] * np.asarray(w)[-1],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("how", ["checkpoint", "scan", "scan_of_checkpoint"])
def test_under_remat_and_inside_a_scan_as_the_stack_walker_uses_it(how, bias):
    """A run of like layers is ``lax.scan`` over ``jax.checkpoint`` of the
    layer's body (``models/stack.py``): the custom gradient has to hold
    there, with the layers' weights stacked on the scan's axis."""
    layers = 3
    x, w, b, dy = _draw((2, 10, 8, 4), bias, seed=1)
    ws = jnp.stack([w * (1 + 0.1 * i) for i in range(layers)])
    bs = None if b is None else jnp.stack([b + 0.1 * i for i in range(layers)])

    def stack(f):
        def layer(h, p):
            return h + f(h, p[0], p[1] if bias else None)

        def run(x, ws, bs):
            ps = (ws, bs) if bias else (ws,)
            if how == "checkpoint":
                h = x
                for i in range(layers):
                    h = jax.checkpoint(layer)(h, tuple(p[i] for p in ps))
                return h
            body = jax.checkpoint(layer) if how != "scan" else layer
            return jax.lax.scan(lambda h, p: (body(h, p), None), x, ps)[0]
        return run

    def grads(f):
        loss = lambda x, ws, bs: jnp.sum(stack(f)(x, ws, bs) * dy)  # noqa
        if not bias:
            return jax.jit(jax.grad(lambda x, ws: loss(x, ws, None),
                                    (0, 1)))(x, ws)
        return jax.jit(jax.grad(loss, (0, 1, 2)))(x, ws, bs)

    for g, a in zip(grads(causal_conv1d_silu), grads(_silu_of_conv)):
        assert g.shape == a.shape
        _close(g, a, 1e-5)


def test_the_backward_keeps_x_and_the_parameters_alone():
    """Residuals: x, weight, bias; no pre-activation, no sigmoid."""
    x, w, b, _ = _draw((2, 12, 8, 4), True)
    _, vjp = jax.vjp(causal_conv1d_silu, x, w, b)
    kept = [a for a in jax.tree.leaves(vjp) if hasattr(a, "shape")]
    assert sorted(a.shape for a in kept) == sorted(
        [x.shape, w.shape, b.shape])
