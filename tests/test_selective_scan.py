"""ISSUE 43: ``ops/selective_scan.py`` against the recurrence it stands for,
token by token in float32, written out here and not taken from the module:

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]

The Pallas kernels in interpret mode and the plain route, y and the
gradients of all six arguments, decays from 0.002 to 0.999 a token.

Tolerances. With float32 arguments everything on either side is float32
and what differs is the order of the sums (the kernels add the states of a
channel eight and eight, the gradient of A a chunk at a time): the worst
element measured over these cases is 3e-6 of the array's largest entry, so
5e-5 holds with room. A state rounded to bf16 between chunks reads 1e-3
(``test_a_bf16_state_would_fail``). With bf16 x, B and C the kernels widen
them where they are read and y leaves in bf16: 2e-2.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sel = importlib.import_module("ray_tpu.ops.selective_scan")

NAMES = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, A, B, C, D):
    """The definition: one token at a time, float32, no chunk."""
    f = lambda v: v.astype(jnp.float32)                      # noqa: E731
    x, dt, A, B, C, D = map(f, (x, dt, A, B, C, D))

    def step(state, tok):
        xt, dtt, bt, ct = tok                # [b, c] [b, c] [b, n] [b, n]
        state = jnp.exp(dtt[..., None] * A) * state \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return state, jnp.einsum("bcn,bn->bc", state, ct) + D * xt

    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(
            step, jnp.zeros((x.shape[0],) + A.shape, jnp.float32),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def arguments(seed, t, channels, state=16, batch=2, dtype=jnp.float32):
    """dt log-uniform in [0.001, 0.1] and A from -1 to -62 by state, so
    that the decays exp(dt A) run from 0.999 a token down to 0.002, as a
    trained model's do: a scan that forgot nothing or everything misses."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.exp(jax.random.uniform(k[1], (batch, t, channels),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    A = -jnp.broadcast_to(jnp.asarray(np.geomspace(1.0, 62.0, state),
                                      jnp.float32), (channels, state))
    return {
        "x": jax.random.normal(k[0], (batch, t, channels)).astype(dtype),
        "dt": dt,
        "A": A * (1 + 0.1 * jax.random.uniform(k[2], (channels, 1))) / 1.05,
        "B": (jax.random.normal(k[2], (batch, t, state)) * 0.5).astype(dtype),
        "C": (jax.random.normal(k[3], (batch, t, state)) * 0.5).astype(dtype),
        "D": jax.random.normal(k[4], (channels,)),
    }, jax.random.normal(k[5], (batch, t, channels))


def value_and_grads(fn, args, dy):
    def scalar(*a):
        y = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * dy), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(6)), has_aux=True))(
        *(args[n] for n in NAMES))
    return dict(zip(("y",) + NAMES, (y,) + grads))


def worst(got, want):
    """The largest distance of any output, as a share of that output's
    largest entry in the recurrence."""
    out = {}
    for n in want:
        w = np.asarray(want[n], np.float64)
        out[n] = float(np.abs(np.asarray(got[n], np.float64) - w).max()
                       / max(np.abs(w).max(), 1e-30))
    return out


# (T, chunk, channels, state, the route the call must take)
CASES = [
    pytest.param(64, 32, 128, 16, "kernel", id="kernel-two-chunks"),
    pytest.param(96, 32, 256, 16, "kernel", id="kernel-three-chunks"),
    pytest.param(32, 32, 128, 16, "kernel", id="kernel-single-chunk"),
    pytest.param(64, 32, 1024, 8, "kernel",
                 id="kernel-two-channel-blocks-state-8"),
    pytest.param(75, 32, 128, 16, "kernel", id="kernel-T-no-multiple"),
    pytest.param(20, 128, 128, 16, "kernel", id="kernel-chunk-over-T"),
    pytest.param(40, 16, 96, 16, "reference", id="plain-channels-96"),
    pytest.param(40, 16, 128, 4, "reference", id="plain-state-4"),
]


@pytest.mark.parametrize("t,chunk,channels,state,route", CASES)
def test_selective_scan_is_the_recurrence(t, chunk, channels, state, route):
    """y and the gradients of x, dt, A, B, C, D equal the token-by-token
    recurrence's (float32: 5e-5 of the largest entry)."""
    args, dy = arguments(t + channels, t, channels, state,
                         batch=1 if channels >= 1024 else 2)
    before = sel.PATH_COUNTS[route]
    got = value_and_grads(
        lambda *a: sel.selective_scan(*a, chunk=chunk), args, dy)
    assert sel.PATH_COUNTS[route] > before
    want = value_and_grads(recurrence, args, dy)
    decays = np.exp(np.asarray(args["dt"])[..., None] * np.asarray(args["A"]))
    assert decays.min() < 0.004 and decays.max() > 0.998, (
        decays.min(), decays.max())
    for name, d in worst(got, want).items():
        assert d < 5e-5, (name, d)


def test_the_chunk_is_not_part_of_the_mathematics():
    args, dy = arguments(3, 96, 128)
    a, b = (value_and_grads(lambda *v: sel.selective_scan(*v, chunk=q),
                            args, dy) for q in (16, 96))
    for name, d in worst(a, b).items():
        assert d < 5e-5, (name, d)


def test_bf16_arguments_keep_decays_and_state_in_float32():
    args, dy = arguments(5, 64, 128, dtype=jnp.bfloat16)
    got = value_and_grads(lambda *a: sel.selective_scan(*a, chunk=32),
                          args, dy)
    assert got["y"].dtype == jnp.bfloat16 and got["dt"].dtype == jnp.float32
    want = value_and_grads(recurrence, args, dy)
    for name, d in worst(got, want).items():
        assert d < 2e-2, (name, d)


def test_a_bf16_state_would_fail(monkeypatch):
    """The tolerance tells a float32 state from one rounded to bf16
    between chunks."""
    real = sel._scan_fwd

    def rounded(*a):
        y, states = real(*a)
        return y, states.astype(jnp.bfloat16).astype(jnp.float32)

    args, dy = arguments(7, 128, 128)

    def run():
        return value_and_grads(
            lambda *a: sel.selective_scan(*a, chunk=16), args, dy)

    want = value_and_grads(recurrence, args, dy)
    good = worst(run(), want)
    monkeypatch.setattr(sel, "_scan_fwd", rounded)
    bad = worst(run(), want)
    assert max(good.values()) < 5e-5 < max(bad.values())


def test_the_route_leaves_its_event_and_count():
    """``rtpu.ops.selscan.path`` at trace time, as ``rtpu.ops.ssd.path``:
    the route, the chunk and the tile widths the call showed."""
    from ray_tpu.perf.recorder import get_recorder

    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        args, _ = arguments(1, 32, 128)
        jax.eval_shape(lambda *a: sel.selective_scan(*a, chunk=16),
                       *(args[n] for n in NAMES))
        event = [e for e in rec.snapshot()
                 if e["kind"] == "rtpu.ops.selscan.path"][-1]
    finally:
        rec.enabled = was
    assert event["label"] == "kernel" and event["data"] == {
        "route": "kernel", "chunk": 16, "channels": 128, "state": 16,
        "block_channels": 128, "chunks": 2, "steps_per_iteration": 8}
