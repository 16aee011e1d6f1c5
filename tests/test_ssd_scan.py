"""ISSUE 36: ``ops/ssd_scan.py`` against the recurrence it stands for,
token by token in float32:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;  y_t = h_t C_t + D x_t

Both routes (the Pallas kernels in interpret mode, the plain chunked
``jnp``), y and the gradients of all six arguments.

Tolerances. With float32 arguments every product, cumulative sum, decay
and state of either route is float32, and what differs from the recurrence
is the order of the sums: the worst element over all cases measured here is
8e-6 of the array's largest entry (dA of a single chunk), so 5e-5 holds
with six times of room. A state rounded to bf16 between chunks reads 1e-4
to 5e-4 and a bf16 cumulative sum 3e-4 to 2e-2, so either misses it
(``test_a_bf16_state_or_cumulative_sum_would_fail`` shows both).
With bf16 arguments the products' operands are bf16 (the MXU's path) and
decays, sums and state stay float32: 2e-2 of the largest entry.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ssd = importlib.import_module("ray_tpu.ops.ssd_scan")

NAMES = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, A, B, C, D):
    """The definition: one token at a time, float32, no chunk."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    f = lambda v: v.astype(jnp.float32)                      # noqa: E731
    x, dt, A, B, C, D = map(f, (x, dt, A, B, C, D))
    B, C = (jnp.repeat(v, h // g, axis=2) for v in (B, C))   # [b, t, h, n]

    def step(state, tok):
        xt, dtt, bt, ct = tok
        state = jnp.exp(dtt * A)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + D[:, None] * xt

    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32),
                            tuple(jnp.moveaxis(v, 1, 0)
                                  for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def arguments(seed, t, heads, groups=1, state=128, p=64, batch=2,
              dtype=jnp.float32):
    """Decays exp(dt A) from 0.999 a token down to 0.002, as a trained
    model's are: a scan that forgot nothing or everything would miss."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.exp(jax.random.uniform(k[1], (batch, t, heads),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    return {
        "x": jax.random.normal(k[0], (batch, t, heads, p)).astype(dtype),
        "dt": dt,
        "A": -jnp.arange(1, heads + 1, dtype=jnp.float32) * 64 / heads,
        "B": (jax.random.normal(k[2], (batch, t, groups, state))
              * 0.5).astype(dtype),
        "C": (jax.random.normal(k[3], (batch, t, groups, state))
              * 0.5).astype(dtype),
        "D": jax.random.normal(k[4], (heads,)),
    }, jax.random.normal(k[5], (batch, t, heads, p))


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


# (T, chunk, heads, groups, the route the call must take, head size)
CASES = [
    pytest.param(256, 128, 4, 1, "kernel", 64, id="kernel-two-chunks"),
    pytest.param(128, 128, 2, 1, "kernel", 64, id="kernel-single-chunk"),
    pytest.param(256, 128, 16, 1, "kernel", 64, id="kernel-two-head-blocks"),
    pytest.param(96, 32, 4, 1, "reference", 64, id="plain-three-chunks"),
    pytest.param(100, 32, 4, 2, "reference", 64, id="plain-T-no-multiple"),
    pytest.param(24, 1, 2, 1, "reference", 64, id="plain-chunk-of-one"),
    pytest.param(64, 256, 2, 2, "reference", 64, id="plain-single-chunk"),
    # ISSUE 40, the body that works a tile's two heads at once: both head
    # sizes, both chunks, H > 16 (two head blocks of 16) and three chunks
    pytest.param(384, 128, 32, 1, "kernel", 64,
                 id="kernel-heads-64-two-blocks-three-chunks"),
    pytest.param(384, 128, 32, 1, "kernel", 128,
                 id="kernel-heads-128-two-blocks-three-chunks"),
    pytest.param(768, 256, 4, 1, "kernel", 64,
                 id="kernel-heads-64-chunk-256-three-chunks"),
    pytest.param(768, 256, 4, 1, "kernel", 128,
                 id="kernel-heads-128-chunk-256-three-chunks"),
]


@pytest.mark.parametrize("t,chunk,heads,groups,route,p", CASES)
def test_ssd_scan_is_the_recurrence(t, chunk, heads, groups, route, p):
    """y and the gradients of x, dt, A, B, C, D of the chunked form equal
    the token-by-token recurrence's (float32: 5e-5 of the largest entry;
    the module docstring says why)."""
    args, dy = arguments(t + heads, t, heads, groups, p=p,
                         batch=1 if heads >= 16 else 2)
    before = ssd.PATH_COUNTS[route]
    got = value_and_grads(
        lambda *a: ssd.ssd_scan(*a, chunk=chunk), args, dy)
    assert ssd.PATH_COUNTS[route] > before
    want = value_and_grads(recurrence, args, dy)
    for name, d in worst(got, want).items():
        assert d < 5e-5, (name, d)


def test_the_chunk_is_not_part_of_the_mathematics():
    """Chunks of 128 and 256 through the kernels and of 64 through the
    plain route give one y and one set of gradients (float32: 5e-5)."""
    args, dy = arguments(7, 256, 2)
    outs = [value_and_grads(lambda *a, c=c: ssd.ssd_scan(*a, chunk=c),
                            args, dy) for c in (128, 256, 64)]
    for other in outs[1:]:
        for name, d in worst(other, outs[0]).items():
            assert d < 5e-5, (name, d)


@pytest.mark.parametrize("seed,heads,p,batch", [
    pytest.param(11, 4, 64, 2, id="four-heads"),
    # ISSUE 40: the LARGEST head block the kernels take (a body that works
    # a tile's heads at once), at both head sizes, two chunks of 128;
    # seeded as ``test_ssd_scan_is_the_recurrence`` seeds (T + heads)
    pytest.param(272, 16, 64, 1, id="sixteen-heads-of-64"),
    pytest.param(264, 8, 128, 1, id="eight-heads-of-128")])
def test_bf16_arguments_keep_decays_sums_and_state_in_float32(seed, heads, p,
                                                              batch):
    """bf16 x, B, C (a model's dtypes) against the float32 recurrence on
    the same rounded values: the operands of the products are bf16, so
    2e-2 of the largest entry; y comes back in x's dtype. (The limit is
    the rounding's, not the kernels': sixteen heads at batch 1 on seed 11
    read 2.5e-2 on d(A) through the kernels and 3.0e-2 through the plain
    route.)"""
    args, dy = arguments(seed, 256, heads, p=p, batch=batch,
                         dtype=jnp.bfloat16)
    before = ssd.PATH_COUNTS["kernel"]
    got = value_and_grads(lambda *a: ssd.ssd_scan(*a, chunk=128), args, dy)
    assert ssd.PATH_COUNTS["kernel"] == before + 1
    assert got["y"].dtype == jnp.bfloat16
    want = value_and_grads(recurrence, args, dy)
    for name, d in worst(got, want).items():
        assert d < 2e-2, (name, d)


@pytest.mark.parametrize("what", ["state", "cumulative_sum"])
def test_a_bf16_state_or_cumulative_sum_would_fail(what, monkeypatch):
    """The float32 tolerance is tight enough to see either rounded to
    bf16: the plain route with that one quantity rounded misses 5e-5 four
    times over (the state where decays are slow and it matters most, the
    sum where they are fast and it is large)."""
    args, dy = arguments(3, 96, 4)
    if what == "state":
        args["A"] = -jnp.ones((4,)) / 2
    want = recurrence(*(args[n] for n in NAMES))
    round16 = lambda v: v.astype(jnp.bfloat16).astype(v.dtype)  # noqa: E731
    if what == "state":
        real = jax.lax.scan

        def scan(f, init, xs):
            return real(lambda s, c: f(round16(s), c), init, xs)

        monkeypatch.setattr(jax.lax, "scan", scan)
    else:
        real = jnp.cumsum
        monkeypatch.setattr(jnp, "cumsum",
                            lambda v, axis=None: round16(real(v, axis=axis)))
    got = ssd.ssd_scan(*(args[n] for n in NAMES), chunk=32)
    assert worst({"y": got}, {"y": want})["y"] > 2e-4


def test_the_route_leaves_its_event_and_count():
    """``rtpu.ops.ssd.path`` at trace time, as ``rtpu.ops.flash.path``:
    the route, the chunk and the sizes the call showed."""
    from ray_tpu.perf.recorder import get_recorder

    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    before = dict(ssd.PATH_COUNTS)
    try:
        for t, heads, groups in ((512, 4, 1), (100, 4, 2)):
            args, _ = arguments(0, t, heads, groups, batch=1)
            jax.eval_shape(ssd.ssd_scan, *(args[n] for n in NAMES))
        events = [e for e in rec.snapshot()
                  if e["kind"] == "rtpu.ops.ssd.path"][-2:]
    finally:
        rec.enabled = was
    assert ssd.PATH_COUNTS["kernel"] == before.get("kernel", 0) + 1
    assert ssd.PATH_COUNTS["reference"] == before.get("reference", 0) + 1
    assert events[0]["label"] == "kernel" and events[0]["data"] == {
        "route": "kernel", "chunk": 256, "heads": 4, "head_dim": 64,
        "state": 128, "groups": 1, "chunks": 2}
    assert events[1]["data"] == {
        "route": "reference", "chunk": 100, "heads": 4, "head_dim": 64,
        "state": 128, "groups": 2, "chunks": 1}


def test_the_cells_scan_traces_no_more_helpers_than_the_parents():
    """A guard on the trace's cost that needs no clock: the jitted helpers
    jax traces inside the gradient of ``ssd_scan`` at
    ``granite4h_train_s4096``'s shapes (``data.inner`` of the
    ``rtpu.jax.trace`` span, ``perf/jaxbuild.py``; from shapes, nothing
    runs). PR 38's bodies traced 1471 with no helper cached; the bodies in
    ``jax.lax`` primitives trace 89."""
    from ray_tpu.perf import get_recorder, install_jax_spans

    install_jax_spans()
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    b, t, h, p, n = 2, 4096, 64, 64, 128
    sd = jax.ShapeDtypeStruct
    bf = jnp.bfloat16

    def cell_scan_loss(x, dt, a, bm, cm, d):
        return ssd.ssd_scan(x, dt, a, bm, cm, d).astype(jnp.float32).sum()

    jax.clear_caches()      # a new process's view: every helper is traced
    try:
        jax.jit(jax.grad(cell_scan_loss, argnums=tuple(range(6)))).trace(
            sd((b, t, h, p), bf), sd((b, t, h), jnp.float32),
            sd((h,), jnp.float32), sd((b, t, 1, n), bf),
            sd((b, t, 1, n), bf), sd((h,), jnp.float32))
        span = [e for e in rec.spans("rtpu.jax.trace")
                if e["label"] == "cell_scan_loss"][-1]
    finally:
        rec.enabled = was
    assert 0 < span["data"]["inner"] <= 200, span
