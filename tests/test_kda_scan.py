"""ISSUE 49: ``ops/kda_scan.py`` against the recurrence it stands for, token
by token in float32 (per head, S [d_k, d_v] from zero):

    S <- diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
    o_t = S^T (scale q_t)

o and the gradient of every input (q, k, v, g, beta).

Tolerances, as a share of the compared array's largest entry. With float32
arguments every product, cumulative sum, decay, solve and state of the
chunked form is float32, and what differs from the recurrence is the order
of the sums and the triangular solve: the worst element over all cases
measured here is 1.2e-6 (dg with neighbouring keys alike; 9e-7 elsewhere),
so 1e-5 holds with eight times of room. A state rounded to bf16 after every token reads 4e-3, one
scalar decay a head (the channels' mean) 0.3 and a missing ``- S^T k``
0.14, so each misses it (``test_a_wrong_scan_would_fail`` shows all three).
With bf16 arguments the products' operands are bf16 (the MXU's path) and
gates, sums, solve and state stay float32: 3e-2 of the largest entry.

ISSUE 50: every test runs on both routes, ``chunked_jnp`` (the plain form,
forced here by replacing the module's ``_route``) and ``kernel`` (the Pallas
pair, interpreted on the CPU; heads of 128 and a chunk of 64 take it by
themselves); the kernel route's five gradients are also held to ``jax.vjp``
of the plain route.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

kda = importlib.import_module("ray_tpu.ops.kda_scan")

NAMES = ("q", "k", "v", "g", "beta")
F32_TOL = 1e-5
D = 128
ROUTES = ("chunked_jnp", "kernel")


@pytest.fixture(params=ROUTES)
def route(request, monkeypatch):
    """The route the module's calls take in this test: the plain form for
    every shape, or what the shape gives (the kernel pair at d 128 and a
    chunk of 64)."""
    if request.param == "chunked_jnp":
        monkeypatch.setattr(kda, "_route", lambda *shape: "chunked_jnp")
    return request.param


def took(route, before, chunk=64):
    """The one route counted since ``before`` (a copy of PATH_COUNTS) is
    the one the test asked for, or the plain one for a chunk the kernels do
    not take."""
    want = route if chunk == 64 else "chunked_jnp"
    gained = {k: n - before[k] for k, n in kda.PATH_COUNTS.items()
              if n != before[k]}
    assert set(gained) == {want}, (gained, want)


def recurrence(q, k, v, g, beta, *, scale, heads, state_dtype=jnp.float32,
               head_decay=False, delta=True):
    """The definition: one token at a time, float32, no chunk. The three
    switches make the WRONG scans the tolerances must catch."""
    b, t, _ = q.shape
    per_head = lambda x: x.astype(jnp.float32).reshape(     # noqa: E731
        b, t, heads, -1)
    q, k, v, g = map(per_head, (q, k, v, g))
    if head_decay:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = beta.astype(jnp.float32)

    def step(s, tok):
        qt, kt, vt, gt, bt = tok
        s = jnp.exp(gt)[..., None] * s
        held = jnp.einsum("bhkv,bhk->bhv", s, kt) if delta else 0.0
        s = s + (bt[..., None] * kt)[..., None] * (vt - held)[..., None, :]
        s = s.astype(state_dtype).astype(jnp.float32)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt * scale)

    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(
            step, jnp.zeros((b, heads, q.shape[-1], v.shape[-1])),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, -1)


def arguments(seed, t, heads=2, batch=2, dtype=jnp.float32, gate=None):
    """Keys and queries of unit length a head, decays exp(g) from 0.999 a
    token down to 0.2 (``A`` in [1, 16] x a step log-uniform in [0.001,
    0.1], what the configuration's ``assumed`` initialisation gives), or
    ``gate`` a token and channel where it is given."""
    r = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda x: (x / jnp.linalg.norm(                   # noqa: E731
        x.reshape(batch, t, heads, D), axis=-1, keepdims=True
    ).repeat(D, -1).reshape(x.shape)).astype(dtype)
    shape = (batch, t, heads * D)
    a = jax.random.uniform(r[3], (heads,), minval=1.0, maxval=16.0)
    step = jnp.exp(jax.random.uniform(r[4], (batch, t, heads, D),
                                      minval=np.log(1e-3), maxval=np.log(0.1)))
    g = (-a[:, None] * step).reshape(shape)
    if gate is not None:
        g = jnp.full(shape, gate, jnp.float32)
    return {
        "q": unit(jax.random.normal(r[0], shape)),
        "k": unit(jax.random.normal(r[1], shape)),
        "v": jax.random.normal(r[2], shape).astype(dtype),
        "g": g,
        "beta": jax.nn.sigmoid(jax.random.normal(r[5], (batch, t, heads))),
    }, jax.random.normal(r[6], shape)


def value_and_grads(fn, args, do):
    def scalar(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(5)), has_aux=True))(
        *(args[n] for n in NAMES))
    return dict(zip(("o",) + NAMES, (o,) + grads))


def worst(got, want):
    """{name: largest difference as a share of want's largest entry}."""
    return {n: float(jnp.max(jnp.abs(got[n].astype(jnp.float32) - want[n]))
                     / (jnp.max(jnp.abs(want[n])) + 1e-30)) for n in want}


def both(args, do, heads=2, **kw):
    scale = D ** -0.5
    want = value_and_grads(
        lambda *a: recurrence(*a, scale=scale, heads=heads), args, do)
    got = value_and_grads(
        lambda *a: kda.kda_scan(*a, scale=scale, **kw), args, do)
    return got, want


@pytest.mark.parametrize("t,chunk", [(150, 64), (64, 64), (40, 64), (96, 16)],
                         ids=["ragged", "one_chunk", "short", "chunk16"])
def test_kda_scan_is_the_recurrence(t, chunk, route):
    """o and all five gradients, T a multiple of the chunk or not (150 =
    2 chunks and 22 tokens: padded; 40 tokens: the kernels pad them to one
    chunk of 64), decays as strong as the assumed initialisation makes
    them. A chunk of 16 is the plain form's on either route."""
    args, do = arguments(0, t)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do, chunk=chunk)
    took(route, before, chunk)
    for name, err in worst(got, want).items():
        assert err < F32_TOL, (name, err)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in got.values())


def test_kda_scan_under_the_strongest_decay(route):
    """g = -20 a token and channel: the cumulative gate of a chunk reaches
    -1280, exp(+1280) is inf in float32, so a factorised exp(G) exp(-G)
    would be NaN. Every exponent here is <= 0: the state is forgotten
    between tokens and o_t = scale beta_t (q_t.k_t) v_t, with every
    gradient finite."""
    args, do = arguments(1, 150, gate=-20.0)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do)
    took(route, before)
    for name, v in got.items():
        assert bool(jnp.all(jnp.isfinite(v))), name
    # dg is of the order exp(-20) itself (2e-10 at its largest): held to
    # zero, not to a share of it
    assert float(jnp.max(jnp.abs(got["g"] - want["g"]))) < 1e-8
    for name, err in worst(got, want).items():
        assert name == "g" or err < F32_TOL, (name, err)
    q, k, v = (args[n].reshape(2, 150, 2, D) for n in "qkv")
    alone = (D ** -0.5 * args["beta"] * (q * k).sum(-1))[..., None] * v
    np.testing.assert_allclose(got["o"], alone.reshape(2, 150, -1),
                               atol=1e-6)


def test_keys_alike_are_solved_in_blocks(monkeypatch, route):
    """Neighbouring keys alike (k_i . k_j near 0.8), beta 0.9 and a weak
    decay: the chunk's A has entries near 0.7 everywhere under its
    diagonal, and the Neumann product over the WHOLE chunk, whose terms
    grow as C(63, n) 0.7^n before they cancel, is wrong by orders of
    magnitude in float32. In blocks of 8, merged, it reads 1.2e-6."""
    args, do = arguments(5, 150)
    r = jax.random.split(jax.random.PRNGKey(105), 2)
    k = jax.random.normal(r[0], (2, 1, 2, D)) \
        + 0.5 * jax.random.normal(r[1], (2, 150, 2, D))
    args["k"] = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).reshape(
        2, 150, 2 * D)
    args["beta"] = jnp.full((2, 150, 2), 0.9)
    args["g"] = args["g"] * 0.05
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do)
    took(route, before)
    for name, err in worst(got, want).items():
        assert err < F32_TOL, (name, err)
    monkeypatch.setattr(kda, "_SUB", 64)        # one block: the whole chunk
    got, _ = both(args, do)
    assert not worst({"o": got["o"]}, {"o": want["o"]})["o"] < 1.0


def test_chunk_16_equals_chunk_64_up_to_rounding(route):
    """On the kernel route: the pair at 64 against the plain form at 16."""
    args, do = arguments(2, 192)
    scale = D ** -0.5
    a, b = (value_and_grads(
        lambda *x, c=c: kda.kda_scan(*x, scale=scale, chunk=c), args, do)
        for c in (16, 64))
    for name, err in worst(a, b).items():
        assert err < F32_TOL, (name, err)


def test_a_wrong_scan_would_fail():
    """What the tolerance is for: a state kept in bf16, one scalar decay a
    head, and a rule without its correction each read well over it."""
    args, do = arguments(0, 150)
    scale = D ** -0.5
    right = recurrence(*(args[n] for n in NAMES), scale=scale, heads=2)
    for wrong, at_least in ((dict(state_dtype=jnp.bfloat16), 1e-3),
                            (dict(head_decay=True), 5e-2),
                            (dict(delta=False), 5e-2)):
        o = recurrence(*(args[n] for n in NAMES), scale=scale, heads=2,
                       **wrong)
        err = worst({"o": o}, {"o": right})["o"]
        assert err > at_least > 10 * F32_TOL, (wrong, err)


def test_bf16_arguments(route):
    """The model's call: bf16 q, k, v; g and beta float32."""
    args, do = arguments(3, 150, dtype=jnp.bfloat16)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do)
    took(route, before)
    assert got["o"].dtype == jnp.bfloat16
    for name, err in worst(got, want).items():
        assert err < 3e-2, (name, err)


def test_path_event_and_padding(route):
    from ray_tpu.perf import recorder

    before = kda.PATH_COUNTS[route]
    args, _ = arguments(4, 150)
    jax.eval_shape(lambda *a: kda.kda_scan(*a, scale=1.0),
                   *(args[n] for n in NAMES))
    assert kda.PATH_COUNTS[route] == before + 1
    events = [e for e in recorder.get_recorder().snapshot()
              if e["kind"] == "rtpu.ops.kda.path"]
    facts = {"route": route, "chunk": 64, "tokens": 150,
             "padded_tokens": 42, "heads": 2, "d_k": D, "d_v": D, "chunks": 3}
    if route == "kernel":
        facts["heads_per_block"] = 2
    assert events and events[-1]["data"] == facts


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_kernels_gradients_are_the_plain_routes(dtype, tol):
    """ISSUE 50: o and the five gradients of the kernel pair against
    ``jax.vjp`` of the plain route, 3 chunks of 2 x 2 heads. With bf16
    arguments o differs by a rounding of bf16 at most (the cumulative
    gates are summed in another order); the gradients differ by the
    rounding of the cotangents the plain form's autodiff casts to bf16 and
    the backward kernel keeps in float32."""
    args, do = arguments(6, 192, dtype=dtype)
    scale = D ** -0.5
    before = kda.PATH_COUNTS.copy()
    got = value_and_grads(
        lambda *a: kda.kda_scan(*a, scale=scale), args, do)
    took("kernel", before)
    want = value_and_grads(
        lambda *a: kda._chunked(*a, 2, 64, scale), args, do)
    want = {n: v.astype(jnp.float32) for n, v in want.items()}
    for name, err in worst(got, want).items():
        assert err < (8e-3 if name == "o" and tol > 1e-3 else tol), (name, err)
    assert got["g"].dtype == got["beta"].dtype == jnp.float32
    assert got["q"].dtype == got["k"].dtype == got["v"].dtype == dtype


def test_other_shapes_fall_back_to_the_plain_route():
    """Heads of 64 (two to a 128-lane tile) and a chunk that is not 64 are
    the plain form's, and ``PATH_COUNTS`` says so; three heads of 128 take
    the kernels (an odd number of heads is solved one by one)."""
    r = jax.random.split(jax.random.PRNGKey(7), 5)
    b, t, h = 1, 64, 2
    for d, chunk, want in ((64, 64, "chunked_jnp"), (128, 32, "chunked_jnp"),
                           (128, 64, "kernel")):
        q, k, v = (jax.random.normal(r[i], (b, t, h * d)) for i in range(3))
        g = -jnp.abs(jax.random.normal(r[3], (b, t, h * d))) * 0.1
        beta = jax.nn.sigmoid(jax.random.normal(r[4], (b, t, h)))
        before = kda.PATH_COUNTS.copy()
        o = jax.eval_shape(lambda *a, c=chunk: kda.kda_scan(
            *a, scale=1.0, chunk=c), q, k, v, g, beta)
        assert o.shape == (b, t, h * d)
        took(want, before)
    assert [kda._heads_per_block(n) for n in (1, 2, 3, 6, 32)] == [
        1, 2, 3, 2, 4]
