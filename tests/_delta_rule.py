"""What ``test_kda_scan.py`` (one decay a key channel: KDA) and
``test_gdn_scan.py`` (one decay a head: Gated DeltaNet) share: the module
under test, the recurrence both rules are held to, token by token in
float32, the fixture that picks the route and the reading of which route a
call took."""
import importlib

import jax
import jax.numpy as jnp
import pytest

kda = importlib.import_module("ray_tpu.ops.kda_scan")

NAMES = ("q", "k", "v", "g", "beta")
GATED = ("q", "k", "v", "step", "a_log", "dt_bias", "beta")
F32_TOL = 1e-5
D = 128
EPS = 1e-6      # ``layers.l2norm``'s
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


def unit_heads(x, heads, eps=EPS):
    """x [B, T, heads * d] float32 / sqrt(sum of a head's squares + eps)."""
    xh = x.astype(jnp.float32).reshape(*x.shape[:2], heads, -1)
    return (xh / jnp.sqrt(jnp.sum(xh * xh, -1, keepdims=True) + eps)
            ).reshape(x.shape)


def value_and_grads(fn, args, do):
    names = GATED if "step" in args else NAMES

    def scalar(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(names))), has_aux=True))(
        *(args[n] for n in names))
    return dict(zip(("o",) + names, (o,) + grads))
