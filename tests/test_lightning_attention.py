"""ISSUE 69: Lightning Attention's recurrence (``ops/lightning_attention.py``:
every head its own q and k, groups == heads, a decay that is a constant of
the head) against the token-by-token definition, both routes, forward and
``jax.vjp``, the kernels interpreted.

Tolerances. Everything is float32 here, so a route differs from the
definition by the order of its sums alone. Read on these seeds: the kernel
route's o by 4.1e-7 of its largest entry and its gradients by 4.7e-7 of
theirs; the limit is 5e-6 of the largest entry, ten times that. Against it
(``test_a_wrong_recurrence_would_fail``): the decay of the NEXT head (o
moves by 7.1e-2 of its largest entry), a state rounded to bf16 after every
token (2.0e-3, the least: 400 times the limit) and a scale left out (0.91)
each pass a hundred times the limit.

The decays are the published ones (``slope_h = 2^(-8 (h + 1) / 32)``, the
fastest head 0.84 a token: e^-215 over a chunk of 256, which a factorised
exp(c_t) exp(-c_s) would overflow on), and one test runs that head over a
full chunk of 256.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the module, not the function of its name that the package exports
la = importlib.import_module("ray_tpu.ops.lightning_attention")

LIMIT = 5e-6          # of the largest entry (module docstring)
SCALE = 128 ** -0.5


def slopes(heads, of=32, first=0):
    """log lambda of heads ``first`` .. of a layer of ``of`` heads at layer
    index 0: -2^(-8 (h + 1) / of) (1 + 1e-5)."""
    h = np.arange(first, first + heads, dtype=np.float64)
    return jnp.asarray(-(2.0 ** (-8.0 * (h + 1) / of)) * (1 + 1e-5),
                       jnp.float32)


def token_by_token(q, k, v, log_decay, scale, state_dtype=jnp.float32):
    """The definition: S_t = lambda S_{t-1} + k_t v_t^T, o_t = scale S_t^T
    q_t, float32 sums on the VPU."""
    b, t, h, n = q.shape
    lam = jnp.exp(log_decay.astype(jnp.float32))[None, :, None, None]

    def token(s, tok):
        q_t, k_t, v_t = tok                                    # [B, H, .]
        s = (lam * s + k_t[..., :, None] * v_t[..., None, :]).astype(
            state_dtype).astype(jnp.float32)
        return s, jnp.sum(s * q_t[..., None], axis=-2) * scale

    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, n, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 1)


def draw(seed, b, t, h, n=128, p=128):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(kk, (b, t, h, n), jnp.float32)
            for kk in keys[:2])
    v, do = (jax.random.normal(kk, (b, t, h, p), jnp.float32)
             for kk in keys[2:])
    return q, k, v, do


def close(got, want, limit=LIMIT):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    return np.abs(got - want).max() / np.abs(want).max() < limit


def _events(since):
    from ray_tpu.perf import recorder

    return [dict(e["data"]) for e in recorder.get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.lightning.path" and e["ts"] >= since]


@pytest.mark.parametrize("shape, chunk, route", [
    ((2, 384, 2, 128, 128), 128, "kernel"),     # three chunks, two rows
    ((1, 256, 2, 128, 128), 256, "kernel"),     # the fastest head, a full 256
    ((1, 200, 2, 128, 128), 128, "reference"),  # T no multiple of the chunk
    ((1, 80, 2, 32, 64), 32, "reference"),      # heads no kernel takes
])
def test_both_routes_equal_the_definition(shape, chunk, route):
    b, t, h, n, p = shape
    q, k, v, do = draw(3, b, t, h, n, p)
    a = slopes(h)
    since = time.time()
    fn = lambda q, k, v: la.lightning_attention(               # noqa: E731
        q, k, v, a, scale=SCALE, chunk=chunk)
    o, vjp = jax.vjp(fn, q, k, v)
    want, ref_vjp = jax.vjp(
        lambda q, k, v: token_by_token(q, k, v, a, SCALE), q, k, v)
    (event,) = _events(since)
    assert event == {"route": route, "chunk": chunk, "heads": h, "groups": h,
                     "head_dim": p, "state": n, "decay": "constant",
                     "chunks": -(-t // chunk)}
    assert close(o, want)
    for got, ref in zip(vjp(do), ref_vjp(do)):
        assert close(got, ref)


def test_the_decay_may_be_a_layers_traced_constant():
    """Inside a scanned run of layers the decays are an entry of the scan's
    xs: traced, and no gradient reaches them."""
    q, k, v, do = draw(5, 1, 256, 2)
    decays = jnp.stack([slopes(2), slopes(2) * 0.5])

    def layer(carry, a):
        o = la.lightning_attention(q, k, v, a, scale=SCALE, chunk=128)
        return carry + jnp.sum(o * do), None

    total, grad_a = jax.value_and_grad(
        lambda d: jax.lax.scan(layer, jnp.float32(0.0), d)[0])(decays)
    want = sum(jnp.sum(token_by_token(q, k, v, a, SCALE) * do)
               for a in decays)
    assert abs(float(total) - float(want)) < LIMIT * abs(float(want)) * 10
    assert not np.asarray(grad_a).any()


@pytest.mark.parametrize("fault", ["next_heads_decay", "bf16_state",
                                   "no_scale"])
def test_a_wrong_recurrence_would_fail(fault):
    q, k, v, _ = draw(3, 1, 256, 2)
    o = la.lightning_attention(q, k, v, slopes(2), scale=SCALE, chunk=128)
    assert close(o, token_by_token(q, k, v, slopes(2), SCALE))
    wrong = {
        "next_heads_decay": lambda: token_by_token(
            q, k, v, slopes(2, first=1), SCALE),
        "bf16_state": lambda: token_by_token(
            q, k, v, slopes(2), SCALE, state_dtype=jnp.bfloat16),
        "no_scale": lambda: token_by_token(q, k, v, slopes(2), 1.0),
    }[fault]()
    assert not close(o, wrong, 100 * LIMIT)


@pytest.mark.parametrize("chunk", [128, 256, 512])
@pytest.mark.parametrize("layer", [0, 1, 31])
def test_no_power_of_the_decay_overflows(chunk, layer):
    """ISSUE 69: every exponent stays a difference with t >= s. The four
    tables of the 32 published heads are finite, between 0 and the scale (1
    for what the state keeps), zero above the diagonal and ``scale`` on it,
    whatever the chunk: the fastest head (0.84 a token at layer 0) decays by
    e^-215 over 256 tokens and by e^-430 over 512, which underflows to 0 and
    never overflows, where a factorised exp(c_t) exp(-c_s) would."""
    a = slopes(32) * (1 - layer / 31 + 1e-5) / (1 + 1e-5)
    local, into, out, keep = (np.asarray(x) for x in la._tables(
        a, chunk, SCALE, 1))
    for table, top in ((local, SCALE), (into, SCALE), (out, 1.0),
                       (keep, 1.0)):
        assert np.isfinite(table).all()
        assert table.min() >= 0.0 and table.max() <= top * (1 + 1e-6)
    assert not np.triu(local[0], 1).any()
    assert np.allclose(np.diagonal(local, axis1=1, axis2=2), SCALE)
    lam = np.exp(np.asarray(a, np.float64))
    assert np.allclose(keep[:, 0, 0], lam ** chunk, rtol=1e-4, atol=1e-37)
    assert np.allclose(out[:, -1, 0], 1.0) and np.allclose(
        into[:, 0, 0], SCALE * lam, rtol=1e-5)
