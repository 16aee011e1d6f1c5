"""ISSUE 52: the delta rule with ONE decay a head and several value heads to
a key head (Gated DeltaNet), ``ops/kda_scan.py``'s other rule
(``test_kda_scan.py`` holds KDA's, and its docstring the tolerances).
``gated_delta_scan`` is held to the same recurrence (the decay spread over
a head's channels, q and k repeated to the value heads) and to ``kda_scan``
handed that spread gate; ``gdn_gated_scan``, a layer's entry, to the
recurrence on both routes, o and seven gradients, by the same limits
(ISSUE 53: on the kernel route the pair's body for one decay a head).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _delta_rule import (D, F32_TOL, kda, recurrence, route,  # noqa: F401
                         took, unit_heads, value_and_grads)

GDN = ("q", "k", "v", "g", "beta")
GDN_GATED = ("q", "k", "v", "a", "a_log", "dt_bias", "beta")


def gdn_arguments(seed, t, key_heads=2, value_heads=4, batch=2, gate=None,
                  raw=False):
    """What a Gated DeltaNet layer hands its scan: q, k [B, T, Hk * 128] of
    unit length a head (``raw``: of any length, 0.01 to 10), v [B, T, Hv *
    128], one decay a VALUE head and token from 0.999 down to 0.2 (A in
    [1, 16] x a step log-uniform in [0.001, 0.1]) or ``gate``, beta; and,
    with ``raw``, the layer's own a, A_log and dt_bias in place of g."""
    r = jax.random.split(jax.random.PRNGKey(seed), 10)
    kshape, vshape = (batch, t, key_heads * D), (batch, t, value_heads * D)

    def keys(key, length):
        x = jax.random.normal(key, (batch, t, key_heads, D))
        x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        if raw:
            x = x * jnp.exp(jax.random.uniform(
                length, (batch, t, key_heads, 1), minval=np.log(1e-2),
                maxval=np.log(10.0)))
        return x.reshape(kshape)

    out = {"q": keys(r[0], r[6]), "k": keys(r[1], r[7]),
           "v": jax.random.normal(r[2], vshape),
           "beta": jax.nn.sigmoid(jax.random.normal(
               r[5], (batch, t, value_heads)))}
    a_log = jnp.log(jax.random.uniform(r[3], (value_heads,), minval=1.0,
                                       maxval=16.0))
    if raw:
        dt = jnp.exp(jax.random.uniform(r[4], (value_heads,),
                                        minval=np.log(1e-3),
                                        maxval=np.log(0.1)))
        out.update(a=0.5 * jax.random.normal(r[8], (batch, t, value_heads)),
                   a_log=a_log, dt_bias=dt + jnp.log(-jnp.expm1(-dt)))
    else:
        step = jnp.exp(jax.random.uniform(
            r[4], (batch, t, value_heads), minval=np.log(1e-3),
            maxval=np.log(0.1)))
        out["g"] = -jnp.exp(a_log) * step if gate is None \
            else jnp.full((batch, t, value_heads), gate, jnp.float32)
    return out, jax.random.normal(r[9], vshape)


def to_value_heads(x, key_heads, value_heads):
    """[B, T, Hk * 128] -> [B, T, Hv * 128]: value head j reads key head
    j // (Hv / Hk)."""
    b, t, _ = x.shape
    return jnp.repeat(x.reshape(b, t, key_heads, D),
                      value_heads // key_heads, 2).reshape(b, t, -1)


def gdn_recurrence(q, k, v, g, beta, *, scale):
    """The definition, token by token: the file's ``recurrence`` with the
    head's one decay on all of its key channels."""
    hv = beta.shape[-1]
    hk = k.shape[-1] // D
    return recurrence(to_value_heads(q, hk, hv), to_value_heads(k, hk, hv), v,
                      jnp.repeat(g, D, -1), beta, scale=scale, heads=hv)


def gdn_gated_recurrence(q, k, v, a, a_log, dt_bias, beta, *, scale):
    hk = k.shape[-1] // D
    g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    return gdn_recurrence(unit_heads(q, hk), unit_heads(k, hk), v, g, beta,
                          scale=scale)


def o_and_grads(f, args, do):
    """{o and the gradient of every argument of ``f``} under the cotangent
    ``do``; the arguments the five of a scan or a layer's seven."""
    names = GDN_GATED if "a" in args else GDN

    def scalar(*a):
        o = f(*a)
        return jnp.sum(o.astype(jnp.float32) * do), o

    (_, o), g = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(len(names))), has_aux=True))(
        *(args[n] for n in names))
    return dict(zip(("o",) + names, (o,) + g))


def gdn_both(args, do, **kw):
    ref, fn = (gdn_gated_recurrence, kda.gdn_gated_scan) if "a" in args \
        else (gdn_recurrence, kda.gated_delta_scan)
    scale = D ** -0.5
    return (o_and_grads(lambda *a: fn(*a, scale=scale, **kw), args, do),
            o_and_grads(lambda *a: ref(*a, scale=scale), args, do))


def gdn_worst(got, want, args):
    """``worst`` with the sums of the layer's entry held to what they add
    up: dA_log = sum dg g and ddt_bias = sum da, over every token."""
    of = {n: jnp.max(jnp.abs(want[n])) for n in want}
    if "a" in args:
        x = args["a"] + args["dt_bias"]
        da = jnp.abs(want["a"])
        of["a_log"] = jnp.max((da * jax.nn.softplus(x) / jax.nn.sigmoid(x)
                               ).sum((0, 1)))
        of["dt_bias"] = jnp.max(da.sum((0, 1)))
    return {n: float(jnp.max(jnp.abs(got[n] - want[n])) / (of[n] + 1e-30))
            for n in want}


@pytest.mark.parametrize("t,chunk,heads", [
    (150, 64, (2, 2)), (150, 64, (2, 4)), (150, 64, (1, 4)),
    (64, 64, (2, 4)), (40, 64, (2, 4)), (96, 16, (2, 4))],
    ids=["ragged-one_to_one", "ragged-two_to_one", "ragged-four_to_one",
         "one_chunk", "short", "chunk16"])
def test_gated_delta_scan_is_the_recurrence(t, chunk, heads):
    """o and all five gradients (``jax.vjp`` through the chunks' scan), T a
    whole number of chunks or not, one, two and four value heads to a key
    head: dq and dk are sums over a key head's value heads."""
    args, do = gdn_arguments(0, t, *heads)
    before = kda.PATH_COUNTS.copy()
    got, want = gdn_both(args, do, chunk=chunk)
    took("chunked_jnp", before)
    assert got["o"].shape == (2, t, heads[1] * D)
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)


def test_gated_delta_scan_is_kda_scan_under_a_channel_constant_gate(route):
    """``kda_scan`` (either route) handed the head's decay on all 128 key
    channels and q and k repeated to the value heads computes the same
    thing: a decay that is constant over a head's lanes is a case of the
    one it computes. o and the gradients of v and beta element by element,
    dq and dk summed over a key head's value heads, dg over the lanes."""
    args, do = gdn_arguments(3, 150)
    got, _ = gdn_both(args, do)
    wide = {"q": to_value_heads(args["q"], 2, 4),
            "k": to_value_heads(args["k"], 2, 4), "v": args["v"],
            "g": jnp.repeat(args["g"], D, -1), "beta": args["beta"]}
    before = kda.PATH_COUNTS.copy()
    theirs = value_and_grads(
        lambda *a: kda.kda_scan(*a, scale=D ** -0.5), wide, do)
    took(route, before)
    group = lambda x: x.reshape(2, 150, 2, 2, D).sum(3).reshape(  # noqa: E731
        2, 150, 2 * D)
    want = {"o": theirs["o"], "q": group(theirs["q"]),
            "k": group(theirs["k"]), "v": theirs["v"],
            "g": theirs["g"].reshape(2, 150, 4, D).sum(-1),
            "beta": theirs["beta"]}
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)


def test_gated_delta_scan_under_the_strongest_decay():
    """g = -20 a token: the state is forgotten between tokens, o_t = scale
    beta_t (q_t . k_t) v_t, and every gradient is finite (every exponent
    is a later cumulative sum less an earlier one)."""
    args, do = gdn_arguments(1, 150, gate=-20.0)
    got, want = gdn_both(args, do)
    for name, v in got.items():
        assert bool(jnp.all(jnp.isfinite(v))), name
    assert float(jnp.max(jnp.abs(got["g"] - want["g"]))) < 1e-6
    for name, err in gdn_worst(got, want, args).items():
        assert name == "g" or err < F32_TOL, (name, err)
    q, k = (to_value_heads(args[n], 2, 4).reshape(2, 150, 4, D) for n in "qk")
    alone = (D ** -0.5 * args["beta"] * (q * k).sum(-1))[..., None] \
        * args["v"].reshape(2, 150, 4, D)
    np.testing.assert_allclose(got["o"], alone.reshape(2, 150, -1),
                               atol=1e-6)


def test_gated_delta_scan_refuses_heads_that_do_not_group():
    args, _ = gdn_arguments(0, 64, key_heads=2, value_heads=3)
    with pytest.raises(ValueError, match="3 value heads over 2 key heads"):
        kda.gated_delta_scan(*(args[n] for n in GDN), scale=1.0)


def in_dtype(args, dtype):
    """A layer's arguments with what the model computes in its own dtype
    (q, k, v and ``a``) cast to it; A_log, dt_bias and beta stay float32."""
    return {n: x.astype(dtype) if n in ("q", "k", "v", "a") else x
            for n, x in args.items()}


def gdn_layer_worst(got, want, args):
    """``gdn_worst`` for arguments of any dtype."""
    f32 = lambda tree: {n: x.astype(jnp.float32)             # noqa: E731
                        for n, x in tree.items()}
    return gdn_worst(f32(got), f32(want), f32(args))


@pytest.mark.parametrize("t,heads", [
    (150, (2, 2)), (150, (2, 4)), (150, (1, 4)), (150, (1, 3)),
    (150, (3, 3)), (64, (1, 2)), (40, (1, 2))],
    ids=["ragged-one_to_one", "ragged-two_to_one", "ragged-four_to_one",
         "ragged-three_to_one", "ragged-odd_heads", "one_chunk", "short"])
def test_a_gated_deltanet_layers_scan_is_the_recurrence(route, t, heads):
    """``gdn_gated_scan`` from what a layer's convolution and b | a
    projection leave, on the kernel route (ISSUE 53: the pair's body for
    one decay a head; q and k read once a key head, the norms, the gate and
    its cumulative sums made in the kernels) and on the plain one
    (``l2norm``, the softplus, ``gated_delta_scan``): o and seven gradients
    against the recurrence. One, two, three and four value heads to a key
    head (a program works four value heads over four, two and one key
    heads, or three over one; three key heads of one value head each are
    solved one by one), T ragged, one chunk, shorter than a chunk (those
    two at the smallest block the kernels take: two value heads over one
    key head)."""
    args, do = gdn_arguments(2, t, *heads, raw=True)
    before = kda.PATH_COUNTS.copy()
    got, want = gdn_both(args, do)
    took(route, before)
    assert got["o"].shape == (2, t, heads[1] * D)
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)


def test_value_heads_no_block_holds_take_the_plain_route():
    """Eight value heads to a key head: a program works at most four, and
    dq and dk leave the backward kernel summed over a key head's value
    heads, so a block holds whole key heads or the call is the plain
    route's. It falls back, and the event says so."""
    assert [kda._gdn_heads_per_block(h, g) for h, g in (
        (32, 2), (4, 1), (4, 4), (6, 1), (6, 3), (3, 1), (8, 8), (10, 5))
    ] == [4, 4, 4, 2, 3, 3, None, None]
    args, do = gdn_arguments(2, 64, 1, 8, raw=True)
    before = kda.PATH_COUNTS.copy()
    got, want = gdn_both(args, do)
    took("chunked_jnp", before)
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)


def test_a_gated_deltanet_layers_scan_under_the_strongest_decay(route):
    """g = -20 a token through the layer's entry (A_log = log 20, softplus(
    a + dt_bias) = 1): a chunk's cumulative gate reaches -1280 and the
    table's exponents above the diagonal +1280, which are never taken (a
    table factored as exp(G_i) exp(-G_j) would be inf x 0). The state is
    forgotten between tokens, o_t = scale beta_t (q_t . k_t) v_t with q and
    k of unit length, every gradient finite."""
    args, do = gdn_arguments(1, 150, raw=True)
    args.update(a=jnp.zeros_like(args["a"]),
                a_log=jnp.full((4,), np.log(20.0), jnp.float32),
                dt_bias=jnp.full((4,), np.log(np.e - 1), jnp.float32))
    before = kda.PATH_COUNTS.copy()
    got, want = gdn_both(args, do)
    took(route, before)
    for name, v in got.items():
        assert bool(jnp.all(jnp.isfinite(v))), name
    # the gate's gradients are of the order exp(-20) (KDA's test): held to
    # zero, not to a share of themselves
    zero = {"a": 1e-6, "dt_bias": 1e-4, "a_log": 1e-4}
    for name, limit in zero.items():
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) < limit, name
    for name, err in gdn_worst(got, want, args).items():
        assert name in zero or err < F32_TOL, (name, err)
    q, k = (to_value_heads(unit_heads(args[n], 2), 2, 4).reshape(
        2, 150, 4, D) for n in "qk")
    alone = (D ** -0.5 * args["beta"] * (q * k).sum(-1))[..., None] \
        * args["v"].reshape(2, 150, 4, D)
    np.testing.assert_allclose(got["o"], alone.reshape(2, 150, -1),
                               atol=1e-6)


def test_a_gated_deltanet_layers_keys_alike_are_solved_in_blocks(
        monkeypatch, route):
    """``test_keys_alike_are_solved_in_blocks`` for the body with one decay
    a head: neighbouring keys alike in direction, beta 0.9, a weak decay.
    The solve in blocks of 8, merged, is the one in use on both routes:
    with one block of 64 (the Neumann product over the whole chunk) o is
    wrong by more than its own size."""
    args, do = gdn_arguments(5, 150, raw=True)
    r = jax.random.split(jax.random.PRNGKey(105), 2)
    k = jax.random.normal(r[0], (2, 1, 2, D)) \
        + 0.5 * jax.random.normal(r[1], (2, 150, 2, D))
    args.update(k=k.reshape(2, 150, 2 * D), beta=jnp.full((2, 150, 4), 0.9),
                a_log=args["a_log"] + np.log(0.05))
    before = kda.PATH_COUNTS.copy()
    got, want = gdn_both(args, do)
    took(route, before)
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)
    monkeypatch.setattr(kda, "_SUB", 64)        # one block: the whole chunk
    got, _ = gdn_both(args, do)
    assert not gdn_worst({"o": got["o"]}, {"o": want["o"]}, {})["o"] < 1.0


def test_a_gated_deltanet_layers_bf16_arguments(route):
    """The model's call: bf16 q, k, v and ``a``; A_log, dt_bias and beta
    float32. Products on bf16 operands, everything a gate touches float32:
    3e-2 of the largest entry, as KDA's."""
    args, do = gdn_arguments(3, 150, raw=True)
    args = in_dtype(args, jnp.bfloat16)
    before = kda.PATH_COUNTS.copy()
    got, want = gdn_both(args, do)
    took(route, before)
    assert got["o"].dtype == jnp.bfloat16
    for name, err in gdn_layer_worst(got, want, args).items():
        assert err < 3e-2, (name, err)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_head_decay_kernels_gradients_are_the_plain_routes(
        dtype, tol, monkeypatch):
    """ISSUE 53: o and the SEVEN gradients of ``gdn_gated_scan`` on the
    kernel pair against ``jax.vjp`` of its plain route (``l2norm``, the
    softplus, ``gated_delta_scan``), 3 chunks of a block of 4 value heads
    over 2 key heads. dq and dk leave the backward kernel summed over a key
    head's value heads, in q's dtype and shape; A_log's and dt_bias's as
    partial sums a batch row, head and token of the chunk, held to what
    they sum. With bf16 arguments o differs by a rounding of bf16 (the
    kernels keep the normalised q and k and e^G (k S_0) in float32 where
    the plain form rounds them first)."""
    args, do = gdn_arguments(6, 192, raw=True)
    args = in_dtype(args, dtype)
    before = kda.PATH_COUNTS.copy()
    got, _ = gdn_both(args, do)
    took("kernel", before)
    monkeypatch.setattr(kda, "_route", lambda *shape: "chunked_jnp")
    before = kda.PATH_COUNTS.copy()
    want, _ = gdn_both(args, do)
    took("chunked_jnp", before)
    for name, err in gdn_layer_worst(got, want, args).items():
        assert err < (8e-3 if name == "o" and tol > 1e-3 else tol), (name, err)
    for name in got:
        assert got[name].shape == want[name].shape
        assert got[name].dtype == (
            jnp.float32 if name in ("a_log", "dt_bias", "beta") else dtype)


def _traced_again():
    """The pair's pure bodies are traced once a process (``jax.jit``): a
    fault planted in what they call shows only to a fresh trace, and must
    not outlive the test."""
    kda._gdn_forward_of.clear_cache()
    kda._gdn_backward_of.clear_cache()


def _the_decay_table_dropped(monkeypatch):
    """The value heads' tables exp(G_i - G_j) left off the shared scores:
    every earlier token weighs as the newest."""
    monkeypatch.setattr(kda, "_decay", lambda d: jnp.ones_like(d))
    return ("o", "q", "k", "v", "a"), 1e-2


def _dq_of_one_value_head(monkeypatch):
    """dq and dk taken from the key head's LAST value head alone, not
    summed over the pair: o and what belongs to a value head are right."""
    monkeypatch.setattr(kda, "_summed",
                        lambda parts, onto=None: parts[-1] if onto is None
                        else onto + parts[-1])
    return ("q", "k"), 1e-2


@pytest.mark.parametrize("fault", ["table_dropped", "dq_of_one_value_head"])
def test_a_wrong_head_decay_body_would_fail(fault, monkeypatch):
    """What the limits of the kernel route are for: two faults planted in
    the new body, each reads a thousand times over them."""
    args, do = gdn_arguments(2, 150, raw=True)
    plant = {"table_dropped": _the_decay_table_dropped,
             "dq_of_one_value_head": _dq_of_one_value_head}[fault]
    _traced_again()
    try:
        with monkeypatch.context() as m:
            wrong, at_least = plant(m)
            before = kda.PATH_COUNTS.copy()
            got, want = gdn_both(args, do)
            took("kernel", before)
    finally:
        _traced_again()
    err = gdn_worst(got, want, args)
    assert min(err[n] for n in wrong) > at_least > 10 * F32_TOL, err
    right = set(err) - set(wrong) if fault == "dq_of_one_value_head" else ()
    assert all(err[n] < F32_TOL for n in right), err
    # and the body as it is, traced afresh, is right again
    got, want = gdn_both(args, do)
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)


def test_a_gated_deltanet_scans_path_event(route):
    """The facts of ``rtpu.ops.kda.path`` for a Gated DeltaNet layer's
    call: ``decay`` (``head``: one a head, or ``channel``: KDA's),
    ``key_heads`` (ISSUE 52) and ``body`` (ISSUE 53: ``head_decay``, the
    program with the decay factored out of the scores, on both routes;
    PR 52's kernel route said ``decay: head`` and ran ``channel_decay``);
    on the kernel route four value heads a program, whole key heads."""
    from ray_tpu.perf import recorder

    args, _ = gdn_arguments(4, 150, raw=True)
    jax.eval_shape(lambda *a: kda.gdn_gated_scan(*a, scale=1.0),
                   *(args[n] for n in GDN_GATED))
    data = [e["data"] for e in recorder.get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.kda.path"][-1]
    facts = {"route": route, "chunk": 64, "tokens": 150, "padded_tokens": 42,
             "heads": 4, "d_k": D, "d_v": D, "chunks": 3, "decay": "head",
             "key_heads": 2, "body": "head_decay",
             # ISSUE 67: heads of 128 are whole tiles; the solve's blocks
             "lanes_k": D, "lanes_v": D, "solve_block": 8,
             "prologue": "in_kernel" if route == "kernel" else "jnp"}
    if route == "kernel":      # ISSUE 66: two pairs' solves in lock step
        facts.update(heads_per_block=4, pairs_in_step=2)
    assert data == facts


# -- ISSUE 67: key and value heads of unlike sizes, beta up to 2 --------------

DK, DV = 96, 192      # Olmo-Hybrid's head: a state [96, 192]


def unlike_arguments(seed, t, key_heads, value_heads, batch=2, alike=None,
                     beta=None):
    """``gdn_arguments(raw=True)`` at key heads of 96 and value heads of
    192, beta drawn in (0, 2) (``2 sigmoid``, what a layer with
    ``allow_neg_eigval`` hands its scan) or ``beta`` everywhere; ``alike``:
    neighbouring keys alike in direction (k_i . k_j near 0.8) and a weak
    decay, the solve's hard case."""
    r = jax.random.split(jax.random.PRNGKey(seed), 10)

    def keys(key, length, around=None):
        x = jax.random.normal(key, (batch, t, key_heads, DK))
        if around is not None:
            x = jax.random.normal(r[6], (batch, 1, key_heads, DK)) + around * x
        x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        x = x * jnp.exp(jax.random.uniform(
            length, (batch, t, key_heads, 1), minval=np.log(1e-2),
            maxval=np.log(10.0)))
        return x.reshape(batch, t, key_heads * DK)

    dt = jnp.exp(jax.random.uniform(r[4], (value_heads,), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    a_log = jnp.log(jax.random.uniform(r[3], (value_heads,), minval=1.0,
                                       maxval=16.0))
    shape = (batch, t, value_heads)
    args = {"q": keys(r[0], r[7]), "k": keys(r[1], r[8], alike),
            "v": jax.random.normal(r[2], (batch, t, value_heads * DV)),
            "a": 0.5 * jax.random.normal(r[5], shape),
            "a_log": a_log if alike is None else a_log + np.log(0.05),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "beta": 2.0 * jax.nn.sigmoid(jax.random.normal(r[9], shape))
            if beta is None else jnp.full(shape, beta)}
    return args, jax.random.normal(r[6], (batch, t, value_heads * DV))


def unlike_recurrence(q, k, v, a, a_log, dt_bias, beta, *, key_heads, scale,
                      **wrong):
    """The definition at unlike head sizes, token by token: q and k of unit
    length a key head, repeated to the value heads; the head's one decay on
    all of its key channels; a state [96, 192] a value head."""
    b, t, hv = beta.shape
    wide = lambda x: jnp.repeat(                             # noqa: E731
        unit_heads(x, key_heads).reshape(b, t, key_heads, DK),
        hv // key_heads, 2).reshape(b, t, hv * DK)
    g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    return recurrence(wide(q), wide(k), v, jnp.repeat(g, DK, -1), beta,
                      scale=scale, heads=hv, **wrong)


def unlike_both(args, do, key_heads, beta_max=2.0, **wrong):
    scale = DK ** -0.5
    return (o_and_grads(lambda *a: kda.gdn_gated_scan(
        *a, scale=scale, key_heads=key_heads, beta_max=beta_max), args, do),
        o_and_grads(lambda *a: unlike_recurrence(
            *a, key_heads=key_heads, scale=scale, **wrong), args, do))


@pytest.mark.parametrize("t,heads", [(150, (3, 3)), (150, (2, 4)),
                                     (64, (1, 1)), (40, (2, 2))],
                         ids=["ragged-odd_heads", "ragged-two_to_one",
                              "one_chunk", "short"])
def test_heads_of_unlike_sizes_are_the_recurrence(route, t, heads):
    """ISSUE 67: ``gdn_gated_scan`` with the key heads STATED, keys of 96
    and values of 192, beta drawn in (0, 2), on both routes: o and the
    seven gradients against the recurrence on a [96, 192] state. On the
    kernel route a head's keys are zero-padded to one 128-lane tile and its
    values to two tiles, each a value head of 128 on the one key head (a
    program: one key head and its two tiles, or two key heads and their
    four where the heads are even); the solve in blocks of 4."""
    args, do = unlike_arguments(2, t, *heads)
    before = kda.PATH_COUNTS.copy()
    got, want = unlike_both(args, do, heads[0])
    took(route, before)
    assert got["o"].shape == (2, t, heads[1] * DV)
    for name, err in gdn_worst(got, want, args).items():
        assert err < F32_TOL, (name, err)
        assert got[name].shape == args.get(name, got["o"]).shape


@pytest.mark.parametrize("fault,least", [
    ({"state_dtype": jnp.bfloat16}, 1e-3), ("beta_left_at_sigmoid", 1e-2)],
    ids=["bf16_state", "beta_left_at_sigmoid"])
def test_a_wrong_scan_of_unlike_heads_would_fail(route, fault, least):
    """What ``F32_TOL`` is for at these shapes: a state rounded to bf16
    after every token (o off by 1.2e-2 of its largest entry), and beta left
    at the sigmoid where the layer hands twice that (0.54): a thousand
    times over it and more."""
    args, do = unlike_arguments(2, 150, 3, 3)
    if fault == "beta_left_at_sigmoid":
        got, _ = unlike_both(dict(args, beta=0.5 * args["beta"]), do, 3)
        _, want = unlike_both(args, do, 3)
    else:
        got, want = unlike_both(args, do, 3, **fault)
    assert gdn_worst(got, want, args)["o"] > least >= 100 * F32_TOL


def test_the_plain_definition_takes_unlike_head_sizes():
    """``gated_delta_scan`` with ``key_heads`` stated: q and k [B, T, Hk *
    96] as the caller normalised them, v [B, T, Hv * 192], against the
    recurrence, o and five gradients. (Left unstated, these merged arrays
    would read as ONE key head of 192 under four value heads, a shape like
    any other: nothing to refuse, which is why the layer states it.)"""
    args, do = unlike_arguments(3, 150, 2, 4)
    g = -jnp.exp(args["a_log"]) * jax.nn.softplus(args["a"] + args["dt_bias"])
    plain = {"q": unit_heads(args["q"], 2), "k": unit_heads(args["k"], 2),
             "v": args["v"], "g": g, "beta": args["beta"]}
    scale = DK ** -0.5
    wide = lambda x: jnp.repeat(x.reshape(2, 150, 2, DK), 2, 2).reshape(  # noqa: E731
        2, 150, 4 * DK)
    before = kda.PATH_COUNTS.copy()
    got = value_and_grads(lambda *a: kda.gated_delta_scan(
        *a, scale=scale, key_heads=2, beta_max=2.0), plain, do)
    took("chunked_jnp", before)
    want = value_and_grads(lambda q, k, v, g, beta: recurrence(
        wide(q), wide(k), v, jnp.repeat(g, DK, -1), beta, scale=scale,
        heads=4), plain, do)
    for name, err in gdn_worst(got, want, plain).items():
        assert err < F32_TOL, (name, err)
    with pytest.raises(ValueError, match="4 value heads over 3 key heads"):
        kda.gated_delta_scan(*(plain[n] for n in GDN), scale=scale,
                             key_heads=3)


def test_heads_of_128_are_untouched_by_the_new_arguments(route):
    """Qwen3-Next's call: the key heads stated or known from the one head
    size, it is the same jaxpr and the same numbers to the bit; the solve
    in blocks of 8 and the lanes the head sizes. (That the cell's whole
    train step is the program it was: ``scripts/train_step_hlo.py
    --compare``.)"""
    from ray_tpu.perf import recorder

    args, _ = gdn_arguments(4, 150, raw=True)
    xs = [args[n] for n in GDN_GATED]
    as_was = lambda *a: kda.gdn_gated_scan(*a, scale=D ** -0.5)  # noqa: E731
    stated = lambda *a: kda.gdn_gated_scan(                      # noqa: E731
        *a, scale=D ** -0.5, key_heads=2, beta_max=1.0)
    assert str(jax.make_jaxpr(as_was)(*xs)) == str(jax.make_jaxpr(stated)(*xs))
    np.testing.assert_array_equal(jax.jit(as_was)(*xs), jax.jit(stated)(*xs))
    data = [e["data"] for e in recorder.get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.kda.path"][-1]
    assert (data["lanes_k"], data["lanes_v"], data["solve_block"]) == (D, D, 8)


def test_which_heads_take_the_padded_kernel_route():
    """More than half of what the kernels read of a head is the model's, or
    the plain route runs: 96 and 192 take the pair on 128 and 256 lanes (75
    %), heads of 64 stay the plain form's (two to a tile: a packed layout's
    to win, ROADMAP), heads of 128 are whole tiles."""
    assert [kda._gdn_lanes(*d) for d in (
        (96, 192), (128, 128), (64, 64), (96, 64), (64, 128), (128, 256),
        (192, 192), (100, 130))] == [
        (128, 256), (128, 128), None, None, None, (128, 256), None,
        (128, 256)]
    args, _ = unlike_arguments(0, 64, 1, 8)     # 16 tiles on one key head
    before = kda.PATH_COUNTS.copy()
    jax.eval_shape(lambda *a: kda.gdn_gated_scan(
        *a, scale=1.0, key_heads=1), *(args[n] for n in GDN_GATED))
    took("chunked_jnp", before)


def test_keys_alike_at_beta_near_two_need_blocks_of_four(route):
    """The beta-to-2 study end to end (``test_kda_scan.py`` has the solve
    alone): neighbouring keys alike, a weak decay, beta 1.9 everywhere. With
    ``beta_max`` 2 (blocks of 4) o and every gradient hold ``F32_TOL``; the
    same call with the solve left at blocks of 8 reads two to three times
    worse and misses it."""
    args, do = unlike_arguments(5, 150, 2, 2, alike=0.5, beta=1.9)
    before = kda.PATH_COUNTS.copy()
    got, want = unlike_both(args, do, 2)
    took(route, before)
    fine = gdn_worst(got, want, args)
    for name, err in fine.items():
        assert err < F32_TOL, (name, err)
    got, _ = unlike_both(args, do, 2, beta_max=1.0)
    coarse = gdn_worst(got, want, args)
    assert max(coarse.values()) > F32_TOL > max(fine.values()), (coarse, fine)
