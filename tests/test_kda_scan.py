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

ISSUE 51: every such test also runs through the second entry,
``kda_gated_scan`` (``entry`` = ``gated``): q and k as a layer's
convolutions leave them (any length, some tokens so short that the norm's
``eps`` shows), the gate projection's ``step``, ``A_log`` and ``dt_bias``,
held to the recurrence of ``l2norm``-ed q and k and g = -exp(A_log)
softplus(step + dt_bias) written out here, o and SEVEN gradients. On the
kernel route the norms and the gate are made inside the kernels. The
gradients of ``A_log`` and ``dt_bias`` are sums over every token (and a
head's channels) of terms of both signs: they are held to a share of what
those sums ADD UP in magnitude (``added``), as a sum in another order can
be, not of the sum that is left (against float64 the plain route's own
``A_log`` is off by 7e-5 of its largest entry here, the kernels' by 1.5e-4;
of what is added both read under 1e-6).

ISSUE 52, the delta rule with ONE decay a head (Gated DeltaNet):
``test_gdn_scan.py``; what both files share (the recurrence, the route's
fixture) is in ``_delta_rule.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _delta_rule import (D, F32_TOL, GATED, NAMES, kda,  # noqa: F401
                         recurrence, route, took, unit_heads, value_and_grads)

ENTRIES = ("scan", "gated")


@pytest.fixture(params=ENTRIES)
def entry(request):
    """``scan``: ``kda_scan`` (q and k normalised, g ready); ``gated``:
    ``kda_gated_scan`` from the raw q, k and the gate projection's step."""
    return request.param


def gate_of(step, a_log, dt_bias):
    """g = -exp(A_log)[head] softplus(step + dt_bias), float32."""
    x = step.astype(jnp.float32) + dt_bias
    soft = jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return -jnp.repeat(jnp.exp(a_log), x.shape[-1] // a_log.shape[0]) * soft


def gated_recurrence(q, k, v, step, a_log, dt_bias, beta, *, scale, heads,
                     **wrong):
    """``kda_gated_scan``'s definition, token by token."""
    return recurrence(unit_heads(q, heads), unit_heads(k, heads), v,
                      gate_of(step, a_log, dt_bias), beta, scale=scale,
                      heads=heads, **wrong)


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


def gated(args, dtype=None, gate=None):
    """``arguments`` as a KDA layer hands them to ``kda_gated_scan``: q and
    k of any length (a token's and head's from 0.01 to 10: at 0.01 the
    norm's eps moves the result by 5e-3), the gate projection's step, A_log
    and dt_bias in place of g (decays in the same range; ``gate`` a token
    and channel where it is given: A_log = log(-gate), softplus(dt_bias) =
    1, step 0)."""
    b, t, h = args["beta"].shape
    dtype = dtype or args["q"].dtype
    r = jax.random.split(jax.random.PRNGKey(b * t), 5)

    def any_length(x, key):
        m = jnp.exp(jax.random.uniform(key, (b, t, h, 1), minval=np.log(
            1e-2), maxval=np.log(10.0)))
        return (x.astype(jnp.float32).reshape(b, t, h, D) * m).reshape(
            x.shape).astype(dtype)

    dt = jnp.exp(jax.random.uniform(r[2], (h * D,), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    out = {"q": any_length(args["q"], r[0]), "k": any_length(args["k"], r[1]),
           "v": args["v"],
           "step": (0.5 * jax.random.normal(r[3], (b, t, h * D))).astype(dtype),
           "a_log": jnp.log(jax.random.uniform(r[4], (h,), minval=1.0,
                                               maxval=16.0)),
           "dt_bias": dt + jnp.log(-jnp.expm1(-dt)), "beta": args["beta"]}
    if gate is not None:
        out.update(step=jnp.zeros_like(out["step"]),
                   a_log=jnp.full((h,), np.log(-gate), jnp.float32),
                   dt_bias=jnp.full((h * D,), np.log(np.e - 1), jnp.float32))
    return out


def added(args, want):
    """What the gradients of ``a_log`` (dg g, a head) and ``dt_bias``
    (dstep, a channel) ADD UP in magnitude, from ``want``'s dstep = dg g
    sigmoid / softplus: the scale a sum in another order is held to."""
    x = args["step"].astype(jnp.float32) + args["dt_bias"]
    dstep = jnp.abs(want["step"].astype(jnp.float32))
    b, t, _ = dstep.shape
    dgg = dstep * jax.nn.softplus(x) / jax.nn.sigmoid(x)
    return {"a_log": jnp.max(dgg.reshape(b, t, -1, D).sum((0, 1, 3))),
            "dt_bias": jnp.max(dstep.sum((0, 1)))}


def worst(got, want, args=None):
    """{name: largest difference as a share of want's largest entry}; with
    the ``gated`` arguments given, a_log's and dt_bias's as a share of what
    their sums add up (``added``)."""
    of = {n: jnp.max(jnp.abs(want[n].astype(jnp.float32))) for n in want}
    if args is not None and "step" in args:
        of.update(added(args, want))
    return {n: float(jnp.max(jnp.abs(got[n].astype(jnp.float32)
                                     - want[n].astype(jnp.float32)))
                     / (of[n] + 1e-30)) for n in want}


def both(args, do, heads=2, **kw):
    """(the module's o and gradients, the recurrence's) of ``args``: the
    five of ``kda_scan`` or, with a ``step`` among them, the seven of
    ``kda_gated_scan``."""
    scale = D ** -0.5
    ref, fn = (gated_recurrence, kda.kda_gated_scan) if "step" in args \
        else (recurrence, kda.kda_scan)
    want = value_and_grads(
        lambda *a: ref(*a, scale=scale, heads=heads), args, do)
    got = value_and_grads(lambda *a: fn(*a, scale=scale, **kw), args, do)
    return got, want


@pytest.mark.parametrize("t,chunk", [(150, 64), (64, 64), (40, 64), (96, 16)],
                         ids=["ragged", "one_chunk", "short", "chunk16"])
def test_kda_scan_is_the_recurrence(t, chunk, route, entry):
    """o and all five gradients (seven through the gated entry), T a
    multiple of the chunk or not (150 = 2 chunks and 22 tokens: padded; 40
    tokens: the kernels pad them to one chunk of 64), decays as strong as
    the assumed initialisation makes them. A chunk of 16 is the plain
    form's on either route. One row of one head: the smallest call the
    kernels take (a program's heads are unrolled, and sixteen cases
    compile them; two rows of two heads a program are every other test's
    shape)."""
    args, do = arguments(0, t, heads=1, batch=1)
    if entry == "gated":
        args = gated(args)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do, heads=1, chunk=chunk)
    took(route, before, chunk)
    for name, err in worst(got, want, args).items():
        assert err < F32_TOL, (name, err)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in got.values())


def test_kda_scan_under_the_strongest_decay(route, entry):
    """g = -20 a token and channel: the cumulative gate of a chunk reaches
    -1280, exp(+1280) is inf in float32, so a factorised exp(G) exp(-G)
    would be NaN. Every exponent here is <= 0: the state is forgotten
    between tokens and o_t = scale beta_t (q_t.k_t) v_t, with every
    gradient finite. Through the gated entry: A_log = log 20 and
    softplus(step + dt_bias) = 1."""
    args, do = arguments(1, 150, gate=-20.0)
    if entry == "gated":
        args = gated(args, gate=-20.0)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do)
    took(route, before)
    for name, v in got.items():
        assert bool(jnp.all(jnp.isfinite(v))), name
    # dg is of the order exp(-20) itself (2e-10 at its largest): held to
    # zero, not to a share of it; and so is what the gate's chain rule
    # makes of it (dstep = 12.6 dg: read 9e-8 from the kernels; dt_bias's
    # gradient a sum of 300 such, 5e-6, A_log's of 38 400 of 20 dg, 1e-5),
    # where the other gradients are of order 0.1
    zero = {"g": 1e-8, "step": 1e-6, "dt_bias": 1e-4, "a_log": 1e-4}
    for name in zero.keys() & got.keys():
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) < zero[name], \
            name
    for name, err in worst(got, want).items():
        assert name in zero or err < F32_TOL, (name, err)
    q, k, v = (args[n].reshape(2, 150, 2, D) for n in "qkv")
    if entry == "gated":
        q, k = (unit_heads(args[n], 2).reshape(2, 150, 2, D) for n in "qk")
    alone = (D ** -0.5 * args["beta"] * (q * k).sum(-1))[..., None] * v
    np.testing.assert_allclose(got["o"], alone.reshape(2, 150, -1),
                               atol=1e-6)


def test_keys_alike_are_solved_in_blocks(monkeypatch, route, entry):
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
    if entry == "gated":        # the keys alike in direction, of any length
        args = gated(args)
        args["a_log"] = args["a_log"] + np.log(0.05)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do)
    took(route, before)
    for name, err in worst(got, want, args).items():
        assert err < F32_TOL, (name, err)
    if entry == "gated":        # the solve is the one body's, shown once
        return
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


def _the_scan_wrong():
    """A state kept in bf16, one scalar decay a head, and a rule without
    its correction each read well over the tolerance."""
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


def _the_norms_eps_dropped():
    """The kernels' prologue with eps = 0: the tokens whose q or k is 0.01
    long come out of unit length where ``l2norm`` leaves them 0.995 long; o
    and the gradients through the norm miss the tolerance a hundredfold.
    (Whole chunks: a padded token's q is 0, and 0 / sqrt(0 + 0) is NaN.)"""
    args, do = arguments(0, 128)
    args = gated(args)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do, eps=0.0)
    took("kernel", before)
    err = worst(got, want, args)
    assert min(err[n] for n in ("o", "q", "k")) > 1e-3 > 10 * F32_TOL, err


def _the_softplus_slope_left_out(monkeypatch):
    """The backward kernel's chain rule without softplus's derivative (a
    sigmoid): dstep = dg (-exp(A_log)). o is right; step's and dt_bias's
    gradients are wrong by their own size and more."""
    whole = kda._gate

    def no_sigmoid(g_ref, rows_ref=None, *, slope=False):
        g, ds = whole(g_ref, rows_ref, slope=slope)
        if ds is not None:
            ds = jnp.broadcast_to(-jnp.exp(rows_ref[...][:1]), ds.shape)
        return g, ds

    monkeypatch.setattr(kda, "_gate", no_sigmoid)
    args, do = arguments(0, 150)
    args = gated(args)
    got, want = both(args, do)
    err = worst(got, want, args)
    assert err["o"] < F32_TOL and err["a_log"] < F32_TOL, err
    assert min(err["step"], err["dt_bias"]) > 0.5, err


@pytest.mark.parametrize("fault", ["scan", "eps_dropped", "slope_left_out"])
def test_a_wrong_scan_would_fail(fault, monkeypatch):
    """What the tolerance is for: the recurrence computed wrongly in three
    ways, and (ISSUE 51) two faults planted in the kernels' prologue, each
    reads well over it."""
    if fault == "scan":
        _the_scan_wrong()
    elif fault == "eps_dropped":
        _the_norms_eps_dropped()
    else:
        _the_softplus_slope_left_out(monkeypatch)


def test_bf16_arguments(route, entry):
    """The model's call: bf16 q, k, v (and step, through the gated entry:
    the model's call since ISSUE 51); g and beta, A_log and dt_bias
    float32."""
    args, do = arguments(3, 150, dtype=jnp.bfloat16)
    if entry == "gated":
        args = gated(args)
    before = kda.PATH_COUNTS.copy()
    got, want = both(args, do)
    took(route, before)
    assert got["o"].dtype == jnp.bfloat16
    for name, err in worst(got, want, args).items():
        assert err < 3e-2, (name, err)


def test_path_event_and_padding(route, entry):
    """The event's facts; ``prologue`` (ISSUE 51) says who made the norms
    and the gate: the kernels for the gated entry on the kernel route, the
    plain code everywhere else."""
    from ray_tpu.perf import recorder

    before = kda.PATH_COUNTS[route]
    args, _ = arguments(4, 150)
    if entry == "gated":
        args = gated(args)
        jax.eval_shape(lambda *a: kda.kda_gated_scan(*a, scale=1.0),
                       *(args[n] for n in GATED))
    else:
        jax.eval_shape(lambda *a: kda.kda_scan(*a, scale=1.0),
                       *(args[n] for n in NAMES))
    assert kda.PATH_COUNTS[route] == before + 1
    events = [e for e in recorder.get_recorder().snapshot()
              if e["kind"] == "rtpu.ops.kda.path"]
    facts = {"route": route, "chunk": 64, "tokens": 150,
             "padded_tokens": 42, "heads": 2, "d_k": D, "d_v": D, "chunks": 3,
             "prologue": "in_kernel" if (entry, route) == ("gated", "kernel")
             else "jnp", "decay": "channel", "key_heads": 2,
             "body": "channel_decay",     # ISSUE 53: which body a run measured
             # ISSUE 67: the lanes a head occupies, the solve's blocks
             "lanes_k": D, "lanes_v": D, "solve_block": 8}
    if route == "kernel":      # ISSUE 66: one pair, solved alone
        facts.update(heads_per_block=2, pairs_in_step=1)
    assert events and events[-1]["data"] == facts


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_kernels_gradients_are_the_plain_routes(dtype, tol, entry):
    """ISSUE 50: o and the five gradients of the kernel pair against
    ``jax.vjp`` of the plain route, 3 chunks of 2 x 2 heads. With bf16
    arguments o differs by a rounding of bf16 at most (the cumulative
    gates are summed in another order); the gradients differ by the
    rounding of the cotangents the plain form's autodiff casts to bf16 and
    the backward kernel keeps in float32. ISSUE 51, ``gated``: the fused
    entry's o and SEVEN gradients against ``jax.vjp`` of ``l2norm``, the
    softplus and the plain route; A_log's and dt_bias's come out of the
    backward kernel as partial sums a batch row and channel. (The kernels
    keep the normalised q and k in float32 where the definition rounds
    them to bf16 first: nearer the recurrence, ``test_bf16_arguments``.)"""
    args, do = arguments(6, 192, dtype=dtype)
    scale = D ** -0.5
    if entry == "gated":
        args = gated(args)
        fn = kda.kda_gated_scan

        def plain(q, k, v, step, a_log, dt_bias, beta):
            unit = lambda x: unit_heads(x, 2).astype(x.dtype)    # noqa: E731
            return kda._chunked(unit(q), unit(k), v,
                                gate_of(step, a_log, dt_bias), beta, 2, 64,
                                scale)
    else:
        fn = kda.kda_scan
        plain = lambda *a: kda._chunked(*a, 2, 64, scale)        # noqa: E731
    before = kda.PATH_COUNTS.copy()
    got = value_and_grads(lambda *a: fn(*a, scale=scale), args, do)
    took("kernel", before)
    want = value_and_grads(plain, args, do)
    want = {n: v.astype(jnp.float32) for n, v in want.items()}
    for name, err in worst(got, want, args).items():
        assert err < (8e-3 if name == "o" and tol > 1e-3 else tol), (name, err)
    gates = ("g", "beta") if entry == "scan" else ("a_log", "dt_bias", "beta")
    for name in got:
        assert got[name].dtype == (jnp.float32 if name in gates else dtype)


def _traced_again():
    """The pair's pure bodies are traced once a process (``jax.jit``): what
    a test plants in what they call, or in how many heads make a block,
    shows only to a fresh trace and must not outlive the test."""
    kda._forward_of.clear_cache()
    kda._backward_of.clear_cache()


@pytest.mark.parametrize("heads", [4, 3], ids=["four_heads", "three_heads"])
def test_a_block_worked_whole_changes_no_number(heads, entry, monkeypatch):
    """ISSUE 66: a program works its whole block of heads in one body, the
    pairs' solves in lock step and every later stage for all the heads
    before the next. That is an order of issue: o and every gradient are
    EQUAL TO THE LAST BIT to the same call at two heads a program (a pair
    alone, the program before; three heads then go one by one) and at one
    (no pair at all: a [C, C] solve a head)."""
    args, do = arguments(8, 128, heads=heads, batch=1)
    if entry == "gated":
        args = gated(args)
    fn = kda.kda_gated_scan if entry == "gated" else kda.kda_scan
    blocks, got = [], []
    try:
        for most in (4, 2, 1):
            monkeypatch.setattr(kda, "_MAX_HEADS_PER_BLOCK", most)
            if most == 1:     # an even number of heads is never cut to ones
                monkeypatch.setattr(kda, "_heads_per_block", lambda h: 1)
            _traced_again()
            blocks.append(kda._heads_per_block(heads))
            got.append(value_and_grads(
                lambda *a: fn(*a, scale=D ** -0.5), args, do))
    finally:
        _traced_again()
    assert blocks == ([4, 2, 1] if heads == 4 else [3, 1, 1])
    for other in got[1:]:
        for name, x in got[0].items():
            np.testing.assert_array_equal(
                np.asarray(x.astype(jnp.float32)),
                np.asarray(other[name].astype(jnp.float32)), err_msg=name)


def test_four_heads_a_program_solve_their_pairs_in_lock_step(monkeypatch):
    """ISSUE 66: at four heads a program ``_solve`` is handed a LIST of two
    [C, 2C] matrices (two pairs, their chains of products side by side),
    once in the forward body and once in the backward's, and never a pair
    alone; and the path event says how many pairs a program solves side by
    side: 2 at four heads, 1 at two, 0 at one."""
    from ray_tpu.perf import recorder

    whole, calls = kda._solve, []

    def seen(a, r):
        calls.append([x.shape for x in a] if isinstance(a, (list, tuple))
                     else a.shape)
        return whole(a, r)

    monkeypatch.setattr(kda, "_solve", seen)
    facts = {}
    try:
        for heads in (4, 2, 1):
            args, do = arguments(9, 64, heads=heads, batch=1)
            _traced_again()
            del calls[:]
            jax.eval_shape(jax.grad(lambda *a: jnp.sum(kda.kda_scan(
                *a, scale=1.0) * do), argnums=(0, 1, 2, 3, 4)),
                *(args[n] for n in NAMES))
            top = [c for c in calls if isinstance(c, list)]
            facts[heads] = (top, [e["data"] for e in
                                  recorder.get_recorder().snapshot()
                                  if e["kind"] == "rtpu.ops.kda.path"][-1])
    finally:
        _traced_again()
    pair = (64, 128)
    assert facts[4][0] == [[pair, pair]] * 2       # forward, then backward
    assert facts[2][0] == [[pair]] * 2 and facts[1][0] == [[(64, 64)]] * 2
    assert [(facts[h][1]["heads_per_block"], facts[h][1]["pairs_in_step"])
            for h in (4, 2, 1)] == [(4, 2), (2, 1), (1, 0)]


def test_other_shapes_fall_back_to_the_plain_route(entry):
    """Heads of 64 (two to a 128-lane tile) and a chunk that is not 64 are
    the plain form's, and ``PATH_COUNTS`` says so; three heads of 128 take
    the kernels (an odd number of heads is solved one by one). The gated
    entry takes the same route by the same rule."""
    r = jax.random.split(jax.random.PRNGKey(7), 5)
    b, t, h = 1, 64, 2
    for d, chunk, want in ((64, 64, "chunked_jnp"), (128, 32, "chunked_jnp"),
                           (128, 64, "kernel")):
        q, k, v = (jax.random.normal(r[i], (b, t, h * d)) for i in range(3))
        g = -jnp.abs(jax.random.normal(r[3], (b, t, h * d))) * 0.1
        beta = jax.nn.sigmoid(jax.random.normal(r[4], (b, t, h)))
        before = kda.PATH_COUNTS.copy()
        if entry == "gated":
            o = jax.eval_shape(lambda *a, c=chunk: kda.kda_gated_scan(
                *a, scale=1.0, chunk=c), q, k, v, g, jnp.zeros((h,)),
                jnp.zeros((h * d,)), beta)
        else:
            o = jax.eval_shape(lambda *a, c=chunk: kda.kda_scan(
                *a, scale=1.0, chunk=c), q, k, v, g, beta)
        assert o.shape == (b, t, h * d)
        took(want, before)
    assert [kda._heads_per_block(n) for n in (1, 2, 3, 6, 32)] == [
        1, 2, 3, 2, 4]


# -- ISSUE 67: the solve where beta reaches 2 ---------------------------------

def _alike_a(beta, n=64, heads=8, seed=105):
    """A = beta tril(K K^T e^(G_i - G_j), -1) [heads, n, n] float32 of keys
    alike in direction (k_i . k_j near 0.8) under a weak decay."""
    r = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(r[0], (heads, 1, D)) \
        + 0.5 * jax.random.normal(r[1], (heads, n, D))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -0.002 * jnp.arange(n)
    a = jnp.einsum("hid,hjd->hij", k, k) * jnp.exp(g[:, None] - g[None]) * beta
    return jnp.tril(a, -1).astype(jnp.float32)


def _off(t, a):
    """T's largest error against float64's (I + A)^-1, as a share of that
    inverse's largest entry."""
    want = np.linalg.inv(np.eye(a.shape[-1]) + np.asarray(a, np.float64))
    return float(np.max(np.abs(np.asarray(t, np.float64) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_the_solve_holds_float32_where_beta_reaches_two(form):
    """The accuracy study of ``test_keys_alike_are_solved_in_blocks`` taken
    to beta in (0, 2) (Gated DeltaNet with ``allow_neg_eigval``: beta = 2
    sigmoid), the solve alone against float64, neighbouring keys alike. A
    diagonal block's Neumann terms grow as C(r - 1, n) a^n before they
    cancel and a = beta k_i . k_j doubles with beta. Read here (plain form;
    the kernels' ``_solve`` the same to the digit shown): blocks of 8 are
    off by 9.4e-7 at beta 0.9, 1.5e-6 at 1, 7.7e-6 at 1.5 and 3.3e-5 at 2;
    blocks of 4 by 3.0e-7, 3.1e-7, 7.7e-7 and 2.9e-6; blocks of 2 no better
    than 4 (2.5e-6 at 2: what is left is the merges' and the problem's
    own). So a call that says its beta reaches past 1 is solved in blocks
    of 4 (``_solve_block``: as many products, one doubling less, one merge
    more) and every other call as before: at beta 2 it holds 5e-6 where
    blocks of 8 miss 2e-5."""
    solve = {"plain": lambda a, r: kda._inverse_by_blocks(a, r),
             "kernel": lambda a, r: jnp.stack(
                 [kda._solve(x, r) for x in a])}[form]
    assert (kda._solve_block(1.0), kda._solve_block(2.0)) == (8, 4)
    off = {(beta, r): _off(solve(_alike_a(beta), r), _alike_a(beta))
           for beta in (0.9, 2.0) for r in (8, 4)}
    assert off[0.9, 8] < 2e-6 and off[0.9, 4] < 2e-6, off
    assert off[2.0, 8] > 2e-5, off          # sixteen times what it was
    assert off[2.0, kda._solve_block(2.0)] < 5e-6, off
