"""ISSUE 62: the zero of a padding row of the expert layer's buffer is its
WEIGHT (``pairs_to_rows``), not its content. ``tokens_to_rows`` returns the
rows as gathered, so a padding row holds a copy of a real token's row.

* The layer EQUALS the layer composed by hand on the masked gathers it had
  before (gather, then ``where`` over [rows, D]): output, held rows and
  every gradient with a limit of 0.0.
* Nothing reads a padding row: what is multiplied by the weight's 0 (the
  buffer going in, the cotangent coming back into the experts) may hold any
  finite number, and what is never gathered (the experts' output rows, the
  buffer's cotangent) may hold NaN.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import expert_layer as el
from ray_tpu.ops.expert_layer import held_expert_layer

TILE = 8
TOKENS = 64


def _layer_inputs(expert, latent, e, held, *, shared=True, t=TOKENS, d=32,
                  f=16, fs=24):
    """(x [t, d], the parameters of one expert layer of kind ``expert``
    holding ``held`` of ``e`` experts, in a latent where ``latent``)."""
    width = latent or d
    keys = iter(jax.random.split(jax.random.PRNGKey(62), 16))
    draw = lambda *s: 0.3 * jax.random.normal(next(keys), s)    # noqa: E731
    p = {"w_router": draw(d, e), "router_bias": draw(e),
         "e_up": draw(held, width, f), "e_down": draw(held, f, width)}
    if shared:
        p.update(s_up=draw(d, fs), s_down=draw(fs, d))
    if expert == "swiglu":
        p["e_gate"] = draw(held, width, f)
        if shared:
            p["s_gate"] = draw(d, fs)
    if latent:
        p.update(w_fc1=draw(d, latent), w_fc2=draw(latent, d))
    return jax.random.normal(next(keys), (t, d)), p


# -- the plain reference: the gathers of PR 61, padding rows written as zeros


def _masked_rows_of(values, at):
    """``ops/expert_layer.py``'s ``_rows_of`` as PR 61 had it, on the pair
    ids of ISSUE 65 (``choice * T + token`` where ``at.slot_axis`` is 0,
    ``token * k + choice`` where it is 1; values [T, ...] a token or
    [pairs] a pair)."""
    pairs = at["pair_row"].size
    pair = at["row_pair"]
    filled = (pair < pairs).reshape((-1,) + (1,) * (values.ndim - 1))
    pair = jnp.minimum(pair, pairs - 1)
    n = values.shape[0]
    return jnp.where(filled, values[
        pair % n if at.slot_axis == 0 else pair // (pairs // n)], 0)


def _sum_of_held_rows(y, at):
    picked = jnp.where(at["pair_held"][..., None], y[at["pair_row"]],
                       jnp.zeros((), y.dtype))      # [k, T, D] or [T, k, D]
    return jnp.sum(picked.astype(jnp.float32),
                   axis=at.slot_axis).astype(y.dtype)


def _masked_pairs_to_rows_impl(w, at):
    return _masked_rows_of(
        (w.T if at.slot_axis == 0 else w).reshape(-1), at)


def _masked_pairs_to_rows_bwd(at, g):
    dw = jnp.where(at["pair_held"], g[at["pair_row"]], 0.0)
    return (dw.T if at.slot_axis == 0 else dw), None


masked_tokens_to_rows = jax.custom_vjp(_masked_rows_of)
masked_rows_to_tokens = jax.custom_vjp(_sum_of_held_rows)
masked_pairs_to_rows = jax.custom_vjp(_masked_pairs_to_rows_impl)
masked_tokens_to_rows.defvjp(
    lambda x, at: (_masked_rows_of(x, at), at),
    lambda at, g: (_sum_of_held_rows(g, at), None))
masked_rows_to_tokens.defvjp(
    lambda y, at: (_sum_of_held_rows(y, at), at),
    lambda at, g: (_masked_rows_of(g, at), None))
masked_pairs_to_rows.defvjp(
    lambda w, at: (_masked_pairs_to_rows_impl(w, at), at),
    _masked_pairs_to_rows_bwd)

MASKED = (masked_tokens_to_rows, masked_pairs_to_rows, masked_rows_to_tokens)


def _by_hand(x, p, *, top_k, held, offset, scale, expert, score="sigmoid",
             moves=None, going_in=lambda buf, padding: buf,
             coming_out=lambda y, padding: y):
    """``held_expert_layer`` composed by hand, the three moves between
    tokens and rows given (``moves``: the layer's own where None);
    ``going_in`` / ``coming_out`` see the buffer before and after the
    experts with the mask of its padding rows [rows, 1] -> (output, held
    rows). In the layer's own order: a gradient is summed in the order its
    terms were traced."""
    to_rows, weight_rows, to_tokens = moves or (
        el.tokens_to_rows, el.pairs_to_rows, el.rows_to_tokens)
    shared = el._mlp(expert, x, p, "s", jnp.dot) if "s_up" in p else None
    rows = el.buffer_rows(x.shape[0], top_k, held, TILE)
    weights, chosen = el.route(x, p["w_router"], p.get("router_bias"),
                               top_k=top_k, routed_scale=scale, score=score)
    if top_k > held:
        weights, chosen = el.compact_held(weights, chosen, held, offset)
    at = el.sort_rows(chosen, held, offset, rows, TILE)
    padding = (at["row_pair"] >= chosen.size)[:, None]
    u = jnp.dot(x, p["w_fc1"].astype(x.dtype)) if "w_fc1" in p else x
    y = el._mlp(expert, going_in(to_rows(u, at), padding), p, "e",
                lambda a, w: el.grouped_matmul(a, w, at["tile_expert"],
                                               at["n_used"], TILE),
                weight_rows(weights, at))
    routed = to_tokens(coming_out(y, padding), at)
    if "w_fc2" in p:
        routed = jnp.dot(routed, p["w_fc2"].astype(x.dtype))
    if shared is not None:
        routed = shared + routed
    return routed, at["held_rows"]


def _both_ways(x, p, by_hand, *, top_k, held, offset, expert, score):
    """(output, held rows) and the gradients of x and ``p`` under ONE
    cotangent, of ``held_expert_layer`` and of ``by_hand``."""
    # one cotangent for both, so that a gradient differs by its own sums alone
    cot = jnp.cos(7.0 * x[:, ::-1])

    def layer(x, p):
        y, rows = held_expert_layer(
            x, p, experts_held=held, expert_offset=offset, top_k=top_k,
            routed_scale=2.5, expert=expert, score=score, tile=TILE)
        return jnp.sum(y * cot), (y, rows)

    def hand(x, p):
        y, rows = by_hand(x, p)
        return jnp.sum(y * cot), (y, rows)

    with jax.default_matmul_precision("highest"):
        return [jax.jit(jax.value_and_grad(fn, (0, 1), has_aux=True))(x, p)
                for fn in (layer, hand)]


def _assert_equal(got, want, routing="drawn"):
    """Output, held rows and every gradient: finite, and equal to the bit."""
    ((_, (y, rows)), g), ((_, (want_y, want_rows)), gw) = got, want
    assert int(rows) == int(want_rows)
    for a, b, name in [(y, want_y, "y"), (g[0], gw[0], "x")] + [
            (g[1][n], gw[1][n], n) for n in gw[1]]:
        assert np.isfinite(np.asarray(a)).all(), name
        # no gradient reaches the selection bias; under ``none`` no row
        # reaches an expert, a projection of the latent or the router
        if name != "router_bias" and (routing != "none"
                                      or name[:2] not in ("e_", "w_")):
            assert float(jnp.abs(b).max()) > 0, name
        assert float(jnp.abs(a - b).max()) == 0.0, name


def _routed(p, routing, e, held, offset):
    """``every`` / ``none``: the selection bias makes every token choose
    every held expert (no padding but the tiles' ragged ends) or none (the
    whole buffer is padding, one empty tile an expert)."""
    if routing != "drawn":
        here = (jnp.arange(e) >= offset) & (jnp.arange(e) < offset + held)
        p["router_bias"] = jnp.where(
            here, {"every": 10.0, "none": -10.0}[routing], 0.0)
    return p


@pytest.mark.parametrize("routing", ["drawn", "every", "none"])
@pytest.mark.parametrize("top_k,held,offset", [(22, 8, 0), (6, 16, 8)],
                         ids=["compacted", "uncompacted"])
@pytest.mark.parametrize("expert,latent", [
    ("relu2", 32), ("relu2", 0), ("swiglu", 32), ("swiglu", 0)])
def test_the_layer_is_its_masked_self_to_the_bit(expert, latent, top_k, held,
                                                 offset, routing):
    """Without the ``where`` over [rows, D] the layer's output, held rows and
    EVERY gradient equal those of the layer composed on the masked gathers
    of PR 61: the sums gain exact zeros where they gained exact zeros."""
    e = 32
    x, p = _layer_inputs(expert, latent, e, held)
    p = _routed(p, routing, e, held, offset)
    kw = dict(top_k=top_k, held=held, offset=offset, expert=expert,
              score="sigmoid")
    layer, masked = _both_ways(
        x, p, lambda x, p: _by_hand(x, p, scale=2.5, moves=MASKED, **kw),
        **kw)
    _assert_equal(layer, masked, routing)


@pytest.mark.parametrize("routing", ["drawn", "every", "none"])
def test_a_padding_row_holds_the_last_tokens_row(routing):
    """``tokens_to_rows`` and its masked self on one ``sort_rows``: the rows
    that hold a pair are the same rows; the others were zeros and are the
    gather's clamped index, a copy of the last token's row."""
    top_k, held, offset, e = 6, 16, 8, 32
    x, p = _layer_inputs("swiglu", 0, e, held)
    p = _routed(p, routing, e, held, offset)
    _, chosen = el.route(x, p["w_router"], p["router_bias"], top_k=top_k,
                         routed_scale=2.5)
    at = el.sort_rows(chosen, held, offset,
                      el.buffer_rows(TOKENS, top_k, held, TILE), TILE)
    buf, want_buf = (np.asarray(to_rows(x, at)) for to_rows in (
        el.tokens_to_rows, masked_tokens_to_rows))
    filled = np.asarray(at["row_pair"]) < TOKENS * top_k
    assert filled.sum() == int(at["held_rows"])
    assert (filled.sum() == 0) == (routing == "none") and not filled.all()
    np.testing.assert_array_equal(buf[filled], want_buf[filled])
    assert not want_buf[~filled].any()
    np.testing.assert_array_equal(
        buf[~filled], np.broadcast_to(np.asarray(x)[-1],
                                      ((~filled).sum(), x.shape[1])))
    # the zero that is left: the weight of a padding row
    weights = np.asarray(el.pairs_to_rows(jnp.ones(chosen.shape), at))
    np.testing.assert_array_equal(weights, filled.astype(np.float32))


def test_a_softmax_layer_without_a_shared_expert_is_its_masked_self():
    """qwen3next's and keyevl2's call: ``score="softmax"``, no selection
    bias and (keyevl2) no shared expert."""
    x, p = _layer_inputs("swiglu", 0, 32, 8, shared=False)
    del p["router_bias"]
    kw = dict(top_k=6, held=8, offset=8, expert="swiglu", score="softmax")
    layer, masked = _both_ways(
        x, p, lambda x, p: _by_hand(x, p, scale=2.5, moves=MASKED, **kw),
        **kw)
    assert 0 < int(layer[0][1][1]) < TOKENS * 6
    _assert_equal(layer, masked)


@jax.custom_vjp
def _overwrite(v, padding, forward, backward):
    """v with its padding rows overwritten by ``forward``, and the
    cotangent's by ``backward``."""
    return jnp.where(padding, forward, v)


_overwrite.defvjp(
    lambda v, padding, forward, backward: (
        jnp.where(padding, forward, v), (padding, backward)),
    lambda res, g: (jnp.where(res[0], res[1], g), None, None, None))


@pytest.mark.parametrize("unread", [1e30, float("nan")], ids=["1e30", "nan"])
@pytest.mark.parametrize("expert,latent,top_k,held,offset", [
    ("relu2", 32, 22, 8, 0), ("swiglu", 0, 6, 16, 8), ("swiglu", 32, 5, 2, 2)])
def test_nothing_reads_a_padding_row(expert, latent, top_k, held, offset,
                                     unread):
    """The test that catches a reader of padding. A padding row's content
    meets the weight's 0 where it goes into a sum (the buffer into the
    experts, and the cotangent coming back into them): ANY finite number
    there changes nothing (1e10 here; NaN or a number whose hidden
    activation overflows would not be finite x 0). Where a padding row is
    a RESULT (the experts' output going out, the buffer's cotangent coming
    back) it is never gathered: 1e30 or NaN there changes nothing."""
    e = 32 if top_k > 5 else 8
    x, p = _layer_inputs(expert, latent, e, held)
    kw = dict(top_k=top_k, held=held, offset=offset, expert=expert,
              score="sigmoid")
    finite, unread = jnp.float32(1e10), jnp.float32(unread)
    layer, overwritten = _both_ways(
        x, p, lambda x, p: _by_hand(
            x, p, scale=2.5,
            going_in=lambda buf, pad: _overwrite(buf, pad, finite, unread),
            coming_out=lambda y, pad: _overwrite(y, pad, unread, -finite),
            **kw), **kw)
    rows = int(layer[0][1][1])
    assert 0 < rows < el.buffer_rows(TOKENS, top_k, held, TILE) - TILE
    _assert_equal(overwritten, layer)


# -- ISSUE 65: a token's slots lie down the LEADING axis ----------------------


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations
    (``custom_vjp_call``, ``pjit``)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("k", [4, 6, 10, 8, 16])
@pytest.mark.parametrize("move", ["rows_to_tokens", "tokens_to_rows_bwd"])
def test_the_slots_are_summed_where_the_gather_lands(move, k):
    """The guard of the layout on the CPU, where the chip's tiling cannot
    be read. At 4, 6 and 10 slots (xing4's and lfm2moe's, kanana2's,
    qwen3next's) the rows gathered back are [k, T, D] and the sum over a
    token's slots is over axis 0, in float32; no ``transpose`` and no
    ``reshape`` touches an array of T * k * D elements but the gather's own
    [k * T, D] -> [k, T, D] (a bitcast on the chip: T rows tile by 8 where
    k rows do not), forward and as ``tokens_to_rows``' backward. At 8 and
    16, whole tiles, they are [T, k, D] summed over axis 1: the program
    the three cells of 8 slots had."""
    t, d, held = 24, 16, 20
    axis = el.slot_axis(k)
    assert axis == (0 if k % 8 else 1)
    rows = el.buffer_rows(t, k, held, TILE)
    chosen = jnp.argsort(jax.random.normal(jax.random.PRNGKey(65), (t, 32)),
                         axis=1)[:, :k].astype(jnp.int32)
    at = el.sort_rows(chosen, held, 4, rows, TILE)
    at.pop("held_rows")
    gathered = (k, t, d) if axis == 0 else (t, k, d)
    assert at.slot_axis == axis and at["pair_row"].shape == gathered[:2]
    y = jnp.ones((rows, d), jnp.bfloat16)
    if move == "rows_to_tokens":
        jaxpr = jax.make_jaxpr(el.rows_to_tokens)(y, at)
    else:
        jaxpr = jax.make_jaxpr(lambda g, at: jax.vjp(
            lambda x: el.tokens_to_rows(x, at),
            jnp.ones((t, d), jnp.bfloat16))[1](g)[0])(y, at)
    assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [(t, d)]
    eqns = list(_equations(jaxpr.jaxpr))
    sums = [e for e in eqns if e.primitive.name == "reduce_sum"]
    assert [(e.invars[0].aval.shape, e.invars[0].aval.dtype,
             tuple(e.params["axes"])) for e in sums] == [
        (gathered, jnp.float32, (axis,))]
    gathers = [e for e in eqns if e.primitive.name == "gather"
               and e.invars[0].aval.shape == (rows, d)]
    assert [e.outvars[0].aval.shape for e in gathers] in (
        [gathered], [(k * t, d)])
    moved = [(e.primitive.name, e.invars[0].aval.shape,
              e.outvars[0].aval.shape) for e in eqns
             if e.primitive.name in ("transpose", "reshape", "copy",
                                     "broadcast_in_dim", "squeeze")
             and e.invars[0].aval.size == t * k * d]
    assert all(m == ("reshape", (k * t, d), gathered) for m in moved), moved
