"""ISSUE 68: the routed experts' first half (both products, the activation,
the rows' weights) is ONE kernel and its backward one more
(``ops/expert_layer.py``: ``expert_hidden``). Held here, on the CPU's
interpret route, to what it replaced: ``_gated`` / ``_relu2`` over
``grouped_matmul``, which the shared expert still runs over ``jnp.dot``.

* the whole layer against the layer composed by hand on the unfused route:
  output, held rows and every gradient, to the bit in float32;
* the pair alone on row tables written down by hand, at a filling that
  leaves whole tiles past ``n_used`` and at one that leaves none (a routing
  cannot fill the worst-case buffer: an expert's last tile is ragged), with
  all of F one block and with F in two;
* in bfloat16, where the unfused route rounds after every operation of the
  activation and the kernel once;
* the block of F each benchmark cell's shape gets.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import expert_layer as el
from test_expert_layer_padding import (_assert_equal, _both_ways, _by_hand,
                                       _layer_inputs, _routed)

TILE = 8


@pytest.mark.parametrize("routing", ["drawn", "every"])
@pytest.mark.parametrize("top_k,held,offset", [(22, 8, 0), (6, 16, 8)],
                         ids=["compacted", "uncompacted"])
@pytest.mark.parametrize("expert,latent", [
    ("relu2", 32), ("relu2", 0), ("swiglu", 32), ("swiglu", 0)])
def test_the_layer_is_its_unfused_self_to_the_bit(expert, latent, top_k, held,
                                                  offset, routing):
    """``held_expert_layer`` (the kernel pair) against the layer composed by
    hand from its own moves and ``_mlp`` over ``grouped_matmul`` (the route it
    had): in float32 the pair makes the same sums of the same terms, so
    output, held rows and EVERY gradient are equal with a limit of 0.0."""
    e = 32
    x, p = _layer_inputs(expert, latent, e, held)
    p = _routed(p, routing, e, held, offset)
    kw = dict(top_k=top_k, held=held, offset=offset, expert=expert,
              score="sigmoid")
    layer, unfused = _both_ways(
        x, p, lambda x, p: _by_hand(x, p, scale=2.5, **kw), **kw)
    _assert_equal(layer, unfused, routing)


def _tables(n_tiles, held, fill):
    """``tile_expert``, ``n_used`` of a buffer whose leading tiles are used:
    ``some`` one tile an expert but two for the first (whole tiles past
    ``n_used``), ``all`` every tile of the buffer, none past it."""
    used = {"some": held + 1, "all": n_tiles}[fill]
    expert = np.minimum(np.maximum(np.arange(n_tiles) - 1, 0), held - 1) \
        if fill == "some" else np.arange(n_tiles) * held // n_tiles
    return (jnp.asarray(expert, jnp.int32), jnp.asarray([used], jnp.int32),
            used * TILE)


def _pair_inputs(expert, dtype, k=32, f=256, held=4, n_tiles=12):
    keys = iter(jax.random.split(jax.random.PRNGKey(68), 8))
    draw = lambda *s: 0.3 * jax.random.normal(next(keys), s)    # noqa: E731
    rows = n_tiles * TILE
    p = {"e_gate": draw(held, k, f), "e_up": draw(held, k, f),
         "e_down": draw(held, f, k)}
    if expert == "relu2":
        del p["e_gate"]
    buf = jax.random.normal(next(keys), (rows, k)).astype(dtype)
    # padding rows: the weight 0 on a copy of a real row
    weight = jnp.where(jnp.arange(rows) % 4 == 3, 0.0,
                       jax.random.uniform(next(keys), (rows,)))
    cot = jax.random.normal(next(keys), (rows, k)).astype(dtype)
    return p, buf, weight, cot


def _fused_and_unfused(expert, p, buf, weight, cot, tables):
    """[y, dx, the weights' gradients by name, the rows' weights' gradient]
    of the layer's ``_held_mlp`` and of ``_mlp`` over ``grouped_matmul``,
    the rows of the used tiles alone (nothing writes or reads the others:
    the cotangent coming in is 0 there, as ``rows_to_tokens``' vjp leaves
    them out)."""
    te, nu, used = tables
    at = {"tile_expert": te, "n_used": nu}
    routes = (
        lambda b, p, w: el._held_mlp(expert, b, p, w, at, TILE),
        lambda b, p, w: el._mlp(
            expert, b, p, "e",
            lambda a, m: el.grouped_matmul(a, m, te, nu, TILE), w))
    out = []
    with jax.default_matmul_precision("highest"):
        for fn in routes:
            y, pull = jax.vjp(fn, buf, p, weight)
            mask = (jnp.arange(y.shape[0]) < used)[:, None]
            dx, dp, dweight = pull(jnp.where(mask, cot, 0).astype(y.dtype))
            out.append([("y", y[:used]), ("dx", dx[:used])] + sorted(
                dp.items()) + [("row_weight", dweight[:used])])
    return out


@pytest.mark.parametrize("blocks", [1, 2])
@pytest.mark.parametrize("fill", ["some", "all"])
@pytest.mark.parametrize("expert", ["swiglu", "relu2"])
def test_the_pair_is_the_unfused_first_half(expert, fill, blocks,
                                            monkeypatch):
    """Output, dx, dW_gate, dW_up, dW_down and the rows' weights' gradient of
    the pair against ``_gated`` / ``_relu2`` over ``grouped_matmul``, in
    float32. All of F one block: equal to the bit. F in two blocks (a fast
    memory too small for the expert's matrices beside their second buffers,
    here by a smaller ``VMEM_BYTES``): dx and the rows' weights' gradient
    are sums over F made a block at a time, in another order, and equal to
    float32's rounding."""
    p, buf, weight, cot = _pair_inputs(expert, jnp.float32)
    mats = len(p) - 1
    if blocks == 2:
        monkeypatch.setattr(el, "VMEM_BYTES", 160_000)
    assert el.hidden_block(32, 256, mats, 4, TILE) == 256 // blocks
    fused, unfused = _fused_and_unfused(expert, p, buf, weight, cot,
                                        _tables(12, 4, fill))
    assert [n for n, _ in fused] == ["y", "dx"] + sorted(p) + ["row_weight"]
    for (name, a), (_, b) in zip(fused, unfused):
        assert np.isfinite(np.asarray(a)).all() and float(
            jnp.abs(b).max()) > 0, name
        limit = 0.0 if blocks == 1 else 1e-6 * float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= limit, name


@pytest.mark.parametrize("expert", ["swiglu", "relu2"])
def test_the_pair_in_bfloat16_rounds_no_more_than_the_unfused(expert):
    """In the buffer's bfloat16 the pair rounds where the grouped product
    rounds (the pre-activations, h, their cotangents, dx) and keeps float32
    between. ``relu2``: the ReLU is exact in bfloat16, both routes square
    in float32 and dx is ONE transposed product either way, so everything is
    equal to the bit. ``swiglu``: the unfused route rounds SiLU and its
    product with the up product apart and dx's two products before it adds
    them; within bfloat16's rounding of the largest entry."""
    p, buf, weight, cot = _pair_inputs(expert, jnp.bfloat16)
    fused, unfused = _fused_and_unfused(expert, p, buf, weight, cot,
                                        _tables(12, 4, "some"))
    for (name, a), (_, b) in zip(fused, unfused):
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        assert np.isfinite(a).all(), name
        limit = 0.0 if expert == "relu2" else 2.0 ** -6 * np.abs(b).max()
        assert np.abs(a - b).max() <= limit, name


@pytest.mark.parametrize("cell,k,f,mats,block", [
    ("nemotron3super_train_s8192", 1024, 2688, 1, 2688),
    ("keyevl2_train_s16384", 2048, 768, 2, 768),
    ("lfm2moe_train_s8192", 2048, 1792, 2, 1792),
    ("kanana2_train_s8192", 2048, 768, 2, 768),
    ("qwen3next_train_s8192", 2048, 512, 2, 512),
    ("kimilinear_train_s8192", 2304, 1024, 2, 1024),
    ("xing4_train_s4096", 3584, 1024, 2, 1024),
    ("twice_lfm2moes_f", 2048, 3584, 2, 1792),
    ("four_times_xing4s_f", 3584, 4096, 2, 1024)])
def test_the_block_of_f_at_the_benchmark_cells_shapes(cell, k, f, mats,
                                                      block):
    """What ``hidden_block`` chooses from the widths alone (bfloat16, tiles
    of ``ROW_TILE``): all of F where the expert's matrices, their second
    buffers and a tile's rows fit the kernels' fast memory, which is every
    cell's case, the largest whole share of F in whole lanes where they do
    not (an expert twice and four times as wide as the widest two)."""
    assert el.hidden_block(k, f, mats, 2) == block
    assert f % block == 0 and block % 128 == 0


def test_a_width_no_lane_tile_divides_is_one_block():
    """F that is no multiple of 128 (the tests' own sizes) is never cut."""
    assert el.hidden_block(32, 48, 2, 4, TILE) == 48
    assert el.hidden_block(1 << 20, 200, 2, 4, TILE) == 200
