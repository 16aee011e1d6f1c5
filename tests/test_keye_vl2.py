"""ISSUE 59: Keye-VL-2.0's language model (grouped-query attention over the
keys a learned indexer selects for each query, positions of three
components, a softmax-routed expert layer WITHOUT a shared expert of which
the chip may hold a share; ``models/keye_vl2.py`` on ``models/stack.py``,
``ops/sparse_attention.py``) against the benchmark's plain reference
(``benchmark/reference/keye_vl2.py``: ``lax.top_k`` for the selection), on
seeded random weights at a small size.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums and the interpreted kernels' online
softmax. Read on this seed: the loss by 4.8e-7 (one float32 step at 7.5),
the logits by 1.1e-5 at worst, the gradients by at most 1.7e-6 of a
parameter's largest entry. The limits are ``test_qwen3_next.py``'s: 5e-6 on
the loss, 1e-4 on the logits, 5e-5 of the largest entry on each gradient.
Against that (``test_a_departed_reference_would_fail``) each of: the relu of
the index scores dropped, the index key's LayerNorm dropped, ties taken to
the HIGHER position, and ``topk`` - 1 keys a query moves the reference's own
loss by more than fifty times the limit.

The fixture holds a SHARE (experts 2-5 of 8). S = 256 with a selection of
64: three quarters of the queries select. With 4 index heads a sixteenth of
all pairs score exactly 0 (every relu shut), and 0 is the median of the
scores, so for the queries near 128 the 64th largest score is a TIE and the
tie rule decides their sets.
"""
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import KeyeVL2, KeyeVL2Config
from ray_tpu.ops import expert_layer as el
from ray_tpu.ops import mha_reference

sa = importlib.import_module("ray_tpu.ops.sparse_attention")  # the module
ref = importlib.import_module("benchmark.reference.keye_vl2")

# init_std 0.2: with 0.02 a tiny model's sublayers are rounding beside the
# residual and nothing they do would show in the loss
SHARE = dict(experts_held=4, expert_offset=2, init_std=0.2,
             dtype=jnp.float32)
LOSS_LIMIT = 5e-6     # absolute (module docstring)
LOGIT_LIMIT = 1e-4
GRAD_LIMIT = 5e-5     # of the gradient's largest entry
S = 256


def _ref_logits(model, params, tokens, positions=None, **patch):
    kw = dict(ref.model_kwargs(model.config), **patch)
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(params, tokens, jnp.float32, positions=positions, **kw)
        return ref.head(params, h, jnp.float32)


def _nll(logits, tokens):
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0])


def _init(model, seed=0):
    """``model.init`` with the norms' gains off one and the index key's
    LayerNorm bias off zero, so that each is seen."""
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), len(params)))
    return {n: v + 0.3 * jax.random.normal(next(keys), v.shape)
            if "norm" in n.split(".")[-1] or ".i_kn_" in n else v
            for n, v in params.items()}


@pytest.fixture(scope="module")
def tiny():
    """(model, params, tokens, the program's and the reference's (loss,
    logits, gradients)): one compiled program each."""
    model = KeyeVL2(KeyeVL2Config.tiny(**SHARE))
    params = _init(model)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              model.config.vocab_size)

    def both(logits_of):
        def fn(p):
            logits = logits_of(p)
            return _nll(logits, toks), logits
        (loss, logits), grads = jax.jit(
            jax.value_and_grad(fn, has_aux=True))(params)
        return loss, logits, grads

    with jax.default_matmul_precision("highest"):
        got = both(lambda p: model.apply(p, toks))
    return model, params, toks, got, both(
        lambda p: _ref_logits(model, p, toks))


def test_logits_and_loss_equal_the_references(tiny):
    model, params, toks, (loss, logits, _), (ref_loss, want, _) = tiny
    assert sa.CALL_COUNTS["masked_flash"] >= 1
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(logits - want).max()) < LOGIT_LIMIT
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def test_gradients_equal_the_references_and_the_indexers_are_zero(tiny):
    _, params, _, (_, _, grads), (_, _, ref_grads) = tiny
    assert set(grads) == set(params)
    seen = 0
    for name, g in grads.items():
        want = np.asarray(ref_grads[name])
        if ".i_" in name:     # no gradient passes the selection: EXACTLY 0
            assert not np.asarray(g).any() and not want.any(), name
            seen += 1
            continue
        top = np.abs(want).max()
        assert top > 0, name
        assert np.abs(np.asarray(g) - want).max() < GRAD_LIMIT * top, name
    assert seen == 5          # W_qI, W_kI, W_w, the LayerNorm's two


def test_unequal_position_components_equal_the_reference(tiny):
    """A patch of an image: the temporal position stands, height and width
    run. One row, the forward."""
    model, params, toks, (_, logits, _) = tiny[:4]
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    pos = jnp.stack([jnp.arange(S)[None],
                     jax.random.randint(k1, (1, S), 0, 48),
                     jax.random.randint(k2, (1, S), 0, 48)])
    every = jnp.broadcast_to(jnp.arange(S)[None, None], (3, 1, S))
    apply = jax.jit(model.apply)
    with jax.default_matmul_precision("highest"):
        got, same = apply(params, toks[:1], pos), apply(params, toks[:1],
                                                        every)
    want = jax.jit(lambda p: _ref_logits(model, p, toks[:1], pos))(params)
    assert float(jnp.abs(got - want).max()) < LOGIT_LIMIT
    # the components do something, and None (the fixture's) is three times
    # arange
    assert float(jnp.abs(got - logits[:1]).max()) > 100 * LOGIT_LIMIT
    assert float(jnp.abs(same - logits[:1]).max()) < LOGIT_LIMIT / 10


@pytest.fixture(scope="module")
def one_row(tiny):
    """One row of the fixture's batch and the reference's loss on it (the
    departed references run eagerly: a row is enough to see them)."""
    toks, want = tiny[2], tiny[4][1]
    return toks[:1], float(_nll(want[:1], toks[:1]))


@pytest.mark.parametrize("fault", ["no_relu", "no_layernorm",
                                   "ties_to_the_higher", "topk_less_one"])
def test_a_departed_reference_would_fail(tiny, one_row, monkeypatch, fault):
    """The limits are tight enough to see each departure: the reference,
    departed, moves its own loss by more than fifty times LOSS_LIMIT."""
    model, params = tiny[:2]
    toks, ref_loss = one_row
    patch = {}
    if fault == "no_relu":
        monkeypatch.setattr(ref, "_relu", lambda x: x)
    elif fault == "no_layernorm":
        monkeypatch.setattr(ref, "index_key_norm", lambda x, w, b, eps: x)
    elif fault == "ties_to_the_higher":
        monkeypatch.setattr(
            ref, "top_keys", lambda scores, k: scores.shape[-1] - 1
            - jax.lax.top_k(scores[..., ::-1], k)[1])
    elif fault == "topk_less_one":
        patch["topk"] = model.config.index_topk - 1
    got = _nll(_ref_logits(model, params, toks, **patch), toks)
    assert abs(float(got) - ref_loss) > 50 * LOSS_LIMIT, fault


# -- the selection ---------------------------------------------------------


def _top_k_mask(scores, topk, row0=0):
    """``lax.top_k``'s set of the causal part of each row, as a mask."""
    c, s = scores.shape
    seen = (row0 + np.arange(c))[:, None] >= np.arange(s)[None, :]
    masked = jnp.where(seen, scores, -jnp.inf)
    chosen = np.asarray(jax.lax.top_k(masked, min(topk, s))[1])
    want = np.zeros((c, s), bool)
    np.put_along_axis(want, chosen, True, axis=1)
    return want & seen


@pytest.mark.parametrize("scores_of", ["normal", "tied", "signed_zeros"])
def test_select_is_top_ks_set(scores_of):
    """The bit-by-bit search for the k-th largest score and the ties' prefix
    count give exactly the set ``lax.top_k`` takes: scores all unlike, scores
    in steps of a quarter (dozens of ties at every threshold), and +0.0
    beside -0.0 at the threshold (-0.0 the lower in ``lax.top_k``'s total
    order, no tie)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 256))
    if scores_of == "tied":
        x = jnp.round(x * 4) / 4
    elif scores_of == "signed_zeros":
        x = jnp.where(jnp.abs(x) < 0.8,
                      jnp.where(x < 0, -0.0, 0.0), x)
    for row0, topk in ((192, 32), (0, 32), (100, 200)):
        got = np.asarray(sa.select(x, topk, row0)).astype(bool)
        assert (got == _top_k_mask(x, topk, row0)).all(), (row0, topk)
        causal = np.minimum(row0 + np.arange(64) + 1, 256)
        assert (got.sum(1) == np.minimum(causal, topk)).all()


def test_selected_sets_equal_the_references(tiny):
    """The program's mask of a layer's index scores is the reference's
    ``top_keys`` of its own, as sets, in every query; some query's threshold
    is a tie (module docstring)."""
    model, params, toks = tiny[:3]
    c = model.config
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("0.")}
    x = params["wte"][toks]
    xn = ref._rmsnorm(x, lp["norm"], c.rms_eps)
    pos = jnp.broadcast_to(jnp.arange(S)[None, None], (3, 2, S))
    def both(xn, lp):
        qi, ki, wi = ref.index_parts(xn, lp, pos, index_heads=c.index_heads,
                                     rope_base=c.rope_base, eps=c.rms_eps)
        return sa.selection_mask(
            qi, ki, wi, topk=c.index_topk,
            q_chunk=c.index_q_chunk), ref.index_scores(qi, ki, wi)

    with jax.default_matmul_precision("highest"):
        got, scores = map(np.asarray, jax.jit(both)(xn, lp))
    tied = 0
    for b in range(2):
        assert (got[b].astype(bool)
                == _top_k_mask(scores[b], c.index_topk)).all()
        s = np.where(np.tri(S, dtype=bool), np.asarray(scores[b]), -np.inf)
        kth = np.sort(s, axis=1)[:, -c.index_topk]
        tied += int(((s == kth[:, None]).sum(1) > 1)[c.index_topk:].sum())
    assert tied > 0
    assert got.sum() == 2 * sa.selected_pairs(S, c.index_topk)


def _qkv(key, b, s, h, kv, d=128, hi=4, di=64):
    ks = jax.random.split(key, 6)
    n = jax.random.normal
    return (n(ks[0], (b, s, h, d)), n(ks[1], (b, s, kv, d)),
            n(ks[2], (b, s, kv, d)), n(ks[3], (b, s, hi, di)),
            n(ks[4], (b, s, di)), n(ks[5], (b, s, hi)))


@pytest.mark.parametrize("kv, group, block_q, block_k", [
    (1, 2, 128, 256), (1, 2, 128, 128), (2, 2, 256, 128), (1, 8, 128, 128),
    (2, 8, 128, 256)])
def test_the_kernels_equal_the_plain_masked_attention_over_several_blocks(
        kv, group, block_q, block_k):
    """S 512 in several block shapes: blocks above the diagonal skipped
    (their outputs' index holding still), the group's heads in one program,
    dk and dv summed over a group's query heads and over ALL query blocks
    and written in the last one's row alone, key blocks whose first
    computed query block is not block 0, a second key/value head that
    finds the accumulators zeroed again; o and the three gradients."""
    q, k, v, qi, ki, wi = _qkv(jax.random.PRNGKey(0), 1, 512, kv * group, kv)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    mask = sa.selection_mask(qi, ki, wi, topk=64, q_chunk=128)

    def kernels(q, k, v):
        return sa.sparse_attention(q, k, v, qi, ki, wi, topk=64, q_chunk=128,
                                   block_q=block_q, block_k=block_k)

    def plain(q, k, v):
        return sa.masked_attention_reference(q, k, v, mask, 128 ** -0.5)

    run = lambda f: jax.jit(jax.value_and_grad(               # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2)))(q, k, v)
    (got, grads), (want, ref_grads) = run(kernels), run(plain)
    assert abs(float(got - want)) < 1e-3 * abs(float(want))
    for g, r in zip(grads, ref_grads):
        assert float(jnp.abs(g - r).max()) < 2e-5 * float(jnp.abs(r).max())


def test_a_row_too_long_for_the_backwards_accumulators_is_refused():
    """dk and dv of a key/value head's WHOLE sequence stay in VMEM through
    the backward: a call they do not fit beside the tiles is refused by
    name at trace time, with the bytes it would keep (shapes only)."""
    s, h, kv = 32768, 32, 4
    shapes = [jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in (
        (1, s, h, 128), (1, s, kv, 128), (1, s, kv, 128), (1, s, 16, 64),
        (1, s, 64), (1, s, 16))]
    need = sa._bwd_vmem(s, 1024, 1024, h // kv, 128, 2)
    assert need > sa.VMEM_BYTES
    with pytest.raises(ValueError, match=rf"sparse_attention at S={s}: the "
                       rf"backward keeps {need} bytes in VMEM"):
        jax.eval_shape(functools.partial(sa.sparse_attention, topk=2048),
                       *shapes)
    # the benchmark cell's row fits, and so does this one in smaller blocks
    assert sa._bwd_vmem(16384, 1024, 1024, 8, 128, 2) < sa.VMEM_BYTES
    out = jax.eval_shape(functools.partial(
        sa.sparse_attention, topk=2048, block_q=512, block_k=512), *shapes)
    assert out.shape == (1, s, h, 128)


def test_no_more_keys_than_topk_is_causal_attention():
    """S <= topk: every causal key is selected, the indexer decides nothing
    and the call takes the flash routes the other models take."""
    q, k, v, qi, ki, wi = _qkv(jax.random.PRNGKey(2), 1, 128, 4, 2)
    before = sa.CALL_COUNTS["causal_flash"]
    got = jax.jit(sa.sparse_attention, static_argnames=("topk",))(
        q, k, v, qi, ki, wi, topk=128)
    assert sa.CALL_COUNTS["causal_flash"] == before + 1
    want = mha_reference(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                         causal=True)
    assert float(jnp.abs(got - want).max()) < 2e-5


# -- the share -------------------------------------------------------------


def test_the_shares_of_one_layer_add_up_to_the_uncut_references():
    """Two chips hold 4 of 8 experts each and all of the attention: the
    attention counted once plus the two partial expert sums is the uncut
    reference's layer."""
    tiny_of = lambda **kw: KeyeVL2(KeyeVL2Config.tiny(         # noqa: E731
        n_layer=1, init_std=0.2, dtype=jnp.float32, **kw))
    whole = tiny_of()
    c = whole.config
    params = _init(whole, seed=3)
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("0.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 128, c.d_model))
    pos = jnp.broadcast_to(jnp.arange(128)[None, None], (3, 1, 128))

    def reference(x, lp):
        kw = ref.model_kwargs(c)
        y = x + ref.sparse_attention(
            ref._rmsnorm(x, lp["norm"], c.rms_eps), lp, pos,
            **{n: kw[n] for n in ("n_head", "n_kv_head", "rope_base",
                                  "mrope_section", "index_heads", "topk",
                                  "eps")})
        return y + ref.routed_experts(
            ref._rmsnorm(y, lp["mlp_norm"], c.rms_eps), lp, top_k=c.top_k)

    def shares(x, lp):
        after = whole._attn(x, lp, *whole._angles(None, 128))
        out, rows = after, 0
        for offset in (0, 4):
            mine = dict(lp, **{n: lp[n][offset:offset + 4]
                               for n in ("e_gate", "e_up", "e_down")})
            y, held, _ = tiny_of(experts_held=4,
                                 expert_offset=offset)._moe_ffn(after, mine)
            out, rows = out + (y - after), rows + held
        return out, rows

    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(x, lp)
        got, rows = jax.jit(shares)(x, lp)
    assert int(rows) == 128 * c.top_k     # every pair on exactly one chip
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("score", ["softmax"])
def test_a_layer_without_a_shared_expert_is_its_routed_part(score):
    """``held_expert_layer`` with no ``s_up``: nothing of the shared path,
    the routed part alone, against a dense one-hot computation, forward and
    every gradient; the event says so."""
    from ray_tpu.perf import get_recorder

    t, d, f, e, held, off, k = 64, 32, 48, 8, 4, 2, 3
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    p = {"w_router": jax.random.normal(ks[0], (d, e)),
         "e_gate": jax.random.normal(ks[1], (held, d, f)) * 0.3,
         "e_up": jax.random.normal(ks[2], (held, d, f)) * 0.3,
         "e_down": jax.random.normal(ks[3], (held, f, d)) * 0.3}
    if score == "sigmoid":
        p["router_bias"] = jnp.zeros((e,))
    x = jax.random.normal(ks[4], (t, d))
    w = jax.random.normal(ks[5], (t, d))

    def layer(x, p):
        return el.held_expert_layer(x, p, experts_held=held,
                                    expert_offset=off, top_k=k,
                                    routed_scale=1.0, score=score)[0]

    def dense(x, p):
        logits = x @ p["w_router"]
        s = jax.nn.softmax(logits, -1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        top, chosen = jax.lax.top_k(s, k)
        weight = jnp.sum(jax.nn.one_hot(chosen, e)
                         * (top / top.sum(-1, keepdims=True))[..., None], 1)
        every = jnp.einsum("etf,efd->etd",
                           jax.nn.silu(jnp.einsum("td,edf->etf", x,
                                                  p["e_gate"]))
                           * jnp.einsum("td,edf->etf", x, p["e_up"]),
                           p["e_down"])
        return jnp.einsum("te,etd->td", weight[:, off:off + held], every)

    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = jax.jit(layer).lower(x, p).as_text(debug_info=True)
        event = [ev for ev in rec.snapshot()
                 if ev["kind"] == "rtpu.ops.expert_layer"][-1]
    finally:
        rec.enabled = was
    assert event["data"]["shared"] is False
    assert re.search(r"[/(]router[/)]", text)     # scopes are in the text
    assert not re.search(r"[/(]shared_expert[/)]", text)
    run = lambda f: jax.jit(jax.value_and_grad(                # noqa: E731
        lambda x, p: jnp.sum(f(x, p) * w), argnums=(0, 1)))(x, p)
    with jax.default_matmul_precision("highest"):
        (got, (gx, gp)), (want, (rx, rp)) = run(layer), run(dense)
    assert abs(float(got - want)) < 1e-4 * max(1.0, abs(float(want)))
    assert float(jnp.abs(gx - rx).max()) < 1e-4 * float(jnp.abs(rx).max())
    for n in rp:
        if n == "router_bias":
            continue
        assert float(jnp.abs(gp[n] - rp[n]).max()) \
            < 1e-4 * float(jnp.abs(rp[n]).max()), n


def test_parameter_count_and_routing_stats(tiny):
    model, params, toks = tiny[:3]
    c = model.config
    sizes = {"hidden_size": c.d_model, "num_attention_heads": c.n_head,
             "num_key_value_heads": c.n_kv_head, "head_dim": c.head_dim,
             "indexer_num_heads": c.index_heads,
             "indexer_head_dim": c.index_dim,
             "moe_intermediate_size": c.d_expert,
             "num_experts": c.n_routed_experts,
             "experts_held": c.n_experts_held,
             "num_hidden_layers": c.n_layer}
    assert model.num_params() == ref.num_params(sizes, c.padded_vocab) \
        == sum(int(np.prod(v.shape)) for v in params.values())
    rows = np.asarray(jax.jit(model.routing_stats)(params, toks))
    assert rows.shape == (c.n_layer,)
    assert (rows > 0).all() and (rows < toks.size * c.top_k).all()
    # the cut of the benchmark: 5 layers, 16 of 128 experts, 18 992 ids
    cut = KeyeVL2(KeyeVL2Config.keye_vl2_30b_a3b(
        n_layer=5, experts_held=16, vocab_size=18992, max_seq=16384))
    assert cut.num_params() == 562_618_240
    assert cut.config.share() == {"experts": [16, 128], "expert_offset": 0,
                                  "vocab_rows": 19072, "layers": 5}
    with pytest.raises(ValueError):
        KeyeVL2Config.tiny(experts_held=4, expert_offset=6)


@pytest.mark.parametrize("routers, want", [("level", 1.0),
                                           ("one_choice", 8 / 3)])
def test_the_balancing_term_is_one_when_level_and_e_over_k_collapsed(
        routers, want):
    """E sum_e f_e P_e a sequence: 1 where every expert is as likely as
    any other, E / k where every token makes the same k choices with
    certainty; program and reference alike."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2 * 64, 16))
    w = jnp.zeros((16, 8))
    if routers == "one_choice":     # a constant channel decides
        x = x.at[:, 0].set(1.0)
        w = w.at[0, :3].set(50.0)
    got = el.balance_term(x, w, top_k=3, groups=2)
    theirs = ref.router_balance(x.reshape(2, 64, 16), {"w_router": w},
                                top_k=3)
    assert got.shape == theirs.shape == (2,)
    assert np.allclose(np.asarray(got), want, rtol=1e-5)
    assert np.allclose(np.asarray(theirs), want, rtol=1e-5)


@pytest.fixture(scope="module")
def balanced():
    """A model whose loss holds the routers' balancing term, times 0.5 so
    that it shows: (model, params, tokens, the program's (loss, gradients)
    by the family file's ``objective``, the reference's by the mean of
    its ``losses``)."""
    from benchmark.lib import spec

    model = KeyeVL2(KeyeVL2Config.tiny(router_aux_coef=0.5, **SHARE))
    params = _init(model, seed=5)
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 128), 0,
                              model.config.vocab_size)
    kw = ref.model_kwargs(model.config)
    assert kw["router_aux_coef"] == 0.5
    mine = spec.objective_of(spec.load_family("keye_vl2"), ref)(model)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(mine))(params, toks)
        want = jax.jit(jax.value_and_grad(lambda p: jnp.mean(
            ref.losses(p, toks, jnp.float32, **kw))))(params)
        bare = jax.jit(jax.value_and_grad(lambda p: jnp.mean(ref.losses(
            p, toks, jnp.float32, **dict(kw, router_aux_coef=0.0)))))(params)
    return model, params, toks, got, want, bare


def test_the_objective_equals_the_references_losses(balanced):
    model, params, toks, (loss, _), (want, _), (bare, _) = balanced
    assert abs(float(loss) - float(want)) < LOSS_LIMIT
    # the term is there: two layers of at least 1 each, times 0.5; a
    # reference that left it out (``router_aux_coef`` 0) is far off
    assert float(want) - float(bare) > 1.0 > 1e5 * LOSS_LIMIT
    # and a model without the coefficient traces none of it
    plain = KeyeVL2(KeyeVL2Config.tiny(**SHARE))
    assert jax.eval_shape(plain.forward, params, toks)[1] is None
    assert jax.eval_shape(lambda p, t: model.forward(p, t, balance=True),
                          params, toks)[1].shape == (2,)


def test_the_objectives_gradients_equal_the_references(balanced):
    """Every gradient with the balancing term in the loss; the routers' are
    where it acts (a quarter or more of their gradient is its own)."""
    _, params, _, (_, grads), (_, ref_grads), (_, bare) = balanced
    for name, g in grads.items():
        want = np.asarray(ref_grads[name])
        if ".i_" in name:
            assert not np.asarray(g).any() and not want.any(), name
            continue
        top = np.abs(want).max()
        assert np.abs(np.asarray(g) - want).max() < GRAD_LIMIT * top, name
    name = "0.attn_moe.w_router"
    moved = np.abs(np.asarray(ref_grads[name]) - np.asarray(bare[name])).max()
    assert moved > 0.25 * np.abs(np.asarray(ref_grads[name])).max()
