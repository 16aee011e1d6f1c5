"""ISSUE 64: an LFM2-MoE shaped model (a double-gated short convolution or
grouped-query attention as the operator, a dense MLP then sigmoid-routed
experts with a selection bias and no shared expert, of which the chip may
hold a share, the head tied to the embedding; ``models/lfm2_moe.py`` on
``models/stack.py``, ``ops/short_conv.py``) against the benchmark's plain
reference (``benchmark/reference/lfm2_moe.py``: the convolution as three
shifted sums written out), on seeded random weights at a small size.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums and the interpreted kernels' online
softmax. Read on this seed: the loss by 9.5e-7 (one float32 step at 10),
the logits by 9.5e-6 at worst, the gradients by at most 2.2e-6 of a
parameter's largest entry. The limits are ``test_qwen3_next.py``'s: 5e-6 on
the loss, 1e-4 on the logits, 5e-5 of the largest entry on each gradient.
Against that (``test_a_departed_reference_would_fail``) each of: SiLU on the
taps, ``B`` and ``C`` swapped, the taps reversed, the selection bias inside
the weights and no norm on q and k moves the reference's own loss by more
than fifty times the limit.

The fixture holds a SHARE (experts 2-5 of 8), its norm gains are off one
and its selection bias off zero so that each is seen, and its loss holds
the routers' balancing term times 0.5 so that it shows.
"""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Lfm2Moe, Lfm2MoeConfig
from ray_tpu.ops import expert_layer as el
from ray_tpu.ops import short_conv as sc

ref = importlib.import_module("benchmark.reference.lfm2_moe")

# init_std 0.2: with 0.02 a tiny model's sublayers are rounding beside the
# residual and nothing they do would show in the loss
SHARE = dict(experts_held=4, expert_offset=2, init_std=0.2,
             dtype=jnp.float32)
LOSS_LIMIT = 5e-6     # absolute (module docstring)
LOGIT_LIMIT = 1e-4
GRAD_LIMIT = 5e-5     # of the gradient's largest entry
S = 128


def _nll(logits, tokens):
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0])


def _ref_logits(model, params, tokens):
    kw = ref.model_kwargs(model.config)
    with jax.default_matmul_precision("highest"):
        return ref.head(params, ref.hidden(params, tokens, jnp.float32, **kw),
                        jnp.float32)


def _init(model, seed=0):
    """``model.init`` with the norms' gains off one and the selection bias
    off zero, so that each is seen."""
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), len(params)))
    return {n: v + 0.3 * jax.random.normal(next(keys), v.shape)
            if "norm" in n.split(".")[-1] or n.endswith("router_bias") else v
            for n, v in params.items()}


@pytest.fixture(scope="module")
def tiny():
    """(model, params, tokens, the program's and the reference's (bare
    loss, logits, its gradients, objective, its gradients)): one compiled
    program each. The objective is the family file's; the reference's the
    mean of its ``losses``."""
    from benchmark.lib import spec

    model = Lfm2Moe(Lfm2MoeConfig.tiny(router_aux_coef=0.5, **SHARE))
    params = _init(model)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              model.config.vocab_size)
    kw = ref.model_kwargs(model.config)
    assert kw["router_aux_coef"] == 0.5
    objective = spec.objective_of(spec.load_family("lfm2_moe"), ref)(model)

    def both(logits_of, objective_of):
        def bare(p):
            logits = logits_of(p)
            return _nll(logits, toks), logits

        def fn(p):
            (loss, logits), grads = jax.value_and_grad(bare, has_aux=True)(p)
            return (loss, logits, grads) + jax.value_and_grad(objective_of)(p)
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(params)

    got = both(lambda p: model.apply(p, toks), lambda p: objective(p, toks))
    want = both(lambda p: _ref_logits(model, p, toks), lambda p: jnp.mean(
        ref.losses(p, toks, jnp.float32, **kw)))
    return model, params, toks, got, want


def test_the_stack_is_three_runs_and_both_operators_take_their_kernels(tiny):
    model, params, toks = tiny[:3]
    assert model.runs == [(("conv_mlp",), 1), (("attn_moe",), 1),
                          (("conv_moe",), 2)]
    before = dict(sc.PATH_COUNTS)
    jax.eval_shape(model.apply, params, toks)
    # a run's layer is traced once, the scanned run's too
    assert sc.PATH_COUNTS["kernel"] == before.get("kernel", 0) + 2
    assert sc.PATH_COUNTS["plain"] == before.get("plain", 0)


def test_logits_and_loss_equal_the_references(tiny):
    _, _, _, (loss, logits, *_), (ref_loss, want, *_) = tiny
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(logits - want).max()) < LOGIT_LIMIT
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def _same_gradients(params, grads, ref_grads):
    assert set(grads) == set(params)
    for name, g in grads.items():
        want = np.asarray(ref_grads[name])
        if name.endswith("router_bias"):    # a buffer: EXACTLY no gradient
            assert not np.asarray(g).any() and not want.any(), name
            continue
        top = np.abs(want).max()
        assert top > 0, name
        assert np.abs(np.asarray(g) - want).max() < GRAD_LIMIT * top, name


def test_gradients_equal_the_references_and_the_bias_has_none(tiny):
    _, params, _, got, want = tiny
    _same_gradients(params, got[2], want[2])


def test_the_objective_and_its_gradients_equal_the_references_losses(tiny):
    """The next-token loss plus 0.5 times three expert layers' balancing
    terms; the routers' gradients are where the term acts."""
    model, params, toks, got, want = tiny
    assert abs(float(got[3]) - float(want[3])) < LOSS_LIMIT
    # the term is there: three layers of about 1 each, times 0.5
    assert float(want[3]) - float(want[0]) > 1.0 > 1e5 * LOSS_LIMIT
    _same_gradients(params, got[4], want[4])
    name = "1.attn_moe.w_router"
    moved = np.abs(np.asarray(want[4][name]) - np.asarray(want[2][name])).max()
    assert moved > 0.25 * np.abs(np.asarray(want[4][name])).max()
    # a model without the coefficient traces none of it
    plain = Lfm2Moe(Lfm2MoeConfig.tiny(**SHARE))
    assert jax.eval_shape(plain.forward, params, toks)[1] is None
    assert jax.eval_shape(lambda p, t: model.forward(p, t, balance=True),
                          params, toks)[1].shape == (2,)


@pytest.mark.parametrize("fault", ["silu_on_the_taps", "b_and_c_swapped",
                                   "taps_reversed", "bias_in_the_weights",
                                   "no_qk_norm"])
def test_a_departed_reference_would_fail(tiny, monkeypatch, fault):
    """The limits are tight enough to see each departure: the reference,
    departed, moves its own loss (one row, eagerly) by more than fifty
    times LOSS_LIMIT."""
    model, params, toks, _, (_, want, *_) = tiny
    row = toks[:1]
    ref_loss = float(_nll(want[:1], row))
    if fault == "silu_on_the_taps":
        monkeypatch.setattr(ref, "tap_activation", jax.nn.silu)
    elif fault == "b_and_c_swapped":
        chunks = ref.in_chunks
        monkeypatch.setattr(ref, "in_chunks", lambda h: (
            chunks(h)[1], chunks(h)[0], chunks(h)[2]))
    elif fault == "taps_reversed":
        conv = ref.short_conv
        monkeypatch.setattr(ref, "short_conv", lambda z, w: conv(z, w[::-1]))
    elif fault == "bias_in_the_weights":
        monkeypatch.setattr(ref, "weight_scores", lambda s, b: s + b)
    elif fault == "no_qk_norm":
        monkeypatch.setattr(ref, "head_norm", lambda x, w, eps: x)
    got = _nll(_ref_logits(model, params, row), row)
    assert abs(float(got) - ref_loss) > 50 * LOSS_LIMIT, fault


@pytest.mark.parametrize("routers", ["level", "collapsed"])
def test_the_sigmoid_balancing_term_is_one_when_level_and_the_chosen_share(
        routers):
    """sum_e f_e P_e a sequence: 1 where every expert is as likely as any
    other; where every token makes the same k choices, E / k times the
    chosen experts' mean share of the scores (their sigmoids near 1, the
    others at a half: 3 / (3 + 5 / 2) of the sum, times 8 / 3); program and
    reference alike, the selection bias choosing without weighing."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2 * 64, 16))
    w, bias = jnp.zeros((16, 8)), jnp.zeros((8,))
    want = 1.0
    if routers == "collapsed":      # a constant channel decides
        x = x.at[:, 0].set(1.0)
        w = w.at[0, :3].set(50.0)
        want = 8 / 3 * 3 / 5.5
    got = el.balance_term(x, w, top_k=3, groups=2, score="sigmoid",
                          bias=bias)
    theirs = ref.router_balance(x.reshape(2, 64, 16),
                                {"w_router": w, "router_bias": bias}, top_k=3)
    assert got.shape == theirs.shape == (2,)
    assert np.allclose(np.asarray(got), want, rtol=1e-5)
    assert np.allclose(np.asarray(theirs), want, rtol=1e-5)
    if routers == "collapsed":
        # the bias moves the choice to experts 5-7 and the counts with it;
        # P_e is of the scores alone: their share is 3 / 2 of 5.5
        bias = bias.at[5:].set(10.0)
        moved = el.balance_term(x, w, top_k=3, groups=2, score="sigmoid",
                                bias=bias)
        assert np.allclose(np.asarray(moved), 8 / 3 * 1.5 / 5.5, rtol=1e-5)
        assert np.allclose(np.asarray(ref.router_balance(
            x.reshape(2, 64, 16), {"w_router": w, "router_bias": bias},
            top_k=3)), 8 / 3 * 1.5 / 5.5, rtol=1e-5)


def test_the_four_shares_of_one_layer_add_up_to_the_uncut_references():
    """Four chips hold 8 of 32 experts each (offsets 0, 8, 16, 24) and all
    of the conv operator: the operator counted once plus the four partial
    expert sums is the uncut reference's layer, and every (token, choice)
    pair lands on exactly one chip."""
    tiny_of = lambda **kw: Lfm2Moe(Lfm2MoeConfig.tiny(         # noqa: E731
        layer_types=("conv",), num_dense_layers=0, n_routed_experts=32,
        top_k=4, init_std=0.2, dtype=jnp.float32, **kw))
    whole = tiny_of()
    c = whole.config
    params = _init(whole, seed=3)
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("0.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 128, c.d_model))

    def reference(x, lp):
        y = x + ref.conv_operator(ref._rmsnorm(x, lp["norm"], c.rms_eps), lp)
        return y + ref.routed_experts(
            ref._rmsnorm(y, lp["mlp_norm"], c.rms_eps), lp, top_k=c.top_k,
            routed_scale=c.routed_scale)

    def shares(x, lp):
        after = whole._conv_operator(x, lp)
        out, rows = after, 0
        for offset in (0, 8, 16, 24):
            mine = dict(lp, **{n: lp[n][offset:offset + 8]
                               for n in ("e_gate", "e_up", "e_down")})
            y, held, _ = tiny_of(experts_held=8,
                                 expert_offset=offset)._moe(after, mine,
                                                            False)
            out, rows = out + (y - after), rows + held
        return out, rows

    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(x, lp)
        got, rows = jax.jit(shares)(x, lp)
    assert int(rows) == 128 * c.top_k     # every pair on exactly one chip
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_parameter_count_and_routing_stats(tiny):
    model, params, toks = tiny[:3]
    c = model.config
    sizes = {"hidden_size": c.d_model, "num_attention_heads": c.n_head,
             "num_key_value_heads": c.n_kv_head, "head_dim": c.head_dim,
             "conv_L_cache": c.conv_taps, "intermediate_size": c.d_ff,
             "moe_intermediate_size": c.d_expert,
             "num_experts": c.n_routed_experts,
             "experts_held": c.n_experts_held,
             "num_dense_layers": c.num_dense_layers,
             "layer_types": ["attention" if t == "full_attention" else t
                             for t in c.layer_types]}
    assert model.num_params() == ref.num_params(sizes, c.padded_vocab) \
        == sum(int(np.prod(v.shape)) for v in params.values())
    rows = np.asarray(jax.jit(model.routing_stats)(params, toks))
    assert rows.shape == (3,)               # the expert layers, in order
    assert (rows > 0).all() and (rows < toks.size * c.top_k).all()
    # the cut of the benchmark, by shapes alone: the configuration's file
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           "lfm2-8b-a1b-ep4.json")) as f:
        cfg = json.load(f)
    kw = dict(cfg["model"])
    assert (kw.pop("family"), kw.pop("preset")) == ("lfm2_moe", "lfm2_8b_a1b")
    cut = Lfm2Moe(Lfm2MoeConfig.lfm2_8b_a1b(**kw))
    assert cut.num_params() == cfg["n_params"] == 507_820_288 \
        == ref.num_params(cfg["sizes"], cut.config.padded_vocab)
    assert cut.runs == [(("conv_mlp",), 1), (("attn_moe",), 1),
                        (("conv_moe",), 3)]
    assert cut.config.share() == {
        "experts": [8, 32], "expert_offset": 0, "vocab_rows": 16384,
        "kinds": ["conv_mlp", "attn_moe", "conv_moe", "conv_moe",
                  "conv_moe"]}
    # the residual projections are drawn at the depth of the model these
    # layers are layers of: 24, not the 5 held
    shapes = cut._shapes()
    assert shapes["0.conv_mlp.w_out"][1] == pytest.approx(
        0.02 / (2 * 24) ** 0.5)
    assert shapes["2.conv_moe.e_down"][1] == shapes["1.attn_moe.w_o"][1] \
        == shapes["0.conv_mlp.w_down"][1] == shapes["0.conv_mlp.w_out"][1]
    # and the whole published model is the published 8.3 B
    whole = Lfm2Moe(Lfm2MoeConfig.lfm2_8b_a1b())
    assert 8.30e9 < whole.num_params() < 8.36e9
    with pytest.raises(ValueError):
        Lfm2MoeConfig.tiny(experts_held=4, expert_offset=6)
    with pytest.raises(ValueError):
        Lfm2MoeConfig.tiny(layer_types=("conv", "mamba"))
