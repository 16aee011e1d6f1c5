"""ISSUE 45: the DeepSeek-V3 shaped model with ``hc_mult`` residual streams
mixed by manifold-constrained hyper-connections, a query bottleneck and
YaRN positions, against the benchmark's plain reference
(``benchmark/reference/deepseek_v3_hc.py``: the one copy, the natural
[tokens, n, n] form with a Python loop of 20) on seeded random weights at a
small size: 4 streams, ``q_lora_rank`` 16, YaRN factor 64, 2 of 8 experts
held. Pallas kernels run in interpret mode here.

The maps of the comparison are made to matter: every α is set to 1 (the
model starts them at 0.01) and the hyper-connections' biases are
stretched by 2.5, so that the maps differ from token to token and the
Sinkhorn iterations are still moving at the twentieth.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums only (the program holds the coefficients
tokens-minor and sums the streams one by one; the reference contracts
[tokens, n, n] einsums). Read on this seed: the logits by 3.8e-6 of a
largest logit of 7.5, the loss by 4.8e-7 at 7.59 (one float32 step), each
gradient by at most 1.9e-5 of the parameter's largest entry
(``moe.hc_attn.alpha``, a sum over every token of either sign). The
limits: 2e-5 of the largest logit, 3e-6 on the loss, 1e-4 of the largest
entry on each gradient. 19 Sinkhorn iterations in place of 20 move the
logits by 5.0e-3 of the largest and ``moe.hc_attn.alpha``'s gradient by
9.2e-3 of its largest entry; coefficients rounded to bfloat16 move the
logits by 1.7e-2 and the gradients by up to 1.4e-2: both fail the limits
(the last two tests hold that).
"""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import DeepseekV3, DeepseekV3Config
from ray_tpu.ops import hyper_connection as hc
from ray_tpu.ops import rope_cache
from ray_tpu.ops.expert_layer import held_expert_layer
from ray_tpu.ops.layers import yarn_rope_cache, yarn_softmax_scale

ref = importlib.import_module("benchmark.reference.deepseek_v3_hc")
plain = importlib.import_module("benchmark.reference.deepseek_v3")

LOGIT_LIMIT = 2e-5    # of the largest logit (module docstring)
LOSS_LIMIT = 3e-6     # absolute, on a loss of 7.59
GRAD_LIMIT = 1e-4     # of the gradient's largest entry
YARN = dict(rope_base=10000.0, rope_factor=64.0, rope_original_max=32,
            rope_mscale=1.0, rope_mscale_all_dim=1.0)


def _config(**kw):
    base = dict(hc_mult=4, q_lora_rank=16, experts_held=2, init_std=0.2,
                dtype=jnp.float32, **YARN)
    base.update(kw)
    return DeepseekV3Config.tiny(**base)


def _tokens(vocab, seed=1, shape=(2, 128)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def _ref_logits(model, params, tokens, **kw):
    kwargs = dict(ref.model_kwargs(model.config), **kw)
    return ref.head(params, ref.hidden(params, tokens, jnp.float32, **kwargs),
                    jnp.float32)


def _next_token_loss(logits, tokens):
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0])


@pytest.fixture(scope="module")
def both():
    """(model, params, tokens, the program's logits, loss and gradients,
    the reference's)."""
    model = DeepseekV3(_config())
    params = model.init(jax.random.PRNGKey(0))
    params["moe.router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe.router_bias"].shape)
    for name in params:                     # maps that matter (docstring)
        if name.endswith(".alpha"):
            params[name] = jnp.ones_like(params[name])
        elif ".hc_" in name and name.endswith(".bias"):
            params[name] = 2.5 * params[name]
    toks = _tokens(model.config.vocab_size)
    mine = (jax.jit(model.apply)(params, toks),) + jax.jit(
        jax.value_and_grad(model.loss))(params, toks, jnp.roll(toks, -1, 1))
    theirs = (jax.jit(lambda p: _ref_logits(model, p, toks))(params),) \
        + jax.jit(jax.value_and_grad(lambda p: _next_token_loss(
            _ref_logits(model, p, toks), toks)))(params)
    return model, params, toks, mine, theirs


def _grad_gaps(params, grads, ref_grads):
    """{name: largest difference as a share of the reference's largest
    entry}, the selection bias (a buffer: no gradient) left out."""
    out = {}
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        if name == "moe.router_bias":
            assert not g.any() and not r.any()
            continue
        assert np.abs(r).max() > 0, name
        out[name] = float(np.abs(g - r).max() / np.abs(r).max())
    return out


def test_logits_and_loss_equal_the_references(both):
    _, _, _, (logits, loss, _), (ref_logits, ref_loss, _) = both
    gap = float(jnp.abs(logits - ref_logits).max())
    assert gap < LOGIT_LIMIT * float(jnp.abs(ref_logits).max())
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def test_every_gradient_equals_the_references(both):
    model, params, _, (_, _, grads), (_, _, ref_grads) = both
    gaps = _grad_gaps(params, grads, ref_grads)
    # the new parameters are there, on both kinds of layer, and are held
    for kind in ("dense", "moe"):
        for sub in ("hc_attn", "hc_mlp"):
            for name in hc.HC_PARAMS:
                assert f"{kind}.{sub}.{name}" in gaps
        assert f"{kind}.w_q_a" in gaps and f"{kind}.q_norm" in gaps
    assert max(gaps.values()) < GRAD_LIMIT, max(gaps, key=gaps.get)


# -- the maps ----------------------------------------------------------------


def _one_set(n=4, d=64, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = hc.hc_param_shapes(n, d)
    return {"phi": 0.2 * jax.random.normal(ks[0], shapes["phi"]),
            "gain": 1.0 + 0.1 * jax.random.normal(ks[1], shapes["gain"]),
            "bias": jax.random.normal(ks[2], shapes["bias"]),
            "alpha": jnp.array([0.5, 0.3, 0.7])}


HC_KW = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6)


def test_h_res_is_doubly_stochastic_and_the_tokens_minor_form_is_the_natural():
    """Rows sum to 1 / (1 + eps) exactly as the last step leaves them
    (within 2e-6: float32 sums of four), columns as near as 20 iterations
    bring them: within 5e-4 for three tokens in four and 1.7e-2 on this
    seed's worst token, whose logits spread over +-4 (the limit 5e-2: a
    map left unnormalised is off by more than 1); and the maps held
    tokens-minor ([n, tokens], [n, n, tokens]) are the reference's [B, S, n] and
    [B, S, n, n] within 1e-6 (float32 sums in another order; entries of
    order 1)."""
    n, d = 4, 64
    p = _one_set(n, d)
    x = tuple(jax.random.normal(k, (2, 48, d))
              for k in jax.random.split(jax.random.PRNGKey(8), n))
    pre, post, res = hc.hc_coefficients(x, p, **HC_KW)
    assert pre.shape == post.shape == (n, 96) and res.shape == (n, n, 96)
    assert float(jnp.abs(res.sum(1) - 1.0).max()) < 2e-6        # rows
    off = jnp.abs(res.sum(0) - 1.0)                             # columns
    assert float(off.max()) < 5e-2
    assert float(jnp.mean(off.max(0) < 5e-4)) >= 0.75
    assert float(res.min()) > 0.0
    r_pre, r_post, r_res = ref.hc_maps(
        jnp.stack(x, 2), p, iters=20, hc_eps=1e-6, clamp=(-30.0, 30.0),
        eps=1e-6)
    natural = lambda t: jnp.moveaxis(t.reshape(t.shape[:-1] + (2, 48)),  # noqa: E731
                                     (-2, -1), (0, 1))
    assert float(jnp.abs(natural(pre) - r_pre).max()) < 1e-6
    assert float(jnp.abs(natural(post) - r_post).max()) < 1e-6
    assert float(jnp.abs(natural(res) - r_res).max()) < 1e-6
    # the mixings: z and X' of the natural einsums
    y = jax.random.normal(jax.random.PRNGKey(9), (2, 48, d))
    z = hc.hc_pre(x, pre)
    assert float(jnp.abs(z - jnp.einsum("bsj,bsjd->bsd", r_pre,
                                        jnp.stack(x, 2))).max()) < 1e-5
    want = jnp.einsum("bsij,bsjd->bsid", r_res, jnp.stack(x, 2)) \
        + r_post[..., None] * y[:, :, None, :]
    got = jnp.stack(hc.hc_post(x, y, post, res), 2)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_the_clamp_bounds_the_logits_before_exp():
    """A residual bias of +-100 would overflow exp in float32 (e^100);
    clipped to +-30 the maps stay finite and doubly stochastic."""
    p = _one_set()
    p["bias"] = p["bias"].at[8:].set(
        100.0 * jnp.sign(jnp.arange(16) % 3 - 0.5))
    x = tuple(jax.random.normal(k, (1, 16, 64))
              for k in jax.random.split(jax.random.PRNGKey(2), 4))
    _, _, res = hc.hc_coefficients(x, p, **HC_KW)
    assert bool(jnp.isfinite(res).all())
    assert float(jnp.abs(res.sum(1) - 1.0).max()) < 2e-6


# -- YaRN ---------------------------------------------------------------------


def test_yarn_at_factor_one_is_rope_cache():
    cos, sin = rope_cache(96, 64, 10000.0)
    ycos, ysin = yarn_rope_cache(96, 64, 10000.0, factor=1.0,
                                 original_max=4096, mscale_all_dim=1.0)
    assert np.array_equal(np.asarray(cos), np.asarray(ycos))
    assert np.array_equal(np.asarray(sin), np.asarray(ysin))
    assert yarn_softmax_scale(192, 1.0, 1.0) == 192 ** -0.5


def test_yarn_at_factor_64_is_the_formula_by_hand():
    """dr 64, θ 10000, original 4096, β 32 / 1: low = floor(64 ln(4096 /
    (32 · 2π)) / (2 ln 10000)) = 10, high = ceil(64 ln(4096 / 2π) /
    (2 ln 10000)) = 23; pairs 0-10 keep f_i, pairs 23-31 turn at f_i / 64,
    the others blend linearly; the softmax scale is (0.1 ln 64 + 1)² /
    sqrt(192) = 2.0047 / sqrt(192), and cos, sin carry m(1) / m(1) = 1."""
    dr, base, seq = 64, 10000.0, 128
    cos, sin = yarn_rope_cache(seq, dr, base, factor=64.0, original_max=4096,
                               beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                               mscale_all_dim=1.0)
    want = []
    for i in range(dr // 2):
        f = base ** (-2 * i / dr)
        ramp = min(max((i - 10) / (23 - 10), 0.0), 1.0)
        want.append(f / 64 * ramp + f * (1 - ramp))
    ang = np.arange(seq)[:, None] * np.asarray(want)[None]
    # float32 angles up to 127 rad: 127 x 6e-8 = 8e-6 of a turn
    assert np.abs(np.asarray(cos) - np.cos(ang)).max() < 2e-5
    assert np.abs(np.asarray(sin) - np.sin(ang)).max() < 2e-5
    assert float(cos[5, 0]) == pytest.approx(math.cos(5.0), abs=1e-6)
    assert float(cos[64, 31]) == pytest.approx(
        math.cos(64 * base ** (-62 / 64) / 64), abs=1e-6)
    s = yarn_softmax_scale(192, 64.0, 1.0)
    assert s == pytest.approx((0.1 * math.log(64) + 1) ** 2 / math.sqrt(192))
    assert s * math.sqrt(192) == pytest.approx(2.0047, abs=1e-4)
    # the reference states the same table
    freqs, on_table, on_scale = ref.yarn_frequencies(dr, base, {
        "factor": 64.0, "original_max": 4096, "beta_fast": 32.0,
        "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0})
    assert np.abs(np.asarray(freqs) - np.asarray(want)).max() < 1e-7
    assert on_table == 1.0 and on_scale == pytest.approx(2.0047, abs=1e-4)


# -- what stays as it was ------------------------------------------------------


def test_one_stream_and_no_bottleneck_is_the_kanana_family():
    """``hc_mult`` 1 and ``q_lora_rank`` None: no parameter is added, the
    count is the plain family's own arithmetic and the loss is the plain
    reference's (``benchmark/reference/deepseek_v3.py``)."""
    c = DeepseekV3Config.tiny(dtype=jnp.float32)
    assert c.hc_mult == 1 and c.q_lora_rank is None and c.rope_factor == 1.0
    model = DeepseekV3(c)
    params = model.init(jax.random.PRNGKey(0))
    assert not [n for n in params if "hc_" in n or "w_q_a" in n
                or "q_norm" in n]
    assert sorted({n.split(".", 1)[1] for n in params if "." in n}) == sorted(
        ["attn_norm", "w_q_nope", "w_q_rope", "w_kv_a", "w_k_rope",
         "kv_norm", "w_k_b", "w_v_b", "w_o", "mlp_norm", "w_gate", "w_up",
         "w_down", "w_router", "router_bias", "s_gate", "s_up", "s_down",
         "e_gate", "e_up", "e_down"])
    sizes = {"hidden_size": 64, "num_attention_heads": 2,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "v_head_dim": 128, "kv_lora_rank": 32, "intermediate_size": 128,
             "moe_intermediate_size": 32, "n_shared_experts": 2,
             "first_k_dense_replace": 1, "num_hidden_layers": 3,
             "n_routed_experts": 8, "experts_held": 8}
    assert model.num_params() == plain.num_params(sizes, 512)
    toks = _tokens(512)
    loss = jax.jit(model.loss)(params, toks, jnp.roll(toks, -1, 1))
    h = plain.hidden(params, toks, jnp.float32, **plain.model_kwargs(c))
    want = _next_token_loss(plain.head(params, h, jnp.float32), toks)
    assert abs(float(loss) - float(want)) < 3e-6     # test_deepseek_v3.py's


def test_the_count_of_parameters_is_the_references_from_sizes():
    model = DeepseekV3(_config())
    sizes = {"hidden_size": 64, "num_attention_heads": 2,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
             "v_head_dim": 128, "kv_lora_rank": 32, "q_lora_rank": 16,
             "hc_mult": 4, "intermediate_size": 128,
             "moe_intermediate_size": 32, "n_shared_experts": 2,
             "first_k_dense_replace": 1, "num_hidden_layers": 3,
             "n_routed_experts": 8, "experts_held": 2}
    assert model.num_params() == ref.num_params(sizes, 512)
    # the published widths, cut as the benchmark's configuration cuts them
    cut = DeepseekV3(DeepseekV3Config.xing4_29b_a4b(
        n_layer=5, first_k_dense=1, experts_held=8, vocab_size=16384,
        max_seq=4096))
    assert cut.num_params() == 759_489_806


def test_four_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 chips, 2 each, the layer's parameters those of a
    model with the bottleneck and the streams on: the shares' outputs,
    with the shared experts (which every chip computes alike) counted
    once, are the reference's whole layer."""
    c = _config(experts_held=8)
    params = DeepseekV3(c).init(jax.random.PRNGKey(1))
    lp = {n.split(".", 1)[1]: v[0] for n, v in params.items()
          if n.startswith("moe.")}
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    share = lambda lo: dict(lp, **{k: lp[k][lo:lo + 2]  # noqa: E731
                                   for k in ("e_gate", "e_up", "e_down")})
    x = jax.random.normal(jax.random.PRNGKey(4), (96, c.d_model))
    shared = ref.shared_expert(x, lp)
    whole = shared + ref.routed_experts(
        x, lp, top_k=c.top_k, routed_scale=c.routed_scaling_factor)
    total, rows = jnp.zeros_like(x), 0
    for chip in range(4):
        y, n = held_expert_layer(
            x, share(2 * chip), experts_held=2, expert_offset=2 * chip,
            top_k=c.top_k, routed_scale=c.routed_scaling_factor)
        total, rows = total + y - shared, rows + int(n)
    # outputs of order 1 at init_std 0.2; float32 sums in another order
    assert float(jnp.abs(total + shared - whole).max()) \
        < 1e-5 * float(jnp.abs(whole).max())
    assert rows == 96 * c.top_k      # every (token, choice) pair on some chip


def test_a_prediction_module_is_refused_with_what_is_missing():
    with pytest.raises(ValueError, match="multi-token-prediction"):
        DeepseekV3Config.xing4_29b_a4b(num_nextn_predict_layers=1)
    assert DeepseekV3Config.xing4_29b_a4b().num_nextn_predict_layers == 0


def test_the_model_says_what_its_streams_cost():
    """ISSUE 45: the trace-time event a per-layer reader or a post-mortem
    finds: streams, sublayers wrapped, bytes of X a layer keeps."""
    from ray_tpu.perf import get_recorder

    model = DeepseekV3(_config())
    t0 = __import__("time").time()
    jax.eval_shape(model.loss, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)),
                   jnp.zeros((2, 128), jnp.int32),
                   jnp.zeros((2, 128), jnp.int32))
    events = [e for e in get_recorder().snapshot(clear=False)
              if e["kind"] == "rtpu.models.deepseek_v3.residual"
              and e["ts"] >= t0]
    assert events and events[-1]["data"] == {
        "hc_streams": 4, "hc_sublayers": 6,
        "residual_stream_bytes": 4 * 2 * 128 * 64 * 4}


# -- what would slip through a looser comparison --------------------------------


def test_nineteen_iterations_fail_the_limits(both):
    model, params, toks, _, (ref_logits, _, ref_grads) = both
    short = DeepseekV3(_config(hc_sinkhorn_iters=19))
    logits = jax.jit(short.apply)(params, toks)
    grads = jax.jit(jax.grad(short.loss))(params, toks,
                                          jnp.roll(toks, -1, 1))
    gaps = _grad_gaps(params, grads, ref_grads)
    assert max(gaps.values()) > GRAD_LIMIT
    assert float(jnp.abs(logits - ref_logits).max()) \
        > LOGIT_LIMIT * float(jnp.abs(ref_logits).max())


def test_bfloat16_coefficients_fail_the_limits(both, monkeypatch):
    model, params, toks, _, (ref_logits, _, _) = both
    real = hc.hc_coefficients

    def rounded(*a, **kw):
        return tuple(h.astype(jnp.bfloat16).astype(jnp.float32)
                     for h in real(*a, **kw))

    # d_model 64: the plain route of ``hc_mix``, which calls the module's
    # ``hc_coefficients`` (tests/test_hyper_connection_kernels.py rounds
    # the coefficients between the kernels)
    monkeypatch.setattr(hc, "hc_coefficients", rounded)
    logits = jax.jit(model.apply)(params, toks)
    assert float(jnp.abs(logits - ref_logits).max()) \
        > LOGIT_LIMIT * float(jnp.abs(ref_logits).max())
