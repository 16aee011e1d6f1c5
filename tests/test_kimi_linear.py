"""ISSUE 49: the Kimi-Linear shaped model (Kimi Delta Attention in the
chunked scan, latent attention without positions, a dense MLP and then a
held-expert layer, in one stack of unlike layers on ``models/stack.py``)
against the benchmark's plain reference
(``benchmark/reference/kimi_linear.py``: the delta rule token by token),
on seeded random weights at a small size with 2 of 8 experts held.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums, the triangular solve of the chunked
form and the interpreted flash kernels' online softmax. Read on this seed:
the loss by 4.8e-7 (one float32 step at 7.37), the logits by 1.3e-5 at
worst (the largest is 7.3), the gradients by at most 4.7e-6 of a
parameter's largest entry (``dt_bias`` of the last KDA layer). The limits:
5e-6 on the loss, 1e-4 on the logits, 5e-5 of the largest entry on each
gradient: ten times what was read. Against that, on the same seed
(``test_a_wrong_layer_would_fail``): a KDA state rounded to bf16 after
every token moves the loss by 2.2e-3, one scalar decay a head (the
channels' mean) by 3.9e-2, a delta rule without its ``- S^T k`` by 3.6e-3,
and each moves some logit by 2 to 11 (a token's route flips): each misses
the limits by a factor of four hundred or more.

ISSUE 51: the model calls ``ops.kda_gated_scan`` with q and k as the
convolutions left them and the gate projection's step. The tiny preset's
heads are the published 128 x 128 and the scan's chunk 64, so the fixture
``tiny`` runs the KERNEL route (interpreted here): the l2 norms and the
gate are made inside the kernels, and the gradients of ``A_log``,
``dt_bias``, ``w_f_b``, ``conv_q`` and ``conv_k`` come out of the backward
kernel's chain rule (``test_what_the_kernels_prologue_differentiates``).
``plain_route`` is the same model with the scan's route held to
``chunked_jnp``: ``l2norm``, the softplus and the plain scan, literally.
Both are held to the same reference by the same limits.
"""
import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import KimiLinear, KimiLinearConfig
from ray_tpu.models.deepseek_v3 import latent_attention
from ray_tpu.ops.expert_layer import held_expert_layer

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = importlib.import_module("benchmark.reference.kimi_linear")
ref_v3 = importlib.import_module("benchmark.reference.deepseek_v3")

F32 = dict(dtype=jnp.float32)
# init_std 0.2: with 0.02 a tiny model's mixers are rounding beside the
# residual and nothing they do would show in the loss
TINY = dict(experts_held=2, expert_offset=2, init_std=0.2, **F32)
LOSS_LIMIT = 5e-6     # absolute, on a loss of 7.37 (module docstring)
LOGIT_LIMIT = 1e-4    # absolute, on logits up to 7.3
GRAD_LIMIT = 5e-5     # of the gradient's largest entry


def _ref_logits(model, params, tokens, **patch):
    kw = dict(ref.model_kwargs(model.config), **patch)
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(params, tokens, jnp.float32, **kw)
        return ref.head(params, h, jnp.float32)


def _nll(logits, tokens):
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0])


def _tokens(vocab, seed=1, shape=(2, 150)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


@pytest.fixture(scope="module")
def tiny():
    """2 of 8 experts held (experts 2 and 3), T = 150 (two chunks and 22
    tokens of the scan): (model, params, tokens, the program's logits,
    loss and gradients, the reference's)."""
    model = KimiLinear(KimiLinearConfig.tiny(**TINY))
    params = model.init(jax.random.PRNGKey(0))
    # a selection bias that is not zero, so that it is seen to select
    for name in params:
        if name.endswith("router_bias"):
            params[name] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(5), params[name].shape)
    toks = _tokens(model.config.vocab_size)
    logits = jax.jit(model.apply)(params, toks)
    mine = jax.jit(jax.value_and_grad(model.loss))(
        params, toks, jnp.roll(toks, -1, 1))
    theirs = jax.jit(jax.value_and_grad(
        lambda p: _nll(_ref_logits(model, p, toks), toks)))(params)
    return model, params, toks, logits, mine, theirs


@pytest.fixture(scope="module")
def plain_route(tiny):
    """``tiny``'s model, parameters and tokens with the scan's route held to
    the plain form: (the routes its trace took, its loss and gradients)."""
    kda = importlib.import_module("ray_tpu.ops.kda_scan")
    model, params, toks = tiny[:3]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "_route", lambda *shape: "chunked_jnp")
        before = kda.PATH_COUNTS.copy()
        mine = jax.jit(jax.value_and_grad(model.loss))(
            params, toks, jnp.roll(toks, -1, 1))
        routes = kda.PATH_COUNTS - before
    return routes, mine


def _kda_path_events():
    from ray_tpu.perf.recorder import get_recorder

    return [e["data"] for e in get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.kda.path"]


PROLOGUE_PARAMS = ("A_log", "dt_bias", "w_f_b", "conv_q", "conv_k")


@pytest.mark.parametrize("name", PROLOGUE_PARAMS)
@pytest.mark.parametrize("route", ["kernel", "chunked_jnp"])
def test_what_the_kernels_prologue_differentiates(tiny, plain_route, route,
                                                  name):
    """ISSUE 51: the parameters whose gradients pass through the norms of q
    and k and through the gate (made inside the kernels on the kernel
    route: ``A_log``'s and ``dt_bias``'s are the backward kernel's partial
    sums, ``w_f_b``'s comes through its dstep, the convolutions' through
    its dq and dk with respect to the RAW q and k), in every run of KDA
    layers, against the float32 reference; and the plain route gives the
    same model."""
    _, params, _, _, (loss, grads), (ref_loss, ref_grads) = tiny
    if route == "chunked_jnp":
        routes, (loss, grads) = plain_route
        assert set(routes) == {"chunked_jnp"}
        assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT
    else:
        made = {e["prologue"] for e in _kda_path_events()
                if e["route"] == "kernel" and e["tokens"] == 150}
        assert "in_kernel" in made
    names = [n for n in params if n.endswith("." + name)]
    assert len(names) == 3          # the runs kda_dense, kda_moe x 2, kda_moe
    for n in names:
        g, r = np.asarray(grads[n]), np.asarray(ref_grads[n])
        assert np.abs(r).max() > 0, n
        assert np.abs(g - r).max() < GRAD_LIMIT * np.abs(r).max(), n


def test_the_stack_is_the_published_order_in_runs(tiny):
    model = tiny[0]
    assert model.config.kinds == ("kda_dense", "kda_moe", "kda_moe",
                                  "mla_moe", "kda_moe")
    assert model.runs == [(("kda_dense",), 1), (("kda_moe",), 2),
                          (("mla_moe",), 1), (("kda_moe",), 1)]
    full = KimiLinearConfig.kimi_linear_48b_a3b()
    assert full.n_layer == 27 and full.layer_types.count("mla") == 7
    assert [i + 1 for i, k in enumerate(full.layer_types) if k == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert full.mlp_layer_types == ("dense",) + ("moe",) * 26
    cut = KimiLinearConfig.kimi_linear_48b_a3b(n_layer=5)
    assert cut.kinds == model.config.kinds


def test_logits_equal_the_references(tiny):
    model, params, toks, logits, _, _ = tiny
    want = _ref_logits(model, params, toks)
    assert float(jnp.abs(logits - want).max()) < LOGIT_LIMIT
    assert float(jnp.abs(want).max()) > 0.5       # logits of order 1


def test_loss_equals_the_references(tiny):
    _, _, _, _, (loss, _), (ref_loss, _) = tiny
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def test_gradients_equal_the_references(tiny):
    _, params, _, _, (_, grads), (_, ref_grads) = tiny
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        if name.endswith("router_bias"):    # a buffer: selects, no gradient
            assert not g.any() and not r.any()
            continue
        scale = np.abs(r).max()
        assert scale > 0, name
        assert np.abs(g - r).max() < GRAD_LIMIT * scale, name


def test_a_wrong_layer_would_fail(tiny, monkeypatch):
    """The limits against a KDA layer computed wrongly in the three ways
    the issue names: each moves the reference's own logits (and loss) by
    far more than the program is allowed to differ from it."""
    model, params, toks, _, _, (ref_loss, _) = tiny
    right = _ref_logits(model, params, toks)
    rule = ref.delta_rule

    def bf16_state(q, k, v, g, beta):
        def token(s, tok):
            q_t, k_t, v_t, g_t, b_t = tok
            s = jnp.exp(g_t)[..., None] * s
            held = jnp.sum(s * k_t[..., None], axis=-2)
            s = s + (b_t[..., None] * k_t)[..., None] \
                * (v_t - held)[..., None, :]
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
            return s, jnp.sum(s * (q_t * q.shape[-1] ** -0.5)[..., None], -2)
        _, o = jax.lax.scan(
            token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:]),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)

    wrong = {
        "bf16_state": bf16_state,
        "head_decay": lambda q, k, v, g, beta: rule(
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape),
            beta),
        # beta k v^T alone: what is held for k is never taken off
        "no_delta": _written_only,
    }
    for name, fn in wrong.items():
        monkeypatch.setattr(ref, "delta_rule", fn)
        got = _ref_logits(model, params, toks)
        assert float(jnp.abs(got - right).max()) > 100 * LOGIT_LIMIT, name
        moved = abs(float(_nll(got, toks)) - float(ref_loss))
        assert moved > 100 * LOSS_LIMIT, (name, moved)
    monkeypatch.setattr(ref, "delta_rule", rule)


def _written_only(q, k, v, g, beta):
    """S <- diag(exp g) S + beta k v^T, o = S^T q / sqrt(d): the rule
    WITHOUT its correction."""
    def token(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        s = jnp.exp(g_t)[..., None] * s \
            + (b_t[..., None] * k_t)[..., None] * v_t[..., None, :]
        return s, jnp.sum(s * (q_t * q.shape[-1] ** -0.5)[..., None], -2)
    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:]),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def test_parameter_count_is_the_references(tiny):
    model = tiny[0]
    c = model.config
    sizes = {
        "hidden_size": c.d_model, "num_attention_heads": c.n_head,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "kv_lora_rank": c.kv_lora_rank, "kda_num_heads": c.kda_n_heads,
        "kda_head_dim": 128, "kda_conv_size": 4,
        "kda_gate_rank": c.kda_gate_rank, "intermediate_size": c.d_ff,
        "moe_intermediate_size": c.d_expert, "num_shared_experts": 1,
        "num_experts_per_token": c.top_k, "num_experts": 8,
        "experts_held": 2, "first_k_dense_replace": 1,
        "layer_types": list(c.layer_types), "num_hidden_layers": 5,
        "vocab_size": c.vocab_size}
    assert model.num_params() == ref.num_params(sizes, c.padded_vocab)
    assert model.num_params() == sum(
        int(np.prod(v.shape)) for v in tiny[1].values())


def test_the_cut_of_the_benchmark_counts_what_its_file_states():
    """The configuration's ``model`` builds the cut whose ``n_params`` the
    file states, and ``sizes`` count the same (shapes only: nothing is
    allocated)."""
    import json

    with open(os.path.join(HERE, "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        cfg = json.load(f)
    kw = dict(cfg["model"])
    kw.pop("family")
    model = KimiLinear(getattr(KimiLinearConfig, kw.pop("preset"))(**kw))
    assert model.runs == [(("kda_dense",), 1), (("kda_moe",), 2),
                          (("mla_moe",), 1), (("kda_moe",), 1)]
    assert model.num_params() == cfg["n_params"] \
        == ref.num_params(cfg["sizes"], model.config.padded_vocab)
    # every published width, unchanged
    c, pub = model.config, cfg["published"]
    assert (c.d_model, c.d_ff, c.d_expert, c.kv_lora_rank) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["moe_intermediate_size"], pub["kv_lora_rank"])
    assert (c.kda_n_heads, c.kda_head_dim, c.kda_d_conv) == (
        pub["linear_attn_config"]["num_heads"],
        pub["linear_attn_config"]["head_dim"],
        pub["linear_attn_config"]["short_conv_kernel_size"])
    assert (c.n_routed_experts, c.top_k, c.routed_scaling_factor) == (
        pub["num_experts"], pub["num_experts_per_token"],
        pub["routed_scaling_factor"])
    assert (c.experts_held, c.vocab_size) == (8, 20480)


def test_latent_attention_without_positions_is_kananas_under_an_identity():
    """``rope=None`` is the rotation by the angle 0 (cos 1, sin 0) of the
    DeepSeek-V3 layer, program and reference alike: the same kernels, the
    64 columns as the projections made them."""
    c = KimiLinearConfig.tiny(**F32)
    model = KimiLinear(c)
    params = model.init(jax.random.PRNGKey(3))
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("2.mla_moe.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, c.d_model))
    kw = dict(n_head=c.n_head, dtype=jnp.float32, eps=c.rms_eps)
    plain = latent_attention(x, lp, **kw)
    half = c.qk_rope_head_dim // 2
    turned = latent_attention(
        x, lp, rope=(jnp.ones((128, half)), jnp.zeros((128, half))), **kw)
    # the rotation's path sorts the pairs into halves, q and k alike: the
    # same scores summed in another order
    assert float(jnp.abs(plain - turned).max()) < 1e-5
    # the references: this file's attention against DeepSeek-V3's with its
    # rotation made the identity
    xn = ref_v3._rmsnorm(x, lp["attn_norm"], c.rms_eps)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ref_v3, "rope_pairs", lambda t, base: t)
        theirs = ref_v3.attention(xn, lp, n_head=c.n_head, rope_base=1e4,
                                  eps=c.rms_eps)
    ours = ref.attention(xn, lp, n_head=c.n_head, eps=c.rms_eps)
    assert float(jnp.abs(ours - theirs).max()) == 0.0
    assert float(jnp.abs(plain - ours).max()) < 1e-5
    assert float(jnp.abs(ours).max()) > 1e-3


def test_four_shares_add_up_to_the_uncut_layer():
    """The tiny model's 8 experts over 4 chips, 2 each: the shares'
    outputs, with the shared expert (which every chip computes alike)
    counted once, are the reference's whole layer."""
    c = KimiLinearConfig.tiny(**F32)
    params = KimiLinear(c).init(jax.random.PRNGKey(11))
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("1.kda_moe.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (96, c.d_model))
    kw = dict(top_k=c.top_k, routed_scale=c.routed_scaling_factor)
    shared = ref.shared_expert(x, lp)
    whole = shared + ref.routed_experts(x, lp, **kw)
    share = lambda off: dict(lp, **{                         # noqa: E731
        n: lp[n][off:off + 2] for n in ("e_gate", "e_up", "e_down")})
    total, rows = jnp.zeros_like(x), 0
    for chip in range(4):
        y, n = held_expert_layer(x, share(2 * chip), experts_held=2,
                                 expert_offset=2 * chip, **kw)
        part = ref.routed_experts(x, share(2 * chip), expert_offset=2 * chip,
                                  **kw)
        assert float(jnp.abs(y - shared - part).max()) < 1e-6
        total, rows = total + y - shared, rows + int(n)
    assert float(jnp.abs(total + shared - whole).max()) < 1e-6
    assert rows == 96 * c.top_k      # every (token, choice) pair on some chip


def test_routing_stats_counts_the_held_rows_of_every_expert_layer(tiny):
    model, params, toks, _, _, _ = tiny
    rows = np.asarray(jax.jit(model.routing_stats)(params, toks))
    assert rows.shape == (4,)        # layers 2 to 5 have experts
    assert (rows > 0).all() and (rows < toks.size * model.config.top_k).all()
