"""ISSUE 52: the Qwen3-Next shaped model (Gated DeltaNet in the chunked
scan, gated grouped-query attention of 256-wide heads with norms on q and k
and a quarter of each head rotated, every layer before a softmax-routed
held-expert layer with a gated shared expert, in one stack of unlike layers
on ``models/stack.py``) against the benchmark's plain reference
(``benchmark/reference/qwen3_next.py``: the delta rule token by token), on
seeded random weights at a small size with 2 of 8 experts held.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums, the triangular solve of the chunked
form and the interpreted flash kernels' online softmax. Read on this seed:
the loss by 9.5e-7 (two float32 steps at 7.66), the logits by 1.3e-5 at
worst (the largest is 7.1), the gradients by at most 4.6e-6 of a
parameter's largest entry. The limits: 5e-6 on the loss, 1e-4 on the
logits, 5e-5 of the largest entry on each gradient: five to ten times what
was read (kimi's limits: the same kernels, the same order of sums). Against
that, on the same seed (``test_a_wrong_layer_would_fail``): each of a
Gated DeltaNet state rounded to bf16 after every token, a delta rule
without its ``- S^T k``, value heads paired with the wrong key head, a
norm of the form ``w`` in place of ``1 + w``, the whole head rotated, a
router whose chosen weights are not renormalised and an ungated shared
expert moves the reference's own loss by more than fifty times the limit
(the bf16 state the least: 4.5e-4, ninety times, at 256 tokens; one decay
a head makes the state less tender than KDA's, which read 2.2e-3).

The tiny preset's Gated DeltaNet heads are the published 128 x 128 and the
scan's chunk 64, so the fixture ``tiny`` runs the KERNEL route
(interpreted here; ISSUE 53: the pair's body for one decay a head, a
program the 4 value heads over their 2 key heads, q and k read once a key
head and ``a`` a number a value head and token); T = 128 is one block of
the flash kernels and two chunks of the scan. ``plain_route`` is the same model with the scan's
route held to ``chunked_jnp``: ``l2norm``, the softplus and
``gated_delta_scan``, literally. Both are held to the same reference by
the same limits.
"""
import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Qwen3Next, Qwen3NextConfig
from ray_tpu.ops.expert_layer import held_expert_layer

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = importlib.import_module("benchmark.reference.qwen3_next")
kda = importlib.import_module("ray_tpu.ops.kda_scan")

F32 = dict(dtype=jnp.float32)
# init_std 0.2: with 0.02 a tiny model's mixers are rounding beside the
# residual and nothing they do would show in the loss
TINY = dict(experts_held=2, expert_offset=2, init_std=0.2, **F32)
LOSS_LIMIT = 5e-6     # absolute, on a loss of 7.66 (module docstring)
LOGIT_LIMIT = 1e-4    # absolute, on logits up to 7.1
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


def _tokens(vocab, seed=1, shape=(2, 128)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def _init(model, seed=0):
    """``model.init`` with the ``1 + w`` norms' weights off zero, so that
    the form of the gain is seen."""
    params = model.init(jax.random.PRNGKey(seed))
    for i, name in enumerate(sorted(params)):
        if name.endswith("norm") and not name.endswith("o_norm"):
            params[name] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(100 + i), params[name].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    """2 of 8 experts held (experts 2 and 3), T = 128: (model, params,
    tokens, the program's logits, loss and gradients, the reference's)."""
    _SINCE[0] = time.time()
    model = Qwen3Next(Qwen3NextConfig.tiny(**TINY))
    params = _init(model)
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
    model, params, toks = tiny[:3]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "_route", lambda *shape: "chunked_jnp")
        before = kda.PATH_COUNTS.copy()
        mine = jax.jit(jax.value_and_grad(model.loss))(
            params, toks, jnp.roll(toks, -1, 1))
        routes = kda.PATH_COUNTS - before
    return routes, mine


def test_the_stack_is_the_published_order_in_runs(tiny):
    model = tiny[0]
    assert model.config.kinds == ("gdn_moe",) * 3 + ("attn_moe",)
    assert model.runs == [(("gdn_moe",), 3), (("attn_moe",), 1)]
    full = Qwen3NextConfig.qwen3_next_80b_a3b()
    assert full.n_layer == 48 and full.layer_types.count("attn") == 12
    assert [i for i, k in enumerate(full.layer_types) if k == "attn"] \
        == list(range(3, 48, 4))                  # full_attention_interval 4
    assert full.rotary_dim == 64
    cut = Qwen3NextConfig.qwen3_next_80b_a3b(n_layer=4)
    assert cut.kinds == model.config.kinds


def test_logits_equal_the_references(tiny):
    model, params, toks, logits, _, _ = tiny
    want = _ref_logits(model, params, toks)
    assert float(jnp.abs(logits - want).max()) < LOGIT_LIMIT
    assert float(jnp.abs(want).max()) > 0.5       # logits of order 1


def test_loss_equals_the_references(tiny):
    _, _, _, _, (loss, _), (ref_loss, _) = tiny
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def _grads_agree(params, grads, ref_grads):
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        scale = np.abs(r).max()
        assert scale > 0, name
        assert np.abs(g - r).max() < GRAD_LIMIT * scale, name


def test_gradients_equal_the_references(tiny):
    """Every parameter, through the kernel route: ``A_log`` and ``dt_bias``
    (one a value head) from the backward kernel's partial sums a token of
    the chunk, ``w_ba`` through its da, the convolution through dq and dk,
    which the backward kernel sums over a key head's value heads. The
    routes the fixture took say which body ran (ISSUE 53)."""
    _, params, _, _, (_, grads), (_, ref_grads) = tiny
    made = {(e["decay"], e["body"], e["key_heads"], e["heads_per_block"],
             e["prologue"])
            for e in _kda_path_events()
            if e["route"] == "kernel" and e["tokens"] == 128}
    assert made == {("head", "head_decay", 2, 4, "in_kernel")}
    _grads_agree(params, grads, ref_grads)


def test_the_plain_route_is_the_same_model(tiny, plain_route):
    _, params, _, _, _, (ref_loss, ref_grads) = tiny
    routes, (loss, grads) = plain_route
    assert set(routes) == {"chunked_jnp"}
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT
    _grads_agree(params, grads, ref_grads)


# when the fixture ``tiny`` began to trace: a worker's ring also holds what
# the files it ran before this one traced (a KDA model's kernel route at 128
# tokens is in it whenever ``test_kimi_linear.py`` came first)
_SINCE = [0.0]


def _kda_path_events():
    from ray_tpu.perf.recorder import get_recorder

    return [e["data"] for e in get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.kda.path" and e["ts"] >= _SINCE[0]]


def test_a_prefix_sees_nothing_of_what_follows(tiny):
    """The causal stack: the logits of the first 64 positions are the same
    whether 64 or 128 tokens are run (the scan, the convolution, the
    attention and the rotation look back only; the router is a token's
    own), by other routes too: 64 tokens are one padded chunk of the scan
    and no multiple of the flash kernels' 128."""
    model, params, toks, logits, _, _ = tiny
    short = jax.jit(model.apply)(params, toks[:, :64])
    assert float(jnp.abs(short - logits[:, :64]).max()) < LOGIT_LIMIT
    other = toks.at[:, 64:].set((toks[:, 64:] + 7) % 512)
    moved = jax.jit(model.apply)(params, other)
    assert float(jnp.abs(moved[:, :64] - logits[:, :64]).max()) < LOGIT_LIMIT
    assert float(jnp.abs(moved[:, 64:] - logits[:, 64:]).max()) > 0.1


def _bf16_state(q, k, v, g, beta):
    def token(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        s = jnp.exp(g_t)[..., None, None] * s
        held = jnp.sum(s * k_t[..., None], axis=-2)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - held)[..., None, :]
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.sum(s * q_t[..., None], -2)
    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:]),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _written_only(q, k, v, g, beta):
    """S <- exp(g) S + beta k v^T, o = S^T q: the rule WITHOUT its
    correction."""
    def token(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        s = jnp.exp(g_t)[..., None, None] * s \
            + (b_t[..., None] * k_t)[..., None] * v_t[..., None, :]
        return s, jnp.sum(s * q_t[..., None], -2)
    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:]),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


WRONG = ("bf16_state", "no_delta", "wrong_key_head", "norm_w",
         "whole_rotation", "weights_not_renormalised", "shared_ungated")


@pytest.mark.parametrize("fault", WRONG)
def test_a_wrong_layer_would_fail(tiny, monkeypatch, fault):
    """The limits against a layer computed wrongly in the ways the
    architecture invites: each moves the reference's own loss by far more
    than the program is allowed to differ from it."""
    model, params, toks, _, _, (ref_loss, _) = tiny
    patch = {}
    rule = ref.delta_rule
    if fault == "bf16_state":
        monkeypatch.setattr(ref, "delta_rule", _bf16_state)
    elif fault == "no_delta":
        monkeypatch.setattr(ref, "delta_rule", _written_only)
    elif fault == "wrong_key_head":
        # value head j on key head j % Hk in place of j // (Hv / Hk)
        monkeypatch.setattr(ref, "delta_rule", lambda q, k, v, g, beta: rule(
            q[:, :, (0, 2, 1, 3)], k[:, :, (0, 2, 1, 3)], v, g, beta))
    elif fault == "norm_w":
        monkeypatch.setattr(ref, "zrms", lambda x, w, eps: (
            x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w))
    elif fault == "whole_rotation":
        patch["rotary"] = model.config.head_dim
    elif fault == "weights_not_renormalised":
        routed = ref.routed_experts

        def raw(xn, lp, *, top_k, expert_offset=0):
            # w = p in place of p / (sum of the chosen p): a token's routed
            # sum times that sum
            p = jax.nn.softmax(xn @ lp["w_router"], -1)
            return routed(xn, lp, top_k=top_k, expert_offset=expert_offset) \
                * jax.lax.top_k(p, top_k)[0].sum(-1, keepdims=True)

        monkeypatch.setattr(ref, "routed_experts", raw)
    elif fault == "shared_ungated":
        monkeypatch.setattr(ref, "shared_expert", lambda xn, lp: ref._gated(
            xn, lp["s_gate"], lp["s_up"], lp["s_down"]))
    got = _ref_logits(model, params, toks, **patch)
    moved = abs(float(_nll(got, toks)) - float(ref_loss))
    assert moved > 50 * LOSS_LIMIT, (fault, moved)


def _sizes(c, held):
    return {
        "hidden_size": c.d_model, "num_attention_heads": c.n_head,
        "num_key_value_heads": c.n_kv_head, "head_dim": c.head_dim,
        "linear_num_key_heads": c.gdn_key_heads,
        "linear_num_value_heads": c.gdn_value_heads,
        "linear_key_head_dim": c.gdn_head_dim,
        "linear_value_head_dim": c.gdn_head_dim,
        "linear_conv_kernel_dim": c.gdn_d_conv,
        "moe_intermediate_size": c.d_expert,
        "shared_expert_intermediate_size": c.d_shared,
        "num_experts_per_tok": c.top_k, "num_experts": c.n_routed_experts,
        "experts_held": held, "num_hidden_layers": c.n_layer,
        "layer_types": ["attention" if k == "attn" else "linear_attention"
                        for k in c.layer_types],
        "vocab_size": c.vocab_size}


def test_parameter_count_is_the_references(tiny):
    model = tiny[0]
    c = model.config
    assert model.num_params() == ref.num_params(_sizes(c, 2), c.padded_vocab)
    assert model.num_params() == sum(
        int(np.prod(v.shape)) for v in tiny[1].values())


def test_the_cut_of_the_benchmark_counts_what_its_file_states():
    """The configuration's ``model`` builds the cut whose ``n_params`` the
    file states, and ``sizes`` count the same (shapes only: nothing is
    allocated); every published width is the model's."""
    with open(os.path.join(HERE, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep16.json")) as f:
        cfg = json.load(f)
    kw = dict(cfg["model"])
    kw.pop("family")
    model = Qwen3Next(getattr(Qwen3NextConfig, kw.pop("preset"))(**kw))
    assert model.runs == [(("gdn_moe",), 3), (("attn_moe",), 1)]
    assert model.num_params() == cfg["n_params"] == 625994816 \
        == ref.num_params(cfg["sizes"], model.config.padded_vocab)
    assert cfg["sizes"] == _sizes(model.config, 32)
    c, pub = model.config, cfg["published"]
    assert (c.d_model, c.d_expert, c.d_shared, c.head_dim) == (
        pub["hidden_size"], pub["moe_intermediate_size"],
        pub["shared_expert_intermediate_size"], pub["head_dim"])
    assert (c.n_head, c.n_kv_head, c.partial_rotary_factor, c.rope_base) == (
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["partial_rotary_factor"], pub["rope_theta"])
    assert (c.gdn_key_heads, c.gdn_value_heads, c.gdn_head_dim,
            c.gdn_d_conv) == (
        pub["linear_num_key_heads"], pub["linear_num_value_heads"],
        pub["linear_key_head_dim"], pub["linear_conv_kernel_dim"])
    assert pub["linear_value_head_dim"] == pub["linear_key_head_dim"]
    assert (c.n_routed_experts, c.top_k, c.rms_eps) == (
        pub["num_experts"], pub["num_experts_per_tok"], pub["rms_norm_eps"])
    assert (c.experts_held, c.vocab_size, c.padded_vocab) == (
        32, 18992, 19072)
    # the top-level keys are the published ones, changed where listed only
    changed = {k for k, v in pub.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == set(cfg["reduced_how"])


def test_four_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. The tiny model's 8 experts over 4 chips, 2 each: the
    shares' outputs, with the gated shared expert (which every chip
    computes alike) counted once, are the uncut reference's whole
    sublayer."""
    c = Qwen3NextConfig.tiny(**F32)
    params = Qwen3Next(c).init(jax.random.PRNGKey(11))
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("0.gdn_moe.")}
    x = jax.random.normal(jax.random.PRNGKey(4), (96, c.d_model))
    shared = ref.shared_expert(x, lp)
    whole = shared + ref.routed_experts(x, lp, top_k=c.top_k)
    share = lambda off: dict(lp, **{                         # noqa: E731
        n: lp[n][off:off + 2] for n in ("e_gate", "e_up", "e_down")})
    total, rows = jnp.zeros_like(x), 0
    for chip in range(4):
        y, n = held_expert_layer(
            x, share(2 * chip), experts_held=2, expert_offset=2 * chip,
            top_k=c.top_k, routed_scale=1.0, score="softmax")
        part = ref.routed_experts(x, share(2 * chip), top_k=c.top_k,
                                  expert_offset=2 * chip)
        assert float(jnp.abs(y - shared - part).max()) < 1e-6
        total, rows = total + y - shared, rows + int(n)
    assert float(jnp.abs(total + shared - whole).max()) < 1e-6
    assert float(jnp.abs(whole - shared).max()) > 1e-3
    assert rows == 96 * c.top_k      # every (token, choice) pair on some chip


def test_routing_stats_counts_the_held_rows_of_every_layer(tiny):
    model, params, toks, _, _, _ = tiny
    rows = np.asarray(jax.jit(model.routing_stats)(params, toks))
    assert rows.shape == (4,)        # every layer has experts
    assert (rows > 0).all() and (rows < toks.size * model.config.top_k).all()
    # the first layer's by hand: the softmax's top 3 of 8 that name 2 or 3
    lp = {n.split(".", 2)[2]: v[0] for n, v in params.items()
          if n.startswith("0.gdn_moe.")}
    x = params["wte"][toks]
    with jax.default_matmul_precision("highest"):
        x = x + ref.gdn_mixer(ref.zrms(x, lp["norm"], 1e-6), lp, key_heads=2,
                              value_heads=4, eps=1e-6)
        p = jax.nn.softmax(ref.zrms(x, lp["mlp_norm"], 1e-6)
                           @ lp["w_router"], -1)
    chosen = np.asarray(jax.lax.top_k(p, 3)[1])
    assert abs(int(rows[0]) - int(((chosen == 2) | (chosen == 3)).sum())) <= 2
