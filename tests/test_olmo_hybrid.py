"""ISSUE 67: the Olmo-Hybrid shaped model (Gated DeltaNet with key heads of
96 and value heads of 192 and beta = 2 sigmoid, attention without positions
whose q/k norms run over ALL of the layer's channels, POST-norm residuals, a
gated MLP after every mixer, a share of both mixers' heads held by offset;
``models/olmo_hybrid.py`` on ``models/stack.py``) against the benchmark's
plain reference (``benchmark/reference/olmo_hybrid.py``: the delta rule
token by token on a [96, 192] state), on seeded random weights at a small
size: three heads of a layer's four held from head 1 (an odd number: a
program of the scan's kernels holds one key head, two value tiles).

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums, the triangular solve of the chunked form
and the interpreted flash kernels' online softmax. Read on this seed: the
loss by less than one float32 step (4.8e-7 at 7.60), the logits by 2.0e-5 at
worst (the largest is 7.5), the gradients by at most 6.8e-6 of a parameter's
largest entry (``wte``). The limits: 5e-6 on the loss, 1e-4 on the logits,
5e-5 of the largest entry on each gradient: five to ten times what was read
(``test_qwen3_next.py``'s limits: the same kernels, the same order of sums).
Against that, on the same seed (``test_a_wrong_layer_would_fail``): each of
a Gated DeltaNet state rounded to bf16 after every token (2.6e-3), beta left
at sigmoid (no factor 2: 6.5e-2), a pre-norm block (9.4e-2), q/k norms a
head in place of the full width (6.0e-4, the least: 119 times the limit),
and q scaled by 1 / sqrt(128) (the padded tile's size, not the model's 96:
6.6e-3) moves the reference's own loss by more than forty times the limit.

The preset's Gated DeltaNet heads are the published 96 x 192 and the scan's
chunk 64, so the fixture ``tiny`` runs the KERNEL route (interpreted here):
keys padded to one 128-lane tile, values to two, the solve in blocks of 4
(``beta_max`` 2). ``plain_route`` is the same model with the scan's route
held to ``chunked_jnp``. Both are held to the same reference by the same
limits.

**The shares add up** (``test_the_shares_add_up``): with 4 heads and 2
shares the two shares' mixer outputs, before the post-norm, add up to the
uncut reference's, for both mixers; the q/k norm's mean of squares is the
one number completed over the two shares (summed by the test, as a pair's
all-reduce would), the MLP and the norms are counted once.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import OlmoHybrid, OlmoHybridConfig
from ray_tpu.models.stack import run_params

ref = importlib.import_module("benchmark.reference.olmo_hybrid")
kda = importlib.import_module("ray_tpu.ops.kda_scan")

F32 = dict(dtype=jnp.float32)
# init_std 0.2: with 0.02 a tiny model's mixers are rounding beside the
# residual and nothing they do would show in the loss
TINY = dict(n_head=4, heads_held=3, head_offset=1, init_std=0.2, **F32)
LOSS_LIMIT = 5e-6     # absolute, on a loss of 7.60 (module docstring)
LOGIT_LIMIT = 1e-4    # absolute, on logits up to 7.5
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
    """``model.init`` with every norm's gain off one, so that a gain left
    out or applied twice is seen."""
    params = model.init(jax.random.PRNGKey(seed))
    for i, name in enumerate(sorted(params)):
        if name.endswith("norm"):
            params[name] = 1.0 + 0.1 * jax.random.normal(
                jax.random.PRNGKey(100 + i), params[name].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    """(model, params, tokens, the program's logits, loss and gradients,
    the reference's)."""
    _SINCE[0] = time.time()
    model = OlmoHybrid(OlmoHybridConfig.tiny(**TINY))
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
    assert model.config.layer_types == ("gdn",) * 3 + ("attn",)
    assert model.runs == [(("gdn",), 3), (("attn",), 1)]
    full = OlmoHybridConfig.olmo_hybrid_7b()
    assert full.n_layer == 32 and full.layer_types.count("attn") == 8
    assert [i for i, k in enumerate(full.layer_types) if k == "attn"] \
        == list(range(3, 32, 4))
    assert (full.heads, full.gdn_key_dim, full.gdn_value_dim) == (30, 96, 192)
    with pytest.raises(ValueError, match="heads 20..36 of 30"):
        OlmoHybridConfig.olmo_hybrid_7b(heads_held=16, head_offset=20)


def test_the_cells_cut_has_the_parameters_the_issue_counts():
    """One period, 15 of 30 heads, an eighth of the vocabulary: 766.2 M."""
    cut = OlmoHybrid(OlmoHybridConfig.olmo_hybrid_7b(
        n_layer=4, heads_held=15, vocab_size=12544))
    shapes = cut._shapes()
    per = lambda kind: sum(                                  # noqa: E731
        int(np.prod(s[1:])) for n, (s, _) in shapes.items()
        if f".{kind}." in n and n.split(".")[2] not in (
            "w_gate", "w_up", "w_down", "mix_norm", "mlp_norm"))
    assert per("gdn") == 44_375_262 and per("attn") == 29_495_040
    assert cut.num_params() == 766_241_946
    sizes = {"hidden_size": 3840, "intermediate_size": 11008,
             "linear_num_key_heads": 15, "linear_num_value_heads": 15,
             "linear_key_head_dim": 96, "linear_value_head_dim": 192,
             "linear_conv_kernel_dim": 4, "num_attention_heads": 15,
             "num_key_value_heads": 15, "head_dim": 128,
             "layer_types": ["linear_attention"] * 3 + ["attention"]}
    assert ref.num_params(sizes, 12544) == cut.num_params()


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
    """Every parameter, through the kernel route; the routes the fixture
    took say which program ran: the model's head sizes, the lanes they
    occupy, one key head with its two value tiles a program, the solve in
    blocks of 4."""
    _, params, _, _, (_, grads), (_, ref_grads) = tiny
    made = {(e["route"], e["decay"], e["heads"], e["key_heads"], e["d_k"],
             e["d_v"], e["lanes_k"], e["lanes_v"], e["solve_block"],
             e.get("heads_per_block"))
            for e in _kda_path_events() if e["tokens"] == 128}
    assert made == {("kernel", "head", 3, 3, 96, 192, 128, 256, 4, 2)}
    _grads_agree(params, grads, ref_grads)


def test_the_plain_route_is_the_same_model(tiny, plain_route):
    _, params, _, _, _, (ref_loss, ref_grads) = tiny
    routes, (loss, grads) = plain_route
    assert set(routes) == {"chunked_jnp"}
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT
    _grads_agree(params, grads, ref_grads)


_SINCE = [0.0]


def _kda_path_events():
    from ray_tpu.perf import recorder

    return [dict(e["data"]) for e in recorder.get_recorder().snapshot()
            if e["kind"] == "rtpu.ops.kda.path" and e["ts"] >= _SINCE[0]]


# -- what the limits are for -------------------------------------------------

def _bf16_state(mp):
    def delta_rule(q, k, v, g, beta):
        b, t, h, dk = q.shape

        def token(s, tok):
            q_t, k_t, v_t, g_t, b_t = tok
            s = jnp.exp(g_t)[..., None, None] * s
            held = jnp.sum(s * k_t[..., None], axis=-2)
            s = s + (b_t[..., None] * k_t)[..., None] \
                * (v_t - held)[..., None, :]
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
            return s, jnp.sum(s * q_t[..., None], axis=-2)

        _, o = jax.lax.scan(
            token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
            tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)

    mp.setattr(ref, "delta_rule", delta_rule)
    return {}


def _beta_left_at_sigmoid(mp):
    mp.setattr(ref, "delta_rule", lambda q, k, v, g, beta, f=ref.delta_rule:
               f(q, k, v, g, 0.5 * beta))
    return {}


def _pre_norm(mp):
    """``x + mixer(rms(x))``: the norm before the sublayer, as every other
    family here has it."""
    def hidden(params, tokens, dtype, *, head_dim, key_dim, eps):
        p = {k: v.astype(dtype) for k, v in params.items()}
        x = p["wte"][tokens]
        for kind, lp in ref._layers(p):
            xn = ref.rms(x, lp["mix_norm"], eps)
            x = x + (ref.gdn_mixer(xn, lp, key_dim=key_dim, eps=eps)
                     if kind == "gdn" else
                     ref.attention(xn, lp, head_dim=head_dim, eps=eps))
            x = x + ref.mlp(ref.rms(x, lp["mlp_norm"], eps), lp)
        return ref.rms(x, p["out_norm"], eps)

    mp.setattr(ref, "hidden", hidden)
    return {}


def _qk_norm_a_head(mp):
    def rms(x, w, eps, f=ref.rms):
        if x.shape[-1] != 3 * 128 or w.shape[-1] != 3 * 128:
            return f(x, w, eps)
        xh = x.reshape(*x.shape[:-1], 3, 128)
        return f(xh, w.reshape(3, 128), eps).reshape(x.shape)

    mp.setattr(ref, "rms", rms)
    return {}


def _q_scaled_by_the_tile(mp):
    """q / sqrt(128): the padded tile's size in place of the model's 96."""
    mp.setattr(ref, "delta_rule", lambda q, k, v, g, beta, f=ref.delta_rule:
               f(q * (96 / 128) ** 0.5, k, v, g, beta))
    return {}


@pytest.mark.parametrize("fault", [
    _bf16_state, _beta_left_at_sigmoid, _pre_norm, _qk_norm_a_head,
    _q_scaled_by_the_tile], ids=lambda f: f.__name__.lstrip("_"))
def test_a_wrong_layer_would_fail(tiny, fault, monkeypatch):
    """Each fault, planted in the reference, moves its loss by more than
    forty times the limit the program is held to."""
    model, params, toks, _, _, (ref_loss, _) = tiny
    patch = fault(monkeypatch)
    wrong = _nll(_ref_logits(model, params, toks, **patch), toks)
    assert abs(float(wrong) - float(ref_loss)) > 40 * LOSS_LIMIT, (
        float(wrong), float(ref_loss))


# -- the shares add up -------------------------------------------------------

def _layer(params, run, kind, i=0):
    return {n: v[i] for n, v in run_params(params, run)[kind].items()}


class _StatisticCompleted(OlmoHybrid):
    """A share whose q/k norm reads the WHOLE layer's mean of squares, handed
    over by the test: what a tensor-parallel pair's all-reduce of one number
    a token (the sum of squares over the channels held) would give it."""

    completed = None      # [mean of squares of q, of k], popped in order

    def _qk_norm(self, t, w):
        ms = self.completed.pop(0)
        return (t.astype(jnp.float32) * jax.lax.rsqrt(
            ms + self.config.rms_eps) * w.astype(jnp.float32)).astype(t.dtype)


def test_the_shares_add_up():
    """4 heads, 2 shares of 2. For a Gated DeltaNet layer and for the
    attention layer the two shares' outputs before the post-norm add up to
    the uncut reference's mixer output on the whole layer's parameters
    (read: 9.9e-7 and 6.9e-7 of its largest entry, float32 sums in another
    order through the scan's kernels; held to 1e-5; one share alone is 0.7
    to 0.9 of that entry off).
    The attention shares are handed the q/k statistic completed over both
    (the sums of squares over each share's 256 channels added, over 512);
    a Gated DeltaNet layer has no statistic that spans heads. And how far
    the one-chip statistic is from the whole: over the held half (256 of
    512 channels) the root mean square of q differs from the whole layer's
    by 2.5 % a token in the mean and 9.2 % at most here (the spread falls as
    1 / sqrt(channels): at the cell's 1920 of 3840 about a third of that),
    a factor that differs token by token, so the cell's q and k are NOT the
    whole model's rescaled by a constant; with it the shares' sum is 4.2 %
    of the largest entry off the whole layer's. The configuration says
    so."""
    whole = OlmoHybrid(OlmoHybridConfig.tiny(n_head=4, init_std=0.2, **F32))
    params = _init(whole)
    c = whole.config
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, c.d_model))
    shares = [_StatisticCompleted(OlmoHybridConfig.tiny(
        n_head=4, heads_held=2, head_offset=off, init_std=0.2, **F32))
        for off in (0, 2)]
    held = [s.held_share(params) for s in shares]
    assert [s.num_params() for s in shares] == [
        sum(int(v.size) for v in h.values()) for h in held]
    kw = ref.model_kwargs(c)

    def close(parts, want):
        got = sum(parts)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) < 1e-5 * scale
        for part in parts:       # no share is the whole, none is nothing
            assert float(jnp.abs(part - want).max()) > 0.5 * scale

    with jax.default_matmul_precision("highest"):
        want = ref.gdn_mixer(x, _layer(params, 0, "gdn", 1),
                             key_dim=kw["key_dim"], eps=kw["eps"])
        close([s._gdn_mixer(x, _layer(h, 0, "gdn", 1))
               for s, h in zip(shares, held)], want)

        lp = _layer(params, 1, "attn")
        want = ref.attention(x, lp, head_dim=kw["head_dim"], eps=kw["eps"])
        # each share's sum of squares over ITS channels, added over the pair
        sums = [[jnp.sum(jnp.square(x @ _layer(h, 1, "attn")[w]), -1,
                         keepdims=True) for h in held]
                for w in ("w_q", "w_k")]
        channels = c.n_head * c.head_dim
        parts = []
        for s, h in zip(shares, held):
            s.completed = [sum(of) / channels for of in sums]
            parts.append(s._attn_mixer(x, _layer(h, 1, "attn")))
        close(parts, want)

        # the one-chip statistic: the held half's root mean square against
        # the whole layer's, a token
        half = jnp.sqrt(sums[0][0] / (channels // 2))
        full = jnp.sqrt(sum(sums[0]) / channels)
        apart = jnp.abs(half / full - 1.0)
        assert 0.01 < float(apart.mean()) < 0.05, float(apart.mean())
        assert float(apart.max()) < 0.2, float(apart.max())
        # and with the statistic left over the held half (the cell's model),
        # the shares do NOT add up to the whole layer: the exchange is no
        # detail
        plain = [OlmoHybrid(s.config)._attn_mixer(x, _layer(h, 1, "attn"))
                 for s, h in zip(shares, held)]
        assert float(jnp.abs(sum(plain) - want).max()) \
            > 1e-2 * float(jnp.abs(want).max())
