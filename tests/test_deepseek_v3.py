"""ISSUE 33: the DeepSeek-V3 shaped model (latent attention in the flash
kernels, a dropless held-expert layer with shared experts, a leading dense
layer, a vocabulary slice) against the benchmark's plain reference
(``benchmark/reference/deepseek_v3.py``: the one copy), on seeded random
weights at a small size. Pallas kernels run in interpret mode here.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums only. Read on this seed: the loss by
4.8e-7 (one float32 step at 6.26), the gradients by at most 7.2e-7 of a
parameter's largest entry. The limits: 3e-6 on the loss, 2e-5 of the
largest entry on each gradient. Parameters rounded to bf16 move the loss
by 1.8e-5 and every parameter's gradient by 2.1e-3 to 7.4e-3 of its
largest entry; a missing ``routed_scaling_factor`` changes the routed sum
by a factor of 2.448: both fail the limits (the last two tests hold that).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import DeepseekV3, DeepseekV3Config
from ray_tpu.models.deepseek_v3 import _rope_interleaved
from ray_tpu.ops import mha_reference
from ray_tpu.ops.expert_layer import (buffer_rows, held_expert_layer,
                                      sort_rows)
from ray_tpu.ops.flash_attention import PATH_COUNTS, flash_attention


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference",
        "deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek_v3",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
F32 = dict(dtype=jnp.float32)
LOSS_LIMIT = 3e-6     # absolute, on a loss of 6.26 (module docstring)
GRAD_LIMIT = 2e-5     # of the gradient's largest entry


def _ref_loss(model, params, tokens):
    h = ref.hidden(params, tokens, jnp.float32,
                   **ref.model_kwargs(model.config))
    logits = ref.head(params, h, jnp.float32)
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0])


def _tokens(vocab, seed=1, shape=(2, 128)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


@pytest.fixture(scope="module")
def whole():
    """All experts held, the whole vocabulary: (model, params, tokens,
    the program's loss and gradients, the reference's)."""
    model = DeepseekV3(DeepseekV3Config.tiny(**F32))
    params = model.init(jax.random.PRNGKey(0))
    # a selection bias that is not zero, so that it is seen to select
    params["moe.router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe.router_bias"].shape)
    toks = _tokens(model.config.vocab_size)
    mine = jax.jit(jax.value_and_grad(model.loss))(
        params, toks, jnp.roll(toks, -1, 1))
    theirs = jax.jit(jax.value_and_grad(
        lambda p: _ref_loss(model, p, toks)))(params)
    return model, params, toks, mine, theirs


def test_loss_equals_the_references(whole):
    _, _, _, (loss, _), (ref_loss, _) = whole
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def test_gradients_equal_the_references(whole):
    _, params, _, (_, grads), (_, ref_grads) = whole
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        scale = np.abs(r).max()
        if name == "moe.router_bias":       # a buffer: selects, no gradient
            assert not g.any() and not r.any()
            continue
        assert scale > 0, name
        assert np.abs(g - r).max() < GRAD_LIMIT * scale, name


def test_the_loss_over_a_vocabulary_slice_is_the_references_over_it():
    """``vocab_size`` an eighth of the tiny vocabulary, no multiple of
    128: embedding, head and loss are over the slice (padded to 128 rows,
    which take part in the softmax on both sides)."""
    model = DeepseekV3(DeepseekV3Config.tiny(vocab_size=64, **F32))
    assert model.config.padded_vocab == 128
    params = model.init(jax.random.PRNGKey(2))
    assert params["wte"].shape[0] == params["lm_head"].shape[0] == 128
    toks = _tokens(64, seed=3)
    loss = jax.jit(model.loss)(params, toks, jnp.roll(toks, -1, 1))
    want = _ref_loss(model, params, toks)
    assert abs(float(loss) - float(want)) < LOSS_LIMIT


# -- the latent kernels ------------------------------------------------------


def _latent_inputs(b=2, s=256, h=4, dn=128, dr=64, dv=128):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    n = jax.random.normal
    return (n(ks[0], (b, s, h, dn)), n(ks[1], (b, s, h, dn)),
            n(ks[2], (b, s, h, dv)), n(ks[3], (b, s, h, dr)),
            n(ks[4], (b, s, dr))), n(ks[5], (b, s, h, dv))


def _latent_plain(q, k, v, q_rope, k_rope, causal=True):
    """The 192-wide key written out for every head."""
    shared = jnp.broadcast_to(k_rope[:, :, None, :],
                              q_rope.shape[:3] + k_rope.shape[-1:])
    qq = jnp.concatenate([q, q_rope], -1)
    return mha_reference(qq, jnp.concatenate([k, shared], -1), v,
                         causal=causal, sm_scale=qq.shape[-1] ** -0.5)


# case -> (S, (block_q, block_k) asked for, causal, heads, dr, the square
# block the kernels run or None where the call takes ``mha_reference``).
# ISSUE 34: "fused" has diagonal and full block pairs and two head blocks,
# whose shared-key gradients add up. ISSUE 46: blocks of 256 are worked in
# two row bands of 128 where the diagonal crosses them (``_latent_band``):
# "banded" has two diagonal block pairs and a full one, two head blocks;
# case 256 is one banded block. ISSUE 48: ONE backward; "full", a
# non-causal call, takes the reference route and still answers;
# "one_head_block" (two heads of 64: the shared key's gradient is
# initialised and leaves in the SAME head block); "rope128" (a rope part
# of 128: one head a program); "unequal" (the kernels run square blocks,
# the smaller of the two asked for).
LATENT_CASES = {
    256: (256, (256, 256), True, 4, 64, 256),   # one K block a program
    128: (256, (128, 128), True, 4, 64, 128),   # streamed
    "fused": (512, (128, 128), True, 4, 64, 128),
    "full": (256, (128, 128), False, 4, 64, None),
    "banded": (512, (256, 256), True, 4, 64, 256),
    "one_head_block": (512, (256, 256), True, 2, 64, 256),
    "rope128": (512, (128, 128), True, 2, 128, 128),
    "unequal": (1024, (512, 1024), True, 2, 64, 512),
}


@pytest.fixture(scope="module")
def latent_grads():
    import sys
    fa = sys.modules[flash_attention.__module__]   # the module, not the op
    out = {}
    for case, (s, (bq, bk), causal, h, dr, block) in LATENT_CASES.items():
        args, w = _latent_inputs(s=s, h=h, dr=dr)

        def loss(*a, bq=bq, bk=bk, causal=causal, w=w):
            return (flash_attention(a[0], a[1], a[2], causal=causal,
                                    block_q=bq, block_k=bk,
                                    q_rope=a[3], k_rope=a[4]) * w).sum()
        layout = "latent" if block else "latent_reference"
        before, ran = fa.PATH_COUNTS[layout], []
        with pytest.MonkeyPatch.context() as mp:
            kernels = fa._flash_latent
            mp.setattr(fa, "_flash_latent", lambda *a: (
                ran.append(a[-1]), kernels(*a))[1])
            out[case] = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
                *args)
        assert fa.PATH_COUNTS[layout] == before + 1, case
        assert ran == ([block] if block else []), case
        if (s, causal, h, dr) not in out:
            out[s, causal, h, dr] = jax.value_and_grad(
                lambda *a, causal=causal, w=w: (
                    _latent_plain(*a, causal=causal) * w).sum(),
                argnums=(0, 1, 2, 3, 4))(*args)
    return out


@pytest.mark.parametrize("blocks", list(LATENT_CASES))
@pytest.mark.parametrize("which", ["forward", "dq_nope", "dk_nope", "dv",
                                   "dq_rope", "dk_rope"])
def test_latent_kernels_against_mha_reference(latent_grads, blocks, which):
    """Head sizes 192 (128 + 64, the 64 one key for all heads; "rope128":
    256) and 128, float32 in interpret mode: the sums' order only, values
    of order 1 to 5; 2e-5 absolute. Every route a latent call can take
    (``LATENT_CASES``)."""
    s, _, causal, h, dr, _ = LATENT_CASES[blocks]
    (val, grads), (want_val, want) = latent_grads[blocks], \
        latent_grads[s, causal, h, dr]
    if which == "forward":
        assert abs(float(val) - float(want_val)) < 2e-5 * abs(float(want_val)) + 2e-4
        return
    i = ["dq_nope", "dk_nope", "dv", "dq_rope", "dk_rope"].index(which)
    assert grads[i].shape == want[i].shape
    assert float(jnp.abs(grads[i] - want[i]).max()) < 2e-5


def _latent_shapes(s):
    """q, k, v, q_rope, k_rope of a call with two heads, as shapes."""
    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    return (sd(1, s, 2, 128), sd(1, s, 2, 128), sd(1, s, 2, 128),
            sd(1, s, 2, 64), sd(1, s, 64))


@pytest.mark.parametrize("causal", [False, True],
                         ids=["non_causal", "too_long"])
def test_a_latent_call_the_one_backward_cannot_take(monkeypatch, causal):
    """ISSUE 48: the route is decided once, before anything is traced. A
    non-causal call (no diagonal step at which a q block's dq is
    complete) takes ``mha_reference`` and says so; a causal call whose
    whole-sequence accumulators do not fit VMEM is refused with S, the
    bytes and the limit (the reference would make S x S scores)."""
    import sys

    fa = sys.modules[flash_attention.__module__]

    def loss(q, k, v, qr, kr):
        return flash_attention(q, k, v, causal=causal, q_rope=qr,
                               k_rope=kr).astype(jnp.float32).sum()

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    if causal:
        monkeypatch.setattr(fa, "_LATENT_VMEM_BYTES", 1 << 20)
        with pytest.raises(ValueError, match=r"S=2048.* bytes .*1048576"):
            grad.lower(*_latent_shapes(2048))
        return
    before = PATH_COUNTS["latent_reference"]
    text = grad.lower(*_latent_shapes(2048)).as_text(debug_info=True)
    assert PATH_COUNTS["latent_reference"] == before + 1
    assert "flash_latent_" not in text


def test_a_latent_call_takes_the_latent_kernels_and_says_so():
    args, _ = _latent_inputs(b=1, s=128, h=2)
    before = PATH_COUNTS["latent"]
    text = jax.jit(lambda *a: flash_attention(
        a[0], a[1], a[2], q_rope=a[3], k_rope=a[4])).lower(*args).as_text(
        debug_info=True)
    assert PATH_COUNTS["latent"] == before + 1
    assert "flash_latent_fwd" in text and "flash_fwd_single" not in text
    # heads that do not tile the lanes: the plain route, named as such
    odd = _latent_inputs(b=1, s=128, h=2, dn=32, dr=16, dv=32)[0]
    before = PATH_COUNTS["latent_reference"]
    got = flash_attention(odd[0], odd[1], odd[2], q_rope=odd[3],
                          k_rope=odd[4])
    assert PATH_COUNTS["latent_reference"] == before + 1
    assert float(jnp.abs(got - _latent_plain(*odd)).max()) < 1e-5


@pytest.mark.parametrize("blocks,causal,bands", [
    (1024, True, 4), (256, True, 2), (128, True, 1), (1024, False, 0)])
def test_a_latent_call_says_how_many_bands_a_diagonal_step_works(
        blocks, causal, bands):
    """ISSUE 46: ``bands`` of the event ``rtpu.ops.flash.path`` and
    ``BAND_COUNTS``: a quarter of a block of 1024, halves of one of 256,
    the whole block of 128. ISSUE 48: a non-causal call leaves the
    reference route's event, which has no bands."""
    import sys

    from ray_tpu.perf.recorder import get_recorder

    fa = sys.modules[flash_attention.__module__]
    layout = "latent" if causal else "latent_reference"
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    before = fa.BAND_COUNTS[bands]
    try:
        jax.eval_shape(
            lambda q, k, v, qr, kr: flash_attention(
                q, k, v, causal=causal, block_q=blocks, block_k=blocks,
                q_rope=qr, k_rope=kr), *_latent_shapes(2048))
        events = rec.snapshot()
    finally:
        rec.enabled = was
    assert fa.BAND_COUNTS[bands] == before + 1
    last = [e for e in events if e["kind"] == "rtpu.ops.flash.path"
            and e["label"].startswith("latent")][-1]
    assert last["label"] == last["data"]["layout"] == layout
    assert last["data"]["bands"] == bands and last["data"]["S"] == 2048


def test_the_score_is_scaled_by_one_over_sqrt_192_by_hand():
    """q = ones, every key c_pos * ones: score = 192 c_pos / sqrt(192);
    v_pos = pos. The last row sees every position."""
    s, h = 128, 2
    c = np.linspace(-0.2, 0.2, s).astype(np.float32)
    ones = lambda d: jnp.ones((1, s, h, d))  # noqa: E731
    k = jnp.asarray(c)[None, :, None, None] * ones(128)
    k_rope = jnp.asarray(c)[None, :, None] * jnp.ones((1, s, 64))
    v = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] * ones(128)
    o = flash_attention(ones(128), k, v, q_rope=ones(64), k_rope=k_rope)
    e = np.exp(c.astype(np.float64) * np.sqrt(192.0))
    want_last = float((e * np.arange(s)).sum() / e.sum())
    want_mid = float((e[:65] * np.arange(65)).sum() / e[:65].sum())
    assert abs(float(o[0, -1, 0, 0]) - want_last) < 1e-3
    assert abs(float(o[0, 64, 1, 5]) - want_mid) < 1e-3
    # sqrt(128), the nope part's own width, would give another value
    e2 = np.exp(c.astype(np.float64) * 192.0 / np.sqrt(128.0))
    assert abs((e2 * np.arange(s)).sum() / e2.sum() - want_last) > 1.0


def test_rope_turns_neighbouring_pairs_by_hand():
    """One head of 4 at position 1, base 100: the pair (x0, x1) turns by
    1 rad, (x2, x3) by 100**-0.5 = 0.1 rad. The program sorts the pairs
    into halves first, as the published code does: (x0, x2 | x1, x3)."""
    x = jnp.zeros((1, 2, 1, 4)).at[0, 1, 0].set(jnp.array([1., 0., 0., 2.]))
    want_pairs = np.array([np.cos(1.0), np.sin(1.0),
                           -2 * np.sin(0.1), 2 * np.cos(0.1)])
    got_ref = np.asarray(ref.rope_pairs(x, 100.0))[0, 1, 0]
    np.testing.assert_allclose(got_ref, want_pairs, atol=1e-6)
    half = 1.0 / (100.0 ** (jnp.arange(0, 2, dtype=jnp.float32) / 2))
    ang = jnp.arange(2, dtype=jnp.float32)[:, None] * half[None]
    got = np.asarray(_rope_interleaved(x, jnp.cos(ang), jnp.sin(ang)))[0, 1, 0]
    np.testing.assert_allclose(got, want_pairs[[0, 2, 1, 3]], atol=1e-6)
    # position 0 is left alone
    assert not np.asarray(ref.rope_pairs(x, 100.0))[0, 0].any()


# -- the expert layer --------------------------------------------------------


def _layer_params(model, seed=11, layer=0):
    p = model.init(jax.random.PRNGKey(seed))
    return {n.split(".", 1)[1]: v[layer] for n, v in p.items()
            if n.startswith("moe.")}


def _layer_kw(c, held, offset):
    return dict(experts_held=held, expert_offset=offset, top_k=c.top_k,
                routed_scale=c.routed_scaling_factor)


def _share(lp, held, offset):
    return dict(lp, **{n: lp[n][offset:offset + held]
                       for n in ("e_gate", "e_up", "e_down")})


def test_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips, 2 each: the shares' outputs, with the
    shared experts (which every chip computes alike) counted once, are
    the reference's whole layer."""
    c = DeepseekV3Config.tiny(n_routed_experts=16, experts_held=16, top_k=6,
                              **F32)
    lp = _layer_params(DeepseekV3(c))
    x = jax.random.normal(jax.random.PRNGKey(4), (96, c.d_model))
    shared = ref.shared_expert(x, lp)
    whole = shared + ref.routed_experts(
        x, lp, top_k=6, routed_scale=c.routed_scaling_factor)
    total, rows = jnp.zeros_like(x), 0
    for chip in range(8):
        y, n = held_expert_layer(x, _share(lp, 2, 2 * chip),
                                 **_layer_kw(c, 2, 2 * chip))
        # the reference, given the same share, gives the same part
        part = ref.routed_experts(
            x, _share(lp, 2, 2 * chip), top_k=6,
            routed_scale=c.routed_scaling_factor, expert_offset=2 * chip)
        assert float(jnp.abs(y - shared - part).max()) < 1e-6
        total, rows = total + y - shared, rows + int(n)
    # outputs of order 1e-2; float32 sums in another order
    assert float(jnp.abs(total + shared - whole).max()) < 1e-6
    assert rows == 96 * 6        # every (token, choice) pair on some chip


@pytest.mark.parametrize("favoured,rows", [("held", 64 * 3), ("absent", 0)])
def test_no_token_is_dropped_whatever_the_routing(favoured, rows):
    """A selection bias that sends EVERY token to the same three experts:
    the held ones (the buffer's worst case, all 192 rows on experts 0-2 of
    the 4 held) or absent ones (no row at all). Equal to the reference,
    which computes every held expert for every token."""
    c = DeepseekV3Config.tiny(experts_held=4, **F32)      # 4 of 8, top 3
    lp = _share(_layer_params(DeepseekV3(c)), 4, 0)
    first = 0 if favoured == "held" else 5
    lp["router_bias"] = jnp.zeros(8).at[first:first + 3].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, c.d_model))
    y, n = held_expert_layer(x, lp, tile=8, **_layer_kw(c, 4, 0))
    want = ref.shared_expert(x, lp) + ref.routed_experts(
        x, lp, top_k=3, routed_scale=c.routed_scaling_factor)
    assert int(n) == rows
    assert float(jnp.abs(y - want).max()) < 1e-6
    if favoured == "held":
        assert float(jnp.abs(y - ref.shared_expert(x, lp)).max()) > 1e-3
    # and the gradient reaches x through every row
    g = jax.grad(lambda x: held_expert_layer(
        x, lp, tile=8, **_layer_kw(c, 4, 0))[0].sum())(x)
    g_want = jax.grad(lambda x: (ref.shared_expert(x, lp) + ref.routed_experts(
        x, lp, top_k=3, routed_scale=c.routed_scaling_factor)).sum())(x)
    assert float(jnp.abs(g - g_want).max()) < 1e-5 * float(
        jnp.abs(g_want).max()) + 1e-7


def _softmax_layer(x, lp, top_k, expert_offset=0, gated=True):
    """ISSUE 52's expert sublayer by hand: softmax over ALL experts, the
    top k, weights over the chosen k, no bias, no scale; the shared expert
    times sigmoid(x w_sg) -> (shared, the held experts' part)."""
    p = jax.nn.softmax(x @ lp["w_router"], -1)
    picked, chosen = jax.lax.top_k(p, top_k)
    w = picked / picked.sum(-1, keepdims=True)
    mlp = lambda g, u, d: (jax.nn.silu(x @ g) * (x @ u)) @ d   # noqa: E731
    routed = jnp.zeros_like(x)
    for e in range(lp["e_gate"].shape[0]):
        w_e = jnp.where(chosen == e + expert_offset, w, 0.0).sum(-1)
        routed += w_e[:, None] * mlp(lp["e_gate"][e], lp["e_up"][e],
                                     lp["e_down"][e])
    shared = mlp(lp["s_gate"], lp["s_up"], lp["s_down"])
    if gated:
        shared = shared * jax.nn.sigmoid(x @ lp["s_gate_w"])
    return shared, routed


@pytest.mark.parametrize("held,offset,gated", [(8, 0, True), (2, 4, False)],
                         ids=["whole-gated", "share-ungated"])
def test_the_softmax_route_and_the_gated_shared_expert(held, offset, gated):
    """ISSUE 52: ``score="softmax"`` (no selection bias in the layer's
    parameters at all) and ``s_gate_w`` in them, against the layer by hand:
    the output, the held rows, and the gradients of the input, the router
    and the shared expert's gate."""
    c = DeepseekV3Config.tiny(**F32)                      # 8 experts, top 3
    lp = _share(_layer_params(DeepseekV3(c)), held, offset)
    del lp["router_bias"]
    if gated:
        lp["s_gate_w"] = 0.3 * jax.random.normal(jax.random.PRNGKey(8),
                                                 (c.d_model, 1))
    x = jax.random.normal(jax.random.PRNGKey(6), (64, c.d_model))
    kw = dict(experts_held=held, expert_offset=offset, top_k=3,
              routed_scale=1.0, score="softmax", tile=8)

    def mine(x, lp):
        return held_expert_layer(x, lp, **kw)[0]

    def theirs(x, lp):
        return sum(_softmax_layer(x, lp, 3, offset, gated))

    y, n = held_expert_layer(x, lp, **kw)
    assert float(jnp.abs(y - theirs(x, lp)).max()) < 1e-6
    p = jax.nn.softmax(x @ lp["w_router"], -1)
    chosen = jax.lax.top_k(p, 3)[1]
    assert int(n) == int(((chosen >= offset) & (chosen < offset + held)).sum())
    if held == 8:
        assert int(n) == 64 * 3
    g = jax.grad(lambda x, lp: mine(x, lp).sum(), argnums=(0, 1))(x, lp)
    g_want = jax.grad(lambda x, lp: theirs(x, lp).sum(), argnums=(0, 1))(x, lp)
    names = ("w_router", "s_down") + (("s_gate_w",) if gated else ())
    for got, want in [(g[0], g_want[0])] + [(g[1][k], g_want[1][k])
                                            for k in names]:
        assert float(jnp.abs(want).max()) > 0
        assert float(jnp.abs(got - want).max()) < 2e-5 * float(
            jnp.abs(want).max())


def test_the_softmax_weights_sum_to_one_over_the_chosen():
    """``norm_topk_prob``: the k weights sum to 1 whatever the scores, the
    chosen are the k largest of the softmax (of the logits), and a
    sigmoid-scored call still needs its bias."""
    from ray_tpu.ops.expert_layer import route

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    w_r = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    w, chosen = route(x, w_r, None, top_k=4, routed_scale=1.0,
                      score="softmax")
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    np.testing.assert_array_equal(
        np.sort(np.asarray(chosen), -1),
        np.sort(np.asarray(jax.lax.top_k(x @ w_r, 4)[1]), -1))
    assert bool((w[:, :-1] >= w[:, 1:]).all())       # largest first
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        route(x, w_r, None, top_k=4, routed_scale=1.0, score="tanh")


def test_the_row_buffer_is_static_and_covers_the_worst_case():
    assert buffer_rows(64, 3, 4, 8) == 64 * 3 + 4 * 8
    assert buffer_rows(16384, 6, 16) == (16384 * 6 // 256 + 16) * 256
    assert buffer_rows(10, 6, 2, 8) == (3 + 2) * 8     # 2 held: 2 a token
    # every pair on one expert: its rows are the first 192, the other
    # three experts own one empty tile each
    chosen = jnp.zeros((64, 3), jnp.int32)
    at = sort_rows(chosen, 4, 0, buffer_rows(64, 3, 4, 8), 8)
    assert int(at["n_used"][0]) == 24 + 3
    # ISSUE 65: the pairs' tables are [k, T], a pair is choice * T + token
    assert at["pair_row"].shape == at["pair_held"].shape == (3, 64)
    assert sorted(np.asarray(at["pair_row"]).ravel()) == list(range(192))
    assert list(np.asarray(at["tile_expert"])[:27]) == [0] * 24 + [1, 2, 3]
    assert (np.asarray(at["row_pair"])[192:] == 192).all()
    # one expert's rows stand in the order of their tokens, a token's by
    # choice: row 3 * token + choice holds the pair choice * 64 + token
    np.testing.assert_array_equal(
        np.asarray(at["pair_row"]),
        3 * np.arange(64)[None, :] + np.arange(3)[:, None])
    np.testing.assert_array_equal(
        np.asarray(at["row_pair"])[:192],
        (np.arange(3)[None, :] * 64 + np.arange(64)[:, None]).ravel())


def _token_major_buffer(x, weights, chosen, held, offset, rows, tile):
    """The row buffer as every layer built it before ISSUE 65, in numpy and
    with nothing of the module: a pair is ``token * k + choice``, the pairs
    are sorted (stably) by held expert, an expert's rows start on a tile
    (one tile at least) -> (buffer, a row's weight, ``tile_expert``,
    ``n_used``); a padding row holds the last token's row and weighs 0."""
    k = chosen.shape[1]
    local = chosen - offset
    key = np.where((local >= 0) & (local < held), local, held).ravel()
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=held + 1)[:held]
    last_tile = np.cumsum(np.maximum(-(-counts // tile), 1))
    first_row = np.concatenate([[0], last_tile[:-1]]) * tile
    first_pair = np.cumsum(counts) - counts
    buf = np.broadcast_to(x[-1], (rows, x.shape[1])).copy()
    row_weight = np.zeros((rows,), np.float32)
    for e in range(held):
        pairs = order[first_pair[e]:first_pair[e] + counts[e]]
        at_row = first_row[e] + np.arange(counts[e])
        buf[at_row] = x[pairs // k]
        row_weight[at_row] = weights.ravel()[pairs]
    tile_expert = np.minimum(np.searchsorted(
        last_tile, np.arange(rows // tile), side="right"), held - 1)
    return buf, row_weight, tile_expert, last_tile[-1]


@pytest.mark.parametrize("top_k,held", [(4, 8), (6, 16), (8, 16), (10, 32),
                                        (22, 8)])
def test_the_choice_major_pairs_fill_the_token_major_buffer(top_k, held):
    """ISSUE 65: where a token's slots are not whole tiles the pairs are
    NAMED choice-major (``choice * T + token``, the tables [k, T]: xing4's
    and lfm2moe's top 4, kanana2's 6, qwen3next's 10), where they are (two
    cells' top 8, nemotron3super's 22 compacted to 8 slots) token-major as
    they were, and either way nothing else moved: the row buffer, the rows'
    weights, ``tile_expert`` and ``n_used`` EQUAL the token-major
    construction's element by element, and the layer's output and every
    gradient are the dense per-token layer's."""
    from ray_tpu.ops import expert_layer as el

    t, d, f, e, offset, tile = 64, 32, 16, 64, 8, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(65), 12))
    draw = lambda *s: 0.3 * jax.random.normal(next(keys), s)    # noqa: E731
    lp = {"w_router": 3.0 * draw(d, e), "s_gate_w": draw(d, 1),
          "s_gate": draw(d, f), "s_up": draw(d, f), "s_down": draw(f, d),
          "e_gate": draw(held, d, f), "e_up": draw(held, d, f),
          "e_down": draw(held, f, d)}
    x = jax.random.normal(next(keys), (t, d))
    rows = buffer_rows(t, top_k, held, tile)
    weights, chosen = el.route(x, lp["w_router"], None, top_k=top_k,
                               routed_scale=1.0, score="softmax")
    want = _token_major_buffer(np.asarray(x), np.asarray(weights),
                               np.asarray(chosen), held, offset, rows, tile)
    if top_k > held:
        weights, chosen = el.compact_held(weights, chosen, held, offset)
    at = sort_rows(chosen, held, offset, rows, tile)
    slots = min(top_k, held)
    # 8 slots are whole tiles and stay token-major, [T, k]; the others [k, T]
    assert at.slot_axis == el.slot_axis(slots) == (0 if slots % 8 else 1)
    assert at["pair_row"].shape == at["pair_held"].shape == (
        (slots, t) if slots % 8 else (t, slots))
    got = (el.tokens_to_rows(x, at), el.pairs_to_rows(weights, at),
           at["tile_expert"], at["n_used"][0])
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), b)
    filled = np.asarray(at["row_pair"]) < slots * t
    assert 0 < filled.sum() == int(at["held_rows"]) < slots * t
    assert (want[1] > 0).sum() == filled.sum()
    # a row's pair names the row's token and is sent back to the row
    pair = np.asarray(at["row_pair"])[filled]
    np.testing.assert_array_equal(
        np.asarray(at["pair_row"]).ravel()[pair], np.flatnonzero(filled))
    np.testing.assert_array_equal(
        np.asarray(x)[pair % t if slots % 8 else pair // slots],
        want[0][filled])
    assert np.asarray(at["pair_held"]).ravel()[pair].all()

    kw = dict(experts_held=held, expert_offset=offset, top_k=top_k,
              routed_scale=1.0, score="softmax", tile=tile)
    cot = jnp.cos(7.0 * x[:, ::-1])

    def under_cot(layer):
        """(output, gradients of x and ``lp``) under one cotangent."""
        def fn(x, lp):
            y = layer(x, lp)
            return (y * cot).sum(), y
        (_, y), g = jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1), has_aux=True))(x, lp)
        return [y, g[0]] + [g[1][n] for n in lp]

    mine = under_cot(lambda x, lp: held_expert_layer(x, lp, **kw)[0])
    theirs = under_cot(
        lambda x, lp: sum(_softmax_layer(x, lp, top_k, offset)))
    for got, ref, limit in zip(mine, theirs, [2e-6] + [2e-5] * (1 + len(lp))):
        assert float(jnp.abs(ref).max()) > 0
        assert float(jnp.abs(got - ref).max()) < limit * float(
            jnp.abs(ref).max())


def test_routing_stats_counts_the_held_rows_of_each_expert_layer():
    c = DeepseekV3Config.tiny(experts_held=2, expert_offset=2, **F32)
    model = DeepseekV3(c)
    params = model.init(jax.random.PRNGKey(0))
    toks = _tokens(c.vocab_size)
    rows = np.asarray(jax.jit(model.routing_stats)(params, toks))
    assert rows.shape == (c.n_layer - c.first_k_dense,)
    assert (rows > 0).all() and (rows < toks.size * c.top_k).all()
    # with every expert held, every pair is a row
    every = DeepseekV3(DeepseekV3Config.tiny(**F32))
    rows = jax.jit(every.routing_stats)(every.init(jax.random.PRNGKey(0)),
                                        toks)
    assert (np.asarray(rows) == toks.size * c.top_k).all()


# -- the limits refuse what they must ----------------------------------------


def test_bf16_parameters_fail_the_limits(whole):
    model, params, toks, (loss, grads), _ = whole
    rounded = {n: v.astype(jnp.bfloat16).astype(jnp.float32)
               for n, v in params.items()}
    other, other_grads = jax.jit(jax.value_and_grad(model.loss))(
        rounded, toks, jnp.roll(toks, -1, 1))
    assert abs(float(other) - float(loss)) > 3 * LOSS_LIMIT
    for name in ("lm_head", "moe.e_down", "moe.w_q_rope", "dense.w_gate"):
        g = np.asarray(grads[name])
        assert np.abs(np.asarray(other_grads[name]) - g).max() \
            > 50 * GRAD_LIMIT * np.abs(g).max(), name


def test_a_missing_routed_scaling_factor_fails_the_loss_limit(whole):
    model, params, toks, (loss, _), _ = whole
    unscaled = DeepseekV3(DeepseekV3Config.tiny(routed_scaling_factor=1.0,
                                                **F32))
    other = jax.jit(unscaled.loss)(params, toks, jnp.roll(toks, -1, 1))
    assert abs(float(other) - float(loss)) > 3 * LOSS_LIMIT
