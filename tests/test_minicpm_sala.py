"""ISSUE 69: the MiniCPM-SALA shaped model (three Lightning linear-attention
layers, a head's own q and k under a constant decay, rotated, to one
InfLLM-v2 layer over the blocks a query's key/value group selects; per-head
q/k norms, sigmoid output gates, muP scales, a share of the heads, of the
key/value groups and of the MLP's hidden units held by offset;
``models/minicpm_sala.py`` on ``models/stack.py``) against the benchmark's
plain reference (``benchmark/reference/minicpm_sala.py``: the recurrence
token by token, the selection by ``lax.top_k`` on block scores) on seeded
random weights at a small size: heads of the published 128, one period
(attention, then three Lightning layers), four heads on two key/value heads
of which the model holds heads 2-3 (the second group), half the MLP's hidden
units from unit 64, a row of 256 (longer than the preset's ``dense_len`` 64:
the selection takes 6 of up to 16 blocks of 16 keys) and a row of 64.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums, the chunked form of the recurrence and
the interpreted kernels' online softmax. Read on this seed at the row of
256: the loss by 4.8e-7 (at 6.34), the logits by 7.2e-7 at worst (the
largest is 1.97), the gradients by at most 1.7e-5 of a parameter's largest
entry. The limits: 5e-6 on the loss, 1e-5 on the logits, 1e-4 of the largest
entry on each gradient (six to fourteen times what was read). Against that,
on the same seed (``test_a_wrong_layer_would_fail``), each departure moves
the reference's own LOGITS by more than a hundred times their limit: a
state rounded to bf16 after every token (9.9e-3, the least), the decays of
heads 0-1 in place of 2-3's (0.23), the depth scale left at 1 (1.2), the
embedding's 12 left out (2.1), the head's division left out (5.9), a
selection of 4 blocks in place of 6 (``topk`` alone, the window's blocks not
beside it: 3.9e-2) and a window of one block (3.3e-2). (The mean LOSS is the
wrong yardstick for these: 512 tokens' terms average a departure of one
mixer away, the bf16 state to 8.6e-6.)

The selection is compared EXACTLY (both sides float32 here): no near-tie of
two block scores lies inside the float32 noise on this seed, which the
logits' agreement shows (a selection two blocks short moves a logit by
3.9e-2). On the chip the program's bf16 scores do flip near-ties; what that
costs is inside the tolerance the harness measures from the reference's own
bf16 run (``reference/keye_vl2.py``'s treatment).

**The shares add up** (``test_the_shares_add_up``): with 4 heads, 2
key/value heads and 2 shares, both mixers' and the MLP's outputs of the two
shares add up to the uncut reference's; norms and the residual are counted
once. Nothing needs completing over the shares: every norm is a head's, the
selection's sum a group's.
"""
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import MiniCPMSALA, MiniCPMSALAConfig
from ray_tpu.models.stack import run_params
from ray_tpu.ops import chunked_head_nll, cross_entropy_loss, rope_cache

ref = importlib.import_module("benchmark.reference.minicpm_sala")

F32 = dict(dtype=jnp.float32)
# init_std 0.2: with 0.02 a tiny model's mixers are rounding beside the
# residual and nothing they do would show in the loss
TINY = dict(heads_held=2, head_offset=2, ff_held=64, ff_offset=64,
            init_std=0.2, **F32)
LOSS_LIMIT = 5e-6     # absolute, on a loss of 6.34 (module docstring)
LOGIT_LIMIT = 1e-5    # absolute, on logits up to 1.97
GRAD_LIMIT = 1e-4     # of the gradient's largest entry


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


def _tokens(vocab, seed=1, shape=(2, 256)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def _init(model, seed=0):
    """``model.init`` with every norm's gain off one, so that a gain left
    out or applied twice is seen."""
    params = model.init(jax.random.PRNGKey(seed))
    for i, name in enumerate(sorted(params)):
        if "norm" in name:
            params[name] = 1.0 + 0.1 * jax.random.normal(
                jax.random.PRNGKey(100 + i), params[name].shape)
    return params


_SINCE = [0.0]
_WANT = [None]        # the reference's logits on the fixture's row, made once


def _events(kind):
    from ray_tpu.perf import recorder

    return [dict(e["data"]) for e in recorder.get_recorder().snapshot()
            if e["kind"] == kind and e["ts"] >= _SINCE[0]]


def _both(model, params, toks):
    mine = jax.jit(jax.value_and_grad(model.loss))(
        params, toks, jnp.roll(toks, -1, 1))
    theirs = jax.jit(jax.value_and_grad(
        lambda p: _nll(_ref_logits(model, p, toks), toks)))(params)
    return mine, theirs


@pytest.fixture(scope="module")
def tiny():
    """(model, params, tokens, the program's logits, loss and gradients,
    the reference's) at a row of 256: the selection decides."""
    _SINCE[0] = time.time()
    model = MiniCPMSALA(MiniCPMSALAConfig.tiny(**TINY))
    params = _init(model)
    toks = _tokens(model.config.vocab_size)
    logits = jax.jit(model.apply)(params, toks)
    _WANT[0] = _ref_logits(model, params, toks)
    return (model, params, toks, logits) + _both(model, params, toks)


def test_the_stack_is_the_published_order_in_runs(tiny):
    model = tiny[0]
    assert model.runs == [(("attn",), 1), (("lightning",), 3)]
    whole = MiniCPMSALAConfig()
    assert whole.n_layer == 32
    assert whole.layer_types.count("attn") == 8
    assert whole.layer_types[:10] == ("attn",) + ("lightning",) * 8 \
        + ("attn",)
    assert whole.layer_types[-3:] == ("attn",) * 3


def test_the_cells_cut_has_the_parameters_the_issue_counts():
    cut = MiniCPMSALA(MiniCPMSALAConfig.minicpm_sala_9b(
        n_layer=4, heads_held=16, ff_held=8192, vocab_size=9181))
    shapes = cut._shapes()
    per = lambda kind, names: sum(                             # noqa: E731
        int(np.prod(shapes[n][0])) for n in shapes
        if f".{kind}." in n and n.rsplit(".", 1)[1] in names) // \
        dict((p[0], n) for p, n in cut.runs)[kind]
    mixer = ("w_q", "w_k", "w_v", "w_g", "w_o")
    assert per("attn", mixer) == 26_214_400
    assert per("lightning", mixer) == 41_943_040
    assert per("attn", ("w_gate", "w_up", "w_down")) == 100_663_296
    assert cut.config.padded_vocab == 9216
    assert cut.num_params() == 630_232_448
    sizes = {"hidden_size": 4096, "intermediate_held": 8192,
             "num_attention_heads": 16, "num_key_value_heads": 1,
             "head_dim": 128, "lightning_nh": 16, "lightning_head_dim": 128,
             "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3}
    assert ref.num_params(sizes, 9216) == cut.num_params()
    c = cut.config
    assert (c.sparse_blocks, c.sparse_window // c.sparse_block, c.kv_heads,
            c.group) == (96, 32, 1, 16)
    assert abs(c.residual_scale - 0.24749) < 1e-5
    # each held head keeps its PUBLISHED slope: head 0's at layer 1
    assert abs(float(c.log_decays(1)[0])
               + 2 ** -0.25 * (1 - 1 / 31 + 1e-5)) < 1e-7
    assert abs(float(np.exp(c.log_decays(0)[0])) - np.exp(-0.8409)) < 1e-4


def test_logits_equal_the_references(tiny):
    logits, want = tiny[3], _WANT[0]
    assert float(jnp.abs(logits - want).max()) < LOGIT_LIMIT
    assert float(jnp.abs(want).max()) > 0.5       # logits of order 1


def test_loss_equals_the_references(tiny):
    (loss, _), (ref_loss, _) = tiny[4:]
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def _grads_agree(params, grads, ref_grads):
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        scale = np.abs(r).max()
        assert scale > 0, name
        assert np.abs(g - r).max() < GRAD_LIMIT * scale, name


def test_gradients_equal_the_references(tiny):
    """Every parameter, through both kernel routes; the events say which
    programs ran: the recurrence's kernels with groups == heads under a
    constant decay, the masked kernels over 6 blocks a query's group
    selected."""
    params, (_, grads), (_, ref_grads) = tiny[1], tiny[4], tiny[5]
    scans = {(e["route"], e["groups"], e["heads"], e["head_dim"], e["state"],
              e["decay"], e["chunk"])
             for e in _events("rtpu.ops.lightning.path")}
    assert scans == {("kernel", 2, 2, 128, 128, "constant", 128)}
    picks = {(e["route"], e["select_by"], e["block"], e["blocks"],
              e["init_blocks"], e["local_blocks"], tuple(e["pool"]),
              e["heads"], e["kv_heads"], e["saved"])
             for e in _events("rtpu.ops.sparse_attention")}
    assert picks == {("masked_flash", "block", 16, 6, 1, 2, (8, 4), 2, 1,
                      "block_mask_int8")}
    _grads_agree(params, grads, ref_grads)


def test_a_row_under_dense_len_equals_the_references(tiny):
    """64 tokens: the attention layer is causal attention over everything
    (route ``causal_flash``), the head and loss one chunk."""
    model, params = tiny[:2]
    toks = _tokens(model.config.vocab_size, seed=2, shape=(2, 64))
    _SINCE[0] = time.time()
    (loss, grads), (ref_loss, ref_grads) = _both(model, params, toks)
    assert {e["route"] for e in _events("rtpu.ops.sparse_attention")} == {
        "causal_flash"}
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT
    _grads_agree(params, grads, ref_grads)


def test_the_chunked_heads_loss_and_gradients_equal_the_whole_rows(tiny):
    """The model walks 512 tokens a step as 8 chunks of the preset's 64
    (its agreement with the reference, whose head is the whole row's, is the
    tests above); ``ops.chunked_head_nll`` itself gives the whole-row form's
    loss and gradients to float32 rounding, in 1, 4 and 8 chunks."""
    model, _, toks = tiny[:3]
    assert model.head_chunks(toks.size) == 8
    assert model.head_chunks(96) == 1         # no whole chunks: one
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    head = jax.random.normal(keys[0], (512, 64), jnp.float32)
    x = jax.random.normal(keys[1], (2, 256, 64), jnp.float32)
    targets = jnp.roll(toks, -1, 1)

    def whole(head, x):
        return cross_entropy_loss(jnp.einsum("bsd,vd->bsv", x, head), targets)

    want, want_grads = jax.value_and_grad(whole, argnums=(0, 1))(head, x)
    for chunks in (1, 4, 8):
        loss, grads = jax.value_and_grad(
            lambda h, x: chunked_head_nll(h, x, targets, chunks),
            argnums=(0, 1))(head, x)
        assert abs(float(loss) - float(want)) < 2e-6
        for g, w in zip(grads, want_grads):
            assert float(jnp.abs(g - w).max()) < 1e-5 * float(
                jnp.abs(w).max())
    with pytest.raises(ValueError, match="whole chunks"):
        chunked_head_nll(head, x, targets, 7)


@pytest.mark.parametrize("bad", [
    dict(heads_held=3),                   # no whole key/value groups
    dict(heads_held=2, head_offset=1),    # a share starts at a group
    dict(heads_held=2, head_offset=4),    # past the layer's heads
    dict(ff_held=64, ff_offset=96),       # past the MLP's hidden units
    dict(layer_types=("attn", "mamba")),  # a kind the family does not have
])
def test_a_share_that_is_no_share_is_refused(bad):
    with pytest.raises(ValueError):
        MiniCPMSALAConfig.tiny(**bad)


# -- what the limits are for -------------------------------------------------

FAULTS = {
    # a state rounded to bf16 after every token
    "bf16_state": lambda mp: mp.setattr(ref, "lightning_scan", _bf16_scan),
    # the decays of heads 0-1 in place of the held 2-3's
    "another_heads_decay": lambda mp: {"head_offset": 0},
    # the depth scale left at 1: scale_depth = sqrt(32)
    "no_depth_scale": lambda mp: {"scale_depth": 32 ** 0.5},
    "no_embedding_scale": lambda mp: {"scale_emb": 1.0},
    # the head's input not divided by d_model / dim_model_base
    "no_head_scale": lambda mp: {"dim_model_base": 64},
    # topk alone, the window's blocks not counted beside it
    "fewer_blocks": lambda mp: {"blocks": 4},
    "no_local_window": lambda mp: {"local_blocks": 1},
}


def _bf16_scan(q, k, v, log_decay, scale):
    b, t, h, d = q.shape
    lam = jnp.exp(log_decay.astype(jnp.float32))[None, :, None, None]
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731

    def token(s, tok):
        q_t, k_t, v_t = tok
        s = (lam * s + k_t[..., :, None] * v_t[..., None, :]).astype(
            jnp.bfloat16).astype(jnp.float32)
        return s, jnp.sum(s * q_t[..., None], axis=-2) * scale

    _, o = jax.lax.scan(token, jnp.zeros((b, h, d, d), jnp.float32),
                        (f32(q), f32(k), f32(v)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_layer_would_fail(tiny, fault, monkeypatch):
    """Each departure moves the REFERENCE's own logits by more than a
    hundred times the limit the program's are held to."""
    model, params, toks = tiny[:3]
    want = _WANT[0]
    patch = FAULTS[fault](monkeypatch) or {}
    wrong = _ref_logits(model, params, toks, **patch)
    assert float(jnp.abs(wrong - want).max()) > 100 * LOGIT_LIMIT, fault


# -- the shares add up -------------------------------------------------------

def test_the_shares_add_up():
    """4 heads on 2 key/value heads and 128 hidden units in 2 shares (a
    group and 64 units each), a row of 128 (8 blocks of 16 keys, of which a
    query takes 6: the selection decides): the two shares' outputs of the attention
    mixer, of a Lightning mixer (layer 2, each head with its published
    decay) and of the MLP add up to the uncut reference's; the norm before
    each and the residual are counted once."""
    base = dict(init_std=0.2, **F32)
    whole = MiniCPMSALA(MiniCPMSALAConfig.tiny(**base))
    shares = [MiniCPMSALA(MiniCPMSALAConfig.tiny(
        heads_held=2, head_offset=2 * i, ff_held=64, ff_offset=64 * i,
        **base)) for i in range(2)]
    params = _init(whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 64), jnp.float32)
    kw = ref.model_kwargs(whole.config)
    rope = rope_cache(128, 128, whole.config.rope_base)
    lp_of = lambda p, run, kind, i: {                          # noqa: E731
        n: v[i] for n, v in run_params(p, run)[kind].items()}
    with jax.default_matmul_precision("highest"):
        lp = lp_of(params, 0, "attn", 0)
        want_attn = ref.attention_mixer(
            ref.rms(x, lp["norm1"], 1e-6), lp, n_kv_held=2,
            **{n: kw[n] for n in ("block", "blocks", "init_blocks",
                                  "local_blocks", "pool", "dense_len",
                                  "eps")})
        lp2 = lp_of(params, 1, "lightning", 1)         # published layer 2
        want_light = ref.lightning_mixer(
            ref.rms(x, lp2["norm1"], 1e-6), lp2, ref.log_decays(
                4, n_head=4, head_offset=0, layer=2, n_layer=32),
            rope_base=kw["rope_base"], eps=1e-6)
        want_mlp = ref.mlp(ref.rms(x, lp2["norm2"], 1e-6), lp2)
    got_attn = got_light = got_mlp = 0.0
    for share in shares:
        held = share.held_share(params)
        assert held["0.attn.w_k"].shape[-1] == 128      # one key/value head
        a, l2 = lp_of(held, 0, "attn", 0), lp_of(held, 1, "lightning", 1)
        got_attn = got_attn + share._attn_mixer(x, a)
        got_light = got_light + share._lightning_mixer(
            x, l2, jnp.asarray(share.config.log_decays(2)), rope)
        got_mlp = got_mlp + share._mlp(x, l2)
    for got, want in ((got_attn, want_attn), (got_light, want_light),
                      (got_mlp, want_mlp)):
        assert float(jnp.abs(want).max()) > 0.1
        assert float(jnp.abs(got - want).max()) \
            < 2e-5 * float(jnp.abs(want).max())
    # and one share alone is NOT the layer
    assert float(jnp.abs(shares[0]._mlp(x, lp_of(
        shares[0].held_share(params), 1, "lightning", 1)) - want_mlp).max()) \
        > 0.1 * float(jnp.abs(want_mlp).max())


# -- a share's parameters, by name -------------------------------------------

@pytest.mark.parametrize("name, shape", [
    ("0.attn.w_q", (1, 64, 256)), ("0.attn.w_g", (1, 64, 256)),
    ("0.attn.w_k", (1, 64, 128)), ("0.attn.w_v", (1, 64, 128)),
    ("0.attn.w_o", (1, 256, 64)), ("0.attn.q_norm", (1, 128)),
    ("0.attn.w_gate", (1, 64, 64)), ("0.attn.w_down", (1, 64, 64)),
    ("1.lightning.w_q", (3, 64, 256)), ("1.lightning.w_k", (3, 64, 256)),
    ("1.lightning.w_v", (3, 64, 256)), ("1.lightning.w_o", (3, 256, 64)),
    ("1.lightning.o_norm", (3, 128)), ("1.lightning.w_up", (3, 64, 64)),
    ("1.lightning.norm1", (3, 64)), ("wte", (512, 64)),
])
def test_a_shares_parameters_are_the_held_columns_and_rows(name, shape):
    """``held_share`` of a whole layer's parameters (4 heads on 2 key/value
    heads, 128 hidden units) for the second group and the second half of
    the MLP: the held heads' columns (rows of ``W_o``, ``W_down``), ONE
    key/value head, norms, gains and the vocabulary whole; and they are the
    shapes the share's own ``init`` draws."""
    whole = MiniCPMSALA(MiniCPMSALAConfig.tiny(**F32))
    share = MiniCPMSALA(MiniCPMSALAConfig.tiny(**TINY))
    cut = jax.eval_shape(share.held_share,
                         jax.eval_shape(whole.init, jax.random.PRNGKey(0)))
    drawn = jax.eval_shape(share.init, jax.random.PRNGKey(0))
    assert cut[name].shape == drawn[name].shape == shape
    assert set(cut) == set(drawn)


@pytest.mark.parametrize("offset, layer, head, slope", [
    (0, 0, 0, 2 ** -0.25), (0, 1, 0, 2 ** -0.25), (0, 3, 15, 2 ** -4.0),
    (16, 1, 0, 2 ** -4.25), (16, 31, 15, 2 ** -8.0)])
def test_each_held_head_keeps_its_published_decay(offset, layer, head, slope):
    """log lambda = -2^(-8 (h + 1) / 32) (1 - l / 31 + 1e-5) for the
    PUBLISHED head h = offset + head and layer l, whatever share holds it;
    the reference's rule gives the same number."""
    c = MiniCPMSALAConfig.minicpm_sala_9b(n_layer=4, heads_held=16,
                                          head_offset=offset)
    want = -slope * (1 - layer / 31 + 1e-5)
    assert abs(float(c.log_decays(layer)[head]) - want) < 1e-7
    theirs = ref.log_decays(16, n_head=32, head_offset=offset, layer=layer,
                            n_layer=32)
    assert abs(float(theirs[head]) - want) < 1e-6
