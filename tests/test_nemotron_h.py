"""ISSUE 56: the Nemotron-H shaped model (layers of ONE sublayer each: a
Mamba-2 mixer, grouped-query attention without positions, or sigmoid-routed
squared-ReLU experts in a latent beside a shared expert; of every layer the
chip may hold a share: experts, Mamba-2 groups with their heads, query heads
with their key/value head; ``models/nemotron_h.py`` on ``models/stack.py``)
against the benchmark's plain reference (``benchmark/reference/
nemotron_h.py``: the recurrence token by token), on seeded random weights at
a small size.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums, the chunked form of the scan and the
interpreted flash kernels' online softmax. Read on this seed: the loss by
4.8e-7 (one float32 step at 7.81), the logits by 5.6e-6 at worst, the
gradients by at most 7.3e-6 of a parameter's largest entry. The limits: 5e-6 on the
loss, 1e-4 on the logits, 5e-5 of the largest entry on each gradient (the
limits of ``test_qwen3_next.py``: the same flash kernels, the same order of
sums). Against that (``test_a_wrong_layer_would_fail``) each of:
an expert without its square, routing weights not times ``routed_scale``,
attention scaled by 1 / head_dim, the held experts taken for experts 0-1, and a run's two kinds walked one kind after
the other moves the reference's own loss by more than fifty times the limit.

The fixture holds a SHARE: 1 of 2 Mamba-2 groups (2 heads of 64, state 128:
the scan's kernel route, interpreted here), query heads 2-3 of 4 on
key/value head 1 of 2, experts 2-3 of 8. T = 128 is one block of the flash
kernels and one chunk of the scan.
"""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import NemotronH, NemotronHConfig
from ray_tpu.models.stack import run_params
from ray_tpu.ops import expert_layer as el
from ray_tpu.ops.expert_layer import held_expert_layer

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = importlib.import_module("benchmark.reference.nemotron_h")
granite_ref = importlib.import_module("benchmark.reference.granite_hybrid")

F32 = dict(dtype=jnp.float32)
# init_std 0.2: with 0.02 a tiny model's sublayers are rounding beside the
# residual and nothing they do would show in the loss
SHARE = dict(experts_held=2, expert_offset=2, mamba_groups_held=1,
             mamba_group_offset=1, heads_held=2, head_offset=2, init_std=0.2,
             **F32)
LOSS_LIMIT = 5e-6     # absolute (module docstring)
LOGIT_LIMIT = 1e-4
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


def _init(model, seed=0):
    """``model.init`` with the norms' gains and D off one and the selection
    bias off zero, so that each is seen."""
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), len(params)))
    out = {}
    for n, v in params.items():
        k = next(keys)
        leaf = n.split(".")[-1]
        if leaf in ("norm", "gate_norm", "out_norm", "D"):
            v = v + 0.3 * jax.random.normal(k, v.shape)
        elif leaf == "router_bias":
            v = 0.2 * jax.random.normal(k, v.shape)
        out[n] = v
    return out


@pytest.fixture(scope="module")
def tiny():
    """(model, params, tokens, the program's and the reference's (loss,
    logits, gradients)): one compiled program each."""
    model = NemotronH(NemotronHConfig.tiny(**SHARE))
    params = _init(model)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
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


def test_the_stack_is_the_published_order_in_runs(tiny):
    model, params = tiny[0], tiny[1]
    # init sets A_log of every Mamba-2 layer of a run by the head's number
    assert np.allclose(params["0.mamba.A_log"], model._a_log()[None])
    assert model.config.layer_types == ("moe", "mamba", "moe", "mamba",
                                        "attention")
    assert model.runs == [(("moe", "mamba"), 2), (("attention",), 1)]
    big = NemotronHConfig.nemotron_3_super_120b_a12b()
    assert len(big.layer_types) == 88
    assert [big.layer_types.count(k) for k in ("mamba", "moe", "attention")] \
        == [40, 40, 8]
    period = NemotronH(NemotronHConfig.nemotron_3_super_120b_a12b(
        first_layer=26, n_layer=11, vocab_size=1024))
    assert period.runs == [(("moe", "mamba"), 5), (("attention",), 1)]


def test_logits_and_loss_equal_the_references(tiny):
    model, params, toks, (loss, logits, _), (ref_loss, want, _) = tiny
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(logits - want).max()) < LOGIT_LIMIT
    assert abs(float(loss) - float(ref_loss)) < LOSS_LIMIT


def test_gradients_equal_the_references(tiny):
    _, params, _, (_, _, grads), (_, _, ref_grads) = tiny
    assert set(grads) == set(params)
    for name, g in grads.items():
        if name.endswith("router_bias"):      # a buffer: no gradient
            assert not np.asarray(g).any()
            continue
        want = np.asarray(ref_grads[name])
        top = np.abs(want).max()
        assert top > 0, name
        assert np.abs(np.asarray(g) - want).max() < GRAD_LIMIT * top, name


@pytest.fixture(scope="module")
def one_row(tiny):
    """One row of the fixture's batch and the reference's loss on it (the
    departed references run eagerly: a row is enough to see them)."""
    model, params, toks = tiny[:3]
    return toks[:1], _nll(_ref_logits(model, params, toks[:1]), toks[:1])


@pytest.mark.parametrize("fault", [
    "no_square", "no_routed_scale", "attention_scale", "experts_from_zero",
    "kinds_out_of_order"])
def test_a_wrong_layer_would_fail(tiny, one_row, monkeypatch, fault):
    """The limits are tight enough to see each departure: the reference,
    departed, moves its own loss by more than fifty times LOSS_LIMIT."""
    model, params = tiny[:2]
    toks, ref_loss = one_row
    patch = {}
    if fault == "no_square":          # relu in place of relu^2
        monkeypatch.setattr(
            ref, "_relu2", lambda x, up, down: jnp.maximum(x @ up, 0) @ down)
    elif fault == "no_routed_scale":
        patch["routed_scale"] = 1.0
    elif fault == "attention_scale":  # 1 / head_dim in place of its root
        patch["head_dim"] = model.config.head_dim ** 2
    elif fault == "experts_from_zero":    # the held experts are 2-3, not 0-1
        patch["expert_offset"] = 0
    elif fault == "kinds_out_of_order":   # all of a run's moe, then its mamba
        patch["layer_types"] = ("moe", "moe", "mamba", "mamba", "attention")
    got = _nll(_ref_logits(model, params, toks, **patch), toks)
    assert abs(float(got) - float(ref_loss)) > 50 * LOSS_LIMIT, fault


def test_parameter_count_is_the_references(tiny):
    model, params = tiny[0], tiny[1]
    c = model.config
    sizes = {"hidden_size": c.d_model, "mamba_n_heads": c.mamba_heads_held,
             "mamba_d_head": c.mamba_d_head, "mamba_d_state": c.mamba_d_state,
             "mamba_n_groups": c.groups_held, "mamba_d_conv": c.mamba_d_conv,
             "num_attention_heads": c.q_heads_held,
             "num_key_value_heads": c.kv_heads_held, "head_dim": c.head_dim,
             "moe_latent_size": c.d_latent,
             "moe_intermediate_size": c.d_expert,
             "moe_shared_expert_intermediate_size": c.d_shared,
             "n_routed_experts": c.n_routed_experts,
             "experts_held": c.n_experts_held,
             "layer_types": list(c.layer_types)}
    assert model.num_params() == ref.num_params(sizes, c.padded_vocab) \
        == sum(int(np.prod(v.shape)) for v in params.values())


def test_the_cut_of_the_benchmark_counts_what_its_file_states():
    """The configuration's ``model`` builds the cut whose ``n_params`` the
    file states, and ``sizes`` count the same (shapes only: nothing is
    allocated); every published width is the model's; ``reduced`` lists
    every key that differs from ``published`` and no other."""
    with open(os.path.join(HERE, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b-ep64tp8.json")) as f:
        cfg = json.load(f)
    kw = dict(cfg["model"])
    kw.pop("family")
    model = NemotronH(getattr(NemotronHConfig, kw.pop("preset"))(**kw))
    c, pub, sizes = model.config, cfg["published"], cfg["sizes"]
    assert model.runs == [(("moe", "mamba"), 5), (("attention",), 1)]
    assert c.pattern == pub["hybrid_override_pattern"] \
        == cfg["hybrid_override_pattern"]
    assert pub["hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    assert list(c.layer_types) == sizes["layer_types"]
    assert model.num_params() == cfg["n_params"] == 700865520 \
        == ref.num_params(sizes, c.padded_vocab)
    # every width as published
    assert (c.d_model, c.mamba_d_head, c.mamba_d_state, c.mamba_d_conv,
            c.head_dim, c.d_latent, c.d_expert, c.d_shared,
            c.n_routed_experts, c.top_k, c.routed_scale, c.rms_eps) == (
        pub["hidden_size"], pub["mamba_head_dim"], pub["ssm_state_size"],
        pub["conv_kernel"], pub["head_dim"], pub["moe_latent_size"],
        pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"], pub["routed_scaling_factor"],
        pub["layer_norm_epsilon"])
    assert (c.mamba_n_heads, c.mamba_n_groups, c.n_head, c.n_kv_head) == (
        pub["mamba_num_heads"], pub["n_groups"], pub["num_attention_heads"],
        pub["num_key_value_heads"])
    assert pub["expand"] * pub["hidden_size"] \
        == pub["mamba_num_heads"] * pub["mamba_head_dim"]
    # the share: the held counts, under the keys the roofline readers read
    assert (c.mamba_heads_held, c.groups_held, c.q_heads_held,
            c.kv_heads_held, c.n_experts_held, c.vocab_size) == (
        sizes["mamba_n_heads"], sizes["mamba_n_groups"],
        sizes["num_attention_heads"], sizes["num_key_value_heads"],
        sizes["experts_held"], sizes["vocab_size"]) == (16, 1, 4, 1, 8, 16384)
    assert (cfg["mamba_num_heads"], cfg["n_groups"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (16, 1, 4, 1, 8,
                                                            16384)
    changed = {k for k, v in pub.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == set(cfg["reduced_how"])
    # no width is among them
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in changed)


# -- the share ties to the model ----------------------------------------------


def _layer(params, run, kind, j=0):
    return {k: v[j] for k, v in run_params(params, run)[kind].items()}


@pytest.fixture(scope="module")
def whole():
    """The tiny model UNCUT, its parameters, and an input of one layer."""
    c = NemotronHConfig.tiny(init_std=0.2, **F32)
    params = _init(NemotronH(c), seed=11)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, c.d_model))
    return c, params, x


@pytest.mark.parametrize("kind", ["mamba", "attention", "moe"])
def test_all_shares_add_up_to_the_uncut_layer(whole, kind):
    """THE SHARE TEST. One layer of each kind over all the chips that share
    it: the PROGRAM's outputs on every share's slice of the uncut layer's
    parameters, with what every chip computes alike (the residual; the
    shared expert) counted once, add up to the uncut REFERENCE's layer."""
    c, params, x = whole
    kw = ref.model_kwargs(c)
    with jax.default_matmul_precision("highest"):
        if kind == "mamba":
            x = x[:, :64]      # half a chunk: the scan's plain route (the
            lp = _layer(params, 0, "mamba")    # fixture walks the kernels)
            xn = granite_ref._rmsnorm(x, lp["norm"], c.rms_eps)
            want = ref.mamba_mixer(xn, lp, heads=c.mamba_n_heads,
                                    state=c.mamba_d_state,
                                    groups=c.mamba_n_groups, eps=c.rms_eps)
            total = jnp.zeros_like(x)
            per, n = c.mamba_n_heads // c.mamba_n_groups, c.mamba_d_state
            di = c.mamba_n_heads * c.mamba_d_head
            # a share's layer is the same program whichever group it holds
            layer = jax.jit(NemotronH(NemotronHConfig.tiny(
                mamba_groups_held=1, **F32))._mamba)
            for g in range(c.mamba_n_groups):
                ch = slice(g * per * c.mamba_d_head,
                           (g + 1) * per * c.mamba_d_head)
                hs = slice(g * per, (g + 1) * per)
                # x | B | C: the group's channels of each block
                xbc = np.r_[ch, di + g * n:di + (g + 1) * n,
                            di + (c.mamba_n_groups + g) * n:
                            di + (c.mamba_n_groups + g + 1) * n]
                share = dict(
                    lp, w_z=lp["w_z"][:, ch], w_xbc=lp["w_xbc"][:, xbc],
                    conv_w=lp["conv_w"][:, xbc], conv_b=lp["conv_b"][xbc],
                    w_dt=lp["w_dt"][:, hs], dt_bias=lp["dt_bias"][hs],
                    A_log=lp["A_log"][hs], D=lp["D"][hs],
                    gate_norm=lp["gate_norm"][ch], w_out=lp["w_out"][ch])
                total = total + layer(x, share) - x
                # log(1..H) counts the whole layer's heads
                a_log = NemotronH(NemotronHConfig.tiny(
                    mamba_groups_held=1, mamba_group_offset=g, **F32)
                    )._a_log()
                assert np.allclose(a_log,
                                   np.log(np.arange(per) + g * per + 1))
        elif kind == "attention":
            lp = _layer(params, 1, "attention")
            xn = granite_ref._rmsnorm(x, lp["norm"], c.rms_eps)
            want = ref.attention_mixer(xn, lp, n_head=c.n_head,
                                        n_kv_head=c.n_kv_head,
                                        scale=c.head_dim ** -0.5)
            total = jnp.zeros_like(x)
            hd, per_kv = c.head_dim, c.n_head // c.n_kv_head
            layer = jax.jit(NemotronH(NemotronHConfig.tiny(
                heads_held=1, **F32))._attention)
            for h in range(c.n_head):           # a chip a query head
                q = slice(h * hd, (h + 1) * hd)
                kv = slice(h // per_kv * hd, (h // per_kv + 1) * hd)
                share = dict(lp, w_q=lp["w_q"][:, q], w_k=lp["w_k"][:, kv],
                             w_v=lp["w_v"][:, kv], w_o=lp["w_o"][q])
                assert NemotronHConfig.tiny(
                    heads_held=1, head_offset=h).kv_heads_held == 1
                total = total + layer(x, share) - x
        else:
            lp = _layer(params, 0, "moe")
            xn = granite_ref._rmsnorm(x, lp["norm"], c.rms_eps)
            shared = ref.shared_expert(xn, lp)
            want = shared + ref.routed_experts(
                xn, lp, top_k=kw["top_k"], routed_scale=kw["routed_scale"])
            total, rows = shared, 0
            for chip in range(2):                # 8 experts, 4 a chip
                m = NemotronH(NemotronHConfig.tiny(
                    experts_held=4, expert_offset=4 * chip, **F32))
                held = slice(4 * chip, 4 * chip + 4)
                share = dict(lp, e_up=lp["e_up"][held],
                             e_down=lp["e_down"][held])
                y, n = jax.jit(m._moe)(x, share)
                total, rows = total + (y - x) - shared, rows + int(n)
            assert rows == x.shape[0] * x.shape[1] * c.top_k
            assert float(jnp.abs(want - shared).max()) > 1e-3
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(total - want).max()) < 2e-5 * float(
        jnp.abs(want).max())


def test_a_share_across_key_value_heads_is_refused():
    with pytest.raises(ValueError, match="several key/value heads"):
        NemotronHConfig.tiny(heads_held=2, head_offset=1)
    with pytest.raises(ValueError, match="groups"):
        NemotronHConfig.tiny(mamba_groups_held=2, mamba_group_offset=1)
    # whole key/value heads, or a part of one's readers
    assert NemotronHConfig.tiny(heads_held=4).kv_heads_held == 2
    assert NemotronHConfig.tiny(heads_held=2, head_offset=2).kv_heads_held == 1


# -- the expert layer's kinds ---------------------------------------------------


def _dense_layer(x, p, *, top_k, scale, expert, offset):
    """``held_expert_layer`` by one-hot products over every held expert."""
    s = jax.nn.sigmoid(x @ p["w_router"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], top_k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    held = p["e_up"].shape[0]
    onehot = (chosen[..., None] == jnp.arange(held) + offset)   # [T, k, held]
    w_e = jnp.sum(jnp.where(onehot, w[..., None], 0.0), 1)      # [T, held]

    def mlp(v, pre):
        if expert == "relu2":
            return jnp.einsum("t...f,...fd->t...d", jnp.square(jax.nn.relu(
                jnp.einsum("td,...df->t...f", v, p[pre + "_up"]))),
                p[pre + "_down"])
        return jnp.einsum(
            "t...f,...fd->t...d",
            jax.nn.silu(jnp.einsum("td,...df->t...f", v, p[pre + "_gate"]))
            * jnp.einsum("td,...df->t...f", v, p[pre + "_up"]),
            p[pre + "_down"])

    u = x @ p["w_fc1"] if "w_fc1" in p else x
    routed = jnp.einsum("te,ted->td", w_e, mlp(u, "e"))
    if "w_fc2" in p:
        routed = routed @ p["w_fc2"]
    return mlp(x, "s") + routed


def _layer_inputs(expert, latent, t, d, f, fs, e, held):
    """(x [t, d], the parameters of one expert layer of kind ``expert``
    holding ``held`` of ``e`` experts, in a latent where ``latent``)."""
    width = latent or d
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 16))
    draw = lambda *s: 0.3 * jax.random.normal(next(keys), s)    # noqa: E731
    p = {"w_router": draw(d, e), "router_bias": draw(e),
         "s_up": draw(d, fs), "s_down": draw(fs, d),
         "e_up": draw(held, width, f), "e_down": draw(held, f, width)}
    if expert == "swiglu":
        p.update(s_gate=draw(d, fs), e_gate=draw(held, width, f))
    if latent:
        p.update(w_fc1=draw(d, latent), w_fc2=draw(latent, d))
    return jax.random.normal(next(keys), (t, d)), p


@pytest.mark.parametrize("expert,latent", [
    ("relu2", 32), ("relu2", 0), ("swiglu", 32)])
def test_expert_kinds_and_the_latent_equal_a_dense_computation(expert, latent):
    """Forward and every gradient of ``held_expert_layer`` by kind of expert
    and with or without the latent against the one-hot form, 3 of 8 held."""
    d, f, fs, e, held, off, k = 64, 48, 80, 8, 3, 2, 3
    x, p = _layer_inputs(expert, latent, 32, d, f, fs, e, held)
    kw = dict(top_k=k, expert_offset=off)

    def program(x, p):
        y, rows = held_expert_layer(x, p, experts_held=held,
                                    routed_scale=2.5, expert=expert, **kw)
        return jnp.sum(y * jnp.cos(y)), (y, rows)

    def dense(x, p):
        y = _dense_layer(x, p, top_k=k, scale=2.5, expert=expert, offset=off)
        return jnp.sum(y * jnp.cos(y)), y

    with jax.default_matmul_precision("highest"):
        (_, (y, rows)), g = jax.jit(jax.value_and_grad(
            program, (0, 1), has_aux=True))(x, p)
        (_, want), gw = jax.jit(jax.value_and_grad(
            dense, (0, 1), has_aux=True))(x, p)
    assert 0 < int(rows) < 32 * k
    assert float(jnp.abs(y - want).max()) < 1e-4 * float(jnp.abs(want).max())
    for got, ref_g, name in [(g[0], gw[0], "x")] + [
            (g[1][n], gw[1][n], n) for n in p]:
        if name == "router_bias":
            assert not np.asarray(got).any()
            continue
        top = float(jnp.abs(ref_g).max())
        assert top > 0, name
        assert float(jnp.abs(got - ref_g).max()) < 1e-4 * top, name


def test_an_unknown_kind_of_expert_is_refused():
    with pytest.raises(ValueError, match="swiglu or relu2"):
        held_expert_layer(jnp.zeros((8, 4)), {"w_router": jnp.zeros((4, 2))},
                          experts_held=1, expert_offset=0, top_k=1,
                          routed_scale=1.0, expert="gelu")


def test_the_swiglu_path_is_the_program_it_was():
    """The layer the four families of before ISSUE 56 call (gated SiLU
    experts on the model width) lowers to the text it lowered to when
    ``_gated`` was called by name: the same operations in the same order
    (since ISSUE 68 by name for the shared expert alone: the routed
    experts' call is the kernel pair's, composed by hand here as the layer
    composes it)."""
    d, f, e, held = 32, 16, 8, 3    # top 3 of 3 held: not compacted (ISSUE 57)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 12))
    draw = lambda *s: jax.random.normal(next(keys), s)          # noqa: E731
    p = {"w_router": draw(d, e), "router_bias": draw(e),
         "s_gate": draw(d, f), "s_up": draw(d, f), "s_down": draw(f, d),
         "e_gate": draw(held, d, f), "e_up": draw(held, d, f),
         "e_down": draw(held, f, d)}
    x = draw(64, d).astype(jnp.bfloat16)
    kw = dict(experts_held=held, expert_offset=2, top_k=3, routed_scale=2.0)

    def before(x, p, tile=el.ROW_TILE):
        """``held_expert_layer`` as PR 55 had it."""
        t, dt = x.shape[0], x.dtype
        rows = el.buffer_rows(t, kw["top_k"], held, tile)
        shared = el._gated(x, p["s_gate"].astype(dt), p["s_up"].astype(dt),
                           p["s_down"].astype(dt), jnp.dot)
        weights, chosen = el.route(x, p["w_router"], p.get("router_bias"),
                                   top_k=kw["top_k"],
                                   routed_scale=kw["routed_scale"],
                                   score="sigmoid")
        at = el.sort_rows(chosen, held, kw["expert_offset"], rows, tile)
        held_rows = at.pop("held_rows")
        buf = el.tokens_to_rows(x, at)
        row_weight = el.pairs_to_rows(weights, at)
        # ISSUE 68: the routed experts' first half is the kernel pair
        # (``_held_mlp``), for both kinds; the shared expert is ``_gated``
        y = el._held_mlp("swiglu", buf, p, row_weight, at, tile)
        return shared + el.rows_to_tokens(y, at), held_rows

    # the forward's text: the backward is autodiff's of the same operations
    texts = [jax.jit(lambda x, p: fn(x, p)).lower(x, p).as_text()
             for fn in (lambda x, p: held_expert_layer(x, p, **kw), before)]
    assert texts[0] == texts[1] and len(texts[0]) > 10000


# -- ISSUE 57: the pair domain is what a token can hold -----------------------

PAIR_TILE = 8
PAIR_TOKENS = 64


def _rows_by_hand(x, p, *, top_k, held, offset, scale, compact):
    """The layer's way into the row buffer, its parts called one by one on
    the [T, k] pair domain or, ``compact``, on ``compact_held``'s [T, held]
    -> (``sort_rows``' dict, the pairs' weights and experts, the buffer)."""
    rows = el.buffer_rows(x.shape[0], top_k, held, PAIR_TILE)
    weights, chosen = el.route(x, p["w_router"], p["router_bias"],
                               top_k=top_k, routed_scale=scale)
    if compact:
        weights, chosen = el.compact_held(weights, chosen, held, offset)
    assert chosen.shape == weights.shape == (
        x.shape[0], held if compact else top_k)
    at = el.sort_rows(chosen, held, offset, rows, PAIR_TILE)
    u = jnp.dot(x, p["w_fc1"].astype(x.dtype)) if "w_fc1" in p else x
    return at, weights, chosen, el.tokens_to_rows(u, at)


def _uncompacted(x, p, *, expert, **kw):
    """``held_expert_layer`` composed by hand on the [T, k] pair domain, as
    every layer was before ISSUE 57 -> (output, held rows, the row buffer,
    ``n_used``, ``row_pair``)."""
    at, weights, _, buf = _rows_by_hand(x, p, compact=False, **kw)
    y = el._mlp(expert, buf, p, "e",
                lambda a, w: el.grouped_matmul(a, w, at["tile_expert"],
                                               at["n_used"], PAIR_TILE),
                el.pairs_to_rows(weights, at))
    routed = el.rows_to_tokens(y, at)
    if "w_fc2" in p:
        routed = jnp.dot(routed, p["w_fc2"].astype(x.dtype))
    return (el._mlp(expert, x, p, "s", jnp.dot) + routed, at["held_rows"],
            buf, at["n_used"], at["row_pair"])


def _compacted_buffer(x, p, **kw):
    """The row buffer, ``n_used`` and ``row_pair`` of the compacted path and
    the slots each token fills."""
    at, _, chosen, buf = _rows_by_hand(x, p, compact=True, **kw)
    return buf, at["n_used"], at["row_pair"], jnp.sum(chosen >= 0, axis=1)


@pytest.mark.parametrize("top_k,held,offset,expert,latent,routing", [
    shape + kind + ("drawn",)
    for shape in [(22, 8, 0), (9, 4, 8), (5, 2, 2)]
    for kind in [("relu2", 32), ("relu2", 0), ("swiglu", 32), ("swiglu", 0)]
] + [(22, 8, 0, "relu2", 32, "every"), (22, 8, 0, "relu2", 32, "none")])
def test_the_compacted_pair_domain_is_the_layer_it_was(
        top_k, held, offset, expert, latent, routing):
    """ISSUE 57: with a token's held choices compacted to ``held`` slots the
    row buffer's filled rows and the held rows EQUAL the [T, k] path's, and
    the output and every gradient equal it in float32 (the stable sort orders
    an expert's rows by token either way). ``every`` / ``none``: the selection
    bias makes every token choose every held expert (all slots full, the
    buffer full: no pair dropped) or none (all slots empty, one empty tile an
    expert)."""
    t, d, e = PAIR_TOKENS, 32, 32 if top_k > 5 else 8
    x, p = _layer_inputs(expert, latent, t, d, 16, 24, e, held)
    if routing != "drawn":
        here = (jnp.arange(e) >= offset) & (jnp.arange(e) < offset + held)
        p["router_bias"] = jnp.where(
            here, {"every": 10.0, "none": -10.0}[routing], 0.0)
    kw = dict(top_k=top_k, held=held, offset=offset, scale=2.5)
    # one cotangent for both, so that a gradient differs by its own sums alone
    cot = jnp.cos(7.0 * x[:, ::-1])

    def program(x, p):
        y, rows = held_expert_layer(
            x, p, experts_held=held, expert_offset=offset, top_k=top_k,
            routed_scale=2.5, expert=expert, tile=PAIR_TILE)
        return jnp.sum(y * cot), (y, rows)

    def before(x, p):
        y, *rest = _uncompacted(x, p, expert=expert, **kw)
        return jnp.sum(y * cot), (y, *rest)

    with jax.default_matmul_precision("highest"):
        (_, (y, rows)), g = jax.jit(jax.value_and_grad(
            program, (0, 1), has_aux=True))(x, p)
        (_, (want, want_rows, want_buf, want_used, want_row_pair)), gw = (
            jax.jit(jax.value_and_grad(before, (0, 1), has_aux=True))(x, p))
        buf, n_used, row_pair, full = jax.jit(
            lambda x, p: _compacted_buffer(x, p, **kw))(x, p)
    # the rows that hold a pair (ISSUE 62: a padding row holds a copy of a
    # token's row, not zeros; tests/test_expert_layer_padding.py)
    filled, want_filled = (np.asarray(pair) < n for pair, n in (
        (row_pair, t * held), (want_row_pair, t * top_k)))
    np.testing.assert_array_equal(filled, want_filled)
    np.testing.assert_array_equal(np.asarray(buf)[filled],
                                  np.asarray(want_buf)[filled])
    assert int(n_used[0]) == int(want_used[0])
    assert int(rows) == int(want_rows) == int(full.sum()) == filled.sum()
    if routing == "drawn":
        assert 0 < int(rows) < t * held and np.asarray(buf)[filled].any()
        assert held >= int(full.max()) > int(full.min())
    elif routing == "every":
        assert int(rows) == t * held and (np.asarray(full) == held).all()
        assert int(n_used[0]) * PAIR_TILE == t * held
    else:
        assert int(rows) == 0 and not np.asarray(full).any()
        assert int(n_used[0]) == held
    # a token's rows summed over 8 slots or over 22 pairs, 14 of them zero:
    # the same terms in another tree, read 1.8e-7 of the largest entry apart
    # at most (one float32 step) at top 22 and 0 at top 9 and top 5
    for got, ref_g, name in [(y, want, "y"), (g[0], gw[0], "x")] + [
            (g[1][n], gw[1][n], n) for n in p]:
        top = float(jnp.abs(ref_g).max())
        if name != "router_bias" and (routing != "none"
                                      or name[:2] not in ("e_", "w_")):
            assert top > 0, name
        # what is computed from the buffer's rows alone is EQUAL
        limit = 1e-6 * top if name in ("y", "x", "w_fc1", "w_fc2") else 0.0
        assert float(jnp.abs(got - ref_g).max()) <= limit, name


@pytest.mark.parametrize("top_k,held,compacts", [
    (6, 16, False), (8, 8, False), (9, 8, True)])
def test_the_compaction_is_traced_only_where_more_are_chosen_than_held(
        top_k, held, compacts, monkeypatch):
    """ISSUE 57: the choice is ``top_k > experts_held``, two static arguments.
    Where it is not so (kanana2's 6 of 16 held, kimilinear's 8 of 8) the
    event's ``pair_slots`` is ``top_k``, ``compact_held`` is never entered and
    the layer's jaxpr holds no cumsum over the k axis."""
    from ray_tpu.perf.recorder import get_recorder

    entered = []
    compact = el.compact_held
    monkeypatch.setattr(el, "compact_held",
                        lambda *a: entered.append(1) or compact(*a))
    x, p = _layer_inputs("relu2", 32, PAIR_TOKENS, 32, 16, 24, 32, held)
    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        text = str(jax.make_jaxpr(lambda x, p: held_expert_layer(
            x, p, experts_held=held, expert_offset=8, top_k=top_k,
            routed_scale=2.5, expert="relu2", tile=PAIR_TILE))(x, p))
        event = [ev for ev in rec.snapshot()
                 if ev["kind"] == "rtpu.ops.expert_layer"][-1]["data"]
    finally:
        rec.enabled = was
    assert event["top_k"] == top_k
    assert event["pair_slots"] == (held if compacts else top_k)
    # ISSUE 65: 8 slots, whole tiles, stay token-major; kanana2's 6 do not
    assert event["slot_axis"] == (0 if event["pair_slots"] % 8 else 1)
    assert bool(entered) == compacts
    # ``sort_rows``' own cumsums run over its [held] tables: axis 0
    assert "cumsum[axis=0" in text
    assert ("cumsum[axis=1" in text) == compacts


def test_routing_stats_counts_the_held_rows_of_every_expert_layer(tiny):
    model, params, toks, _, _ = tiny
    rows = np.asarray(jax.jit(model.routing_stats)(params, toks))
    assert rows.shape == (2,)        # the two expert layers of EMEM*
    assert (rows > 0).all() and (rows < toks.size * model.config.top_k).all()
    # the first layer's by hand: the top 3 of 8 that name experts 2 or 3
    lp = _layer(params, 0, "moe")
    xn = granite_ref._rmsnorm(params["wte"][toks], lp["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(xn @ lp["w_router"])
    _, chosen = jax.lax.top_k(s + lp["router_bias"], 3)
    assert rows[0] == int(((chosen >= 2) & (chosen < 4)).sum())
