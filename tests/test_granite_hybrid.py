"""ISSUE 36: the Granite-4.0-H shaped model (Mamba-2 layers through
``ops.ssd_scan``, a grouped-query attention layer without positions, a
stack of unlike layers walked as runs, four multipliers, a vocabulary
slice) against the benchmark's plain reference
(``benchmark/reference/granite_hybrid.py``: the one copy, its recurrence
one token at a time), on seeded random weights at a small size. Pallas
kernels run in interpret mode here.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums only (the program's scan is chunked,
the reference's is not). Read on this seed (``init_std`` 0.2, a loss of
6.26): the losses are the same float32, the logits differ by 1.8e-7 of
the largest, the gradients by at most 5.3e-6 of a parameter's largest
entry (``A_log`` and ``dt_bias`` of the first run: sums of a few hundred
terms of either sign). Parameters rounded to bf16 move the loss by 1.6e-5
and every gradient by 2.1e-3 to 1.8e-2 of its largest entry
(``test_bf16_parameters_would_fail``). The limits lie between: 1.5e-6 on
the loss (three float32 steps at 6.26; a tenth of what bf16 parameters
move it), 1e-4 of the largest entry on the logits and on each gradient
(twenty times the worst reading, a twentieth of the least that bf16 moves).
"""
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import GraniteHybrid, GraniteHybridConfig
from ray_tpu.models.granite_hybrid import stack_runs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_granite_hybrid", "benchmark", "reference",
            "granite_hybrid.py")
# init_std 0.2 (the rehearsal's): at 0.02 and d = 64 the logits are so
# small that the loss is log(V) whatever the layers do
F32 = dict(dtype=jnp.float32, init_std=0.2)
LOSS_LIMIT = 1.5e-6   # absolute, on a loss of 6.26 (module docstring)
REL_LIMIT = 1e-4      # of the largest entry: logits, each gradient


def _ref_logits(model, params, tokens):
    h = ref.hidden(params, tokens, jnp.float32,
                   **ref.model_kwargs(model.config))
    return ref.head(params, h, jnp.float32)


def _ref_loss(model, params, tokens, with_logits=False):
    logits = _ref_logits(model, params, tokens)
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    loss = jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0])
    return (loss, logits) if with_logits else loss


def _tokens(vocab, seed=1, shape=(2, 128)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


@pytest.fixture(scope="module")
def tiny():
    """The ``tiny`` preset (runs of 2, 1 and 1) on one seeded batch: the
    model, its parameters and tokens, the program's jitted loss-and-
    gradients (``step``) with what it gave, its logits, and the
    reference's loss, gradients and logits."""
    model = GraniteHybrid(GraniteHybridConfig.tiny(**F32))
    params = model.init(jax.random.PRNGKey(0))
    toks = _tokens(model.config.vocab_size)
    step = jax.jit(jax.value_and_grad(model.loss))
    with jax.default_matmul_precision("highest"):
        loss, grads = step(params, toks, jnp.roll(toks, -1, 1))
        logits = jax.jit(model.apply)(params, toks)
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _ref_loss(model, p, toks, True), has_aux=True))(params)
    return types.SimpleNamespace(
        model=model, params=params, toks=toks, step=step, loss=loss,
        grads=grads, logits=np.asarray(logits), ref_loss=ref_loss,
        ref_grads=ref_grads, ref_logits=np.asarray(ref_logits))


def test_loss_and_logits_equal_the_references(tiny):
    assert abs(float(tiny.loss) - float(tiny.ref_loss)) < LOSS_LIMIT
    assert np.abs(tiny.logits - tiny.ref_logits).max() \
        < REL_LIMIT * np.abs(tiny.ref_logits).max()


def test_every_parameters_gradient_equals_the_references(tiny):
    params, grads, ref_grads = tiny.params, tiny.grads, tiny.ref_grads
    assert set(grads) == set(params)
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        scale = np.abs(r).max()
        assert scale > 0, name
        assert np.abs(g - r).max() < REL_LIMIT * scale, name


def test_bf16_parameters_would_fail(tiny):
    """The limits see a lower precision: with the parameters rounded to
    bf16 (everything else float32) the loss and a gradient leave them."""
    params, toks, loss, grads = tiny.params, tiny.toks, tiny.loss, tiny.grads
    rounded = {k: v.astype(jnp.bfloat16).astype(v.dtype)
               for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        low, low_grads = tiny.step(rounded, toks, jnp.roll(toks, -1, 1))
    assert abs(float(low) - float(loss)) > 5 * LOSS_LIMIT
    name = "0.mamba.w_xbc"
    g, r = np.asarray(low_grads[name]), np.asarray(grads[name])
    assert np.abs(g - r).max() > 5 * REL_LIMIT * np.abs(r).max()


def test_the_published_period_is_runs_of_5_1_4():
    c = GraniteHybridConfig.granite4_h_micro(n_layer=10)
    assert c.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert stack_runs(c.layer_types) == [("mamba", 5), ("attention", 1),
                                         ("mamba", 4)]
    whole = GraniteHybridConfig.granite4_h_micro()
    assert whole.n_layer == 40
    assert [n for _, n in stack_runs(whole.layer_types)] == [
        5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert stack_runs(("attention", "attention", "mamba")) == [
        ("attention", 2), ("mamba", 1)]
    with pytest.raises(ValueError):
        GraniteHybridConfig.tiny(layer_types=("mamba", "conv"))


def _stack_event(model, batch, seq):
    """The newest ``rtpu.models.stack.runs`` of a trace of ``model.loss``
    from shapes."""
    from ray_tpu.perf.recorder import get_recorder

    rec = get_recorder()
    was, rec.enabled = rec.enabled, True
    try:
        toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        jax.eval_shape(model.loss, jax.eval_shape(
            model.init, jax.random.PRNGKey(0)), toks, toks)
        events = rec.snapshot()
    finally:
        rec.enabled = was
    return [e for e in events if e["kind"] == "rtpu.models.stack.runs"][-1]


def test_the_traced_runs_leave_their_event(tiny):
    """``rtpu.models.stack.runs`` at trace time: the run lengths and
    kinds the model walked; both state-space bodies take the kernels."""
    from ray_tpu.ops.ssd_scan import PATH_COUNTS

    before = PATH_COUNTS["kernel"]
    runs = _stack_event(tiny.model, 2, 128)
    assert runs["data"]["runs"] == [["mamba", 2], ["attention", 1],
                                    ["mamba", 1]]
    assert PATH_COUNTS["kernel"] == before + 2     # one a run, not a layer


def test_the_traced_runs_say_what_each_run_keeps(tiny):
    """ISSUE 37: the event names what the layers of each run keep (the
    first run the flash kernels' output only, later runs the MLP's two
    products too) and their bytes from the traced shapes: 2 x B x S x d_ff
    x itemsize a layer; at the benchmark cell's shape (batch 2 of 4096,
    8192 wide, bf16) 268 435 456, 1.34 GB over the five layers of the runs
    of 1 and 4."""
    flash, both = ["flash_out", "flash_lse"], ["flash_out", "flash_lse",
                                               "mlp_gate", "mlp_up"]
    data = _stack_event(tiny.model, 2, 128)["data"]
    assert data["kept"] == [flash, both, both]
    assert data["kept_bytes_per_layer"] == 2 * 2 * 128 * 128 * 4
    assert data["kept_bytes"] == 2 * data["kept_bytes_per_layer"]
    cell = GraniteHybrid(GraniteHybridConfig.granite4_h_micro(
        n_layer=10, vocab_size=12544))
    data = _stack_event(cell, 2, 4096)["data"]
    assert data["runs"] == [["mamba", 5], ["attention", 1], ["mamba", 4]]
    assert data["kept"] == [flash, both, both]
    assert data["kept_bytes_per_layer"] == 268_435_456
    assert data["kept_bytes"] == 1_342_177_280


_WITHOUT_THE_PRODUCTS = ("flash_out", "flash_lse")    # what PR 36 kept


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_keeping_the_mlp_products_changes_no_number(monkeypatch, dtype):
    """ISSUE 37: a kept product is the array the recomputation would have
    made again, so the loss and every gradient are those of the same
    model with the two names taken out of ``_REMAT_SAVE_LATER_RUNS``
    (monkeypatched: every run keeps what the first does): equal in EVERY
    element, in float32 and in bf16 (read here, CPU). In bf16 that holds
    with ``xla_allow_excess_precision`` off, as compiled here; XLA's
    default lets a fusion skip the rounding between the recomputed
    product and the silu behind it, which a kept bf16 array cannot, and
    then the losses are still one float and the gradients up to 1.7 % of
    a parameter's largest entry apart (``dt_bias`` of run 0): the
    compiler's licence, not another mathematics."""
    import ray_tpu.models.granite_hybrid as gh

    model = GraniteHybrid(GraniteHybridConfig.tiny(
        dtype=jnp.dtype(dtype), init_std=0.2))
    params = model.init(jax.random.PRNGKey(0))
    toks = _tokens(model.config.vocab_size)
    args = (params, toks, jnp.roll(toks, -1, 1))

    def read():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(model.loss)).lower(
                *args).compile(compiler_options={
                    "xla_allow_excess_precision": False})(*args)

    assert gh._REMAT_SAVE == _WITHOUT_THE_PRODUCTS
    assert set(gh._REMAT_SAVE_LATER_RUNS) - set(_WITHOUT_THE_PRODUCTS) == {
        "mlp_gate", "mlp_up"}
    loss, grads = read()
    monkeypatch.setattr(gh, "_REMAT_SAVE_LATER_RUNS", _WITHOUT_THE_PRODUCTS)
    loss_without, grads_without = read()
    assert float(loss) == float(loss_without)
    for name in params:
        assert np.array_equal(np.asarray(grads[name], np.float32),
                              np.asarray(grads_without[name], np.float32),
                              equal_nan=False), name


def _dot_outputs(jaxpr, times=1, out=None):
    """Output shape -> how many ``dot_general``s of a jaxpr make it, those
    of inner jaxprs included, a scan's counted by its length."""
    import collections

    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out[tuple(eqn.outvars[0].aval.shape)] += times
        inner = times * (eqn.params["length"]
                         if eqn.primitive.name == "scan" else 1)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_outputs(sub, inner, out)
    return out


def test_the_backward_does_not_make_the_mlp_products_again(monkeypatch):
    """ISSUE 37, in the jaxpr of the gradient of a small period (runs of
    2, 1 and 1; ``d_ff`` 192 so that no other product is as wide): the
    products with output [B, S, d_ff] are 3 a layer of the later runs
    (gate, up, and the hidden's gradient through ``w_down``) and 5 a layer
    of the first run and of every run with the two names taken out (gate
    and up made again), and every other product's count, the mixers'
    in-projections and q, k, v among them, is what it was: nothing else
    is kept."""
    import ray_tpu.models.granite_hybrid as gh

    model = GraniteHybrid(GraniteHybridConfig.tiny(d_ff=192, **F32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def count():
        return _dot_outputs(jax.make_jaxpr(jax.grad(model.loss))(
            params, toks, toks).jaxpr)

    kept = count()
    monkeypatch.setattr(gh, "_REMAT_SAVE_LATER_RUNS", _WITHOUT_THE_PRODUCTS)
    without = count()
    wide = (2, 128, 192)
    first, later = model.runs[0][1], sum(n for _, n in model.runs[1:])
    assert (first, later) == (2, 2)
    assert kept[wide] == 5 * first + 3 * later
    assert without[wide] == 5 * (first + later)
    c = model.config
    for width in (c.d_inner, c.d_conv_channels, c.n_head * c.head_dim,
                  c.n_kv_head * c.head_dim, c.d_model):
        assert kept[(2, 128, width)] > 0
    del kept[wide], without[wide]
    assert kept == without


@pytest.mark.parametrize("field", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling"])
def test_each_multiplier_changes_the_output(tiny, field):
    """None of the four is dropped on the way: another value gives other
    logits, and the reference, told the same value, follows."""
    model, params, toks, base = (tiny.model, tiny.params, tiny.toks,
                                 tiny.logits)
    other = GraniteHybrid(GraniteHybridConfig.tiny(
        **{field: getattr(model.config, field) * 1.5}, **F32))
    with jax.default_matmul_precision("highest"):
        moved = np.asarray(jax.jit(other.apply)(params, toks))
        theirs = np.asarray(_ref_logits(other, params, toks))
    assert np.abs(moved - base).max() > 1e-3 * np.abs(base).max()
    assert np.abs(moved - theirs).max() < REL_LIMIT * np.abs(theirs).max()


def test_the_period_at_published_sizes_counts_772160448():
    """The cut of the benchmark's configuration: ten layers of the
    published widths over 12 544 rows of the vocabulary. The family's
    count, the reference's from ``sizes`` and the issue's arithmetic."""
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro-period.json")) as f:
        config = json.load(f)
    family = _load("family_granite_hybrid", "benchmark", "families",
                   "granite_hybrid.py")
    model = family.build(config["model"])
    assert model.config.vocab_size == model.config.padded_vocab == 12544
    mamba = 17_432_576 + 21_760 + 192 + 4_096 + 8_388_608 + 50_331_648 + 4_096
    attention = 10_485_760 + 50_331_648 + 4_096
    want = 9 * mamba + attention + 12_544 * 2048 + 2048
    assert want == 772_160_448
    assert model.num_params() == want
    assert ref.num_params(config["sizes"], model.config.padded_vocab) == want
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) == want


def test_a_sliced_vocabulary_draws_no_id_outside_the_slice():
    """``vocab_size`` is the slice: the benchmark's feed draws from it and
    the model's logits and loss are over it."""
    traffic = _load("benchmark_lib_traffic", "benchmark", "lib", "traffic.py")
    model = GraniteHybrid(GraniteHybridConfig.tiny(vocab_size=384, **F32))
    feed = traffic.TokenFeed({"token_dist": {"zipf_a": 1.0}}, 2147489999,
                             model.config.vocab_size, 4, 128)
    ids = np.concatenate([feed.batch(i) for i in range(8)])
    assert ids.min() >= 0 and ids.max() < 384
    assert len(np.unique(ids)) > 192          # and it reaches most of them
    params = model.init(jax.random.PRNGKey(2))
    assert params["wte"].shape == (384, 64)
    toks = jnp.asarray(feed.batch(0)[:2])
    logits = jax.jit(model.apply)(params, toks)
    assert logits.shape == (2, 128, 384)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(model.loss)(params, toks, jnp.roll(toks, -1, 1))
        want = _ref_loss(model, params, toks)
    assert abs(float(loss) - float(want)) < LOSS_LIMIT
