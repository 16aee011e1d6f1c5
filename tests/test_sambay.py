"""ISSUE 43: the Phi-4-mini-flash-reasoning shaped model (Mamba-1 layers
through ``ops.selective_scan``, differential attention under a window and
full in the flash kernels, gated memory units and cross-attention that
read ONE earlier layer's scan output, keys and values; a stack of unlike
layers walked as runs of like periods with a side state; a vocabulary
slice) against the benchmark's plain reference
(``benchmark/reference/phi4flash.py``: the one copy, its recurrence one
token at a time, each score map once against a value of twice the width),
on seeded random weights at tiny widths: the six-layer cut (one layer of
each kind) and the WHOLE 32-layer pattern. Pallas kernels run in interpret
mode here.

Tolerances. Program and reference both compute in float32 here, so they
differ by the order of their sums only. Read on these seeds (``init_std``
0.2): the losses differ by at most 5e-7, the logits by 4e-7 of the largest
(six layers) and 1.2e-6 (32 layers), the gradients by at most 1.5e-5 of a
parameter's largest entry (the ``lam`` vectors and ``A_log`` of the 32-layer
stack: sums of thousands of terms of either sign). Parameters rounded to
bf16 move the loss by 1e-4 and a gradient by over 1e-2 of its largest entry
(``test_bf16_parameters_would_fail``). The limits lie between: 5e-6 on the
loss, 2e-4 of the largest entry on the logits and on each gradient.
"""
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (GraniteHybrid, GraniteHybridConfig, SambaY,
                            SambaYConfig)
from ray_tpu.models import stack
from ray_tpu.models.sambay import layer_kind

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the fixture of the 32-layer pattern builds three programs of 32 unrolled
# reference layers and interpreted kernels: two minutes alone, more beside
# five other workers
pytestmark = pytest.mark.time_limit(900)


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_phi4flash", "benchmark", "reference", "phi4flash.py")
# init_std 0.2 (the rehearsal's): at 0.02 and d = 64 the logits are so
# small that the loss is log(V) whatever the layers do
F32 = dict(dtype=jnp.float32, init_std=0.2)
LOSS_LIMIT = 5e-6     # absolute, on a loss of about 6.3 (module docstring)
REL_LIMIT = 2e-4      # of the largest entry: logits, each gradient

# ISSUE 44: the ``tiny`` preset's heads are of 64 (``head_dim`` is the
# published one), so its attention layers take the PAIRED streamed kernels
# (each score map once against the value of 128; at S 128 a grid of one
# step). Heads of 16 do not tile: ``flash_attention`` expands them to four
# heads a differential head, the form every cut had before.
EXPANDED = dict(head_dim=16)
CUTS = {
    "six-layer-cut": dict(),
    "six-layer-cut-heads-of-16": EXPANDED,
    "whole-32-layer-pattern": dict(layers=tuple(range(32)), n_published=32),
}


def _ref_loss(model, params, tokens):
    h = ref.hidden(params, tokens, jnp.float32,
                   **ref.model_kwargs(model.config))
    logits = ref.head(params, h, jnp.float32)
    targets = jnp.roll(tokens, -1, 1)
    lse = jax.scipy.special.logsumexp(logits, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        logits, targets[..., None], -1)[..., 0]), logits


# the whole pattern is ``slow`` in every case that asks for it (its programs
# take 150 s to build): it compares the six kinds of layer the two cuts compare
@pytest.fixture(scope="module", params=[
    pytest.param(cut, marks=pytest.mark.slow if "32" in cut else ())
    for cut in sorted(CUTS)])
def run(request):
    """One cut on one seeded batch: the model, its parameters and tokens,
    the program's jitted loss-and-gradients (``step``) with what it gave,
    its logits, and the reference's loss, gradients and logits."""
    model = SambaY(SambaYConfig.tiny(**F32, **CUTS[request.param]))
    params = model.init(jax.random.PRNGKey(0))
    # biases and LayerNorm shifts start at 0 or small: move every leaf
    params = {k: v + 0.05 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
              if k.endswith(("_b", "b_q", "b_k", "b_v", "b_o", "subln_g"))
              else v for i, (k, v) in enumerate(sorted(params.items()))}
    rows = 1 if model.config.n_layer == 32 else 2
    toks = jax.random.randint(jax.random.PRNGKey(1), (rows, 128), 0,
                              model.config.vocab_size)
    step = jax.jit(jax.value_and_grad(model.loss))
    with jax.default_matmul_precision("highest"):
        loss, grads = step(params, toks, jnp.roll(toks, -1, 1))
        logits = jax.jit(model.apply)(params, toks)
        (ref_loss, ref_logits), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: _ref_loss(model, p, toks), has_aux=True))(params)
    return types.SimpleNamespace(
        name=request.param, model=model, params=params, toks=toks, step=step,
        loss=loss, grads=grads, logits=np.asarray(logits), ref_loss=ref_loss,
        ref_grads=ref_grads, ref_logits=np.asarray(ref_logits))


def test_loss_and_logits_equal_the_references(run):
    assert abs(float(run.loss) - float(run.ref_loss)) < LOSS_LIMIT
    assert np.abs(run.logits - run.ref_logits).max() \
        < REL_LIMIT * np.abs(run.ref_logits).max()


def test_every_parameters_gradient_equals_the_references(run):
    """Every leaf, so the writers' too: ``w_k`` and ``w_v`` of the full
    attention layer and the ``mamba_m`` layer's mixer carry the sum over
    their readers (seven cross-attention layers and seven gated memory
    units in the whole pattern, scanned)."""
    params, grads, ref_grads = run.params, run.grads, run.ref_grads
    assert set(grads) == set(params)
    for name in params:
        g, r = np.asarray(grads[name]), np.asarray(ref_grads[name])
        if name.endswith(".b_k"):
            # a key's bias moves every score of a row alike, and a softmax
            # does not see that: the gradient is zero but for rounding, on
            # both sides, beside the query bias's
            scale = np.abs(np.asarray(ref_grads[name[:-1] + "q"])).max()
            assert max(np.abs(g).max(), np.abs(r).max()) < REL_LIMIT * scale
            continue
        scale = np.abs(r).max()
        assert scale > 0, name
        assert np.abs(g - r).max() < REL_LIMIT * scale, name
    writers = [n for n in params if ".attn_kv.w_k" in n or ".attn_kv.w_v" in n
               or ".mamba_m.w_in_x" in n]
    assert len(writers) == 3


def test_the_readers_reach_the_writers(run):
    """With the cross-decoder's output projections zeroed the writers'
    gradients change: what they carry does come from their readers."""
    cut = {k: (jnp.zeros_like(v) if ".gmu.w_out" in k or ".cross.w_o" in k
               else v) for k, v in run.params.items()}
    with jax.default_matmul_precision("highest"):
        _, grads = run.step(cut, run.toks, jnp.roll(run.toks, -1, 1))
    for name in run.params:
        if ".attn_kv.w_v" in name or ".mamba_m.A_log" in name:
            g, full = np.asarray(grads[name]), np.asarray(run.grads[name])
            assert np.abs(g - full).max() > 1e-2 * np.abs(full).max(), name


def test_bf16_parameters_would_fail(run):
    """The limits see a lower precision: with the parameters rounded to
    bf16 (everything else float32) the loss and a gradient leave them."""
    rounded = {k: v.astype(jnp.bfloat16).astype(v.dtype)
               for k, v in run.params.items()}
    with jax.default_matmul_precision("highest"):
        low, low_grads = run.step(rounded, run.toks,
                                  jnp.roll(run.toks, -1, 1))
    assert abs(float(low) - float(run.loss)) > 5 * LOSS_LIMIT
    worst = max(np.abs(np.asarray(low_grads[n]) - np.asarray(g)).max()
                / np.abs(np.asarray(g)).max() for n, g in run.grads.items())
    assert worst > 5 * REL_LIMIT


def test_the_number_of_parameters_is_the_references(run):
    c = run.model.config
    sizes = {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
             "head_dim": c.head_dim, "num_attention_heads": c.n_head,
             "num_key_value_heads": c.n_kv_head,
             "mamba_expand": c.mamba_expand, "mamba_d_state": c.mamba_d_state,
             "mamba_dt_rank": c.mamba_dt_rank, "mamba_d_conv": c.mamba_d_conv,
             "layers": list(c.layers),
             "num_hidden_layers_published": c.n_published}
    assert run.model.num_params() == ref.num_params(sizes, c.padded_vocab) \
        == sum(v.size for v in run.params.values())


def test_what_a_layer_keeps_changes_no_number(monkeypatch):
    """A kept value is the array the recomputation would have made again:
    the loss and every gradient with the policy's names (the kernels'
    outputs, the MLP's two products, the mixers' input projections) are
    those with NOTHING kept, in every
    element (float32, read here on the CPU)."""
    from ray_tpu.models import sambay

    model = SambaY(SambaYConfig.tiny(**F32))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)

    def both():
        return jax.jit(jax.value_and_grad(model.loss))(
            params, toks, jnp.roll(toks, -1, 1))

    assert {"flash_out", "selscan_out", "mlp_gate", "mlp_up", "mixer_in"} \
        <= set(sambay._REMAT_SAVE)
    loss, grads = both()
    monkeypatch.setattr(sambay, "_REMAT_SAVE", ())
    bare, bare_grads = both()
    assert np.array_equal(np.asarray(loss), np.asarray(bare))
    for name in grads:
        assert np.array_equal(np.asarray(grads[name]),
                              np.asarray(bare_grads[name])), name


# -- the walker ---------------------------------------------------------------


def test_the_published_order_is_runs_of_like_periods():
    kinds = tuple(layer_kind(i) for i in range(32))
    assert kinds[:4] == ("mamba", "swa", "mamba", "swa")
    assert kinds[14:20] == ("mamba", "swa", "mamba_m", "attn_kv", "gmu",
                            "cross")
    assert stack.period_runs(kinds, max_period=2) == [
        (("mamba", "swa"), 8), (("mamba_m",), 1), (("attn_kv",), 1),
        (("gmu", "cross"), 7)]
    # the benchmark's cut: one layer of each kind, six runs of one
    cut = tuple(layer_kind(i) for i in (0, 1, 16, 17, 18, 19))
    assert [n for _, n in stack.period_runs(cut, 2)] == [1] * 6
    # periods of one kind are runs of like layers, whatever is allowed
    granite = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    for most in (1, 2, 3):
        assert stack.period_runs(granite, most) == [
            (("mamba",), 5), (("attention",), 1), (("mamba",), 4)]
    assert stack.period_runs(("a", "b", "c") * 3 + ("a",), 3) == [
        (("a", "b", "c"), 3), (("a",), 1)]
    # greedy from the front: a repetition further on is not looked for
    assert stack.period_runs(("a", "a", "b", "a", "b"), 2) == [
        (("a",), 2), (("b",), 1), (("a",), 1), (("b",), 1)]
    assert stack.period_runs(("a", "b", "a", "b", "a"), 2) == [
        (("a", "b"), 2), (("a",), 1)]


@pytest.mark.parametrize("layers", [(0, 1, 18), (0, 1, 16, 19), (1, 0),
                                    (0, 40)])
def test_a_cut_that_holds_a_reader_holds_its_writer(layers):
    with pytest.raises(ValueError):
        SambaYConfig(layers=layers)


def _trace_events(model, kinds, batch=2, seq=128):
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
    return {k: [e for e in events if e["kind"] == k][-1] for k in kinds}


def test_the_traced_runs_leave_their_event_with_the_side_states_bytes():
    """``rtpu.models.stack.runs``: the runs, what each keeps, and the side
    state (m, k, v) with its bytes; ``rtpu.ops.selscan.path`` and
    ``rtpu.ops.flash.path`` with the window beside it."""
    model = SambaY(SambaYConfig.tiny(layers=tuple(range(32)),
                                     n_published=32))
    ev = _trace_events(model, ("rtpu.models.stack.runs",
                               "rtpu.ops.selscan.path"))
    data = ev["rtpu.models.stack.runs"]["data"]
    assert ev["rtpu.models.stack.runs"]["label"] == "sambay"
    assert data["runs"] == [["mamba+swa", 8], ["mamba_m", 1], ["attn_kv", 1],
                            ["gmu+cross", 7]]
    assert data["kept"] == [["flash_out", "flash_lse", "selscan_out",
                             "selscan_states", "mlp_gate", "mlp_up",
                             "mixer_in"]] * 4
    assert data["side_state"] == {"m": [2, 128, 128], "k": [2, 128, 2, 64],
                                  "v": [2, 128, 2, 64]}
    assert data["side_state_bytes"] == 2 * (2 * 128 * 128) * 3
    assert ev["rtpu.ops.selscan.path"]["data"]["route"] == "kernel"
    # the benchmark cell's cut at its shape: 84 MB of m, 21 MB each of k, v
    cell = SambaY(SambaYConfig(layers=(0, 1, 16, 17, 18, 19),
                               vocab_size=25088))
    data = _trace_events(cell, ("rtpu.models.stack.runs",), 1, 8192)[
        "rtpu.models.stack.runs"]["data"]
    assert data["side_state_bytes"] == 2 * 8192 * (5120 + 2 * 1280)


def _pallas_eqns(jaxpr):
    """(kernel name, equation) of every Pallas call in ``jaxpr`` and every
    jaxpr under it."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            info = e.params.get("name_and_src_info") or e.params.get("name")
            yield str(getattr(info, "name", info)), e
            continue
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _pallas_eqns(j)


def test_heads_of_64_take_the_paired_kernels_three_times():
    """ISSUE 44: a traced loss of the six-layer cut (heads of 64) leaves
    ``rtpu.ops.flash.path`` with ``layout`` ``paired`` three times (the
    window, the full and the cross layer; one with its window) and no other
    layout, and hands the kernels q, k and v at the ``n_head`` score heads
    the projections made: nothing is expanded to four heads a differential
    head. Heads of 16 fall back to that expansion."""
    import collections
    import time

    from ray_tpu.perf.recorder import get_recorder

    def traced(model):
        rec = get_recorder()
        was, rec.enabled = rec.enabled, True
        try:
            start = time.time()     # the ring may be full: by time
            toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
            jaxpr = jax.make_jaxpr(jax.grad(model.loss))(
                jax.eval_shape(model.init, jax.random.PRNGKey(0)), toks, toks)
            events = [e["data"] for e in rec.snapshot()
                      if e["kind"] == "rtpu.ops.flash.path"
                      and e["ts"] >= start]
        finally:
            rec.enabled = was
        return jaxpr.jaxpr, events

    model = SambaY(SambaYConfig.tiny())
    c = model.config
    jaxpr, events = traced(model)
    assert [e["layout"] for e in events] == ["paired"] * 3
    assert all(e["hd"] == 64 and e["hd_v"] == 128 for e in events)
    assert [e.get("window") for e in events] == [c.sliding_window, None, None]
    assert dict(_kernel_calls(jaxpr, collections.Counter())) == {
        "selscan_chunk_fwd": 2, "selscan_chunk_bwd": 2, "flash_fwd": 3,
        "flash_bwd_dq": 3, "flash_bwd_dkv": 3}
    widths = {v.aval.shape[-1] for name, e in _pallas_eqns(jaxpr)
              if name == "flash_fwd" for v in e.invars + e.outvars[:1]}
    # q, k, v at n_head x 64 lanes (v: n_head / 2 values of 128), o at
    # n_head x 128; the four-head form's operands were 2 n_head x 64 wide
    assert widths == {c.n_head * 64, c.n_head * 128}
    # heads of 16 do not tile: expanded inside the call, the routes of a
    # call with one head size (eight heads of 16 for n_head 4)
    _, events = traced(SambaY(SambaYConfig.tiny(**EXPANDED)))
    assert [e["layout"] for e in events] == ["relayout"] * 3
    assert all(e["hd"] == 16 and "hd_v" not in e for e in events)


def _kernel_calls(jaxpr, out):
    """Pallas calls by kernel name in ``jaxpr`` and every jaxpr under it."""
    out.update(name for name, _ in _pallas_eqns(jaxpr))
    return out


def test_each_kernel_body_stands_a_bounded_number_of_times():
    """The jaxpr of the whole 32-layer pattern's loss and gradients holds
    each kernel as often as that of an 8-layer model of the same pattern
    does: the Samba pairs are one scanned body and the cross-decoder pairs
    another, so the step's build does not grow with the depth. The scan
    kernels stand in two bodies (the pairs' and the writer's), the flash
    kernels in three (window, full, cross: heads of 64 are PAIRED calls,
    which take the streamed kernels whatever S; ISSUE 44); every forward
    kernel once, because what it made is kept for the backward. With heads
    of 16 the calls are expanded to one head size and take the routes they
    took before: the window's streamed kernels, and for full and cross at
    S = 128 the single-block pair."""
    import collections

    def calls(depth, **kw):
        model = SambaY(SambaYConfig.tiny(layers=tuple(range(depth)),
                                         n_published=depth, **kw))
        toks = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        jaxpr = jax.make_jaxpr(jax.grad(model.loss))(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)), toks, toks)
        return dict(_kernel_calls(jaxpr.jaxpr, collections.Counter()))

    assert calls(32) == calls(8) == {
        "selscan_chunk_fwd": 2, "selscan_chunk_bwd": 2, "flash_fwd": 3,
        "flash_bwd_dq": 3, "flash_bwd_dkv": 3}
    assert calls(8, **EXPANDED) == {
        "selscan_chunk_fwd": 2, "selscan_chunk_bwd": 2, "flash_fwd": 1,
        "flash_bwd_dq": 1, "flash_bwd_dkv": 1, "flash_fwd_single": 2,
        "flash_bwd_fused": 2}


def test_granite_hybrid_through_the_shared_walker_is_the_parents(monkeypatch):
    """The walker of ``models/stack.py`` gives ``granite_hybrid`` the loss
    and gradients its own walker gave (the parent's ``_run_layers``, kept
    here as it stood), bit for bit."""
    from ray_tpu.models import granite_hybrid as gh

    def parents_run_layers(self, x, params):
        kept = [gh._REMAT_SAVE if i == 0 else gh._REMAT_SAVE_LATER_RUNS
                for i in range(len(self.runs))]
        for i, (kind, n) in enumerate(self.runs):
            prefix = f"{i}.{kind}."
            lp = {name[len(prefix):]: v for name, v in params.items()
                  if name.startswith(prefix)}
            body = jax.checkpoint(
                lambda h, p, kind=kind: self._block(kind, h, p),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *kept[i]))
            if n == 1:
                x = body(x, {name: v[0] for name, v in lp.items()})
            else:
                x, _ = jax.lax.scan(lambda h, p: (body(h, p), None), x, lp)
        return x

    model = GraniteHybrid(GraniteHybridConfig.tiny(init_std=0.2))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)

    def both():
        return jax.jit(jax.value_and_grad(model.loss))(
            params, toks, jnp.roll(toks, -1, 1))

    loss, grads = both()
    monkeypatch.setattr(GraniteHybrid, "_run_layers", parents_run_layers)
    was, was_grads = both()
    assert np.array_equal(np.asarray(loss), np.asarray(was))
    for name in grads:
        assert np.array_equal(np.asarray(grads[name]),
                              np.asarray(was_grads[name])), name
