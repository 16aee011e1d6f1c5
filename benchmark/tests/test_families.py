"""Every file of ``benchmark/families/`` against what the harness asks of
a family (``lib/spec.load_family``): it builds its rehearsal model from a
``model`` dict, the model has the interface ``train_loop`` drives, its
count of operations is a positive whole number, and, where the family's
plain reference is in the tree, the built model has the number of
parameters the reference computes from the configuration's ``sizes`` and
its ``loss`` passes the harness's own ``train_reference_check`` at the
tolerance that check measures. Guards the harness against a rename under
``ray_tpu/models/``. The fixture family of ``test_run.py`` is held to the
same, from its files under ``fixtures/``."""
import importlib
import json
import os

import pytest

from benchmark.lib import spec

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")


def _configs_of(family, directory):
    """(model, sizes, reference name) of the configurations in
    ``directory`` whose model is of ``family``, as a rehearsal runs them."""
    for f in sorted(os.listdir(directory)):
        with open(os.path.join(directory, f)) as fh:
            c = json.load(fh)
        c.update(c.get("rehearse", {}))
        if c["model"]["family"] == family:
            yield c["model"], c["sizes"], c["reference"]


def _cases():
    for name in spec._module_names("families"):
        found = next(_configs_of(name, os.path.join(spec.BENCH_DIR,
                                                    "configs")), None)
        # a family no configuration names yet: its tiny preset, and no
        # reference to hold it to
        model, sizes, ref = found or ({"family": name}, None, None)
        if ref and not os.path.exists(os.path.join(
                spec.BENCH_DIR, "reference", ref + ".py")):
            ref = None
        yield pytest.param(
            lambda name=name: spec.load_family(name), model, sizes,
            ref and (lambda ref=ref: importlib.import_module(
                "benchmark.reference." + ref)), id=name)
    for f in sorted(os.listdir(os.path.join(FIXTURES, "families"))):
        name = f[:-3]
        model, sizes, ref = next(_configs_of(
            name, os.path.join(FIXTURES, "configs")))
        yield pytest.param(
            lambda name=name: spec._load_module(
                "tests/fixtures/families", name),
            model, sizes, lambda ref=ref: spec._load_module(
                "tests/fixtures/reference", ref), id="fixtures/" + name)


@pytest.mark.parametrize("family,model_dict,sizes,reference", _cases())
def test_a_family_file_gives_what_the_harness_asks_of_it(
        family, model_dict, sizes, reference):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from benchmark.lib import chip
    from benchmark.lib.traffic import TokenFeed

    fam = family()
    assert all(isinstance(s, str) and s for s in fam.SCOPES)
    assert len(set(fam.SCOPES)) == len(fam.SCOPES) > 0
    model = fam.build(dict(model_dict))
    assert "family" in model_dict          # build() does not eat the dict
    vocab, rows = int(model.config.vocab_size), int(model.config.padded_vocab)
    assert 0 < vocab <= rows
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = int(model.num_params())
    assert n == sum(int(v.size) for v in jax.tree.leaves(shapes)) > 0
    # a mesh of one device under the names train.get_mesh() gives
    from ray_tpu.parallel.mesh import MESH_AXES
    mesh = Mesh(np.array(jax.devices()[:1]).reshape((1,) * len(MESH_AXES)),
                MESH_AXES)
    assert jax.tree.structure(model.param_shardings(mesh)) == \
        jax.tree.structure(shapes)
    if reference is None:
        # no reference in the tree: the interface and the build only
        tokens = jnp.zeros((2, 16), jnp.int32)
        assert jax.eval_shape(model.loss, shapes, tokens, tokens).shape == ()
        return
    ref = reference()
    # the objective: the family's own where its two files state one, else
    # the next-token loss, as ``make_train_step`` takes it
    objective = spec.objective_of(fam, ref)
    if objective is None:
        def loss_of(params, tokens):
            return model.loss(params, tokens, jnp.roll(tokens, -1, axis=1))
    else:
        loss_of = objective(model)
    assert n == ref.num_params(sizes, rows)
    flops = fam.train_flops_per_token(sizes, 16)
    assert isinstance(flops, int) and flops > 0
    params = jax.jit(model.init)(jax.random.PRNGKey(3))
    tokens = TokenFeed({"kind": "train", "token_dist": {"zipf_a": 1.0}},
                       2**31 + 5, vocab, 2, 16).batch(0)
    loss = float(jax.jit(loss_of)(params, tokens))
    check = chip.train_reference_check(ref, model, params, tokens, loss, 2)
    assert check["ok"], check
    # the rows of a call are the check's own business: one at a time too
    assert chip.train_reference_check(ref, model, params, tokens, loss,
                                      1)["reference_loss"] == \
        pytest.approx(check["reference_loss"], rel=1e-6)
    # the comparison is one that fails: a loss that left out half the batch
    half = float(jax.jit(loss_of)(params, tokens[:1]))
    assert not chip.train_reference_check(ref, model, params, tokens, half,
                                          2)["ok"]


def test_an_unknown_family_fails_with_the_list_of_families():
    # whatever files families/ holds, sorted
    have = spec._module_names("families")
    assert "gpt" in have and "mamba" not in have
    with pytest.raises(ValueError) as e:
        spec.load_family("mamba")
    assert "unknown model family 'mamba'" in str(e.value)
    assert str(have) in str(e.value)
    with pytest.raises(ValueError, match="unknown model family '_helper'"):
        spec.load_family("_helper")


def _module(name, **names):
    import types

    mod = types.ModuleType(name)
    vars(mod).update(names)
    return mod


@pytest.mark.parametrize("family,reference,missing", [
    (_module("fx_fam", objective=lambda model: None), _module("fx_ref"),
     "fx_ref (benchmark/reference/) defines no 'losses'"),
    (_module("fx_fam"), _module("fx_ref", losses=lambda *a, **kw: None),
     "fx_fam (benchmark/families/) defines no 'objective'"),
])
def test_half_an_objective_is_refused_by_the_name_of_the_missing_half(
        family, reference, missing):
    with pytest.raises(ValueError, match="half an objective") as e:
        spec.objective_of(family, reference)
    assert missing in str(e.value)


def test_a_family_states_its_objective_in_both_files_or_in_neither():
    stated = lambda model: None  # noqa: E731
    assert spec.objective_of(_module("fx_fam"), _module("fx_ref")) is None
    assert spec.objective_of(
        _module("fx_fam", objective=stated),
        _module("fx_ref", losses=lambda *a, **kw: None)) is stated
    # the families the benchmark has state none: the next-token loss
    for name in spec._module_names("families"):
        path = os.path.join(spec.BENCH_DIR, "reference", name + ".py")
        if os.path.exists(path):
            assert spec.objective_of(spec.load_family(name), importlib.
                                     import_module("benchmark.reference."
                                                   + name)) is None, name


def test_the_fixture_objectives_noise_is_the_stated_function_of_the_row():
    """Both halves of the denoising fixture noise a row as the rule says,
    worked here one position at a time in Python's integers."""
    import numpy as np

    fam = spec._load_module("tests/fixtures/families", "fx_denoise")
    ref = spec._load_module("tests/fixtures/reference", "fx_denoise")
    block, levels, m32 = 4, 4, 0xFFFFFFFF
    tokens = np.random.default_rng(5).integers(0, 512, (3, 64)).astype(
        np.int32)
    masked = np.zeros(tokens.shape, bool)
    t = np.zeros(tokens.shape, np.float32)
    for r, row in enumerate(tokens):
        key = sum((int(x) + 1) * (2 * i + 1) for i, x in enumerate(row)) & m32
        for i in range(len(row)):
            t[r, i] = (1 + (key + 7 * (i // block)) % levels) / levels
            h = ((key ^ (i * 0x9E3779B1 & m32)) * 0x85EBCA6B) & m32
            h = ((h ^ (h >> 13)) * 0xC2B2AE35) & m32
            masked[r, i] = ((h ^ (h >> 16)) >> 8) / float(1 << 24) < t[r, i]
    assert 0.4 < masked.mean() < 0.8 and len(np.unique(t)) == levels
    for noise in (fam._noise, ref.noise):
        got_masked, got_t = noise(tokens, block, levels)
        assert (np.asarray(got_masked) == masked).all()
        assert np.allclose(np.asarray(got_t), t)
