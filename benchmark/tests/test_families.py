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
    assert n == ref.num_params(sizes, rows)
    flops = fam.train_flops_per_token(sizes, 16)
    assert isinstance(flops, int) and flops > 0
    params = jax.jit(model.init)(jax.random.PRNGKey(3))
    tokens = TokenFeed({"kind": "train", "token_dist": {"zipf_a": 1.0}},
                       2**31 + 5, vocab, 2, 16).batch(0)
    loss = float(jax.jit(model.loss)(params, tokens,
                                     jnp.roll(tokens, -1, axis=1)))
    check = chip.train_reference_check(ref, model, params, tokens, loss, 2)
    assert check["ok"], check
    # the comparison is one that fails: a loss that left out half the batch
    half = float(jax.jit(model.loss)(params, tokens[:1],
                                     jnp.roll(tokens[:1], -1, axis=1)))
    assert not chip.train_reference_check(ref, model, params, tokens, half,
                                          2)["ok"]


def test_an_unknown_family_fails_with_the_list_of_families():
    with pytest.raises(ValueError, match=r"unknown model family 'mamba'.*"
                       r"\['gpt', 'llama'\]"):
        spec.load_family("mamba")
    with pytest.raises(ValueError, match="unknown model family '_helper'"):
        spec.load_family("_helper")
