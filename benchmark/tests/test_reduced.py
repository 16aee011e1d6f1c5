"""Every configuration file that states what was ``published`` is held to
it: its top-level keys are the published ones, changed where ``reduced``
lists them and nowhere else, each change with its ``reduced_how``; and no
listed key is a width. (``gpt2-medium`` reduces nothing and keeps the
published keys under ``published`` alone: it has nothing to hold.)"""
import json
import os

import pytest

from benchmark.lib import spec

WIDTHS = ("_dim", "_rank", "hidden_size", "intermediate_size", "latent_size",
          "state_size", "expand", "num_experts_per_tok")


def _configs():
    d = os.path.join(spec.BENCH_DIR, "configs")
    for f in sorted(os.listdir(d)):
        with open(os.path.join(d, f)) as fh:
            c = json.load(fh)
        if "published" in c and set(c["published"]) <= set(c):
            yield pytest.param(c, id=f[:-5])


@pytest.mark.parametrize("cfg", _configs())
def test_reduced_lists_every_key_that_differs_from_published(cfg):
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]), (changed, cfg["reduced"])
    assert set(cfg["reduced"]) <= set(cfg.get("reduced_how", cfg["reduced"]))
    assert not [k for k in cfg["reduced"] if k.endswith(WIDTHS)]
