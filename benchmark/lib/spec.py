"""Loads a cell and what it names. A cell is data: ``cells/<cell>.json``
names one file in ``configs/`` and one in ``traffic/``; nothing here knows
the name of any cell, configuration or metric."""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(REPO_DIR, ".bench_out")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
TRAFFIC_KINDS = ("train", "serve_closed", "serve_open")


def _load_json(kind: str, name: str) -> dict:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    return data


def load_cell(name: str, rehearse: bool = False) -> dict:
    """-> the cell with its configuration and traffic resolved. With
    ``rehearse`` every ``rehearse`` group found in the three files
    replaces the keys it names: tiny presets for the CPU walk-through."""
    cell = _load_json("cells", name)
    config = _load_json("configs", cell["config"])
    traffic = _load_json("traffic", cell["traffic"])
    if traffic.get("kind") not in TRAFFIC_KINDS:
        raise ValueError(f"traffic {cell['traffic']!r}: kind must be one of "
                         f"{TRAFFIC_KINDS}, got {traffic.get('kind')!r}")
    if rehearse:
        for d in (cell, config, traffic):
            d.update(d.get("rehearse", {}))
        traffic.update(cell.get("traffic_overrides", {}))
    if cell.get("chips") not in (1, 4):
        raise ValueError(f"cell {name!r}: chips must be 1 or 4")
    cell["name"] = name
    cell["config_file"] = config
    cell["traffic_file"] = traffic
    return cell


def _module_names(group: str) -> list:
    """The files of ``benchmark/<group>/`` less ``.py``; those that start
    with ``_`` are helpers."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, group))
                  if f.endswith(".py") and not f.startswith("_"))


def _load_module(group: str, name: str):
    """``benchmark/<group>/<name>.py`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", f"benchmark_{group}_{name}"),
        os.path.join(BENCH_DIR, group, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric_readers(group: str) -> dict:
    """One reader per file in ``benchmark/<group>/`` (``end_to_end`` or
    ``layer_metrics``), found by listing the directory. The file's name is
    the metric's; it holds UNIT, SOURCE, (for a layer metric) LAYER and
    MOVES, and ``read(view) -> number or None``."""
    return {name: _load_module(group, name) for name in _module_names(group)}


_families: dict = {}


def load_family(name: str):
    """``benchmark/families/<name>.py``: what the harness knows of a model
    family, found by the ``family`` key of a configuration's ``model``
    dict and by listing the directory. The file holds

    * ``build(model: dict) -> model``: the object a training cell drives,
      from the configuration's ``model`` dict (``family`` and whatever the
      family's own builder takes). What the harness calls on it, and
      nothing else: ``config.vocab_size`` (the ids the feed draws from),
      ``config.padded_vocab`` (the rows of the embedding, for the
      parameter count), ``init(rng) -> params``,
      ``param_shardings(mesh)``, ``num_params()`` and, where the family
      states no objective of its own, ``loss(params, tokens, targets) ->
      scalar``, the next-token loss (a term that the reference's
      ``losses`` does not hold must be off); where it has one,
      ``routing_stats(params, tokens)`` is called after the window and its
      counts ride in the final report as ``held_rows``;
    * ``SCOPES``: the ``jax.named_scope`` names by which a train step of
      the family is split (``layer_metrics/_program.scope_ms_per_step``);
    * ``train_flops_per_token(sizes, seq) -> int``: the model's own
      forward + backward operations a token, from the configuration's
      ``sizes``, recomputation not counted (``layer_metrics/mfu.py``).

    * optionally ``objective(model) -> fn(params, tokens) -> scalar``: the
      loss the job minimises where that is not the next-token loss
      (``objective_of`` has the rules).

    The family's plain reference is ``reference/<family>.py``. An unknown
    family fails with the list of those that have a file."""
    if name not in _families:
        known = _module_names("families")
        if name not in known:
            raise ValueError(f"unknown model family {name!r}: "
                             f"benchmark/families/ has {known}")
        _families[name] = _load_module("families", name)
    return _families[name]


def family_of(cell: dict):
    """The family file of a loaded cell's configuration."""
    return load_family(cell["config_file"]["model"]["family"])


def objective_of(family, reference):
    """The training objective a family states, or None: then the step
    minimises ``model.loss(params, tokens, roll(tokens, -1))`` and the
    check forms the next-token terms from the reference's ``hidden`` and
    ``head``. A family states its objective in two halves, found by what
    the two files hold and by nothing else:

    * ``families/<family>.py``: ``objective(model) -> fn(params, tokens)
      -> scalar``, what the train step differentiates;
    * ``reference/<family>.py``: ``losses(params, tokens, dtype, **kw) ->
      float32 [b, n]`` (``kw`` from its ``model_kwargs``): the objective's
      per-position terms, masks and weights applied, zero where a position
      does not count, whose MEAN over all entries is that loss, in plain
      ``jax.numpy``. The check calls it on ``reference_rows`` rows at a
      time, so a row's terms depend on that row alone.

    Both get the parameters and the batch and nothing else: an objective
    that draws (which positions are masked, a block's noise level) draws
    as a pure function of the row's ids and of constants written under
    ``assumed`` in the configuration's file, so that both halves noise the
    same positions, a seed repeats its run and the checks at the first
    step and after the window compare like with like. ``tokens`` of the
    final report stays steps x B x S, the data consumed; a longer sequence
    run inside counts in ``train_flops_per_token``. One half without the
    other fails here, before anything is built."""
    objective = getattr(family, "objective", None)
    losses = getattr(reference, "losses", None)
    both = "a family states both halves of its objective or neither"
    if objective is not None and losses is None:
        raise ValueError(
            f"half an objective: {family.__name__} defines 'objective' but "
            f"{reference.__name__} (benchmark/reference/) defines no "
            f"'losses'; {both}")
    if losses is not None and objective is None:
        raise ValueError(
            f"half an objective: {reference.__name__} defines 'losses' but "
            f"{family.__name__} (benchmark/families/) defines no "
            f"'objective'; {both}")
    return objective
