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


def load_metric_readers(group: str) -> dict:
    """One reader per file in ``benchmark/<group>/`` (``end_to_end`` or
    ``layer_metrics``), found by listing the directory. The file's name is
    the metric's; it holds UNIT, SOURCE, (for a layer metric) LAYER and
    MOVES, and ``read(view) -> number or None``."""
    d = os.path.join(BENCH_DIR, group)
    out = {}
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        name = fname[:-3]
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{group}_" + re.sub(r"\W", "_", name),
            os.path.join(d, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out
