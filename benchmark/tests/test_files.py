"""Every data file loads, and every name in BENCHMARK.json resolves."""
import json
import os
import re

import pytest

from benchmark.lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
    BM = json.load(f)
CELLS = [w["name"] for w in BM["workloads"]]
E2E = {m["name"]: m for m in BM["end_to_end"]}
LAYER = {m["name"]: m for m in BM["per_layer"]}


def _json_files(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                          kind))
                  if f.endswith(".json"))


def _cells_of(metric):
    return metric.get("workloads", CELLS)


@pytest.mark.parametrize("kind", ["configs", "traffic", "cells"])
def test_every_data_file_is_a_json_object_with_a_good_name(kind):
    for name in _json_files(kind):
        assert NAME.match(name), name
        assert isinstance(spec._load_json(kind, name), dict)


def test_every_cell_file_is_a_cell_of_the_benchmark():
    assert _json_files("cells") == sorted(CELLS)
    assert _json_files("configs") == sorted(c["name"] for c in BM["configs"])
    assert _json_files("traffic") == sorted({w["traffic"]
                                             for w in BM["workloads"]})


@pytest.mark.parametrize("cell", _json_files("cells"))
def test_every_cell_file_resolves(cell):
    for rehearse in (False, True):
        c = spec.load_cell(cell, rehearse=rehearse)
        assert c["traffic_file"]["kind"] in spec.TRAFFIC_KINDS
        family = c["config_file"]["model"]["family"]
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "families",
                                           family + ".py"))
        assert callable(spec.family_of(c).build)
        ref = c["config_file"]["reference"]
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "reference",
                                           ref + ".py"))
        if c["traffic_file"]["kind"] == "train":
            assert c["trainer"]["batch"] > 0
        else:
            assert c["engine"]["num_blocks"] % 64 == 0
            longest = c["engine"]["max_blocks_per_seq"] * \
                c["engine"].get("block_size", 16)
            assert c["ref_pad"] <= longest


@pytest.mark.parametrize("w", BM["workloads"], ids=CELLS)
def test_benchmark_cells_match_their_files(w):
    c = spec.load_cell(w["name"])
    assert (c["config"], c["traffic"], c["chips"], c["why"]) == \
        (w["config"], w["traffic"], w["chips"], w["why"])
    assert len(w["why"]) <= 200 and NAME.match(w["traffic"])


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_benchmark_configs_match_their_files(cfg):
    assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
    with open(os.path.join(spec.REPO_DIR, cfg["file"])) as f:
        data = json.load(f)
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in BM["workloads"])
    for key in ("assumed", "deployment", "model", "sizes", "reference"):
        assert key in data, key


@pytest.mark.parametrize("group,metrics", [("end_to_end", E2E),
                                           ("layer_metrics", LAYER)])
def test_every_metric_has_a_reader_that_agrees(group, metrics):
    readers = spec.load_metric_readers(group)
    assert set(metrics) <= set(readers)
    for name, m in metrics.items():
        assert NAME.match(name)
        r = readers[name]
        assert r.UNIT == m["unit"] and r.SOURCE == m["source"], name
        assert callable(r.read)
        if group == "layer_metrics":
            assert (r.LAYER, r.MOVES) == (m["layer"], m["moves"]), name


@pytest.mark.parametrize("name", sorted(LAYER))
def test_layer_metric_moves_one_metric_each_of_its_cells_reports(name):
    m = LAYER[name]
    assert m["moves"] in E2E
    moved = _cells_of(E2E[m["moves"]])
    for cell in _cells_of(m):
        assert cell in CELLS and cell in moved, (name, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    e2e = [n for n, m in E2E.items() if cell in _cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in _cells_of(m) for m in LAYER.values())


def test_end_to_end_readers_on_a_synthetic_view():
    from benchmark.lib.stats import request_latencies

    recs = [{"due": 0.0, "sent": 0.1, "ok": True, "first": 0.5, "last": 1.5,
             "n_tokens": 11, "token_times": [0.5 + 0.1 * i for i in range(11)]}
            for _ in range(20)]
    view = {"spans": {"process_start_to_window": 12.5},
            "latencies": request_latencies(recs),
            "window": {"records": recs, "t0": 0.0, "seconds": 1.0},
            "train": {"steps": 10, "tokens": 81920, "elapsed_s": 2.0}}
    got = {n: r.read(view) for n, r in
           spec.load_metric_readers("end_to_end").items()}
    assert got["setup_s"] == 12.5
    assert got["train_tokens_per_s"] == 40960.0
    assert got["ttft_p95_ms"] == pytest.approx(500.0)
    assert got["tpot_p95_ms"] == pytest.approx(100.0)
    assert got["serve_tokens_per_s"] == 20 * 5      # arrivals before 1.0 s
    for r in spec.load_metric_readers("end_to_end").values():
        if r.__name__.endswith("setup_s"):
            continue
        assert r.read({"spans": {}}) is None


def test_readers_return_nothing_when_there_is_nothing_to_read():
    empty = {"spans": {}, "cell": {"engine": {"max_batch": 1},
                                   "config_file": {"sizes": {}}},
             "device": {"platform": "cpu", "kind": "cpu", "count": 1},
             "trace": None}
    for name, r in spec.load_metric_readers("layer_metrics").items():
        assert r.read(empty) is None, name


def test_run_py_names_no_cell_configuration_or_metric():
    with open(os.path.join(spec.BENCH_DIR, "run.py")) as f:
        src = f.read()
    names = set(CELLS) | set(E2E) | set(LAYER) \
        | {c["name"] for c in BM["configs"]} \
        | {w["traffic"] for w in BM["workloads"]}
    for n in names:
        assert n not in src, n


def test_peaks_are_keyed_by_exact_device_kind():
    from benchmark.lib.peaks import peak

    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peak("TPU v5")
