"""run.py end to end on the CPU: one rehearsal per traffic kind, and the
refusal to run a cell without a chip.

The training kind is rehearsed on the benchmark's own cell. The serving
kinds have no cell in ``BENCHMARK.json`` yet (PERF.md, Open questions), and
no cell trains a family other than GPT, so those are rehearsed the way a
later PR will add them: a copy of the checkout gets the files under
``fixtures/`` (for the training cell of a third family: its family file,
its plain reference, a configuration, a traffic mix, a cell, a scope
reader and an mfu-like reader) and entries in its ``BENCHMARK.json``, and
no file that was there is edited."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import spec

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
    BM = json.load(f)
TRAIN_CELL = next(w["name"] for w in BM["workloads"] if spec.load_cell(
    w["name"])["traffic_file"]["kind"] == "train")
FIXTURE_CELLS = {"serve_closed": "fx_serve_closed",
                 "serve_open": "fx_serve_open"}
# a training cell of a family that enters as files (fixtures/families/)
FAMILY_CELL = "fx_train_moe"
# the end-to-end metrics the cells of each fixture kind report
REPORTS = {"serve_closed": ["serve_tokens_per_s"],
           "serve_open": ["ttft_p95_ms", "tpot_p95_ms"],
           "train": ["train_tokens_per_s"]}


def _tree(root):
    """{path relative to ``root``: bytes} of every file under it."""
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = fh.read()
    return out


@pytest.fixture(scope="module")
def checkout_with_fixture_cells(tmp_path_factory):
    """A copy of the checkout's benchmark, plus every file under
    ``fixtures/`` in the directory of its kind, plus entries appended to
    the copy's ``BENCHMARK.json``: what a later PR's diff looks like."""
    root = str(tmp_path_factory.mktemp("checkout"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    before = _tree(bench)
    added = set()
    for kind in sorted(os.listdir(FIXTURES)):
        for f in os.listdir(os.path.join(FIXTURES, kind)):
            assert not os.path.exists(os.path.join(bench, kind, f))
            shutil.copy(os.path.join(FIXTURES, kind, f),
                        os.path.join(bench, kind, f))
            added.add(os.path.join(kind, f))
    after = _tree(bench)
    # files were added and none that was there was edited
    assert set(after) - set(before) == added
    assert all(after[f] == data for f, data in before.items())
    bm = json.loads(json.dumps(BM))
    for f in sorted(os.listdir(os.path.join(FIXTURES, "configs"))):
        bm["configs"].append({
            "name": f[:-5], "source": "test fixture",
            "file": "benchmark/configs/" + f, "reduced": [],
            "why": "test fixture"})
    e2e = spec.load_metric_readers("end_to_end")
    cells_of = {}
    for kind, cell in [*FIXTURE_CELLS.items(), ("train", FAMILY_CELL)]:
        c = json.load(open(os.path.join(FIXTURES, "cells", cell + ".json")))
        bm["workloads"].append({"name": cell, "config": c["config"],
                                "traffic": c["traffic"], "chips": 1,
                                "why": c["why"]})
        for name in REPORTS[kind]:
            have = next((m for m in bm["end_to_end"] if m["name"] == name),
                        None)
            if have is not None:
                # a metric the benchmark has: the cell joins its list
                have["workloads"].append(cell)
                continue
            cells_of[name] = [cell]
            bm["end_to_end"].append({
                "name": name, "unit": e2e[name].UNIT, "better": "lower",
                "bound": 0.1, "source": e2e[name].SOURCE,
                "workloads": [cell]})
    listed = {m["name"] for m in bm["per_layer"]}
    readers = {name: (r, cells_of.get(r.MOVES)) for name, r in
               spec.load_metric_readers("layer_metrics").items()}
    for f in os.listdir(os.path.join(FIXTURES, "layer_metrics")):
        readers[f[:-3]] = (spec._load_module(
            "tests/fixtures/layer_metrics", f[:-3]), [FAMILY_CELL])
    for name, (r, cells) in readers.items():
        if name not in listed and cells:
            bm["per_layer"].append({
                "name": name, "unit": r.UNIT, "better": "lower",
                "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
                "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def _run(root, *argv):
    # ray_tpu comes from the real checkout; benchmark/ from ``root``
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def _rehearse(root, cell, trace):
    p = _run(root, "--workload", cell, "--rehearse-cpu", "--seconds", "5",
             "--seed", str(2**31 + 7), "--trace", trace)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # a CPU walk-through never prints a value under a metric's name
    assert "metrics" not in line
    return line["metric_names"]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_training_rehearsal_ends_with_a_well_formed_line(trace):
    names = _rehearse(spec.REPO_DIR, TRAIN_CELL, trace)
    assert ("setup_s" in names) == (trace == "0")


@pytest.mark.parametrize("kind", sorted(FIXTURE_CELLS))
@pytest.mark.parametrize("trace", ["0", "1"])
def test_serving_rehearsal_of_cells_added_as_files_and_entries(
        checkout_with_fixture_cells, kind, trace):
    names = _rehearse(checkout_with_fixture_cells, FIXTURE_CELLS[kind],
                      trace)
    if trace == "0":
        assert sorted(names) == sorted(REPORTS[kind] + ["setup_s"])
    else:
        assert "worker_start_s" in names and len(names) >= 2


@pytest.mark.parametrize("trace", ["0", "1"])
def test_training_rehearsal_of_a_family_added_as_files_and_entries(
        checkout_with_fixture_cells, trace):
    """``ray_tpu.models.MoE``, a class the harness never names: its family
    file, plain reference, configuration, traffic, cell and readers are
    files of the copy, and the run ends ``correct`` against the plain
    float32 loss. With ``--trace 1`` its scope reader and its mfu-like
    reader are found by name and asked (a CPU trace has no device plane,
    so neither has anything to read)."""
    root = checkout_with_fixture_cells
    names = _rehearse(root, FAMILY_CELL, trace)
    if trace == "0":
        assert sorted(names) == ["setup_s", "train_tokens_per_s"]
    else:
        assert "worker_start_s" in names
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            listed = [m["name"] for m in json.load(f)["per_layer"]
                      if FAMILY_CELL in m.get("workloads", [FAMILY_CELL])]
        assert {"fx_train_moe_ms", "fx_active_mfu"} <= set(listed)


def test_a_family_without_a_file_fails_with_the_families_that_have_one(
        checkout_with_fixture_cells, tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(checkout_with_fixture_cells, root)
    os.remove(os.path.join(root, "benchmark", "families", "fx_moe.py"))
    p = _run(root, "--workload", FAMILY_CELL, "--rehearse-cpu", "--seconds",
             "2")
    assert p.returncode != 0
    assert "unknown model family 'fx_moe'" in p.stderr
    assert "['gpt', 'llama']" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_every_traffic_kind_is_rehearsed():
    assert sorted(["train", *FIXTURE_CELLS]) == sorted(spec.TRAFFIC_KINDS)


def test_without_a_chip_a_cell_fails_and_prints_no_result():
    p = _run(spec.REPO_DIR, "--workload", TRAIN_CELL, "--seconds", "2")
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
