"""run.py end to end on the CPU: one rehearsal per traffic kind, and the
refusal to run a cell without a chip.

The training kind is rehearsed on the benchmark's own cell. The serving
kinds have no cell in ``BENCHMARK.json`` yet (PERF.md, Open questions), and
no cell trains by another objective than the next-token loss, so those are
rehearsed the way a later PR will add them: a copy of the checkout gets
the files under ``fixtures/`` (for the training cell of a further family:
its family file, its plain reference, a configuration, a traffic mix, a
cell, a scope reader and an mfu-like reader; for the cell whose family
states its own objective: family file, reference, configuration and cell)
and entries in its ``BENCHMARK.json``, and no file that was there is
edited."""
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
# and one whose family states an objective of its own: masked denoising,
# weighted 1/t (``objective`` in its family file, ``losses`` in its
# reference)
DENOISE_CELL = "fx_train_denoise"
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
    fixture_cells = [*FIXTURE_CELLS.items(), ("train", FAMILY_CELL),
                     ("train", DENOISE_CELL)]
    for kind, cell in fixture_cells:
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
    # a metric the benchmark lists for its own cells: the new cells join
    next(m for m in bm["per_layer"] if m["name"] == "worker_start_s")[
        "workloads"].extend(cell for _, cell in fixture_cells)
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
    # whatever files families/ holds, sorted
    left = sorted(f[:-3] for f in os.listdir(
        os.path.join(root, "benchmark", "families"))
        if f.endswith(".py") and not f.startswith("_"))
    assert "gpt" in left and "fx_moe" not in left and str(left) in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def _patched(checkout, tmp_path, path, old, new):
    """A copy of ``checkout`` in which ``old`` of the file ``path`` reads
    ``new``: a fault planted under the run."""
    root = str(tmp_path / "checkout")
    shutil.copytree(checkout, root)
    with open(os.path.join(root, path)) as f:
        src = f.read()
    assert src.count(old) == 1, (path, old)
    with open(os.path.join(root, path), "w") as f:
        f.write(src.replace(old, new))
    return root


def test_a_family_that_states_its_objective_enters_as_files_and_entries(
        checkout_with_fixture_cells):
    """Masked denoising weighted 1/t on ``ray_tpu.models.GPT``: the step
    differentiates the family file's ``objective``, the check takes the
    mean of the reference's ``losses``, one row a call, and the run ends
    ``correct``. (That no file that was there differs is the fixture's
    own assertion.)"""
    names = _rehearse(checkout_with_fixture_cells, DENOISE_CELL, "0")
    assert sorted(names) == ["setup_s", "train_tokens_per_s"]


@pytest.mark.parametrize("path,old,new", [
    pytest.param("benchmark/families/fx_denoise.py", "nll / t, 0.0",
                 "nll, 0.0", id="the_programs_weight_dropped"),
    pytest.param("benchmark/reference/fx_denoise.py", "0x9E3779B1",
                 "0x9E3779B9", id="the_references_noise_by_another_function"),
])
def test_a_wrong_objective_cannot_pass(checkout_with_fixture_cells, tmp_path,
                                       path, old, new):
    """The same cell with one half of its objective altered: the loss the
    step reports is no longer the mean of the reference's terms, at the
    first step already, and by far more than the tolerance."""
    root = _patched(checkout_with_fixture_cells, tmp_path, path, old, new)
    p = _run(root, "--workload", DENOISE_CELL, "--rehearse-cpu", "--seconds",
             "5", "--seed", str(2**31 + 7))
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and list(line)[-1] == "compared"
    diff, tolerance = line["compared"]["first_step.loss_abs_diff"]
    assert diff > 10 * tolerance > 0
    # the last lines of standard error say the same
    assert f"compared first_step.loss_abs_diff: {diff!r} limit " \
        f"{tolerance!r}" in p.stderr[-2000:]


def test_half_an_objective_fails_at_load_and_names_the_missing_half(
        checkout_with_fixture_cells, tmp_path):
    root = _patched(checkout_with_fixture_cells, tmp_path,
                    "benchmark/reference/fx_denoise.py", "def losses(",
                    "def terms(")
    p = _run(root, "--workload", DENOISE_CELL, "--rehearse-cpu", "--seconds",
             "2")
    assert p.returncode != 0
    assert "half an objective" in p.stderr
    assert "defines no 'losses'" in p.stderr
    # at load: no step was built, let alone run
    assert "steps of" not in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


TRAIN_CELLS = [w["name"] for w in BM["workloads"] if spec.load_cell(
    w["name"])["traffic_file"]["kind"] == "train"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_final_report_holds_the_held_rows_where_the_model_counts_them(
        cell, tmp_path):
    """``scratch/final_report.py`` keeps the loop's final report: where
    the cell's model has ``routing_stats``, ``held_rows`` holds a count a
    layer at the first step and after the window; a model without it
    reports no such key and built no further program."""
    import jax
    import jax.numpy as jnp

    c = spec.load_cell(cell, rehearse=True)
    model = spec.family_of(c).build(dict(c["config_file"]["model"]))
    out = str(tmp_path / "final.json")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "scratch",
                                      "final_report.py"), out,
         "--workload", cell, "--rehearse-cpu", "--seconds", "3", "--seed",
         str(2**31 + 11)], cwd=spec.REPO_DIR, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"] is True
    with open(out) as f:
        final = json.load(f)
    if not hasattr(model, "routing_stats"):
        assert "held_rows" not in final and "held rows" not in p.stderr
        return
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((c["trainer"]["batch"],
                                   c["trainer"]["seq"]), jnp.int32)
    layers, = jax.eval_shape(model.routing_stats, shapes, tokens).shape
    held = final["held_rows"]
    assert sorted(held) == ["after_window", "first_step"]
    assert all(len(rows) == layers > 0 and all(
        isinstance(n, int) and n >= 0 for n in rows)
        for rows in held.values())
    assert f"held rows {held}" in p.stderr


def test_every_traffic_kind_is_rehearsed():
    assert sorted(["train", *FIXTURE_CELLS]) == sorted(spec.TRAFFIC_KINDS)


def test_without_a_chip_a_cell_fails_and_prints_no_result():
    p = _run(spec.REPO_DIR, "--workload", TRAIN_CELL, "--seconds", "2")
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
