"""ISSUE 69: what the benchmark's files of `minicpmsala_train_s32768` say,
held to the program and to the issue's arithmetic: the family file's own
count of selected pairs (the benchmark imports nothing of the program)
equals the program's at every length; its operations a token are the
issue's; the configuration's sizes give the parameters the model built; the
two new readers count what their docstrings say and return NOTHING, without
raising, on a view they have nothing to read in (another cell's sizes, an
untraced run, no training at all: the driver runs them against the parent's
program too); `BENCHMARK.json` lists the cell where the issue says. No jax
program is built here."""
import importlib
import json
import os

import pytest

from benchmark.lib import spec

fam = importlib.import_module("benchmark.families.minicpm_sala")
ref = importlib.import_module("benchmark.reference.minicpm_sala")
scan_reader = importlib.import_module(
    "benchmark.layer_metrics.lightning_scan_roofline")
attn_reader = importlib.import_module(
    "benchmark.layer_metrics.block_sparse_attention_roofline")
sa = importlib.import_module("ray_tpu.ops.sparse_attention")

CELL = "minicpmsala_train_s32768"
ROOT = os.path.dirname(spec.BENCH_DIR)


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "minicpm-sala-9b-tp2.json")
SIZES = CONFIG["sizes"]


@pytest.mark.parametrize("seq", [128, 6144, 6208, 8192, 8256, 12288, 16384,
                                 32768, 65536])
def test_the_familys_pairs_are_the_programs(seq):
    """Dense up to ``dense_len`` 8192 (and while a query sees no more than
    96 blocks); beyond, 96 blocks of 64 a query, the causal part of its
    own."""
    mine = fam.selected_pairs(seq, SIZES)
    if seq <= 8192:
        assert mine == seq * (seq + 1) // 2
    else:
        assert mine == sa.block_selected_pairs(seq, 64, 96) \
            < seq * (seq + 1) // 2


def test_operations_a_token_are_the_issues():
    """3.7 G a token and 121 T a step; the MLP 65 %, the Lightning layers'
    projections a fifth, the selected pairs 0.14 G, the pooled scores a
    thirtieth of that."""
    total = fam.train_flops_per_token(SIZES, 32768)
    assert total == 3_703_650_561
    assert round(total * 32768 / 1e12, 1) == 121.4
    mlp = 6 * 4 * 3 * 4096 * 8192
    assert round(mlp / total, 3) == 0.652
    assert round(6 * 3 * 5 * 4096 * 2048 / total, 3) == 0.204
    pairs = 3 * 16 * 4 * 128 * fam.selected_pairs(32768, SIZES) / 32768
    assert round(pairs / 1e9, 2) == 0.14
    assert fam.pooled_pairs(32768, SIZES) == 33_507_343
    assert fam.pooled_pairs(8192, SIZES) == 0          # a dense row


def test_the_sizes_give_the_parameters_the_file_states():
    assert ref.num_params(SIZES, 9216) == CONFIG["n_params"] == 630_232_448
    assert CONFIG["intermediate_size"] == 16384 \
        and SIZES["intermediate_held"] == 8192
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "num_attention_heads", "lightning_nh",
        "lightning_nkv", "num_key_value_heads", "vocab_size"]
    assert CONFIG["mixer_types"] == CONFIG["published"]["mixer_types"]
    assert SIZES["mixer_types"] == CONFIG["mixer_types"][:4]


@pytest.mark.parametrize("line", [
    "sparse_config", "sparse_selection", "sparse_blocks", "decay",
    "qk_norm", "output_norm_and_gate", "no_feature_map", "no_logit_scaling",
    "mup_denominator", "param_dtype"])
def test_every_assumed_line_is_written_with_its_reason(line):
    assert len(CONFIG["assumed"][line]) > 60


def test_the_readers_costs_are_their_docstrings():
    scan = scan_reader.lightning_scan_cost(1, 32768, SIZES)
    tokens = 32768 * 3                      # a token and Lightning layer
    assert scan["flops"] // tokens == 6_815_744          # 6.82 M
    assert scan["bytes"] // tokens == 53_248             # 53.2 KB
    attn = attn_reader.block_sparse_attention_cost(1, 32768, SIZES)
    assert attn["flops"] == 7 * 2 * 16 * 181_616_640 * 128
    # q, o, dO, dq at 16 heads; k, v, dk, dv at 1; lse and delta; the
    # selection as 96 block indices of 4 bytes a query, each way
    row = 32768 * 128 * 2
    assert attn["bytes"] == 6 * 16 * row + 6 * row + 3 * 16 * 32768 * 4 \
        + 2 * 32768 * 96 * 4
    # a chunked scan is HBM-bound, the selected attention MXU-bound
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    assert attn["flops"] / 197e12 > attn["bytes"] / 819e9


@pytest.mark.parametrize("reader", [scan_reader, attn_reader],
                         ids=["lightning_scan", "block_sparse_attention"])
@pytest.mark.parametrize("view", ["no_training", "another_cells_sizes",
                                  "untraced"])
def test_a_reader_with_nothing_to_read_returns_nothing(reader, view):
    train = {"batch": 1, "seq": 32768}
    other = {"hidden_size": 1024, "num_hidden_layers": 24}
    v = {"no_training": {"cell": {"config_file": {"sizes": SIZES}}},
         "another_cells_sizes": {"train": train, "trace": None,
                                 "cell": {"config_file": {"sizes": other}}},
         "untraced": {"train": train, "trace": None,
                      "cell": {"config_file": {"sizes": SIZES}}}}[view]
    assert reader.read(v) is None
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
        "%", "device_trace", "kernels", "train_tokens_per_s")


def test_the_benchmark_lists_the_cell_where_the_issue_says():
    b = _json("BENCHMARK.json")
    assert [w["name"] for w in b["workloads"]][-1] == CELL
    assert len(b["workloads"]) == 12
    assert all(w["chips"] == 1 for w in b["workloads"])
    lists = {m["name"]: m.get("workloads") for m in
             b["end_to_end"] + b["per_layer"]}
    for name in ("train_tokens_per_s", "mfu", "train_attn_ms",
                 "train_indexer_ms", "train_select_ms", "train_mixer_ms",
                 "train_scan_ms", "train_mlp_ms", "train_head_loss_ms",
                 "train_unscoped_ms", "train_report_ms"):
        assert lists[name][-1] == CELL, name
    # two reports a window (20 steps of 1.47 s apart, 50 s): the reader asks
    # three, reads nothing here, and the cell stays off its list (as Keye's)
    assert CELL not in lists["train_stall_s"]
    for name in ("lightning_scan_roofline",
                 "block_sparse_attention_roofline"):
        assert lists[name] == [CELL]
    assert CELL not in lists["ssd_scan_roofline"]
    assert CELL not in lists["sparse_attention_roofline"]
    cell = _json("benchmark", "cells", CELL + ".json")
    assert cell["trainer"]["seq"] == 32768 and cell["trainer"]["batch"] == 1
    assert cell["traffic"] == "train_s32768_zipf" \
        and cell["trace_seconds"] == 8.0
