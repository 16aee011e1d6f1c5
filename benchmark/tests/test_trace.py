"""The reduction from a profiler trace to numbers: interval arithmetic on
synthetic events, and the readers on a trace recorded on a TPU v5e
(``data_train_trace.json.gz``: the first 7000 operations, a little more
than one train step of the training cell, trimmed by
``trace.save_fixture`` from the profiler's xplane file of PR 23's first
chip run; the xplane itself is 7.6 MB and is not kept)."""
import os

import pytest

from benchmark.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_total_subtract_clip():
    u = T.union([(0, 2), (1, 3), (5, 6), (6, 6), (5.5, 7)])
    assert u == [(0, 3), (5, 7)]
    assert T.total(u) == 5
    assert T.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert T.subtract([(0, 1), (2, 3)], [(0, 5)]) == []
    assert T.subtract([(0, 1)], []) == [(0, 1)]
    assert T.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def _trace(programs, ops, async_ops=()):
    return T.Trace({0: {"programs": [list(p) for p in programs],
                        "ops": [list(o) for o in ops],
                        "async_ops": [list(a) for a in async_ops]}}, [])


def test_busy_is_the_union_of_operations_and_the_window_their_span():
    tr = _trace([("jit_f", 0.0, 1.0)],
                [("%a", 0.0, 0.4, ""), ("%b", 0.2, 0.1, ""),   # nested
                 ("%c", 0.6, 0.4, "")])
    busy, window = T.busy_and_window(tr)
    assert busy == pytest.approx(0.8) and window == pytest.approx(1.0)


def test_busy_averages_over_devices():
    tr = T.Trace({0: {"programs": [], "ops": [["%a", 0.0, 1.0, ""]]},
                  1: {"programs": [], "ops": [["%a", 0.0, 0.5, ""]]}}, [])
    assert T.busy_and_window(tr)[0] == pytest.approx(0.75)


def test_program_name_drops_the_run_id():
    assert T.program_name("jit__decode(1234567890)") == "jit__decode"
    assert T.program_name("jit_bench_train_step") == "jit_bench_train_step"


def test_split_hlo_keeps_the_result_name_and_the_kernel_target():
    name, detail = T.split_hlo(
        '%closed_call.22 = (bf16[128,1024,64]{2,1,0}) custom-call(bf16[1] '
        '%x), custom_call_target="tpu_custom_call", operand_layout={}')
    assert name == "%closed_call.22"
    assert "custom_call_target=tpu_custom_call" in detail
    assert T.split_hlo("copy.5") == ("copy.5", "")


def test_gaps_between_programs_skip_pairs_with_a_prefill_between():
    tr = _trace([("jit__decode", 0.0, 1.0), ("jit__decode", 1.5, 1.0),
                 ("jit__prefill", 2.6, 0.5), ("jit__decode", 3.3, 1.0),
                 ("jit__decode", 4.4, 1.0)], [])
    gaps = T.gaps_between(tr, r"^jit__decode$", not_between=r"prefill")
    assert gaps == [pytest.approx(0.5), pytest.approx(0.1)]
    every = T.gaps_between(tr, r"^jit__decode$")
    assert every[1] == pytest.approx(0.8 - 0.5)   # less the prefill's time
    assert T.program_durations(tr, "prefill") == [0.5]


def test_self_time_charges_a_loop_only_what_its_body_leaves():
    st = T.self_times([("%while", 0.0, 10.0, ""), ("%f", 1.0, 3.0, ""),
                       ("%g", 5.0, 4.0, ""), ("%h", 5.5, 1.0, ""),
                       ("%tail", 10.0, 2.0, "")])
    assert st == {"%while": pytest.approx(3.0), "%f": pytest.approx(3.0),
                  "%g": pytest.approx(3.0), "%h": pytest.approx(1.0),
                  "%tail": pytest.approx(2.0)}


def test_idle_gaps_are_named_by_their_neighbours():
    tr = _trace([("jit__decode", 0.0, 1.0), ("jit__prefill", 1.4, 1.0),
                 ("jit__decode", 3.0, 1.0)],
                [("%a", 0.0, 1.0, ""), ("%b", 1.4, 1.0, ""),
                 ("%c", 3.0, 0.5, ""), ("%d", 3.7, 0.3, "")])
    gaps = dict(T.longest_idle_gaps(tr))
    assert gaps["jit__prefill -> jit__decode"] == pytest.approx(0.6)
    assert gaps["jit__decode -> jit__prefill"] == pytest.approx(0.4)
    assert gaps["inside jit__decode"] == pytest.approx(0.2)


def test_exposed_collective_time_is_what_no_compute_hides():
    programs = [("jit__decode", 0.0, 10.0), ("jit__prefill", 10.0, 5.0)]
    ops = [("%fusion.1", 0.0, 2.0, ""), ("%all-reduce.1", 2.0, 1.0, ""),
           ("%all-gather-start.1", 3.0, 0.1, ""), ("%fusion.2", 3.1, 2.0, ""),
           ("%all-gather-done.1", 5.1, 0.5, ""),
           ("%all-reduce.9", 11.0, 1.0, "")]
    asy = [("%all-gather-start.1", 3.0, 2.6, "")]
    sec, runs = T.exposed_collective_s(_trace(programs, ops, asy),
                                       within=r"decode")
    # all-reduce.1 whole (1.0), the gather's 0.1 before fusion.2 starts and
    # the 0.5 it is waited for; the 2.0 under fusion.2 are hidden; the
    # prefill's all-reduce lies outside the decode program
    assert sec == pytest.approx(1.0 + 0.1 + 0.5) and runs == 1
    assert T.exposed_collective_s(_trace(programs, ops[:1])) is None


def test_a_trace_without_a_device_plane_gives_nothing():
    empty = T.Trace({}, [])
    assert T.busy_and_window(empty) is None
    assert T.top_device_ops(empty) == [] and T.longest_idle_gaps(empty) == []
    assert T.programs(empty) == [] and T.exposed_collective_s(empty) is None


def test_load_xplane_of_a_cpu_run_has_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    assert T.load_xplane(path).devices == {}


# -- the recorded trace ---------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return T.load_fixture(os.path.join(HERE, "data_train_trace.json.gz"))


def test_recorded_trace_reduces(recorded):
    from benchmark.lib import spec

    progs = T.programs(recorded)
    assert {p[0] for p in progs} == {"jit_bench_train_step"}
    busy, window = T.busy_and_window(recorded)
    assert 0.95 < busy / window <= 1.0        # a train step keeps the chip busy
    kernels = T.ops_matching(recorded, r"custom_call_target=tpu_custom_call")
    assert len(kernels) >= 24                 # at least one per layer
    top = T.top_device_ops(recorded, 5)
    assert len(top) == 5 and top[0][1] >= top[-1][1] > 0
    assert sum(T.self_times(recorded.devices[0]["ops"]).values()) == \
        pytest.approx(busy, rel=1e-3)
    readers = spec.load_metric_readers("layer_metrics")
    view = {"trace": recorded, "spans": {},
            "cell": spec.load_cell(sorted(
                f[:-5] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                        "cells"))
                if spec.load_cell(f[:-5])["traffic_file"]["kind"]
                == "train")[0]),
            "train": {"batch": 8, "seq": 1024, "steps": 10, "tokens": 81920,
                      "elapsed_s": 2.0},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    got = {n: r.read(view) for n, r in readers.items()}
    assert 100 < got["train_step_ms"] < 300
    # recorded in PR 23, before the kernels had names ("%closed_call.7",
    # "%checkpoint.3"): the reader of the flash kernels knows them by their
    # pinned names and finds nothing to read here (test_program.py reads
    # it on a trace recorded since); their time is there all the same
    assert got["flash_attention_roofline"] is None
    from benchmark.layer_metrics._common import kernel_s_per_step
    assert 0 < kernel_s_per_step(
        view, r"custom_call_target=tpu_custom_call") < 0.036
    # 8 x 1024 tokens x 2.28 GFLOP in a 197.27 ms step of a 197 TFLOP/s chip
    assert got["mfu"] == pytest.approx(48.06, abs=0.05)
    assert got["collective_exposed_ms"] is None    # one chip: no collective
