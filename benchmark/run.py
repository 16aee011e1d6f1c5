#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <cell> --rehearse-cpu --trace <0|1>
    python3 benchmark/run.py --workload <cell> --sweep 2,3,4,5 --seconds 20

Runs one cell (``benchmark/cells/<cell>.json``) through the entry points
users call: training through ``JaxTrainer(...).fit()``, serving through
``serve.run(...)`` behind ``serve.start_http_proxy`` with requests streamed
over HTTP. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``). With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, as ``BENCHMARK.json``
lists them for the cell.

This file holds no name of a cell, a configuration or a metric: cells,
configurations and traffic mixes are data files, metrics are one reader
file each (``benchmark/end_to_end/``, ``benchmark/layer_metrics/``).

One process per chip: this parent never initialises a jax backend; the chip
belongs to the trainer's worker or the serving replica, and the run ends
only when that process is gone. Without a TPU the run fails and prints no
result line; ``--rehearse-cpu`` walks the same control flow at tiny presets
on the CPU and prints counts and ``correct`` only.
"""
from __future__ import annotations

T_PROCESS_START = __import__("time").time()

import argparse
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import spec as _spec  # noqa: E402
from benchmark.lib import stats as _stats  # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def wait_gone(pid: int, timeout: float = 90.0) -> None:
    """The run ends only when the process that held the chip is gone."""
    deadline = time.monotonic() + timeout
    while os.path.exists(f"/proc/{pid}"):
        if time.monotonic() > deadline:
            raise RuntimeError(f"chip worker pid {pid} still alive "
                               f"{timeout}s after shutdown")
        time.sleep(0.05)


def declared_metrics(cell_name: str) -> dict:
    """-> {"end_to_end": [names], "per_layer": [names]} that
    BENCHMARK.json lists for this cell (a metric without a ``workloads``
    key is reported by every cell)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    return {group: [m["name"] for m in bm[group]
                    if cell_name in m.get("workloads", [cell_name])]
            for group in ("end_to_end", "per_layer")}


def read_metrics(group: str, names: list, view: dict) -> dict:
    """Each metric is read by the file of its name; a reader that finds
    nothing to read returns None and the metric is left out."""
    readers = _spec.load_metric_readers(group)
    out = {}
    for name in names:
        mod = readers.get(name)
        if mod is None:
            raise RuntimeError(f"BENCHMARK.json lists {group} metric "
                               f"{name!r} but benchmark/{group}/ has no "
                               f"reader of that name")
        value = mod.read(view)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


# ---------------------------------------------------------------------------
# training cells
# ---------------------------------------------------------------------------

def run_train(cell: dict, args, out_dir: str) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark.lib.chip import train_loop

    chips = cell["chips"]
    config = {
        "model": cell["config_file"]["model"],
        "reference": cell["config_file"]["reference"],
        "sizes": cell["config_file"]["sizes"],
        "traffic": cell["traffic_file"], "trainer": cell["trainer"],
        "seed": args.seed, "seconds": args.seconds,
        "require_tpu": not args.rehearse_cpu,
        "trace_dir": os.path.join(out_dir, "trace") if args.trace else None,
        "trace_seconds": cell.get("trace_seconds", 3.0)}
    t_init = time.time()
    ray_tpu.init(num_cpus=8, num_tpus=chips)
    try:
        result = JaxTrainer(
            train_loop, train_loop_config=config,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": float(chips)}),
            run_config=RunConfig(name="train", storage_path=out_dir)).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    final = result.metrics_history[-1]
    if not final.get("final"):
        raise RuntimeError("the train loop ended without its final report")
    wait_gone(final["device"]["pid"])
    reports = [h for h in result.metrics_history if not h.get("final")]
    losses = final["losses"]
    window = losses[final["first_in_window"]:]
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    first10, last10 = sum(window[:10]) / 10, sum(window[-10:]) / 10
    falling = len(window) >= 20 and last10 < first10
    inside = (final["compiles_at_end"]["backend_compiles"]
              - final["compiles_at_warm"]["backend_compiles"])
    ref = final["reference"]
    say(f"  train: {final['steps']} steps of {final['batch']}x"
        f"{final['seq']} in {final['elapsed_s']:.2f} s; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; reports {len(reports)}; "
        f"compiles inside the window {inside}; reference {ref}"
        + (f"; held rows {final['held_rows']}" if "held_rows" in final
           else ""))
    return {
        "kind": "train", "device": final["device"], "train": final,
        "correct": bool(ref["ok"] and finite and falling and inside == 0),
        "why_not": {"reference": ref, "finite": finite,
                    "falling": falling, "compiles_in_window": inside},
        "compared": {
            **{f"{at}.{name}": [ref[at][number], ref[at][limit]]
               for at in ("first_step", "after_window")
               for name, number, limit in (
                   ("loss_abs_diff", "abs_diff", "tolerance"),
                   ("bf16_noise", "bf16_noise", "bf16_noise_limit"))},
            "n_params": [ref["n_params"], ref["n_params_from_sizes"]],
            "window_steps_at_least": [len(window), 20],
            "window_loss_last10_under_first10": [last10, first10],
            "compiles_in_window": [inside, 0]},
        "attempted": final["steps"], "failed": 0,
        "spans": {
            "process_start_to_window": final["t_window"] - T_PROCESS_START,
            "init_to_chip_worker": final["t_enter"] - t_init,
            "jax_start": final["t_jax_up"] - final["t_enter"],
            "build": final["t_built"] - final["t_jax_up"],
            "warm_up": final["t_warm"] - final["t_built"]},
        "trace_span": final["trace_span"], "reports": reports}


# ---------------------------------------------------------------------------
# serving cells
# ---------------------------------------------------------------------------

class StatsSampler(threading.Thread):
    """Reads the engine's ``stats()`` every ``period`` seconds through the
    deployment handle (control calls, off the request path)."""

    def __init__(self, handle, period: float):
        super().__init__(daemon=True, name="bench-stats")
        self.handle, self.period = handle, period
        self.samples: list = []
        self._halt = threading.Event()

    def run(self) -> None:
        import ray_tpu

        while not self._halt.wait(self.period):
            try:
                self.samples.append(ray_tpu.get(
                    self.handle.bench_stats.remote(), timeout=30))
            except Exception as e:  # noqa: BLE001 - sampling is best effort
                self.samples.append({"error": repr(e), "t": time.time()})

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=35)


def start_replica(cell: dict, args):
    import ray_tpu
    from ray_tpu import serve

    from benchmark.lib.chip import BenchServer

    chips = cell["chips"]
    dep = dict(cell.get("deployment", {}))
    t_init = time.time()
    ray_tpu.init(num_cpus=8, num_tpus=chips)
    app = serve.deployment(
        name="llm", num_replicas=1, ray_actor_options={"num_tpus": chips},
        max_concurrent_queries=int(dep.get("max_concurrent_queries", 256)),
        # construction compiles or reads every program of the cell
        health_check_period_s=10.0, health_check_timeout_s=600.0,
    )(BenchServer).bind(
        model=cell["config_file"]["model"], engine_config=cell["engine"],
        seed=args.seed % (1 << 31), require_tpu=not args.rehearse_cpu,
        reference=cell["config_file"]["reference"])
    handle = serve.run(app, timeout=1000.0)
    setup = ray_tpu.get(handle.bench_setup.remote(), timeout=120)
    if "init_error" in setup:
        raise RuntimeError("the replica could not be built:\n"
                           + setup["init_error"])
    host, port = serve.start_http_proxy()
    return handle, f"http://{host}:{port}/llm?stream=1", setup, t_init


def offer_load(cell: dict, args, handle, url: str, setup: dict,
               load: dict, seconds: float, trace_dir, sample: bool):
    """One window of the cell's traffic at ``load``. -> the client's
    records and what was sampled meanwhile. The engine's counters are
    sampled (every 100 ms, under the engine's lock) only in a traced run or
    a sweep, never in a run that reports end-to-end metrics."""
    import ray_tpu

    from benchmark.lib.client import LoadRun, stream_request
    from benchmark.lib.traffic import describe, make_sessions

    traffic = cell["traffic_file"]
    sessions = make_sessions(traffic, load, args.seed, seconds,
                             setup["vocab"], setup["max_prompt"])
    threads = int(load.get("clients") or load.get("client_threads", 64))
    run = LoadRun(url, sessions, threads,
                  float(cell.get("request_timeout_s", 120.0)))
    # the proxy's handle and this process's sockets, once, outside the window
    warm = stream_request(url, [1, 2, 3], 2, "warm", 120.0)
    if not warm["ok"]:
        raise RuntimeError(f"warm-up request over HTTP failed: {warm}")
    stats0 = ray_tpu.get(handle.bench_stats.remote(), timeout=60)
    sampler = StatsSampler(handle, float(cell.get("sample_period_s", 0.1)))
    tracer = None
    trace_span = [None, None]
    closed = traffic["kind"] == "serve_closed"
    t0w, t0 = time.time(), time.perf_counter()
    if trace_dir:
        def traced():
            time.sleep(seconds * 0.4)
            trace_span[0] = ray_tpu.get(
                handle.trace_start.remote(trace_dir), timeout=120)
            time.sleep(float(cell.get("trace_seconds", 3.0)))
            trace_span[1] = ray_tpu.get(handle.trace_stop.remote(),
                                        timeout=300)
        tracer = threading.Thread(target=traced, daemon=True)
        tracer.start()
    if sample:
        sampler.start()
    if closed:
        run.run_closed(t0 + seconds)
    else:
        run.run_open(t0)
    t1 = time.perf_counter()
    if sample:
        sampler.stop()
    if tracer is not None:
        tracer.join(timeout=400)
    stats1 = ray_tpu.get(handle.bench_stats.remote(), timeout=60)
    return {"records": run.records, "t0": t0, "t1": t1, "t_window": t0w,
            "seconds": seconds, "offered": describe(sessions),
            "stats0": stats0, "stats1": stats1, "samples": sampler.samples,
            "trace_span": trace_span, "load": load}


def run_serve(cell: dict, args, out_dir: str) -> dict:
    import numpy as np
    import ray_tpu

    handle = None
    try:
        handle, url, setup, t_init = start_replica(cell, args)
        say(f"  replica up: {setup['device']}; buckets {setup['buckets']}")
        if args.sweep:
            return sweep(cell, args, handle, url, setup)
        win = offer_load(cell, args, handle, url, setup, cell["load"],
                         args.seconds,
                         os.path.join(out_dir, "trace") if args.trace
                         else None, sample=bool(args.trace))
        # correctness, outside the window, in the replica: a seeded
        # sample of completed requests against the float32 reference
        done = [r for r in win["records"] if r["ok"]]
        rng = np.random.default_rng([args.seed, 0xEF])
        pick = rng.permutation(len(done))[:int(cell.get("reference_sample",
                                                        8))]
        sample = []
        for i in pick:
            r = done[int(i)]
            sample.append({"prompt": r["prompt"], "generated": r["tokens"]})
        fin = ray_tpu.get(handle.bench_finish.remote(
            sample, int(cell["ref_pad"])), timeout=900) if sample else None
    finally:
        ray_tpu.shutdown()
    if fin is None:
        raise RuntimeError("no request completed: nothing to check")
    wait_gone(fin["device"]["pid"])
    lat = _stats.request_latencies(
        win["records"], float(cell.get("request_timeout_s", 120.0)))
    inside = (fin["compiles"]["backend_compiles"]
              - fin["compiles_at_warm"]["backend_compiles"])
    ref = fin["reference"]
    say(f"  serve: {lat['attempted']} requests, {lat['failed']} failed; "
        f"compiles inside the window {inside}; reference {ref}")
    errs = sorted({r["error"] for r in win["records"] if r["error"]})
    if errs:
        say(f"  request errors: {errs[:5]}")
    return {
        "kind": cell["traffic_file"]["kind"], "device": fin["device"],
        "correct": bool(ref["ok"] and inside == 0),
        "why_not": {"reference": ref, "compiles_in_window": inside},
        "compared": {
            "tokens_wrong": [ref["tokens_wrong"], 0],
            "worst_gap_to_ref_top": [ref["worst_gap_to_ref_top"],
                                     ref["tolerance"]],
            "first_logits_max_abs_diff": [ref["first_logits_max_abs_diff"],
                                          ref["tolerance"]],
            "bf16_noise": [ref["bf16_noise"], ref["bf16_noise_limit"]],
            "compiles_in_window": [inside, 0]},
        "attempted": lat["attempted"], "failed": lat["failed"],
        "window": win, "latencies": lat, "finish": fin,
        "spans": {
            "process_start_to_window": win["t_window"] - T_PROCESS_START,
            "init_to_chip_worker": setup["t_enter"] - t_init,
            "jax_start": setup["t_jax_up"] - setup["t_enter"],
            "build": setup["t_built"] - setup["t_jax_up"],
            "warm_up": setup["t_warm"] - setup["t_built"]},
        "trace_span": win["trace_span"]}


def sweep(cell: dict, args, handle, url: str, setup: dict) -> dict:
    """Finds the knee of an open-loop cell again: several windows in one
    process, one per rate. A rate is sustained if completed requests per
    second stay within 3 % of offered and the waiting queue at the window's
    end is no longer than at its middle. Prints a table; not a benchmark
    run (no result line for the driver)."""
    rows = []
    for rate in sorted(float(x) for x in args.sweep.split(",")):
        win = offer_load(cell, args, handle, url, setup,
                         dict(cell["load"], rate_rps=rate), args.seconds,
                         None, sample=True)
        lat = _stats.request_latencies(win["records"])
        ok = [r for r in win["records"] if r["ok"]]
        in_window = [r for r in ok if r["last"] < win["t0"] + args.seconds]
        waits = [s.get("waiting", 0) for s in win["samples"]
                 if "waiting" in s and s["t"] <= win["t_window"]
                 + args.seconds]
        mid = waits[len(waits) // 2 - 2:len(waits) // 2 + 3] or [0]
        end = waits[-5:] or [0]
        row = {"rate_rps": rate, "offered": lat["attempted"],
               "failed": lat["failed"],
               "offered_rps": lat["attempted"] / args.seconds,
               "completed_in_window_rps": len(in_window) / args.seconds,
               "drain_s": win["t1"] - win["t0"] - args.seconds,
               "waiting_mid": sum(mid) / len(mid),
               "waiting_end": sum(end) / len(end),
               "running_mean": (sum(s.get("running", 0)
                                    for s in win["samples"])
                                / max(1, len(win["samples"]))),
               "ttft_ms_p50": 1e3 * _stats.percentile(lat["ttft_s"], 50),
               "ttft_ms_p95": 1e3 * _stats.percentile(lat["ttft_s"], 95),
               "tpot_ms_p50": 1e3 * _stats.percentile(lat["tpot_s"], 50),
               "tpot_ms_p95": 1e3 * _stats.percentile(lat["tpot_s"], 95),
               "late_ms_p95": 1e3 * _stats.percentile(lat["late_s"], 95)}
        row["sustained"] = bool(
            row["completed_in_window_rps"] >= 0.97 * row["offered_rps"]
            and row["waiting_end"] <= row["waiting_mid"] + 0.5
            and lat["failed"] == 0)
        rows.append(row)
        say("  sweep " + json.dumps(row))
        if not row["sustained"] and len(rows) >= 2 \
                and not rows[-2]["sustained"]:
            break       # two rates in a row above the knee: higher ones
            #             only queue longer and drain for minutes
        time.sleep(1.0)
    return {"sweep": rows}


# ---------------------------------------------------------------------------

def reduce_trace(out_dir: str):
    """With ``--trace 1``: the profiler's file, read in this process after
    the chip's process has gone (reading needs jax, not a backend)."""
    from benchmark.lib import trace as _trace

    path = _trace.find_xplane(os.path.join(out_dir, "trace"))
    if path is None:
        raise RuntimeError("--trace 1 but the profiler left no xplane file")
    return _trace.load_xplane(path)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny presets on the CPU: control flow only")
    ap.add_argument("--sweep", default="",
                    help="comma-separated request rates: find the knee")
    args = ap.parse_args()
    cell = _spec.load_cell(args.workload, rehearse=args.rehearse_cpu)
    if args.seconds is None:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    # the compile cache: where the environment says, else the program's
    # fixed in-checkout directory; every program is kept, however quick
    from ray_tpu.core.worker_env import use_compile_cache

    cache_dir = use_compile_cache()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    out_dir = os.path.join(_spec.OUT_DIR, args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    say(f"benchmark: cell {args.workload} seed {args.seed} seconds "
        f"{args.seconds} trace {args.trace} rehearse_cpu "
        f"{args.rehearse_cpu}; compile cache {cache_dir}")

    kind = cell["traffic_file"]["kind"]
    result = (run_train if kind == "train" else run_serve)(cell, args,
                                                           out_dir)
    jax_mod = sys.modules.get("jax")
    if jax_mod and jax_mod._src.xla_bridge._backends:
        raise RuntimeError("the parent initialised a jax backend")
    if "sweep" in result:
        print(json.dumps(result))
        return 0
    dev = result["device"]
    if dev["count"] != cell["chips"]:
        raise RuntimeError(f"cell asks for {cell['chips']} chips, the "
                           f"worker saw {dev['count']}")
    if not args.rehearse_cpu and dev["platform"] != "tpu":
        raise RuntimeError(f"not a TPU: {dev}")
    view = dict(result, cell=cell, args={"seed": args.seed,
                                         "seconds": args.seconds},
                trace=None)
    device = {k: dev[k] for k in dev if k in ("platform", "kind", "count")
              or k.startswith("memory_")}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    declared = declared_metrics(args.workload)
    if args.trace:
        from benchmark.lib import trace as _trace

        view["trace"] = tr = reduce_trace(out_dir)
        view["end_to_end"] = read_metrics("end_to_end",
                                          declared["end_to_end"], view)
        bw = _trace.busy_and_window(tr)
        if bw is not None:
            device["busy_s"], device["window_s"] = bw
        line["metrics"] = read_metrics("layer_metrics",
                                       declared["per_layer"], view)
        line["breakdown"] = {
            "device_ops": _trace.top_device_ops(tr, 10),
            "idle_gaps": _trace.longest_idle_gaps(tr, 10)}
        line["end_to_end_in_traced_run"] = view["end_to_end"]
    else:
        line["metrics"] = read_metrics("end_to_end", declared["end_to_end"],
                                       view)
    line["device"] = device
    line["spans"] = result["spans"]
    line["why_not"] = result["why_not"]
    if args.rehearse_cpu:
        # a CPU walk-through gives counts and a verdict, never a time
        # under a device metric's name
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "metric_names": sorted(line["metrics"]),
                "device": {k: device[k] for k in ("platform", "kind",
                                                  "count")}}
    # what `correct` compared, each number beside its limit: the last key
    # of the line and the last lines of standard error
    line["compared"] = result["compared"]
    for name, (number, limit) in result["compared"].items():
        say(f"  compared {name}: {number!r} limit {limit!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
