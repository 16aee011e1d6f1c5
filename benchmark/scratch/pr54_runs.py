#!/usr/bin/env python3
"""Runs of one cell whose flight records are KEPT (PR 54, ROADMAP A14):

    python3 benchmark/scratch/pr54_runs.py <out dir> --cell <cell> \
        --seeds 1,2,3 [--trace 0|1] [--seconds 50] [--env K=V ...] \
        [--tree <dir>] [--stop-when-low 0.99]

One ``benchmark/run.py`` a seed, one after the other, in ``--tree`` (a
checkout; default the current directory). After each run its result line
goes to ``<out dir>/lines.jsonl`` (with ``seed``, ``tree``, ``env``) and its
flight record to ``<out dir>/<tag>-<seed>.flight.json.gz``; stall bundles
land in ``<out dir>/postmortem`` (``RAY_TPU_POSTMORTEM_DIR``). A line of
standard output a run says what the record holds: tokens/s, steps, the
reports' gaps (least, greatest, the sum over the median: ``train_stall_s``'s
arithmetic on every report but the final one), the watcher's samples
(count, median cost, the least duty cycle) and every ``rtpu.chip.stall``.
``--stop-when-low r`` ends the series after a run whose tokens/s fall
under r times the best so far. A script, not a metric."""
import argparse
import gzip
import json
import os
import shutil
import statistics
import subprocess
import sys


def digest(record):
    ring = record["rings"].get("train_worker:0", [])
    reports = [ev["ts"] for ev in ring if ev["kind"] == "rtpu.train.report"]
    gaps = [b - a for a, b in zip(reports[:-1], reports[1:-1])]
    out = {"reports": len(reports)}
    if gaps:
        quiet = statistics.median(gaps)
        out.update(gap_least=round(min(gaps), 4),
                   gap_greatest=round(max(gaps), 4),
                   over_median=round(sum(g - quiet for g in gaps
                                         if g > 1.05 * quiet), 4))
    out["step_cache"] = [
        (ev["data"] or {}).get("cache") for ev in ring
        if ev["kind"] == "rtpu.jax.compile"
        and "bench_train_step" in ev["label"]]
    samples = [ev for ev in ring if ev["kind"] == "rtpu.chip.sample"]
    if samples:
        out["samples"] = len(samples)
        out["sample_ms"] = round(1e3 * statistics.median(
            ev["dur"] for ev in samples), 4)
        duty = [ev["data"]["chip"]["duty_pct"] for ev in samples
                if "duty_pct" in (ev["data"].get("chip") or {})]
        if duty:
            out["duty_min"] = min(duty)
    out["stalls"] = [
        {"at": round(ev["ts"] - reports[0], 3) if reports else ev["ts"],
         "dur": round(ev["dur"], 3), "condition": ev["data"]["condition"],
         "iteration": ev["data"]["iteration"]}
        for ev in ring if ev["kind"] == "rtpu.chip.stall"]
    out["lost"] = [ev["data"] for ev in ring
                   if ev["kind"] in ("rtpu.chip.source_lost",
                                     "rtpu.chip.watch_error")]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out")
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seconds", default="50")
    ap.add_argument("--env", action="append", default=[])
    ap.add_argument("--tree", default=".")
    ap.add_argument("--tag", default="run")
    ap.add_argument("--stop-when-low", type=float, default=0.0)
    ap.add_argument("--worker-exec", default="",
                    help="a statement for pr54_worker_exec.py")
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    env = dict(os.environ, RAY_TPU_POSTMORTEM_DIR=os.path.join(
        out, "postmortem"), **dict(e.split("=", 1) for e in args.env))
    best = 0.0
    for seed in args.seeds.split(","):
        command = ["benchmark/run.py"] if not args.worker_exec else [
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "pr54_worker_exec.py"), args.worker_exec]
        done = subprocess.run(
            [sys.executable, *command, "--workload", args.cell,
             "--seed", seed, "--seconds", args.seconds, "--trace",
             args.trace], cwd=args.tree, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        if done.returncode != 0:
            print(f"{args.tag} seed {seed}: rc {done.returncode}\n"
                  + done.stderr[-3000:], flush=True)
            continue
        line = json.loads(done.stdout.strip().splitlines()[-1])
        with open(os.path.join(out, "lines.jsonl"), "a") as f:
            f.write(json.dumps(dict(line, seed=seed, tag=args.tag,
                                    env=args.env, trace=args.trace)) + "\n")
        said = {"correct": line["correct"], "steps": line["attempted"]}
        metrics = line.get("end_to_end_in_traced_run") or line["metrics"]
        rate = metrics.get("train_tokens_per_s", {}).get("value", 0.0)
        said["tokens_per_s"] = rate
        for name in ("train_stall_s", "chip_sample_ms",
                     "chip_duty_cycle_min_pct", "train_host_gap_ms",
                     "train_report_ms", "step_compile_s",
                     "step_trace_lower_s"):
            if name in line["metrics"]:
                said[name] = line["metrics"][name]["value"]
        if "busy_s" in line.get("device", {}):
            said["idle_share"] = 1 - line["device"]["busy_s"] \
                / line["device"]["window_s"]
        trace_dir = os.path.join(args.tree, ".bench_out", args.cell, "trace")
        if os.path.isdir(trace_dir):
            said["trace_bytes"] = sum(
                os.path.getsize(os.path.join(d, f))
                for d, _, files in os.walk(trace_dir) for f in files)
        flight = os.path.join(args.tree, ".bench_out", args.cell, "train",
                              "flight.json")
        if os.path.exists(flight):
            with open(flight) as f:
                said.update(digest(json.load(f)))
            with open(flight, "rb") as f, gzip.open(os.path.join(
                    out, f"{args.tag}-{seed}.flight.json.gz"), "wb") as g:
                shutil.copyfileobj(f, g)
        print(f"{args.tag} seed {seed}: {json.dumps(said)}", flush=True)
        if args.stop_when_low and rate < args.stop_when_low * best:
            print(f"{args.tag}: seed {seed} read low ({rate} against "
                  f"{best}); the series ends here", flush=True)
            break
        best = max(best, rate)
    return 0


if __name__ == "__main__":
    sys.exit(main())
