#!/usr/bin/env python3
"""What ``scripts/cell_runs.sh`` left in ``<out>/runs.jsonl``, condensed
(PR 57):

    python3 scripts/cell_runs_report.py chiprun_out/<label>/runs.jsonl

One line a run (tree, seed, exit code, ``correct``, the end-to-end metrics, the
held rows), a traced run's per-layer metrics and largest device operations,
then by cell the change over the parent: seed by seed where both trees ran one
(the driver's pairs) and median over median, with each side's spread as the
driver reads it (``statistics.quantiles(n=4)`` over the median). Where two
trees' traced runs of one seed kept their final reports, whether every step's
loss and the held rows are the same. A script, not a metric."""
import collections
import json
import os
import statistics
import sys


def _value(line, key):
    m = (line or {}).get("metrics") or {}
    return m[key]["value"] if key in m else None


def _spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    path = sys.argv[1]
    rows = [json.loads(x) for x in open(path)]
    by = collections.defaultdict(lambda: collections.defaultdict(dict))
    for r in rows:
        line = r["line"] or {}
        print(r["tree"], r["cell"], "seed", r["seed"], "trace", r["trace"],
              "rc", r["rc"], "took_s", r["took_s"], "correct",
              line.get("correct"), "failed", line.get("failed"), "attempted",
              line.get("attempted"), "tokens/s",
              _value(line, "train_tokens_per_s"), "setup_s",
              _value(line, "setup_s"), r["held"])
        if r["trace"]:
            print("  traced:", json.dumps({
                k: v["value"]
                for k, v in (line.get("metrics") or {}).items()}))
            print("  device:", json.dumps(line.get("device")),
                  json.dumps(line.get("end_to_end_in_traced_run")))
            for op, s in (line.get("breakdown") or {}).get("device_ops", []):
                print(f"    {s:9.5f} s  {op}")
        elif _value(line, "train_tokens_per_s") is not None:
            by[r["cell"]][r["seed"]][r["tree"]] = line
    for cell, seeds in by.items():
        trees = sorted({t for s in seeds.values() for t in s})
        for key in ("train_tokens_per_s", "setup_s"):
            cols = {t: [_value(s[t], key) for s in seeds.values() if t in s]
                    for t in trees}
            for t, v in cols.items():
                print(cell, key, t, "n", len(v), "median",
                      statistics.median(v), "spread", _spread(v))
            if "parent" in cols and len(trees) == 2:
                other = [t for t in trees if t != "parent"][0]
                pairs = [(seed, _value(s[other], key) / _value(s["parent"], key))
                         for seed, s in seeds.items() if len(s) == 2]
                print(cell, key, other, "/ parent by seed:",
                      [(seed, round(x, 5)) for seed, x in pairs],
                      "median over median",
                      statistics.median(cols[other])
                      / statistics.median(cols["parent"]))
    # the same result: two trees' final reports of one seed
    reports = collections.defaultdict(dict)
    for name in sorted(os.listdir(os.path.dirname(path))):
        if name.endswith(".report.json"):
            tree, cell, seed = name[:-len(".report.json")].rsplit(".", 2)
            reports[(cell, seed)][tree] = json.load(
                open(os.path.join(os.path.dirname(path), name)))
    for (cell, seed), trees in reports.items():
        if len(trees) != 2:
            continue
        (a, ra), (b, rb) = sorted(trees.items())
        n = min(len(ra["losses"]), len(rb["losses"]))
        diffs = [abs(x - y) for x, y in zip(ra["losses"][:n], rb["losses"][:n])]
        print(cell, "seed", seed, a, "against", b, ":", n, "steps' losses,",
              sum(d == 0 for d in diffs), "equal, largest difference",
              max(diffs), "at step", diffs.index(max(diffs)), "(losses",
              ra["losses"][0], "to", ra["losses"][n - 1], "); held_rows",
              "EQUAL" if ra.get("held_rows") == rb.get("held_rows")
              else "DIFFER", ra.get("held_rows"), rb.get("held_rows"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
