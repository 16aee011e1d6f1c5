# usage: bash benchmark/scratch/chip_sets.sh <cell> <outdir, absolute> <seconds>
# From the root of a checkout, on the chip: one run that may compile (set 0),
# two sets of six runs with the same seeds in both, one traced run, and the
# spreads as the driver reads them. This is how PERF.md's bounds were measured.
cell=$1; out=$2; secs=$3; mkdir -p $out
one() {  # <set> <seed> <trace>
  timeout 900 python3 benchmark/run.py --workload $cell --seed $2 --seconds $secs --trace $3 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/$cell.jsonl
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
}
one 0 2147489999 0
for set in 1 2; do
  for seed in 101 202 303 2147483749 2147484949 2147489999; do one $set $seed 0; done
done
one 3 7 1
python3 - <<PY
import json, statistics
rows = [json.loads(l) for l in open("$out/$cell.jsonl")]
for s in (0, 1, 2, 3):
    ms = {}
    for r in rows:
        if r["set"] == s and r["line"]:
            for k, v in r["line"]["metrics"].items():
                ms.setdefault(k, []).append(v["value"])
    for k, v in ms.items():
        q = statistics.quantiles(v, n=4) if len(v) > 1 else [0, 0, 0]
        print("$cell set", s, k, "n", len(v), "median", statistics.median(v),
              "iqr_share", (q[2] - q[0]) / statistics.median(v), "values", v)
print("correct", [r["line"] and r["line"]["correct"] for r in rows],
      "failed", [r["line"] and r["line"]["failed"] for r in rows])
print("last line of the traced run:", json.dumps(rows[-1]["line"]))
print("why_not of the first warm run:", json.dumps(rows[1]["line"] and rows[1]["line"]["why_not"]))
print("device:", json.dumps(rows[1]["line"] and rows[1]["line"]["device"]))
PY
