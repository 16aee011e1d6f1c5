# usage: bash benchmark/scratch/pr52_sets.sh <cell> <outdir, absolute> <seconds> <part: a|b>   (PR 52)
# chip_sets.sh cut in two calls, so that neither nears the hour a call may last: part a is the run that may
# compile (set 0) and set 1's six seeds, part b set 2's six (the same seeds) and the traced run; the spreads are
# printed over whatever <outdir>/<cell>.jsonl holds by then, as the driver reads them.
cell=$1; out=$2; secs=$3; mkdir -p $out
one() {  # <set> <seed> <trace>
  timeout 900 python3 benchmark/run.py --workload $cell --seed $2 --seconds $secs --trace $3 > $out/last.out 2> $out/last.err; rc=$?
  held=$(grep -h -o "held rows {[^}]*}" $out/last.err $out/last.out | tail -n 1)
  echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"held\": \"$held\", \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/$cell.jsonl
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
}
seeds="101 202 303 2147483749 2147484949 2147489999"
if [ $4 = a ]; then
  one 0 2147489999 0
  for seed in $seeds; do one 1 $seed 0; done
else
  for seed in $seeds; do one 2 $seed 0; done
  one 3 7 1
fi
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
for r in rows:
    l = r["line"] or {}
    print("set", r["set"], "seed", r["seed"], "rc", r["rc"], "correct", l.get("correct"), "failed", l.get("failed"),
          "attempted", l.get("attempted"), r.get("held"))
print("last line:", json.dumps(rows[-1]["line"]))
PY
