# usage: bash benchmark/scratch/pr64_sets.sh <cell> <outdir, absolute or from the checkout's root> <seconds>
# benchmark/scratch/chip_sets.sh (one run that may compile, two sets of six runs with the same
# seeds in both, one traced run, the spreads as the driver reads them) with what ISSUE 64 asks
# beside it: every run's held rows and the steps it attempted (a run that stood still, A14,
# attempts fewer than the others of its set: here two or more under the set's median; one under
# it is a slower seed's own count and repeats), and each set's spread with and without such runs.
# SEEDS="a b c d e f" in the environment gives the sets other seeds than chip_sets.sh's six (the
# last also warms the cache in the run before the sets), TRACED_SEED the traced run's.
cell=$1; out=$2; secs=$3; mkdir -p $out
one() {  # <set> <seed> <trace>
  timeout 900 python3 benchmark/run.py --workload $cell --seed $2 --seconds $secs --trace $3 > $out/last.out 2> $out/last.err; rc=$?
  held=$(grep -a "train:" $out/last.err | sed -n "s/.*held rows \(.*\)$/\1/p" | tr "'" '"' | tail -n 1)
  echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"held_rows\": ${held:-null}, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/$cell.jsonl
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
}
seeds=${SEEDS:-101 202 303 2147483749 2147484949 2147489999}
one 0 ${seeds##* } 0
for set in 1 2; do
  for seed in $seeds; do one $set $seed 0; done
done
one 3 ${TRACED_SEED:-7} 1
python3 - <<PY
import json, statistics
rows = [json.loads(l) for l in open("$out/$cell.jsonl")]
def spread(v):
    q = statistics.quantiles(v, n=4)
    return (q[2] - q[0]) / statistics.median(v)
for s in (0, 1, 2, 3):
    runs = [r for r in rows if r["set"] == s and r["line"]]
    for r in runs:
        print("$cell set", s, "seed", r["seed"], "attempted", r["line"]["attempted"], "correct",
              r["line"]["correct"], "failed", r["line"]["failed"],
              {k: v["value"] for k, v in r["line"]["metrics"].items() if k in ("train_tokens_per_s", "setup_s")},
              "held_rows", json.dumps(r["held_rows"]))
    if len(runs) < 2:
        continue
    most = statistics.median(r["line"]["attempted"] for r in runs)
    still = [r["seed"] for r in runs if r["line"]["attempted"] <= most - 2]
    for k in ("train_tokens_per_s", "setup_s"):
        v = [r["line"]["metrics"][k]["value"] for r in runs]
        quiet = [r["line"]["metrics"][k]["value"] for r in runs if r["seed"] not in still]
        print("$cell set", s, k, "n", len(v), "median", statistics.median(v), "iqr_share", spread(v),
              "stood still (seeds)", still, "iqr_share without them", spread(quiet) if len(quiet) > 1 else None)
print("correct", [r["line"] and r["line"]["correct"] for r in rows],
      "failed", [r["line"] and r["line"]["failed"] for r in rows])
print("last line of the traced run:", json.dumps(rows[-1]["line"]))
print("device:", json.dumps(rows[1]["line"] and rows[1]["line"]["device"]))
PY
