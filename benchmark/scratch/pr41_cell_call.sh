# usage: bash benchmark/scratch/pr41_cell_call.sh <pairs|sets> <cell> [<cell> ...]
# PR 41's calls, on the chip, from the root of the copy; the parent's tree is
# chip_check/parent (git archive of the parent commit). Each starts with a 5 s
# run of this tree that may compile.
#   pairs: one 50 s run of this tree and one of the parent's on one seed (a
#     pair on one machine), a traced run of this tree, and the loop's final
#     report of both trees on one seed (scratch/final_report.py; 5 s windows:
#     what is compared lies outside the window).
#   sets: two sets of six 50 s runs of this tree with the same seeds in both,
#     a second pair's parent run between them, two more traced runs.
# Then every line's verdict and the spreads as the driver reads them. Both
# trees keep their programs in ONE compile cache (their step programs are the
# same text), so the parent compiles nothing.
mode=$1; shift
parent=chip_check/parent
root=$(pwd)
# this tree's side runs from chip_check/final where that is there: what git
# would commit and nothing else (git archive $(git write-tree)), no git
# repository and not at /root/repo, as the driver's checkout is
change=$root; if [ -d chip_check/final/benchmark ]; then change=$root/chip_check/final; fi
out=$root/chiprun_out/pr41; mkdir -p $out; echo "this tree's side runs in $change"
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache
run() {  # <side> <set> <seed> <seconds> <trace>
  if [ $1 = p ]; then cd $root/$parent; else cd $change; fi
  timeout 1200 python3 benchmark/run.py --workload $cell --seed $3 --seconds $4 --trace $5 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"side\": \"$1\", \"set\": $2, \"seed\": $3, \"seconds\": $4, \"trace\": $5, \"rc\": $rc, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/$cell.$mode.jsonl
  grep "^  train:" $out/last.err | sed "s/^/$1 $3 /" >> $out/$cell.$mode.reports.txt
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
  cd $root
}
seeds="4101 4202 4303 2147487749 2147488949 2147493999"
for cell in "$@"; do
if [ $mode = pairs ]; then
  run c 0 2147495555 5 0
  run c 1 4202 50 0; run p 1 4202 50 0
  run c 3 4507 50 1
  for side in p c; do
    if [ $side = p ]; then cd $root/$parent; else cd $change; fi
    timeout 1200 python3 $root/benchmark/scratch/final_report.py $out/$cell.final_$side.json --workload $cell --seed 4303 --seconds 5 --trace 0 > $out/last.out 2> $out/last.err || tail -5 $out/last.err | cut -c1-500
    cd $root
  done
else
  run c 0 2147495556 5 0
  for seed in $seeds; do run c 1 $seed 50 0; done
  run p 1 2147488949 50 0
  for seed in $seeds; do run c 2 $seed 50 0; done
  run c 3 2147496007 50 1; run c 3 77 50 1
fi
python3 - <<PY
import json, statistics
rows = [json.loads(l) for l in open("$out/$cell.$mode.jsonl")]
for r in rows:
    line = r["line"] or {}
    m = {k: v["value"] for k, v in (line.get("metrics") or {}).items()}
    print(r["side"], "set", r["set"], "seed", r["seed"], "trace", r["trace"], "rc", r["rc"],
          "correct", line.get("correct"), "failed", line.get("failed"), "attempted", line.get("attempted"),
          json.dumps(m), "compared", json.dumps(line.get("compared")),
          "memory", (line.get("device") or {}).get("memory_peak_bytes"),
          "busy/window", (line.get("device") or {}).get("busy_s"), (line.get("device") or {}).get("window_s"))
for s in (1, 2):
    ms = {}
    for r in rows:
        if r["side"] == "c" and r["set"] == s and r["line"]:
            for k, v in r["line"]["metrics"].items():
                ms.setdefault(k, []).append(v["value"])
    for k, v in ms.items():
        if len(v) < 2:
            continue
        q = statistics.quantiles(v, n=4)
        print("$cell set", s, k, "n", len(v), "median", statistics.median(v),
              "iqr_share", (q[2] - q[0]) / statistics.median(v))
print("last line of the last traced run:", json.dumps(rows[-1]["line"]))
for side in "pc" if "$mode" == "pairs" else "":
    f = json.load(open("$out/$cell.final_%s.json" % side))
    print("final report,", side, "seed 4303:", json.dumps(
        {k: f.get(k) for k in ("reference", "compiles_at_warm", "compiles_at_end",
                               "compiles_after_reference", "held_rows")}))
PY
cut -c1-1500 $out/$cell.$mode.reports.txt
done
