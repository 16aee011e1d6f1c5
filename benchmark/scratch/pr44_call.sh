#!/bin/bash
# PR 44's chip calls: bash benchmark/scratch/pr44_call.sh <change side> <tag> <what> [seed]
#   <change side>: a directory under chip_check/ (chip_check/final = git archive $(git write-tree)), or
#                  "tree" for the copy's own root (the working tree as it stood on disk);
#   chip_check/parent = git archive 5baafaa, the parent commit;
#   <what> = claimed: benchmark/scratch/paired_chip.py on the change, then phi4flash_train_s8192
#            parent, change, change, parent (two pairs, a seed a pair, 50 s), then parent and change
#            traced with ONE seed, each with its flight record and span report;
#   <what> = others: gpt2m_train_s1024, kanana2_train_s8192, granite4h_train_s4096, each parent then
#            change on one seed (their compiled steps are the parent's: scripts/train_step_hlo.py).
# Both sides write ONE compile cache: the machine's own where it comes with one (call 2 on), so that
# a side's second run of a cell reads its step back; call 1 set a directory of its own, and nothing
# was read back from it (every run of call 1 compiled).
root=/root/repo; side=$1; tag=$2; what=$3; seed=${4:-3200000001}
out=$root/chiprun_out/pr44/$tag; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$root/.jax_cache}
echo "compile cache: $JAX_COMPILATION_CACHE_DIR"
dir() { if [ "$1" = tree ]; then echo $root; else echo $root/chip_check/$1; fi; }
run() {  # <side> <tag> <cell> <seed> <trace>
  cd $(dir $1)
  timeout 1200 python3 benchmark/run.py --workload $3 --seed $4 --seconds 50 --trace $5 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"side\": \"$1\", \"tag\": \"$2\", \"cell\": \"$3\", \"seed\": $4, \"trace\": $5, \"rc\": $rc, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/runs.jsonl
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
  if [ $5 = 1 ]; then
    cp .bench_out/$3/train/flight.json $out/flight_$2.json 2>/dev/null
    python3 benchmark/scratch/span_report.py .bench_out/$3 --family phi4flash > $out/span_report_$2.txt 2>&1
  fi
  cd $root
}
if [ $what = claimed ]; then
  cell=phi4flash_train_s8192
  ( cd $(dir $side) && timeout 900 python3 benchmark/scratch/paired_chip.py > $out/paired_chip.json 2> $out/paired_chip.err ) \
    || tail -5 $out/paired_chip.err | cut -c1-500
  cat $out/paired_chip.json
  run parent p1 $cell $seed 0; run $side c1 $cell $seed 0
  run $side c2 $cell $((seed + 2)) 0; run parent p2 $cell $((seed + 2)) 0
  run parent traced_parent $cell $((seed + 4)) 1; run $side traced_change $cell $((seed + 4)) 1
else
  for cell in gpt2m_train_s1024 kanana2_train_s8192 granite4h_train_s4096; do
    run parent p $cell $((seed + 6)) 0; run $side c $cell $((seed + 6)) 0
  done
fi
python3 - <<PY
import json
for l in open("$out/runs.jsonl"):
    r = json.loads(l); line = r["line"] or {}
    m = {k: v["value"] for k, v in (line.get("metrics") or {}).items()}
    dev = line.get("device") or {}
    print(r["cell"], r["side"], r["tag"], "seed", r["seed"], "rc", r["rc"], "correct", line.get("correct"),
          "steps", line.get("attempted"), json.dumps(m), "memory", dev.get("memory_peak_bytes"),
          "busy/window", dev.get("busy_s"), dev.get("window_s"), "kind", dev.get("kind"))
    if r["trace"]:
        print("  end to end in the traced run:", json.dumps(line.get("end_to_end_in_traced_run")))
        print("  compared:", json.dumps(line.get("compared")))
        print("  device_ops:", json.dumps((line.get("breakdown") or {}).get("device_ops"))[:1500])
PY
