#!/bin/bash
# PR 43 call 5, on the final tree: chip_check/final = git archive $(git write-tree) (what git would
# commit and nothing else), chip_check/parent = git archive of the parent commit.
#   the new cell from chip_check/final: one run that may compile, one 50 s run, one traced run;
#   each old cell: parent, change, change, parent (two pairs, a seed a pair), 50 s each.
# Both sides write ONE compile cache (their step programs are the same text).
root=/root/repo; out=$root/chiprun_out/pr43/final; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache
run() {  # <side dir> <tag> <cell> <seed> <seconds> <trace>
  cd $root/chip_check/$1
  timeout 1200 python3 benchmark/run.py --workload $3 --seed $4 --seconds $5 --trace $6 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"side\": \"$1\", \"tag\": \"$2\", \"cell\": \"$3\", \"seed\": $4, \"seconds\": $5, \"trace\": $6, \"rc\": $rc, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/runs.jsonl
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
  cd $root
}
run final warm phi4flash_train_s8192 3100000001 5 0
run final run phi4flash_train_s8192 3100000003 50 0
run final traced phi4flash_train_s8192 3100000005 50 1
cp $root/chip_check/final/.bench_out/phi4flash_train_s8192/train/flight.json $out/flight.json 2>/dev/null
( cd $root/chip_check/final && python3 benchmark/scratch/span_report.py .bench_out/phi4flash_train_s8192 --family phi4flash > $out/span_report.txt 2>&1 )
for cell in granite4h_train_s4096 gpt2m_train_s1024 kanana2_train_s8192; do
  run final warm $cell 3100000007 5 0
  run parent p1 $cell 3100000011 50 0; run final c1 $cell 3100000011 50 0
  run final c2 $cell 3100000013 50 0; run parent p2 $cell 3100000013 50 0
done
python3 - <<PY
import json
for l in open("$out/runs.jsonl"):
    r = json.loads(l); line = r["line"] or {}
    m = {k: v["value"] for k, v in (line.get("metrics") or {}).items()}
    print(r["cell"], r["side"], r["tag"], "seed", r["seed"], "rc", r["rc"], "correct", line.get("correct"),
          "attempted", line.get("attempted"), json.dumps(m),
          "memory", (line.get("device") or {}).get("memory_peak_bytes"),
          "busy/window", (line.get("device") or {}).get("busy_s"), (line.get("device") or {}).get("window_s"))
    if r["trace"]:
        print("  end to end in the traced run:", json.dumps(line.get("end_to_end_in_traced_run")))
        print("  compared:", json.dumps(line.get("compared")))
PY
