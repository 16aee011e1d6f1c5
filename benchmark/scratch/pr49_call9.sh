# PR 49, call 9 (no JAX_COMPILATION_CACHE_DIR of my own: call 8 pointed it at a
# directory that did not exist, every run compiled afresh and the call was cut
# at its hour after 11 of 13 runs): the two seeds set 2 still lacked, four
# fresh seeds, the traced run, the held rows while the cell trains, and the
# five cells the benchmark had, once each on this tree.
root=$(pwd); out=$root/chiprun_out/pr49/call9; mkdir -p $out
one() {  # <cell> <set> <seed> <trace>
  timeout 900 python3 benchmark/run.py --workload $1 --seed $3 --seconds 50 --trace $4 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"cell\": \"$1\", \"set\": $2, \"seed\": $3, \"trace\": $4, \"rc\": $rc, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/runs.jsonl
  grep "^  train:" $out/last.err | sed "s/^/$1 $3 /" | cut -c1-200 >> $out/reports.txt
  grep -o "held rows {.*" $out/last.err | sed "s/^/$1 $3 /" >> $out/held.txt
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
}
new=kimilinear_train_s8192
for seed in 2147484949 2147489999; do one $new 2 $seed 0; done
for seed in 404 505 2147490001 3000000007; do one $new 4 $seed 0; done
one $new 3 7 1
cp .bench_out/$new/train/flight.json $out/flight_traced.json 2>/dev/null
python3 benchmark/scratch/scope_ops.py .bench_out/$new --family kimi_linear --top 14 > $out/scope_ops.txt 2>&1
python3 benchmark/scratch/held_rows_stack.py --cell $new --train-steps 80 > $out/held_rows.json 2> $out/held_rows.err; echo "held_rows rc=$?"
for cell in kanana2_train_s8192 xing4_train_s4096 granite4h_train_s4096 phi4flash_train_s8192 gpt2m_train_s1024; do one $cell 5 3490000090 0; done
python3 - <<PY
import json
for l in open("$out/runs.jsonl"):
    r = json.loads(l); line = r["line"]
    print(r["cell"], r["set"], r["seed"], r["trace"], "rc", r["rc"], line and line["correct"], line and line["attempted"],
          line and {k: round(v["value"], 3) for k, v in line["metrics"].items() if k in ("train_tokens_per_s", "setup_s", "train_step_ms", "train_scan_ms", "kda_scan_roofline", "mfu")})
PY
cat $out/held.txt | cut -c1-300
