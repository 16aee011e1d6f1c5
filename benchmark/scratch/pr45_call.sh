#!/bin/bash
# PR 45's chip calls: bash benchmark/scratch/pr45_call.sh <side> <tag> <what> [seed]
#   <side>: a directory under chip_check/ (chip_check/final = git archive $(git write-tree),
#           chip_check/parent = git archive ef0aeb2, the parent commit), or "tree" for the copy's own
#           root (the working tree as it stood on disk);
#   <what> = mix:    benchmark/scratch/mhc_chip.py (one sublayer's mixing alone at the cell's shape);
#   <what> = cell:   xing4_train_s4096 once, traced, with its flight record and span report;
#   <what> = parent: the parent commit on the new cell (it must fail at once: unknown family);
#   <what> = sets:   benchmark/scratch/chip_sets.sh xing4_train_s4096 (two sets of six seeds and a traced run);
#   <what> = held:   benchmark/scratch/held_rows.py --cell xing4_train_s4096 --train-steps 80;
#   <what> = others: the four cells the benchmark had, once each on <side>, one seed;
#   <what> = oldtraced: kanana2_train_s8192 traced on the PARENT with this PR's benchmark files laid over it (what the
#                    driver's traced runs of an old cell run on the parent's side);
#   <what> = seeds:  xing4_train_s4096 six times on <side>, each run with a seed of its own (seed+11, +22, ...),
#                    and the spread as the driver reads it.
# several <what> may be given joined by commas; they run in that order in ONE call.
root=/root/repo; side=$1; tag=$2; whats=$3; seed=${4:-3300000001}
out=$root/chiprun_out/pr45/$tag; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$root/.jax_cache}
echo "compile cache: $JAX_COMPILATION_CACHE_DIR"
dir() { if [ "$1" = tree ]; then echo $root; else echo $root/chip_check/$1; fi; }
cell=xing4_train_s4096
run() {  # <side> <tag> <cell> <seed> <trace>
  cd $(dir $1)
  t0=$(date +%s)
  timeout 1500 python3 benchmark/run.py --workload $3 --seed $4 --seconds 50 --trace $5 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"side\": \"$1\", \"tag\": \"$2\", \"cell\": \"$3\", \"seed\": $4, \"trace\": $5, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 )), \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" | tee -a $out/runs.jsonl | cut -c1-3000
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-600; fi
  if [ $5 = 1 ]; then
    cp .bench_out/$3/train/flight.json $out/flight_$2.json 2>/dev/null
    python3 benchmark/scratch/span_report.py .bench_out/$3 --family deepseek_v3_hc > $out/span_report_$2.txt 2>&1
    tail -40 $out/span_report_$2.txt | cut -c1-400
    python3 benchmark/scratch/scope_ops.py .bench_out/$3 --family deepseek_v3_hc --top 10 > $out/scope_ops_$2.txt 2>&1
  fi
  cd $root
}
for what in ${whats//,/ }; do
  case $what in
    mix) ( cd $(dir $side) && timeout 600 python3 benchmark/scratch/mhc_chip.py > $out/mhc_chip.json 2> $out/mhc_chip.err ) \
           || tail -8 $out/mhc_chip.err | cut -c1-600
         cat $out/mhc_chip.json ;;
    cell) run $side traced $cell $seed 1 ;;
    parent) run parent parent_on_new_cell $cell $seed 0 ;;
    sets) ( cd $(dir $side) && bash benchmark/scratch/chip_sets.sh $cell $out/sets 50 ) ;;
    held) ( cd $(dir $side) && timeout 1500 python3 benchmark/scratch/held_rows.py --cell $cell --train-steps 80 > $out/held_rows.json 2> $out/held_rows.err ) \
            || tail -8 $out/held_rows.err | cut -c1-600
          cat $out/held_rows.json ;;
    oldtraced) run parent old_cell_traced_on_parent kanana2_train_s8192 $seed 1 ;;
    seeds) for k in 1 2 3 4 5 6; do run $side seeds $cell $((seed + 11 * k)) 0; done
           python3 - <<PY
import json, statistics
v = [r["line"]["metrics"]["train_tokens_per_s"]["value"] for r in map(json.loads, open("$out/runs.jsonl"))
     if r["tag"] == "seeds" and r["line"]]
q = statistics.quantiles(v, n=4)
print("seeds: n", len(v), "median", statistics.median(v), "iqr_share", (q[2] - q[0]) / statistics.median(v), v)
PY
           ;;
    others) for c in kanana2_train_s8192 gpt2m_train_s1024 granite4h_train_s4096 phi4flash_train_s8192; do
              run $side others $c $((seed + 6)) 0; done ;;
  esac
done
