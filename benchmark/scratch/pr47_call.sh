#!/bin/bash
# PR 47's chip calls: bash benchmark/scratch/pr47_call.sh <side> <tag> <what>[,<what>...] [seed]
#   <side>: the CHANGE's tree: a directory under chip_check/ (chip_check/final = git archive $(git write-tree)) or
#           "tree" for the copy's own root (the working tree as it stood on disk); the PARENT is always
#           chip_check/parent (git archive d8fbf4e, the parent commit);
#   <what> = kernels:       benchmark/scratch/mhc_kernel_chip.py from <side>'s root (one sublayer's kernel pair alone at the
#                           cell's shape: forward, forward + backward, each kernel, the plain form, the differences);
#   <what> = pair:<cell>:   the cell untraced on parent, change, change, parent (seeds s, s, s+1, s+1);
#   <what> = traced:<cell>: the cell traced on the parent, then on the change, one seed, with span_report.py and
#                           scope_ops.py on each xplane and the step's trace + lower seconds from the flight record;
#   <what> = seeds:<cell>:  the cell six times on the change, each run with a seed of its own (seed+11, +22, ...),
#                           and the spread as the driver reads it;
#   <what> = once:<cell>:   the cell once on the change, untraced.
# several <what> may be given joined by commas; they run in that order in ONE call.
root=/root/repo; side=$1; tag=$2; whats=$3; seed=${4:-3400000001}
out=$root/chiprun_out/pr47/$tag; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$root/.jax_cache}
echo "compile cache: $JAX_COMPILATION_CACHE_DIR"
dir() { if [ "$1" = tree ]; then echo $root; else echo $root/chip_check/$1; fi; }
family() { case $1 in xing4*) echo deepseek_v3_hc ;; kanana2*) echo deepseek_v3 ;; gpt2m*) echo gpt ;; esac; }
run() {  # <side> <tag> <cell> <seed> <trace>
  cd $(dir $1)
  t0=$(date +%s)
  timeout 1500 python3 benchmark/run.py --workload $3 --seed $4 --seconds 50 --trace $5 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"side\": \"$1\", \"tag\": \"$2\", \"cell\": \"$3\", \"seed\": $4, \"trace\": $5, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 )), \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" | tee -a $out/runs.jsonl | cut -c1-2500
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-600; fi
  python3 - $3 <<'PY'
import json, sys
try:
    rec = json.load(open(f".bench_out/{sys.argv[1]}/train/flight.json"))
except Exception as e:
    print("no flight record:", e); sys.exit(0)
rings = rec.get("rings", rec)
evs = [e for r in (rings.values() if isinstance(rings, dict) else rings)
       for e in (r.get("events", r) if isinstance(r, dict) else r)]
step = [e for e in evs if isinstance(e, dict) and str(e.get("kind", "")).startswith("rtpu.jax.")
        and "bench_train_step" in str(e.get("label", ""))]
print("step build:", {e["kind"].rsplit(".", 1)[1]: round(e.get("dur", 0), 3) for e in step})
paths = [e["data"] for e in evs if isinstance(e, dict) and e.get("kind") == "rtpu.ops.hyper_connection"]
print("hyper_connection events:", json.dumps(paths)[:900])
PY
  if [ $5 = 1 ]; then
    python3 benchmark/scratch/span_report.py .bench_out/$3 --family $(family $3) > $out/span_report_$1_$3.txt 2>&1
    python3 benchmark/scratch/scope_ops.py .bench_out/$3 --family $(family $3) --top 8 > $out/scope_ops_$1_$3.txt 2>&1
    grep "ms a step" $out/scope_ops_$1_$3.txt | cut -c1-200
    grep -A8 "^mhc" $out/scope_ops_$1_$3.txt | cut -c1-220
  fi
  cd $root
}
spread() {  # <tag>
  python3 - <<PY
import json, statistics
v = [r["line"]["metrics"]["train_tokens_per_s"]["value"] for r in map(json.loads, open("$out/runs.jsonl"))
     if r["tag"] == "$1" and r["line"]]
q = statistics.quantiles(v, n=4)
print("$1: n", len(v), "median", statistics.median(v), "iqr_share", (q[2] - q[0]) / statistics.median(v), v)
PY
}
for what in ${whats//,/ }; do
  cell=${what#*:}
  case $what in
    kernels) ( cd $(dir $side) && timeout 1700 python3 benchmark/scratch/mhc_kernel_chip.py > $out/mhc_kernel_chip.json 2> $out/mhc_kernel_chip.err ) \
               || tail -8 $out/mhc_kernel_chip.err | cut -c1-600
             cat $out/mhc_kernel_chip.json | cut -c1-4000 ;;
    pair:*) run parent pair_$cell $cell $seed 0; run $side pair_$cell $cell $seed 0
            run $side pair_$cell $cell $((seed + 1)) 0; run parent pair_$cell $cell $((seed + 1)) 0 ;;
    traced:*) run parent traced_$cell $cell $seed 1; run $side traced_$cell $cell $seed 1 ;;
    seeds:*) for k in 1 2 3 4 5 6; do run $side seeds_$cell $cell $((seed + 11 * k)) 0; done; spread seeds_$cell ;;
    once:*) run $side once_$cell $cell $seed 0 ;;
    oncep:*) run parent oncep_$cell $cell $seed 0 ;;
  esac
done
