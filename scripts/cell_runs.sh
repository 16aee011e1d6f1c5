# usage: bash scripts/cell_runs.sh <label> <cell> <family> <tree>:<seed>:<trace> [...]      (PR 57)
# Runs of one benchmark cell from several trees in the order given, as the driver runs them
# (`python3 benchmark/run.py --workload <cell> --seed <n> --seconds 50 --trace <0|1>` from the
# tree's root), for a comparison of two commits on one chip: parent, change, change, parent.
# Every result line goes to chiprun_out/<label>/runs.jsonl with its tree, seed and exit code;
# a traced run goes through benchmark/scratch/final_report.py (run.py, with the loop's final
# report kept: every step's loss, the held rows) and also leaves the largest operations of every
# scope (benchmark/scratch/scope_ops.py). Then scripts/cell_runs_report.py prints the pairs.
# RUN_SECONDS=5 RUN_EXTRA=--rehearse-cpu walks it here.
label=$1; cell=$2; family=$3; shift 3
root=$(pwd); out=$root/chiprun_out/$label; mkdir -p $out
for run in "$@"; do
  tree=${run%%:*}; rest=${run#*:}; seed=${rest%%:*}; trace=${rest#*:}
  name=$(basename $tree)
  cd $root/$tree || exit 1
  t0=$(date +%s)
  entry="benchmark/run.py"
  [ $trace = 1 ] && entry="benchmark/scratch/final_report.py $out/$name.$cell.$seed.report.json"
  timeout 1200 python3 $entry --workload $cell --seed $seed --seconds ${RUN_SECONDS:-50} --trace $trace $RUN_EXTRA > $out/last.out 2> $out/last.err; rc=$?
  line=$(tail -n 1 $out/last.out | grep '^{' || echo null)
  held=$(grep -h -o "held rows {[^}]*}" $out/last.err $out/last.out | tail -n 1)
  echo "{\"tree\": \"$name\", \"cell\": \"$cell\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"took_s\": $(( $(date +%s) - t0 )), \"held\": \"$held\", \"line\": $line}" >> $out/runs.jsonl
  if [ $rc -ne 0 ]; then grep -v -e '^W0' -e '^I0' -e hugepages -e warnings.warn $out/last.err | tail -15 | cut -c1-500; fi
  if [ $trace = 1 ]; then
    gzip -c .bench_out/$cell/train/flight.json > $out/$name.$cell.$seed.flight.json.gz 2>/dev/null
    python3 benchmark/scratch/scope_ops.py .bench_out/$cell --family $family --top 40 > $out/$name.$cell.$seed.scope_ops.txt 2>&1
  fi
  cd $root
done
python3 scripts/cell_runs_report.py $out/runs.jsonl
