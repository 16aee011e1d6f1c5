#!/bin/bash
# PR 43 call 6: what call 5's time limit cut: kanana2_train_s8192's second pair (change, parent on one seed)
root=/root/repo; out=$root/chiprun_out/pr43/final; mkdir -p $out
for side in final parent; do
  cd $root/chip_check/$side
  timeout 1200 python3 benchmark/run.py --workload kanana2_train_s8192 --seed 3100000013 --seconds 50 --trace 0 > $out/last.out 2> $out/last.err; rc=$?
  echo "{\"side\": \"$side\", \"tag\": \"call6\", \"cell\": \"kanana2_train_s8192\", \"seed\": 3100000013, \"seconds\": 50, \"trace\": 0, \"rc\": $rc, \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" | tee -a $out/runs6.jsonl | cut -c1-600
done
