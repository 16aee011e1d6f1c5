#!/bin/bash
# PR 43 call 4: scratch/chip_sets.sh on the new cell (a run that may compile, two sets of the same six
# seeds, one traced run, the spreads as the driver reads them)
cd /root/repo
bash benchmark/scratch/chip_sets.sh phi4flash_train_s8192 /root/repo/chiprun_out/pr43/sets 50
cp .bench_out/phi4flash_train_s8192/train/flight.json chiprun_out/pr43/c4_flight.json 2>/dev/null
python3 benchmark/scratch/span_report.py .bench_out/phi4flash_train_s8192 --family phi4flash > chiprun_out/pr43/c4_span_report.txt 2>&1 || true
