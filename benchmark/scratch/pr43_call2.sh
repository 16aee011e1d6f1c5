#!/bin/bash
# PR 43 call 2: the new cell traced on the change; on the parent under this PR's benchmark files the
# new cell (must fail cleanly) and one old cell traced
set -x
cd /root/repo
mkdir -p chiprun_out/pr43
python3 benchmark/run.py --workload phi4flash_train_s8192 --seed 2147483659 --seconds 50 --trace 1 > chiprun_out/pr43/c2_new_traced.json 2> chiprun_out/pr43/c2_new_traced.err; echo "rc new traced $?"
tail -c 3000 chiprun_out/pr43/c2_new_traced.err
cp -r .bench_out/phi4flash_train_s8192/train/flight.json chiprun_out/pr43/c2_flight.json 2>/dev/null
python3 benchmark/scratch/span_report.py .bench_out/phi4flash_train_s8192 --family phi4flash > chiprun_out/pr43/c2_span_report.txt 2>&1 || true
cd chip_check/parent_new
( time python3 benchmark/run.py --workload phi4flash_train_s8192 --seed 2147483659 --seconds 50 --trace 0 ) > /root/repo/chiprun_out/pr43/c2_parent_new.out 2>&1; echo "rc parent new cell $?"
tail -n 12 /root/repo/chiprun_out/pr43/c2_parent_new.out | cut -c1-400
python3 benchmark/run.py --workload granite4h_train_s4096 --seed 2147483660 --seconds 50 --trace 1 > /root/repo/chiprun_out/pr43/c2_parent_granite_traced.json 2> /root/repo/chiprun_out/pr43/c2_parent_granite_traced.err; echo "rc parent granite traced $?"
cd /root/repo
python3 - <<'PY'
import json
for f in ("c2_new_traced", "c2_parent_granite_traced"):
    try:
        line = json.loads(open(f"chiprun_out/pr43/{f}.json").read().strip().splitlines()[-1])
    except Exception as e:
        print(f, "no line", e); continue
    print(f, "correct", line["correct"], "attempted", line["attempted"])
    print({k: v["value"] for k, v in line["metrics"].items()})
    print({k: v["value"] for k, v in line.get("end_to_end_in_traced_run", {}).items()})
    print(line["device"]); print(line["compared"])
PY
