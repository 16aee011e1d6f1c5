#!/bin/bash
# PR 43 call 3: does keeping the gated MLP's two products in every run pay? chip_check/base is the
# tree of call 2 (nothing of the MLP kept), the working tree keeps them. base, change, change traced.
set -x
cd /root/repo
mkdir -p chiprun_out/pr43
run() {  # <tree> <tag> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload phi4flash_train_s8192 --seed $3 --seconds 50 --trace $4 > /root/repo/chiprun_out/pr43/c3_$2.json 2> /root/repo/chiprun_out/pr43/c3_$2.err; echo "rc $2 $?" )
}
run chip_check/base base 3000000011 0
run . keep 3000000011 0
run . keep_traced 3000000017 1
run chip_check/base base_again 3000000013 0
python3 - <<'PY'
import json
for f in ("base", "keep", "keep_traced", "base_again"):
    try:
        line = json.loads(open(f"chiprun_out/pr43/c3_{f}.json").read().strip().splitlines()[-1])
    except Exception as e:
        print(f, "no line", e); continue
    print(f, "correct", line["correct"], "attempted", line["attempted"], {k: v["value"] for k, v in line["metrics"].items()})
    print("  ", line["device"])
PY
