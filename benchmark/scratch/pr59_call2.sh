# PR 59, call 2 (one chip): (a) the parent commit with this PR's BENCHMARK.json and benchmark/ laid
# over it (chip_check/parent_bench) tries the new cell three times: it has no such family and should
# fail at once, rc != 0; (b) an old cell traced from that overlay (the readers this PR adds return
# nothing there); (c) the gather route's backward with a block's rows made again, and select against
# the DEVICE's lax.top_k on ties; (d) six seeds of the new cell from the working tree.
out=$(pwd)/chiprun_out/pr59_call2; mkdir -p $out
cd chip_check/parent_bench
for seed in 2147483801 2147483802 2147483803; do
  t0=$(date +%s)
  timeout 600 python3 benchmark/run.py --workload keyevl2_train_s16384 --seed $seed --seconds 50 --trace 0 > $out/parent.out 2> $out/parent.err; rc=$?
  echo "parent try seed $seed rc $rc took_s $(( $(date +%s) - t0 )) last: $(grep -v -e '^W0' -e '^I0' $out/parent.err | tail -n 2 | cut -c1-300)" | tee -a $out/parent_tries.txt
done
timeout 900 python3 benchmark/run.py --workload gpt2m_train_s1024 --seed 2147483811 --seconds 50 --trace 1 > $out/parent_old_traced.out 2> $out/parent_old_traced.err; echo "old cell traced on the overlay rc $?"
tail -n 1 $out/parent_old_traced.out | cut -c1-1500
cd ../..
python3 scripts/sparse_routes_chip.py --skip dense,blocks > $out/routes.jsonl 2> $out/routes.err; echo "routes rc $?"
cat $out/routes.jsonl
grep -v -e '^W0' -e '^I0' $out/routes.err | tail -4 | cut -c1-400
bash scripts/cell_runs.sh pr59_call2 keyevl2_train_s16384 keye_vl2 .:101:0 .:202:0 .:303:0 .:2147483749:0 .:2147484949:0 .:2147489999:0
python3 - <<PY
import json, statistics
rows=[json.loads(l) for l in open("$out/runs.jsonl")]
v=[r["line"]["metrics"]["train_tokens_per_s"]["value"] for r in rows if r["line"]]
q=statistics.quantiles(v,n=4)
print("tokens/s", v, "median", statistics.median(v), "iqr share", (q[2]-q[0])/statistics.median(v))
print("setup_s", [r["line"]["metrics"]["setup_s"]["value"] for r in rows if r["line"]])
print("correct", [r["line"] and r["line"]["correct"] for r in rows])
for r in rows:
    print(r["seed"], r["held"], json.dumps(r["line"] and r["line"].get("compared"))[:600])
PY
