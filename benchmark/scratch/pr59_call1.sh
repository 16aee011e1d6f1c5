# PR 59, call 1 (one chip): ONE layer's sparse attention a part at a time at the cell's shape
# (both routes, the selection against lax.top_k), then the new cell traced from the working tree.
out=chiprun_out/pr59_call1; mkdir -p $out
python3 scripts/sparse_routes_chip.py > $out/routes.jsonl 2> $out/routes.err; echo "routes rc $?"
cat $out/routes.jsonl
grep -v -e '^W0' -e '^I0' $out/routes.err | tail -5 | cut -c1-400
bash scripts/cell_runs.sh pr59_call1 keyevl2_train_s16384 keye_vl2 .:2147489911:1
tail -n 1 chiprun_out/pr59_call1/runs.jsonl | cut -c1-6000
head -60 chiprun_out/pr59_call1/..keyevl2_train_s16384.2147489911.scope_ops.txt 2>/dev/null
ls chiprun_out/pr59_call1
