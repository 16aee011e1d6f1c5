# PR 56, call 1 (the working tree, before /simplify): the parent under this PR's benchmark files on the new cell (must fail
# at once); the new cell traced once; its scopes' largest operations and the route events of its flight record.
c=nemotron3super_train_s8192
t0=$(date +%s)
(cd chip_check/parent && timeout 600 python3 benchmark/run.py --workload $c --seed 1 --seconds 50 --trace 0 > ../parent_new.out 2> ../parent_new.err; echo "parent on the new cell: rc=$? after $(( $(date +%s) - t0 )) s"; grep -v -e '^W0' -e '^I0' -e hugepages ../parent_new.err | tail -4 | cut -c1-300)
bash benchmark/scratch/pr56_cell.sh . change $c 3520000001 1
python3 benchmark/scratch/scope_ops.py .bench_out/$c --family nemotron_h --top 6 2>&1 | cut -c1-200 | head -110
python3 - <<'PY'
import json
d = json.load(open("chiprun_out/pr56/change.flight.json"))
seen = {}
def walk(x):
    if isinstance(x, dict):
        if x.get("kind") in ("rtpu.ops.ssd.path", "rtpu.ops.expert_layer", "rtpu.models.stack.runs", "rtpu.ops.flash_attention.path", "rtpu.models.nemotron_h.share", "rtpu.ops.conv"):
            seen.setdefault(x["kind"], set()).add(json.dumps(x.get("data"), sort_keys=True))
        for v in x.values(): walk(v)
    elif isinstance(x, list):
        for v in x: walk(v)
walk(d)
for k, v in seen.items(): print("flight:", k, v)
PY
