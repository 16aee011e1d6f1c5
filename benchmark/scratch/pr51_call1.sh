# PR 51, call 1 (the working tree, before /simplify): one KDA layer alone at the cell's shape, the fused entry beside
# the parent's call (l2norm + softplus + kda_scan) and the plain definition; then the cell: the change traced, its
# scope's operations and its flight record's route events, and one untraced pair parent / change on a shared seed.
python3 benchmark/scratch/kda_gated_chip.py --ops 8 2>&1 | grep '^{' | cut -c1-1200
c=kimilinear_train_s8192
bash benchmark/scratch/pr51_cell.sh . change $c 3510000001 1
python3 benchmark/scratch/scope_ops.py .bench_out/$c --family kimi_linear --top 10 2>&1 | grep -A10 "^scan\|^mixer\|^conv" | cut -c1-230
bash benchmark/scratch/pr51_cell.sh chip_check/parent parent $c 3510000002 0
bash benchmark/scratch/pr51_cell.sh . change $c 3510000002 0
python3 - <<'PY'
import json
d = json.load(open("chiprun_out/pr51/change.flight.json"))
seen = []
def walk(x):
    if isinstance(x, dict):
        if x.get("kind") == "rtpu.ops.kda.path": seen.append(json.dumps(x["data"], sort_keys=True))
        for v in x.values(): walk(v)
    elif isinstance(x, list):
        for v in x: walk(v)
walk(d)
print("flight: rtpu.ops.kda.path x", len(seen), set(seen))
PY
