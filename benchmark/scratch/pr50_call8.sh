# PR 50, call 8: the FINAL form (two heads' share of a program a pure function under jax.jit, no staged planes, no loop)
# from the committed files alone (chip_check/final = git archive $(git write-tree)) against the parent (git archive
# f12c9bc) in kimilinear_train_s8192: the kernel pair alone, a traced run of the change, three untraced pairs on shared
# seeds (parent, change, change, parent, parent, change), the flight record's route events, the scope's operations.
(cd chip_check/final && python3 benchmark/scratch/kda_kernel_chip.py --heads 4,8 --ops 4 2>&1 | grep '^{' | cut -c1-700)
c=kimilinear_train_s8192
bash benchmark/scratch/pr50_cell.sh chip_check/final final2 $c 3500000041 1
(cd chip_check/final && python3 benchmark/scratch/scope_ops.py .bench_out/$c --family kimi_linear --top 8 2>&1 | grep -A8 "^scan\|^mixer\|^(unscoped)" | cut -c1-230)
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent2 $c 3500000042 0
bash benchmark/scratch/pr50_cell.sh chip_check/final final2 $c 3500000042 0
bash benchmark/scratch/pr50_cell.sh chip_check/final final2 $c 3500000043 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent2 $c 3500000043 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent2 $c 2147489999 0
bash benchmark/scratch/pr50_cell.sh chip_check/final final2 $c 2147489999 0
python3 - <<'PY'
import json
d = json.load(open("chiprun_out/pr50/final2.flight.json"))
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
