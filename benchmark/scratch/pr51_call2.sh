# PR 51, call 2: the FINAL tree from the committed files alone (chip_check/final = git archive $(git write-tree), after
# /simplify) against the parent (chip_check/parent = git archive 7ecd0ed) in kimilinear_train_s8192: one KDA layer alone,
# a traced pair on one seed with the scope's operations, four untraced pairs on shared seeds in the order parent, change,
# change, parent, parent, change, change, parent, the flight record's route events.
(cd chip_check/final && python3 benchmark/scratch/kda_gated_chip.py --ops 4 2>&1 | grep '^{' | cut -c1-1200)
c=kimilinear_train_s8192
bash benchmark/scratch/pr51_cell.sh chip_check/final final $c 3510000021 1
(cd chip_check/final && python3 benchmark/scratch/scope_ops.py .bench_out/$c --family kimi_linear --top 10 2>&1 | grep -A10 "^scan\|^conv\|^(unscoped)" | cut -c1-230)
bash benchmark/scratch/pr51_cell.sh chip_check/parent parent2 $c 3510000021 1
(cd chip_check/parent && python3 benchmark/scratch/scope_ops.py .bench_out/$c --family kimi_linear --top 10 2>&1 | grep -A10 "^conv\|^(unscoped)" | cut -c1-230)
bash benchmark/scratch/pr51_cell.sh chip_check/parent parent2 $c 3510000022 0
bash benchmark/scratch/pr51_cell.sh chip_check/final final $c 3510000022 0
bash benchmark/scratch/pr51_cell.sh chip_check/final final $c 3510000023 0
bash benchmark/scratch/pr51_cell.sh chip_check/parent parent2 $c 3510000023 0
bash benchmark/scratch/pr51_cell.sh chip_check/parent parent2 $c 2147489999 0
bash benchmark/scratch/pr51_cell.sh chip_check/final final $c 2147489999 0
bash benchmark/scratch/pr51_cell.sh chip_check/final final $c 77 0
bash benchmark/scratch/pr51_cell.sh chip_check/parent parent2 $c 77 0
python3 - <<'PY'
import json
d = json.load(open("chiprun_out/pr51/final.flight.json"))
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
