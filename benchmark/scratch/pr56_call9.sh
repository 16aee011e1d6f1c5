# PR 56, call 9: qwen3next_train_s8192 again, parent / final / final / parent on two seeds (call 8's pair read 25986.1 /
# 25727.7 with 82 and 81 steps attempted: a step lost on one side of a program that compiles to the same optimised HLO).
c=qwen3next_train_s8192
bash benchmark/scratch/pr56_cell.sh chip_check/parent parent_pairs2 $c 3560000201 0 > /dev/null
bash benchmark/scratch/pr56_cell.sh chip_check/final final_pairs2 $c 3560000201 0 > /dev/null
bash benchmark/scratch/pr56_cell.sh chip_check/final final_pairs2 $c 3560000202 0 > /dev/null
bash benchmark/scratch/pr56_cell.sh chip_check/parent parent_pairs2 $c 3560000202 0 > /dev/null
python3 - <<'PY'
import json
for f in ("parent_pairs2", "final_pairs2"):
    for l in open(f"chiprun_out/pr56/{f}.jsonl"):
        r = json.loads(l)
        print(f, "correct", r["correct"], "attempted", r["attempted"], r["metrics"]["train_tokens_per_s"]["value"], r["metrics"]["setup_s"]["value"])
PY
