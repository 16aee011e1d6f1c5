# PR 50, call 6: the committed files alone (chip_check/final = git archive $(git write-tree)) against the parent
# (chip_check/parent = git archive f12c9bc) in kimilinear_train_s8192: the kernel pair alone, a traced run of each
# side on one seed, three untraced pairs parent / change / change / parent on seeds of their own.
(cd chip_check/final && python3 benchmark/scratch/kda_kernel_chip.py --ops 4 2>&1 | grep '^{' | cut -c1-700)
c=kimilinear_train_s8192
bash benchmark/scratch/pr50_cell.sh chip_check/final final $c 3500000021 1
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent $c 3500000022 0
bash benchmark/scratch/pr50_cell.sh chip_check/final final $c 3500000022 0
bash benchmark/scratch/pr50_cell.sh chip_check/final final $c 3500000023 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent $c 3500000023 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent $c 2147489999 0
bash benchmark/scratch/pr50_cell.sh chip_check/final final $c 2147489999 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent $c 3500000021 1
python3 -m ray_tpu.cli postmortem chiprun_out/pr50/final.flight.json --tail 400 2>/dev/null | grep -c "rtpu.ops.kda.path"
python3 - <<'PY'
import json
d = json.load(open("chiprun_out/pr50/final.flight.json"))
def walk(x):
    if isinstance(x, dict):
        if x.get("kind") == "rtpu.ops.kda.path": print("flight:", json.dumps(x)[:400])
        for v in x.values(): walk(v)
    elif isinstance(x, list):
        for v in x: walk(v)
walk(d)
PY
