# PR 56, the last call: chip_check/final = git archive $(git write-tree) (the committed files alone), chip_check/parent =
# git archive a549046 with this PR's BENCHMARK.json and benchmark/ laid over it (what the driver does for a new cell).
# The parent on the new cell (must fail at once); one old cell traced on the parent under this PR's benchmark files; the
# new cell traced from the committed files; then untraced pairs parent / final on shared seeds in the four cells that call
# ops/expert_layer.py (the code this PR changed).
new=nemotron3super_train_s8192
t0=$(date +%s)
(cd chip_check/parent && timeout 600 python3 benchmark/run.py --workload $new --seed 3560000001 --seconds 50 --trace 0 > ../parent_new.out 2> ../parent_new.err; echo "parent on the new cell: rc=$? after $(( $(date +%s) - t0 )) s"; grep -v -e '^W0' -e '^I0' -e hugepages ../parent_new.err | tail -2 | cut -c1-300)
bash benchmark/scratch/pr56_cell.sh chip_check/parent parent_traced kanana2_train_s8192 3560000002 1
bash benchmark/scratch/pr56_cell.sh chip_check/final final_traced $new 3560000003 1
seed=3560000100
for c in kanana2_train_s8192 qwen3next_train_s8192 kimilinear_train_s8192 xing4_train_s4096; do
  seed=$((seed + 1))
  bash benchmark/scratch/pr56_cell.sh chip_check/parent parent_pairs $c $seed 0 | head -2
  bash benchmark/scratch/pr56_cell.sh chip_check/final final_pairs $c $seed 0 | head -2
done
