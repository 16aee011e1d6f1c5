# PR 52, call 4, the committed files alone (chip_check/final = git archive $(git write-tree)) beside the parent
# (chip_check/parent = git archive 3f5d494 + this PR's BENCHMARK.json and benchmark/ laid over it): the new cell
# traced from the final tree with its scopes' largest operations; the parent under this PR's benchmark files on
# kimi's cell TRACED (the new readers must find nothing there and raise nothing); then the six cells that are
# there, one untraced run each on the parent and on the final tree, a seed a cell shared by both sides, kimi's and
# kanana's first.
c=qwen3next_train_s8192
bash benchmark/scratch/pr52_cell.sh chip_check/final final_traced $c 3520000500 1
(cd chip_check/final && python3 benchmark/scratch/scope_ops.py .bench_out/$c --family qwen3_next --top 4 2>&1 | cut -c1-200 | head -70)
bash benchmark/scratch/pr52_cell.sh chip_check/parent parent_traced kimilinear_train_s8192 3520000499 1 | cut -c1-1500
n=3520000400
for c in kimilinear_train_s8192 kanana2_train_s8192 xing4_train_s4096 granite4h_train_s4096 phi4flash_train_s8192 gpt2m_train_s1024; do
  n=$((n + 1))
  bash benchmark/scratch/pr52_cell.sh chip_check/parent parent $c $n 0 | head -2
  bash benchmark/scratch/pr52_cell.sh chip_check/final final $c $n 0 | head -2
done
