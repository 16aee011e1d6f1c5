# PR 50, call 7: the five cells that do not run the changed code, one untraced run each from the committed files.
for c in kanana2_train_s8192 xing4_train_s4096 granite4h_train_s4096 phi4flash_train_s8192 gpt2m_train_s1024; do
  bash benchmark/scratch/pr50_cell.sh chip_check/final final_others $c 3500000090 0
done
# and one more traced run of the claimed cell, with its largest operations under every scope
bash benchmark/scratch/pr50_cell.sh chip_check/final final_traced kimilinear_train_s8192 3500000031 1
(cd chip_check/final && python3 benchmark/scratch/scope_ops.py .bench_out/kimilinear_train_s8192 --family kimi_linear --top 8 2>&1 | cut -c1-260 | head -120)
