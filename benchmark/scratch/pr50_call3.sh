# PR 50, call 3: the kernel pair alone at 4 and 8 heads a program, then the cell: this tree (a KDA layer keeps
# its input alone) traced and untraced, then chip_check/keep_all (the same tree, `_REMAT_SAVE["kda"]` =
# ("kda_out", "kda_states")) untraced and traced.
python3 benchmark/scratch/kda_kernel_chip.py --heads 4,8 2>&1 | grep '^{' | cut -c1-700
bash benchmark/scratch/pr50_cell.sh . keep_none kimilinear_train_s8192 3500000001 1
bash benchmark/scratch/pr50_cell.sh . keep_none kimilinear_train_s8192 3500000002 0
bash benchmark/scratch/pr50_cell.sh chip_check/keep_all keep_all kimilinear_train_s8192 3500000002 0
bash benchmark/scratch/pr50_cell.sh chip_check/keep_all keep_all kimilinear_train_s8192 3500000001 1
