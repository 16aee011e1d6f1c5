# PR 50, call 4: the kernel pair alone (8 heads a program, two at a time in a loop), then the cell from this tree
# (traced, may compile; then untraced) and from chip_check/parent (git archive f12c9bc), seeds shared by the two sides.
python3 benchmark/scratch/kda_kernel_chip.py --heads 8,4 --ops 4 2>&1 | grep '^{' | cut -c1-700
bash benchmark/scratch/pr50_cell.sh . change kimilinear_train_s8192 3500000011 1
bash benchmark/scratch/pr50_cell.sh . change kimilinear_train_s8192 3500000012 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent kimilinear_train_s8192 3500000012 0
bash benchmark/scratch/pr50_cell.sh chip_check/parent parent kimilinear_train_s8192 3500000013 0
bash benchmark/scratch/pr50_cell.sh . change kimilinear_train_s8192 3500000013 0
