# NOTE (kept as run): the export of JAX_COMPILATION_CACHE_DIR below pointed at a directory that did not exist, so every run of this
# call compiled afresh (setup_s 141 to 157 s); pr49_call9.sh leaves the machine's own cache alone.
# PR 49, call 8: the new cell as the driver runs it: one run that may compile,
# two sets of six seeds, one traced run (chip_sets.sh), then the held rows
# while the cell trains.
root=$(pwd); out=$root/chiprun_out/pr49/sets; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache
bash benchmark/scratch/chip_sets.sh kimilinear_train_s8192 $out 50 2>&1 | cut -c1-6000
cp .bench_out/kimilinear_train_s8192/train/flight.json $out/flight_traced.json 2>/dev/null
python3 benchmark/scratch/scope_ops.py .bench_out/kimilinear_train_s8192 --family kimi_linear --top 12 > $out/scope_ops.txt 2>&1
python3 benchmark/scratch/held_rows_stack.py --cell kimilinear_train_s8192 --train-steps 80 > $out/held_rows.json 2> $out/held_rows.err; echo "held_rows rc=$?"
python3 -c "
import json; d=json.load(open('$out/held_rows.json')); print(json.dumps({k: d[k] for k in ('row_buffer','layer_alone','held_rows_by_seed','held_rows_while_training')}))" | cut -c1-3000
