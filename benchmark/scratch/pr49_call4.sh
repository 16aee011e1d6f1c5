# NOTE (kept as run): the export of JAX_COMPILATION_CACHE_DIR below pointed at a directory that did not exist, so every run of this
# call compiled afresh (setup_s 141 to 157 s); pr49_call9.sh leaves the machine's own cache alone.
# PR 49, call 4: the scan alone (one chunk a turn), the new cell traced on this
# tree, and the parent commit under this PR's benchmark files (chip_check/parent:
# git archive of the parent + BENCHMARK.json + benchmark/): the new cell must
# fail at once there, an old cell must run traced.
root=$(pwd); out=$root/chiprun_out/pr49; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$root/.jax_cache
python3 benchmark/scratch/kda_chip.py --ops 30 2>&1 | grep '^{' > $out/kda_chip_call4.txt; head -c 1500 $out/kda_chip_call4.txt; echo
python3 benchmark/run.py --workload kimilinear_train_s8192 --seed 3490000002 --seconds 50 --trace 1 > $out/c4_new.out 2> $out/c4_new.err; echo "new cell, this tree: rc=$?"
tail -n 1 $out/c4_new.out | cut -c1-5000
python3 benchmark/scratch/scope_ops.py .bench_out/kimilinear_train_s8192 --family kimi_linear --top 25 > $out/c4_scope_ops.txt 2>&1
cp .bench_out/kimilinear_train_s8192/train/flight.json $out/c4_flight.json
cd $root/chip_check/parent
t0=$(date +%s)
timeout 600 python3 benchmark/run.py --workload kimilinear_train_s8192 --seed 3490000003 --seconds 50 --trace 0 > $out/c4_parent_new.out 2> $out/c4_parent_new.err; echo "new cell, parent + this PR's benchmark files: rc=$? after $(( $(date +%s) - t0 )) s"
grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/c4_parent_new.err | tail -6 | cut -c1-400
python3 benchmark/run.py --workload kanana2_train_s8192 --seed 3490000004 --seconds 50 --trace 1 > $out/c4_parent_kanana.out 2> $out/c4_parent_kanana.err; echo "kanana2 traced, parent + this PR's benchmark files: rc=$?"
tail -n 1 $out/c4_parent_kanana.out | cut -c1-2500
