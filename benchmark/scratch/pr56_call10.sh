# PR 56, call 10 (after the review): the cell at the issue's lr 3e-4 from the committed files alone, chip_check/final = git
# archive $(git write-tree); chip_check/parent = git archive a549046 with this PR's BENCHMARK.json and benchmark/ laid over it.
# The parent on the new cell (must fail at once); then from chip_check/final a run that may compile, two sets of six and a
# traced run, every run a seed no earlier call used.
new=nemotron3super_train_s8192; root=$(pwd); out=$root/chiprun_out/pr56r; mkdir -p $out
t0=$(date +%s)
(cd chip_check/parent && timeout 600 python3 benchmark/run.py --workload $new --seed 3570000002 --seconds 50 --trace 0 > $out/parent_new.out 2> $out/parent_new.err; echo "parent on the new cell: rc=$? after $(( $(date +%s) - t0 )) s"; grep -v -e '^W0' -e '^I0' -e hugepages $out/parent_new.err | tail -2 | cut -c1-300)
cd chip_check/final
bash benchmark/scratch/pr56_sets.sh $new $out 50 c
bash benchmark/scratch/pr56_sets.sh $new $out 50 d
cp .bench_out/$new/train/flight.json $out/final_traced.flight.json 2>/dev/null
