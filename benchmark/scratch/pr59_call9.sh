# PR 59, call 9 (one chip): the PARENT's program under this PR's benchmark files (chip_check/parent_bench = git archive d5c868f
# with BENCHMARK.json and benchmark/ of chip_check/final2 laid over it, as the driver lays them): the new cell once (it has to fail at
# once: the parent has no such family), then one old cell traced (it has to give a whole line: nothing this PR adds may stand in its way).
cd chip_check/parent_bench
t0=$(date +%s); timeout 300 python3 benchmark/run.py --workload keyevl2_train_s16384 --seed 2147497001 --seconds 50 --trace 0 > /tmp/new.out 2> /tmp/new.err; rc=$?
echo "parent, new cell: rc $rc after $(( $(date +%s) - t0 )) s"; grep -v -e '^W0' -e '^I0' -e hugepages -e warnings.warn /tmp/new.err | tail -4 | cut -c1-300
t0=$(date +%s); timeout 900 python3 benchmark/run.py --workload kanana2_train_s8192 --seed 2147497103 --seconds 50 --trace 1 > /tmp/old.out 2> /tmp/old.err; rc=$?
echo "parent, kanana2_train_s8192 traced: rc $rc after $(( $(date +%s) - t0 )) s"; tail -n 1 /tmp/old.out | cut -c1-3000
