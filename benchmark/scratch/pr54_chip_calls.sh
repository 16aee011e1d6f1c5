#!/bin/bash
# The commands behind PR 54's figures (PERF.md sections 6 and 7), one call of
# the chip tool each: `chiprun --timeout <s> -- bash
# benchmark/scratch/pr54_chip_calls.sh <a|b|c|d|e|f>`. Every run's result
# line and flight record land under chiprun_out/pr54/<call>/ (pr54_runs.py).
# The watcher changed between the calls as PERF.md tells: a and b ran it
# with five counters at every sample and /proc/self/task at every sample; c
# and d with two counters at every sample, two every eighth, the tasks every
# eighth; e and f as committed (the fast counters every other sample).
set -u
call=$1
out=chiprun_out/pr54/$call
runs="python3 benchmark/scratch/pr54_runs.py $out"
gpt2m="$runs --cell gpt2m_train_s1024"
case $call in
a)  # what tpumonitoring answers inside the chip worker (every first call
    # under a watchdog that ends the process), then the watcher itself
    timeout 400 python3 benchmark/scratch/tpumon_probe.py chiprun_out/pr54/probe \
        --workload gpt2m_train_s1024 --seed 7001 --seconds 20 --trace 0 2>&1 \
        | cut -c1-1200 | tail -120
    timeout 500 $gpt2m --seeds 7002 --trace 1 --tag traced
    timeout 500 $gpt2m --seeds 7003,7004 --trace 0 --tag on ;;
b)  # a traced run, the same with the chip's half taken out, the parent's
    # (chip_check/parent = git archive of the parent commit with this PR's
    # benchmark files laid over it), three untraced runs
    timeout 400 $gpt2m --seeds 7101 --trace 1 --tag traced
    timeout 400 $gpt2m --seeds 7101 --trace 1 --tag traced_hostonly --worker-exec \
        "from ray_tpu.perf import chipwatch as c; c._watcher._source = None"
    timeout 400 $gpt2m --seeds 7101 --trace 1 --tag parent_traced --tree chip_check/parent
    timeout 900 $gpt2m --seeds 7102,7103,7104 --trace 0 --tag on ;;
c)  # a traced run; four untraced pairs on shared seeds, recorder off against
    # on, in the order off on on off (the watcher's cost end to end); twelve
    # untraced runs with their records kept
    timeout 400 $gpt2m --seeds 7200 --trace 1 --tag traced
    for pair in "7201 7202" "7203 7204"; do
        set -- $pair
        timeout 300 $gpt2m --seeds $1 --trace 0 --tag off --env RAY_TPU_FLIGHTREC=0
        timeout 300 $gpt2m --seeds $1 --trace 0 --tag on --env RAY_TPU_FLIGHTREC=1
        timeout 300 $gpt2m --seeds $2 --trace 0 --tag on --env RAY_TPU_FLIGHTREC=1
        timeout 300 $gpt2m --seeds $2 --trace 0 --tag off --env RAY_TPU_FLIGHTREC=0
    done
    timeout 1500 $gpt2m --trace 0 --tag catch \
        --seeds 7301,7302,7303,7304,7305,7306,7307,7308,7309,7310,7311,7312 ;;
d)  # one traced run of each of the other six cells
    seed=7400
    for cell in kanana2_train_s8192 granite4h_train_s4096 phi4flash_train_s8192 \
            xing4_train_s4096 kimilinear_train_s8192 qwen3next_train_s8192; do
        seed=$((seed + 1))
        timeout 1200 $runs --cell $cell --seeds $seed --trace 1 --tag $cell
    done ;;
e)  # ROADMAP A14, catch one: PR 26 saw the standstill only in runs whose
    # program had been compiled after its machine's first process (11 of 66
    # against 0 of 31), and calls a to c read theirs from the cache that
    # comes with the machine. Four series of seven untraced runs, each
    # series with a compile cache of its own that starts empty (its first
    # run compiles the step, the other six read THAT instance)
    seed=7500
    for k in 1 2 3 4; do
        seeds=""
        for i in 1 2 3 4 5 6 7; do seed=$((seed + 1)); seeds="$seeds,$seed"; done
        timeout 1100 $gpt2m --seeds ${seeds#,} --trace 0 --tag series$k \
            --env JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_cache_e$k
    done ;;
f)  # the committed files alone (chip_check/final = git archive of the final
    # tree) beside the parent: parent, change, change, parent on one seed,
    # untraced, then both traced
    for side in parent final final parent; do
        timeout 300 $gpt2m --seeds 7601 --trace 0 --tag $side --tree chip_check/$side
    done
    timeout 400 $gpt2m --seeds 7602 --trace 1 --tag final_traced --tree chip_check/final
    timeout 400 $gpt2m --seeds 7602 --trace 1 --tag parent_traced --tree chip_check/parent ;;
esac
