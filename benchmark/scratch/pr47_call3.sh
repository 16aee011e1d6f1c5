#!/bin/bash
# PR 47: the kernel pair alone, then cells through pr47_call.sh, in ONE call:
#   bash benchmark/scratch/pr47_call3.sh <tag> "<options of mhc_kernel_chip.py>" <side> <whats> [seed]
cd /root/repo
tag=$1; opts=$2; side=$3; whats=$4; seed=${5:-3470000001}
mkdir -p chiprun_out/pr47/$tag
python3 benchmark/scratch/mhc_kernel_chip.py $opts > chiprun_out/pr47/$tag/mhc_kernel_chip.json 2> chiprun_out/pr47/$tag/mhc_kernel_chip.err \
  || tail -5 chiprun_out/pr47/$tag/mhc_kernel_chip.err
cut -c1-6000 chiprun_out/pr47/$tag/mhc_kernel_chip.json
bash benchmark/scratch/pr47_call.sh $side $tag $whats $seed
