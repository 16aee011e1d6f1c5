# PR 56, calls 7 and 8 in one (no machine was free for them apart): set 2 and the traced run of the new cell from the
# working tree, then pr56_call8.sh (the committed files beside the parent).
bash benchmark/scratch/pr56_sets.sh nemotron3super_train_s8192 /root/repo/chiprun_out/pr56 50 b
bash benchmark/scratch/pr56_call8.sh
