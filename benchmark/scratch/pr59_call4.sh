# PR 59, call 4 (one chip): nemotron3super_train_s8192 again, change, parent, parent, change on two seeds: call 3's
# pair read 25 443.5 (change) against 25 869.8 (parent) with the same optimised HLO, seed and held rows.
bash scripts/cell_runs.sh pr59_call4 nemotron3super_train_s8192 nemotron_h .:2147485915:0 chip_check/parent:2147485915:0 chip_check/parent:2147485925:0 .:2147485925:0
