# PR 59, calls 5 and 6 (one chip): ONE run of the new cell from the committed files as they stood (chip_check/final = git
# archive $(git write-tree)) after each of the last two edits to code that followed the sets: held_expert_layer's early check
# of `expert` (call 5, seed 2147490611) and KeyeVL2Config.index_rope_dim as a property (call 6, seed 2147490712). SEED=<n>.
bash scripts/cell_runs.sh pr59_call${CALL:-5} keyevl2_train_s16384 keye_vl2 chip_check/final:${SEED:-2147490611}:0
