# PR 59, call 3 (one chip): one parent / change pair for each of the five cells whose model calls
# ops/expert_layer.py (their optimised HLO is the parent's: scripts/train_step_hlo.py --compare).
# parent = chip_check/parent = git archive d5c868f; change = the working tree. Both sides share a seed.
bash scripts/cell_runs.sh pr59_call3 kanana2_train_s8192 deepseek_v3 chip_check/parent:2147485901:0 .:2147485901:0
bash scripts/cell_runs.sh pr59_call3 xing4_train_s4096 deepseek_v3_hc .:2147485902:0 chip_check/parent:2147485902:0
bash scripts/cell_runs.sh pr59_call3 kimilinear_train_s8192 kimi_linear chip_check/parent:2147485903:0 .:2147485903:0
bash scripts/cell_runs.sh pr59_call3 qwen3next_train_s8192 qwen3_next .:2147485904:0 chip_check/parent:2147485904:0
bash scripts/cell_runs.sh pr59_call3 nemotron3super_train_s8192 nemotron_h chip_check/parent:2147485905:0 .:2147485905:0
