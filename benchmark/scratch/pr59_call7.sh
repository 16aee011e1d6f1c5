# PR 59, call 7 (one chip): the working tree with the routers' balancing term on (router_aux_coef 0.001):
# one set of six runs of the new cell, every run a seed of its own; the spread as the driver reads it.
bash scripts/cell_runs.sh pr59_call7 keyevl2_train_s16384 keye_vl2 .:5101:0 .:2147495101:0 .:3000005103:0 .:5202:0 .:2147495203:0 .:3000005205:0
python3 - <<PY
import json
for l in open("chiprun_out/pr59_call7/runs.jsonl"):
    r = json.loads(l); line = r["line"] or {}
    c = line.get("compared") or {}
    print(r["seed"], "rc", r["rc"], "correct", line.get("correct"), "took", r["took_s"],
          {k: v["value"] for k, v in (line.get("metrics") or {}).items() if k in ("train_tokens_per_s", "setup_s")},
          r["held"], "first", c.get("first_step.loss_abs_diff"), "after", c.get("after_window.loss_abs_diff"),
          json.dumps(line.get("why_not"))[:300])
PY
