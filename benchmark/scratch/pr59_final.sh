# PR 59, the last call (one chip): the committed files alone (chip_check/final = git archive $(git write-tree)):
# two sets of six runs of the new cell, every run a seed of its own, then one traced run; the spreads as the
# driver reads them are printed by scripts/cell_runs_report.py a set.
bash scripts/cell_runs.sh pr59_final_set1 keyevl2_train_s16384 keye_vl2 chip_check/final:1101:0 chip_check/final:2147490101:0 chip_check/final:3000001103:0 chip_check/final:1202:0 chip_check/final:2147490203:0 chip_check/final:3000001205:0
bash scripts/cell_runs.sh pr59_final_set2 keyevl2_train_s16384 keye_vl2 chip_check/final:1303:0 chip_check/final:2147490305:0 chip_check/final:3000001307:0 chip_check/final:1404:0 chip_check/final:2147490407:0 chip_check/final:4294967001:0
bash scripts/cell_runs.sh pr59_final_traced keyevl2_train_s16384 keye_vl2 chip_check/final:2147490509:1
python3 - <<PY
import json
for label in ("set1", "set2", "traced"):
    for l in open("chiprun_out/pr59_final_%s/runs.jsonl" % label):
        r = json.loads(l); line = r["line"] or {}
        c = line.get("compared") or {}
        print(label, r["seed"], "rc", r["rc"], "correct", line.get("correct"), "attempted", line.get("attempted"),
              {k: v["value"] for k, v in (line.get("metrics") or {}).items() if k in ("train_tokens_per_s", "setup_s")},
              r["held"], "first", c.get("first_step.loss_abs_diff"), "after", c.get("after_window.loss_abs_diff"),
              "peak", (line.get("device") or {}).get("memory_buffers_peak_bytes"), (line.get("device") or {}).get("memory_peak_bytes"))
        if r["trace"] == 1:
            print(json.dumps({k: v["value"] for k, v in line["metrics"].items()}))
            print(json.dumps(line["device"]))
PY
head -130 chiprun_out/pr59_final_traced/final.keyevl2_train_s16384.2147490509.scope_ops.txt | cut -c1-230
