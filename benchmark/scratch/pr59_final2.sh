# PR 59, the last call after the driver's refusal (one chip): the committed files alone
# (chip_check/final2 = git archive $(git write-tree)), the routers' balancing term on:
# two sets of six runs of the new cell, every run a seed of its own, then one traced run; the spreads as the
# driver reads them are printed by scripts/cell_runs_report.py a set.
bash scripts/cell_runs.sh pr59_final2_set1 keyevl2_train_s16384 keye_vl2 chip_check/final2:6101:0 chip_check/final2:2147496101:0 chip_check/final2:3000006103:0 chip_check/final2:6202:0 chip_check/final2:2147496203:0 chip_check/final2:3000006205:0
bash scripts/cell_runs.sh pr59_final2_set2 keyevl2_train_s16384 keye_vl2 chip_check/final2:6303:0 chip_check/final2:2147496305:0 chip_check/final2:3000006307:0 chip_check/final2:6404:0 chip_check/final2:2147496407:0 chip_check/final2:4294967011:0
bash scripts/cell_runs.sh pr59_final2_traced keyevl2_train_s16384 keye_vl2 chip_check/final2:2147496509:1
python3 - <<PY
import json
for label in ("set1", "set2", "traced"):
    for l in open("chiprun_out/pr59_final2_%s/runs.jsonl" % label):
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
head -130 chiprun_out/pr59_final2_traced/final2.keyevl2_train_s16384.2147496509.scope_ops.txt | cut -c1-230
