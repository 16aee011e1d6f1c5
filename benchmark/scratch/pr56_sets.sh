# usage: bash benchmark/scratch/pr56_sets.sh <cell> <outdir, absolute> <seconds> <part: a|b|c|d>   (PR 56)
# chip_sets.sh cut in two calls, so that neither nears the hour a call may last, and with a seed of its own for every
# run: part a is the run that may compile (set 0) and set 1's six seeds, part b set 2's six OTHER seeds and the traced
# run; the spreads of the end-to-end metrics are printed over whatever <outdir>/<cell>.<part>.jsonl holds by then, as the
# driver reads them (statistics.quantiles, n=4). Parts c and d are a and b again on fourteen further seeds (call 10).
cell=$1; out=$2; secs=$3; part=$4; mkdir -p $out
one() {  # <set> <seed> <trace>
  timeout 900 python3 benchmark/run.py --workload $cell --seed $2 --seconds $secs --trace $3 > $out/last.out 2> $out/last.err; rc=$?
  held=$(grep -h -o "held rows {[^}]*}" $out/last.err $out/last.out | tail -n 1)
  # seconds between the loop's reports (twenty steps apart), from the run's flight record
  held="$held since_s $(python3 -c "
import json
d = json.load(open('.bench_out/$cell/train/flight.json'))
print([round(e['data']['since_s'], 3) for ring in d['rings'].values() for e in ring
       if isinstance(e, dict) and e.get('kind') == 'rtpu.train.report'])" 2>&1 | tail -n 1 | tr -d '"')"
  echo "{\"set\": $1, \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"held\": \"$held\", \"line\": $(tail -n 1 $out/last.out | grep '^{' || echo null)}" >> $out/$cell.$part.jsonl
  if [ $rc -ne 0 ]; then grep -v "^W0\|^I0\|hugepages\|warnings.warn" $out/last.err | tail -15 | cut -c1-500; fi
}
if [ $part = a ]; then
  one 0 2147489999 0
  for seed in 101 2147483749 3000000202 303 2147484949 3999999999; do one 1 $seed 0; done
elif [ $part = b ]; then
  for seed in 404 2147485151 3111111505 606 2147486161 4294967290; do one 2 $seed 0; done
  one 3 3520000007 1
elif [ $part = c ]; then
  one 0 3570000001 0
  for seed in 7001 2147490011 3000000777 909 2147491013 4100000001; do one 1 $seed 0; done
else
  for seed in 1212 2147492015 3222222333 1515 2147493017 4294960001; do one 2 $seed 0; done
  one 3 3570000003 1
fi
python3 - <<PY
import json, statistics
rows = [json.loads(l) for l in open("$out/$cell.$part.jsonl")]
for s in (0, 1, 2, 3):
    ms = {}
    for r in rows:
        if r["set"] == s and r["line"]:
            for k in ("train_tokens_per_s", "setup_s"):
                if k in r["line"]["metrics"]:      # a traced line holds neither
                    ms.setdefault(k, []).append(r["line"]["metrics"][k]["value"])
    for k, v in ms.items():
        q = statistics.quantiles(v, n=4) if len(v) > 1 else [0, 0, 0]
        print("$cell set", s, k, "n", len(v), "median", statistics.median(v),
              "iqr_share", (q[2] - q[0]) / statistics.median(v), "values", v)
for r in rows:
    l = r["line"] or {}
    ref = (l.get("why_not") or {}).get("reference") or {}
    print("set", r["set"], "seed", r["seed"], "rc", r["rc"], "correct", l.get("correct"), "failed", l.get("failed"),
          "attempted", l.get("attempted"), r.get("held"),
          "| first", [ref.get("first_step", {}).get(k) for k in ("loss", "reference_loss", "tolerance")],
          "after", [ref.get("after_window", {}).get(k) for k in ("loss", "reference_loss", "tolerance")],
          "| peak", (l.get("device") or {}).get("memory_peak_bytes"))
if rows[-1]["trace"] == 1 and rows[-1]["line"]:
    l = rows[-1]["line"]
    print("traced:", json.dumps({k: v.get("value") if isinstance(v, dict) else v for k, v in l["metrics"].items()}))
    print("traced device:", json.dumps(l["device"]), "end to end in the traced run:", json.dumps(l.get("end_to_end_in_traced_run")))
PY
