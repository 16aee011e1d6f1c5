# usage: bash benchmark/scratch/pr56_cell.sh <tree> <label> <cell> <seed> <trace>   (PR 56)
# One run of a cell from <tree>'s root as the driver runs it; the result line goes to
# chiprun_out/pr56/<label>.jsonl and its numbers, condensed, to stdout.
root=$(pwd); out=$root/chiprun_out/pr56; mkdir -p $out
cd $1 && timeout 1200 python3 benchmark/run.py --workload $3 --seed $4 --seconds 50 --trace $5 > $out/last.out 2> $out/last.err; rc=$?
tail -n 1 $out/last.out >> $out/$2.jsonl
[ $5 = 1 ] && cp .bench_out/$3/train/flight.json $out/$2.flight.json 2>/dev/null
cd $root
python3 - <<PY
import json
try:
    r = json.loads(open("$out/last.out").read().strip().splitlines()[-1])
    flat = lambda d: {k: (v["value"] if isinstance(v, dict) and "value" in v else v) for k, v in (d or {}).items()}
    print("$2 $3 seed $4 trace $5 rc $rc correct", r.get("correct"), "failed", r.get("failed"))
    print("  end_to_end", json.dumps(flat(r.get("metrics"))))
    print("  per_layer", json.dumps(flat(r.get("per_layer"))))
    print("  why_not", json.dumps(r.get("why_not"))[:1500])
    print("  device", json.dumps(r.get("device")))
except Exception as e:
    print("$2 $3 rc $rc no result line:", e)
    import subprocess
    print(subprocess.run("grep -v -e '^W0' -e '^I0' -e hugepages -e warnings.warn $out/last.err | tail -25 | cut -c1-400", shell=True, capture_output=True, text=True).stdout)
PY
