cd chip_check/final
out=/root/repo/chiprun_out/pr49/call10; mkdir -p $out
for spec in "3490000101 0" "3490000102 1"; do set -- $spec
  timeout 900 python3 benchmark/run.py --workload kimilinear_train_s8192 --seed $1 --seconds 50 --trace $2 > $out/run_$2.out 2> $out/run_$2.err; echo "final tree, seed $1 trace $2: rc=$?"
  tail -n 1 $out/run_$2.out | python3 -c "
import json,sys; l=json.loads(sys.stdin.read()); print(l['correct'], l['attempted'], {k: round(v['value'],3) for k,v in l['metrics'].items()}); print(l['compared']['window_steps_at_least'], l['device'])"
done
python3 - <<PY
import json
f=json.load(open('.bench_out/kimilinear_train_s8192/train/flight.json'))
def walk(o):
    if isinstance(o, dict):
        if o.get('kind') in ('rtpu.ops.kda.path','rtpu.models.stack.runs'): print(json.dumps(o)[:400])
        for v in o.values(): walk(v)
    elif isinstance(o, list):
        for v in o: walk(v)
walk(f)
PY
