# PR 56, calls 4 and 5: the new cell under other learning rates: does the routers' drift onto or off the held experts, and
# with it a run's work, follow the rate? The cell's file holds the rate tried only while a run lasts (a cell under another
# name is in no metric's workloads and reports no train_tokens_per_s); it is put back as it was when the script ends,
# however it ends. Run from a checkout's root; everything is written under that checkout.
# usage: bash benchmark/scratch/pr56_exp_lr.sh "<lr>:<seed> <lr>:<seed> ..."
cell=nemotron3super_train_s8192; out=$(pwd)/chiprun_out/pr56; mkdir -p $out
cp benchmark/cells/$cell.json $out/$cell.json.kept
trap "cp $out/$cell.json.kept benchmark/cells/$cell.json" EXIT
for pair in $1; do
  lr=${pair%%:*}; seed=${pair##*:}
  python3 - <<PY
import json
p = "benchmark/cells/$cell.json"; c = json.load(open(p)); c["trainer"]["optimizer"]["lr"] = float("$lr"); json.dump(c, open(p, "w"), indent=1)
PY
  timeout 900 python3 benchmark/run.py --workload $cell --seed $seed --seconds 50 --trace 0 > $out/exp.out 2> $out/exp.err; rc=$?
  held=$(grep -h -o "held rows {[^}]*}" $out/exp.err $out/exp.out | tail -n 1)
  python3 - <<PY
import json
try:
    r = json.loads(open("$out/exp.out").read().strip().splitlines()[-1])
    d = json.load(open('.bench_out/$cell/train/flight.json'))
    since = [round(e['data']['since_s'], 3) for ring in d['rings'].values() for e in ring if isinstance(e, dict) and e.get('kind') == 'rtpu.train.report']
    c = r.get("compared") or {}
    print("lr $lr seed $seed rc $rc correct", r["correct"], "tokens/s", r["metrics"]["train_tokens_per_s"]["value"], "$held", "since_s", since,
          "first", c.get("first_step.loss_abs_diff"), "after", c.get("after_window.loss_abs_diff"),
          "last10 under first10", c.get("window_loss_last10_under_first10"))
except Exception as e:
    print("lr $lr seed $seed rc $rc no line", repr(e))
    import subprocess; print(subprocess.run("grep -v -e '^W0' -e '^I0' $out/exp.err | tail -8 | cut -c1-300", shell=True, capture_output=True, text=True).stdout)
PY
done
