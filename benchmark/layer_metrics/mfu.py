"""Model FLOP/s utilisation: the tokens of one step x the operations per
token the model needs (forward + backward, recomputation not counted; the
count is the ``train_flops_per_token`` of the cell's family file), over
the device's time per step (the median train-step program plus the mean
idle gap to the next one, both from the trace), over chips x the chip's
published bf16 peak. Taken from the trace and not from the window's rate,
which in a traced run holds the seconds the profiler's own start and stop
stall the host."""
from benchmark.lib.peaks import peak
from benchmark.lib.spec import family_of

from benchmark.layer_metrics._common import T, TRAIN_STEP, complete_runs, \
    median

LAYER = "models"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    tr, t = view.get("trace"), view.get("train")
    if tr is None or not t or view["device"]["platform"] != "tpu":
        return None
    steps = complete_runs(tr, TRAIN_STEP)
    if not steps:
        return None
    gaps = T.gaps_between(tr, TRAIN_STEP)
    period = median([p[2] for p in steps]) \
        + (sum(gaps) / len(gaps) if gaps else 0.0)
    per_token = family_of(view["cell"]).train_flops_per_token(
        view["cell"]["config_file"]["sizes"], t["seq"])
    return 100.0 * t["batch"] * t["seq"] * per_token / period \
        / (view["device"]["count"]
           * peak(view["device"]["kind"])["bf16_flops"])
