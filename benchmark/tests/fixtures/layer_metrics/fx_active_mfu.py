"""Test fixture: an ``mfu``-like reader of a family in which a token does
not touch every parameter: the operations a step needs, by the family's
own count, over the chip's peak for the median train-step program."""
from benchmark.lib.peaks import peak
from benchmark.lib.spec import family_of

from benchmark.layer_metrics._common import TRAIN_STEP, complete_runs, median

LAYER = "models"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    tr, t = view.get("trace"), view.get("train")
    if tr is None or not t:
        return None
    per_token = family_of(view["cell"]).train_flops_per_token(
        view["cell"]["config_file"]["sizes"], t["seq"])
    steps = complete_runs(tr, TRAIN_STEP)
    if not steps or view["device"]["platform"] != "tpu":
        return None
    return 100.0 * t["batch"] * t["seq"] * per_token \
        / median([p[2] for p in steps]) \
        / (view["device"]["count"]
           * peak(view["device"]["kind"])["bf16_flops"])
