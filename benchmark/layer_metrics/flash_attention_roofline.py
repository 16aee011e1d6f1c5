"""The flash-attention kernels (forward and backward, all layers) of one
train step against their roofline: the least time the chip could take,
max(operations / peak FLOP/s, bytes / peak bytes/s) from
benchmark/lib/flops.py, over the kernels' device time per step."""
from benchmark.lib.flops import flash_attention_cost
from benchmark.lib.peaks import peak

from benchmark.layer_metrics._common import T, TRAIN_STEP, complete_runs

# a Pallas kernel is a custom call to this target; the flash kernels are
# the only ones in the train step today
KERNEL = r"custom_call_target=tpu_custom_call"

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    tr, t = view.get("trace"), view.get("train")
    if tr is None or not t:
        return None
    steps = complete_runs(tr, TRAIN_STEP)
    if not steps:
        return None
    inside = T.union((p[1], p[1] + p[2]) for p in steps)
    kernel = T.union((o[1], o[1] + o[2]) for o in T.ops_matching(tr, KERNEL))
    busy = T.total(e for lo, hi in inside for e in T.clip(kernel, lo, hi))
    if not busy:
        return None
    sizes = view["cell"]["config_file"]["sizes"]
    cost = flash_attention_cost(t["batch"], sizes["n_head"], t["seq"],
                                sizes["d_model"] // sizes["n_head"],
                                sizes["n_layer"])
    pk = peak(view["device"]["kind"])
    least = max(cost["flops"] / pk["bf16_flops"],
                cost["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (busy / len(steps))
