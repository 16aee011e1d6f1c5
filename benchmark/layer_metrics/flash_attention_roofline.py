"""The flash-attention kernels (forward and backward, all layers) of one
train step against their roofline: the least time the chip could take,
max(operations / peak FLOP/s, bytes / peak bytes/s) from
benchmark/lib/flops.py, over the kernels' device time per step."""
from benchmark.lib.flops import flash_attention_cost

from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/flash_attention.py pins on its Pallas calls (PR 24;
# tests/test_tracing_names.py): an operation of the trace is "%<name>" or
# "%<name>.<n>". Another kernel in the same step does not match.
KERNEL = (r"^%(flash_fwd_single|flash_fwd|flash_bwd_fused|flash_bwd_dq"
          r"|flash_bwd_dkv)(\.\d+)?$")

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(view):
    t = view.get("train")
    seconds = kernel_s_per_step(view, KERNEL) if t else None
    if not seconds:
        return None
    sizes = view["cell"]["config_file"]["sizes"]
    cost = flash_attention_cost(t["batch"], sizes["n_head"], t["seq"],
                                sizes["d_model"] // sizes["n_head"],
                                sizes["n_layer"])
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
