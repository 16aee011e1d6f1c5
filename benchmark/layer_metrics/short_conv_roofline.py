"""The gated short convolution's kernels (forward and backward, every conv
operator) of one train step against what a gated 3-tap convolution NEEDS,
whatever computes it: the least time the chip could take, max(operations /
peak FLOP/s, bytes / peak bytes/s), over the kernels' device time a step.

Per token, layer and channel of ``hidden_size``: the forward reads b, c, x
and writes y (4 values), 2 products (b x, c conv) and the taps' 5 (K
multiplies, K - 1 adds at K = ``conv_L_cache`` 3); the backward reads b, c,
x, dy and writes db, dc, dx (7 values) and makes the forward's 7 again, the
cotangent dy c, the transposed taps' 5, the two gates' gradients and the
taps' own (K multiply-adds into [K, D] float32 sums, whose bytes are
nothing beside the rows'): 21. At d 2048 and 16 384 tokens a layer is 0.94 G
operations and 738 MB, 0.005 ms of the MXU's peak and 0.90 ms of HBM: the
bytes set the floor.

THE SHARE READS LOW for two reasons that are the program's, not the
kernels': every layer is rematerialised and keeps nothing of the operator,
so the forward kernel runs twice a layer (4 more values a channel that the
need does not hold: the program moves 15 values an element for the 11
needed, and a perfect kernel pair reads 11 / 15 = 73 %, not 100), and the operations are the VPU's, whose peak
``lib/peaks.py`` does not know (against the MXU's 197 T they count for
nothing). Where the program has no such kernels (a tree before PR 64, or
the plain route) nothing matches and the metric is left out."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/short_conv.py pins on its Pallas calls
# (KERNEL_NAMES; tests/test_tracing_names.py): an operation of the trace is
# "%<name>" or "%<name>.<n>"
KERNEL = r"^%(short_conv_fwd|short_conv_bwd)(\.\d+)?$"

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def short_conv_cost(batch: int, seq: int, c: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's gated short convolutions,
    every ``conv`` layer of ``sizes``, forward and backward."""
    taps = c["conv_L_cache"]
    elements = batch * seq * c["hidden_size"] * c["layer_types"].count("conv")
    fwd_ops = 2 + 2 * taps - 1
    bwd_ops = fwd_ops + 1 + (2 * taps - 1) + 2 + 2 * taps
    return {"flops": elements * (fwd_ops + bwd_ops),
            "bytes": elements * (4 + 7) * itemsize}


def read(view):
    t = view.get("train")
    sizes = view["cell"]["config_file"]["sizes"]
    if not t or "conv_L_cache" not in sizes:
        return None
    seconds = kernel_s_per_step(view, KERNEL)
    if not seconds:
        return None
    cost = short_conv_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
