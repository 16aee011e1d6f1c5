"""The selective-scan kernels (forward and backward, every Mamba-1 layer)
of one train step against their roofline: the least time the chip could
take, max(operations / peak FLOP/s, bytes / peak bytes/s), over the
kernels' device time a step.

What the recurrence needs, whatever computes it: per token, layer, channel
of ``d_inner`` and state of ``d_state``, forward about 7 operations (dt A,
its exponential, the decay times the state, dt x times B, their sum, the
state times C, the sum over the states) and backward twice that (the same
again to have the states, and as many for the five gradients):

    ops = tokens x layers x d_inner x d_state x 21

Bytes, per token and layer: forward reads x' (d_inner, bf16), dt (d_inner,
float32), B and C (d_state each) and writes y (d_inner) and the state each
chunk of 128 tokens starts from (d_inner x d_state float32 a chunk);
backward reads x', dy, dt, B, C and those states and writes dx, d(dt), dB,
dC. At d_inner 5120, state 16: 1.72 M operations and 104 KB a token and
layer, so HBM bounds it: two layers of 8192 tokens are 28 G operations and
1.7 GB, 0.14 ms of the MXU's peak and 2.1 ms of HBM.

THE SHARE READS LOW, and not only for what the kernels leave undone: the
recurrence has no matrix product in it, so its operations are the VPU's,
whose peak (a few T operations a second in float32) ``lib/peaks.py`` does
not know; against the MXU's 197 T they count for nothing, and the bytes
alone set the floor. A kernel that kept the VPU full would still read well
under 100 % here."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct
from benchmark.lib.spec import family_of

# the names ray_tpu/ops/selective_scan.py pins on its Pallas calls
# (KERNEL_NAMES; tests/test_tracing_names.py): an operation of the trace is
# "%<name>" or "%<name>.<n>"
KERNEL = r"^%(selscan_chunk_fwd|selscan_chunk_bwd)(\.\d+)?$"
CHUNK = 128

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def selective_scan_cost(batch: int, seq: int, c: dict, layers: int,
                        itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's Mamba-1 recurrence in the
    ``layers`` Mamba-1 layers of ``sizes``, forward and backward."""
    di, n = c["mamba_expand"] * c["hidden_size"], c["mamba_d_state"]
    states = di * n * 4 // CHUNK
    vectors = di * 4 + 2 * n * itemsize                  # dt; B and C
    fwd_bytes = 2 * di * itemsize + vectors + states
    bwd_bytes = 3 * di * itemsize + 2 * vectors + states
    tokens = batch * seq * layers
    return {"flops": tokens * di * n * 21,
            "bytes": tokens * (fwd_bytes + bwd_bytes)}


def read(view):
    t = view.get("train")
    seconds = kernel_s_per_step(view, KERNEL) if t else None
    if not seconds:
        return None
    sizes = view["cell"]["config_file"]["sizes"]
    layers = family_of(view["cell"]).layer_kinds(sizes).count("mamba")
    cost = selective_scan_cost(t["batch"], t["seq"], sizes, layers)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
