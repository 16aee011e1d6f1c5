"""The state-space scan kernels (forward and backward, every Mamba-2 layer)
of one train step against their roofline: the least time the chip could
take, max(operations / peak FLOP/s, bytes / peak bytes/s), over the
kernels' device time a step (a rematerialised layer runs its forward
kernel twice; the second run is time, not need).

What the recurrence needs, whatever computes it, counted as a chunked scan
of Q = 256 tokens a chunk must: per token, layer and head of P with a
state of N, forward 2 P (Q/2 + 2 N) operations (the chunk's causal local
product over Q/2 tokens, the state read through C and updated through B),
and once a GROUP 2 N Q/2 for the scores C.B^T its heads share; the
backward twice the forward and the scores once more:

    ops = tokens x layers x (3 x (H x 2 P (Q/2 + 2 N) + G x N Q) + G x N Q)

Bytes, per token and layer: forward reads x (H P), dt (H, float32), B and
C (G N each) and writes y (H P) and the state each chunk starts from
(H P N float32 a chunk: H P N 4 / Q a token); backward reads x, dy, dt, B,
C and those states and writes dx, d(dt), dB, dC. At 64 heads of 64, state
128, one group: 9.57 M operations and 59.6 KB a token and layer, so HBM
bounds it (0.71 T operations and 4.4 GB a step of 8192 tokens in nine
layers: 3.6 ms of the MXU, 5.4 ms of HBM)."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/ssd_scan.py pins on its Pallas calls (KERNEL_NAMES;
# tests/test_tracing_names.py): an operation of the trace is "%<name>" or
# "%<name>.<n>"
KERNEL = r"^%(ssd_chunk_fwd|ssd_chunk_bwd)(\.\d+)?$"
CHUNK = 256

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def ssd_scan_cost(batch: int, seq: int, c: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's state-space recurrence,
    every ``mamba`` layer of ``sizes``, forward and backward."""
    h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    g, q = c["mamba_n_groups"], CHUNK
    layers = c["layer_types"].count("mamba")
    scores = g * n * q                                   # 2 N Q/2 a group
    fwd = h * 2 * p * (q // 2 + 2 * n) + scores
    states = h * p * n * 4 // q
    vectors = h * 4 + 2 * g * n * itemsize               # dt; B and C
    fwd_bytes = 2 * h * p * itemsize + vectors + states
    bwd_bytes = 3 * h * p * itemsize + 2 * vectors + states
    tokens = batch * seq * layers
    return {"flops": tokens * (3 * fwd + scores),
            "bytes": tokens * (fwd_bytes + bwd_bytes)}


def read(view):
    t = view.get("train")
    seconds = kernel_s_per_step(view, KERNEL) if t else None
    if not seconds:
        return None
    cost = ssd_scan_cost(t["batch"], t["seq"],
                         view["cell"]["config_file"]["sizes"])
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
