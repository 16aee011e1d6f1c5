"""The Lightning-attention scan kernels (forward and backward, every
``lightning-attn`` layer) of one train step against their roofline: the
least time the chip could take, max(operations / peak FLOP/s, bytes / peak
bytes/s), over the kernels' device time a step (a rematerialised layer runs
its forward kernel twice; the second run is time, not need).

What the recurrence needs, whatever computes it, counted as a chunked scan
of Q = 256 tokens a chunk must: per token, layer and HELD head of 128 on a
state of 128 x 128, forward 2 x 128 x (Q/2 + 2 x 128) operations (the
chunk's causal local product over Q/2 tokens, the state read through q and
written through k) and 128 x Q for the head's OWN scores q k^T (2 x 128 x
Q/2: every head has its own q and k, where ``ssd_scan_roofline`` counts the
scores once a group); the backward twice the forward and the scores once
more:

    ops = tokens x layers x H x (3 x (2 x 128 (Q/2 + 256) + 128 Q) + 128 Q)

Bytes, per token, layer and head: forward reads q, k, v (128 each) and
writes o and the float32 state each chunk starts from (128 x 128 x 4 / Q a
token); backward reads q, k, v, dO and those states and writes dq, dk, dv.
The decays are a constant of the head: no dt, no gradient of one. At 16
heads: 6.82 M operations and 53.2 KB a token and layer, so HBM bounds it
(0.67 T operations and 5.23 GB a step of 32 768 tokens in three layers: 3.4
ms of the MXU, 6.4 ms of HBM)."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/lightning_attention.py pins on its Pallas calls
# (KERNEL_NAMES; tests/test_tracing_names.py): an operation of the trace is
# "%<name>" or "%<name>.<n>"
KERNEL = r"^%(lightning_chunk_fwd|lightning_chunk_bwd)(\.\d+)?$"
CHUNK = 256

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def lightning_scan_cost(batch: int, seq: int, c: dict,
                        itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's Lightning recurrence, every
    ``lightning-attn`` layer of ``sizes``, forward and backward."""
    h, d, q = c["lightning_nh"], c["lightning_head_dim"], CHUNK
    layers = c["mixer_types"].count("lightning-attn")
    scores = d * q                                     # 2 d Q/2 a head
    fwd = 2 * d * (q // 2 + 2 * d) + scores
    states = d * d * 4 // q
    fwd_bytes = 4 * d * itemsize + states              # q, k, v in; o out
    bwd_bytes = 7 * d * itemsize + states              # q, k, v, dO; dq dk dv
    tokens = batch * seq * layers * h
    return {"flops": tokens * (3 * fwd + scores),
            "bytes": tokens * (fwd_bytes + bwd_bytes)}


def read(view):
    t = view.get("train")
    sizes = view["cell"]["config_file"]["sizes"]
    if not t or "lightning_nh" not in sizes:
        return None
    seconds = kernel_s_per_step(view, KERNEL)
    if not seconds:
        return None
    cost = lightning_scan_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
