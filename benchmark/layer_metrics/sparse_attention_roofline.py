"""The kernels that attend over an indexer's selection (forward and
backward, every layer) in one train step against what SELECTED
grouped-query attention needs, whatever computes it: the least time the
chip could take, max(operations / peak FLOP/s, bytes / peak bytes/s), over
the kernels' device time a step.

Operations are those of the query heads over the SELECTED pairs of a row,
sum_t min(t + 1, topk), not S^2 / 2: forward QK^T and PV, backward QK^T
again, dV, dP, dQ, dK: 7 products of 2 hd a selected pair and query head.
Bytes are of q, o, dO and dq at the ``num_attention_heads`` query heads, of
k, v, dk and dv at the ``num_key_value_heads`` key/value heads (what a
kernel that reads each key/value head once for its group moves), the
float32 row statistics (lse, delta) S x 4 bytes a query head each way, and
the selection once each way at its smaller form, the indices: S x topk x 4
bytes (a one-byte mask of every pair is S^2 bytes, twice that at the cell's
shape). A kernel that works every pair of every causal block, as a masked
flash kernel does, reads low here by the share of pairs that are selected;
that distance is what the metric is for."""
from benchmark.families.keye_vl2 import selected_pairs
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/sparse_attention.py pins on its Pallas calls
# (tests/test_tracing_names.py): an operation of the trace is "%<name>" or
# "%<name>.<n>"
KERNEL = r"^%(sparse_attn_fwd|sparse_attn_bwd_dq|sparse_attn_bwd_dkv)(\.\d+)?$"

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def sparse_attention_cost(batch: int, seq: int, c: dict,
                          itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's attention over the
    selection, every layer of ``sizes``, forward and backward."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    layers = c["num_hidden_layers"]
    pairs = selected_pairs(seq, c["topk"])
    product = 2 * batch * h * pairs * hd              # one matmul, selected
    query = batch * h * seq * hd * itemsize           # q, o, dO or dq
    keyval = batch * kv * seq * hd * itemsize         # k, v, dk or dv
    rows = batch * h * seq * 4
    selection = batch * seq * min(seq, c["topk"]) * 4
    fwd = 2 * query + 2 * keyval + rows + selection
    bwd = 4 * query + 4 * keyval + 2 * rows + selection
    return {"flops": layers * 7 * product, "bytes": layers * (fwd + bwd)}


def read(view):
    t = view.get("train")
    sizes = view["cell"]["config_file"]["sizes"]
    if not t or "topk" not in sizes:
        return None
    seconds = kernel_s_per_step(view, KERNEL)
    if not seconds:
        return None
    cost = sparse_attention_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
