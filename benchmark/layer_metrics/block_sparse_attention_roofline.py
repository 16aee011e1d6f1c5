"""The kernels that attend over a BLOCK selection (forward and backward,
every ``minicpm4`` layer) in one train step against what attention over the
SELECTED blocks needs, whatever computes it: the least time the chip could
take, max(operations / peak FLOP/s, bytes / peak bytes/s), over the
kernels' device time a step.

Operations are those of the held query heads over the SELECTED pairs of a
row (``families/minicpm_sala.selected_pairs``: 96 blocks of 64 keys a
query, the causal part of its own; 34 % of the causal pairs at 32 768), not
S^2 / 2: forward QK^T and PV, backward QK^T again, dV, dP, dQ, dK: 7
products of 2 x 128 a selected pair and query head. Bytes as
``sparse_attention_roofline`` counts them: q, o, dO and dq at the query
heads held, k, v, dk and dv at the key/value heads held (what a kernel that
reads each key/value head once for its group moves), the float32 row
statistics (lse, delta) S x 4 bytes a query head each way, and the
selection once each way at its smaller form, the block indices: S x 96 x 4
bytes a key/value group (a byte a pair is S^2 bytes, 171 times that at the
cell's shape). A kernel that works every pair of every causal block, as the
masked flash kernel does, reads low here by the share of pairs that are
selected; that distance is what the metric is for."""
from benchmark.families.minicpm_sala import selected_pairs
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/sparse_attention.py pins on its Pallas calls
# (tests/test_tracing_names.py): an operation of the trace is "%<name>" or
# "%<name>.<n>"
KERNEL = r"^%(sparse_attn_fwd|sparse_attn_bwd_dkv)(\.\d+)?$"

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def block_sparse_attention_cost(batch: int, seq: int, c: dict,
                                itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's attention over the selected
    blocks, every ``minicpm4`` layer of ``sizes``, forward and backward."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    layers = c["mixer_types"].count("minicpm4")
    product = 2 * batch * h * selected_pairs(seq, c) * hd   # one matmul
    query = batch * h * seq * hd * itemsize            # q, o, dO or dq
    keyval = batch * kv * seq * hd * itemsize          # k, v, dk or dv
    rows = batch * h * seq * 4
    selection = batch * kv * seq * min(
        -(-seq // c["sparse_block"]), c["sparse_blocks"]) * 4
    fwd = 2 * query + 2 * keyval + rows + selection
    bwd = 4 * query + 4 * keyval + 2 * rows + selection
    return {"flops": layers * 7 * product, "bytes": layers * (fwd + bwd)}


def read(view):
    t = view.get("train")
    sizes = view["cell"]["config_file"]["sizes"]
    if not t or "sparse_blocks" not in sizes:
        return None
    seconds = kernel_s_per_step(view, KERNEL)
    if not seconds:
        return None
    cost = block_sparse_attention_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
