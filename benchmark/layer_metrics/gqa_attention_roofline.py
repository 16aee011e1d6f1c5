"""The one-part flash kernels (forward and backward) of a grouped-query
model's attention layers in one train step against what GROUPED-QUERY
attention needs, whatever computes it: the least time the chip could take,
max(operations / peak FLOP/s, bytes / peak bytes/s), over the kernels'
device time a step.

Operations are those of the query heads (causal, so S^2 / 2 pairs a head):
forward QK^T and PV, backward QK^T again, dV, dP, dQ, dK: 7 products of
2 hd a pair. Bytes are of q, o, dO and dq at the ``num_attention_heads``
query heads but of k, v, dk and dv at the ``num_key_value_heads`` key/value
heads, which is what a kernel that reads each key/value head once for its
group of query heads would move; a program that repeats k and v to the
query heads before the kernels (models/granite_hybrid.py today, ROADMAP
B19) moves more and reads lower here for it. The float32 row statistics
(lse, delta) are S x 4 bytes a query head each way."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct
# the one-part kernels by their pinned names, the same events that
# ``flash_attention_roofline`` reads (a sequence one program holds whole
# would take the single-block pair)
from benchmark.layer_metrics.flash_attention_roofline import KERNEL

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def gqa_attention_cost(batch: int, seq: int, c: dict,
                       itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's causal grouped-query
    attention, every ``attention`` layer of ``sizes``, forward and
    backward."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    layers = c["layer_types"].count("attention")
    product = 2 * batch * h * seq * seq * hd // 2        # one causal matmul
    query = batch * h * seq * hd * itemsize              # q, o, dO or dq
    keyval = batch * kv * seq * hd * itemsize            # k, v, dk or dv
    rows = batch * h * seq * 4
    fwd = 2 * query + 2 * keyval + rows
    bwd = 4 * query + 4 * keyval + 2 * rows
    return {"flops": layers * 7 * product, "bytes": layers * (fwd + bwd)}


def read(view):
    t = view.get("train")
    sizes = view["cell"]["config_file"]["sizes"]
    if not t or "num_key_value_heads" not in sizes:
        return None
    seconds = kernel_s_per_step(view, KERNEL)
    if not seconds:
        return None
    cost = gqa_attention_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
