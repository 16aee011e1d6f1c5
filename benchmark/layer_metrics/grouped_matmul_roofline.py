"""The grouped products of the held experts (forward and backward, all
expert layers) of one train step against their roofline, over the two
kernels' device time a step.

What the held experts need: under a level router a layer's held experts
see ``num_experts_per_tok`` x ``experts_held`` / ``n_routed_experts`` rows
a token (0.75 at 6 x 16 / 128). A gated expert is three matrices of
hidden_size x moe_intermediate_size; forward, the gradient of the rows
and the gradient of the weights are one product each: 9 products of
2 rows D F operations a layer. Recomputation under remat is in the
kernels' time and NOT in the count. Bytes: every held expert's three
matrices read twice (forward, rows' gradient) and their gradient written
once; the rows' activations in and out of each product.

WAITING, not in BENCHMARK.json (PR 33; PERF.md sections 6 and 7): the
count holds under a level router only, and a share of the experts that is
trained without the others is not routed to for long. In
``kanana2_train_s8192`` the held rows fall from 12 000 a layer to none
within twenty steps, the kernels then work one empty tile an expert, and
this reader gives 115 %: operations counted too high. It enters with a
counter of the rows held (``model.routing_stats`` in the loop's report,
an edit to ``lib/chip.py``) or with a cell whose router keeps its load."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/expert_layer.py pins on its two Pallas calls
# (KERNEL_NAMES; tests/test_tracing_names.py)
KERNEL = r"^%(grouped_matmul|grouped_matmul_dw)(\.\d+)?$"

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def grouped_matmul_cost(tokens: int, c: dict, itemsize: int = 2) -> dict:
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    rows = tokens * c["num_experts_per_tok"] * c["experts_held"] \
        // c["n_routed_experts"]
    weights = c["experts_held"] * 3 * d * f * itemsize
    # a product reads its rows (D or F wide) and writes the other width
    acts = rows * (d + f) * itemsize
    return {"flops": layers * 9 * 2 * rows * d * f,
            "bytes": layers * (3 * weights + 9 * acts)}


def read(view):
    t = view.get("train")
    seconds = kernel_s_per_step(view, KERNEL) if t else None
    if not seconds:
        return None
    cost = grouped_matmul_cost(t["batch"] * t["seq"],
                               view["cell"]["config_file"]["sizes"])
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
