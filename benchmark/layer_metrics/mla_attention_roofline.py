"""The latent-attention flash kernels (forward and backward, all layers)
of one train step against their roofline: the least time the chip could
take, max(operations / peak FLOP/s, bytes / peak bytes/s), over the
kernels' device time a step.

What the attention needs, whatever implements it. A head's score is
qk = qk_nope_head_dim + qk_rope_head_dim wide, its value v_head_dim, and
the rope part of the key is ONE vector a position for all heads. Causal,
so S^2 / 2 (query, key) pairs a sequence. Operations a head a layer a
sequence: S^2 (qk + v) forward (QK^T, PV) and S^2 (3 qk + 2 v) backward
(QK^T again, dV, dP, dQ, dK: flash recomputes the score): 320 + 832 =
1152 S^2 at 192 / 128.
Bytes: forward reads q (qk), k_nope, v and the shared k_pe once a batch
row, writes o and the row statistic; backward reads q, k_nope, v, o, dO,
k_pe and two row statistics, writes dq (qk), dk_nope, dv and dk_pe."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct

# the names ray_tpu/ops/flash_attention.py pins on the latent kernels'
# Pallas calls (LATENT_KERNEL_NAMES; tests/test_tracing_names.py)
KERNEL = r"^%(flash_latent_fwd|flash_latent_bwd_dq|flash_latent_bwd_dkv)(\.\d+)?$"

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def latent_attention_cost(batch: int, seq: int, c: dict,
                          itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's causal latent attention,
    all layers, forward and backward, from the configuration's ``sizes``."""
    h, layers = c["num_attention_heads"], c["num_hidden_layers"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    qk = dn + dr
    pairs = batch * h * seq * seq // 2               # causal (q, k) pairs
    flops = 2 * pairs * ((qk + dv) + (3 * qk + 2 * dv))
    per_head = lambda d: batch * h * seq * d * itemsize  # noqa: E731
    shared = batch * seq * dr * itemsize
    rows = batch * h * seq * 4
    fwd = per_head(qk) + per_head(dn) + 2 * per_head(dv) + shared + rows
    bwd = 2 * per_head(qk) + 2 * per_head(dn) + 4 * per_head(dv) \
        + 2 * shared + 2 * rows
    return {"flops": layers * flops, "bytes": layers * (fwd + bwd)}


def read(view):
    t = view.get("train")
    seconds = kernel_s_per_step(view, KERNEL) if t else None
    if not seconds:
        return None
    cost = latent_attention_cost(t["batch"], t["seq"],
                                 view["cell"]["config_file"]["sizes"])
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
