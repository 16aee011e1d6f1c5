"""Operations and bytes the algorithms need, from shapes alone. Copied
arithmetic: ``GPT.flops_per_token`` (ray_tpu/models/gpt.py:156) and the
``cost_estimate`` of the flash kernels (ray_tpu/ops/flash_attention.py);
kept here so that no later PR can move the yardstick."""
from __future__ import annotations


def gpt_num_params(c: dict) -> int:
    """Parameters of a GPT-2 shaped model from its published sizes (tied
    head, learned positions, biases, two LayerNorms a block + final)."""
    d, f, layers = c["d_model"], c["d_ff"], c["n_layer"]
    block = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) \
        + 4 * d
    return c["vocab_size"] * d + c["max_seq"] * d + layers * block + 2 * d


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward matmul operations per token: 6 N plus causal
    attention 6 L S D (QK^T and PV, 2 S D MACs each forward, x3 for
    forward + backward, halved by the causal mask). Recomputation under
    remat is NOT counted: this is the model's cost, not the program's."""
    return 6 * gpt_num_params(c) + 6 * c["n_layer"] * c["d_model"] * seq


def flash_attention_cost(batch: int, heads: int, seq: int, head_dim: int,
                         layers: int, itemsize: int = 2) -> dict:
    """Causal flash attention over one train step, all layers, forward
    and backward. Forward: QK^T and PV = 2 matmuls of 2 S^2 hd each,
    halved by the mask. Backward (flash recomputes the scores): 5 matmuls
    (QK^T, dV, dP, dQ, dK). Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv; the float32
    row statistics (lse, delta) are S x 4 bytes a head each way."""
    bh = batch * heads
    mm = 2 * bh * seq * seq * head_dim // 2      # one causal matmul
    qkv = bh * seq * head_dim * itemsize         # one [B,H,S,hd] tensor
    rows = bh * seq * 4
    fwd = {"flops": 2 * mm, "bytes": 4 * qkv + rows}
    bwd = {"flops": 5 * mm, "bytes": 8 * qkv + 2 * rows}
    return {"flops": layers * (fwd["flops"] + bwd["flops"]),
            "bytes": layers * (fwd["bytes"] + bwd["bytes"])}
