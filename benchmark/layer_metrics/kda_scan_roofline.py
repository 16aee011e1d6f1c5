"""The Kimi-Delta-Attention recurrence of one train step against its
roofline: the least time the chip could take, max(operations / peak
FLOP/s, bytes / peak bytes/s), over the device time of EVERYTHING under
the scope ``scan`` (the gates' softplus, the l2 norms and the scan with
its backward), found by scope and not by a kernel's name, so that it reads
the same work whatever implements it (``train_scan_ms`` is the
denominator).

What the recurrence needs, whatever computes it, counted as a chunked
delta rule of C = 64 tokens a chunk must. A token, head and KDA layer,
keys of d_k and values of d_v:

* forward: 2 (C/2) (3 d_k + 2 d_v) operations for the chunk's triangular
  products and solve (the decayed key-key and query-key scores over C/2
  earlier tokens and the keys scaled for the state, d_k each; the solve's
  right-hand sides and the local output, d_v each) + 6 d_k d_v for the
  three products with the state (the read through k, the update, the read
  through q);
* backward: twice the forward;
* every layer is rematerialised and keeps nothing of the scan
  (``models/kimi_linear.py`` ``_REMAT_SAVE``), so the forward runs a second
  time in the backward pass: counted as a forward, as ``mhc_mix_roofline``
  counts it.

Bytes, a token, head and layer: forward reads q, k, v (bf16), g (float32)
and beta (float32, one a head) and writes o (bf16) and the state each
chunk starts from (d_k d_v float32 a chunk: d_k d_v 4 / C a token);
backward reads q, k, v, g, beta, do and those states and writes dq, dk, dv
(bf16), dg and dbeta (float32); the rematerialised forward once more as a
forward. At 32 heads of 128 x 128 and C 64: 0.56 M operations and 9.0 KB a
token, head and layer; over 16 384 tokens and four layers 1.17 T operations
(5.9 ms of the MXU's peak) and 18.8 GB (23.0 ms of HBM): HBM bounds it."""
from benchmark.layer_metrics._program import scope_ms_per_step
from benchmark.layer_metrics._common import roofline_pct

CHUNK = 64

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def kda_scan_cost(batch: int, seq: int, c: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's delta-rule recurrence,
    every ``kda`` layer of ``sizes``: forward, rematerialised forward and
    backward."""
    h, dk = c["kda_num_heads"], c["kda_head_dim"]
    dv = dk
    layers = c["layer_types"].count("kda")
    fwd = 2 * (CHUNK // 2) * (3 * dk + 2 * dv) + 6 * dk * dv
    states = dk * dv * 4 // CHUNK
    reads = (2 * dk + dv) * itemsize + dk * 4 + 4          # q, k, v, g, beta
    fwd_bytes = reads + dv * itemsize + states
    bwd_bytes = reads + dv * itemsize + states \
        + (2 * dk + dv) * itemsize + dk * 4 + 4            # do; dq .. dbeta
    units = batch * seq * h * layers
    return {"flops": units * (2 + 2) * fwd,
            "bytes": units * (2 * fwd_bytes + bwd_bytes)}


def read(view):
    t, by = view.get("train"), scope_ms_per_step(view)
    sizes = view["cell"]["config_file"].get("sizes", {})
    if not t or not by or not by.get("scan") or "kda_num_heads" not in sizes:
        return None
    cost = kda_scan_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, by["scan"] * 1e-3, cost["flops"], cost["bytes"])
