"""The Gated DeltaNet recurrence of one train step against its roofline:
the least time the chip could take, max(operations / peak FLOP/s, bytes /
peak bytes/s), over the device time of EVERYTHING under the scope ``scan``
(beta's sigmoid, whatever brings q and k to the value heads, the l2 norms,
the gate's softplus and the scan with its backward), found by scope and not
by a kernel's name, so that it reads the same work whatever implements it
(``train_scan_ms`` is the denominator).

What a delta rule with ONE decay a head needs, whatever computes it,
counted as a chunked delta rule of C = 64 tokens a chunk must. The decay
is a scalar a value head and token, so it factors out of every contraction
over the key channels: the scores are made once a KEY head and each value
head lays its own [C, C] table of decays over them. A token and Gated
DeltaNet layer, Hk key heads of d_k, Hv value heads of d_v:

* forward, a key head: 2 (C/2) (2 d_k) operations for the key-key and the
  query-key scores over C/2 earlier tokens; a value head: 2 (C/2) (2 d_v)
  for the solve against its right-hand side (beta (v - e^G k S_0): the
  keys' own W is never needed where the decay is a scalar) and the local
  output, + 6 d_k d_v for the three products with the state (the read
  through k, the update, the read through q);
* backward: twice the forward;
* every layer is rematerialised and keeps nothing of the scan
  (``models/qwen3_next.py`` ``_REMAT_SAVE``), so the forward runs a second
  time in the backward pass: counted as a forward, as
  ``kda_scan_roofline`` counts it.

Bytes, a token and layer: forward reads q and k (bf16, once a KEY head), v
(bf16), g and beta (float32, one a value head each) and writes o (bf16)
and the state each chunk starts from (d_k d_v float32 a chunk and value
head: d_k d_v 4 / C a token); backward reads q, k, v, g, beta, do and those
states and writes dq, dk (a key head), dv (bf16), dg and dbeta (float32);
the rematerialised forward once more as a forward. At 16 key heads, 32
value heads of 128 x 128 and C 64: 3.93 M operations forward and 189 KB a
token and layer; over 16 384 tokens and three layers 0.77 T operations
(3.9 ms of the MXU's peak) and 9.3 GB (11.4 ms of HBM): HBM bounds it. A
program that repeats q and k to the value heads before the kernels and
spreads the scalar over a head's lanes (``ops.gdn_gated_scan`` today,
ROADMAP A) moves and computes more and reads lower here for it."""
from benchmark.layer_metrics._program import scope_ms_per_step
from benchmark.layer_metrics._common import roofline_pct

CHUNK = 64

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def gdn_scan_cost(batch: int, seq: int, c: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's delta-rule recurrence,
    every ``linear_attention`` layer of ``sizes``: forward, rematerialised
    forward and backward."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    layers = c["layer_types"].count("linear_attention")
    fwd = hk * 2 * (CHUNK // 2) * 2 * dk \
        + hv * (2 * (CHUNK // 2) * 2 * dv + 6 * dk * dv)
    states = hv * dk * dv * 4 // CHUNK
    reads = 2 * hk * dk * itemsize + hv * dv * itemsize + 2 * hv * 4
    fwd_bytes = reads + hv * dv * itemsize + states
    bwd_bytes = reads + hv * dv * itemsize + states \
        + 2 * hk * dk * itemsize + hv * dv * itemsize + 2 * hv * 4
    units = batch * seq * layers
    return {"flops": units * (2 + 2) * fwd,
            "bytes": units * (2 * fwd_bytes + bwd_bytes)}


def read(view):
    t, by = view.get("train"), scope_ms_per_step(view)
    sizes = view["cell"]["config_file"].get("sizes", {})
    if not t or not by or not by.get("scan") \
            or "linear_num_value_heads" not in sizes:
        return None
    cost = gdn_scan_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, by["scan"] * 1e-3, cost["flops"], cost["bytes"])
