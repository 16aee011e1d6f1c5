"""The one-part flash kernels (forward and backward) of a differential-
attention model's attention layers in one train step against what
DIFFERENTIAL attention needs, whatever computes it: the least time the chip
could take, max(operations / peak FLOP/s, bytes / peak bytes/s), over the
kernels' device time a step.

A differential head is two score maps of head size hd against ONE value of
2 hd. Per (query, key) pair the mask leaves and differential head, each
map formed once: forward two QK^T of 2 hd and two PV of 4 hd, 12 hd = 768
operations at hd 64; backward, per map, QK^T again (2 hd), dP and dV (4 hd
each), dQ and dK (2 hd each), 28 hd = 1792. The pairs are the family
file's (``families/phi4flash.attention_pairs``: S / 2 a token in a full or
cross layer, about the window in a window layer). A program that hands its
kernels four heads of hd a differential head (models/sambay.py today: each
map formed twice; ROADMAP B19) does twice the products and reads under
half here for it.

Bytes are of q, o, dO and dq at the ``num_attention_heads`` query heads
and of k, v, dk and dv at the ``num_key_value_heads`` key/value heads (a
cross layer reads k and v and writes their gradients like any other), plus
the float32 row statistics, S x 4 bytes a query head each way."""
from benchmark.layer_metrics._common import kernel_s_per_step, roofline_pct
# the one-part kernels by their pinned names, the same events that
# ``flash_attention_roofline`` reads: a window layer takes the streamed
# three whatever S
from benchmark.layer_metrics.flash_attention_roofline import KERNEL
from benchmark.lib.spec import family_of

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def diff_attention_cost(batch: int, seq: int, c: dict, pairs: float,
                        layers: int, itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's differential attention:
    ``pairs`` (query, key) pairs a token summed over the ``layers``
    attention layers of ``sizes``, forward and backward."""
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    tokens = batch * seq
    query = tokens * h * hd * itemsize                   # q, o, dO or dq
    keyval = tokens * kv * hd * itemsize                 # k, v, dk or dv
    rows = tokens * h * 4
    return {"flops": int(tokens * (h // 2) * (12 + 28) * hd * pairs),
            "bytes": layers * (6 * query + 6 * keyval + 3 * rows)}


def read(view):
    t = view.get("train")
    seconds = kernel_s_per_step(view, KERNEL) if t else None
    if not seconds:
        return None
    family = family_of(view["cell"])
    pairs = getattr(family, "attention_pairs", None)
    if pairs is None:           # no differential attention in this family
        return None
    sizes = view["cell"]["config_file"]["sizes"]
    layers = sum(k in ("window", "full", "cross")
                 for k in family.layer_kinds(sizes))
    cost = diff_attention_cost(t["batch"], t["seq"], sizes,
                               pairs(sizes, t["seq"]), layers)
    return roofline_pct(view, seconds, cost["flops"], cost["bytes"])
