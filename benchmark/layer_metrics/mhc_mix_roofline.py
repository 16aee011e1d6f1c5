"""The hyper-connections' mixings of one train step against their
roofline: the least time the chip could take, max(operations / peak
FLOP/s, bytes / peak bytes/s), over the device time of EVERYTHING under
the scope ``mhc``, found by scope and not by a kernel's name, so that it
reads the same work whatever implements it (``train_mhc_ms`` is the
denominator).

What the mixing needs, whatever implements it. n streams of d a token,
2 x layers sublayers, an activation of one stream [tokens, d] the unit:

* forward of a sublayer: X is read twice (a token's coefficients need its
  whole row before any of it can be mixed: once for the statistic and
  ũΦ, once for z and X'), y once; z and X' are written once: 3 n + 2
  units. ũΦ is 2 n d (2 n + n²) operations a token.
* every layer is rematerialised and keeps none of the mixings' outputs
  (``models/deepseek_v3.py`` ``_REMAT_SAVE``), so the forward runs a
  second time in the backward pass: counted as a forward, as
  ``mla_attention_roofline`` counts the score the flash backward makes
  again.
* backward of a sublayer: X and dX' are read twice (before and after the
  sublayer's own backward, which stands between the two mixings), y and
  dz once; dX and dy are written once: 5 n + 3 units. The two products
  with Φ (dΦ and dũ) are twice the forward's operations.

The coefficients (2 n + n² floats a token), the Sinkhorn iterations on
them and the multiply-adds of the mixings ((2 n + n²) d a token, far
under the MXU's peak's worth of time) add nothing to either bound at these
sizes: the bytes bound it."""
from benchmark.layer_metrics._program import scope_ms_per_step
from benchmark.layer_metrics._common import roofline_pct

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def mhc_mix_cost(batch: int, seq: int, c: dict, itemsize: int = 2) -> dict:
    """Operations and bytes of one train step's mixings, all sublayers,
    forward, rematerialised forward and backward, from the
    configuration's ``sizes``."""
    n, d = c["hc_mult"], c["hidden_size"]
    sublayers = 2 * c["num_hidden_layers"]
    tokens = batch * seq
    unit = tokens * d * itemsize
    product = 2 * n * d * (2 * n + n * n) * tokens
    return {"flops": sublayers * (2 + 2) * product,
            "bytes": sublayers * (2 * (3 * n + 2) + 5 * n + 3) * unit}


def read(view):
    t, by = view.get("train"), scope_ms_per_step(view)
    sizes = view["cell"]["config_file"].get("sizes", {})
    if not t or not by or not by.get("mhc") or "hc_mult" not in sizes:
        return None
    cost = mhc_mix_cost(t["batch"], t["seq"], sizes)
    return roofline_pct(view, by["mhc"] * 1e-3, cost["flops"], cost["bytes"])
