"""Phi-4-mini-flash-reasoning shaped models: ``ray_tpu.models.SambaY`` (a
decoder-hybrid-decoder: Mamba-1 layers through the selective-scan kernels
and differential attention under a window in the flash kernels, then gated
memory units and cross-attention that read ONE earlier layer's scan
output, keys and values; a gated MLP after every mixer; LayerNorms; the
head tied to the embedding; the vocabulary a slice; the stack walked as
runs of like periods with a side state). The configuration's ``model`` dict
names a ``SambaYConfig`` constructor under ``preset``; every other key is a
keyword of it (``layers``: the published indices held). Plain reference:
``reference/phi4flash.py``."""

# the jax.named_scope names of models/sambay.py: ``mixer`` is the Mamba-1
# layer's norm, projections and residual, ``conv`` its causal convolution,
# ``scan`` dt, the scan kernels, D x and the gate; ``attn`` the self-attention
# layers whole (window and full), ``gmu`` and ``cross_attn`` the
# cross-decoder's two mixers
SCOPES = ("embed", "mixer", "conv", "scan", "attn", "gmu", "cross_attn",
          "mlp", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import SambaY, SambaYConfig

    kw = dict(model)
    kw.pop("family")
    if "layers" in kw:
        kw["layers"] = tuple(kw["layers"])
    return SambaY(getattr(SambaYConfig, kw.pop("preset", "tiny"))(**kw))


def layer_kinds(c: dict) -> list:
    """The kind of every layer ``sizes`` holds, by its published index:
    mamba / window / full / gmu / cross."""
    half = c["num_hidden_layers_published"] // 2
    return [("mamba" if i <= half else "gmu") if i % 2 == 0 else
            ("window" if i < half else "full" if i == half + 1 else "cross")
            for i in c["layers"]]


def attention_pairs(c: dict, seq: int) -> float:
    """(query, key) pairs a token of one differential head's score map
    meets, summed over the attention layers ``sizes`` holds: S / 2 in a
    full or cross layer, and under a window w the mean of min(i + 1, w),
    w - w (w - 1) / (2 S) (496.0 at w = 512, S = 8192)."""
    w = min(c["sliding_window"], seq)
    kinds = layer_kinds(c)
    return (kinds.count("full") + kinds.count("cross")) * seq / 2 \
        + kinds.count("window") * (w - w * (w - 1) / (2 * seq))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a
    Mamba-1 layer's four projections, a self-attention layer's four, a
    gated memory unit's two, a cross-attention layer's two, every layer's
    gated MLP, the head's ``vocab_size`` rows; the embedding is a lookup,
    the convolution, norms, lam and the vectors of the scan no matmuls),
    plus 3 x the forward's score and value products of DIFFERENTIAL
    attention at the pairs a mask leaves (``attention_pairs``): a pair and
    differential head two score maps of 2 hd and two products with the
    value of 2 hd, 12 hd = 768 operations, each map counted ONCE whatever
    the kernels do; plus 3 x the recurrence of a Mamba-1 layer, 4 x
    d_inner x state a token: one multiply-add an element of the state to
    update it and one to read it (as ``granite_hybrid`` counts its own).
    What flash recomputes and what forming a map twice costs is the
    kernels' work, NOT counted here."""
    d, f, hd = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    di, n, r = c["mamba_expand"] * d, c["mamba_d_state"], c["mamba_dt_rank"]
    per = {"mamba": 2 * d * di + di * (r + 2 * n) + r * di + di * d,
           "window": d * hd * (2 * h + 2 * kv), "gmu": 2 * d * di,
           "cross": 2 * d * hd * h}
    per["full"] = per["window"]
    kinds = layer_kinds(c)
    params = sum(per[k] for k in kinds) + len(kinds) * 3 * d * f \
        + c["vocab_size"] * d
    return int(6 * params
               + 3 * (h // 2) * 12 * hd * attention_pairs(c, seq)
               + 3 * kinds.count("mamba") * 4 * di * n)
