"""Olmo-Hybrid shaped models: ``ray_tpu.models.OlmoHybrid`` (Gated DeltaNet
layers with key heads of 96 and value heads of 192, beta = 2 sigmoid,
through the chunked scan's kernel pair on padded lanes, and softmax
attention layers without positions whose q/k norms run over all of the
layer's channels, in the streamed flash kernels, three to one, in one stack
of unlike layers walked as runs of like layers; POST-norm residuals; a gated
MLP after every mixer; a share of both mixers' heads held; untied head, the
vocabulary a slice). The configuration's ``model`` dict names an
``OlmoHybridConfig`` constructor under ``preset``; every other key is a
keyword of it. Plain reference: ``reference/olmo_hybrid.py``."""

# the jax.named_scope names of models/olmo_hybrid.py, with the meanings the
# Qwen3-Next and Granite families give them. ``mixer`` is a Gated DeltaNet
# layer's q|k|v|gate and b|a projections, the output norm, gate and W_o and
# the post-norm of what they give; ``conv`` its convolution; ``scan`` beta
# and everything of ops/kda_scan.py (the pads to whole lane tiles, the l2
# norms, the gate's softplus, the kernels); ``attn`` the whole attention
# mixer with its post-norm; ``mlp`` the gated MLP with its post-norm
SCOPES = ("embed", "attn", "mixer", "conv", "scan", "mlp", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import OlmoHybrid, OlmoHybridConfig

    kw = dict(model)
    kw.pop("family")
    return OlmoHybrid(
        getattr(OlmoHybridConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a Gated
    DeltaNet layer's q|k|v|gate, b|a and output projections over the heads
    HELD; the attention layer's four projections over the heads held; every
    layer's gated MLP, whole; the head's ``vocab_size`` rows; the embedding
    is a lookup, the convolution, norms and gates' vectors are no matmuls),
    plus 3 x the forward's causal score and value products of an attention
    layer, 2 (head_dim + head_dim) S / 2 a head held, plus 3 x the
    recurrence of a Gated DeltaNet layer, 6 H d_k d_v a token: one
    multiply-add an element of the state for each of the read through k,
    the update and the read through q, at the MODEL's head sizes (96 x
    192), whatever lanes a route pads them to. What a chunked scan adds and
    what flash recomputes are the kernels' work, NOT counted here
    (``gdn_scan_roofline`` counts the former)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    gdn = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    kinds = c["layer_types"]
    n_attn = kinds.count("attention")
    n_gdn = len(kinds) - n_attn
    params = n_gdn * gdn + n_attn * attn + len(kinds) * 3 * d * f \
        + c["vocab_size"] * d
    return int(6 * params + 3 * n_attn * h * 2 * hd * seq
               + 3 * n_gdn * 6 * hv * dk * dv)
