"""Granite-4.0-H shaped models: ``ray_tpu.models.GraniteHybrid`` (Mamba-2
state-space layers through the chunked scan kernels and grouped-query
attention layers without positions in one stack of unlike layers, walked
as runs of like layers; a gated MLP after every mixer; four scalar
multipliers; the head tied to the embedding; the vocabulary a slice). The
configuration's ``model`` dict names a ``GraniteHybridConfig`` constructor
under ``preset``; every other key is a keyword of it. Plain reference:
``reference/granite_hybrid.py``."""

# the jax.named_scope names of models/granite_hybrid.py: ``mixer`` is the
# state-space layer's norm, in- and out-projection and splits, ``conv`` its
# causal convolution, ``scan`` dt, the scan kernels, D x and the gated norm
SCOPES = ("embed", "attn", "mixer", "conv", "scan", "mlp", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import GraniteHybrid, GraniteHybridConfig

    kw = dict(model)
    kw.pop("family")
    return GraniteHybrid(
        getattr(GraniteHybridConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a
    state-space layer's in- and out-projection, an attention layer's four
    projections, every layer's gated MLP, and the head's ``vocab_size``
    rows; the embedding is a lookup, the convolution, norms and the
    vectors of the scan are no matmuls), plus 3 x the forward's causal
    score and value products of an attention layer, 2 x heads x 2 head_dim
    x S / 2 a token, plus 3 x the recurrence of a state-space layer, 4 H P N
    a token: one multiply-add an element of the state to update it and one
    to read it, whatever chunking computes them. What a chunked scan adds
    (its Q/2-wide local products) and what flash recomputes are the
    kernels' work, NOT counted here (``ssd_scan_roofline`` and
    ``gqa_attention_roofline`` count them)."""
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    d_inner = h * p
    mamba = d * (2 * d_inner + 2 * c["mamba_n_groups"] * n + h) + d_inner * d
    hd = c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attention = d * hd * (2 * heads + 2 * kv)
    kinds = c["layer_types"]
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    params = n_mamba * mamba + n_attn * attention \
        + len(kinds) * 3 * d * f + c["vocab_size"] * d
    return int(6 * params + 3 * n_attn * 2 * heads * 2 * hd * seq // 2
               + 3 * n_mamba * 4 * h * p * n)
