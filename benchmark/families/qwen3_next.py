"""Qwen3-Next shaped models: ``ray_tpu.models.Qwen3Next`` (Gated DeltaNet
layers, a delta rule with one decay a head and two value heads to a key
head, through the chunked scan, and gated grouped-query softmax-attention
layers of 256-wide heads in the streamed flash kernels, three to one, in
one stack of unlike layers walked as runs of like layers; every layer
before a softmax-routed expert layer with a gated shared expert, of whose
routed experts the chip holds a share; untied head, the vocabulary a
slice). The configuration's ``model`` dict names a ``Qwen3NextConfig``
constructor under ``preset``; every other key is a keyword of it. Plain
reference: ``reference/qwen3_next.py``."""

# the jax.named_scope names of models/qwen3_next.py and
# ops/expert_layer.py. ``mixer`` is a Gated DeltaNet layer's norm, its
# q|k|v|z and b|a projections, the output norm, gate and W_o; ``conv`` its
# convolution; ``scan`` beta's sigmoid and everything of
# ops/kda_scan.py (the repeat of q and k to the value heads, the l2 norms,
# the gate's softplus, the kernels); ``attn`` the whole attention mixer;
# ``router`` the expert layer's norm, scores, top-k, the sort and the rows'
# gathers
SCOPES = ("embed", "attn", "mixer", "conv", "scan", "router", "experts",
          "shared_expert", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import Qwen3Next, Qwen3NextConfig

    kw = dict(model)
    kw.pop("family")
    return Qwen3Next(
        getattr(Qwen3NextConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a Gated
    DeltaNet layer's q|k|v|z, b|a and output projections; the attention
    layer's q|gate, k, v and output projections; in every layer the
    router, the shared expert with its gate and ``num_experts_per_tok`` x
    ``experts_held`` / ``num_experts`` routed experts in expectation, which
    is what the held share sees under a level router; the head's
    ``vocab_size`` rows; the embedding is a lookup, the convolution, norms
    and gates' vectors are no matmuls), plus 3 x the forward's causal score
    and value products of an attention layer, 2 (head_dim + head_dim) S / 2
    a query head, plus 3 x the recurrence of a Gated DeltaNet layer, 6 Hv
    d_k d_v a token: one multiply-add an element of the state for each of
    the read through k, the update and the read through q (the decay rides
    with the read), whatever chunking computes them. What a chunked scan
    adds (its local triangular products and solve) and what flash
    recomputes are the kernels' work, NOT counted here
    (``gdn_scan_roofline`` counts the former)."""
    d = c["hidden_size"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    gdn = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * h * 2 * hd + 2 * d * kv * hd + h * hd * d
    routed = c["num_experts_per_tok"] * c["experts_held"] / c["num_experts"]
    moe = d * c["num_experts"] + 3 * d * c["shared_expert_intermediate_size"] \
        + d + routed * 3 * d * c["moe_intermediate_size"]
    kinds = c["layer_types"]
    n_attn = kinds.count("attention")
    n_gdn = len(kinds) - n_attn
    params = n_gdn * gdn + n_attn * attn + len(kinds) * moe \
        + c["vocab_size"] * d
    return int(6 * params + 3 * n_attn * h * 2 * hd * seq
               + 3 * n_gdn * 6 * hv * dk * dv)
