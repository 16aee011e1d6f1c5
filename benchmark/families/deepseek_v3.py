"""DeepSeek-V3 shaped models: ``ray_tpu.models.DeepseekV3`` (multi-head
latent attention in the latent flash kernels, leading dense layers, then
layers of shared + routed experts of which the chip holds a share, untied
head, the vocabulary a slice). The configuration's ``model`` dict names a
``DeepseekV3Config`` constructor under ``preset``; every other key is a
keyword of it. Plain reference: ``reference/deepseek_v3.py``."""

# the jax.named_scope names of models/deepseek_v3.py and ops/expert_layer.py
SCOPES = ("embed", "attn", "mlp", "router", "experts", "shared_expert",
          "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import DeepseekV3, DeepseekV3Config

    kw = dict(model)
    kw.pop("family")
    return DeepseekV3(getattr(DeepseekV3Config, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward matmul operations per token of the cut that
    ``sizes`` describes: 6 x the parameters a token is multiplied by (the
    attention projections, the dense MLP of the leading layers, the
    router, the shared experts, ``num_experts_per_tok`` x ``experts_held``
    / ``n_routed_experts`` routed experts in expectation, which is what
    the held share sees under a level router, and the head's
    ``vocab_size`` rows; the embedding is a lookup, the norms' gains are
    no matmuls) plus the causal score and value products, 3 x the
    forward's 2 (qk_head_dim + v_head_dim) S / 2 a head a layer. The
    score that the flash backward computes again is recomputation, NOT
    counted here (``mla_attention_roofline`` counts it: it is the
    kernel's work)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attn = d * h * qk + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d
    f = c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] * c["experts_held"] \
        / c["n_routed_experts"]
    moe = d * c["n_routed_experts"] + 3 * d * f * c["n_shared_experts"] \
        + routed * 3 * d * f
    k, layers = c["first_k_dense_replace"], c["num_hidden_layers"]
    params = layers * attn + k * 3 * d * c["intermediate_size"] \
        + (layers - k) * moe + c["vocab_size"] * d
    return int(6 * params + 3 * layers * h * (qk + c["v_head_dim"]) * seq)
