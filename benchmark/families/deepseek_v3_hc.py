"""DeepSeek-V3 shaped models whose residual is several streams mixed by
manifold-constrained hyper-connections: ``ray_tpu.models.DeepseekV3`` with
``hc_mult`` > 1 (``ops/hyper_connection.py`` around every attention and
expert sublayer), a query bottleneck (``q_lora_rank``) before the latent
flash kernels and YaRN positions; leading dense layers, then layers of
shared + routed experts of which the chip holds a share, untied head, the
vocabulary a slice. The configuration's ``model`` dict names a
``DeepseekV3Config`` constructor under ``preset``; every other key is a
keyword of it. Plain reference: ``reference/deepseek_v3_hc.py``."""

# the jax.named_scope names of models/deepseek_v3.py and
# ops/expert_layer.py, and ``mhc``: everything ops/hyper_connection.py does
# (coefficients, Sinkhorn, the two mixings, the streams' sum at the end)
SCOPES = ("embed", "attn", "mlp", "router", "experts", "shared_expert",
          "mhc", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import DeepseekV3, DeepseekV3Config

    kw = dict(model)
    kw.pop("family")
    return DeepseekV3(getattr(DeepseekV3Config, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward matmul operations per token of the cut that
    ``sizes`` describes, counted as ``families/deepseek_v3.py`` counts
    them: 6 x the parameters a token is multiplied by (attention with the
    query bottleneck: W_qa, W_qb, W_kva, W_kvb, W_o; the two Φ of a layer,
    hc_mult·d x (2 hc_mult + hc_mult²) each; the dense MLP of the leading
    layers; the router, the shared experts and ``num_experts_per_tok`` x
    ``experts_held`` / ``n_routed_experts`` routed experts in expectation;
    the head's ``vocab_size`` rows) plus the causal score and value
    products, 3 x the forward's 2 (qk_head_dim + v_head_dim) S / 2 a head
    a layer. Not counted: recomputation (the flash backward's score, the
    rematerialised layers), and the mixings' own multiply-adds, (2 n + n²)
    d a sublayer a token, which are no matmuls and 0.2 % of these."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    q, n = c["q_lora_rank"], c["hc_mult"]
    attn = d * q + q * h * qk + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d
    mixing = 2 * n * d * (2 * n + n * n)
    f = c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] * c["experts_held"] \
        / c["n_routed_experts"]
    moe = d * c["n_routed_experts"] + 3 * d * f * c["n_shared_experts"] \
        + routed * 3 * d * f
    k, layers = c["first_k_dense_replace"], c["num_hidden_layers"]
    params = layers * (attn + mixing) + k * 3 * d * c["intermediate_size"] \
        + (layers - k) * moe + c["vocab_size"] * d
    return int(6 * params + 3 * layers * h * (qk + c["v_head_dim"]) * seq)
