"""Kimi-Linear shaped models: ``ray_tpu.models.KimiLinear`` (Kimi Delta
Attention layers through the chunked scan and latent-attention layers
without positions in the latent flash kernels, three to one, in one stack
of unlike layers walked as runs of like layers; a leading dense gated MLP,
then shared + routed experts of which the chip holds a share; untied head,
the vocabulary a slice). The configuration's ``model`` dict names a
``KimiLinearConfig`` constructor under ``preset``; every other key is a
keyword of it. Plain reference: ``reference/kimi_linear.py``."""

# the jax.named_scope names of models/kimi_linear.py, models/deepseek_v3.py
# (``attn``: the latent layer) and ops/expert_layer.py. ``mixer`` is a KDA
# layer's norm, its q/k/v/gate/beta projections, the output gate, norm and
# W_o; ``conv`` its three convolutions; ``scan`` the gates' softplus, the l2
# norms and everything of ops/kda_scan.py
SCOPES = ("embed", "attn", "mixer", "conv", "scan", "mlp", "router",
          "experts", "shared_expert", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import KimiLinear, KimiLinearConfig

    kw = dict(model)
    kw.pop("family")
    return KimiLinear(
        getattr(KimiLinearConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a KDA
    layer's q, k, v, gate, output-gate, beta and output projections; the
    latent projections of the LATENT layers only; the dense MLP of the
    leading layers; the router, the shared expert and
    ``num_experts_per_token`` x ``experts_held`` / ``num_experts`` routed
    experts in expectation, which is what the held share sees under a
    level router; the head's ``vocab_size`` rows; the embedding is a
    lookup, the convolutions, norms and gates' vectors are no matmuls),
    plus 3 x the forward's causal score and value products of a latent
    layer, 2 (qk_head_dim + v_head_dim) S / 2 a head, plus 3 x the
    recurrence of a KDA layer, 6 H d_k d_v a token: one multiply-add an
    element of the state for each of the read through k, the update and
    the read through q (the decay is a multiply of the same element and
    rides with the read), whatever chunking computes them. What a chunked
    scan adds (its local triangular products and solve) and what flash
    recomputes are the kernels' work, NOT counted here
    (``kda_scan_roofline`` counts the former)."""
    d = c["hidden_size"]
    kh, kd, r = c["kda_num_heads"], c["kda_head_dim"], c["kda_gate_rank"]
    kw = kh * kd
    kda = 3 * d * kw + 2 * (d * r + r * kw) + d * kh + kw * d
    h = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    mla = d * h * qk + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"]) \
        + h * c["v_head_dim"] * d
    f = c["moe_intermediate_size"]
    routed = c["num_experts_per_token"] * c["experts_held"] / c["num_experts"]
    moe = d * c["num_experts"] + 3 * d * f * c["num_shared_experts"] \
        + routed * 3 * d * f
    kinds = c["layer_types"]
    n_kda, n_mla, k = kinds.count("kda"), kinds.count("mla"), \
        c["first_k_dense_replace"]
    params = n_kda * kda + n_mla * mla + k * 3 * d * c["intermediate_size"] \
        + (len(kinds) - k) * moe + c["vocab_size"] * d
    return int(6 * params + 3 * n_mla * h * (qk + c["v_head_dim"]) * seq
               + 3 * n_kda * 6 * kh * kd * kd)
