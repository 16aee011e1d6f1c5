"""Nemotron-H shaped models: ``ray_tpu.models.NemotronH`` (layers that are
ONE sublayer each, a Mamba-2 state-space mixer through the chunked scan
kernels, grouped-query attention without positions in the streamed flash
kernels, or an expert layer whose sigmoid-routed squared-ReLU experts work
in a latent beside a shared expert on the model width; walked as runs of
like periods of two kinds; of every layer the chip may hold a share: some
routed experts, some Mamba-2 groups with their heads, some query heads with
the key/value heads they read; untied head, the vocabulary a slice). The
configuration's ``model`` dict names a ``NemotronHConfig`` constructor under
``preset``; every other key is a keyword of it. Plain reference:
``reference/nemotron_h.py``."""

# the jax.named_scope names of models/nemotron_h.py and
# ops/expert_layer.py. ``mixer`` is a Mamba-2 layer's norm, in- and
# out-projection and splits, ``conv`` its causal convolution, ``scan`` dt,
# the scan kernels, D x and the grouped gated norm; ``attn`` the whole
# attention layer; ``router`` the expert layer's norm, scores, top-k, the
# sort and the rows' gathers; ``latent_proj`` the projections into and out
# of the experts' latent
SCOPES = ("embed", "attn", "mixer", "conv", "scan", "router", "experts",
          "shared_expert", "latent_proj", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import NemotronH, NemotronHConfig

    kw = dict(model)
    kw.pop("family")
    return NemotronH(
        getattr(NemotronHConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes, i.e. of THIS chip's share: 6 x the matmul parameters a token
    is multiplied by here (a Mamba-2 layer's in- and out-projection at the
    ``mamba_n_heads`` heads and ``mamba_n_groups`` groups held; the
    attention layer's four projections at the query and key/value heads
    held; an expert layer's router, both latent projections, the shared
    expert and ``num_experts_per_tok`` x ``experts_held`` /
    ``n_routed_experts`` routed experts in expectation, which is what the
    held share sees under a level router; the head's ``vocab_size`` rows;
    the embedding is a lookup, the convolution, norms and the vectors of
    the scan are no matmuls), plus 3 x the forward's causal score and value
    products of an attention layer, 2 x heads x 2 head_dim x S / 2 a token,
    plus 3 x the recurrence of a Mamba-2 layer, 4 H P N a token: one
    multiply-add an element of the state to update it and one to read it,
    whatever chunking computes them. What a chunked scan adds and what
    flash recomputes are the kernels' work, NOT counted here
    (``ssd_scan_roofline`` and ``gqa_attention_roofline`` count them)."""
    d = c["hidden_size"]
    h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    d_inner = h * p
    mamba = d * (2 * d_inner + 2 * c["mamba_n_groups"] * n + h) + d_inner * d
    hd = c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attention = d * hd * (2 * heads + 2 * kv)
    lat = c["moe_latent_size"]
    routed = c["num_experts_per_tok"] * c["experts_held"] \
        / c["n_routed_experts"]
    moe = d * c["n_routed_experts"] + 2 * d * lat \
        + 2 * d * c["moe_shared_expert_intermediate_size"] \
        + routed * 2 * lat * c["moe_intermediate_size"]
    kinds = c["layer_types"]
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    params = n_mamba * mamba + n_attn * attention \
        + kinds.count("moe") * moe + c["vocab_size"] * d
    return int(6 * params + 3 * n_attn * 2 * heads * 2 * hd * seq // 2
               + 3 * n_mamba * 4 * h * p * n)
