"""LFM2-MoE shaped models: ``ray_tpu.models.Lfm2Moe`` (a stack whose layers
mix tokens by a double-gated short convolution, through the kernel pair of
``ops/short_conv.py``, or by grouped-query attention with a per-head norm on
q and k and a full rotation in the streamed flash kernels, in a published
order; a dense gated MLP in the leading layers, then sigmoid-routed experts
with a selection bias and no shared expert, of whose routed experts the
chip holds a share; walked as runs of like layers; the head tied to the
embedding, the vocabulary a slice). The configuration's ``model`` dict
names an ``Lfm2MoeConfig`` constructor under ``preset``; every other key is
a keyword of it. Plain reference: ``reference/lfm2_moe.py``."""

# the jax.named_scope names of models/lfm2_moe.py and ops/expert_layer.py,
# with the meanings families/granite_hybrid.py gives ``mixer`` and ``conv``:
# ``mixer`` is a conv operator's norm, in- and out-projection and residual,
# ``conv`` its two gates and the taps (the kernel pair); ``attn`` the whole
# attention operator; ``mlp`` the dense layer's gated MLP with its norm;
# ``router`` an expert layer's norm, scores, top-k, balancing term, the
# sort and the rows' gathers
SCOPES = ("embed", "attn", "mixer", "conv", "mlp", "router", "experts",
          "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import Lfm2Moe, Lfm2MoeConfig

    kw = dict(model)
    kw.pop("family")
    return Lfm2Moe(getattr(Lfm2MoeConfig, kw.pop("preset", "tiny"))(**kw))


def objective(model):
    """fn(params, tokens) -> the next-token loss plus the configuration's
    ``router_aux_coef`` times the expert layers' balancing terms
    (``Lfm2Moe.loss``; the other half is ``reference/lfm2_moe.losses``)."""
    import jax.numpy as jnp

    def loss(params, tokens):
        return model.loss(params, tokens, jnp.roll(tokens, -1, axis=1))
    return loss


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a conv
    operator's in- and out-projection; the attention operator's four
    projections; a dense layer's gated MLP; an expert layer's router and
    ``num_experts_per_tok`` x ``experts_held`` / ``num_experts`` routed
    experts in expectation, which is what the held share sees under a
    level router; the head's ``vocab_size`` rows; the embedding is a
    lookup; the taps, the gates and the norms are no matmuls), plus 3 x
    the forward's causal score and value products of an attention layer,
    2 x heads x 2 head_dim x S / 2 a token. What flash recomputes is the
    kernels' work, NOT counted here (``gqa_attention_roofline`` counts
    it)."""
    d, hd = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    conv = d * 3 * d + d * d
    attention = d * hd * (2 * heads + 2 * kv)
    routed = c["num_experts_per_tok"] * c["experts_held"] / c["num_experts"]
    moe = d * c["num_experts"] + routed * 3 * d * c["moe_intermediate_size"]
    mlp = 3 * d * c["intermediate_size"]
    kinds = c["layer_types"]
    n_attn = kinds.count("attention")
    dense = min(c["num_dense_layers"], len(kinds))
    params = n_attn * attention + (len(kinds) - n_attn) * conv \
        + dense * mlp + (len(kinds) - dense) * moe + c["vocab_size"] * d
    return int(6 * params + 3 * n_attn * 2 * heads * 2 * hd * seq // 2)
