"""Test fixture: a third family, entered as files. The program's sparse
mixture-of-experts transformer (``ray_tpu.models.MoE``: GPT-2 shaped
attention, a router and ``num_experts`` GELU feed-forwards a layer of
which a token takes ``top_k``), a model class the harness has never been
told of. What ``benchmark/lib/spec.load_family`` asks of a family file."""

# the scopes a step of this family would be split by; models/moe.py names
# none today, so a reader of one finds nothing to read
SCOPES = ("embed", "attn", "moe", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import MoE, MoEConfig

    kw = dict(model)
    kw.pop("family")
    return MoE(getattr(MoEConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward matmul operations per token: 6 x the parameters a
    token is multiplied by (qkv and output projections 4 D^2, the router
    D E, ``top_k`` of the E experts at 2 D F each, a layer; the tied V x D
    head) plus causal attention 6 L D S. A token does NOT touch every
    parameter: 6 x ``num_params`` would count all E experts."""
    d = c["d_model"]
    block = 4 * d * d + d * c["num_experts"] + c["top_k"] * 2 * d * c["d_ff"]
    return 6 * (c["n_layer"] * block + c["vocab_size"] * d) \
        + 6 * c["n_layer"] * d * seq
