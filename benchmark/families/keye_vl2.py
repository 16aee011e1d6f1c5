"""Keye-VL-2.0 shaped language models: ``ray_tpu.models.KeyeVL2`` (a stack
of like layers, each grouped-query attention over the keys a learned
indexer selects for each query, 16 index heads of 64 on ONE key of 64 and
the top ``topk`` scores a query, before a softmax-routed expert layer
without a shared expert, of whose routed experts the chip holds a share;
positions of three components; untied head, the vocabulary a slice). The
configuration's ``model`` dict names a ``KeyeVL2Config`` constructor under
``preset``; every other key is a keyword of it. Plain reference:
``reference/keye_vl2.py``."""

# the jax.named_scope names of models/keye_vl2.py, ops/sparse_attention.py
# and ops/expert_layer.py. ``indexer`` is the three index projections, the
# index key's LayerNorm, the rotation and the index scores; ``select`` the
# exact top ``topk`` a query and the one-byte mask it leaves as; ``attn``
# the rest of the mixer (norm, q, k, v, their norms and rotation, the
# kernels over the selection, the output projection); ``router`` the expert
# layer's norm, scores, top-k, the sort and the rows' gathers
SCOPES = ("embed", "attn", "indexer", "select", "router", "experts",
          "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import KeyeVL2, KeyeVL2Config

    kw = dict(model)
    kw.pop("family")
    return KeyeVL2(getattr(KeyeVL2Config, kw.pop("preset", "tiny"))(**kw))


def objective(model):
    """fn(params, tokens) -> the next-token loss plus the configuration's
    ``router_aux_coef`` times the routers' load-balancing terms
    (``KeyeVL2.loss``; the other half is ``reference/keye_vl2.losses``)."""
    import jax.numpy as jnp

    def loss(params, tokens):
        return model.loss(params, tokens, jnp.roll(tokens, -1, axis=1))
    return loss


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a row of ``seq`` tokens selects:
    sum_t min(t + 1, topk)."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (the
    attention's four projections; the router; ``num_experts_per_tok`` x
    ``experts_held`` / ``num_experts`` routed experts in expectation, which
    is what the held share sees under a level router; the head's
    ``vocab_size`` rows; the embedding is a lookup, norms are no matmuls);
    of the INDEXER, which has no backward, 2 x its three projections and
    1 x its index scores, 2 Hi Di a causal pair; plus 3 x the forward's
    score and value products of the main attention over the SELECTED pairs,
    sum_t min(t + 1, topk), not S^2 / 2: 2 (head_dim + head_dim) a pair and
    query head. What the kernels work beyond the selection (every pair of a
    causal block) and what they make again is their work, NOT counted here
    (``sparse_attention_roofline`` reads the distance)."""
    d = c["hidden_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    hi, di = c["indexer_num_heads"], c["indexer_head_dim"]
    attn = 2 * d * h * hd + 2 * d * kv * hd
    routed = c["num_experts_per_tok"] * c["experts_held"] / c["num_experts"]
    moe = d * c["num_experts"] + routed * 3 * d * c["moe_intermediate_size"]
    layers = c["num_hidden_layers"]
    index_proj = d * hi * di + d * di + d * hi
    causal = seq * (seq + 1) // 2
    return int(6 * (layers * (attn + moe) + c["vocab_size"] * d)
               + layers * (2 * index_proj + 2 * hi * di * causal / seq
                           + 3 * h * 4 * hd
                           * selected_pairs(seq, c["topk"]) / seq))
