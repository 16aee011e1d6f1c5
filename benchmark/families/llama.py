"""Llama shaped models: ``ray_tpu.models.Llama`` (RMSNorm, rotary
positions, grouped-query attention, gated SiLU feed-forward, untied head,
one scanned stack). The ``model`` dict is the form the program's
``build_model`` takes: ``preset`` names a ``LlamaConfig`` constructor,
every other key is a keyword of it. No cell trains this family yet and its
plain reference left the tree with its cell (PERF.md section 7): it
returns as ``reference/llama.py`` with the first configuration that names
it."""

# the jax.named_scope names of models/llama.py
SCOPES = ("embed", "attn", "mlp", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import Llama, LlamaConfig

    kw = dict(model)
    kw.pop("family")
    return Llama(getattr(LlamaConfig, kw.pop("preset", "tiny"))(**kw))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward matmul operations per token: 6 x the parameters
    a token is multiplied by (q and o projections D x H hd, k and v
    D x KV hd, three D x F feed-forward matrices a layer, the V x D head;
    the embedding is a lookup and the norms' gains are not matmuls) plus
    causal attention 6 L S H hd, at the full head count (k and v are
    broadcast to the query heads before QK^T and PV). Recomputation under
    remat is NOT counted."""
    d, layers, heads = c["d_model"], c["n_layer"], c["n_head"]
    hd = d // heads
    block = 2 * d * heads * hd + 2 * d * c["n_kv_head"] * hd \
        + 3 * d * c["d_ff"]
    return 6 * (layers * block + c["vocab_size"] * d) \
        + 6 * layers * heads * hd * seq
