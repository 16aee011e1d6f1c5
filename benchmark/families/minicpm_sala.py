"""MiniCPM-SALA shaped models: ``ray_tpu.models.MiniCPMSALA`` (Lightning
linear-attention layers, every head its own q and k under a constant decay,
rotated, through the chunked scan's kernel pair, and InfLLM-v2 attention
layers over the blocks of keys a query's key/value group selects by its own
heads' scores on mean-pooled keys, through the masked flash kernels, three
to one, in one stack of unlike layers walked as runs of like layers;
pre-norm, muP scales, sigmoid output gates; a gated MLP after every mixer;
a share of both mixers' heads, of the key/value groups and of the MLP's
hidden units held; untied head, the vocabulary a slice, the head and loss
walked in token chunks). The configuration's ``model`` dict names a
``MiniCPMSALAConfig`` constructor under ``preset``; every other key is a
keyword of it. Plain reference: ``reference/minicpm_sala.py``."""

# the jax.named_scope names of models/minicpm_sala.py and
# ops/sparse_attention.py, with the meanings the Keye, Granite and Olmo
# families give them. ``attn`` is the attention mixer but for the selection
# (norm, q, k, v, gate, their norms, the masked kernels, the gate and the
# output projection); ``indexer`` what scores the pairs (the pooled keys and
# each head's softmax over them, summed over the group); ``select`` the
# max-pool to blocks, the exact top 96 a query and the bytes they leave as;
# ``mixer`` a Lightning layer's five projections, norms, rotation, gate and
# W_o; ``scan`` everything of ops/lightning_attention.py (the decays'
# tables, the kernels); ``mlp`` the gated MLP with its norm
SCOPES = ("embed", "attn", "indexer", "select", "mixer", "scan", "mlp",
          "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import MiniCPMSALA, MiniCPMSALAConfig

    kw = dict(model)
    kw.pop("family")
    return MiniCPMSALA(
        getattr(MiniCPMSALAConfig, kw.pop("preset", "tiny"))(**kw))


def selected_pairs(seq: int, c: dict) -> int:
    """(query, key) pairs a row of ``seq`` tokens attends over in ONE
    ``minicpm4`` layer: every causal pair up to ``dense_len`` (or while a
    query sees no more than ``sparse_blocks`` blocks); else per query
    ``sparse_blocks`` blocks of ``sparse_block`` keys, its own among them
    and of that the causal part."""
    block, blocks = c["sparse_block"], c["sparse_blocks"]
    if seq <= c["dense_len"] or seq <= block * blocks:
        return seq * (seq + 1) // 2
    total = 0
    for own in range(-(-seq // block)):
        rows = min(block, seq - own * block)
        total += rows * min(own, blocks - 1) * block + rows * (rows + 1) // 2
    return total


def pooled_pairs(seq: int, c: dict) -> int:
    """(query, pooled key) pairs the selection scores in one ``minicpm4``
    layer: the windows that end at or before each query; none on a row that
    does not select."""
    size, stride = c["sparse_pool"]
    if selected_pairs(seq, c) == seq * (seq + 1) // 2:
        return 0
    return sum((t - size + 1) // stride + 1 for t in range(size - 1, seq))


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward + backward operations per token of the cut that ``sizes``
    describes: 6 x the matmul parameters a token is multiplied by (a
    Lightning layer's five projections and the ``minicpm4`` layer's five
    over the heads HELD; every layer's gated MLP over the hidden units
    HELD; the head's ``vocab_size`` rows; the embedding is a lookup, norms
    and gates' vectors are no matmuls), plus 3 x the forward's score and
    value products of the ``minicpm4`` layers over the SELECTED pairs, 2
    (head_dim + head_dim) a pair and query head held, not S^2 / 2; of the
    SELECTION, which has no backward, 1 x its pooled scores, 2 head_dim a
    (query, pooled key) pair and head; plus 3 x the recurrence of a
    Lightning layer, 4 H d^2 a token: one multiply-add an element of the
    state for the update and for the read. What a chunked scan adds, what
    the masked kernels work beyond the selection (every pair of a causal
    block) and what they make again is the kernels' work, NOT counted here
    (``lightning_scan_roofline`` and ``block_sparse_attention_roofline``
    read the distance)."""
    d, f = c["hidden_size"], c["intermediate_held"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    lh, ld = c["lightning_nh"], c["lightning_head_dim"]
    attn = 3 * d * h * hd + 2 * d * kv * hd
    light = 5 * d * lh * ld
    kinds = c["mixer_types"]
    n_attn = kinds.count("minicpm4")
    n_light = len(kinds) - n_attn
    params = n_attn * attn + n_light * light + len(kinds) * 3 * d * f \
        + c["vocab_size"] * d
    return int(6 * params
               + n_attn * (3 * h * 4 * hd * selected_pairs(seq, c) / seq
                           + h * 2 * hd * pooled_pairs(seq, c) / seq)
               + 3 * n_light * 4 * lh * ld * ld)
