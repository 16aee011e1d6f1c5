"""Test fixture: a family whose training objective is NOT the next-token
loss, entered as files. The program's ``ray_tpu.models.GPT`` (a class the
program already has, wrapped here and not edited) trained to DENOISE: the
ids of a row are cut into blocks, each block gets a noise level t, each
position of the block is replaced by the mask id with probability t, the
model reads the noised row and the loss is the cross entropy of the TRUE
id at each masked position, weighted 1/t, summed and divided by B S.

The harness hands an objective the parameters and the batch and nothing
else, so the noise is a pure function of the row's ids and of the
constants of the configuration's ``model.noise`` (written out under
``assumed`` there): ``_noise`` below, which ``reference/fx_denoise.py``
states again in its own words. What ``benchmark/lib/spec.load_family``
and ``objective_of`` ask of a family file."""
from types import SimpleNamespace

from benchmark.lib.flops import train_flops_per_token  # noqa: F401  one pass over S: GPT's count

# the jax.named_scope names of models/gpt.py
SCOPES = ("embed", "attn", "mlp", "lm_head", "loss")


class Denoiser:
    """The program's GPT with the objective's constants beside its sizes:
    what ``train_loop`` calls on a model that states its own objective
    (no ``loss``), and ``config`` as ``reference/fx_denoise.model_kwargs``
    reads it."""

    def __init__(self, gpt, noise: dict):
        self.gpt = gpt
        c = gpt.config
        self.config = SimpleNamespace(
            vocab_size=c.vocab_size, padded_vocab=c.padded_vocab,
            n_head=c.n_head, **noise)
        self.init, self.num_params = gpt.init, gpt.num_params
        self.param_shardings = gpt.param_shardings


def build(model: dict):
    from ray_tpu.models import GPT, GPTConfig

    kw = dict(model)
    kw.pop("family")
    noise = kw.pop("noise")
    return Denoiser(GPT(getattr(GPTConfig, kw.pop("preset", "tiny"))(**kw)),
                    noise)


def _noise(tokens, block: int, levels: int):
    """-> (masked [B, S] bool, t [B, S] float32), from the ids alone. A
    row's key is the sum of (id + 1)(2 i + 1) over its positions i; block
    j of the row has t = (1 + (key + 7 j) mod levels) / levels; position
    i is masked where a 24-bit hash of (key, i) is under t. All in uint32,
    which wraps."""
    import jax.numpy as jnp

    u32 = jnp.uint32
    pos = jnp.arange(tokens.shape[1], dtype=u32)
    key = jnp.sum((tokens.astype(u32) + u32(1)) * (u32(2) * pos + u32(1)),
                  axis=1, keepdims=True, dtype=u32)
    t = (u32(1) + (key + u32(7) * (pos // u32(block))) % u32(levels)
         ).astype(jnp.float32) / levels
    h = (key ^ (pos * u32(0x9E3779B1))) * u32(0x85EBCA6B)
    h = (h ^ (h >> u32(13))) * u32(0xC2B2AE35)
    h = h ^ (h >> u32(16))
    return (h >> u32(8)).astype(jnp.float32) / float(1 << 24) < t, t


def objective(model):
    """fn(params, tokens) -> the denoising loss of the batch."""
    import jax
    import jax.numpy as jnp

    c = model.config

    def loss(params, tokens):
        masked, t = _noise(tokens, c.block, c.levels)
        logits = model.gpt.apply(params,
                                 jnp.where(masked, c.mask_id, tokens))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(masked, nll / t, 0.0)) / tokens.size

    return loss
