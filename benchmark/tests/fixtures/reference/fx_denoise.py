"""Test fixture: the plain statement of a masked-denoising objective on
the GPT-2 forward (``reference/gpt.py``). ``losses`` is what a reference
holds when its family's objective is not the next-token loss: the
per-position terms, mask and weight applied, whose mean is the loss.

The noise, stated here independently of the family file: a row's key k is
the sum over its positions i of (id_i + 1)(2 i + 1), modulo 2^32. Block j
(``block`` positions) has the noise level t_j = (1 + (k + 7 j) mod
``levels``) / ``levels``. Position i is replaced by ``mask_id`` where
mix(k, i) / 2^24 < t_j, with mix the 24 high bits of the 32-bit finaliser
below. A masked position's term is the cross entropy of its TRUE id at
that position, over t_j; every other term is zero. Reads the parameter
dict of ``ray_tpu.models.gpt.GPT`` and nothing else of the program."""
import jax
import jax.numpy as jnp

from benchmark.reference.gpt import head, hidden, num_params  # noqa: F401


def noise(tokens, block: int, levels: int):
    """tokens [b, S] -> (masked [b, S] bool, t [b, S] float32), by the
    rule of the module's docstring; uint32 arithmetic wraps at 2^32."""
    i = jnp.arange(tokens.shape[1], dtype=jnp.uint32)[None]
    k = ((tokens.astype(jnp.uint32) + 1) * (2 * i + 1)).sum(
        1, dtype=jnp.uint32)[:, None]
    t = (1 + (k + 7 * (i // block)) % levels) / jnp.float32(levels)
    mix = (k ^ (i * jnp.uint32(0x9E3779B1))) * jnp.uint32(0x85EBCA6B)
    mix = (mix ^ (mix >> 13)) * jnp.uint32(0xC2B2AE35)
    mix = (mix ^ (mix >> 16)) >> 8
    return mix / jnp.float32(1 << 24) < t, t


def losses(params, tokens, dtype, n_head, block, levels, mask_id):
    """tokens [b, S] -> float32 [b, S]: the objective's terms, whose mean
    over all entries is the loss."""
    masked, t = noise(tokens, block, levels)
    h = hidden(params, jnp.where(masked, mask_id, tokens), n_head=n_head,
               dtype=dtype)
    logits = head(params, h, dtype).astype(jnp.float32)
    nll = jax.scipy.special.logsumexp(logits, -1) \
        - jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return jnp.where(masked, nll / t, 0.0)


def model_kwargs(model_config) -> dict:
    return {name: getattr(model_config, name)
            for name in ("n_head", "block", "levels", "mask_id")}
