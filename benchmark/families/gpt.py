"""GPT-2 shaped models: ``ray_tpu.models.GPT`` (pre-LayerNorm blocks in
one scanned stack, learned positions, head tied to the embedding). The
configuration's ``model`` dict is the form the program's ``build_model``
takes: ``preset`` names a ``GPTConfig`` constructor, every other key is a
keyword of it. Plain reference: ``reference/gpt.py``."""
from benchmark.lib.flops import train_flops_per_token  # noqa: F401  6 N + 6 L D S

# the jax.named_scope names of models/gpt.py
SCOPES = ("embed", "attn", "mlp", "lm_head", "loss")


def build(model: dict):
    from ray_tpu.models import GPT, GPTConfig

    kw = dict(model)
    kw.pop("family")
    return GPT(getattr(GPTConfig, kw.pop("preset", "tiny"))(**kw))
