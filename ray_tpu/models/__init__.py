"""ray_tpu.models — model families used by the Train/Serve/RLlib layers
and the benchmark configs (BASELINE.md north stars: GPT-2, ResNet-18/CIFAR,
ViT-B/16, Llama-2-7B, PPO nets).

Design: plain-pytree functional models (init/apply pairs), parameters
stacked over layers and iterated with lax.scan (one compiled block instead
of L unrolled ones), logical-axis annotations consumed by
ray_tpu.parallel.mesh.AxisRules for dp/fsdp/tp/sp sharding, bf16 compute
with f32 master dtypes chosen per-config.
"""
from .gpt import GPT, GPTConfig
from .llama import Llama, LlamaConfig
from .resnet import ResNet, ResNetConfig
from .vit import ViT, ViTConfig
from .mlp import MLP, MLPConfig
from .moe import MoE, MoEConfig
from .deepseek_v3 import DeepseekV3, DeepseekV3Config
from .granite_hybrid import GraniteHybrid, GraniteHybridConfig
from .sambay import SambaY, SambaYConfig
from .kimi_linear import KimiLinear, KimiLinearConfig
from .qwen3_next import Qwen3Next, Qwen3NextConfig
from .nemotron_h import NemotronH, NemotronHConfig
from .keye_vl2 import KeyeVL2, KeyeVL2Config
from .lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from .olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from .minicpm_sala import MiniCPMSALA, MiniCPMSALAConfig

__all__ = [
    "GPT", "GPTConfig", "Llama", "LlamaConfig", "ResNet", "ResNetConfig",
    "ViT", "ViTConfig", "MLP", "MLPConfig", "MoE", "MoEConfig",
    "DeepseekV3", "DeepseekV3Config", "GraniteHybrid", "GraniteHybridConfig",
    "SambaY", "SambaYConfig", "KimiLinear", "KimiLinearConfig",
    "Qwen3Next", "Qwen3NextConfig", "NemotronH", "NemotronHConfig",
    "KeyeVL2", "KeyeVL2Config", "Lfm2Moe", "Lfm2MoeConfig",
    "OlmoHybrid", "OlmoHybridConfig", "MiniCPMSALA", "MiniCPMSALAConfig",
]
