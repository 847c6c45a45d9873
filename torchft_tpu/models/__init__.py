"""Model zoo for the TPU-native fault-tolerant trainer.

The flagship is a Llama-3-style decoder (``torchft_tpu.models.llama``) used
by the HSDP benchmark config (BASELINE.json config #4). The reference drives
external models (torchtitan Llama, CIFAR CNN in train_ddp.py:116-146); here
the models are in-repo so the framework is standalone.
"""

from torchft_tpu.models.resnet import (  # noqa: F401
    ResNet,
    resnet_tiny,
    resnet50,
    resnet101,
)
from torchft_tpu.models.gated_delta import GatedDeltaConfig, KDAConfig  # noqa: F401
from torchft_tpu.models.mamba2 import Mamba2Config  # noqa: F401
from torchft_tpu.models.mla import MLAConfig  # noqa: F401
from torchft_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    Transformer,
    joyai_flash_debug,
    joyai_llm_flash,
    keye_vl2_30b_a3b,
    keye_vl2_debug,
    llama3_8b,
    llama_debug,
    llama_moe_debug,
    lfm2_8b_a1b,
    lfm2_moe_debug,
    llama_small,
    nemotron3_nano,
    nemotron_h_debug,
    olmo_hybrid_7b,
    olmo_hybrid_debug,
    olmoe_1b_7b,
    ouro_2_6b,
    ouro_debug,
    sdar_30b_a3b,
    sdar_moe_debug,
    smallthinker_21b,
    smallthinker_debug,
    solar_open2_250b,
    solar_open2_debug,
    trinity_debug,
    trinity_mini,
)

# What ``train_hsdp.py --model`` names: each architecture's small preset, the 125M
# ``small``, and ``olmoe`` at its published 6.9B: for a group's mesh of chips, not one.
PRESETS = {
    "debug": llama_debug,
    "small": llama_small,
    "moe": llama_moe_debug,
    "olmoe": olmoe_1b_7b,
    "nemotron_h": nemotron_h_debug,
    "lfm2_moe": lfm2_moe_debug,
    "sdar_moe": sdar_moe_debug,
    "joyai_flash": joyai_flash_debug,
    "olmo_hybrid": olmo_hybrid_debug,
    "solar_open2_250b": solar_open2_250b,
    "solar_open2_debug": solar_open2_debug,
    "smallthinker_21b": smallthinker_21b,
    "smallthinker_debug": smallthinker_debug,
    "trinity_mini": trinity_mini,
    "keye_vl2_30b_a3b": keye_vl2_30b_a3b,
    "keye_vl2_debug": keye_vl2_debug,
    "trinity_debug": trinity_debug,
    "ouro_2_6b": ouro_2_6b,
    "ouro_debug": ouro_debug,
}
