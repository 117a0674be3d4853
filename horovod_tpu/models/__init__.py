"""Model zoo for benchmarks and examples.

The reference ships its benchmark models via ``tf.keras.applications`` /
``torchvision.models`` in ``examples/*_synthetic_benchmark.py``; this
package provides the TPU-native (flax, NHWC, bf16-friendly) equivalents
used by ``examples/`` and ``bench.py``.
"""

from horovod_tpu.models.hybrid import (
    HybridConfig,
    HybridLM,
    expert_load,
    hybrid_lm_loss,
)
from horovod_tpu.models.moe import (
    MoEConfig,
    MoETransformerLM,
    moe_aux_loss,
)
from horovod_tpu.models.resnet import ResNet50, ResNet101, ResNet152
from horovod_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    lm_loss,
)
from horovod_tpu.models.vit import (
    ViT_B16,
    ViT_S16,
    ViTConfig,
    VisionTransformer,
)

__all__ = ["ResNet50", "ResNet101", "ResNet152",
           "TransformerLM", "TransformerConfig", "lm_loss",
           "MoETransformerLM", "MoEConfig", "moe_aux_loss",
           "HybridLM", "HybridConfig", "hybrid_lm_loss", "expert_load",
           "VisionTransformer", "ViTConfig", "ViT_S16", "ViT_B16"]
