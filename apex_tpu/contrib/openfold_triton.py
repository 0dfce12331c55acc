"""``apex.contrib.openfold_triton`` import-surface alias (reference:
contrib/openfold_triton — AlphaFold-shape-specialized Triton kernels:
LayerNormSmallShapeOptImpl, small fused MHA, FusedAdamSWA).

TPU mapping:

- ``FusedAdamSWA`` is a full port (``apex_tpu.optimizers.fused_adam_swa``).
- ``LayerNormSmallShapeOptImpl`` and the small-MHA tier map onto the
  generic Pallas/XLA kernels; whether those need a small-shape-tuned path
  at the openfold evoformer shapes (LN hidden 64/128, MHA seq<=256 head_dim
  8/16) is not measured on the chip: no benchmark cell runs such shapes.
"""

from apex_tpu.normalization import FusedLayerNorm as LayerNormSmallShapeOptImpl
from apex_tpu.ops.attention import flash_attention as AttnTri
from apex_tpu.optimizers.fused_adam_swa import FusedAdamSWA

__all__ = ["FusedAdamSWA", "LayerNormSmallShapeOptImpl", "AttnTri"]
