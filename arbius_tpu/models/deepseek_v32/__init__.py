"""deepseek_v32 — DeepSeek-V3.2-Exp on the text-serving path: latent
attention over a 576-wide cache, a lightning indexer's selection, group-
limited routing (docs/text-serving.md)."""
from arbius_tpu.models.deepseek_v32.model import DeepSeekV32Config
from arbius_tpu.models.deepseek_v32.pipeline import (
    MESH_LAYOUTS,
    DeepSeekV32Pipeline,
)

__all__ = ["MESH_LAYOUTS", "DeepSeekV32Config", "DeepSeekV32Pipeline"]
