"""trinity — Trinity-Large-Preview (`afmoe`) on the text-serving path
(docs/text-serving.md): one chip's share of an expert-parallel
deployment, window and full attention with two kinds of cache."""
from arbius_tpu.models.trinity.model import TrinityConfig
from arbius_tpu.models.trinity.pipeline import MESH_LAYOUTS, TrinityPipeline

__all__ = ["MESH_LAYOUTS", "TrinityConfig", "TrinityPipeline"]
