"""dots3_note — dots3-note-prev on the text-serving path: DeepSeek-V3.2's
latent attention and indexer in the full layers, a sliding-window latent
attention of its own shape in the others, two forms of cache in one
carry, headwise gates, routed experts told which they hold
(docs/text-serving.md)."""
from arbius_tpu.models.dots3.model import Dots3NoteConfig
from arbius_tpu.models.dots3.pipeline import (
    MESH_LAYOUTS,
    Dots3NotePipeline,
)

__all__ = ["MESH_LAYOUTS", "Dots3NoteConfig", "Dots3NotePipeline"]
