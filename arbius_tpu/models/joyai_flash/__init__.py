"""joyai_llm_flash — JoyAI-LLM-Flash on the text-serving path: latent
attention over every causal key, all routed experts on the chip, and the
multi-token prediction module drafting for a speculative decode loop
(docs/text-serving.md)."""
from arbius_tpu.models.joyai_flash.model import JoyAIFlashConfig
from arbius_tpu.models.joyai_flash.pipeline import (
    MESH_LAYOUTS,
    JoyAIFlashPipeline,
)

__all__ = ["MESH_LAYOUTS", "JoyAIFlashConfig", "JoyAIFlashPipeline"]
