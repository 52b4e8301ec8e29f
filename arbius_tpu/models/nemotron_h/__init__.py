"""nemotron_h — Nemotron 3 Super on the text-serving path: Mamba-2
state-space layers beside grouped-query attention, latent-space routed
experts told which they hold, and the multi-token prediction module
drafting for the speculative loop, whose per-row commit keeps the
recurrent state after the positions it takes (docs/text-serving.md)."""
from arbius_tpu.models.nemotron_h.model import NemotronHConfig
from arbius_tpu.models.nemotron_h.pipeline import (
    MESH_LAYOUTS,
    NemotronHPipeline,
)

__all__ = ["MESH_LAYOUTS", "NemotronHConfig", "NemotronHPipeline"]
