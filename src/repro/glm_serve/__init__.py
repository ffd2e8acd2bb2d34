"""GLM inference plane: registry, batched scoring, micro-batching, refit.

The serving counterpart of the training stack (docs/serving.md): fitted
:class:`repro.core.disco.DiscoResult` models are published to a
versioned :class:`ModelRegistry`, scored in micro-batches of (id, value)
slots gathered against device-resident weights (:class:`ScoringEngine` +
:class:`MicroBatchScheduler`), and refreshed online by warm-started
streaming refits (:class:`RefitLoop`) without pausing traffic.

Not to be confused with ``repro.serve`` — the *legacy LLM token-decode*
engine of the model-zoo track; this package is the paper-model (GLM)
inference subsystem.
"""
from repro.glm_serve.registry import (ModelRegistry, PublishedModel,
                                      REGISTRY_VERSION)
from repro.glm_serve.scoring import (RequestPacker, ScoreRequest,
                                     ScoringEngine, oracle_margins)
from repro.glm_serve.scheduler import (MicroBatchScheduler,
                                       ScoredCompletion, ServeStats)
from repro.glm_serve.refit import RefitLoop

__all__ = [
    "ModelRegistry", "PublishedModel", "REGISTRY_VERSION",
    "RequestPacker", "ScoreRequest", "ScoringEngine", "oracle_margins",
    "MicroBatchScheduler", "ScoredCompletion", "ServeStats",
    "RefitLoop",
]
