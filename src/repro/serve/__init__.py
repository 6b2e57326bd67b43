"""``repro.serve`` — the supported serving surface.

This module's ``__all__`` is the FROZEN public API: everything an
external caller may depend on, snapshot-tested by
``tests/test_public_api.py`` so any change to the surface is a
deliberate, reviewed diff. The supported entry points:

  * ``compile_model`` (re-exported from ``repro.core.families``) —
    train-time: turn an exact ``SVMModel`` into a ``CompiledArtifact``;
  * ``Runtime`` / ``ArtifactRegistry`` / ``SVMEngine`` /
    ``PublishSpec`` — serve-time Python API;
  * ``create_app`` (re-exported from ``repro.serve.server``) — the
    HTTP front door over a ``Runtime``;
  * the error taxonomy (``ServingError`` and its subclasses) — every
    refusal a caller can observe, each with a stable ``code`` and
    ``http_status``.

Anything importable but not listed here is internal and may change
without notice.
"""

from repro.core.families import compile_model
from repro.serve.runtime import (
    ArtifactCorrupt,
    ArtifactRegistry,
    BatcherClosed,
    CircuitBreaker,
    DeadlineExceeded,
    DriftGuard,
    FaultInjector,
    ModelNotFound,
    MicroBatcher,
    PublishSpec,
    Runtime,
    RuntimeOverloaded,
    ServingError,
)
from repro.serve.server import create_app, serve
from repro.serve.svm_engine import (
    EngineResult,
    EngineStats,
    SliceResult,
    SVMEngine,
    bucket_size,
)

__all__ = [
    "ArtifactCorrupt",
    "ArtifactRegistry",
    "BatcherClosed",
    "CircuitBreaker",
    "DeadlineExceeded",
    "DriftGuard",
    "EngineResult",
    "EngineStats",
    "FaultInjector",
    "MicroBatcher",
    "ModelNotFound",
    "PublishSpec",
    "Runtime",
    "RuntimeOverloaded",
    "SVMEngine",
    "ServingError",
    "SliceResult",
    "bucket_size",
    "compile_model",
    "create_app",
    "serve",
]
