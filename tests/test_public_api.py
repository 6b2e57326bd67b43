"""The frozen public surface: ``repro.serve.__all__``, the error
taxonomy's stable (code, http_status) table, and the ``PublishSpec``
unification contract. These are SNAPSHOT tests — a diff here means the
public API changed, which must be a deliberate, reviewed event, never
a side effect of a refactor."""

import dataclasses

import pytest

import repro.serve as serve
import repro.serve.runtime as runtime_pkg
from repro.serve.runtime import PublishSpec, errors
from repro.serve.runtime.publish import resolve_spec

# ------------------------------------------------------------- the snapshots

SERVE_ALL = [
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

RUNTIME_ALL = [
    "ArtifactCorrupt",
    "ArtifactRegistry",
    "BatcherClosed",
    "CircuitBreaker",
    "DeadlineExceeded",
    "DriftGuard",
    "ENGINE_STEP",
    "FaultInjector",
    "InjectedFault",
    "LatencyWindow",
    "MetricsRegistry",
    "MicroBatcher",
    "ModelNotFound",
    "ModelTelemetry",
    "Observability",
    "PublishSpec",
    "REGISTRY_LOAD",
    "RegistryEntry",
    "ReservoirSampler",
    "Runtime",
    "RuntimeOverloaded",
    "ServingError",
    "Tracer",
    "render_prometheus",
]

# Every refusal a wire client can observe: (class name, code, status).
ERROR_TAXONOMY = [
    ("ArtifactCorrupt", "artifact_corrupt", 503),
    ("BatcherClosed", "batcher_closed", 503),
    ("DeadlineExceeded", "deadline_exceeded", 504),
    ("InjectedFault", "injected_fault", 500),
    ("ModelNotFound", "model_not_found", 404),
    ("RuntimeOverloaded", "overloaded", 429),
    ("ServingError", "serving_error", 500),
]


def test_serve_surface_is_frozen():
    assert sorted(serve.__all__) == SERVE_ALL
    for name in serve.__all__:
        assert getattr(serve, name, None) is not None, name


def test_runtime_surface_is_frozen():
    assert sorted(runtime_pkg.__all__) == RUNTIME_ALL
    for name in runtime_pkg.__all__:
        assert getattr(runtime_pkg, name, None) is not None, name


def test_error_codes_and_statuses_are_frozen():
    table = [
        (cls.__name__, cls.code, cls.http_status)
        for cls in vars(errors).values()
        if isinstance(cls, type) and issubclass(cls, errors.ServingError)
    ]
    assert sorted(table) == ERROR_TAXONOMY
    # codes are unique — a wire client switching on code is unambiguous
    codes = [code for _, code, _ in table]
    assert len(codes) == len(set(codes))


def test_errors_keep_their_pre_taxonomy_bases():
    # every pre-taxonomy `except` clause must keep catching
    assert issubclass(errors.RuntimeOverloaded, RuntimeError)
    assert issubclass(errors.DeadlineExceeded, TimeoutError)
    assert issubclass(errors.BatcherClosed, RuntimeError)
    assert issubclass(errors.ArtifactCorrupt, RuntimeError)
    assert issubclass(errors.ModelNotFound, KeyError)
    # and ModelNotFound messages read like messages, not quoted keys
    assert str(errors.ModelNotFound("no such model", ref="x")) == "no such model"


def test_error_to_wire_is_the_wire_body():
    e = errors.RuntimeOverloaded("queue full", retry_after_s=0.25)
    assert e.to_wire() == {
        "code": "overloaded", "status": 429, "message": "queue full",
        "retry_after_s": 0.25,
    }


# ----------------------------------------------------------- PublishSpec API


def test_publish_spec_wire_roundtrip():
    spec = PublishSpec(alias="det", replicas=2, warmup=True)
    assert spec.to_wire() == {"alias": "det", "replicas": 2, "warmup": True}
    assert PublishSpec.from_wire(spec.to_wire()) == spec


def test_publish_spec_exact_never_crosses_the_wire():
    spec = PublishSpec(exact=object())
    assert spec.to_wire() == {"has_exact": True}


def test_publish_spec_rejects_unknown_wire_fields():
    with pytest.raises(ValueError, match="unknown PublishSpec fields"):
        PublishSpec.from_wire({"replcas": 2})


def test_publish_spec_validates_replicas():
    with pytest.raises(ValueError):
        PublishSpec(replicas=0)


def test_publish_spec_is_frozen():
    with pytest.raises(dataclasses.FrozenInstanceError):
        PublishSpec().alias = "x"


def test_legacy_kwargs_fold_with_deprecation_warning():
    with pytest.warns(DeprecationWarning, match="Runtime.publish"):
        spec = resolve_spec(None, caller="Runtime.publish",
                            exact=None, replicas=3)
    assert spec == PublishSpec(replicas=3)


def test_spec_plus_legacy_kwargs_is_an_error():
    with pytest.raises(TypeError, match="not both"):
        resolve_spec(PublishSpec(), caller="x", replicas=2)
