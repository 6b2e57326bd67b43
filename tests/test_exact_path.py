"""The engine's exact path: the model handed to the exact program as
arguments (no support vector compiled into it, one program shared by
every engine of the same shapes), the fallback bucketed (one exact
program per bucket, whatever the count of rows outside Eq 3.11), its
agreement with the plain expansion on every exact path, and its
profiler spans."""

import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core import backend, families
from repro.core.rbf import SVMModel
from repro.serve import svm_engine
from repro.serve.runtime.obs import profile
from repro.serve.svm_engine import SVMEngine


def _model(n_sv, d, heads=1, seed=0, ratio=0.1):
    """A random exact model whose unit-scale rows lie inside Eq 3.11 at
    ``ratio`` 0.1; rows 200 times longer lie outside it."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) / np.sqrt(d)
    ay = rng.uniform(-1, 1, (heads, n_sv)).astype(np.float32)
    b = rng.normal(0, 0.01, heads).astype(np.float32)
    gamma = np.float32(ratio / (4 * np.max(np.sum(X * X, axis=1))))
    if heads == 1:
        return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay[0]), b=jnp.float32(b[0]),
                        gamma=jnp.float32(gamma))
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay), b=jnp.asarray(b),
                    gamma=jnp.float32(gamma))


def _engine(svm, **kw):
    art = families.get_family("maclaurin").compile(svm, dtype="float32")
    return SVMEngine(art, svm, **kw)


def _rows(svm, n, invalid, seed=1, order="C", scattered=False):
    """n rows, ``invalid`` of them outside Eq 3.11: the first ones, or
    rows at seeded places (``scattered``); returns them and that mask."""
    rng = np.random.default_rng(seed)
    d = svm.X.shape[1]
    Z = rng.standard_normal((n, d)).astype(np.float32) / np.sqrt(d)
    out = np.zeros(n, bool)
    out[rng.permutation(n)[:invalid] if scattered else slice(0, invalid)] = True
    Z[out] *= 200.0
    return np.asarray(Z, order=order), out


def _exact(svm, Z):
    """The plain expansion, unbucketed, (n, K)."""
    ay = jnp.atleast_2d(svm.alpha_y)
    return np.asarray(backend.rbf_scores_xla(jnp.asarray(Z), svm.X, ay, svm.gamma,
                                             jnp.reshape(svm.b, (-1,))))


def _cache_size():
    return svm_engine._exact_programs(None)[0]._cache_size()


# --------------------------------------------------- weights as arguments


def test_the_exact_program_holds_no_support_vectors():
    n_sv, d = 300, 24
    eng = _engine(_model(n_sv, d))
    Zb = jnp.zeros((32, d), jnp.float32)
    text = eng._exact_scores.lower(Zb, *eng._exact_weights,
                                   backend_name=eng._backend).as_text()
    shape = f"tensor<{n_sv}x{d}xf32>"
    assert shape in text                                    # an argument ...
    assert not [line for line in text.splitlines()
                if "constant" in line and shape in line]    # ... never a constant
    assert len(text) < n_sv * d                             # no weights printed in it


def test_engines_of_one_shape_share_one_exact_program():
    n_sv, d = 301, 25
    a, b = _model(n_sv, d, seed=1), _model(n_sv, d, seed=2)
    before = _cache_size()
    for svm in (a, b):
        eng = _engine(svm)
        Z, out = _rows(svm, 8, invalid=3)
        values, valid = eng.predict(Z)
        np.testing.assert_array_equal(~valid, out)
        np.testing.assert_allclose(values[out], _exact(svm, Z[out])[:, 0], rtol=1e-5, atol=1e-6)
        assert _cache_size() == before + 1                  # the second one compiles nothing


# --------------------------------------------------------- the buckets


@pytest.mark.parametrize("order,scattered", [("C", True), ("F", True), ("F", False)],
                         ids=["C", "F", "F-run"])
def test_invalid_counts_of_one_bucket_compile_one_exact_program(order, scattered):
    n_sv, d = 150, {"C": 7, "F": 9}[order] + 4 * (not scattered)
    svm = _model(n_sv, d, heads=3)
    eng = _engine(svm, min_bucket=32, max_batch=64)
    before = _cache_size()
    for k in (3, 5, 17):
        Z, out = _rows(svm, 40, invalid=k, seed=k, order=order, scattered=scattered)
        r = eng.submit(Z)
        values, valid = r.values, r.valid
        np.testing.assert_array_equal(~valid, out)
        np.testing.assert_allclose(values[out], _exact(svm, Z[out]), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(r.labels[out], _exact(svm, Z[out]).argmax(axis=1))
    assert _cache_size() == before + 1                      # bucket 32, once
    assert eng.stats.fallback_instances == 3 + 5 + 17
    assert eng.stats.fallback_batches == 3
    assert eng.stats.snapshot()["fallback_batches"] == 3


def test_a_fallback_past_max_batch_takes_one_exact_call_per_chunk():
    svm = _model(120, 6)
    eng = _engine(svm, min_bucket=32, max_batch=64)
    Z, out = _rows(svm, 150, invalid=100, scattered=True)   # 3 chunks of the fast path
    values, valid = eng.predict(Z)
    np.testing.assert_array_equal(~valid, out)
    np.testing.assert_allclose(values[out], _exact(svm, Z[out])[:, 0], rtol=1e-5, atol=1e-6)
    assert eng.stats.fallback_batches == 2                  # 64 + 36 rows


@pytest.mark.parametrize("path", ["fallback", "submit_exact"])
@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "mesh"])
@pytest.mark.parametrize("heads", [1, 3])
def test_exact_paths_agree_with_rbf_scores_xla(path, mesh, heads):
    svm = _model(130, 8, heads=heads, seed=heads)
    m = Mesh(np.array(jax.devices()[:1]), ("data",)) if mesh else None
    eng = _engine(svm, mesh=m)
    Z, out = _rows(svm, 10, invalid=4, scattered=True)
    if path == "fallback":
        r = eng.submit(Z)
        rows = ~r.valid
        np.testing.assert_array_equal(rows, out)
    else:
        r = eng.submit_exact(Z)
        rows = np.ones(len(Z), bool)
        assert not r.valid.any()
    values = np.asarray(r.values).reshape(len(Z), -1)
    exact = _exact(svm, Z)
    np.testing.assert_allclose(values[rows], exact[rows], rtol=1e-5, atol=1e-6)
    labels = exact.argmax(axis=1) if heads > 1 else np.where(exact[:, 0] >= 0, 1, -1)
    np.testing.assert_array_equal(r.labels[rows], labels[rows])


# ------------------------------------------------------------ the spans


def test_fallback_spans_nest_in_order():
    svm = _model(110, 5)
    eng = _engine(svm, min_bucket=32, max_batch=64)
    Z, _ = _rows(svm, 20, invalid=6)
    eng.predict(Z)                                          # compile outside the record
    events = []

    @contextlib.contextmanager
    def span(name):
        events.append(("enter", name))
        yield
        events.append(("exit", name))

    svm_engine.set_profile_annotation(span)
    try:
        eng.predict(Z)
    finally:
        svm_engine.set_profile_annotation(None)
    start = events.index(("enter", "svm_engine.fallback"))
    names = ["svm_engine.fallback.gather/b32", "svm_engine.fallback.put/b32",
             "svm_engine.fallback.step/b32", "svm_engine.fallback.sync"]
    inner = [e for n in names for e in (("enter", n), ("exit", n))]
    assert events[start:] == [("enter", "svm_engine.fallback"), *inner,
                              ("exit", "svm_engine.fallback")]


@pytest.mark.parametrize("on", [True, False])
def test_spans_are_made_only_while_profiling_is_on(monkeypatch, on):
    svm = _model(111, 5)
    eng = _engine(svm)
    Z, _ = _rows(svm, 20, invalid=6)
    eng.predict(Z)
    made = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    prev = profile.enable(on)
    try:
        eng.predict(Z)
    finally:
        profile.enable(prev)
    if on:
        assert {"svm_engine.fallback", "svm_engine.fallback.gather/b32",
                "svm_engine.fallback.sync"} <= set(made)
    else:
        assert made == []
