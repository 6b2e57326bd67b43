"""Fused multi-head serving path: kernel vs per-head oracle, backend
dispatch, engine shape-bucketing (zero recompiles within a bucket),
deferred sync, the mesh-sharded exact fallback, and the staging copy
(padding rows zeroed, the rows' memory order kept, the caller's array
free once submit returns, skipped for a one-request flush's admitted
rows that fill their bucket)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core import approximate, backend, decision_function, gamma_max
from repro.kernels.common import TileConfig
from repro.data.synthetic import make_blobs
from repro.kernels.quadform.kernel import quadform_heads_pallas
from repro.kernels.quadform.ref import quadform_heads_ref
from repro.core.families import maclaurin
from repro.serve import PublishSpec, Runtime, svm_engine
from repro.serve.runtime import ENGINE_STEP, FaultInjector, InjectedFault, scheduler
from repro.serve.svm_engine import EngineResult, SVMEngine, bucket_size
from repro.svm import train_lssvm
from repro.svm.multiclass import (
    approx_ovr_predict,
    approximate_ovr,
    ovr_predict,
    train_one_vs_rest,
)


def _random_heads(K, d, seed=0, gamma=0.05):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((K, d, d)).astype(np.float32) * 0.1
    M_all = jnp.asarray((M + M.transpose(0, 2, 1)) / 2)
    V = jnp.asarray(rng.standard_normal((K, d)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal(K).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(K).astype(np.float32))
    g = jnp.full((K,), gamma, jnp.float32)
    msq = jnp.full((K,), 2.0, jnp.float32)
    return M_all, V, c, b, g, msq


# ------------------------------------------------- fused kernel vs vmap oracle


@pytest.mark.parametrize("K", [1, 3, 10])
@pytest.mark.parametrize("n,d", [(5, 7), (64, 128), (513, 60)])
def test_fused_heads_pallas_matches_vmap_reference(K, n, d):
    """Padded-n (513), padded-d (7, 60) and aligned (128) edge shapes."""
    rng = np.random.default_rng(K * n + d)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.5)
    heads = _random_heads(K, d, seed=K)
    s_ref, zsq_ref, v_ref = quadform_heads_ref(Z, *heads)
    s, zsq, v = quadform_heads_pallas(
        Z, *heads, config=TileConfig(block_n=64), interpret=True
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(zsq), np.asarray(zsq_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


@pytest.mark.parametrize("K", [1, 3, 10])
def test_fused_heads_xla_matches_vmap_reference(K):
    """The CPU serving path (single stacked-Hessian GEMM) is equivalent too."""
    n, d = 130, 33
    rng = np.random.default_rng(K)
    Z = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.5)
    heads = _random_heads(K, d, seed=K + 1)
    s_ref, _, v_ref = quadform_heads_ref(Z, *heads)
    s, _, v = backend.quadform_heads_xla(Z, *heads)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


def test_fused_xla_gemm_count_independent_of_heads():
    """The fused path issues ONE stacked contraction, not K: the number of
    dot_generals in the jaxpr is identical for K=1 and K=10."""
    def count_dots(K):
        d = 16
        Z = jnp.zeros((8, d))
        heads = _random_heads(K, d)
        jaxpr = jax.make_jaxpr(backend.quadform_heads_xla)(Z, *heads)
        return str(jaxpr).count("dot_general")

    assert count_dots(10) == count_dots(1)


def test_backend_dispatch_override():
    prev = backend.set_backend("pallas")
    try:
        assert backend.resolve() == "pallas"
        backend.set_backend("xla")
        assert backend.resolve() == "xla"
        with pytest.raises(ValueError):
            backend.set_backend("cuda")
    finally:
        backend.set_backend(prev or "auto")


# --------------------------------------------------------------- the engine


def _binary_engine(mesh=None, **kw):
    X, y = make_blobs(240, 6, seed=7, separation=3.0)
    X, y = jnp.asarray(X), jnp.asarray(y)
    gamma = float(gamma_max(X)) * 0.8
    m = train_lssvm(X, y, jnp.float32(gamma), jnp.float32(10.0))
    return SVMEngine(approximate(m), m, mesh=mesh, **kw), m, X


def test_bucket_size_policy():
    assert bucket_size(1) == 32
    assert bucket_size(32) == 32
    assert bucket_size(33) == 64
    assert bucket_size(100) == 128
    assert bucket_size(10_000, max_batch=8192) == 8192


def test_engine_zero_recompiles_within_bucket():
    """Repeated batches inside one bucket never grow the jit cache."""
    eng, _, X = _binary_engine()
    rng = np.random.default_rng(0)
    for n in (1, 3, 9, 17, 31, 32):
        eng.predict(rng.standard_normal((n, 6)).astype(np.float32))
    assert eng.jit_cache_size() == 1
    eng.predict(rng.standard_normal((33, 6)).astype(np.float32))  # next bucket
    assert eng.jit_cache_size() == 2
    for n in (2, 40, 20, 64):
        eng.predict(rng.standard_normal((n, 6)).astype(np.float32))
    assert eng.jit_cache_size() == 2                       # steady state
    assert eng.stats.bucket_hits.keys() == {32, 64}


def test_engine_warmup_bounds_cache():
    eng, _, _ = _binary_engine(min_bucket=32, max_batch=128)
    n_variants = eng.warmup()
    assert n_variants == 3                                  # 32, 64, 128
    eng.predict(np.zeros((5, 6), np.float32))
    eng.predict(np.zeros((300, 6), np.float32))             # chunked: 128-buckets
    assert eng.jit_cache_size() == 3                        # nothing new compiled


def test_engine_chunks_oversized_batches():
    from repro.core import approx_decision_function

    eng, m, X = _binary_engine(min_bucket=32, max_batch=64)
    Z = jnp.concatenate([X, X], axis=0)[:150]
    f, valid = eng.predict(Z)                  # 3 chunks: 64 + 64 + 22
    assert f.shape == (150,) and valid.all()
    ref = np.asarray(approx_decision_function(eng.approx, Z))
    np.testing.assert_allclose(f, ref, rtol=1e-5, atol=1e-5)


def test_engine_fallback_exact_and_deferred_sync():
    eng, m, X = _binary_engine()
    Zbad = jnp.concatenate([X[:4], 50.0 * X[:3]], axis=0)
    r = eng.submit(Zbad)                                    # no sync yet
    r.block_until_ready()
    f, valid = r.values, r.valid
    assert (~valid).sum() == 3
    exact = np.asarray(decision_function(m, Zbad))
    np.testing.assert_allclose(f[~valid], exact[~valid], rtol=1e-4, atol=1e-4)
    assert eng.stats.fallback_instances == 3
    labels = r.labels
    assert set(np.unique(labels)) <= {-1, 1}


def test_engine_mesh_sharded_fallback():
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng, m, X = _binary_engine(mesh=mesh)
    Zbad = jnp.concatenate([X[:4], 50.0 * X[:3]], axis=0)
    f, valid = eng.predict(Zbad)
    exact = np.asarray(decision_function(m, Zbad))
    np.testing.assert_allclose(f[~valid], exact[~valid], rtol=1e-4, atol=1e-4)


def test_engine_multiclass_fused_argmax():
    rng = np.random.default_rng(3)
    K, n, d = 3, 120, 5
    mus = rng.standard_normal((K, d)) * 3
    X = np.concatenate([rng.standard_normal((n // K, d)) + mus[k] for k in range(K)])
    y = np.concatenate([np.full(n // K, k) for k in range(K)])
    X, y = jnp.asarray(X.astype(np.float32)), jnp.asarray(y)
    gamma = float(gamma_max(X)) * 0.5
    m = train_one_vs_rest(X, y, K, jnp.float32(gamma), jnp.float32(10.0))
    am = approximate_ovr(m)
    eng = SVMEngine(am, m)
    labels = eng.predict_labels(X)
    np.testing.assert_array_equal(labels, np.asarray(approx_ovr_predict(am, X)))
    scores, valid = eng.predict(X)
    assert scores.shape == (n, K)
    # fused exact OvR (shared kernel-matrix GEMM) agrees with the engine's
    # fallback labels on out-of-envelope rows
    Zbad = 50.0 * X[:3]
    bad_labels = eng.predict_labels(Zbad)
    np.testing.assert_array_equal(bad_labels, np.asarray(ovr_predict(m, Zbad)))


# ------------------------------------------------------ the staging copy


class _NanStaging:
    """numpy, except that ``empty_like`` hands back NaNs, as a reused
    allocation may: a padding row left unzeroed then shows."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def empty_like(*args, **kw):
        out = np.empty_like(*args, **kw)
        out.fill(np.nan)
        return out


def _served_rows(X, m, order):
    """``m`` rows cycled from ``X``, every fifth outside the Eq 3.11
    envelope (so the exact fallback runs), in memory order ``order``."""
    Z = np.asarray(X)[np.arange(m) % X.shape[0]].copy()
    Z[::5] *= 50.0
    return np.asarray(Z, np.float32, order=order)


def _zero_padded_reference(eng, Z):
    """``eng``'s result for ``Z`` with each chunk staged in a row-major
    ``np.zeros`` buffer of its bucket's size."""
    Z = np.ascontiguousarray(Z, np.float32)
    staged, chunks = [], []
    for start in range(0, Z.shape[0], eng.max_batch):
        rows = Z[start:start + eng.max_batch]
        bkt = bucket_size(len(rows), eng.min_bucket, eng.max_batch)
        buf = np.zeros((bkt, eng.d), np.float32)
        buf[:len(rows)] = rows
        staged.append(rows)
        chunks.append((eng._step(eng._put(buf)), len(rows)))
    return EngineResult(eng, staged, chunks)


@pytest.fixture(scope="module")
def staging_engine():
    eng, _, X = _binary_engine(min_bucket=32, max_batch=128)
    return eng, X


# 1, min_bucket - 1, min_bucket, a bucket - 1, a full bucket, max_batch + 3
@pytest.mark.parametrize("order", ["C", "F"])
@pytest.mark.parametrize("m", [1, 31, 32, 63, 64, 131])
def test_engine_stages_rows_with_zeroed_padding(staging_engine, monkeypatch,
                                                m, order):
    """What ``_put`` gets is each chunk's rows, then bit-exact zeros to
    the bucket, in the rows' memory order; the served scores, verdicts
    and labels equal a zero-padded row-major staging, bit for bit."""
    eng, X = staging_engine
    Z = _served_rows(X, m, order)
    handed = []
    put = eng._put
    monkeypatch.setattr(eng, "_put", lambda buf: handed.append(buf) or put(buf))
    monkeypatch.setattr(svm_engine, "np", _NanStaging())
    r = eng.submit(Z)
    monkeypatch.undo()                     # the fallback's puts come later
    starts = range(0, m, eng.max_batch)
    assert len(handed) == len(starts)
    for buf, start in zip(handed, starts):
        rows = Z[start:start + eng.max_batch]
        k = len(rows)
        assert buf.shape == (bucket_size(k, 32, 128), eng.d)
        assert buf.dtype == np.float32
        np.testing.assert_array_equal(buf[:k], rows)
        assert not buf[k:].view(np.uint32).any()          # +0.0, every bit
        if order == "F" and k > 1:
            assert buf.flags.f_contiguous
        else:
            assert buf.flags.c_contiguous
    want = _zero_padded_reference(eng, Z)
    assert not want.valid.all()
    np.testing.assert_array_equal(r.values, want.values)
    np.testing.assert_array_equal(r.valid, want.valid)
    np.testing.assert_array_equal(r.labels, want.labels)


@pytest.mark.parametrize("entry", ["engine", "runtime"])
def test_caller_may_overwrite_its_rows_once_submit_returns(entry):
    """Rows overwritten right after ``submit`` returns are still scored,
    and fallback rows re-scored, as they were when submitted."""
    eng, m, X = _binary_engine(min_bucket=32, max_batch=128)
    Z = _served_rows(X, 40, "C")
    if entry == "engine":
        want = eng.predict(Z.copy())
        r = eng.submit(Z)
        Z[:] = 7.0
        got = r.values, r.valid
    else:
        # the request waits in the queue for max_wait_us after submit
        with Runtime(max_wait_us=50_000,
                     engine_opts=dict(min_bucket=32, max_batch=128)) as rt:
            rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
            want = rt.predict("m", Z.copy())
            fut = rt.submit("m", Z)
            Z[:] = 7.0
            res = fut.result(timeout=60)
            got = res.values, res.valid
    assert not want[1].all()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


# ------------------------------ a one-request flush, sent as it was admitted


@pytest.fixture(scope="module")
def flush_runtime():
    """A runtime over 32- to 128-row buckets whose flush waits for 128
    rows, or 0.2 s: two requests submitted at once share one flush."""
    _, m, X = _binary_engine()
    with Runtime(max_wait_us=200_000, flush_rows=128,
                 engine_opts=dict(min_bucket=32, max_batch=128)) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        _, eng = rt.registry.get_engine("m")
        yield rt, eng, X


def _flush_through(rt, eng, monkeypatch, requests):
    """Submit copies of ``requests`` at once, overwriting each copy as its
    submit returns; returns (the arrays the batcher admitted, the arrays
    ``_put`` got in the flush, the results)."""
    admitted, handed = [], []
    init = scheduler._Pending.__init__
    monkeypatch.setattr(scheduler._Pending, "__init__",
                        lambda self, Z, *a, **kw: admitted.append(Z)
                        or init(self, Z, *a, **kw))
    put = eng._put
    monkeypatch.setattr(eng, "_put", lambda buf: handed.append(buf) or put(buf))
    futs = []
    for Z in requests:
        Z = Z.copy(order="K")
        futs.append(rt.submit("m", Z))
        Z[:] = 7.0
    res = [f.result(timeout=60) for f in futs]
    monkeypatch.undo()                     # the fallback's puts come later
    return admitted, handed, res


def _same_memory(a, b):
    """``a`` is ``b`` or a view of all of it, in its layout: not a copy."""
    return (a.ctypes.data, a.shape, a.strides) == (b.ctypes.data, b.shape, b.strides)


def _assert_served_as(res, want):
    """The requests' results, in order, equal ``want`` bit for bit."""
    assert not want.valid.all()                     # the fallback ran
    for got, w in zip(("values", "valid", "labels"), (want.values, want.valid,
                                                       want.labels)):
        np.testing.assert_array_equal(
            np.concatenate([getattr(r, got) for r in res]), w)


@pytest.mark.parametrize("order", ["C", "F"])
def test_a_one_request_flush_that_fills_its_bucket_is_sent_as_admitted(
        flush_runtime, monkeypatch, order):
    """The batcher's admitted array reaches ``_put`` itself, with no
    concatenate and no staging copy, and ``unstaged_chunks`` counts it;
    the values, verdicts and labels, fallback rows included, equal a
    staged flush's bit for bit."""
    rt, eng, X = flush_runtime
    Z = _served_rows(X, 128, order)
    before = eng.stats.unstaged_chunks
    admitted, handed, res = _flush_through(rt, eng, monkeypatch, [Z])
    assert len(admitted) == 1 and len(handed) == 1
    assert _same_memory(handed[0], admitted[0])
    assert eng.stats.unstaged_chunks == before + 1
    assert eng.stats.snapshot()["unstaged_chunks"] == before + 1
    _assert_served_as(res, _zero_padded_reference(eng, Z))


@pytest.mark.parametrize("case", ["coalesced", "partial", "F-longer-than-max_batch"])
def test_other_flushes_are_staged_as_before(flush_runtime, monkeypatch, case):
    """Two requests in one flush (their 128 rows fill the bucket), a
    partial bucket, and the chunks of a column-major request longer than
    ``max_batch`` are each staged in a bucket buffer of the engine's, and
    the counter does not move; the results equal the staged path's."""
    rt, eng, X = flush_runtime
    Z = _served_rows(X, {"coalesced": 128, "partial": 100}.get(case, 131),
                     "F" if case.startswith("F") else "C")
    requests = [Z[:64], Z[64:]] if case == "coalesced" else [Z]
    before = eng.stats.unstaged_chunks
    admitted, handed, res = _flush_through(rt, eng, monkeypatch, requests)
    assert len(admitted) == len(requests)
    want_buckets = {"coalesced": [128], "partial": [128]}.get(case, [128, 32])
    assert [buf.shape[0] for buf in handed] == want_buckets
    assert not any(np.shares_memory(buf, a) for buf in handed for a in admitted)
    assert eng.stats.unstaged_chunks == before
    _assert_served_as(res, _zero_padded_reference(eng, Z))


def test_a_degraded_one_request_flush_is_sent_as_admitted(monkeypatch):
    """Every breaker open: the exact path gets the admitted rows as they
    are too, and serves what ``submit_exact`` serves from staged rows."""
    _, m, X = _binary_engine()
    faults = FaultInjector(seed=0)
    with Runtime(max_wait_us=200_000, flush_rows=128, fault_injector=faults,
                 breaker=dict(fail_threshold=1, reset_after_s=60.0),
                 engine_opts=dict(min_bucket=32, max_batch=128)) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        _, eng = rt.registry.get_engine("m")
        Z = _served_rows(X, 128, "C")
        faults.fail_next(ENGINE_STEP, 1)
        with pytest.raises(InjectedFault):
            rt.predict("m", Z)                          # trips the breaker
        before = eng.stats.unstaged_chunks
        admitted, handed, (res,) = _flush_through(rt, eng, monkeypatch, [Z])
        assert len(handed) == 1 and _same_memory(handed[0], admitted[0])
        assert eng.stats.unstaged_chunks == before + 1
        assert eng.stats.degraded_batches == 1
        want = eng.submit_exact(Z.copy())
        assert not res.valid.any()
        for got in ("values", "valid", "labels"):
            np.testing.assert_array_equal(getattr(res, got), getattr(want, got))
