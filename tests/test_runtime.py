"""The multi-tenant serving runtime: content-addressed registry (dedupe,
aliases, lazy directory loads, LRU eviction), the micro-batching
scheduler (coalescing correctness, row order, flush rules, zero
steady-state recompiles under concurrency), the fourier per-artifact
fallback flowing through the coalesced path, alias hot-swap mid-traffic,
and thread-safety of the engine's serving statistics."""

import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gamma_max
from repro.core.rbf import SVMModel, rbf_kernel
from repro.core.families import fourier, maclaurin
from repro.serve import PublishSpec, Runtime, SVMEngine
from repro.serve.runtime import ArtifactRegistry, MicroBatcher

ENGINE_OPTS = dict(min_bucket=8, max_batch=64)


def _svm(seed=0, d=8, n_sv=40, bias=0.1):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * 0.6
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                    b=jnp.float32(bias), gamma=jnp.float32(gamma))


def _exact_scores(m, Z):
    ay2 = m.alpha_y if m.alpha_y.ndim == 2 else m.alpha_y[None, :]
    b2 = jnp.reshape(m.b, (ay2.shape[0],))
    return np.asarray(rbf_kernel(jnp.asarray(Z), m.X, m.gamma) @ ay2.T + b2[None, :])


def _batches(rng, count, d=8, lo=1, hi=5):
    return [rng.standard_normal((int(rng.integers(lo, hi + 1)), d))
               .astype(np.float32) * 0.3 for _ in range(count)]


# ----------------------------------------------------------------- registry


def test_registry_dedupes_identical_compiles():
    m = _svm(3)
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS)
    d1 = reg.register(maclaurin.compile(m), PublishSpec(alias="a@latest"))
    d2 = reg.register(maclaurin.compile(m), PublishSpec(alias="b@latest"))
    assert d1 == d2
    snap = reg.snapshot()
    assert snap["models"] == 1
    assert snap["aliases"] == {"a@latest": d1, "b@latest": d1}
    # both aliases serve the SAME engine object (one copy in memory)
    _, e1 = reg.get_engine("a@latest")
    _, e2 = reg.get_engine("b@latest")
    assert e1 is e2
    assert reg.loads == 1


def test_registry_ref_resolution():
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS)
    digest = reg.register(maclaurin.compile(_svm(3)), PublishSpec(alias="det@latest"))
    assert reg.resolve(digest) == digest
    assert reg.resolve("det@latest") == digest
    assert reg.resolve("det") == digest            # @latest convention
    assert reg.resolve(digest[:10]) == digest      # unique prefix
    with pytest.raises(KeyError):
        reg.resolve("nope")


def test_registry_lazy_directory_load(tmp_path):
    m1, m2 = _svm(1), _svm(2)
    maclaurin.compile(m1).save(str(tmp_path / "alpha.npz"))
    maclaurin.compile(m2).save(str(tmp_path / "beta.npz"))
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS)
    added = reg.add_directory(str(tmp_path))
    assert set(added) == {"alpha@latest", "beta@latest"}
    # indexing hashed the files; nothing is deserialized yet
    assert all(e.artifact is None and e.engine is None
               for e in reg._entries.values())
    assert added["alpha@latest"] == maclaurin.compile(m1).digest()
    # first use loads + serves correctly
    digest, eng = reg.get_engine("alpha")
    Z = np.random.default_rng(0).standard_normal((4, 8)).astype(np.float32) * 0.3
    np.testing.assert_allclose(
        eng.predict(Z)[0],
        SVMEngine(maclaurin.compile(m1), None, **ENGINE_OPTS).predict(Z)[0],
        rtol=1e-6, atol=1e-6,
    )
    assert reg.snapshot()["loaded"] == 1           # beta is still cold


def test_registry_lru_eviction_under_budget(tmp_path):
    models = [_svm(s) for s in (1, 2, 3)]
    arts = [maclaurin.compile(m) for m in models]
    for i, a in enumerate(arts):
        a.save(str(tmp_path / f"m{i}.npz"))
    budget = 2 * arts[0].nbytes() + 8              # room for two engines
    reg = ArtifactRegistry(memory_budget_bytes=budget, warmup_on_load=False,
                           engine_opts=ENGINE_OPTS)
    reg.add_directory(str(tmp_path))
    reg.get_engine("m0")
    reg.get_engine("m1")
    assert reg.eviction_count == 0
    reg.get_engine("m2")                           # busts the budget
    assert reg.eviction_count == 1
    snap = reg.snapshot()
    assert snap["loaded"] == 2
    assert snap["loaded_bytes"] <= budget
    # m0 was least recently used -> evicted (arrays dropped, path kept)
    e0 = reg._entries[reg.resolve("m0")]
    assert e0.engine is None and e0.artifact is None and e0.path is not None
    # transparent reload, still correct
    _, eng = reg.get_engine("m0")
    Z = np.random.default_rng(1).standard_normal((3, 8)).astype(np.float32) * 0.3
    np.testing.assert_allclose(
        eng.predict(Z)[0],
        SVMEngine(arts[0], None, **ENGINE_OPTS).predict(Z)[0],
        rtol=1e-6, atol=1e-6,
    )
    assert reg.loads == 4                          # 3 cold loads + 1 reload


def test_registry_in_memory_entry_never_loses_arrays():
    """An artifact registered without a backing file keeps its arrays on
    eviction (they are the only copy) — only the engine is dropped."""
    arts = [maclaurin.compile(_svm(s)) for s in (1, 2)]
    reg = ArtifactRegistry(memory_budget_bytes=arts[0].nbytes() + 8,
                           warmup_on_load=False, engine_opts=ENGINE_OPTS)
    d0 = reg.register(arts[0], PublishSpec(alias="m0"))
    reg.register(arts[1], PublishSpec(alias="m1"))
    reg.get_engine("m0")
    reg.get_engine("m1")
    assert reg.eviction_count == 1
    entry = reg._entries[d0]
    assert entry.engine is None and entry.artifact is not None


# ---------------------------------------------------------------- scheduler


def test_microbatcher_coalesces_one_bucket_fill():
    m = _svm(5)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    eng.warmup([8])
    with MicroBatcher(eng, max_wait_us=200_000, flush_rows=8) as mb:
        rng = np.random.default_rng(2)
        Zs = [rng.standard_normal((1, 8)).astype(np.float32) * 0.3
              for _ in range(8)]
        futs = [mb.submit(Z) for Z in Zs]          # 8 rows == flush_rows
        for Z, f in zip(Zs, futs):
            got = f.result(timeout=10).values
            np.testing.assert_allclose(got, eng.predict(Z)[0],
                                       rtol=1e-6, atol=1e-6)
        snap = mb.telemetry.snapshot()
        assert snap["flushes"] == 1                # ONE engine step for all 8
        assert snap["requests"] == 8
        assert snap["coalescing_factor"] == 8.0
        assert snap["deadline_flushes"] == 0       # the bucket filled


def test_microbatcher_deadline_flushes_lone_request():
    m = _svm(5)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    eng.warmup([8])
    with MicroBatcher(eng, max_wait_us=2_000, flush_rows=64) as mb:
        Z = np.random.default_rng(3).standard_normal((2, 8)).astype(np.float32)
        t0 = time.perf_counter()
        res = mb.submit(Z).result(timeout=10)
        np.testing.assert_allclose(res.values, eng.predict(Z)[0],
                                   rtol=1e-6, atol=1e-6)
        assert time.perf_counter() - t0 < 5.0      # deadline, not forever
        assert mb.telemetry.snapshot()["deadline_flushes"] >= 1


def test_microbatcher_preserves_row_order_under_concurrency():
    """Every concurrent caller gets exactly its rows, in its order — the
    scatter is exercised with per-request distinct values."""
    m = _svm(6)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    eng.warmup()
    rng = np.random.default_rng(4)
    Zs = _batches(rng, 24)
    expected = [eng.predict(Z)[0] for Z in Zs]
    results = [None] * len(Zs)
    with MicroBatcher(eng, max_wait_us=1_000, flush_rows=16) as mb:
        def client(i):
            results[i] = mb.submit(Zs[i]).result(timeout=10)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(Zs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, res in enumerate(results):
        assert len(res) == Zs[i].shape[0]
        np.testing.assert_allclose(res.values, expected[i],
                                   rtol=1e-6, atol=1e-6)


def test_microbatcher_zero_steady_state_recompiles():
    m = _svm(7)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    eng.warmup()                                   # all buckets precompiled
    before = eng.jit_cache_size()
    rng = np.random.default_rng(5)
    Zs = _batches(rng, 40)
    with MicroBatcher(eng, max_wait_us=500, flush_rows=8) as mb:
        futs = [mb.submit(Z) for Z in Zs]
        for f in futs:
            f.result(timeout=10).values
    assert eng.jit_cache_size() == before          # coalescing added no traces


def test_microbatcher_survives_cancelled_future():
    """A client cancelling its queued future must not kill the flush
    worker — later requests still get served."""
    m = _svm(5)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    eng.warmup([8])
    with MicroBatcher(eng, max_wait_us=20_000, flush_rows=64) as mb:
        doomed = mb.submit(np.zeros((1, 8), np.float32))
        assert doomed.cancel()                     # still queued -> cancellable
        Z = np.random.default_rng(12).standard_normal((2, 8)).astype(np.float32)
        res = mb.submit(Z).result(timeout=10)      # worker must still be alive
        np.testing.assert_allclose(res.values, eng.predict(Z)[0],
                                   rtol=1e-6, atol=1e-6)


def test_microbatcher_empty_submit_is_free():
    """A zero-row request resolves immediately with empty outputs and
    burns no engine step (and no padding statistics)."""
    m = _svm(5)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    with MicroBatcher(eng, max_wait_us=1_000) as mb:
        before = eng.stats.snapshot()
        res = mb.submit(np.zeros((0, 8), np.float32)).result(timeout=10)
        assert res.values.shape == (0,)
        assert res.valid.shape == (0,) and res.labels.shape == (0,)
        assert len(res) == 0
        assert eng.stats.snapshot() == before      # engine never touched


def test_runtime_eviction_retires_idle_batcher():
    """LRU eviction must release the engine even when the Runtime holds a
    batcher for it — the batcher is retired via the evict listener."""
    arts = [maclaurin.compile(_svm(s)) for s in (1, 2)]
    with Runtime(memory_budget_bytes=arts[0].nbytes() + 8, max_wait_us=200,
                 warmup_on_load=False, engine_opts=ENGINE_OPTS) as rt:
        d0 = rt.publish("m0", arts[0])
        rt.publish("m1", arts[1])
        Z = np.random.default_rng(13).standard_normal((2, 8)).astype(np.float32)
        v0 = rt.predict("m0", Z)[0]
        rt.predict("m1", Z)                        # busts the budget, evicts m0
        assert rt.registry.eviction_count == 1
        assert d0 not in rt._batchers              # batcher retired with engine
        # transparent reload on next use, same answers
        np.testing.assert_allclose(rt.predict("m0", Z)[0], v0,
                                   rtol=1e-6, atol=1e-6)


def test_runtime_warmup_without_warmup_on_load():
    with Runtime(warmup_on_load=False, engine_opts=ENGINE_OPTS) as rt:
        rt.publish("m", maclaurin.compile(_svm(4)))
        assert rt.warmup("m") >= 4                 # all buckets compiled NOW


def test_engine_result_split_rejects_bad_sizes():
    m = _svm(5)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    res = eng.submit(np.zeros((5, 8), np.float32))
    with pytest.raises(ValueError):
        res.split([2, 2])                          # 4 != 5


def test_slice_result_defers_and_shares_one_materialize():
    m = _svm(5)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    Z = np.random.default_rng(6).standard_normal((6, 8)).astype(np.float32) * 0.3
    res = eng.submit(Z)
    a, b = res.split([2, 4])
    assert res._done is None                       # nothing synced yet
    _ = a.values                                   # first slice materializes
    assert res._done is not None
    np.testing.assert_allclose(np.concatenate([a.values, b.values]),
                               eng.predict(Z)[0], rtol=1e-6, atol=1e-6)


# ------------------------------------------------- fourier artifact fallback


def test_fourier_artifact_fallback_through_runtime():
    """A fourier artifact whose compile-time verdict violates the budget
    must send EVERY coalesced row down the exact rbf_pred path, and the
    scatter must hand each concurrent request its own rows in order."""
    m = _svm(8, d=6, n_sv=30)
    art = fourier.compile(m, num_features=32, err_tolerance=0.0)   # verdict: invalid
    assert art.meta["valid_globally"] is False
    rng = np.random.default_rng(7)
    Zs = [rng.standard_normal((n, 6)).astype(np.float32) * 0.3
          for n in (1, 3, 2, 4, 1, 2, 3, 1)]
    with Runtime(max_wait_us=100_000, flush_rows=17,
                 engine_opts=ENGINE_OPTS) as rt:
        rt.publish("rff", art, PublishSpec(exact=m))
        rt.warmup("rff")
        results = [None] * len(Zs)

        def client(i):
            results[i] = rt.submit("rff", Zs[i]).result(timeout=10)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(Zs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, res in enumerate(results):
            assert not res.valid.any()             # per-ARTIFACT verdict
            np.testing.assert_allclose(            # exact path, request order
                res.values, _exact_scores(m, Zs[i])[:, 0],
                rtol=1e-4, atol=1e-4,
            )
        stats = rt.stats("rff")
        assert stats["fallback_rate"] == 1.0       # every row fell back


# ----------------------------------------------------------------- hot swap


def test_alias_hot_swap_atomic():
    m1, m2 = _svm(1, bias=5.0), _svm(1, bias=-5.0)
    with Runtime(max_wait_us=200, engine_opts=ENGINE_OPTS) as rt:
        d1 = rt.publish("det", maclaurin.compile(m1))
        Z = np.random.default_rng(8).standard_normal((3, 8)).astype(np.float32) * 0.3
        v1 = rt.predict("det", Z)[0]
        d2 = rt.publish("det", maclaurin.compile(m2))      # hot-swap
        assert d1 != d2
        v2 = rt.predict("det", Z)[0]
        np.testing.assert_allclose(v2 - v1, np.full(3, -10.0), atol=1e-4)
        # the old digest remains servable (immutable content address)
        np.testing.assert_allclose(rt.predict(d1, Z)[0], v1, rtol=1e-6)


def test_alias_hot_swap_mid_traffic():
    """Clients pounding an alias while it is re-pointed must only ever see
    a COMPLETE old-model or new-model answer, never a torn mix, and the
    swap must take effect for post-swap traffic."""
    m_old, m_new = _svm(2, bias=5.0), _svm(2, bias=-5.0)
    a_old, a_new = maclaurin.compile(m_old), maclaurin.compile(m_new)
    Z = np.random.default_rng(9).standard_normal((2, 8)).astype(np.float32) * 0.3
    with Runtime(max_wait_us=200, engine_opts=ENGINE_OPTS) as rt:
        rt.publish("det", a_old)
        rt.warmup("det")
        want_old = rt.predict("det", Z)[0].copy()
        want_new = SVMEngine(a_new, None, **ENGINE_OPTS).predict(Z)[0]
        stop = threading.Event()
        errors = []
        saw = {"old": 0, "new": 0}

        def client():
            while not stop.is_set():
                got = rt.predict("det", Z)[0]
                if np.allclose(got, want_old, atol=1e-4):
                    saw["old"] += 1
                elif np.allclose(got, want_new, atol=1e-4):
                    saw["new"] += 1
                else:
                    errors.append(got)
                    return

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.15)
        rt.publish("det", a_new)                   # swap under live traffic
        np.testing.assert_allclose(rt.predict("det", Z)[0], want_new, atol=1e-4)
        time.sleep(0.25)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, f"torn/unknown result observed: {errors[0]}"
        assert saw["old"] > 0                      # traffic before the swap
        assert saw["new"] > 0                      # ... and after


# ------------------------------------------------------------ thread safety


def test_engine_stats_thread_safe_under_concurrent_predict():
    """Bare-int increments lose updates under contention; the locked stats
    must account every row exactly."""
    m = _svm(3)
    eng = SVMEngine(maclaurin.compile(m), None, **ENGINE_OPTS)
    eng.warmup([8])
    Z = np.zeros((3, 8), np.float32)
    threads_n, reps = 8, 50

    def worker():
        for _ in range(reps):
            eng.predict(Z)

    base = eng.stats.snapshot()
    threads = [threading.Thread(target=worker) for _ in range(threads_n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = eng.stats.snapshot()
    assert snap["instances"] - base["instances"] == threads_n * reps * 3
    assert snap["batches"] - base["batches"] == threads_n * reps
    assert sum(snap["bucket_hits"].values()) - sum(base["bucket_hits"].values()) \
        == threads_n * reps


@pytest.mark.stress
def test_runtime_multithreaded_stress():
    """Bounded multi-model stress: concurrent clients over two models with
    mixed batch sizes; every response correct, every row accounted."""
    m1, m2 = _svm(1), _svm(2, d=8)
    a1, a2 = maclaurin.compile(m1), maclaurin.compile(m2)
    ref1 = SVMEngine(a1, None, **ENGINE_OPTS)
    ref2 = SVMEngine(a2, None, **ENGINE_OPTS)
    clients, reps = 8, 25
    rng = np.random.default_rng(10)
    work = [  # per client: (model, Z, expected)
        [("m1", Z, ref1.predict(Z)[0]) if rng.random() < 0.5
         else ("m2", Z, ref2.predict(Z)[0])
         for Z in _batches(rng, reps)]
        for _ in range(clients)
    ]
    with Runtime(max_wait_us=300, flush_rows=16, engine_opts=ENGINE_OPTS) as rt:
        rt.publish("m1", a1)
        rt.publish("m2", a2)
        rt.warmup("m1"), rt.warmup("m2")
        errors = []

        def client(items):
            try:
                futs = [(rt.submit(name, Z), want) for name, Z, want in items]
                for fut, want in futs:
                    got = fut.result(timeout=30).values
                    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            except Exception as e:                 # surfaced after join
                errors.append(e)

        threads = [threading.Thread(target=client, args=(w,)) for w in work]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[0]
        assert time.perf_counter() - t0 < 30.0     # bounded
        stats = rt.stats()
        total_requests = sum(
            ms["requests"] for ms in stats["models"].values()
        )
        total_rows = sum(ms["rows"] for ms in stats["models"].values())
        assert total_requests == clients * reps
        assert total_rows == sum(Z.shape[0] for w in work for _, Z, _ in w)
        # the runtime coalesced: strictly fewer engine steps than requests
        total_flushes = sum(ms["flushes"] for ms in stats["models"].values())
        assert total_flushes <= total_requests
