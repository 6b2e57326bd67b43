"""Robustness layer: admission control (bounded queues, typed shed,
deadlines, SLO tightening), fault isolation (per-batch failure scatter,
circuit breaker degrading to the exact path, half-open recovery),
registry corruption quarantine, shutdown/evict future accounting, the
deterministic fault-injection harness itself, and the DriftGuard
recompile → canary → alias-flip self-healing loop. The chaos tests run
seeded faults under multi-threaded load and assert EXACT accounting:
every submitted request is served, shed, failed, or expired — and
nothing hangs."""

import os
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gamma_max
from repro.core.rbf import SVMModel, rbf_kernel
from repro.core.families import Budget, compile_model, maclaurin
from repro.serve import PublishSpec, Runtime
from repro.serve.runtime import (
    ENGINE_STEP,
    REGISTRY_LOAD,
    ArtifactCorrupt,
    ArtifactRegistry,
    BatcherClosed,
    CircuitBreaker,
    DeadlineExceeded,
    DriftGuard,
    FaultInjector,
    InjectedFault,
    ReservoirSampler,
    RuntimeOverloaded,
)

ENGINE_OPTS = dict(min_bucket=8, max_batch=64)


def _svm(seed=0, d=8, n_sv=40, bias=0.1, scale=0.6):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * scale
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                    b=jnp.float32(bias), gamma=jnp.float32(gamma))


def _exact_scores(m, Z):
    ay2 = m.alpha_y if m.alpha_y.ndim == 2 else m.alpha_y[None, :]
    b2 = jnp.reshape(m.b, (ay2.shape[0],))
    return np.asarray(rbf_kernel(jnp.asarray(Z), m.X, m.gamma) @ ay2.T + b2[None, :])


def _rows(rng, n, d=8, scale=0.3):
    return rng.standard_normal((n, d)).astype(np.float32) * scale


# ---------------------------------------------------------- circuit breaker


def test_breaker_state_machine():
    t = [0.0]
    br = CircuitBreaker(fail_threshold=3, reset_after_s=1.0, clock=lambda: t[0])
    assert br.state == "closed" and br.allow_fast()
    br.record_failure(); br.record_failure()
    assert br.state == "closed"                      # below threshold
    br.record_failure()
    assert br.state == "open" and not br.allow_fast()
    assert 0.0 < br.retry_after() <= 1.0
    t[0] = 0.5
    assert not br.allow_fast()                       # still inside reset window
    t[0] = 1.5
    assert br.allow_fast()                           # this call IS the probe
    assert br.state == "half_open"
    br.record_failure()                              # probe fails -> reopen
    assert br.state == "open"
    t[0] = 3.0
    assert br.allow_fast() and br.state == "half_open"
    br.record_success()                              # probe passes -> closed
    assert br.state == "closed" and br.consecutive_failures == 0
    assert br.retry_after() == 0.0


def test_breaker_success_resets_failure_streak():
    br = CircuitBreaker(fail_threshold=2)
    br.record_failure(); br.record_success(); br.record_failure()
    assert br.state == "closed"                      # streak broken, not 2-in-a-row


# ------------------------------------------------------------ fault harness


def test_fault_injector_is_deterministic():
    def verdicts(seed, n=64):
        fi = FaultInjector(seed, engine_fault_rate=0.3, slow_step_rate=0.2,
                           slow_step_s=0.0, sleep=lambda s: None)
        out = []
        for _ in range(n):
            try:
                fi.check(ENGINE_STEP)
                out.append("ok")
            except InjectedFault:
                out.append("fault")
        return out

    a, b = verdicts(7), verdicts(7)
    assert a == b                                    # same seed -> same run
    assert a != verdicts(8)                          # different seed differs
    assert "fault" in a and "ok" in a


def test_fault_injector_scripts_override_rates():
    fi = FaultInjector(0, engine_fault_rate=1.0)     # every check would fault
    fi.pass_next(ENGINE_STEP, 2)
    fi.check(ENGINE_STEP)                            # scripted pass wins
    fi.check(ENGINE_STEP)
    with pytest.raises(InjectedFault) as ei:
        fi.check(ENGINE_STEP)                        # back on the seeded rate
    assert ei.value.site == ENGINE_STEP and ei.value.ordinal == 3
    snap = fi.snapshot()[ENGINE_STEP]
    assert snap["checks"] == 3 and snap["faults"] == 1


def test_corrupt_bytes_deterministic_and_corrupting():
    data = bytes(range(256)) * 8
    c1 = FaultInjector.corrupt_bytes(data, seed=5)
    c2 = FaultInjector.corrupt_bytes(data, seed=5)
    assert c1 == c2 and c1 != data and len(c1) == len(data)
    assert FaultInjector.corrupt_bytes(data, seed=6) != c1


# -------------------------------------------------------- admission control


def test_bounded_queue_sheds_with_retry_after():
    m = _svm(1)
    art = maclaurin.compile(m)
    fi = FaultInjector(0, slow_step_rate=1.0, slow_step_s=0.02)
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 max_queue_rows=16, max_wait_us=100.0) as rt:
        rt.publish("m", art, PublishSpec(exact=m))
        rt.warmup("m")                               # every bucket compiled
        rt.predict("m", _rows(np.random.default_rng(0), 2))  # warm
        _, eng = rt.registry.get_engine("m")
        compiled = eng.jit_cache_size()
        rng = np.random.default_rng(1)
        futs, shed = [], 0
        for _ in range(80):
            try:
                futs.append(rt.submit("m", _rows(rng, 4)))
            except RuntimeOverloaded as e:
                shed += 1
                assert e.retry_after_s > 0.0         # server names its backoff
        for f in futs:
            f.result(timeout=30.0)                   # every admitted one serves
        st = rt.stats("m")
        assert shed > 0
        assert st["shed_requests"] == shed
        assert st["requests"] == len(futs) + 1       # shed never enqueued (+warm)
        assert st["queue_rows"] == 0                 # accounting drains to zero
        # waiting rows are bounded by admission; a popped batch counts
        # until its flush is recorded, so the high-water is at most 2x
        assert 0 < st["max_queue_rows"] <= 2 * 16
        assert eng.jit_cache_size() == compiled      # the burst compiled nothing


def test_empty_queue_always_admits_oversized_request():
    m = _svm(2)
    with Runtime(engine_opts=ENGINE_OPTS, max_queue_rows=8) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        Z = _rows(np.random.default_rng(0), 32)      # 4x the queue bound
        vals, _ = rt.predict("m", Z)                 # admitted: queue was empty
        assert vals.shape == (32,)


def test_deadline_exceeded_fails_future_not_batcher():
    m = _svm(3)
    with Runtime(engine_opts=ENGINE_OPTS, max_wait_us=50_000.0) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rng = np.random.default_rng(0)
        fut = rt.submit("m", _rows(rng, 1), deadline_s=0.005)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=10.0)
        st = rt.stats("m")
        assert st["deadline_timeouts"] == 1
        assert st["queue_rows"] == 0                 # expired rows left the gauge
        # the batcher survived: a deadline-free request still serves
        vals, _ = rt.predict("m", _rows(rng, 3))
        assert vals.shape == (3,)


def test_queue_pressure_tightens_wait():
    m = _svm(4)
    with Runtime(engine_opts=ENGINE_OPTS, max_queue_rows=16,
                 max_wait_us=10_000.0) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rng = np.random.default_rng(0)
        # 3 queued rows on a 16-row bound is ~19% pressure: below the
        # 8-row bucket (so the flush is deadline-triggered) but above the
        # 10% threshold that marks the flush as tightened
        rt.submit("m", _rows(rng, 3)).result(timeout=10.0)
        st = rt.stats("m")
        assert st["deadline_flushes"] >= 1
        assert st["tightened_waits"] >= 1
        # an UNBOUNDED runtime never tightens (no pressure signal)
        with Runtime(engine_opts=ENGINE_OPTS, max_wait_us=10_000.0) as rt2:
            rt2.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
            rt2.submit("m", _rows(rng, 3)).result(timeout=10.0)
            assert rt2.stats("m")["tightened_waits"] == 0


# ----------------------------------------------------------- fault isolation


def test_engine_fault_fails_only_its_batch():
    m = _svm(5)
    fi = FaultInjector(0)
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 breaker=dict(fail_threshold=5)) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))               # warm
        fi.fail_next(ENGINE_STEP, 1)
        doomed = rt.submit("m", _rows(rng, 3))
        with pytest.raises(InjectedFault):
            doomed.result(timeout=10.0)
        # the flush worker survived the exception: next batch serves fine
        Z = _rows(rng, 4)
        vals, _ = rt.predict("m", Z)
        np.testing.assert_allclose(
            vals, _exact_scores(m, Z)[:, 0], atol=0.15
        )
        st = rt.stats("m")
        assert st["batch_failures"] == 1
        assert st["failed_requests"] == 1 and st["failed_rows"] == 3
        assert st["breaker"]["state"] == "closed"    # one failure < threshold


def test_fault_on_one_model_leaves_others_serving():
    m1, m2 = _svm(6), _svm(7)
    fi = FaultInjector(0)
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 breaker=dict(fail_threshold=1, reset_after_s=60.0)) as rt:
        rt.publish("a", maclaurin.compile(m1), PublishSpec(exact=m1))
        rt.publish("b", maclaurin.compile(m2), PublishSpec(exact=m2))
        rng = np.random.default_rng(0)
        rt.predict("a", _rows(rng, 2))
        rt.predict("b", _rows(rng, 2))
        fi.fail_next(ENGINE_STEP, 1)
        with pytest.raises(InjectedFault):
            rt.submit("a", _rows(rng, 2)).result(timeout=10.0)
        # "a" is now breaker-open (threshold 1) and degrades to exact;
        # "b" has its own breaker, untouched, and serves the fast path
        ra = rt.submit("a", _rows(rng, 3)).result(timeout=10.0)
        assert not np.asarray(ra.valid).any()        # exact-served rows
        rb = rt.submit("b", _rows(rng, 3)).result(timeout=10.0)
        assert rb.values.shape == (3,)
        assert rt.stats("a")["breaker"]["state"] == "open"
        assert rt.stats("b")["breaker"]["state"] == "closed"
        assert rt.stats("b")["batch_failures"] == 0


def test_breaker_degrades_to_exact_and_recovers():
    m = _svm(8)
    fi = FaultInjector(0)
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 breaker=dict(fail_threshold=2, reset_after_s=0.1)) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))
        _, eng = rt.registry.get_engine("m")
        compiled = eng.jit_cache_size()
        fi.fail_next(ENGINE_STEP, 2)
        for _ in range(2):
            with pytest.raises(InjectedFault):
                rt.submit("m", _rows(rng, 2)).result(timeout=10.0)
        st = rt.stats("m")
        assert st["breaker"]["state"] == "open" and st["breaker"]["trips"] == 1
        # open: served EXACTLY (scores match the RBF expansion, not the
        # approximation), valid all-False, fast-path fallback stats untouched
        Z = _rows(rng, 5)
        res = rt.submit("m", Z).result(timeout=10.0)
        np.testing.assert_allclose(
            np.asarray(res.values), _exact_scores(m, Z)[:, 0],
            rtol=1e-4, atol=1e-5,
        )
        assert not np.asarray(res.valid).any()
        st = rt.stats("m")
        assert st["breaker"]["degraded_requests"] == 1
        assert st["breaker"]["degraded_rows"] == 5
        assert st["breaker"]["shed_requests"] == 0   # an exact model serves
        assert st["engine"]["degraded_instances"] == 5
        # the exact path compiles programs of its own, never a fast bucket
        assert eng.jit_cache_size() == compiled
        # degraded traffic must not read as drift (validity window clean)
        assert st["fallback_window"]["rows"] == 0 or \
            st["fallback_window"]["invalid"] < st["fallback_window"]["rows"]
        time.sleep(0.15)                             # past reset_after_s
        res = rt.submit("m", _rows(rng, 3)).result(timeout=10.0)  # probe
        st = rt.stats("m")
        assert st["breaker"]["state"] == "closed"
        assert st["breaker"]["probes"] >= 1


def test_open_breaker_without_exact_sheds_typed():
    m = _svm(9)
    fi = FaultInjector(0)
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 breaker=dict(fail_threshold=1, reset_after_s=60.0)) as rt:
        rt.publish("m", maclaurin.compile(m))        # NO exact model
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))
        fi.fail_next(ENGINE_STEP, 1)
        with pytest.raises(InjectedFault):
            rt.submit("m", _rows(rng, 2)).result(timeout=10.0)
        fut = rt.submit("m", _rows(rng, 2))
        with pytest.raises(RuntimeOverloaded) as ei:
            fut.result(timeout=10.0)
        assert ei.value.retry_after_s > 0.0
        assert rt.stats("m")["breaker"]["shed_requests"] == 1


# ------------------------------------------------------- registry hardening


def test_add_file_rejects_corrupt_and_truncated(tmp_path):
    art = maclaurin.compile(_svm(10))
    good = str(tmp_path / "good.npz")
    art.save(good)
    ArtifactRegistry().add_file(good)                # sanity: clean file indexes

    flipped = str(tmp_path / "flipped.npz")
    art.save(flipped)
    FaultInjector.corrupt_file(flipped, seed=1)
    with pytest.raises(ArtifactCorrupt):
        ArtifactRegistry().add_file(flipped)

    trunc = str(tmp_path / "trunc.npz")
    art.save(trunc)
    FaultInjector.truncate_file(trunc, keep_fraction=0.4)
    with pytest.raises(ArtifactCorrupt):
        ArtifactRegistry().add_file(trunc)


def test_mutated_file_never_serves_under_old_digest(tmp_path):
    m = _svm(11)
    art = maclaurin.compile(m)
    path = str(tmp_path / "m.npz")
    art.save(path)
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS)
    digest = reg.add_file(path, alias="m@latest")
    # mutate on disk BEFORE first load: the digest names the old bytes
    other = maclaurin.compile(_svm(12))
    other.save(path)                                 # valid npz, wrong content
    with pytest.raises(ArtifactCorrupt) as ei:
        reg.get_engine("m")
    assert ei.value.digest == digest
    # quarantined: subsequent resolves fail fast without touching disk
    with pytest.raises(ArtifactCorrupt) as ei2:
        reg.get_engine("m")
    assert "quarantined" in str(ei2.value)
    assert reg.snapshot()["quarantined"] == 1


def test_reload_after_evict_reverifies_sha(tmp_path):
    m = _svm(13)
    art = maclaurin.compile(m)
    path = str(tmp_path / "m.npz")
    art.save(path)
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS,
                           memory_budget_bytes=1)    # evict everything cold
    reg.add_file(path, alias="m@latest")
    other = maclaurin.compile(_svm(14), dtype="float32")
    d2 = reg.register(other, PublishSpec(alias="other@latest"))
    _, e1 = reg.get_engine("m@latest")               # load #1 verifies + serves
    reg.get_engine("other@latest")                   # budget=1 evicts "m"
    assert reg.eviction_count >= 1
    FaultInjector.corrupt_file(path, seed=2)         # mutate while evicted
    with pytest.raises(ArtifactCorrupt):
        reg.get_engine("m@latest")                   # reload re-hashes, refuses


def test_injected_load_fault_is_transient_not_quarantined(tmp_path):
    art = maclaurin.compile(_svm(15))
    path = str(tmp_path / "m.npz")
    art.save(path)
    fi = FaultInjector(0)
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS,
                           fault_injector=fi)
    reg.add_file(path, alias="m@latest")
    fi.fail_next(REGISTRY_LOAD, 1)
    with pytest.raises(InjectedFault):
        reg.get_engine("m")
    _, engine = reg.get_engine("m")                  # next resolve retries
    assert engine is not None
    assert reg.snapshot()["quarantined"] == 0


# ------------------------------------------------------ shutdown / eviction


def test_close_resolves_every_pending_future_and_joins_threads():
    m = _svm(16)
    fi = FaultInjector(0, slow_step_rate=1.0, slow_step_s=0.02)
    rt = Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 max_wait_us=50_000.0)
    rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
    rng = np.random.default_rng(0)
    rt.predict("m", _rows(rng, 2))
    batcher = rt._batchers[rt.registry.resolve("m")]
    futs = [rt.submit("m", _rows(rng, 2)) for _ in range(6)]
    t0 = time.perf_counter()
    rt.close()
    assert time.perf_counter() - t0 < 10.0
    resolved = 0
    for f in futs:
        assert f.done()                              # NOTHING left pending
        try:
            f.result(timeout=0)
            resolved += 1
        except (BatcherClosed, InjectedFault):
            resolved += 1
    assert resolved == len(futs)
    batcher._worker.join(timeout=5.0)                # regression: thread exits
    assert not batcher._worker.is_alive()
    with pytest.raises(BatcherClosed):
        batcher.submit(_rows(rng, 1))


def test_eviction_mid_traffic_resolves_pending_futures():
    m1, m2 = _svm(17), _svm(18)
    rt = Runtime(engine_opts=ENGINE_OPTS, memory_budget_bytes=1,
                 warmup_on_load=False, max_wait_us=20_000.0)
    rt.publish("a", maclaurin.compile(m1), PublishSpec(exact=m1))
    rt.publish("b", maclaurin.compile(m2), PublishSpec(exact=m2))
    rng = np.random.default_rng(0)
    futs = [rt.submit("a", _rows(rng, 2)) for _ in range(4)]
    rt.predict("b", _rows(rng, 2))                   # forces eviction of "a"
    for f in futs:                                   # evict close() drained them
        r = f.result(timeout=10.0)
        assert r.values.shape == (2,)
    rt.close()


# --------------------------------------------------------------- chaos suite


def _chaos_run(seed, *, threads=8, per_thread=25, fi_kwargs=None,
               runtime_kwargs=None, deadline_every=0):
    """Seeded multi-threaded storm; returns exact outcome accounting."""
    m = _svm(seed)
    fi = FaultInjector(seed, **(fi_kwargs or {}))
    counts = {"served": 0, "shed": 0, "failed": 0, "expired": 0}
    lock = threading.Lock()
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 breaker=dict(fail_threshold=3, reset_after_s=0.05),
                 **(runtime_kwargs or {})) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        try:
            rt.predict("m", _rows(np.random.default_rng(seed), 2))
        except InjectedFault:
            pass                                     # warm-up is best-effort
                                                     # under a fault rate

        def client(tid):
            rng = np.random.default_rng((seed, tid))
            got = {"served": 0, "shed": 0, "failed": 0, "expired": 0}
            for i in range(per_thread):
                dl = (0.002 if deadline_every and i % deadline_every == 0
                      else None)
                try:
                    fut = rt.submit("m", _rows(rng, int(rng.integers(1, 5))),
                                    deadline_s=dl)
                except RuntimeOverloaded:
                    got["shed"] += 1
                    continue
                try:
                    fut.result(timeout=30.0)
                    got["served"] += 1
                except DeadlineExceeded:
                    got["expired"] += 1
                except (InjectedFault, RuntimeOverloaded):
                    got["failed"] += 1
            with lock:
                for k in got:
                    counts[k] += got[k]

        ts = [threading.Thread(target=client, args=(t,)) for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60.0)
            assert not t.is_alive(), "client thread hung — a future never resolved"
        stats = rt.stats("m")
    return counts, stats, threads * per_thread


@pytest.mark.stress
def test_chaos_engine_faults_exact_accounting():
    counts, stats, submitted = _chaos_run(
        21, fi_kwargs=dict(engine_fault_rate=0.15),
        runtime_kwargs=dict(max_queue_rows=64),
    )
    assert sum(counts.values()) == submitted         # every request accounted
    assert counts["served"] > 0
    assert stats["queue_rows"] == 0                  # nothing left behind
    # requests the batcher admitted == served + failed through futures
    assert stats["shed_requests"] == counts["shed"]


@pytest.mark.stress
def test_chaos_slow_steps_with_deadlines_and_shedding():
    counts, stats, submitted = _chaos_run(
        22,
        fi_kwargs=dict(engine_fault_rate=0.05, slow_step_rate=0.5,
                       slow_step_s=0.01),
        runtime_kwargs=dict(max_queue_rows=48, max_wait_us=2_000.0),
        deadline_every=5,
    )
    assert sum(counts.values()) == submitted
    assert counts["served"] > 0
    assert stats["queue_rows"] == 0
    assert stats["deadline_timeouts"] == counts["expired"]


@pytest.mark.stress
def test_chaos_corrupt_file_under_load(tmp_path):
    """A model whose file is corrupted mid-flight quarantines; the OTHER
    model keeps serving through the same storm; accounting is exact."""
    m1, m2 = _svm(23), _svm(24)
    p1 = str(tmp_path / "a.npz")
    maclaurin.compile(m1).save(p1)
    rt = Runtime(engine_opts=ENGINE_OPTS, warmup_on_load=False,
                 memory_budget_bytes=1)              # every swap evicts
    rt.registry.add_file(p1, alias="a@latest", exact=m1)
    rt.publish("b", maclaurin.compile(m2), PublishSpec(exact=m2))
    rt.predict("a", _rows(np.random.default_rng(0), 2))
    FaultInjector.corrupt_file(p1, seed=3)           # mutate behind the registry
    outcomes = {"served": 0, "corrupt": 0}
    lock = threading.Lock()

    def client(tid):
        rng = np.random.default_rng((23, tid))
        got = {"served": 0, "corrupt": 0}
        for i in range(20):
            model = "a" if (tid + i) % 2 == 0 else "b"
            try:
                fut = rt.submit(model, _rows(rng, 2))
                fut.result(timeout=30.0)
                got["served"] += 1
            except ArtifactCorrupt:
                assert model == "a"                  # only the mutated model
                got["corrupt"] += 1
        with lock:
            for k in got:
                outcomes[k] += got[k]

    ts = [threading.Thread(target=client, args=(t,)) for t in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60.0)
        assert not t.is_alive()
    assert outcomes["served"] + outcomes["corrupt"] == 8 * 20
    assert outcomes["served"] > 0                    # "b" never stopped
    rt.close()


# ------------------------------------------ interleaving conservation law


def _conservation_world(max_queue_rows, fault_rate, schedule, seed):
    """Replay one submit/outcome schedule; assert shed+served+failed+
    expired == submitted and no future is left unresolved."""
    m = _svm(seed % 7)
    fi = FaultInjector(seed, engine_fault_rate=fault_rate,
                       slow_step_rate=0.3, slow_step_s=0.003)
    submitted = served = shed = failed = expired = 0
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 max_queue_rows=max_queue_rows, max_wait_us=1_000.0,
                 breaker=dict(fail_threshold=2, reset_after_s=0.02)) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rng = np.random.default_rng(seed)
        futs = []
        for step in schedule:
            submitted += 1
            dl = 0.002 if step % 3 == 0 else None
            try:
                futs.append(rt.submit("m", _rows(rng, (step % 4) + 1),
                                      deadline_s=dl))
            except RuntimeOverloaded:
                shed += 1
            if step % 5 == 0:
                time.sleep(0.002)                    # vary the interleaving
        for f in futs:
            try:
                f.result(timeout=30.0)
                served += 1
            except DeadlineExceeded:
                expired += 1
            except (InjectedFault, RuntimeOverloaded, BatcherClosed):
                failed += 1
    assert shed + served + failed + expired == submitted
    assert all(f.done() for f in futs)


@pytest.mark.stress
@pytest.mark.parametrize("seed", [31, 32, 33, 34])
def test_conservation_seeded_interleavings(seed):
    rng = np.random.default_rng(seed)
    schedule = [int(s) for s in rng.integers(0, 16, size=40)]
    _conservation_world(max_queue_rows=int(rng.integers(8, 48)),
                        fault_rate=float(rng.uniform(0, 0.3)),
                        schedule=schedule, seed=seed)


@pytest.mark.stress
def test_conservation_property_hypothesis():
    """Property form of the conservation law (runs when hypothesis is
    installed; the seeded parametrization above always runs)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(
        schedule=st.lists(st.integers(0, 15), min_size=1, max_size=30),
        max_queue_rows=st.integers(8, 48),
        fault_rate=st.floats(0, 0.3),
        seed=st.integers(0, 2**16),
    )
    @hyp.settings(max_examples=15, deadline=None)
    def prop(schedule, max_queue_rows, fault_rate, seed):
        _conservation_world(max_queue_rows, fault_rate, schedule, seed)

    prop()


# -------------------------------------------------------------- drift guard


def test_reservoir_sampler_seeded_and_bounded():
    r1 = ReservoirSampler(capacity=16, seed=3)
    r2 = ReservoirSampler(capacity=16, seed=3)
    rng = np.random.default_rng(0)
    stream = rng.standard_normal((200, 4)).astype(np.float32)
    for i in range(0, 200, 7):
        r1.offer(stream[i:i + 7])
        r2.offer(stream[i:i + 7])
    assert len(r1) == 16 and r1.seen == 200
    np.testing.assert_array_equal(r1.sample(), r2.sample())  # seeded replay
    # the sample is drawn from the stream, uniformly-ish over its span
    s = r1.sample()
    assert all(any(np.array_equal(row, x) for x in stream) for row in s)


def test_drift_guard_green_window_is_cheap_noop():
    m = _svm(26, scale=0.4)
    art = compile_model(m, Budget(max_err=0.05),
                        sample=_rows(np.random.default_rng(0), 128, scale=0.3))
    with Runtime(engine_opts=ENGINE_OPTS) as rt:
        rt.publish("clf", art, PublishSpec(exact=m))
        guard = DriftGuard(rt, "clf", exact=m, budget=Budget(max_err=0.05),
                           threshold=0.5, min_rows=32, seed=5).attach()
        rng = np.random.default_rng(1)
        for _ in range(6):
            rt.submit("clf", _rows(rng, 8, scale=0.3)).result().values
        v = guard.check()
        assert not v["healed"]
        assert rt.stats("clf")["canary"]["recompiles"] == 0


def test_drift_guard_end_to_end_heal():
    """The acceptance-criteria loop: in-distribution traffic serves the
    fast path; drifted traffic pushes the windowed fallback rate over
    threshold; the guard recompiles on reservoir-sampled traffic,
    canaries against the exact judge, flips the alias atomically with
    zero dropped in-flight requests; post-flip fallback drops."""
    m = _svm(27, scale=0.35)
    rng = np.random.default_rng(2)
    art = compile_model(m, Budget(max_err=0.05),
                        sample=_rows(rng, 256, scale=0.25),
                        families=("maclaurin",))
    with Runtime(engine_opts=ENGINE_OPTS) as rt:
        rt.publish("clf", art, PublishSpec(exact=m))
        guard = DriftGuard(rt, "clf", exact=m, budget=Budget(max_err=0.08),
                           threshold=0.3, min_rows=48, min_agreement=0.9,
                           capacity=192, seed=9).attach()
        # phase 1: in-distribution -> fast path, green window
        for i in range(8):
            r = rt.submit("clf", _rows(rng, 8, scale=0.25)).result()
            assert np.asarray(r.valid).all()
        assert guard.fallback_rate()["rate"] < 0.05
        assert not guard.check()["triggered"]
        old_digest = rt.registry.resolve("clf")

        # phase 2: drifted traffic (norms past the Maclaurin bound)
        in_flight = [rt.submit("clf", _rows(rng, 8, scale=1.5))
                     for _ in range(12)]
        for f in in_flight:
            # materializing triggers the exact fallback patch AND feeds
            # the validity window (deferred sync records on first touch)
            assert f.result(timeout=30.0).values.shape == (8,)
        window = guard.fallback_rate()
        assert window["rate"] > 0.3 and window["rows"] >= 48

        # phase 3: heal — submit more traffic DURING the flip to prove
        # nothing in flight is dropped by the alias swap
        concurrent = [rt.submit("clf", _rows(rng, 4, scale=1.5))
                      for _ in range(4)]
        verdict = guard.check()
        assert verdict["triggered"] and verdict["healed"], verdict
        assert verdict["agreement"] >= 0.9
        for f in concurrent:                         # zero dropped in-flight
            assert f.result(timeout=30.0).values.shape == (4,)

        new_digest = rt.registry.resolve("clf")
        assert new_digest == verdict["new_digest"] != old_digest
        old_stats = rt.stats(old_digest)
        assert old_stats["canary"]["recompiles"] == 1
        assert old_stats["canary"]["passed"] == 1

        # phase 4: the same drifted distribution now serves mostly fast
        for i in range(10):
            rt.submit("clf", _rows(rng, 8, scale=1.5)).result().values
        post = guard.fallback_rate()
        assert post["rate"] < 0.3, post              # healed model fits traffic


def test_drift_guard_rejects_bad_canary():
    """A candidate that disagrees with the exact judge must NOT flip."""
    m = _svm(28, scale=0.35)
    rng = np.random.default_rng(3)
    art = compile_model(m, Budget(max_err=0.05),
                        sample=_rows(rng, 128, scale=0.25),
                        families=("maclaurin",))
    with Runtime(engine_opts=ENGINE_OPTS) as rt:
        rt.publish("clf", art, PublishSpec(exact=m))
        # min_agreement=1.01 is unreachable: every canary fails
        guard = DriftGuard(rt, "clf", exact=m, budget=Budget(max_err=0.08),
                           threshold=0.2, min_rows=32, min_agreement=1.01,
                           capacity=128, seed=11).attach()
        old_digest = rt.registry.resolve("clf")
        for _ in range(10):
            rt.submit("clf", _rows(rng, 8, scale=1.5)).result().values
        verdict = guard.check()
        assert verdict["triggered"]
        assert not verdict["healed"]
        assert rt.registry.resolve("clf") == old_digest   # alias untouched
        st = rt.stats("clf")
        assert st["canary"]["failed"] >= 1 or "reason" in verdict


def test_drift_guard_cooldown_limits_heal_rate():
    m = _svm(29, scale=0.35)
    rng = np.random.default_rng(4)
    art = compile_model(m, Budget(max_err=0.05),
                        sample=_rows(rng, 128, scale=0.25),
                        families=("maclaurin",))
    with Runtime(engine_opts=ENGINE_OPTS) as rt:
        rt.publish("clf", art, PublishSpec(exact=m))
        guard = DriftGuard(rt, "clf", exact=m, budget=Budget(max_err=0.08),
                           threshold=0.2, min_rows=32, min_agreement=1.01,
                           capacity=128, seed=13, cooldown_s=300.0).attach()
        for _ in range(10):
            rt.submit("clf", _rows(rng, 8, scale=1.5)).result().values
        v1 = guard.check()                           # attempts (and fails canary)
        v2 = guard.check()                           # inside cooldown: no attempt
        assert v1["triggered"] and v2["triggered"]
        assert v2.get("reason") == "cooldown"
        assert rt.stats("clf")["canary"]["recompiles"] == 1
