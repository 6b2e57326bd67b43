"""Scale-out layer: replicated engine dispatch (least-loaded routing,
per-replica breakers, fault isolation, atomic replica retirement) and
head-sharded extreme multiclass serving (pad -> shard_map -> slice
parity). Runs on however many devices the host exposes — one in the
plain tier-1 suite, eight in CI's forced-host-device step — so every
assertion here is device-count agnostic.

Also covers the analytic cost prior: candidate pre-pruning in
``autotune`` (rank-and-prune, default always measured) and cost
pre-pruning in ``compile_model``.
"""

import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core import gamma_max
from repro.core.rbf import SVMModel, rbf_kernel
from repro.core.families import Budget, compile_model, fourier, maclaurin
from repro.kernels.common import autotune, cost, tuning
from repro.kernels.common.config import TileConfig
from repro.serve import PublishSpec, Runtime
from repro.serve.runtime import (
    ENGINE_STEP,
    ArtifactRegistry,
    FaultInjector,
    InjectedFault,
    MetricsRegistry,
    Observability,
)
from repro.serve.svm_engine import SVMEngine

ENGINE_OPTS = dict(min_bucket=8, max_batch=64)


def _svm(seed=0, d=8, n_sv=40, bias=0.1, scale=0.6):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * scale
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
    return SVMModel(
        X=jnp.asarray(X),
        alpha_y=jnp.asarray(ay),
        b=jnp.float32(bias),
        gamma=jnp.float32(gamma),
    )


def _svm_mc(seed=0, d=8, n_sv=40, k=6, scale=0.6):
    """One-vs-rest multiclass model: (k, n_sv) duals, (k,) biases."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * scale
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    ay = rng.standard_normal((k, n_sv)).astype(np.float32) * 0.5
    b = (rng.standard_normal(k) * 0.1).astype(np.float32)
    return SVMModel(
        X=jnp.asarray(X),
        alpha_y=jnp.asarray(ay),
        b=jnp.asarray(b),
        gamma=jnp.float32(gamma),
    )


def _exact_scores(m, Z):
    ay2 = m.alpha_y if m.alpha_y.ndim == 2 else m.alpha_y[None, :]
    b2 = jnp.reshape(m.b, (ay2.shape[0],))
    return np.asarray(
        rbf_kernel(jnp.asarray(Z), m.X, m.gamma) @ ay2.T + b2[None, :]
    )


def _rows(rng, n, d=8, scale=0.3):
    return rng.standard_normal((n, d)).astype(np.float32) * scale


def _head_mesh():
    return Mesh(np.array(jax.local_devices()), ("heads",))


# ---------------------------------------------------------- replica dispatch


def test_replicated_publish_spreads_flushes_and_conserves():
    m = _svm(1)
    art = maclaurin.compile(m)
    with Runtime(engine_opts=ENGINE_OPTS, max_wait_us=500.0) as rt:
        rt.publish("m", art, PublishSpec(exact=m, replicas=3))
        _, engines = rt.registry.get_engines("m")
        assert len(engines) == 3
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))  # warm + build
        cache_before = sum(e.jit_cache_size() for e in engines)
        # sequential submits: idle replicas tie on load, so the
        # round-robin tiebreak must rotate flushes across all three
        for _ in range(6):
            Z = _rows(rng, 8)
            res = rt.submit("m", Z).result(timeout=30.0)
            np.testing.assert_allclose(
                np.asarray(res.values), _exact_scores(m, Z)[:, 0], atol=0.15
            )
        st = rt.stats("m")
        per = st["replicas"]
        assert sorted(per) == ["0", "1", "2"]
        assert all(per[i]["flushes"] >= 1 for i in per)
        assert sum(per[i]["flushes"] for i in per) == st["flushes"]
        assert sum(per[i]["rows"] for i in per) == st["rows"]
        assert st["failed_requests"] == 0 and st["shed_requests"] == 0
        assert st["queue_rows"] == 0
        # replicated dispatch keeps the zero-steady-state-recompile law
        assert sum(e.jit_cache_size() for e in engines) == cache_before


def test_replicas_dispatch_concurrently():
    """Two flushes routed to two replicas are inside their dispatch at the
    same time. The injector's sleep hook holds each flush until both have
    arrived, which a serial dispatcher can never satisfy (its barrier
    breaks and the flush fails), and counts the overlap."""
    arrived = threading.Barrier(2, timeout=30.0)
    lock = threading.Lock()
    overlap = {"now": 0, "peak": 0}

    def rendezvous(_seconds):
        with lock:
            overlap["now"] += 1
            overlap["peak"] = max(overlap["peak"], overlap["now"])
        try:
            arrived.wait()
        finally:
            with lock:
                overlap["now"] -= 1

    m = _svm(12)
    fi = FaultInjector(0, sleep=rendezvous)
    with Runtime(engine_opts=ENGINE_OPTS, fault_injector=fi,
                 max_wait_us=500.0) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=2))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))  # warm + build
        fi.slow_next(ENGINE_STEP, 2)
        # each request fills max_batch, so each is a flush of its own
        futs = [rt.submit("m", _rows(rng, 64)) for _ in range(2)]
        for fut in futs:
            fut.result(timeout=60.0)
        st = rt.stats("m")
    assert overlap["peak"] == 2
    assert st["failed_requests"] == 0
    assert all(st["replicas"][i]["flushes"] >= 1 for i in ("0", "1"))


def test_replica_fault_trips_only_its_own_breaker():
    m = _svm(2)
    fi = FaultInjector(0)
    with Runtime(
        engine_opts=ENGINE_OPTS,
        fault_injector=fi,
        max_wait_us=500.0,
        breaker=dict(fail_threshold=1, reset_after_s=60.0),
    ) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=3))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))  # warm flush -> replica 0
        # script the NEXT flush on replica 1 only; siblings stay healthy
        fi.fail_next(FaultInjector.replica_site(ENGINE_STEP, 1), 1)
        doomed = rt.submit("m", _rows(rng, 3))  # rotation -> replica 1
        with pytest.raises(InjectedFault):
            doomed.result(timeout=30.0)
        # replica 1 is open (threshold 1); 0 and 2 keep the FAST path —
        # the whole model never degrades to exact serving
        served = 0
        for _ in range(6):
            res = rt.submit("m", _rows(rng, 4)).result(timeout=30.0)
            assert np.asarray(res.valid).all()  # fast path, not degraded
            served += 1
        st = rt.stats("m")
        per = st["replicas"]
        assert per["1"]["breaker_state"] == "open"
        assert per["1"]["trips"] == 1 and per["1"]["failures"] == 1
        assert per["0"]["breaker_state"] == "closed"
        assert per["2"]["breaker_state"] == "closed"
        assert per["0"]["flushes"] >= 1 and per["2"]["flushes"] >= 1
        assert st["batch_failures"] == 1 and st["failed_requests"] == 1
        assert st["breaker"]["degraded_requests"] == 0
        # accounting conserves: warm + doomed + served all enqueued
        assert st["requests"] == 1 + 1 + served
        assert st["queue_rows"] == 0


def test_all_replicas_open_degrades_once_and_keeps_drift_window_clean():
    m = _svm(3)
    fi = FaultInjector(0)
    with Runtime(
        engine_opts=ENGINE_OPTS,
        fault_injector=fi,
        max_wait_us=500.0,
        breaker=dict(fail_threshold=1, reset_after_s=60.0),
    ) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=2))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))  # warm: 2 valid fast-path rows
        for i in range(2):
            fi.fail_next(FaultInjector.replica_site(ENGINE_STEP, i), 1)
        for _ in range(2):  # rotation trips replica 0 then replica 1
            with pytest.raises(InjectedFault):
                rt.submit("m", _rows(rng, 2)).result(timeout=30.0)
        # every breaker refuses -> ONE degraded exact flush for the model
        Z = _rows(rng, 5)
        res = rt.submit("m", Z).result(timeout=30.0)
        np.testing.assert_allclose(
            np.asarray(res.values), _exact_scores(m, Z)[:, 0],
            rtol=1e-4, atol=1e-5,
        )
        assert not np.asarray(res.valid).any()  # exact-served rows
        st = rt.stats("m")
        assert st["replicas"]["0"]["breaker_state"] == "open"
        assert st["replicas"]["1"]["breaker_state"] == "open"
        assert st["breaker"]["degraded_requests"] == 1
        assert st["breaker"]["degraded_rows"] == 5
        # degraded rows never enter the drift window: only the warm
        # flush's 2 valid rows were recorded (a fault is not drift)
        win = st["fallback_window"]
        assert win["rows"] == 2 and win["invalid"] == 0


def test_registry_retires_every_replica_on_count_change():
    art = maclaurin.compile(_svm(4))
    reg = ArtifactRegistry(warmup_on_load=False, engine_opts=ENGINE_OPTS)
    reg.publish("m", art, PublishSpec(replicas=2))
    _, two = reg.get_engines("m")
    assert len(two) == 2
    reg.publish("m", art, PublishSpec(replicas=3))  # same digest, new scale
    _, three = reg.get_engines("m")
    assert len(three) == 3
    # atomic retirement: no old engine survives into the new set
    assert not set(map(id, two)) & set(map(id, three))
    # replicas=None re-publish keeps the scale AND the built engines
    reg.publish("m", art)
    _, again = reg.get_engines("m")
    assert len(again) == 3
    assert [id(e) for e in again] == [id(e) for e in three]


def test_runtime_survives_replica_count_change_mid_traffic():
    m = _svm(5)
    art = maclaurin.compile(m)
    with Runtime(engine_opts=ENGINE_OPTS, max_wait_us=500.0) as rt:
        rt.publish("m", art, PublishSpec(exact=m, replicas=2))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))
        rt.publish("m", art, PublishSpec(exact=m, replicas=3))  # hot re-scale
        Z = _rows(rng, 4)
        vals, _ = rt.predict("m", Z)  # stale batcher retired, rebuilt
        np.testing.assert_allclose(vals, _exact_scores(m, Z)[:, 0], atol=0.15)
        assert len(rt.registry.get_engines("m")[1]) == 3


# ------------------------------------------------------ head-sharded serving


def test_pad_heads_is_argmax_and_validity_neutral():
    art = maclaurin.compile(_svm_mc(6, k=6))
    padded = maclaurin.pad_heads(art, 4)  # 6 -> 8 heads
    assert padded.meta["padded_heads"] == 8
    assert padded.meta["num_heads"] == 6  # real width preserved
    Z = jnp.asarray(_rows(np.random.default_rng(0), 16))
    ref, ref_valid = maclaurin.score(art, Z)
    got, got_valid = maclaurin.score(padded, Z)
    np.testing.assert_allclose(np.asarray(got[:, :6]), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # pad heads score PAD_HEAD_BIAS: argmax can never land on them
    assert int(np.asarray(got).argmax(axis=1).max()) < 6
    np.testing.assert_array_equal(np.asarray(got_valid), np.asarray(ref_valid))
    # already-aligned width is a no-op, not a copy
    assert maclaurin.pad_heads(art, 2) is art


def test_head_sharded_engine_matches_unsharded():
    mesh = _head_mesh()
    shards = mesh.shape["heads"]
    k = 4 * shards + 1 if shards > 1 else 6  # force padding when sharded
    m = _svm_mc(7, k=k)
    art = maclaurin.compile(m)
    ref = SVMEngine(art, **ENGINE_OPTS)
    shd = SVMEngine(art, head_mesh=mesh, **ENGINE_OPTS)
    if shards > 1:
        assert shd._serve_artifact.meta["padded_heads"] % shards == 0
    Z = _rows(np.random.default_rng(0), 32)
    r_ref = ref.submit(Z)
    r_shd = shd.submit(Z)
    assert np.asarray(r_shd.values).shape == (32, k)  # pad columns sliced
    np.testing.assert_allclose(
        np.asarray(r_shd.values), np.asarray(r_ref.values),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(r_shd.labels), np.asarray(r_ref.labels)
    )
    np.testing.assert_array_equal(
        np.asarray(r_shd.valid), np.asarray(r_ref.valid)
    )


def test_head_sharded_fourier_matches_unsharded():
    mesh = _head_mesh()
    shards = mesh.shape["heads"]
    k = 2 * shards + 1 if shards > 1 else 5
    m = _svm_mc(8, k=k, scale=0.4)
    art = fourier.compile(m, num_features=512)
    ref = SVMEngine(art, **ENGINE_OPTS)
    shd = SVMEngine(art, head_mesh=mesh, **ENGINE_OPTS)
    Z = _rows(np.random.default_rng(1), 16, scale=0.25)
    r_ref = ref.submit(Z)
    r_shd = shd.submit(Z)
    np.testing.assert_allclose(
        np.asarray(r_shd.values), np.asarray(r_ref.values),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(r_shd.labels), np.asarray(r_ref.labels)
    )


def test_head_sharded_int8_quadform_matches_unsharded():
    # Flipped from the PR-7 rejection test: int8 quadform now shards.
    mesh = _head_mesh()
    shards = mesh.shape["heads"]
    k = 2 * shards + 1 if shards > 1 else 5  # force padding when sharded
    m = _svm_mc(9, k=k)
    q = maclaurin.compile(m, dtype="int8")
    ref = SVMEngine(q, **ENGINE_OPTS)
    shd = SVMEngine(q, head_mesh=mesh, **ENGINE_OPTS)
    Z = _rows(np.random.default_rng(0), 16)
    r_ref = ref.submit(Z)
    r_shd = shd.submit(Z)
    np.testing.assert_allclose(
        np.asarray(r_shd.values), np.asarray(r_ref.values),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(r_shd.labels), np.asarray(r_ref.labels)
    )
    np.testing.assert_array_equal(
        np.asarray(r_shd.valid), np.asarray(r_ref.valid)
    )


def test_head_sharded_fastfood_matches_unsharded():
    # Flipped from the PR-7 rejection test: structured fourier now shards,
    # in both dtypes.
    mesh = _head_mesh()
    shards = mesh.shape["heads"]
    k = 2 * shards + 1 if shards > 1 else 5
    m = _svm_mc(9, k=k, scale=0.4)
    for dtype in ("float32", "int8"):
        art = fourier.compile(
            m, num_features=256, structured=True, dtype=dtype
        )
        ref = SVMEngine(art, **ENGINE_OPTS)
        shd = SVMEngine(art, head_mesh=mesh, **ENGINE_OPTS)
        Z = _rows(np.random.default_rng(1), 16, scale=0.25)
        r_ref = ref.submit(Z)
        r_shd = shd.submit(Z)
        np.testing.assert_allclose(
            np.asarray(r_shd.values), np.asarray(r_ref.values),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_array_equal(
            np.asarray(r_shd.labels), np.asarray(r_ref.labels)
        )


def _synthetic_fastfood_artifact(k, d=32, num_features=64, seed=0,
                                 dtype="float32"):
    """A fastfood artifact with K heads built directly from arrays —
    compiling a real K=4096 one-vs-rest model would dwarf the test."""
    rng = np.random.default_rng(seed)
    from repro.core.families.base import CompiledArtifact, base_meta

    arrays, f, proj_meta = fourier._fastfood_arrays(rng, d, num_features, 0.5)
    arrays = dict(arrays)
    arrays["phase"] = jnp.asarray(
        rng.uniform(0, 2 * np.pi, (f,)).astype(np.float32)
    )
    arrays["weights"] = jnp.asarray(
        (rng.standard_normal((k, f)) * 0.05).astype(np.float32)
    )
    arrays["b"] = jnp.asarray((rng.standard_normal(k) * 0.1).astype(np.float32))
    art = CompiledArtifact(
        family="fourier",
        arrays=arrays,
        meta=base_meta(
            d=d, num_heads=k, multiclass=True, kind="rff",
            validity="global", num_features=f, seed=seed, **proj_meta,
        ),
    )
    if dtype == "int8":
        art = fourier.quantize_fastfood_artifact(art)
    return art


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_head_sharded_fastfood_argmax_parity_at_k4096(dtype):
    """ISSUE 8 acceptance: extreme-multiclass (K=4096) Fastfood serving
    under head_mesh keeps exact argmax parity with the unsharded path."""
    mesh = _head_mesh()
    art = _synthetic_fastfood_artifact(4096, dtype=dtype)
    Z = _rows(np.random.default_rng(2), 24, d=32)
    ref = SVMEngine(art, **ENGINE_OPTS)
    shd = SVMEngine(art, head_mesh=mesh, **ENGINE_OPTS)
    r_ref = ref.submit(Z)
    r_shd = shd.submit(Z)
    assert np.asarray(r_shd.values).shape == (24, 4096)
    np.testing.assert_allclose(
        np.asarray(r_shd.values), np.asarray(r_ref.values),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(r_shd.labels), np.asarray(r_ref.labels)
    )


def test_runtime_serves_head_sharded_replicas():
    """The two scale-out axes compose: replicated dispatch over engines
    that each serve the head-sharded path."""
    mesh = _head_mesh()
    m = _svm_mc(10, k=6)
    art = maclaurin.compile(m)
    opts = dict(ENGINE_OPTS, head_mesh=mesh)
    with Runtime(engine_opts=opts, max_wait_us=500.0) as rt:
        rt.publish("mc", art, PublishSpec(replicas=2))
        rng = np.random.default_rng(0)
        Z = _rows(rng, 8)
        res = rt.submit("mc", Z).result(timeout=30.0)
        assert np.asarray(res.values).shape == (8, 6)
        exact = _exact_scores(m, Z)
        np.testing.assert_array_equal(
            np.asarray(res.labels), exact.argmax(axis=1)
        )


# ------------------------------------------------------------ roofline prior


def test_roofline_prior_ranks_bigger_tiles_cheaper():
    small = TileConfig(block_n=64)
    big = TileConfig(block_n=512)
    t_small = cost.quadform_tile_seconds(small, n=1024, d=64, k=8)
    t_big = cost.quadform_tile_seconds(big, n=1024, d=64, k=8)
    # fewer row-blocks re-stream the stacked Hessian fewer times
    assert t_big < t_small
    assert cost.rbf_tile_seconds(big, n=1024, d=64, m=512) < \
        cost.rbf_tile_seconds(small, n=1024, d=64, m=512)
    # family-level closed forms: int8 streams fewer weight bytes
    f32 = cost.family_candidate_seconds("maclaurin", "float32",
                                        n=256, d=32, k=8)
    i8 = cost.family_candidate_seconds("maclaurin", "int8",
                                       n=256, d=32, k=8)
    assert i8 < f32
    assert cost.family_candidate_seconds("nope", "float32",
                                         n=256, d=32, k=8) is None


def test_prune_candidates_keeps_default_under_any_prior():
    default = tuning.DEFAULTS["quadform"]
    cands = [TileConfig(block_n=b) for b in (64, 128, 256)] + [default]
    prior = lambda cfg: cost.quadform_tile_seconds(cfg, n=512, d=32, k=4)
    kept = autotune.prune_candidates(cands, default, prior, keep=1)
    assert default in kept  # never-worse-than-default survives pruning
    assert len(kept) <= 2
    assert kept == [c for c in cands if c in set(kept)]  # order preserved
    # an adversarial prior (default ranked worst) still keeps it
    bad = autotune.prune_candidates(
        cands, default, lambda c: -prior(c), keep=1
    )
    assert default in bad


def test_compile_model_prunes_predictably_expensive_candidates():
    m = _svm(11, scale=0.4)
    sample = _rows(np.random.default_rng(0), 64, scale=0.3)
    art = compile_model(
        m,
        Budget(max_err=0.05),
        sample=sample,
        families=("maclaurin", "fourier"),
        family_opts={"fourier": {"num_features": 65536}},
    )
    rows = art.meta["compile_report"]["families"]
    pruned = [r for r in rows if r.get("skipped") == "pruned_by_cost"]
    assert pruned, rows  # a 65536-feature basis prices itself out
    assert all("predicted_cost_s" in r for r in pruned)
    assert art.family == "maclaurin"
    # exhaustive mode: cost_margin=None measures everything
    art2 = compile_model(
        m,
        Budget(max_err=0.05),
        sample=sample,
        families=("maclaurin",),
        cost_margin=None,
    )
    rows2 = art2.meta["compile_report"]["families"]
    assert not any(r.get("skipped") == "pruned_by_cost" for r in rows2)


def test_per_replica_span_counts_sum_to_model_totals_under_faults():
    """Observability across scale-out: the tracer's per-replica served
    sub-keys (plus the degraded sub-key) partition the model's served
    total, and a scripted per-replica fault shows up attributed to
    exactly that replica's flush — even though the span ring could have
    evicted the individual spans."""
    m = _svm(5)
    fi = FaultInjector(0)
    obs = Observability(seed=2, registry=MetricsRegistry())
    with Runtime(
        engine_opts=ENGINE_OPTS,
        fault_injector=fi,
        max_wait_us=500.0,
        breaker=dict(fail_threshold=1, reset_after_s=60.0),
        obs=obs,
    ) as rt:
        digest = rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=3))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))            # warm flush -> replica 0
        fi.fail_next(FaultInjector.replica_site(ENGINE_STEP, 1), 1)
        doomed = rt.submit("m", _rows(rng, 3))    # rotation -> replica 1
        with pytest.raises(InjectedFault):
            doomed.result(timeout=30.0)
        for _ in range(6):
            rt.submit("m", _rows(rng, 4)).result(timeout=30.0)

        st = rt.stats("m")
        counts = obs.tracer.counts(digest[:12])
        per_replica = {
            i: counts.get(f"request.served[replica={i}]", 0) for i in range(3)
        }
        degraded = counts.get("request.served[degraded]", 0)
        assert sum(per_replica.values()) + degraded == counts["request.served"]
        assert counts["request.served"] == st["served_requests"] == 7
        assert degraded == 0                      # siblings kept the fast path
        # replica 1 served nothing after its trip; 0 and 2 carried the load
        assert per_replica[1] == 0
        assert per_replica[0] >= 1 and per_replica[2] >= 1
        # the injected fault is attributed to replica 1, span- and count-wise
        assert counts.get("flush.failed[replica=1]", 0) == 1
        assert counts.get("request.failed", 0) == 1 == st["failed_requests"]
        cons = obs.tracer.conservation(digest[:12])
        assert cons["unaccounted"] == 0 and cons["submitted"] == 8


def test_degraded_rows_never_appear_in_validity_spans():
    """flush.validity spans are the drift window's span-level twin: they
    must cover fast-path rows only. A degraded (all-breakers-open) exact
    flush emits flush.degraded / degraded request.served spans instead,
    so the validity spans' row total equals the fallback window's."""
    m = _svm(3)
    fi = FaultInjector(0)
    obs = Observability(seed=4, registry=MetricsRegistry())
    with Runtime(
        engine_opts=ENGINE_OPTS,
        fault_injector=fi,
        max_wait_us=500.0,
        breaker=dict(fail_threshold=1, reset_after_s=60.0),
        obs=obs,
    ) as rt:
        digest = rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=2))
        rng = np.random.default_rng(0)
        rt.predict("m", _rows(rng, 2))            # warm: 2 fast-path rows
        for i in range(2):
            fi.fail_next(FaultInjector.replica_site(ENGINE_STEP, i), 1)
        for _ in range(2):                        # trip both breakers
            with pytest.raises(InjectedFault):
                rt.submit("m", _rows(rng, 2)).result(timeout=30.0)
        res = rt.submit("m", _rows(rng, 5)).result(timeout=30.0)
        assert not np.asarray(res.valid).any()    # exact-served rows

        key = digest[:12]
        validity = obs.tracer.spans(key, "flush.validity")
        assert validity, "fast-path flushes must record validity spans"
        assert all(not s["attrs"].get("degraded") for s in validity)
        valid_rows = sum(s["attrs"]["rows"] for s in validity)
        st = rt.stats("m")
        assert valid_rows == st["fallback_window"]["rows"] == 2
        # the degraded flush is traced as degraded, not as drift evidence
        degraded = obs.tracer.spans(key, "flush.degraded")
        assert len(degraded) == 1 and degraded[0]["attrs"]["rows"] == 5
        served = obs.tracer.spans(key, "request.served")
        by_degraded = [s for s in served if s["attrs"].get("degraded")]
        assert len(by_degraded) == 1
        assert all("replica" not in s["attrs"] for s in by_degraded)
        assert obs.tracer.counts(key).get("request.served[degraded]") == 1
