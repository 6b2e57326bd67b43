"""Observability layer (PR 9): deterministic request tracing, the unified
metrics registry with Prometheus text exposition, nearest-rank latency
percentiles, DriftGuard heal history, and the ``jax.profiler`` hooks.

The load-bearing property is three-way conservation: every submitted
request is accounted for (served + shed + failed + timed-out + closed ==
submitted) in the telemetry counters, in the tracer's monotone span
counts, AND in the Prometheus rendering — under healthy traffic and
under seeded chaos interleavings alike.
"""

import json
import re

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gamma_max
from repro.core.rbf import SVMModel
from repro.core.families import Budget, compile_model, maclaurin
from repro.serve import PublishSpec, Runtime
from repro.serve.runtime import (
    ENGINE_STEP,
    DriftGuard,
    FaultInjector,
    InjectedFault,
    MetricsRegistry,
    Observability,
    Tracer,
)
from repro.serve.runtime.telemetry import LatencyWindow, _nearest_rank

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


def _rows(rng, n, d=8, scale=0.6):
    return rng.standard_normal((n, d)).astype(np.float32) * scale


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? [^ ]+$",
)


def _parse_prometheus(text):
    """Validate the text format line by line; return {metric: n_samples}."""
    samples = {}
    typed = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split(" ", 3)
            assert len(parts) >= 3, line
            if line.startswith("# TYPE "):
                assert parts[3] in ("counter", "gauge", "histogram"), line
                typed.add(parts[2])
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
        name = line.split("{", 1)[0].split(" ", 1)[0]
        samples[name] = samples.get(name, 0) + 1
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in typed or base in typed, f"untyped sample: {line!r}"
    return samples


def _counter_total(registry, name):
    """Sum a counter family's children across all label sets."""
    return sum(registry.collect().get(name, {}).values())


# ---------------------------------------------------------------- metrics


def test_registry_renders_valid_prometheus_text():
    reg = MetricsRegistry()
    c = reg.counter("demo_requests_total", "Requests.", ("model", "verdict"))
    c.labels(model="m1", verdict="ok").inc()
    c.labels(model="m1", verdict="ok").inc(2)
    c.labels(model='we"ird\\na{me}', verdict="shed").inc()
    g = reg.gauge("demo_depth", "Queue depth.", ("model",))
    g.labels(model="m1").set(7)
    h = reg.histogram(
        "demo_latency_seconds", "Latency.", ("model",), buckets=(0.1, 1.0)
    )
    h.labels(model="m1").observe(0.05)
    h.labels(model="m1").observe(0.5)
    h.labels(model="m1").observe(5.0)

    text = reg.render()
    samples = _parse_prometheus(text)
    assert samples["demo_requests_total"] == 2
    assert samples["demo_depth"] == 1
    # histogram: 2 finite buckets + +Inf + _sum + _count
    assert samples["demo_latency_seconds_bucket"] == 3
    assert samples["demo_latency_seconds_sum"] == 1
    assert samples["demo_latency_seconds_count"] == 1
    assert 'demo_latency_seconds_bucket{model="m1",le="+Inf"} 3' in text
    assert 'demo_latency_seconds_bucket{model="m1",le="0.1"} 1' in text
    assert 'demo_latency_seconds_bucket{model="m1",le="1"} 2' in text
    # label values escaped, not mangled
    assert 'model="we\\"ird\\\\na{me}"' in text
    assert c.labels(model="m1", verdict="ok").value == 3


def test_registry_rejects_type_and_label_conflicts():
    reg = MetricsRegistry()
    reg.counter("demo_total", "x", ("a",))
    reg.counter("demo_total", "x", ("a",))  # re-registration is idempotent
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("demo_total", "x", ("a",))
    with pytest.raises(ValueError, match="labels"):
        reg.counter("demo_total", "x", ("b",))
    with pytest.raises(ValueError, match="expected labels"):
        reg.counter("demo_total", "x", ("a",)).labels(wrong="v")
    with pytest.raises(ValueError, match=">= 0"):
        reg.counter("demo_total", "x", ("a",)).labels(a="v").inc(-1)


# ----------------------------------------------------------------- tracer


def test_span_ids_are_deterministic_replay():
    def drive(tracer):
        ids = [tracer.new_trace()]
        ids.append(tracer.span("m", "request.admitted", attrs={"rows": 3}))
        ids.append(tracer.span("m", "request.served", attrs={"replica": 1}))
        ids.append(tracer.span("other", "engine.step"))
        return ids

    a, b = Tracer(seed=7), Tracer(seed=7)
    assert drive(a) == drive(b)  # pure function of (seed, ordinal)
    assert drive(a) != drive(Tracer(seed=8))
    assert a.new_id() == f"{7:04x}-{8:012x}"  # 2 drives x 4 ids minted
    # ids never encode wall-clock or thread identity: a tracer with a
    # frozen clock mints the exact same ids
    frozen = Tracer(seed=7, clock=lambda: 123.0)
    assert drive(frozen) == drive(Tracer(seed=7))


def test_ring_bounds_spans_but_counts_survive_eviction():
    tracer = Tracer(seed=1, capacity=8)
    for i in range(50):
        tracer.span("m", "request.admitted", attrs={"rows": 1})
        tracer.span("m", "request.served", attrs={"replica": i % 2})
    assert len(tracer.spans("m")) == 8  # ring forgot the early spans
    counts = tracer.counts("m")
    assert counts["request.admitted"] == 50  # accounting did not
    assert counts["request.served"] == 50
    assert counts["request.served[replica=0]"] == 25
    assert counts["request.served[replica=1]"] == 25
    cons = tracer.conservation("m")
    assert cons["submitted"] == 50 and cons["unaccounted"] == 0


def test_jsonl_export_round_trips(tmp_path):
    tracer = Tracer(seed=2, clock=lambda: 5.0)
    trace = tracer.new_trace()
    tracer.span("m", "request.admitted", trace_id=trace, attrs={"rows": 4})
    tracer.span("m", "request.served", trace_id=trace, attrs={"replica": 0})
    path = tmp_path / "spans.jsonl"
    assert tracer.export_jsonl(path) == 2
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in records] == ["request.admitted", "request.served"]
    assert all(r["trace_id"] == trace for r in records)
    assert records[0]["attrs"] == {"rows": 4}
    assert records[0]["t_start"] == records[0]["t_end"] == 5.0


# ------------------------------------------------------------ percentiles


def test_nearest_rank_percentiles_at_small_n():
    # nearest-rank: idx = ceil(p/100 * n) - 1 over the sorted window.
    # At small n this is exact and never interpolates.
    assert _nearest_rank([3.0], 50) == 3.0
    assert _nearest_rank([3.0], 99) == 3.0
    assert _nearest_rank([1.0, 2.0], 50) == 1.0
    assert _nearest_rank([1.0, 2.0], 99) == 2.0
    assert _nearest_rank([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert _nearest_rank([1.0, 2.0, 3.0, 4.0], 99) == 4.0

    for n, p50, p99 in [(1, 10.0, 10.0), (2, 10.0, 20.0), (4, 20.0, 40.0)]:
        win = LatencyWindow(maxlen=64)
        for i in range(n):
            win.record((i + 1) * 0.010)
        snap = win.snapshot()
        assert snap["n"] == n
        assert snap["p50_ms"] == pytest.approx(p50)
        assert snap["p99_ms"] == pytest.approx(p99)


# ---------------------------------------------------- runtime integration


def test_runtime_exposes_first_class_gauges_and_spans():
    m = _svm(0)
    obs = Observability(seed=3, registry=MetricsRegistry())
    rng = np.random.default_rng(1)
    with Runtime(engine_opts=ENGINE_OPTS, max_wait_us=500.0, obs=obs) as rt:
        digest = rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=2))
        rt.predict("m", _rows(rng, 2))
        futs = [rt.submit("m", _rows(rng, 3)) for _ in range(8)]
        for f in futs:
            f.result(timeout=30.0).values       # engine.step closes on the host

        text = rt.render_prometheus()
        samples = _parse_prometheus(text)
        for gauge in (
            "repro_serve_validity_fraction",
            "repro_serve_fallback_rate",
            "repro_serve_queue_rows",
            "repro_serve_step_time_ewma_seconds",
        ):
            assert samples.get(gauge) == 1, gauge
        # per-replica breaker state: one sample per replica, closed == 0
        assert samples.get("repro_serve_breaker_state") == 2
        assert "repro_serve_breaker_state{" in text
        assert _counter_total(obs.metrics, "repro_serve_requests_total") == 9
        assert "repro_serve_request_latency_seconds_bucket" in text

        key = digest[:12]
        steps = rt.obs.tracer.spans(key, "engine.step")
        assert steps, "engine steps must be traced"
        for s in steps:
            assert s["t_end"] > s["t_start"]
            assert s["attrs"]["bucket"] in (8, 16, 32, 64)
            assert "TileConfig" in s["attrs"]["tile_config"]
            assert s["attrs"]["recompiled"] in (True, False)
            assert s["attrs"]["replica"] in (0, 1)
        # queue-wait spans link into the same flush trace as the step
        waits = rt.obs.tracer.spans(key, "request.queue_wait")
        assert waits and all(w["trace_id"] is not None for w in waits)
        served = rt.obs.tracer.spans(key, "request.served")
        assert {s["attrs"]["replica"] for s in served} <= {0, 1}


def test_an_untraced_runtime_serves_the_same_answers_and_records_nothing():
    """``obs=False`` turns tracing and metric mirroring off and nothing
    else: the same requests get the same answers as on a traced runtime,
    and the telemetry counters still balance."""
    m = _svm(5)
    art = maclaurin.compile(m)
    batches = [_rows(np.random.default_rng(3), n) for n in (3, 8, 1, 5)]
    answers = {}
    for traced in (False, True):
        obs = Observability(seed=0, registry=MetricsRegistry()) if traced else False
        with Runtime(engine_opts=ENGINE_OPTS, max_wait_us=500.0, obs=obs) as rt:
            digest = rt.publish("m", art, PublishSpec(exact=m))
            answers[traced] = [rt.predict("m", Z) for Z in batches]
            st = rt.stats("m")
            assert st["requests"] == st["served_requests"] == len(batches)
            if traced:
                cons = rt.obs.tracer.conservation(digest[:12])
                assert cons["served"] == len(batches) and cons["unaccounted"] == 0
            else:
                assert rt.obs is None and rt.render_prometheus() == ""
    for (v_off, ok_off), (v_on, ok_on) in zip(answers[False], answers[True]):
        np.testing.assert_array_equal(v_off, v_on)
        np.testing.assert_array_equal(ok_off, ok_on)


def _conservation_identities(rt, model, digest, registry):
    """Assert the three-way conservation identity; returns the counts."""
    st = rt.stats(model)
    tele_total = (
        st["served_requests"]
        + st["failed_requests"]
        + st["deadline_timeouts"]
        + st["closed_requests"]
    )
    assert st["requests"] == tele_total, st

    cons = rt.obs.tracer.conservation(digest[:12])
    assert cons["unaccounted"] == 0, cons
    assert cons["admitted"] == st["requests"], (cons, st["requests"])
    assert cons["shed"] == st["shed_requests"]
    assert cons["served"] == st["served_requests"]
    assert cons["failed"] == st["failed_requests"]
    assert cons["expired"] == st["deadline_timeouts"]
    assert cons["closed"] == st["closed_requests"]

    prom = {
        name: _counter_total(registry, f"repro_serve_{name}_total")
        for name in (
            "requests",
            "served_requests",
            "failed_requests",
            "deadline_timeouts",
            "closed_requests",
            "shed_requests",
        )
    }
    assert prom["requests"] == st["requests"], prom
    assert prom["requests"] == (
        prom["served_requests"]
        + prom["failed_requests"]
        + prom["deadline_timeouts"]
        + prom["closed_requests"]
    ), prom
    assert prom["shed_requests"] == st["shed_requests"]
    return cons


def test_conservation_holds_under_scripted_faults():
    m = _svm(2)
    fi = FaultInjector(0)
    obs = Observability(seed=5, registry=MetricsRegistry())
    rng = np.random.default_rng(0)
    with Runtime(
        engine_opts=ENGINE_OPTS,
        fault_injector=fi,
        max_wait_us=500.0,
        breaker=dict(fail_threshold=1, reset_after_s=60.0),
        obs=obs,
    ) as rt:
        digest = rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=2))
        rt.predict("m", _rows(rng, 2))
        fi.fail_next(FaultInjector.replica_site(ENGINE_STEP, 1), 1)
        doomed = rt.submit("m", _rows(rng, 3))
        with pytest.raises(InjectedFault):
            doomed.result(timeout=30.0)
        for _ in range(5):
            rt.submit("m", _rows(rng, 4)).result(timeout=30.0)

        cons = _conservation_identities(rt, "m", digest, obs.metrics)
        assert cons["submitted"] == 7
        assert cons["failed"] == 1 and cons["served"] == 6
        # the injected fault is visible as a failed flush span carrying
        # its replica, and the request verdict records the error type
        key = digest[:12]
        flush_failures = rt.obs.tracer.spans(key, "flush.failed")
        assert len(flush_failures) == 1
        assert flush_failures[0]["attrs"]["replica"] == 1
        failed = rt.obs.tracer.spans(key, "request.failed")
        assert failed[0]["attrs"]["error"] == "InjectedFault"


@pytest.mark.stress
def test_conservation_under_seeded_chaos_interleavings():
    """Concurrent submitters + scripted faults + admission pressure +
    close with work in flight: zero unaccounted requests in counters,
    span counts, and the Prometheus rendering alike."""
    import threading

    m = _svm(4)
    for chaos_seed in (0, 1):
        fi = FaultInjector(chaos_seed, engine_fault_rate=0.15)
        obs = Observability(seed=chaos_seed, registry=MetricsRegistry())
        rt = Runtime(
            engine_opts=ENGINE_OPTS,
            fault_injector=fi,
            max_wait_us=200.0,
            max_queue_rows=64,
            breaker=dict(fail_threshold=2, reset_after_s=0.05),
            obs=obs,
        )
        try:
            digest = rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m, replicas=2))
            rng = np.random.default_rng(chaos_seed)
            try:
                rt.predict("m", _rows(rng, 2))  # warm; may itself be faulted
            except Exception:
                pass

            def submitter(worker):
                wrng = np.random.default_rng(100 + worker)
                for _ in range(12):
                    try:
                        fut = rt.submit("m", _rows(wrng, int(wrng.integers(1, 9))))
                        fut.result(timeout=30.0)
                    except Exception:
                        pass  # every verdict is fine; accounting must balance

            threads = [threading.Thread(target=submitter, args=(w,)) for w in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            rt.close()
        cons = _conservation_identities(rt, "m", digest, obs.metrics)
        assert cons["submitted"] == 1 + 4 * 12


# ------------------------------------------------------------ heal history


def test_heal_history_in_stats_with_injected_clock():
    m = _svm(27, scale=0.35)
    rng = np.random.default_rng(2)
    art = compile_model(
        m,
        Budget(max_err=0.05),
        sample=_rows(rng, 256, scale=0.25),
        families=("maclaurin",),
    )
    now = [100.0]
    obs = Observability(seed=9, registry=MetricsRegistry())
    with Runtime(engine_opts=ENGINE_OPTS, obs=obs) as rt:
        old_digest = rt.publish("clf", art, PublishSpec(exact=m))
        guard = DriftGuard(
            rt,
            "clf",
            exact=m,
            budget=Budget(max_err=0.08),
            threshold=0.3,
            min_rows=48,
            min_agreement=1.5,  # impossible bar -> first canary fails
            capacity=192,
            seed=9,
            clock=lambda: now[0],
        ).attach()
        for _ in range(12):
            # materializing .values feeds the validity window (deferred sync)
            fut = rt.submit("clf", _rows(rng, 8, scale=1.5))
            assert fut.result(timeout=30.0).values.shape == (8,)

        now[0] = 111.5
        verdict = guard.check()
        assert verdict["triggered"] and not verdict["healed"]
        heals = rt.stats("clf")["heals"]
        assert heals["attempts"] == 1
        assert heals["last_trigger_at"] == 111.5
        assert heals["flipped_digests"] == []
        assert heals["history"][-1]["healed"] is False
        assert heals["history"][-1]["trigger_at"] == 111.5

        now[0] = 222.5
        guard.min_agreement = 0.8
        verdict = guard.check()
        assert verdict["healed"], verdict
        new_digest = rt.registry.resolve("clf")
        assert new_digest != old_digest
        # the full arc lives on the digest that drifted ...
        heals = rt.stats(old_digest)["heals"]
        assert heals["attempts"] == 2
        assert heals["last_trigger_at"] == 222.5
        assert heals["flipped_digests"] == [new_digest]
        assert [h["healed"] for h in heals["history"]] == [False, True]
        assert heals["history"][-1]["new_digest"] == new_digest
        # ... and the flip is mirrored onto the alias's new digest, so
        # watching ``stats("clf")`` across the swap keeps the heal visible
        heals = rt.stats("clf")["heals"]
        assert heals["attempts"] == 1
        assert heals["last_trigger_at"] == 222.5
        assert [h["healed"] for h in heals["history"]] == [True]

        # the heal arc is traced as linked spans under the OLD digest
        key = old_digest[:12]
        arcs = {
            name: rt.obs.tracer.spans(key, name)
            for name in (
                "heal.trigger",
                "heal.reservoir",
                "heal.recompile",
                "heal.canary",
                "heal.flip",
            )
        }
        assert len(arcs["heal.trigger"]) == 2
        assert len(arcs["heal.canary"]) == 2
        assert len(arcs["heal.flip"]) == 1
        flip = arcs["heal.flip"][0]
        trigger = arcs["heal.trigger"][-1]
        assert flip["trace_id"] == trigger["trace_id"]
        assert flip["parent_id"] == trigger["span_id"]
        assert flip["attrs"]["new_digest"] == new_digest[:12]
        assert [c["attrs"]["passed"] for c in arcs["heal.canary"]] == [False, True]
        # canary verdicts mirrored onto the registry
        collected = obs.metrics.collect()["repro_serve_heals_total"]
        outcomes = {dict(k)["outcome"]: v for k, v in collected.items()}
        assert outcomes == {"failed": 1, "healed": 1}


# -------------------------------------------------------------- profiling


def test_runtime_profile_writes_a_trace(tmp_path):
    import os

    from repro.serve.runtime.obs import profile as obs_profile

    m = _svm(0)
    rng = np.random.default_rng(0)
    with Runtime(engine_opts=ENGINE_OPTS, obs=Observability()) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        out = rt.profile("m", _rows(rng, 4), tmp_path)
        assert out == str(tmp_path)
    assert not obs_profile.enabled()  # capture() restored the hook state
    produced = [
        os.path.join(root, f) for root, _, files in os.walk(tmp_path) for f in files
    ]
    assert produced, "jax.profiler.trace must leave trace files behind"


def test_profile_hooks_install_and_uninstall_cleanly():
    import contextlib

    from jax.profiler import TraceAnnotation

    from repro.serve import svm_engine
    from repro.serve.runtime.obs import profile as obs_profile

    assert not obs_profile.enabled()
    assert svm_engine._profile_annotation is None
    assert isinstance(obs_profile.annotate("runtime.flush"), contextlib.nullcontext)
    prev = obs_profile.enable(True)
    try:
        assert prev is False and obs_profile.enabled()
        assert svm_engine._profile_annotation is not None
        span = obs_profile.annotate("test/annotation", rows=4)
        assert isinstance(span, TraceAnnotation)
        with span:
            pass
    finally:
        obs_profile.enable(False)
    assert svm_engine._profile_annotation is None
    assert isinstance(obs_profile.annotate("runtime.flush"), contextlib.nullcontext)


def _host_events(trace_dir):
    """Host events of the one ``.xplane.pb`` under ``trace_dir``:
    {thread line: [(start_ns, end_ns, name, metadata)]}."""
    import jax

    import warnings

    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    lines = {}
    with warnings.catch_warnings():     # jaxlib's stats type warns when read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/host:"):
                for i, line in enumerate(plane.lines):
                    lines[(plane.name, i, line.name)] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats))
                        for e in line.events
                    ]
    return lines


FLUSH_STAGES = (
    "runtime.flush.assemble",
    "svm_engine.pad/b8",
    "svm_engine.put/b8",
    "svm_engine.step/maclaurin/b8",
    "runtime.flush.resolve",
)


def test_flush_stage_spans_reach_the_profiler_trace(tmp_path):
    """Two requests of 2 rows share one flush: the concatenate and the
    staging copy into bucket 8 both run, and each has its span."""
    import jax

    from repro.serve.runtime.obs import profile as obs_profile

    m = _svm(0)
    rng = np.random.default_rng(0)
    # the flush waits for its bucket's 8 rows, or 50 ms: both requests
    with Runtime(engine_opts=ENGINE_OPTS, obs=Observability(seed=5),
                 max_wait_us=50_000) as rt:
        digest = rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rt.predict("m", _rows(rng, 4))                  # warm the path
        obs_profile.enable(True)
        try:
            with jax.profiler.trace(str(tmp_path / "on")):
                futs = [rt.submit("m", _rows(rng, 2)) for _ in range(2)]
                for fut in futs:
                    fut.result(timeout=30.0).values
        finally:
            obs_profile.enable(False)
        with jax.profiler.trace(str(tmp_path / "off")):
            rt.submit("m", _rows(rng, 4)).result(timeout=30.0).values
        step = rt.obs.tracer.spans(digest[:12], "engine.step")[-2]

    on = _host_events(tmp_path / "on")
    with_flush = [evs for evs in on.values()
                  if any(e[2] == "runtime.flush" for e in evs)]
    assert len(with_flush) == 1, "one flush, on one thread line"
    events = with_flush[0]
    (flush,) = [e for e in events if e[2] == "runtime.flush"]
    f0, f1, _, meta = flush
    assert meta == {"trace": step["trace_id"], "rows": 4, "bucket": 8,
                    "replica": 0}
    stages = sorted((e for e in events if e[2] in FLUSH_STAGES),
                    key=lambda e: e[0])
    assert [e[2] for e in stages] == list(FLUSH_STAGES)
    assert all(f0 <= s and e <= f1 for s, e, _, _ in stages), "nested"
    assert all(a[1] <= b[0] for a, b in zip(stages, stages[1:])), "in order"
    syncs = [e for evs in on.values() for e in evs if e[2] == "svm_engine.sync"]
    assert len(syncs) == 1 and syncs[0][0] >= stages[3][0]

    off = {e[2] for evs in _host_events(tmp_path / "off").values() for e in evs}
    assert not off & {"runtime.flush", "svm_engine.sync", *FLUSH_STAGES}


def test_a_lone_request_that_fills_its_bucket_records_no_copy_spans(tmp_path):
    """One request of 8 rows, its bucket's size: the flush neither
    concatenates nor stages, so only put, step and resolve appear."""
    import jax

    from repro.serve.runtime.obs import profile as obs_profile

    m = _svm(0)
    rng = np.random.default_rng(0)
    with Runtime(engine_opts=ENGINE_OPTS) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        rt.predict("m", _rows(rng, 8))                  # warm the path
        obs_profile.enable(True)
        try:
            with jax.profiler.trace(str(tmp_path)):
                rt.submit("m", _rows(rng, 8)).result(timeout=30.0).values
        finally:
            obs_profile.enable(False)

    events = [e for evs in _host_events(tmp_path).values() for e in evs]
    (flush,) = [e for e in events if e[2] == "runtime.flush"]
    names = [e[2] for e in sorted(events, key=lambda e: e[0])
             if flush[0] <= e[0] and e[1] <= flush[1]]
    assert [n for n in names if n in FLUSH_STAGES] == list(FLUSH_STAGES[2:])


def test_degraded_flush_records_its_spans(tmp_path):
    """Every breaker open: the flush goes down the exact path and records
    ``runtime.flush`` (degraded) around pad, put and ``step_exact``."""
    import jax

    from repro.serve.runtime.obs import profile as obs_profile

    m = _svm(0)
    rng = np.random.default_rng(0)
    faults = FaultInjector(seed=0)
    with Runtime(engine_opts=ENGINE_OPTS, obs=Observability(),
                 fault_injector=faults,
                 breaker=dict(fail_threshold=1, reset_after_s=60.0)) as rt:
        rt.publish("m", maclaurin.compile(m), PublishSpec(exact=m))
        faults.fail_next(ENGINE_STEP, 1)
        with pytest.raises(InjectedFault):
            rt.predict("m", _rows(rng, 4))                  # trips the breaker
        obs_profile.enable(True)
        try:
            with jax.profiler.trace(str(tmp_path)):
                res = rt.submit("m", _rows(rng, 4)).result(timeout=30.0)
                assert not res.valid.any()                  # exact-served
        finally:
            obs_profile.enable(False)

    events = [e for evs in _host_events(tmp_path).values() for e in evs]
    (flush,) = [e for e in events if e[2] == "runtime.flush"]
    assert flush[3]["degraded"] and flush[3]["rows"] == 4
    names = [e[2] for e in sorted(events, key=lambda e: e[0])
             if flush[0] <= e[0] and e[1] <= flush[1]]
    stages = [n for n in names if n.startswith("svm_engine.")]
    assert stages == ["svm_engine.pad/b8", "svm_engine.put/b8",
                      "svm_engine.step_exact/b8"]
