"""Observability demo: trace a burst, expose metrics, profile a step.

The serving runtime answers "what is happening in production" on three
layers, all shown here end to end:

1. **Tracing** — every submitted request gets a deterministic trace id
   (``{seed:04x}-{ordinal:012x}``, a seeded counter — replayable, never
   wall-clock); its lifecycle lands as linked spans (admission, queue
   wait, the coalesced engine step from dispatch until its outputs are
   on the host, with bucket/TileConfig/recompile flag, verdict) in a
   bounded per-model ring, exportable as JSONL. Monotone span counts survive ring eviction, so the
   conservation identity (served + failed + expired + closed ==
   admitted) is checkable forever.

2. **Metrics** — the same record sites feed a typed counter/gauge/
   histogram registry dimensioned by (model_digest, alias, family,
   dtype, replica, bucket), rendered in the Prometheus text format:
   point a scraper at ``render_prometheus()`` and the §4 validity
   fraction, fallback rate, queue depth, per-replica breaker state and
   EWMA step time are first-class series.

3. **Profiling** — ``Runtime.profile(model, Z, path)`` wraps one
   coalesced step in ``jax.profiler.trace`` with a named host span
   around each stage of the flush (``runtime.flush`` and its assemble,
   pad, put, step and resolve, then ``svm_engine.sync``) on the device
   trace's clock, for TensorBoard / Perfetto inspection.

    PYTHONPATH=src python examples/svm_observability.py
"""

import tempfile
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from repro.core import gamma_max
from repro.core.families import maclaurin
from repro.core.rbf import SVMModel
from repro.serve import PublishSpec, Runtime
from repro.serve.runtime import MetricsRegistry, Observability

DIM = 16
REQ_ROWS = 4
BURST = 32


def make_model(seed=0, d=DIM, n_sv=64):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * 0.5
    gamma = 0.8 * float(gamma_max(jnp.asarray(X)))
    ay = rng.standard_normal(n_sv).astype(np.float32) * 0.5
    return SVMModel(
        X=jnp.asarray(X),
        alpha_y=jnp.asarray(ay),
        b=jnp.float32(0.1),
        gamma=jnp.float32(gamma),
    )


def main():
    model = make_model()
    # a private Observability isolates this demo's registry and seeds the
    # tracer; the default (obs=None) shares one process-wide registry so
    # every runtime's series land in a single exposition
    obs = Observability(seed=7, registry=MetricsRegistry())
    out_dir = Path(tempfile.mkdtemp(prefix="svm_obs_"))

    with Runtime(engine_opts=dict(min_bucket=8, max_batch=64), obs=obs) as rt:
        digest = rt.publish(
            "detector", maclaurin.compile(model), PublishSpec(exact=model)
        )
        key = digest[:12]
        rng = np.random.default_rng(1)

        # -- 1. trace a burst of coalesced traffic -----------------------
        futs = [
            rt.submit(
                "detector",
                0.3 * rng.standard_normal((REQ_ROWS, DIM)).astype(np.float32),
            )
            for _ in range(BURST)
        ]
        for f in futs:
            f.result(timeout=30.0).values

        cons = obs.tracer.conservation(key)
        print(f"[obs] conservation for {key}: {cons}")
        assert cons["unaccounted"] == 0
        step = obs.tracer.spans(key, "engine.step")[-1]
        print(
            f"[obs] last engine step: trace={step['trace_id']} "
            f"dispatch-to-host={(step['t_end'] - step['t_start']) * 1e3:.2f} ms "
            f"bucket={step['attrs']['bucket']} "
            f"recompiled={step['attrs']['recompiled']} "
            f"tile={step['attrs']['tile_config']}"
        )

        # -- 2. Prometheus exposition ------------------------------------
        text = rt.render_prometheus()
        wanted = (
            "repro_serve_validity_fraction",
            "repro_serve_fallback_rate",
            "repro_serve_queue_rows",
            "repro_serve_breaker_state",
            "repro_serve_step_time_ewma_seconds",
        )
        picked = [
            line
            for line in text.splitlines()
            if line.startswith(wanted) or line.startswith("repro_serve_requests_total")
        ]
        print(f"[obs] prometheus exposition ({len(text.splitlines())} lines), e.g.:")
        for line in picked:
            print(f"  {line}")

        # -- 3. JSONL span export + one profiler capture -----------------
        jsonl = out_dir / "spans.jsonl"
        n = obs.tracer.export_jsonl(jsonl, key)
        print(f"[obs] exported {n} ring-resident spans to {jsonl}")

        trace_dir = out_dir / "profile"
        probe = 0.3 * rng.standard_normal((8, DIM)).astype(np.float32)
        rt.profile("detector", probe, trace_dir)
        produced = sorted(
            p.relative_to(trace_dir) for p in trace_dir.rglob("*") if p.is_file()
        )
        print(f"[obs] jax.profiler trace under {trace_dir}:")
        for p in produced:
            print(f"  {p}")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
