"""Serving-path latency: engine p50/p99 per shape bucket, fused multi-head
vs per-head-vmap scaling, the approximation-family comparison, the
per-bucket block-size sweep that feeds the checked-in tuning table, and
the multi-tenant runtime's coalesced-vs-per-request throughput.

``--smoke`` shrinks repeat counts for CI (same sections, same JSON shape,
noisier numbers). Naming sections (e.g. ``runtime_throughput``) runs only
those and MERGES them into the existing results JSON, so a partial rerun
never clobbers the other sections' trajectory.

Five questions, all measured for real on this host:

1. What end-to-end latency does ``SVMEngine.predict`` deliver per shape
   bucket once warm (zero recompiles)?  p50 is the steady-state cost; p99
   captures jitter (allocator, host padding, sync).
2. What does fusing K heads into one stacked-Hessian contraction buy over
   the seed's K-pass vmap?  Measured at K in {1, 10} on identical data —
   the ratio is the multiclass serving speedup.
3. Which approximation family serves a given (K, d) cheapest, and at what
   accuracy?  ``family_compare`` compiles the SAME synthetic model through
   the maclaurin, poly2 and fourier families (``repro.core.families``),
   serves each through its engine fast path, and reports p50/p99 next to
   the measured error vs the exact RBF expansion — the exact path itself
   is timed as the baseline row. This is the data ``compile_model``'s
   budget decision is made of, recorded over the trajectory.
4. Which tile sizes are fastest per shape bucket?  The sweep times the
   DISPATCHED serving primitives over candidate ``TileConfig``s (default
   included, so the recorded pick can only tie or beat it), records the
   winners through ``repro.kernels.common.autotune`` and persists them to
   the checked-in ``tuning_table.json`` the engine reads back at warmup.
   On non-TPU hosts the dispatched path is XLA and ignores block sizes —
   the spread there is timing noise and the table entry simply pins the
   default-equivalent winner; on a TPU host the same sweep produces real
   per-bucket Pallas tilings.
5. What does micro-batching buy under concurrent traffic?
   ``runtime_throughput`` drives the multi-tenant ``Runtime`` with
   open-loop concurrent clients issuing small (4-row) requests and
   compares coalesced throughput against the same clients calling
   ``engine.predict`` per request (closed loop) — the scheduler must win
   at >= 8 clients, with ZERO steady-state recompiles (asserted via
   ``jit_cache_size`` before/after the stress).
6. What happens when offered load exceeds capacity?  ``overload`` pins
   the per-flush service time with the deterministic fault injector's
   slow-step hook, then bursts far past that capacity through a runtime
   with a bounded queue. Admission control must shed the excess with
   typed ``RuntimeOverloaded`` (every shed carries a ``retry_after_s``
   hint), every ADMITTED future must resolve (zero hung futures), the
   shed accounting must balance to the request (admitted + shed ==
   submitted), and p99 of the admitted traffic stays bounded because the
   queue is — all gated by ``tools/check_bench_invariants.py``.
8. Does the runtime scale across devices?  ``scaleout`` pins per-flush
   service time with the slow-step hook (one physical core backs every
   forced host device, so a GIL-releasing sleep inside each replica's
   dispatch thread is what can honestly overlap here), then publishes
   the same model at ``replicas`` in {1, 2, 4, 8} and requires rows/s to
   rise monotonically with replica count at zero steady-state
   recompiles — the dispatcher's concurrency, the property that
   transfers to real multi-device hosts. The same section serves a
   K=4096 OvR model through the head-sharded ``shard_map`` path and
   gates per-row argmax parity vs the unsharded reference at small K.
   Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
9. What does breaker-open degraded serving cost?  ``degraded_mode``
   trips the per-model circuit breaker with scripted engine faults,
   then measures the exact streaming ``rbf_pred`` degraded path next to
   the healthy fast path on identical traffic. The gated invariants:
   the breaker really is open during the degraded measurement, every
   degraded request is served (none shed, none hung), and degraded
   serving adds ZERO fast-path recompiles (it compiles its own slow
   variants, never touching the bucket cache).

Emits BENCH_serving.json (benchmarks/common.save_json) so later perf PRs
have a trajectory to compare against.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import RESULTS_DIR, fmt_table, save_json, timeit
from repro.core import approximate, backend, families, gamma_max
from repro.core.rbf import SVMModel, rbf_kernel
from repro.kernels.common import TileConfig, autotune, tuning
from repro.serve.runtime import (
    ENGINE_STEP,
    FaultInjector,
    MetricsRegistry,
    Observability,
    PublishSpec,
    Runtime,
    RuntimeOverloaded,
)
from repro.serve.server import create_app
from repro.serve.server import serve as http_serve
from repro.kernels.quadform.ref import quadform_heads_ref
from repro.serve.svm_engine import SVMEngine, bucket_size

D = 64
N_SV = 512
BATCHES = [1, 8, 32, 64, 256, 1024]
REPEATS = 200
HEAD_COUNTS = [1, 10]
HEADS_BATCH = 1024
SWEEP_BUCKETS = [32, 256, 1024]
SWEEP_BLOCK_N = [64, 128, 256, 512]
SWEEP_BLOCK_M = [64, 128, 256, 512]
SWEEP_PRIOR_KEEP = 3          # measured configs per sweep (+ the default)

# family_compare grid (ISSUE 3): quadform cost grows as K d^2, RFF as F d —
# the d axis is where the families cross over. Every family is measured
# at both storage dtypes (ISSUE 5): int8 rows show what fused-dequant
# serving costs next to the f32 baseline at identical (K, d).
FAMILY_HEADS = [1, 10]
FAMILY_DIMS = [16, 64, 784]
FAMILY_NSV = 256
FAMILY_BATCH = 256
FAMILY_REPEATS = 50
FAMILY_NUM_FEATURES = 2048
FAMILY_DTYPES = ["float32", "int8"]

# model_size (ISSUE 5): serialized footprint of the int8 variant vs its
# f32 parent, with the invariants CI gates on — >= 3x smaller, argmax/
# label parity vs the f32 engine, and the meta's reported quantization
# error reproducible on the same deterministic holdout. Cases are sized
# so the weight payload dominates the constant ~2 KB of npz/zip member
# headers (a K=1 d=64 quadform is an 18 KB file where header overhead,
# not weights, caps the ratio at ~2.8x — not a footprint that needs
# quantizing in the first place).
MODEL_SIZE_CASES = [(10, 64), (1, 256), (10, 784)]  # (K, d)
MODEL_SIZE_NSV = 256
MODEL_SIZE_BATCH = 256

# fastfood (ISSUE 8): the structured-projection fast path head-to-head
# against dense RFF and quadform at fixed (K, F) across the d axis —
# the Fastfood trade is O(F log d') projection FLOPs vs dense's O(F d),
# so the structured rows must pull ahead as d grows (the acceptance
# criterion pins d=784, the mnist shape, where log2(d') = 10 << 784).
# f32 + int8 rows for every variant; the int8 structured rows carry the
# serialized-size ratio and label parity vs their f32 parent, and every
# row asserts zero steady-state recompiles through the timed loop.
FASTFOOD_DIMS = [64, 784, 1024]
FASTFOOD_K = 10
FASTFOOD_NSV = 256
FASTFOOD_BATCH = 256
FASTFOOD_REPEATS = 30
FASTFOOD_VARIANTS = ("structured", "dense", "quadform")
# NOT shrunk under --smoke: the gated claims (structured beats dense at
# d=784, int8 >= 3x smaller) only hold at a real feature count — at
# F = 512 the structured path still pays a full d' = 1024 transform for
# half the features and the scales dominate the int8 layout. Smoke
# reduces dims and repeats instead.
FASTFOOD_FEATURES = 2048

# runtime_throughput: open-loop clients x small requests through the
# micro-batching Runtime vs per-request engine.predict
RUNTIME_CLIENTS = [1, 8, 32]
RUNTIME_REQS_PER_CLIENT = 80
RUNTIME_REQ_ROWS = 4
RUNTIME_FLUSH_ROWS = 256
RUNTIME_MAX_WAIT_US = 1000.0

# overload: the slow-step injection pins service capacity at roughly
# flush_rows / slow_step_s rows/s on ANY host, so the burst (threads
# submitting back-to-back with sheds returning instantly) reliably
# offers a large multiple of capacity without tuning per machine.
OVERLOAD_QUEUE_ROWS = 256
OVERLOAD_FLUSH_ROWS = 64
OVERLOAD_REQ_ROWS = 8
OVERLOAD_CLIENTS = 8
OVERLOAD_REQS_PER_CLIENT = 60
OVERLOAD_SLOW_STEP_S = 0.02
OVERLOAD_RESULT_TIMEOUT_S = 60.0

# degraded_mode: per-request latency of breaker-open exact serving next
# to the healthy fast path on identical traffic
DEGRADED_BATCH = 256
DEGRADED_REPEATS = 50

# scaleout: replicated dispatch across forced host devices, then the
# head-sharded extreme-multiclass path. On this class of host ONE
# physical core backs every forced device, so raw compute cannot scale
# with device count; the per-flush service time is instead PINNED by the
# fault injector's slow-step hook (a GIL-releasing sleep taken inside
# each replica's dispatch thread, the same emulation bench_overload uses
# to pin capacity). What the replica rows measure is therefore the
# DISPATCHER's scaling: N replicas overlap N pinned flushes iff routing,
# inflight accounting and per-replica breaker state are genuinely
# concurrent — the property that transfers to real multi-device hosts.
SCALEOUT_REPLICAS = [1, 2, 4, 8]
SCALEOUT_SLOW_STEP_S = 0.02
SCALEOUT_REQ_ROWS = 64
SCALEOUT_CLIENTS = 8
SCALEOUT_REQS_PER_CLIENT = 25
SCALEOUT_SHARDED_K = 4096       # extreme-OvR head count (the tentpole claim)
SCALEOUT_PARITY_K = 16          # small-K argmax parity vs unsharded reference
SCALEOUT_SHARDED_D = 32
SCALEOUT_SHARDED_BATCH = 256
SCALEOUT_SHARDED_REPEATS = 10

# observability (PR 9): the tracing tax. Identical open-loop workloads
# through an untraced Runtime (obs=False) and a traced one (private
# Observability, so the process-default registry stays clean). The
# flush wait (max_wait_us) dominates both p50s, so the span-recording
# microseconds must vanish into it — CI gates overhead_p50 <= 1.05x.
# The traced run also re-proves three-way conservation: telemetry
# counters, span counts and the Prometheus rendering must agree on
# every request's verdict.
OBS_CLIENTS = 8
OBS_REQS_PER_CLIENT = 60
OBS_REQ_ROWS = 4
OBS_DRIVE_REPEATS = 5

# serving_http (PR 10): the network tax. The SAME runtime serves an
# identical closed-loop workload twice — first in-process
# (rt.submit(...).result()), then through the stdlib HTTP front door
# with one persistent connection per client. Per-request wall-clock
# p50/p99 are measured CLIENT-side in both legs so the ratio is the
# full wire overhead (TCP hop + JSON + ASGI dispatch + executor
# bridge), not just server time. The gated invariants: conservation
# still balances across the HTTP hop (client 200s == telemetry served
# == spans), the queue drains to zero (zero hung futures), requests
# keep coalescing through the bridge, and the HTTP overhead stays
# bounded (generously — CI hosts are noisy; the point is catching a
# 100x regression like an accidental per-request handshake or a
# serialized bridge, not enforcing microseconds).
HTTP_CLIENTS = 8
HTTP_REQS_PER_CLIENT = 50
HTTP_REQ_ROWS = 4
HTTP_MAX_WAIT_US = 1000.0

SMOKE = False           # set by --smoke: same sections, fewer repeats


def family_num_features() -> int:
    """One definition for the fourier basis size the comparison runs at,
    so the measured rows and the recorded JSON meta can never disagree."""
    return 512 if SMOKE else FAMILY_NUM_FEATURES


def _model(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N_SV, D)).astype(np.float32) * 0.5
    ay = rng.standard_normal(N_SV).astype(np.float32)
    gamma = float(gamma_max(jnp.asarray(X))) * 0.8
    return SVMModel(
        X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
        b=jnp.float32(0.1), gamma=jnp.float32(gamma),
    )


def bench_engine() -> list[dict]:
    m = _model()
    eng = SVMEngine(approximate(m), m, min_bucket=32, max_batch=1024)
    eng.warmup()
    rng = np.random.default_rng(1)
    rows = []
    for n in BATCHES:
        batches = [rng.standard_normal((n, D)).astype(np.float32) * 0.3
                   for _ in range(8)]
        for Z in batches:                                  # warm this bucket
            eng.predict(Z)
        times = []
        for i in range(REPEATS):
            Z = batches[i % len(batches)]
            t0 = time.perf_counter()
            f, _ = eng.predict(Z)                          # includes sync
            times.append(time.perf_counter() - t0)
        times = np.asarray(times) * 1e3
        rows.append({
            "batch": n,
            "bucket": bucket_size(n, 32, 1024),
            "p50_ms": round(float(np.percentile(times, 50)), 4),
            "p99_ms": round(float(np.percentile(times, 99)), 4),
            "per_row_us_p50": round(1e3 * float(np.percentile(times, 50)) / n, 2),
        })
    assert eng.jit_cache_size() <= 6, "bucket cache must stay bounded"
    rows_meta = {
        "jit_variants": eng.jit_cache_size(),
        "padding_overhead": round(eng.stats.padding_overhead, 4),
    }
    print("[serving] engine latency per bucket (warm, zero recompiles)")
    print(fmt_table(rows, ["batch", "bucket", "p50_ms", "p99_ms", "per_row_us_p50"]))
    print(f"[serving] {rows_meta}")
    return rows, rows_meta


def bench_heads() -> list[dict]:
    """Fused stacked-Hessian scoring vs the seed's per-head vmap at equal K."""
    rng = np.random.default_rng(2)
    Z = jnp.asarray(rng.standard_normal((HEADS_BATCH, D)).astype(np.float32) * 0.3)
    rows = []
    for K in HEAD_COUNTS:
        Ms = rng.standard_normal((K, D, D)).astype(np.float32) * 0.05
        M_all = jnp.asarray((Ms + Ms.transpose(0, 2, 1)) / 2)
        V = jnp.asarray(rng.standard_normal((K, D)).astype(np.float32))
        c = jnp.asarray(rng.standard_normal(K).astype(np.float32))
        b = jnp.asarray(rng.standard_normal(K).astype(np.float32))
        g = jnp.full((K,), 0.05, jnp.float32)
        msq = jnp.full((K,), 2.0, jnp.float32)

        fused = jax.jit(backend.quadform_heads_xla)
        unfused = jax.jit(quadform_heads_ref)              # K-pass vmap oracle
        t_fused = timeit(fused, Z, M_all, V, c, b, g, msq, repeats=20, warmup=3)
        t_vmap = timeit(unfused, Z, M_all, V, c, b, g, msq, repeats=20, warmup=3)
        rows.append({
            "K": K,
            "batch": HEADS_BATCH,
            "d": D,
            "fused_ms": round(1e3 * t_fused, 3),
            "vmap_ms": round(1e3 * t_vmap, 3),
            "speedup": round(t_vmap / t_fused, 2),
        })
    print("[serving] fused multi-head vs per-head vmap (best-of-20)")
    print(fmt_table(rows, ["K", "batch", "d", "fused_ms", "vmap_ms", "speedup"]))
    return rows


def bench_family_compare() -> list[dict]:
    """Approximation families head-to-head on one synthetic model per (K, d).

    Each family's artifact is served through an ``SVMEngine`` with the
    fallback OFF (pure fast-path latency, including host padding + sync);
    the exact expansion (shared kernel-matrix GEMM across heads) is the
    baseline row. Errors are measured against that exact scorer on the
    same batch the latency is measured on.
    """
    repeats = 5 if SMOKE else FAMILY_REPEATS
    num_features = family_num_features()
    rows = []
    for K in FAMILY_HEADS:
        for d in FAMILY_DIMS:
            rng = np.random.default_rng(K * 1000 + d)
            X = rng.standard_normal((FAMILY_NSV, d)).astype(np.float32) * 0.5
            gamma = float(gamma_max(jnp.asarray(X))) * 0.8
            if K == 1:
                ay = rng.standard_normal(FAMILY_NSV).astype(np.float32)
                b = jnp.float32(0.1)
            else:
                ay = rng.standard_normal((K, FAMILY_NSV)).astype(np.float32)
                b = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32))
            m = SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                         b=b, gamma=jnp.float32(gamma))
            Z = rng.standard_normal((FAMILY_BATCH, d)).astype(np.float32) * 0.3

            ay2 = m.alpha_y if K > 1 else m.alpha_y[None, :]
            b2 = jnp.reshape(m.b, (K,))
            exact_step = jax.jit(
                lambda Zb, X=m.X, g=m.gamma, a=ay2, bb=b2:
                    rbf_kernel(Zb, X, g) @ a.T + bb[None, :]
            )
            exact = np.asarray(exact_step(jnp.asarray(Z)))        # (n, K)

            def timed(fn):
                fn()                                              # warm
                times = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    fn()
                    times.append(time.perf_counter() - t0)
                t = np.asarray(times) * 1e3
                return (round(float(np.percentile(t, 50)), 4),
                        round(float(np.percentile(t, 99)), 4))

            for name in ("maclaurin", "poly2", "fourier"):
                for dtype in FAMILY_DTYPES:
                    art = families.get_family(name).compile(
                        m, num_features=num_features, dtype=dtype
                    )
                    eng = SVMEngine(art, None, allow_fallback=False,
                                    min_bucket=FAMILY_BATCH,
                                    max_batch=FAMILY_BATCH)
                    eng.warmup([FAMILY_BATCH])
                    vals = eng.predict(Z)[0]
                    got = vals if K > 1 else vals[:, None]
                    err = np.abs(got - exact)
                    p50, p99 = timed(lambda: eng.predict(Z))
                    rows.append({
                        "K": K, "d": d, "family": name, "dtype": dtype,
                        "p50_ms": p50, "p99_ms": p99,
                        "mean_abs_err": round(float(err.mean()), 6),
                        "max_abs_err": round(float(err.max()), 6),
                        "artifact_kb": round(art.nbytes() / 1024, 1),
                    })
            p50, p99 = timed(
                lambda: jax.block_until_ready(exact_step(jnp.asarray(Z)))
            )
            rows.append({
                "K": K, "d": d, "family": "exact", "dtype": "float32",
                "p50_ms": p50, "p99_ms": p99,
                "mean_abs_err": 0.0, "max_abs_err": 0.0,
                "artifact_kb": round(
                    (m.X.size + np.asarray(m.alpha_y).size + 2) * 4 / 1024, 1
                ),
            })
    print("[serving] family comparison (fast path only, fallback off)")
    print(fmt_table(rows, ["K", "d", "family", "dtype", "p50_ms", "p99_ms",
                           "mean_abs_err", "artifact_kb"]))
    return rows


def bench_model_size() -> dict:
    """Serialized footprint of int8 artifact variants vs their f32 parents,
    with the invariants the CI smoke gate asserts from the JSON:

      * int8 serializes >= 3x smaller (the acceptance floor; measured
        ratios run 3.5-3.8x — scales + f32 scalars cost the rest of 4x);
      * label/argmax parity vs the f32 engine on a seeded batch;
      * the quantization error REPORTED in the artifact meta reproduces
        on the same deterministic holdout (measured == reported), so the
        error report a registry consumer reads is real, not vestigial.

    Numbers here are sizes and error magnitudes — deterministic, not
    timing noise — which is what makes them gateable in CI.
    """
    from repro.core.families import fourier as _fourier

    cases = MODEL_SIZE_CASES[:2] if SMOKE else MODEL_SIZE_CASES
    num_features = family_num_features()
    rows = []
    for K, d in cases:
        rng = np.random.default_rng(K * 1000 + d)
        X = rng.standard_normal((MODEL_SIZE_NSV, d)).astype(np.float32) * 0.5
        gamma = float(gamma_max(jnp.asarray(X))) * 0.8
        if K == 1:
            ay = rng.standard_normal(MODEL_SIZE_NSV).astype(np.float32)
            b = jnp.float32(0.1)
        else:
            ay = rng.standard_normal((K, MODEL_SIZE_NSV)).astype(np.float32)
            b = jnp.asarray(0.1 * rng.standard_normal(K).astype(np.float32))
        m = SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                     b=b, gamma=jnp.float32(gamma))
        Z = rng.standard_normal((MODEL_SIZE_BATCH, d)).astype(np.float32) * 0.3
        holdout = _fourier.holdout_sample(m, 0, 256)

        for name in ("maclaurin", "poly2", "fourier"):
            fam = families.get_family(name)
            f32_art = fam.compile(m, num_features=num_features)
            q8_art = fam.compile(m, num_features=num_features, dtype="int8")

            # the meta's error report must reproduce on the holdout it was
            # measured on (same deterministic sample: seed 0, n 256) — via
            # the SAME helper compile used, so only genuine nondeterminism
            # can make measured and reported diverge
            remeasured = families.quantize.measure_quant_error(
                f32_art, q8_art, jnp.asarray(holdout)
            )

            f32_eng = SVMEngine(f32_art, None, allow_fallback=False,
                                min_bucket=MODEL_SIZE_BATCH,
                                max_batch=MODEL_SIZE_BATCH)
            q8_eng = SVMEngine(q8_art, None, allow_fallback=False,
                               min_bucket=MODEL_SIZE_BATCH,
                               max_batch=MODEL_SIZE_BATCH)
            parity = float(np.mean(
                f32_eng.predict_labels(Z) == q8_eng.predict_labels(Z)
            ))

            f32_bytes, q8_bytes = len(f32_art.to_bytes()), len(q8_art.to_bytes())
            rows.append({
                "K": K, "d": d, "family": name,
                "f32_bytes": f32_bytes,
                "int8_bytes": q8_bytes,
                "ratio": round(f32_bytes / q8_bytes, 3),
                "f32_mem_kb": round(f32_art.nbytes() / 1024, 1),
                "int8_mem_kb": round(q8_art.nbytes() / 1024, 1),
                "label_parity": parity,
                "quant_mean_abs_err": q8_art.meta["quant_mean_abs_err"],
                "quant_max_abs_err": q8_art.meta["quant_max_abs_err"],
                "remeasured_mean_abs_err": remeasured["quant_mean_abs_err"],
                "remeasured_max_abs_err": remeasured["quant_max_abs_err"],
                "f32_digest": f32_art.digest()[:12],
                "int8_digest": q8_art.digest()[:12],
            })
    print("[serving] model size: int8 variants vs f32 parents")
    print(fmt_table(rows, ["K", "d", "family", "f32_bytes", "int8_bytes",
                           "ratio", "label_parity", "quant_mean_abs_err"]))
    return {
        "note": (
            "serialized deterministic-npz bytes of each family's int8 "
            "variant vs its f32 parent; CI asserts ratio >= 3, label "
            "parity vs the f32 engine, and that the meta's quant error "
            "report reproduces on the deterministic holdout "
            "(tools/check_bench_invariants.py)"
        ),
        "batch": MODEL_SIZE_BATCH,
        "n_sv": MODEL_SIZE_NSV,
        "num_features": num_features,
        "rows": rows,
    }


def bench_fastfood() -> dict:
    """Structured Fastfood vs dense RFF vs quadform at fixed (K, F).

    One synthetic K-head model per d; every variant serves the same
    batch through an ``SVMEngine`` with the fallback off, f32 and int8.
    The structured rows dispatch the fused FWHT path
    (``backend.fastfood_score*``); rows_per_s is the steady-state p50
    throughput. Gated by ``tools/check_bench_invariants.py``: the full
    (d, variant, dtype) grid present, structured beating dense rows/s at
    d=784, int8 structured >= 3x smaller with >= 0.99 label parity, and
    zero steady-state recompiles on every row.
    """
    dims = [d for d in FASTFOOD_DIMS if d != 1024] if SMOKE else FASTFOOD_DIMS
    repeats = 5 if SMOKE else FASTFOOD_REPEATS
    num_features = FASTFOOD_FEATURES
    rows = []
    for d in dims:
        rng = np.random.default_rng(8000 + d)
        X = rng.standard_normal((FASTFOOD_NSV, d)).astype(np.float32) * 0.5
        gamma = float(gamma_max(jnp.asarray(X))) * 0.8
        ay = rng.standard_normal((FASTFOOD_K, FASTFOOD_NSV)).astype(np.float32)
        b = jnp.asarray(
            0.1 * rng.standard_normal(FASTFOOD_K).astype(np.float32)
        )
        m = SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                     b=b, gamma=jnp.float32(gamma))
        Z = rng.standard_normal((FASTFOOD_BATCH, d)).astype(np.float32) * 0.3

        ay2 = m.alpha_y
        b2 = jnp.reshape(m.b, (FASTFOOD_K,))
        exact = np.asarray(
            rbf_kernel(jnp.asarray(Z), m.X, m.gamma) @ ay2.T + b2[None, :]
        )

        def compile_variant(variant, dtype):
            if variant == "quadform":
                return families.get_family("maclaurin").compile(m, dtype=dtype)
            return families.get_family("fourier").compile(
                m, num_features=num_features,
                structured=(variant == "structured"), dtype=dtype,
            )

        f32_engines = {}
        for variant in FASTFOOD_VARIANTS:
            for dtype in FAMILY_DTYPES:
                art = compile_variant(variant, dtype)
                eng = SVMEngine(art, None, allow_fallback=False,
                                min_bucket=FASTFOOD_BATCH,
                                max_batch=FASTFOOD_BATCH)
                eng.warmup([FASTFOOD_BATCH])
                got = eng.predict(Z)[0]
                err = np.abs(got - exact)
                labels = eng.predict_labels(Z)
                if dtype == "float32":
                    f32_engines[variant] = (eng, labels, len(art.to_bytes()))
                    parity, ratio = 1.0, None
                else:
                    _, f32_labels, f32_bytes = f32_engines[variant]
                    parity = float(np.mean(labels == f32_labels))
                    ratio = round(f32_bytes / len(art.to_bytes()), 3)

                cache_before = eng.jit_cache_size()
                times = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    eng.predict(Z)
                    times.append(time.perf_counter() - t0)
                t = np.asarray(times) * 1e3
                p50 = float(np.percentile(t, 50))
                rows.append({
                    "d": d, "variant": variant, "dtype": dtype,
                    "family": art.family,
                    "num_features": int(
                        art.meta.get("num_features", 0)
                    ) or None,
                    "p50_ms": round(p50, 4),
                    "p99_ms": round(float(np.percentile(t, 99)), 4),
                    "rows_per_s": round(FASTFOOD_BATCH / (p50 / 1e3), 1),
                    "mean_abs_err": round(float(err.mean()), 6),
                    "serialized_bytes": len(art.to_bytes()),
                    "size_ratio_vs_f32": ratio,
                    "label_parity_vs_f32": parity,
                    "steady_state_recompiles":
                        eng.jit_cache_size() - cache_before,
                })
    print("[serving] fastfood: structured vs dense RFF vs quadform")
    print(fmt_table(rows, ["d", "variant", "dtype", "p50_ms", "rows_per_s",
                           "mean_abs_err", "size_ratio_vs_f32",
                           "label_parity_vs_f32"]))
    return {
        "note": (
            "same synthetic K-head model served through the structured "
            "(Fastfood/FWHT), dense-RFF and quadform fast paths at f32 "
            "and int8, fallback off; the structured rows must beat dense "
            "rows/s at d=784 and the int8 structured rows must keep the "
            ">=3x size and >=0.99 parity contract "
            "(tools/check_bench_invariants.py)"
        ),
        "K": FASTFOOD_K,
        "batch": FASTFOOD_BATCH,
        "n_sv": FASTFOOD_NSV,
        "num_features": num_features,
        "dims": dims,
        "rows": rows,
    }


def bench_block_sweep() -> list[dict]:
    """Per-bucket TileConfig sweep through the dispatched serving primitives.

    Every row records the tuned pick next to the old fixed default for the
    same bucket; because the default is always among the candidates, the
    tuned pick is never slower by construction. Winners are persisted to
    the kernels/common tuning table (the file the engine's per-bucket
    resolution reads back).

    Candidates are rank-and-pruned through the analytic roofline prior
    (``repro.launch.roofline.quadform_tile_seconds`` etc.) before being
    measured: only the ``SWEEP_PRIOR_KEEP`` cheapest-predicted configs
    (plus, always, the default) burn wall clock. Each row logs how many
    candidates the prior pruned.
    """
    from repro.launch import roofline
    m = _model()
    am = approximate(m)
    one = lambda x: jnp.reshape(jnp.asarray(x, jnp.float32), (1,))
    M_all, V = am.M[None], am.v[None]
    scalars = (one(am.c), one(am.b), one(am.gamma), one(am.max_sv_sq_norm))
    rng = np.random.default_rng(3)
    rows = []

    def record_row(kernel, bucket, key, winner, sweep, offered):
        default = tuning.DEFAULTS[kernel]
        default_ms = next(r["ms"] for r in sweep if r["config"] == default)
        tuned_ms = min(r["ms"] for r in sweep)
        rows.append({
            "kernel": kernel,
            "bucket": bucket,
            "key": key,
            "tuned": {k: v for k, v in winner.to_json().items()
                      if getattr(default, k) != v} or {"(default)": True},
            "tuned_ms": round(tuned_ms, 4),
            "default_ms": round(default_ms, 4),
            # offered = candidate list handed to autotune (plus the default
            # if it was absent); measured = what survived the prior
            "candidates_offered": offered,
            "candidates_pruned_by_prior": offered - len(sweep),
            "candidates": [
                {"block_n": r["config"].block_n, "block_m": r["config"].block_m,
                 "ms": round(r["ms"], 4)}
                for r in sweep
            ],
        })

    for bucket in SWEEP_BUCKETS:
        Z = jnp.asarray(rng.standard_normal((bucket, D)).astype(np.float32) * 0.3)
        key = tuning.shape_key(d=D, k=1, n=bucket)

        def build(cfg):
            step = jax.jit(
                lambda Zb: backend.quadform_heads(Zb, M_all, V, *scalars, config=cfg)
            )
            return lambda: step(Z)

        # clamp candidates to the bucket (dedup) so small buckets still get
        # a real sweep instead of only the appended default
        cands = [TileConfig(block_n=bn)
                 for bn in sorted({min(bn, bucket) for bn in SWEEP_BLOCK_N})]
        offered = len(cands) + (tuning.DEFAULTS["quadform"] not in cands)
        winner, sweep = autotune.autotune(
            "quadform", key, build, cands, source="benchmarks/serving_latency.py",
            prior=lambda cfg, _n=bucket: roofline.quadform_tile_seconds(
                cfg, n=_n, d=D, k=1
            ),
            prior_keep=SWEEP_PRIOR_KEEP,
        )
        record_row("quadform", bucket, key, winner, sweep, offered)

    # exact-fallback path: SV stream tile size at one representative bucket
    n_fb = 256
    Zfb = jnp.asarray(rng.standard_normal((n_fb, D)).astype(np.float32) * 0.3)
    key = tuning.shape_key(d=D, m=N_SV, n=n_fb)

    def build_rbf(cfg):
        step = jax.jit(
            lambda Zb: backend.rbf_scores(Zb, m.X, m.alpha_y, m.gamma, m.b, config=cfg)
        )
        return lambda: step(Zfb)

    cands = [TileConfig(block_n=256, block_m=bm) for bm in SWEEP_BLOCK_M]
    offered = len(cands) + (tuning.DEFAULTS["rbf_pred"] not in cands)
    winner, sweep = autotune.autotune(
        "rbf_pred", key, build_rbf, cands, source="benchmarks/serving_latency.py",
        prior=lambda cfg: roofline.rbf_tile_seconds(cfg, n=n_fb, d=D, m=N_SV),
        prior_keep=SWEEP_PRIOR_KEEP,
    )
    record_row("rbf_pred", n_fb, key, winner, sweep, offered)

    # structured-Fastfood path: Z-tile size through the fused FWHT scorer,
    # same key shape the family's tile_lookup resolves at serve time
    ff_features = family_num_features()
    ff_art = families.get_family("fourier").compile(
        m, num_features=ff_features, structured=True
    )
    fa = ff_art.arrays
    n_ff = 256
    Zff = jnp.asarray(rng.standard_normal((n_ff, D)).astype(np.float32) * 0.3)
    key = tuning.shape_key(d=D, f=ff_features, n=n_ff)

    def build_fwht(cfg):
        step = jax.jit(
            lambda Zb: backend.fastfood_score(
                Zb, fa["ff_b"], fa["ff_g"], fa["ff_perm"], fa["ff_scale"],
                fa["phase"], fa["weights"], fa["b"], config=cfg,
            )
        )
        return lambda: step(Zff)

    cands = [TileConfig(block_n=bn)
             for bn in sorted({min(bn, n_ff) for bn in SWEEP_BLOCK_N})]
    offered = len(cands) + (tuning.DEFAULTS["fwht"] not in cands)
    winner, sweep = autotune.autotune(
        "fwht", key, build_fwht, cands, source="benchmarks/serving_latency.py",
        prior=lambda cfg: roofline.fwht_tile_seconds(
            cfg, n=n_ff, d=D, f=ff_features, k=fa["weights"].shape[0]
        ),
        prior_keep=SWEEP_PRIOR_KEEP,
    )
    record_row("fwht", n_ff, key, winner, sweep, offered)

    table_path = tuning.save_table()
    print("[serving] block-size sweep (tuned pick vs old fixed default)")
    print(fmt_table(rows, ["kernel", "bucket", "tuned", "tuned_ms", "default_ms"]))
    print(f"[serving] tuning table -> {table_path}")
    return rows


def bench_runtime_throughput() -> dict:
    """Coalesced micro-batching vs per-request ``engine.predict`` under
    concurrent clients, through the multi-tenant ``Runtime``.

    Two models are registered (multi-tenant setup); the measured traffic
    targets the primary alias. The per-request baseline is CLOSED loop
    (each client blocks on its own ``predict``, the pre-runtime serving
    pattern); the runtime path is OPEN loop (clients enqueue all their
    requests, then materialize the futures) — exactly the concurrency the
    scheduler exists to exploit. The engine's bounded-compile guarantee
    must survive coalescing: ``jit_cache_size`` is asserted unchanged
    across the whole stress.
    """
    reqs = 10 if SMOKE else RUNTIME_REQS_PER_CLIENT
    m, m2 = _model(), _model(seed=7)
    art = families.maclaurin.compile(m)
    art2 = families.maclaurin.compile(m2)
    rt = Runtime(
        max_wait_us=RUNTIME_MAX_WAIT_US,
        flush_rows=RUNTIME_FLUSH_ROWS,
        engine_opts=dict(min_bucket=32, max_batch=1024),
    )
    rt.publish("primary", art, PublishSpec(exact=m))
    rt.publish("secondary", art2, PublishSpec(exact=m2))
    rt.warmup("primary")
    rt.warmup("secondary")
    digest, engine = rt.registry.get_engine("primary")
    cache_before = engine.jit_cache_size()

    rng = np.random.default_rng(11)
    rows = []
    for clients in RUNTIME_CLIENTS:
        work = [
            [rng.standard_normal((RUNTIME_REQ_ROWS, D)).astype(np.float32) * 0.3
             for _ in range(reqs)]
            for _ in range(clients)
        ]
        total_rows = clients * reqs * RUNTIME_REQ_ROWS

        def fan_out(target):
            threads = [threading.Thread(target=target, args=(w,)) for w in work]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        # baseline: per-request predict, closed loop (pre-runtime pattern)
        def per_request(batches):
            for Z in batches:
                engine.predict(Z)

        t_direct = fan_out(per_request)

        # runtime: open-loop submits, one shared sync per coalesced flush
        before = rt.stats("primary")

        def coalesced(batches):
            futs = [rt.submit("primary", Z) for Z in batches]
            for f in futs:
                f.result().values

        t_runtime = fan_out(coalesced)
        after = rt.stats("primary")

        d_reqs = after["requests"] - before["requests"]
        d_flushes = max(1, after["flushes"] - before["flushes"])
        rows.append({
            "clients": clients,
            "requests": clients * reqs,
            "rows": total_rows,
            "per_request_rows_s": round(total_rows / t_direct, 1),
            "coalesced_rows_s": round(total_rows / t_runtime, 1),
            "speedup": round(t_direct / t_runtime, 2),
            "coalescing_factor": round(d_reqs / d_flushes, 2),
            "p50_ms": after["latency"]["p50_ms"],
            "p99_ms": after["latency"]["p99_ms"],
        })

    cache_after = engine.jit_cache_size()
    assert cache_after == cache_before, (
        f"coalescing must not add compiled variants "
        f"({cache_before} -> {cache_after})"
    )
    snap = rt.stats("primary")
    meta = {
        "req_rows": RUNTIME_REQ_ROWS,
        "flush_rows": RUNTIME_FLUSH_ROWS,
        "max_wait_us": RUNTIME_MAX_WAIT_US,
        "models_registered": 2,
        "steady_state_recompiles": cache_after - cache_before,
        "jit_variants": cache_after,
        "fallback_rate": snap["fallback_rate"],
    }
    rt.close()
    print("[serving] runtime throughput: coalesced vs per-request predict")
    print(fmt_table(rows, ["clients", "requests", "per_request_rows_s",
                           "coalesced_rows_s", "speedup", "coalescing_factor",
                           "p99_ms"]))
    print(f"[serving] {meta}")
    return {
        "note": (
            "open-loop concurrent clients submitting 4-row requests through "
            "Runtime (coalesced into bucket-sized engine steps) vs the same "
            "clients calling engine.predict per request (closed loop); "
            "steady_state_recompiles must be 0"
        ),
        "rows": rows,
        "meta": meta,
    }


def bench_overload() -> dict:
    """Admission control under a burst far past capacity.

    The fault injector's slow-step hook pins per-flush service time at
    ``OVERLOAD_SLOW_STEP_S`` (capacity ~= flush_rows / slow_step_s
    rows/s regardless of host speed); ``OVERLOAD_CLIENTS`` threads then
    submit back-to-back — sheds return instantly, so the offered rate
    is a large multiple of capacity by construction. Everything the CI
    gate asserts is deterministic accounting, not timing: sheds are
    typed ``RuntimeOverloaded`` with a ``retry_after_s`` hint, admitted
    + shed == submitted on both the client and telemetry side, every
    admitted future resolves under a hard timeout (zero hung futures),
    and the burst adds zero fast-path recompiles.
    """
    reqs = 15 if SMOKE else OVERLOAD_REQS_PER_CLIENT
    m = _model(seed=5)
    art = families.maclaurin.compile(m)
    fi = FaultInjector(seed=5, slow_step_rate=1.0,
                       slow_step_s=OVERLOAD_SLOW_STEP_S)
    rt = Runtime(
        max_wait_us=500.0,
        flush_rows=OVERLOAD_FLUSH_ROWS,
        max_queue_rows=OVERLOAD_QUEUE_ROWS,
        engine_opts=dict(min_bucket=32, max_batch=1024),
        fault_injector=fi,
    )
    rt.publish("hot", art, PublishSpec(exact=m))
    rt.warmup("hot")
    rng = np.random.default_rng(13)
    warm = rng.standard_normal((OVERLOAD_REQ_ROWS, D)).astype(np.float32) * 0.3
    rt.predict("hot", warm)                            # warm the serving path
    _, engine = rt.registry.get_engine("hot")
    cache_before = engine.jit_cache_size()

    work = [
        [rng.standard_normal((OVERLOAD_REQ_ROWS, D)).astype(np.float32) * 0.3
         for _ in range(reqs)]
        for _ in range(OVERLOAD_CLIENTS)
    ]
    admitted, retry_hints = [], []
    lock = threading.Lock()

    def client(batches):
        for Z in batches:
            try:
                f = rt.submit("hot", Z)
            except RuntimeOverloaded as e:
                with lock:
                    retry_hints.append(float(e.retry_after_s))
            else:
                with lock:
                    admitted.append(f)

    threads = [threading.Thread(target=client, args=(w,)) for w in work]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_burst = time.perf_counter() - t0

    # every admitted future must resolve — a future still pending after
    # the hard timeout is exactly the hang the robustness layer forbids
    hung = 0
    for f in admitted:
        try:
            f.result(timeout=OVERLOAD_RESULT_TIMEOUT_S).values
        except concurrent.futures.TimeoutError:
            hung += 1

    st = rt.stats("hot")
    cache_after = engine.jit_cache_size()
    rt.close()

    submitted = OVERLOAD_CLIENTS * reqs
    offered_rows_s = submitted * OVERLOAD_REQ_ROWS / t_burst
    capacity_rows_s = OVERLOAD_FLUSH_ROWS / OVERLOAD_SLOW_STEP_S
    meta = {
        "clients": OVERLOAD_CLIENTS,
        "submitted": submitted,
        "admitted": len(admitted),
        "shed_requests": len(retry_hints),
        "shed_requests_telemetry": st["shed_requests"],
        "retry_after_s_min": round(min(retry_hints), 4) if retry_hints else None,
        "retry_after_s_max": round(max(retry_hints), 4) if retry_hints else None,
        "hung_futures": hung,
        "queue_rows_after_drain": st["queue_rows"],
        # the telemetry gauge counts a popped batch until its flush is
        # recorded, so the provable high-water is waiting rows (bounded
        # by admission) + the batch in execution: 2x the bound
        "max_queue_rows_observed": st["max_queue_rows"],
        "max_queue_rows_bound": OVERLOAD_QUEUE_ROWS,
        "offered_rows_s": round(offered_rows_s, 1),
        "pinned_capacity_rows_s": round(capacity_rows_s, 1),
        "burst_multiple": round(offered_rows_s / capacity_rows_s, 1),
        "admitted_p50_ms": st["latency"]["p50_ms"],
        "admitted_p99_ms": st["latency"]["p99_ms"],
        "tightened_waits": st["tightened_waits"],
        "steady_state_recompiles": cache_after - cache_before,
    }
    print("[serving] overload: bounded queue under a burst past capacity")
    print(f"[serving] {meta}")
    return {
        "note": (
            "slow-step injection pins service capacity, then an 8-thread "
            "burst offers a large multiple of it; admission sheds the "
            "excess with RuntimeOverloaded(retry_after_s) and every "
            "admitted future resolves; CI gates the accounting "
            "(tools/check_bench_invariants.py)"
        ),
        "req_rows": OVERLOAD_REQ_ROWS,
        "flush_rows": OVERLOAD_FLUSH_ROWS,
        "slow_step_s": OVERLOAD_SLOW_STEP_S,
        "meta": meta,
    }


def bench_degraded_mode() -> dict:
    """Breaker-open exact serving next to the healthy fast path.

    Scripted engine faults trip the per-model circuit breaker; with a
    long ``reset_after_s`` it stays open for the whole degraded
    measurement, so every request is served by the exact streaming
    ``rbf_pred`` path. The slowdown ratio is the price of graceful
    degradation (the alternative is failing the requests); the gated
    invariants are that the breaker really was open, nothing was shed
    or left unserved, and the fast-path bucket cache gained nothing.
    """
    repeats = 10 if SMOKE else DEGRADED_REPEATS
    m = _model(seed=6)
    art = families.maclaurin.compile(m)
    fi = FaultInjector(seed=6)
    rt = Runtime(
        max_wait_us=500.0,
        flush_rows=DEGRADED_BATCH,
        engine_opts=dict(min_bucket=32, max_batch=1024),
        breaker=dict(fail_threshold=3, reset_after_s=600.0),
        fault_injector=fi,
    )
    rt.publish("hot", art, PublishSpec(exact=m))
    rt.warmup("hot")
    rng = np.random.default_rng(17)
    Z = rng.standard_normal((DEGRADED_BATCH, D)).astype(np.float32) * 0.3

    def timed_predicts():
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            rt.predict("hot", Z)
            times.append(time.perf_counter() - t0)
        t = np.asarray(times) * 1e3
        return (round(float(np.percentile(t, 50)), 4),
                round(float(np.percentile(t, 99)), 4))

    rt.predict("hot", Z)                                  # warm fast path
    healthy_p50, healthy_p99 = timed_predicts()
    _, engine = rt.registry.get_engine("hot")
    cache_before = engine.jit_cache_size()

    # trip the breaker: 3 scripted consecutive engine-step faults
    fi.fail_next(ENGINE_STEP, 3)
    failed_trips = 0
    for _ in range(3):
        try:
            rt.predict("hot", Z)
        except Exception:
            failed_trips += 1

    rt.predict("hot", Z)                  # warm the degraded slow variant
    degraded_p50, degraded_p99 = timed_predicts()
    st = rt.stats("hot")
    cache_after = engine.jit_cache_size()
    rt.close()

    meta = {
        "batch": DEGRADED_BATCH,
        "healthy_p50_ms": healthy_p50,
        "healthy_p99_ms": healthy_p99,
        "degraded_p50_ms": degraded_p50,
        "degraded_p99_ms": degraded_p99,
        "slowdown_p50": round(degraded_p50 / max(healthy_p50, 1e-9), 2),
        "breaker_state": st["breaker"]["state"],
        "breaker_trips": st["breaker"]["trips"],
        "tripping_failures": failed_trips,
        "degraded_requests": st["breaker"]["degraded_requests"],
        "breaker_shed_requests": st["breaker"]["shed_requests"],
        "steady_state_recompiles": cache_after - cache_before,
    }
    print("[serving] degraded mode: breaker-open exact path vs fast path")
    print(f"[serving] {meta}")
    return {
        "note": (
            "scripted faults trip the circuit breaker (reset_after_s "
            "600 keeps it open), then the same traffic is measured on "
            "the exact streaming degraded path; CI gates breaker state, "
            "full service (no sheds) and zero fast-path recompiles"
        ),
        "meta": meta,
    }


def _synthetic_quadform(k: int, d: int, seed: int) -> families.CompiledArtifact:
    """A random K-head quadform artifact sized for the extreme-OvR bench.

    Training a real K=4096 OvR ensemble is not what this section
    measures; serving one is. gamma = 0.01 and msq = 1 keep every
    z ~ 0.3 N(0, I) row inside the Eq 3.11 envelope (msq ||z||^2 ~ 3
    << 0.0625 / gamma^2 = 625), so the fast path serves 100% of rows.
    """
    rng = np.random.default_rng(seed)
    f32 = np.float32
    arrays = {
        "M": jnp.asarray(rng.standard_normal((k, d, d)).astype(f32) * 0.05),
        "v": jnp.asarray(rng.standard_normal((k, d)).astype(f32) * 0.1),
        "c": jnp.asarray(rng.standard_normal((k,)).astype(f32) * 0.1),
        "b": jnp.asarray(rng.standard_normal((k,)).astype(f32) * 0.1),
        "gamma": jnp.full((k,), 0.01, jnp.float32),
        "msq": jnp.ones((k,), jnp.float32),
    }
    from repro.core.families.base import base_meta

    return families.CompiledArtifact(
        family="maclaurin",
        arrays=arrays,
        meta=base_meta(d=d, num_heads=k, multiclass=True, synthetic=True),
    )


def bench_scaleout() -> dict:
    """Multi-device scale-out: replicated dispatch + head-sharded serving.

    Replica rows: each flush's service time is pinned at
    ``SCALEOUT_SLOW_STEP_S`` by the injector (see the constant block for
    why — one physical core backs every forced host device, so pinned
    GIL-releasing sleeps are the honest scaling substrate here), then
    ``replicas=N`` must deliver ~N x rows/s because the micro-batcher
    overlaps N in-flight flushes across the per-replica dispatch
    threads. Gated: rows/s monotone in N, zero steady-state recompiles,
    every replica actually served.

    Sharded rows: the K=4096 synthetic OvR model serves through
    ``head_mesh`` (shard_map over the stacked Hessian); argmax parity vs
    the unsharded reference is asserted exactly at K=16 (identical math,
    different partitioning) and gated at 1.0.
    """
    from jax.sharding import Mesh

    devices = jax.local_devices()
    ndev = len(devices)
    reqs = 8 if SMOKE else SCALEOUT_REQS_PER_CLIENT
    m = _model(seed=9)
    art = families.maclaurin.compile(m)
    rng = np.random.default_rng(23)
    work = [
        [rng.standard_normal((SCALEOUT_REQ_ROWS, D)).astype(np.float32) * 0.3
         for _ in range(reqs)]
        for _ in range(SCALEOUT_CLIENTS)
    ]
    total_rows = SCALEOUT_CLIENTS * reqs * SCALEOUT_REQ_ROWS

    counts = [n for n in SCALEOUT_REPLICAS if n <= ndev] or [1]
    replica_rows = []
    for n_rep in counts:
        fi = FaultInjector(seed=9, slow_step_rate=1.0,
                           slow_step_s=SCALEOUT_SLOW_STEP_S)
        rt = Runtime(
            max_wait_us=500.0,
            flush_rows=SCALEOUT_REQ_ROWS,
            engine_opts=dict(
                min_bucket=SCALEOUT_REQ_ROWS, max_batch=SCALEOUT_REQ_ROWS
            ),
            fault_injector=fi,
        )
        rt.publish("scale", art, PublishSpec(exact=m, replicas=n_rep))
        _, engines = rt.registry.get_engines("scale")
        cache_before = sum(e.jit_cache_size() for e in engines)

        def client(batches):
            futs = [rt.submit("scale", Z) for Z in batches]
            for f in futs:
                f.result().values
        threads = [threading.Thread(target=client, args=(w,)) for w in work]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0

        st = rt.stats("scale")
        cache_after = sum(e.jit_cache_size() for e in engines)
        rt.close()
        per_replica = st.get("replicas", {})
        flushes = [per_replica[k]["flushes"] for k in sorted(per_replica)]
        replica_rows.append({
            "replicas": n_rep,
            "rows": total_rows,
            "rows_s": round(total_rows / elapsed, 1),
            "p50_ms": st["latency"]["p50_ms"],
            "p99_ms": st["latency"]["p99_ms"],
            "per_replica_flushes": flushes,
            "all_replicas_served": (
                len(flushes) == n_rep and all(f > 0 for f in flushes)
            ),
            "steady_state_recompiles": cache_after - cache_before,
            "failed_requests": st["failed_requests"],
            "shed_requests": st["shed_requests"],
        })

    # ---- head-sharded extreme multiclass ------------------------------
    mesh = Mesh(np.array(devices), ("heads",))
    repeats = 3 if SMOKE else SCALEOUT_SHARDED_REPEATS
    d = SCALEOUT_SHARDED_D
    Zs = rng.standard_normal(
        (SCALEOUT_SHARDED_BATCH, d)
    ).astype(np.float32) * 0.3
    eng_opts = dict(
        min_bucket=SCALEOUT_SHARDED_BATCH, max_batch=SCALEOUT_SHARDED_BATCH
    )

    # exact-math parity at small K: same artifact, sharded vs unsharded
    art_small = _synthetic_quadform(SCALEOUT_PARITY_K, d, seed=31)
    ref = SVMEngine(art_small, **eng_opts)
    shd = SVMEngine(art_small, head_mesh=mesh, **eng_opts)
    r_ref, r_shd = ref.submit(Zs), shd.submit(Zs)
    parity = float(np.mean(r_ref.labels == r_shd.labels))
    scores_close = bool(
        np.allclose(r_ref.values, r_shd.values, rtol=1e-4, atol=1e-5)
    )

    def timed(engine):
        engine.predict(Zs)                                  # warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            engine.predict(Zs)
            times.append(time.perf_counter() - t0)
        t = np.asarray(times) * 1e3
        return (round(float(np.percentile(t, 50)), 3),
                round(float(np.percentile(t, 99)), 3))

    art_big = _synthetic_quadform(SCALEOUT_SHARDED_K, d, seed=37)
    big_ref = SVMEngine(art_big, **eng_opts)
    big_shd = SVMEngine(art_big, head_mesh=mesh, **eng_opts)
    ref_p50, ref_p99 = timed(big_ref)
    shd_p50, shd_p99 = timed(big_shd)
    sharded = {
        "K": SCALEOUT_SHARDED_K,
        "d": d,
        "batch": SCALEOUT_SHARDED_BATCH,
        "shards": ndev,
        "padded_heads": int(
            big_shd._serve_artifact.meta.get(
                "padded_heads", SCALEOUT_SHARDED_K
            )
        ),
        "parity_K": SCALEOUT_PARITY_K,
        "argmax_parity": parity,
        "scores_allclose": scores_close,
        "fallback_rate": big_shd.stats.fallback_rate,
        "unsharded_p50_ms": ref_p50,
        "unsharded_p99_ms": ref_p99,
        "sharded_p50_ms": shd_p50,
        "sharded_p99_ms": shd_p99,
    }

    meta = {
        "devices": ndev,
        "device_kind": jax.default_backend(),
        "clients": SCALEOUT_CLIENTS,
        "req_rows": SCALEOUT_REQ_ROWS,
        "slow_step_s": SCALEOUT_SLOW_STEP_S,
    }
    print("[serving] scaleout: replicated dispatch on forced host devices")
    print(fmt_table(replica_rows, ["replicas", "rows_s", "p50_ms", "p99_ms",
                                   "per_replica_flushes",
                                   "steady_state_recompiles"]))
    print(f"[serving] scaleout sharded: {sharded}")
    return {
        "note": (
            "replica rows: per-flush service time pinned by slow-step "
            "injection (one physical core backs all forced host devices, "
            "so sleeps that release the GIL inside the per-replica "
            "dispatch threads are what can honestly scale here); rows/s "
            "must rise monotonically with replica count and is gated "
            "structurally. sharded: K=4096 OvR served via shard_map over "
            "heads; argmax parity vs the unsharded reference gated at "
            "K=16. Generate under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        ),
        "meta": meta,
        "replica_rows": replica_rows,
        "sharded": sharded,
    }


def bench_observability() -> dict:
    """Traced vs untraced serving on identical closed-loop traffic.

    Two fresh runtimes serve the same (seeded) workload: one with
    observability disabled (``obs=False`` — no spans, no metric
    mirroring), one fully traced onto a private registry. Clients are
    CLOSED-LOOP (one outstanding request each): the p50 ratio then
    measures the per-request cost of tracing itself. An open-loop burst
    would instead measure how queueing amplifies any slowdown on a
    saturated box — real, but a property of the load, not the tracer
    (throughput impact stays visible in ``rows_s``). Request p50/p99
    come from each runtime's own latency window, so the comparison is
    request-level, not wall-clock. The traced run's accounting is then
    checked three ways — telemetry counters, tracer span counts,
    Prometheus rendering — and the booleans land in the meta for
    ``check_bench_invariants`` to gate.
    """
    reqs = 10 if SMOKE else OBS_REQS_PER_CLIENT

    def drive(obs):
        m = _model()
        art = families.maclaurin.compile(m)
        rt = Runtime(
            max_wait_us=RUNTIME_MAX_WAIT_US,
            flush_rows=RUNTIME_FLUSH_ROWS,
            engine_opts=dict(min_bucket=32, max_batch=1024),
            obs=obs,
        )
        rt.publish("primary", art, PublishSpec(exact=m))
        rt.warmup("primary")
        digest = rt.registry.resolve("primary")
        rng = np.random.default_rng(11)
        work = [
            [rng.standard_normal((OBS_REQ_ROWS, D)).astype(np.float32) * 0.3
             for _ in range(reqs)]
            for _ in range(OBS_CLIENTS)
        ]

        def client(batches):
            for Z in batches:
                rt.submit("primary", Z).result().values

        threads = [threading.Thread(target=client, args=(w,)) for w in work]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        rt.close()                       # drain; every verdict is final
        st = rt.stats(digest)
        return rt, st, digest, elapsed

    total_reqs = OBS_CLIENTS * reqs
    total_rows = total_reqs * OBS_REQ_ROWS

    def row(mode, st, elapsed):
        return {
            "mode": mode,
            "requests": total_reqs,
            "rows_s": round(total_rows / elapsed, 1),
            "p50_ms": st["latency"]["p50_ms"],
            "p99_ms": st["latency"]["p99_ms"],
        }

    # best-of-N per mode: each drive is ~100 ms, and on a small shared
    # box (1-2 cores) a single drive's p50 carries GIL/scheduler noise
    # comparable to the tracing cost under test — the minimum over
    # repeats estimates each mode's noise floor, which is the honest
    # numerator/denominator for an overhead *ratio*
    def best(make_obs):
        picked = None
        for _ in range(OBS_DRIVE_REPEATS):
            o = make_obs()
            run = (o, *drive(o))
            if picked is None or (
                run[2]["latency"]["p50_ms"] < picked[2]["latency"]["p50_ms"]
            ):
                picked = run
        return picked

    _, _, st_off, _, t_off = best(lambda: False)
    obs, rt_on, st_on, digest, t_on = best(
        lambda: Observability(seed=0, registry=MetricsRegistry())
    )
    rows = [row("untraced", st_off, t_off), row("traced", st_on, t_on)]

    # -- three-way conservation on the traced run ------------------------
    tele_balances = st_on["requests"] == (
        st_on["served_requests"] + st_on["failed_requests"]
        + st_on["deadline_timeouts"] + st_on["closed_requests"]
    )
    cons = obs.tracer.conservation(digest[:12])
    spans_match = (
        cons["admitted"] == st_on["requests"]
        and cons["served"] == st_on["served_requests"]
        and cons["shed"] == st_on["shed_requests"]
    )
    series = obs.metrics.collect()

    def prom_total(name):
        return sum(series.get(f"repro_serve_{name}_total", {}).values())

    prom_balances = prom_total("requests") == (
        prom_total("served_requests") + prom_total("failed_requests")
        + prom_total("deadline_timeouts") + prom_total("closed_requests")
    ) and prom_total("requests") == st_on["requests"]
    rendered = obs.render_prometheus()
    gauges_present = all(
        f"repro_serve_{g}" in rendered
        for g in ("validity_fraction", "fallback_rate", "queue_rows",
                  "step_time_ewma_seconds", "breaker_state")
    )

    p50_off = st_off["latency"]["p50_ms"] or 1e-9
    p99_off = st_off["latency"]["p99_ms"] or 1e-9
    meta = {
        "clients": OBS_CLIENTS,
        "reqs_per_client": reqs,
        "req_rows": OBS_REQ_ROWS,
        "drives_per_mode": OBS_DRIVE_REPEATS,
        "max_wait_us": RUNTIME_MAX_WAIT_US,
        "overhead_p50": round((st_on["latency"]["p50_ms"] or 0) / p50_off, 4),
        "overhead_p99": round((st_on["latency"]["p99_ms"] or 0) / p99_off, 4),
        "span_count": sum(
            v for k, v in obs.tracer.counts(digest[:12]).items()
            if "[" not in k
        ),
        "conservation": {
            "submitted": cons["submitted"],
            "unaccounted": cons["unaccounted"],
            "telemetry_balances": bool(tele_balances),
            "spans_match_telemetry": bool(spans_match),
            "prometheus_balances": bool(prom_balances),
            "prometheus_gauges_present": bool(gauges_present),
        },
    }
    print("[serving] observability: traced vs untraced closed-loop serving")
    print(fmt_table(rows, ["mode", "requests", "rows_s", "p50_ms", "p99_ms"]))
    print(f"[serving] {meta}")
    return {
        "note": (
            "identical seeded closed-loop workloads through Runtime(obs=False) "
            "and a fully traced Runtime (private registry); best-of-N drives "
            "per mode, p50/p99 from the per-request latency window, so "
            "overhead_p50 is the request-level tracing tax (gated <= 1.05x; "
            "the coalesce wait dominates both). "
            "conservation re-proves served+failed+expired+closed == admitted "
            "in telemetry counters, span counts and the Prometheus rendering"
        ),
        "rows": rows,
        "meta": meta,
    }


def bench_serving_http() -> dict:
    """The HTTP front door vs in-process submit on identical traffic.

    One runtime, two legs. Leg A: closed-loop clients calling
    ``rt.submit(...).result()`` directly. Leg B: the same clients as
    HTTP clients (stdlib ``http.client``, one keep-alive connection
    each) POSTing ``:predict`` to the ASGI app — the full wire path:
    parse, tenancy, executor bridge, micro-batcher, JSON response.
    Latencies are client-side per request in BOTH legs, so the
    overhead ratio is honest about everything the network adds.
    """
    reqs = 8 if SMOKE else HTTP_REQS_PER_CLIENT
    m = _model(seed=3)
    art = families.maclaurin.compile(m)
    rt = Runtime(
        max_wait_us=HTTP_MAX_WAIT_US,
        flush_rows=RUNTIME_FLUSH_ROWS,
        engine_opts=dict(min_bucket=32, max_batch=1024),
        obs=Observability(seed=0, registry=MetricsRegistry()),
    )
    rt.publish("primary", art, PublishSpec(exact=m))
    rt.warmup("primary")
    digest = rt.registry.resolve("primary")
    rng = np.random.default_rng(17)
    work = [
        [rng.standard_normal((HTTP_REQ_ROWS, D)).astype(np.float32) * 0.3
         for _ in range(reqs)]
        for _ in range(HTTP_CLIENTS)
    ]
    total_rows = HTTP_CLIENTS * reqs * HTTP_REQ_ROWS

    def fan_out(target):
        threads = [threading.Thread(target=target, args=(i, w))
                   for i, w in enumerate(work)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    # ---- leg A: in-process closed loop --------------------------------
    lat_proc: list[list[float]] = [[] for _ in range(HTTP_CLIENTS)]

    def in_process(i, batches):
        for Z in batches:
            t0 = time.perf_counter()
            rt.submit("primary", Z).result().values
            lat_proc[i].append(time.perf_counter() - t0)

    t_proc = fan_out(in_process)

    # ---- leg B: the same traffic over HTTP ----------------------------
    app = create_app(rt)
    lat_http: list[list[float]] = [[] for _ in range(HTTP_CLIENTS)]
    statuses: list[list[int]] = [[] for _ in range(HTTP_CLIENTS)]
    before = rt.stats("primary")
    with http_serve(app) as handle:
        import http.client

        def over_http(i, batches):
            conn = http.client.HTTPConnection(handle.host, handle.port,
                                              timeout=60)
            for Z in batches:
                body = json.dumps({"rows": Z.tolist()}).encode()
                t0 = time.perf_counter()
                conn.request("POST", "/v1/models/primary:predict", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                lat_http[i].append(time.perf_counter() - t0)
                statuses[i].append(resp.status)
            conn.close()

        t_http = fan_out(over_http)
    after = rt.stats("primary")

    flat_proc = np.array([t for c in lat_proc for t in c])
    flat_http = np.array([t for c in lat_http for t in c])
    flat_status = [s for c in statuses for s in c]
    d_reqs = after["requests"] - before["requests"]
    d_flushes = max(1, after["flushes"] - before["flushes"])
    cons = rt.obs.tracer.conservation(digest[:12])
    queue_rows = after["queue_rows"]
    rt.close()

    p = lambda a, q: round(float(np.percentile(a, q)) * 1e3, 3)  # noqa: E731
    rows = [
        {"path": "in_process", "clients": HTTP_CLIENTS,
         "requests": len(flat_proc), "p50_ms": p(flat_proc, 50),
         "p99_ms": p(flat_proc, 99),
         "rows_s": round(total_rows / t_proc, 1)},
        {"path": "http", "clients": HTTP_CLIENTS,
         "requests": len(flat_http), "p50_ms": p(flat_http, 50),
         "p99_ms": p(flat_http, 99),
         "rows_s": round(total_rows / t_http, 1)},
    ]
    meta = {
        "req_rows": HTTP_REQ_ROWS,
        "max_wait_us": HTTP_MAX_WAIT_US,
        "http_statuses_ok": sum(1 for s in flat_status if s == 200),
        "http_statuses_other": sum(1 for s in flat_status if s != 200),
        "http_overhead_p50": round(
            rows[1]["p50_ms"] / max(rows[0]["p50_ms"], 1e-9), 2
        ),
        "http_coalescing_factor": round(d_reqs / d_flushes, 2),
        "queue_rows_after": queue_rows,
        "conservation": cons,
    }
    print("[serving] serving_http: in-process vs HTTP front door")
    print(fmt_table(rows, ["path", "clients", "requests", "p50_ms",
                           "p99_ms", "rows_s"]))
    print(f"[serving] {meta}")
    return {
        "note": (
            "identical closed-loop traffic served in-process "
            "(rt.submit().result()) and over the stdlib HTTP front door "
            "(persistent connections, JSON bodies); latencies are "
            "client-side per request; conservation must balance and the "
            "queue must drain to zero after the HTTP leg"
        ),
        "rows": rows,
        "meta": meta,
    }


SECTIONS = (
    "engine",
    "head_scaling",
    "family_compare",
    "model_size",
    "fastfood",
    "block_sweep",
    "runtime_throughput",
    "overload",
    "degraded_mode",
    "scaleout",
    "observability",
    "serving_http",
)


def run(sections: list[str] | None = None):
    chosen = set(sections) if sections else set(SECTIONS)
    unknown = chosen - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections {sorted(unknown)}; "
                         f"known: {sorted(SECTIONS)}")

    # partial runs merge over the existing results file so a targeted rerun
    # (e.g. CI's `runtime_throughput --smoke`) keeps the other trajectories
    payload = {}
    existing = os.path.join(RESULTS_DIR, "BENCH_serving.json")
    if chosen != set(SECTIONS) and os.path.exists(existing):
        try:
            with open(existing) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError):
            payload = {}

    payload.update({
        "host_backend": jax.default_backend(),
        "svm_backend": backend.resolve(),
        "smoke": SMOKE,
        "model": {"d": D, "n_sv": N_SV},
    })
    if "engine" in chosen:
        engine_rows, engine_meta = bench_engine()
        payload["engine"] = engine_rows
        payload["engine_meta"] = engine_meta
    if "head_scaling" in chosen:
        payload["head_scaling"] = bench_heads()
    if "family_compare" in chosen:
        payload["family_compare"] = {
            "note": (
                "engine fast-path p50/p99 (fallback off) and measured error "
                "vs the exact RBF expansion on the same batch; 'exact' rows "
                "are the shared kernel-matrix GEMM baseline with zero error "
                "by definition; int8 rows serve the same model through the "
                "fused-dequant path"
            ),
            "batch": FAMILY_BATCH,
            "n_sv": FAMILY_NSV,
            "num_features": family_num_features(),
            "rows": bench_family_compare(),
        }
    if "model_size" in chosen:
        payload["model_size"] = bench_model_size()
    if "fastfood" in chosen:
        payload["fastfood"] = bench_fastfood()
    if "block_sweep" in chosen:
        payload["block_sweep"] = {
            "note": (
                "tuned = argmin over candidates INCLUDING the default, so "
                "tuned_ms <= default_ms by construction; on non-TPU hosts "
                "the dispatched path is XLA and the spread is noise"
            ),
            "platform": tuning.platform(),
            "rows": bench_block_sweep(),
        }
    if "runtime_throughput" in chosen:
        payload["runtime_throughput"] = bench_runtime_throughput()
    if "overload" in chosen:
        payload["overload"] = bench_overload()
    if "degraded_mode" in chosen:
        payload["degraded_mode"] = bench_degraded_mode()
    if "scaleout" in chosen:
        payload["scaleout"] = bench_scaleout()
    if "observability" in chosen:
        payload["observability"] = bench_observability()
    if "serving_http" in chosen:
        payload["serving_http"] = bench_serving_http()
    path = save_json("BENCH_serving.json", payload)
    print(f"[serving] wrote {path}")
    return payload


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("sections", nargs="*", choices=[[], *sorted(SECTIONS)],
                    help="sections to (re)run and merge into the results "
                         "JSON; default: all")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: same sections and JSON shape, far fewer "
                         "repeats (numbers are noisy, structure is exercised)")
    args = ap.parse_args()
    if args.smoke:
        SMOKE = True
        REPEATS = 20
        BATCHES = [1, 64, 256]
        RUNTIME_CLIENTS = [1, 8]
    from repro import compile_cache

    compile_cache.enable()
    run(args.sections or None)
