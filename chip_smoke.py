"""Bring-up smoke of the serving path on a TPU, in one process.

    python3 chip_smoke.py [--seed N]              # one chip: the main path
    python3 chip_smoke.py --chips 4 [--seed N]    # four chips: replicas and
                                                  # head sharding, nothing else

One chip, at the full width of the ``mnist`` deployment (d=780, K=10
one-vs-rest heads, 8,192 support vectors, gamma=1e-4 from
``data/synthetic.py``), built from ``--seed`` and nothing on disk:

  device   JAX reports a TPU, and the backend dispatch picks compiled
           Pallas kernels (not the XLA twins, not interpret mode).
  compile  ``compile_model`` measures every family x {float32, int8} on
           the chip; structured (Fastfood) fourier, which the default
           grid leaves out, is scored against its XLA twin.
  serve    the chosen artifact is published with its exact model behind
           the stdlib HTTP server; concurrent ``:predict`` requests carry
           some rows beyond the Eq 3.11 envelope (per-row exact
           fallback), and one batch goes through ``submit_exact``.
  check    every served row against the exact RBF decision function in
           f32 at "highest" matmul precision, computed on the chip:
           fast-path rows within the artifact's budget, exact rows at
           f32 tolerance, labels equal to the exact argmax.
  account  no failed batch, breaker closed, no degraded row, no
           recompile after warmup, the expected fallback row count.

Four chips: ``replicas=4`` of the mnist model (every replica's step and
exact fallback on its own device, flushes spread over all four), and a
K=4096, d=32 one-vs-rest model head-sharded over a 4-device mesh,
compared with the exact reference and with the unsharded engine on one
chip.

Any failure raises and the exit code is non-zero. Only when every phase
passed is the last line of stdout ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

DATASET = "mnist"
HEADS = 10
N_SV = 8192
ALIAS = "mnist"
NUM_FEATURES = 2048        # fourier basis: two Fastfood stacks at d' = 1024
BUDGET_REL = 1e-2          # fast path: mean |error| <= 1% of mean |exact score|
F32_RTOL = 1e-4            # exact-path rows vs the reference
TWIN_RTOL = 2.0 ** -7     # Pallas vs XLA twin, relative to max |score|: one bf16
                           # pass (the MXU's f32 default) keeps 8 significant bits
MIN_BUCKET, MAX_BATCH = 32, 256
CLIENTS, REQS, ROWS = 8, 3, 16
BIG_HEADS, BIG_D, BIG_SV = 4096, 32, 1024


class SmokeFailure(AssertionError):
    pass


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


# ------------------------------------------------------------------ device


def require_tpu(chips: int):
    """The devices to run on; fails unless the serving path is the chip's."""
    import jax

    from repro.core import backend

    devs = jax.devices()
    require(devs[0].platform == "tpu", f"no TPU: JAX reports {devs[0].platform!r}")
    require(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} present")
    require(backend.resolve() == "pallas",
            f"backend dispatch resolves to {backend.resolve()!r}, not 'pallas'")
    require(not backend._interpret(), "Pallas kernels would run in interpret mode")
    log("device", f"{len(devs)} x {devs[0].device_kind} ({devs[0].platform}), "
                  f"backend=pallas, compiled kernels")
    return devs


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


# ------------------------------------------------------------------ models


def mnist_svm(seed: int):
    """OvR RBF model at the mnist shape; returns (svm, held-out rows)."""
    import jax.numpy as jnp

    from repro.core.rbf import SVMModel
    from repro.data.synthetic import DATASETS, make_dataset

    spec = DATASETS[DATASET]
    X, _, Z, _, _ = make_dataset(DATASET, scale=(N_SV + 1) / spec.n_train, seed=seed)
    rng = np.random.default_rng(seed)
    alpha = rng.uniform(0.0, 1.0, (HEADS, N_SV)) * rng.choice([-1.0, 1.0], (HEADS, N_SV))
    alpha -= alpha.mean(axis=1, keepdims=True)     # sum_i alpha_i y_i = 0, as in the dual
    svm = SVMModel(
        X=jnp.asarray(X[:N_SV]),
        alpha_y=jnp.asarray(alpha, jnp.float32),
        b=jnp.asarray(rng.normal(0.0, 0.01, HEADS), jnp.float32),
        gamma=jnp.float32(spec.paper_gamma),
    )
    return svm, Z


def wide_ovr_svm(seed: int):
    """The K=4096, d=32 one-vs-rest model of the head-sharded path."""
    import jax.numpy as jnp

    from repro.core import gamma_max
    from repro.core.rbf import SVMModel

    rng = np.random.default_rng(seed)
    X = (0.5 * rng.standard_normal((BIG_SV, BIG_D))).astype(np.float32)
    alpha = rng.standard_normal((BIG_HEADS, BIG_SV)).astype(np.float32)
    return SVMModel(
        X=jnp.asarray(X),
        alpha_y=jnp.asarray(alpha - alpha.mean(axis=1, keepdims=True)),
        b=jnp.asarray(rng.normal(0.0, 0.01, BIG_HEADS), jnp.float32),
        gamma=jnp.float32(0.25 * float(gamma_max(jnp.asarray(X)))),
    )


def reference(svm, Z) -> np.ndarray:
    """(n, K) exact decision values, f32 at "highest" precision, on the chip."""
    from repro.core.families.base import exact_scores

    return np.asarray(exact_scores(svm, Z))


def envelope_limit(svm) -> float:
    """Eq 3.11 limit on ||z||^2: ||x_M||^2 ||z||^2 < 1 / (16 gamma^2)."""
    msq = float(np.max(np.sum(np.asarray(svm.X) ** 2, axis=1)))
    return 1.0 / (16.0 * float(svm.gamma) ** 2 * msq)


def beyond_envelope(rows: np.ndarray, svm) -> np.ndarray:
    """``rows`` scaled to 4x the Eq 3.11 limit on ||z||^2."""
    norms = np.sum(rows * rows, axis=1, keepdims=True)
    return (rows * np.sqrt(4.0 * envelope_limit(svm) / norms)).astype(np.float32)


# ------------------------------------------------------------------ checks


def check_exact_rows(what: str, got, ref) -> float:
    """Exact-path rows: f32 agreement with the reference; returns the error."""
    err = float(np.max(np.abs(got - ref))) if len(ref) else 0.0
    tol = F32_RTOL * max(1.0, float(np.max(np.abs(ref)))) if len(ref) else 0.0
    require(err <= tol, f"{what}: max |served - exact| {err:.3g} > {tol:.3g}")
    return err


def check_fast_rows(what: str, got, ref, limit: float) -> float:
    """Fast-path rows: mean |error| within the artifact's budget limit."""
    err = float(np.mean(np.abs(got - ref))) if len(ref) else 0.0
    require(err <= limit, f"{what}: mean |served - exact| {err:.3g} > limit {limit:.3g}")
    return err


def check_labels(what: str, labels, scores, ref) -> int:
    """Labels are the served argmax and the exact argmax. A row may differ
    from the exact argmax only where the exact scores of the two labels are
    closer than twice that row's own served error (a near tie); returns
    how many such rows there were."""
    require(np.array_equal(labels, np.argmax(scores, axis=1)),
            f"{what}: labels are not the argmax of the served scores")
    best = np.argmax(ref, axis=1)
    rows = np.arange(len(ref))
    gap = ref[rows, best] - ref[rows, labels]
    row_err = np.max(np.abs(scores - ref), axis=1)
    ties = (labels != best) & (gap <= 2.0 * row_err)
    require(np.all((labels == best) | ties),
            f"{what}: {int(np.sum((labels != best) & ~ties))} labels differ "
            f"from the exact argmax beyond a near tie")
    return int(np.sum(ties))


def xla_twin(fn, *args) -> np.ndarray:
    """``fn`` through the XLA formulations at "highest" precision: the
    reference a Pallas kernel is held to."""
    import jax

    from repro.core import backend

    prev = backend.set_backend("xla")
    try:
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(fn)(*args))
    finally:
        backend.set_backend(prev)


# ------------------------------------------------------------ one chip


def compile_phase(svm, seed: int):
    from repro.core.families import Budget, compile_model

    t0 = time.perf_counter()
    art = compile_model(
        svm, Budget(max_err=BUDGET_REL, relative=True), seed=seed,
        family_opts={"fourier": {"num_features": NUM_FEATURES}},
        cost_margin=None,                       # measure every candidate
    )
    report = art.meta["compile_report"]
    log("compile", f"compile_model: {time.perf_counter() - t0:.1f} s on the chip, "
                   f"limit {report['limit']:.4g} (mean |err|), "
                   f"sample {report['sample_n']} rows")
    for row in report["families"]:
        require("skipped" not in row, f"candidate not measured: {row}")
        log("compile", f"  {row['family']:9s} {row['dtype']:7s} "
                       f"latency {row['latency_ms']:.4f} ms  "
                       f"mean|err| {row['mean_abs']:.4g}  max|err| {row['max_abs']:.4g}  "
                       f"valid {row['valid_fraction']:.4f}  "
                       f"bytes {row['artifact_bytes']}  "
                       f"meets_budget={row['meets_budget']}")
    grid = {(r["family"], r["dtype"]) for r in report["families"]}
    require(len(grid) == 6, f"expected 3 families x 2 dtypes, got {sorted(grid)}")
    log("compile", f"chosen {report['chosen']} / {report['chosen_dtype']}")
    require(art.meta.get("validity") == "per-row",
            f"chosen family {art.family!r} has no per-row envelope, so no row "
            f"would take the per-row exact fallback")
    return art


def structured_phase(svm, Z, seed: int) -> None:
    """Fastfood fourier (the ``fwht`` kernels) against its XLA twin."""
    import jax
    import jax.numpy as jnp

    from repro.core.families import fourier

    Zd = jnp.asarray(Z[:MAX_BATCH], jnp.float32)
    for dtype in ("float32", "int8"):
        art = fourier.compile(svm, num_features=NUM_FEATURES, structured=True,
                              dtype=dtype, seed=seed, holdout=Z[:MAX_BATCH])
        score = lambda Zb, a=art: fourier.score(a, Zb)[0]     # noqa: E731
        got = np.asarray(jax.jit(score)(Zd))
        twin = xla_twin(score, Zd)
        err = float(np.max(np.abs(got - twin)))
        tol = TWIN_RTOL * float(np.max(np.abs(twin)))
        log("structured", f"fourier/fastfood {dtype}: F={art.meta['num_features']}, "
                          f"max |pallas - xla| {err:.3g} (limit {tol:.3g}), "
                          f"held-out mean|err| vs exact {art.meta['holdout_mean_abs_err']:.4g}")
        require(err <= tol, f"fastfood {dtype}: Pallas and XLA disagree by {err:.3g}")


class Client:
    """JSON over one keep-alive localhost connection."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)

    def post(self, path: str, body: dict):
        self.conn.request("POST", path, body=json.dumps(body),
                          headers={"content-type": "application/json"})
        resp = self.conn.getresponse()
        return resp.status, json.loads(resp.read())


def make_requests(svm, Z):
    """CLIENTS x REQS requests of ROWS rows; every third carries one row
    beyond the envelope. Returns (per-client request lists, far flags)."""
    per_client, far_flags, i = [], [], 0
    for c in range(CLIENTS):
        reqs = []
        for r in range(REQS):
            rows = np.array(Z[np.arange(i, i + ROWS) % len(Z)], np.float32)
            i += ROWS
            far = np.zeros(ROWS, bool)
            if (c + r) % 3 == 0:
                far[c % ROWS] = True
                rows[far] = beyond_envelope(rows[far], svm)
            reqs.append(rows)
            far_flags.append(far)
        per_client.append(reqs)
    return per_client, far_flags


def serve_phase(svm, art, Z, dev) -> None:
    from repro.serve import PublishSpec, Runtime, create_app, serve

    limit = art.meta["compile_report"]["limit"]
    runtime = Runtime(max_wait_us=2000.0, warmup_on_load=True,
                      engine_opts=dict(min_bucket=MIN_BUCKET, max_batch=MAX_BATCH))
    with tempfile.TemporaryDirectory() as spool:
        app = create_app(runtime, spool_dir=spool)
        handle = serve(app)
        try:
            t0 = time.perf_counter()
            digest = runtime.publish(ALIAS, art, PublishSpec(exact=svm, warmup=True))
            variants = runtime.warmup(ALIAS)
            _, engine = runtime.registry.get_engine(ALIAS)
            steps_warm = engine.stats.compiled_steps
            log("serve", f"published {digest[:12]} ({art.family}/{art.dtype}), "
                         f"{variants} bucket variants warm in "
                         f"{time.perf_counter() - t0:.1f} s")

            per_client, far_flags = make_requests(svm, Z)

            def client(reqs):
                c, out = Client(handle.port), []
                try:
                    for rows in reqs:
                        status, body = c.post(f"/v1/models/{ALIAS}:predict",
                                              {"rows": rows.tolist()})
                        require(status == 200, f"predict -> {status}: {body}")
                        require(body["digest"] == digest, "response names another digest")
                        out.append(body)
                finally:
                    c.conn.close()
                return out

            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(CLIENTS) as pool:
                bodies = [b for got in pool.map(client, per_client) for b in got]
            wall = time.perf_counter() - t0
            rows = np.concatenate([r for reqs in per_client for r in reqs])
            far = np.concatenate(far_flags)
            scores = np.concatenate([np.asarray(b["scores"], np.float32) for b in bodies])
            labels = np.concatenate([np.asarray(b["labels"]) for b in bodies])
            valid = np.concatenate([np.asarray(b["valid"], bool) for b in bodies])
            ref = reference(svm, rows)
            stats = runtime.stats(ALIAS)
            log("serve", f"{len(bodies)} HTTP requests, {len(rows)} rows "
                         f"({int(far.sum())} beyond the envelope) from {CLIENTS} "
                         f"clients in {wall:.2f} s, {stats['flushes']} flushes")

            require(np.array_equal(valid, ~far),
                    "fast-path validity is not exactly the in-envelope rows")
            fast_err = check_fast_rows("fast path", scores[valid], ref[valid], limit)
            fb_err = check_exact_rows("per-row fallback", scores[far], ref[far])
            ties = check_labels("served", labels, scores, ref)
            log("check", f"fast path: {int(valid.sum())} rows, mean |err| "
                         f"{fast_err:.4g} <= {limit:.4g}")
            log("check", f"per-row fallback: {int(far.sum())} rows, max |err| "
                         f"{fb_err:.3g} (f32 tolerance)")
            log("check", f"labels == exact argmax on every row "
                         f"(near ties allowed: {ties})")

            require(stats["batch_failures"] == 0, f"batch failures: {stats['batch_failures']}")
            require(stats["failed_requests"] == 0, f"failed requests: {stats['failed_requests']}")
            breaker = stats["breaker"]
            require(breaker["state"] == "closed", f"breaker {breaker['state']}")
            require(breaker["degraded_rows"] == 0, f"degraded rows: {breaker['degraded_rows']}")
            require(engine.stats.compiled_steps == steps_warm,
                    f"{engine.stats.compiled_steps - steps_warm} recompiles after warmup")
            fallback = engine.stats.fallback_instances
            require(fallback == int(far.sum()),
                    f"fallback rows {fallback}, expected {int(far.sum())}")
            log("account", f"batch_failures=0 failed_requests=0 breaker=closed "
                           f"degraded_rows=0 recompiles_after_warmup=0 "
                           f"fallback_rows={fallback}")

            # breaker-degraded serving's path, scored directly: a full bucket
            Zx = np.array(Z[-MAX_BATCH:], np.float32)
            Zx[:4] = beyond_envelope(Zx[:4], svm)
            res = engine.submit_exact(Zx)
            require(not np.any(res.valid), "submit_exact rows claim the fast path")
            ref_x = reference(svm, Zx)
            ex_err = check_exact_rows("submit_exact", res.values, ref_x)
            check_labels("submit_exact", res.labels, res.values, ref_x)
            log("check", f"submit_exact: {len(Zx)} rows, max |err| {ex_err:.3g} "
                         f"(f32 tolerance)")
        finally:
            handle.close()
            runtime.close()
    log("memory", f"peak_bytes_in_use {peak_bytes(dev)}")


def one_chip(seed: int, devs) -> None:
    t0 = time.perf_counter()
    svm, Z = mnist_svm(seed)
    log("model", f"{DATASET}: d={svm.d} K={HEADS} n_sv={svm.n_sv} "
                 f"gamma={float(svm.gamma):g}, built in {time.perf_counter() - t0:.1f} s")
    art = compile_phase(svm, seed)
    structured_phase(svm, Z, seed)
    serve_phase(svm, art, Z, devs[0])


# ----------------------------------------------------------- four chips


def replicas_phase(seed: int, devs) -> None:
    from repro.core.families import maclaurin
    from repro.serve import PublishSpec, Runtime

    svm, Z = mnist_svm(seed)
    art = maclaurin.compile(svm)
    limit = BUDGET_REL * float(np.mean(np.abs(reference(svm, Z[:MAX_BATCH]))))
    bucket = 64
    with Runtime(max_wait_us=2000.0, warmup_on_load=True,
                 engine_opts=dict(min_bucket=bucket, max_batch=bucket)) as runtime:
        t0 = time.perf_counter()
        runtime.publish(ALIAS, art, PublishSpec(exact=svm, replicas=4, warmup=True))
        _, engines = runtime.registry.get_engines(ALIAS)
        log("replicas", f"4 replicas built and warm in {time.perf_counter() - t0:.1f} s")
        placed = [e._device for e in engines]
        require(len(set(placed)) == 4, f"replicas share devices: {placed}")

        rows = np.array(Z[:bucket], np.float32)
        far = beyond_envelope(rows[:3], svm)
        ref, ref_far = reference(svm, rows), reference(svm, far)
        for i, e in enumerate(engines):
            scores = e._step(e._put(rows))[0]
            exact = e._slow(e._put(far))
            on_step, on_exact = scores.devices(), exact.devices()
            log("replicas", f"replica {i}: pinned {e._device}, step ran on "
                            f"{sorted(map(str, on_step))}, exact fallback ran on "
                            f"{sorted(map(str, on_exact))}")
            require(on_step == {e._device} and on_exact == {e._device},
                    f"replica {i} computed off its device")
            check_fast_rows(f"replica {i} step", np.asarray(scores)[:bucket], ref, limit)
            check_exact_rows(f"replica {i} exact", np.asarray(exact), ref_far)

        # concurrent traffic through the batcher: flushes spread over replicas
        batches = [np.array(Z[np.arange(j * 16, (j + 1) * 16) % len(Z)], np.float32)
                   for j in range(32)]
        for j in range(0, 32, 4):
            batches[j][:1] = beyond_envelope(batches[j][:1], svm)
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = [pool.submit(runtime.submit, ALIAS, b) for b in batches]
            results = [f.result().result(timeout=600) for f in futures]
        allrows = np.concatenate(batches)
        ref_all = reference(svm, allrows)
        scores = np.concatenate([r.values for r in results])
        valid = np.concatenate([r.valid for r in results])
        labels = np.concatenate([r.labels for r in results])
        require(int((~valid).sum()) == 8, f"{int((~valid).sum())} fallback rows, expected 8")
        check_fast_rows("replicas fast path", scores[valid], ref_all[valid], limit)
        check_exact_rows("replicas fallback", scores[~valid], ref_all[~valid])
        check_labels("replicas", labels, scores, ref_all)
        stats = runtime.stats(ALIAS)
        flushes = {i: stats["replicas"][i]["flushes"] for i in sorted(stats["replicas"])}
        log("replicas", f"{len(batches)} requests ({len(allrows)} rows, 8 beyond the "
                        f"envelope) -> flushes per replica {flushes}, "
                        f"batch_failures={stats['batch_failures']}, "
                        f"breaker={stats['breaker']['state']}")
        require(len(flushes) == 4 and all(v > 0 for v in flushes.values()),
                f"flushes did not reach all four replicas: {flushes}")
        require(stats["batch_failures"] == 0 and stats["breaker"]["state"] == "closed",
                "replica traffic failed a batch or opened the breaker")


def timed_submit(engine, Z):
    """(result, ms) of one warm submit, materialized, on the host clock."""
    engine.submit(Z).block_until_ready()                    # compile
    t0 = time.perf_counter()
    res = engine.submit(Z)
    res.values
    return res, (time.perf_counter() - t0) * 1e3


def head_sharded_phase(seed: int, devs) -> None:
    from jax.sharding import Mesh

    from repro.core.families import maclaurin
    from repro.serve import SVMEngine

    svm = wide_ovr_svm(seed)
    art = maclaurin.compile(svm)
    mesh = Mesh(np.array(devs[:4]), ("heads",))
    sharded = SVMEngine(art, head_mesh=mesh, min_bucket=MAX_BATCH, max_batch=MAX_BATCH)
    single = SVMEngine(art, device=devs[0], min_bucket=MAX_BATCH, max_batch=MAX_BATCH)
    Z = (0.5 * np.random.default_rng(seed + 1).standard_normal((MAX_BATCH, BIG_D))
         ).astype(np.float32)
    (rs, ms_s), (r1, ms_1) = timed_submit(sharded, Z), timed_submit(single, Z)
    require(rs.valid.all() and r1.valid.all(), "rows outside the envelope")
    ref = reference(svm, Z)
    limit = BUDGET_REL * float(np.mean(np.abs(ref)))
    parity = float(np.mean(rs.labels == r1.labels))
    err_s = check_fast_rows("head-sharded", rs.values, ref, limit)
    err_1 = check_fast_rows("unsharded", r1.values, ref, limit)
    ties = check_labels("head-sharded", rs.labels, rs.values, ref)
    log("sharding", f"K={BIG_HEADS} d={BIG_D} over a 4-device head mesh: argmax "
                    f"parity vs the unsharded engine on one chip {parity:.4f}; "
                    f"mean |err| vs exact {err_s:.4g} (unsharded {err_1:.4g}, "
                    f"limit {limit:.4g}); near ties vs exact argmax {ties}")
    log("sharding", f"one warm {MAX_BATCH}-row submit: sharded "
                    f"{ms_s:.2f} ms, single chip {ms_1:.2f} ms "
                    f"(host clock, one sample)")
    require(parity == 1.0, f"argmax parity {parity} vs the unsharded engine")


# ------------------------------------------------------------------ main


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro import compile_cache

    log("cache", f"compile cache: {compile_cache.enable()}")
    devs = require_tpu(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        replicas_phase(args.seed, devs)
        head_sharded_phase(args.seed, devs)
    else:
        one_chip(args.seed, devs)
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main(sys.argv[1:])
    except Exception:                      # noqa: BLE001 — any failure exits non-zero
        traceback.print_exc()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        # server, batcher and runtime threads are closed on every path above;
        # _exit also ends any that a failed phase left behind
        os._exit(code)
