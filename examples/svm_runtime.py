"""Robust multi-tenant serving demo: one ``Runtime``, many models, many
callers — and everything that can go wrong, handled on stage.

Builds on ``examples/svm_serving.py`` (train -> compile -> artifact
file). Two models are compiled, published under aliases, and served
concurrently through the async micro-batching scheduler; then the demo
walks the runtime's robustness layer end to end:

1. **Coalescing + content addressing** — 8 client threads firing
   single-row requests merge into bucket-sized engine steps; artifacts
   are keyed on the SHA-256 of their deterministic bytes, so the same
   compile registers once. Out-of-envelope rows inside a coalesced
   flush fall back to the exact expansion without touching neighbors.
2. **Overload shedding** — the queue is BOUNDED (``max_queue_rows``).
   When a burst outruns capacity (the demo pins capacity with the
   fault injector's slow-step hook), admission control sheds the
   excess with typed ``RuntimeOverloaded`` carrying a ``retry_after_s``
   hint — bounded queue, bounded latency for everything admitted.
3. **Fault isolation + graceful degradation** — scripted engine faults
   fail exactly the batch they hit (the worker survives); three in a
   row trip the per-model circuit breaker, and while it holds the fast
   path open, traffic is served by the exact streaming ``rbf_pred``
   path (every row correct, ``valid`` all-False, and none of it
   pollutes the drift signal: an engine fault is not input drift).
   After ``reset_after_s`` a half-open probe closes the breaker again.
4. **Drift-triggered self-healing** — traffic drifts out of the
   compiled artifact's §4 validity envelope, so the windowed fallback
   rate climbs: correct, but slow forever. The ``DriftGuard`` notices,
   recompiles the family x dtype search against a reservoir sample of
   the LIVE traffic, canaries the candidate against the exact RBF
   judge, and atomically flips the alias — after which the same
   drifted traffic fast-paths again.

    PYTHONPATH=src python examples/svm_runtime.py
"""

import threading
import time

import numpy as np
import jax.numpy as jnp

from repro.core import Budget, compile_model, gamma_max
from repro.data.synthetic import make_blobs
from repro.serve import (
    DriftGuard,
    FaultInjector,
    PublishSpec,
    Runtime,
    RuntimeOverloaded,
)
from repro.serve.runtime import ENGINE_STEP
from repro.svm import train_lssvm

DIM = 16


def train(seed, sep):
    X, y = make_blobs(400, DIM, seed=seed, separation=sep)
    # moderate gamma: aggressive kernels shrink every family's envelope
    # so far that no recompile can cover drifted traffic (the heal in
    # act 4 needs at least one family whose envelope CAN fit it)
    gamma = 0.4 * float(gamma_max(jnp.asarray(X)))
    return train_lssvm(jnp.asarray(X), jnp.asarray(y),
                       jnp.float32(gamma), jnp.float32(10.0))


def main():
    budget = Budget(max_err=0.05, metric="mean_abs")
    det_model = train(3, 2.5)
    cls_model = train(7, 2.0)
    # the detector compiles to a quadform family on purpose: those carry
    # the PER-ROW §4 validity check the drift act needs to trip
    det_art = compile_model(det_model, budget, families=("maclaurin", "poly2"))
    cls_art = compile_model(cls_model, budget)

    faults = FaultInjector(seed=0, slow_step_s=0.02)
    rt = Runtime(
        max_wait_us=500.0,              # lone requests wait at most 0.5 ms
        flush_rows=64,                  # ... or flush as soon as a bucket fills
        max_queue_rows=256,             # admission bound: beyond this, shed
        breaker=dict(fail_threshold=3, reset_after_s=0.3),
        fault_injector=faults,
        engine_opts=dict(min_bucket=32, max_batch=256),
    )
    d1 = rt.publish("detector", det_art, PublishSpec(exact=det_model))
    d2 = rt.publish("classifier", cls_art, PublishSpec(exact=cls_model))
    assert rt.publish("detector", det_art, PublishSpec(exact=det_model)) == d1  # dedupe
    print(f"published detector   -> {d1[:12]} ({det_art.family})")
    print(f"published classifier -> {d2[:12]} ({cls_art.family})")

    # ---- act 1: coalescing under 8 concurrent clients, mixed tenants
    rng = np.random.default_rng(0)
    work = [
        [("detector" if rng.random() < 0.6 else "classifier",
          rng.standard_normal((1, DIM)).astype(np.float32))
         for _ in range(40)]
        for _ in range(8)
    ]
    for Z in (work[0][5][1], work[3][20][1]):   # out-of-envelope rows:
        Z *= 25.0                               # exact-fallback in place

    def client(items, out):
        futs = [(name, rt.submit(name, Z)) for name, Z in items]  # open loop
        out.extend((name, f.result()) for name, f in futs)

    outs = [[] for _ in work]
    threads = [threading.Thread(target=client, args=(w, o))
               for w, o in zip(work, outs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fellback = sum((~r.valid).sum() for o in outs for _, r in o)
    print(f"\n[coalescing] served {sum(len(o) for o in outs)} requests from "
          f"8 clients; {fellback} rows fell back inside coalesced flushes")
    for alias in ("detector", "classifier"):
        s = rt.stats(alias)
        print(f"  {alias:10s}: {s['requests']} reqs in {s['flushes']} engine "
              f"steps (coalescing x{s['coalescing_factor']}), "
              f"p99 {s['latency']['p99_ms']} ms")

    # ---- act 2: a burst past capacity is SHED, not queued unboundedly
    faults.slow_next(ENGINE_STEP, 1000)         # pin per-flush service time
    shed, admitted = [], []
    lock = threading.Lock()

    def bursty(batches):
        for Z in batches:
            try:
                f = rt.submit("classifier", Z)
            except RuntimeOverloaded as e:
                with lock:
                    shed.append(e.retry_after_s)
            else:
                with lock:
                    admitted.append(f)

    burst = [
        [rng.standard_normal((8, DIM)).astype(np.float32)
         for _ in range(40)]
        for _ in range(4)
    ]
    threads = [threading.Thread(target=bursty, args=(w,)) for w in burst]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for f in admitted:
        f.result().values                       # every admitted future resolves
    faults.clear_scripts(ENGINE_STEP)           # cancel the leftover slowness
    st = rt.stats("classifier")
    print(f"\n[overload] burst of {len(shed) + len(admitted)} requests against "
          f"a {rt.max_queue_rows}-row queue: {len(admitted)} admitted "
          f"(all served), {len(shed)} shed with "
          f"retry_after ~{(np.mean(shed) * 1e3 if shed else 0):.0f} ms hints "
          f"(telemetry agrees: {st['shed_requests']} sheds, "
          f"queue drained to {st['queue_rows']} rows)")

    # ---- act 3: engine faults trip the breaker; serving degrades, not dies
    Zb = rng.standard_normal((8, DIM)).astype(np.float32)
    faults.fail_next(ENGINE_STEP, 3)
    failures = 0
    for _ in range(3):
        try:
            rt.predict("classifier", Zb)
        except Exception:
            failures += 1                       # only ITS batch failed
    _, valid = rt.predict("classifier", Zb)         # breaker now open:
    st = rt.stats("classifier")                     # exact-served, not shed
    print(f"\n[breaker] {failures} injected engine faults failed only their "
          f"own batches, then tripped the breaker "
          f"(state={st['breaker']['state']}, trips={st['breaker']['trips']})")
    print(f"  degraded serving: {st['breaker']['degraded_requests']} request(s) "
          f"answered by the exact streaming path "
          f"(valid all-False: {not valid.any()})")
    time.sleep(0.35)                            # let reset_after_s elapse
    rt.predict("classifier", Zb)                # half-open probe, succeeds
    st = rt.stats("classifier")
    print(f"  after reset_after_s, one probe closed it again "
          f"(state={st['breaker']['state']}, probes={st['breaker']['probes']})")

    # ---- act 4: input drift -> red fallback window -> recompile/canary/flip
    # The heal budget is RELATIVE and looser than the publish budget: the
    # quadform families hit their §4 validity wall on the drifted regime
    # no matter how they recompile, so covering it means switching to the
    # globally-valid fourier family — which costs some error headroom
    # (a bigger basis buys it back; 4096 features here).
    guard = DriftGuard(
        rt, "detector", exact=det_model,
        budget=Budget(max_err=0.2, metric="mean_abs", relative=True),
        threshold=0.25, min_rows=64, min_agreement=0.9, seed=0,
        compile_opts=dict(family_opts={"fourier": {"num_features": 4096}}),
    ).attach()

    X_in, _ = make_blobs(400, DIM, seed=21, separation=2.5)
    X_in = np.asarray(X_in, np.float32)[:256]
    for i in range(0, 256, 8):                  # in-distribution traffic
        rt.predict("detector", X_in[i:i + 8])
    print(f"\n[drift] in-distribution window: "
          f"{guard.fallback_rate()} -> triggered={guard.check()['triggered']}")

    X_drift = X_in * 4.0                        # ||z||^2 leaves the envelope
    for _ in range(2):                          # drift PERSISTS — that is
        for i in range(0, 256, 8):              # what makes it drift, not
            rt.predict("detector", X_drift[i:i + 8])    # a one-off outlier
    print(f"  drifted window:         {guard.fallback_rate()}")
    verdict = guard.check()                     # recompile -> canary -> flip
    d3 = rt.registry.resolve("detector")
    print(f"  heal verdict: healed={verdict['healed']} "
          f"family={verdict.get('family')}[{verdict.get('dtype')}] "
          f"canary agreement {verdict.get('agreement', 0):.3f} "
          f"on {verdict.get('canary_rows')} reservoir rows")
    print(f"  alias flipped {verdict.get('old_digest', '?')[:12]} -> {d3[:12]}")
    for i in range(0, 256, 8):                  # same drifted traffic, again
        rt.predict("detector", X_drift[i:i + 8])
    print(f"  post-flip window:       {guard.fallback_rate()} "
          f"(the drifted traffic fast-paths on the healed artifact)")

    print(f"\nregistry: {rt.stats()['registry']}")
    rt.close()


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
