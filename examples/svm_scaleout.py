"""Multi-device scale-out demo: replicated dispatch + head sharding.

Two independent axes of scale, composed on whatever devices the host
exposes (this demo forces 8 virtual CPU devices so it runs anywhere —
on a real TPU/GPU host, drop the env var and the same code spreads over
the physical devices):

1. **Replicated engine dispatch** — ``publish(..., replicas=N)`` builds
   N engines from ONE content-addressed artifact (same digest, same
   compiled step — consistency is free) and the micro-batcher routes
   each flush to the least-loaded replica. Every replica carries its
   own circuit breaker: the demo trips ONE replica with a scripted
   fault and shows its siblings serving the fast path, undisturbed,
   while per-replica telemetry names the culprit.

2. **Head-sharded extreme multiclass** — a K=4096 one-vs-rest model's
   stacked Hessians (K, d, d) dwarf one device's comfortable footprint;
   ``head_mesh=`` partitions heads across the mesh via ``shard_map``,
   pads K to the shard count with argmax-neutral heads, and slices the
   pad columns back off before anyone sees them. Scores match the
   unsharded engine bit-for-bit at small K (shown), and 4096 heads
   serve within a single submit at large K.

    PYTHONPATH=src python examples/svm_scaleout.py
"""

import os

# must land before jax initializes its backends
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import gamma_max  # noqa: E402
from repro.core.families import maclaurin  # noqa: E402
from repro.core.rbf import SVMModel  # noqa: E402
from repro.serve import FaultInjector, PublishSpec, Runtime  # noqa: E402
from repro.serve.runtime import ENGINE_STEP  # noqa: E402
from repro.serve.svm_engine import SVMEngine  # noqa: E402

DIM = 16
REQ_ROWS = 64
CLIENTS = 8
REQS = 20
# emulated per-flush service time: on this demo's single physical CPU,
# real steps are too fast to show dispatch concurrency, so the fault
# injector pins each flush at 10 ms (a GIL-releasing sleep) — replicas
# then overlap honestly, exactly like N devices would
STEP_S = 0.010


def make_model(seed, k=1, d=DIM, n_sv=64):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_sv, d)).astype(np.float32) * 0.5
    gamma = 0.8 * float(gamma_max(jnp.asarray(X)))
    ay = rng.standard_normal((k, n_sv)).astype(np.float32) * 0.5
    b = (rng.standard_normal(k) * 0.1).astype(np.float32)
    if k == 1:
        return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay[0]),
                        b=jnp.float32(b[0]), gamma=jnp.float32(gamma))
    return SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
                    b=jnp.asarray(b), gamma=jnp.float32(gamma))


def drive(rt, alias, seed):
    """CLIENTS open-loop threads, REQS requests each; returns rows/s."""
    def client(tid, out):
        # 0.3x scale keeps rows inside the §4 envelope: the point here is
        # dispatch concurrency, not fallback traffic
        rng = np.random.default_rng((seed, tid))
        futs = [rt.submit(alias, 0.3 * rng.standard_normal(
            (REQ_ROWS, DIM)).astype(np.float32)) for _ in range(REQS)]
        out.extend(f.result(timeout=60.0) for f in futs)

    outs = [[] for _ in range(CLIENTS)]
    threads = [threading.Thread(target=client, args=(t, o))
               for t, o in enumerate(outs)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for o in outs:
        for r in o:
            r.values  # materialize
    rows = CLIENTS * REQS * REQ_ROWS
    return rows / (time.perf_counter() - t0)


def main():
    ndev = len(jax.local_devices())
    print(f"local devices: {ndev} ({jax.local_devices()[0].platform})")

    # ---- act 1: throughput scales with replica count
    model = make_model(3)
    art = maclaurin.compile(model)
    print(f"\n[replicas] {CLIENTS} clients x {REQS} reqs x {REQ_ROWS} rows, "
          f"per-flush service time pinned at {STEP_S * 1e3:.0f} ms:")
    for n in (1, 2, min(4, ndev), min(8, ndev)):
        fi = FaultInjector(seed=0, slow_step_rate=1.0, slow_step_s=STEP_S)
        with Runtime(max_wait_us=500.0, flush_rows=REQ_ROWS,
                     engine_opts=dict(min_bucket=REQ_ROWS,
                                      max_batch=REQ_ROWS),
                     fault_injector=fi) as rt:
            rt.publish("m", art, PublishSpec(exact=model, replicas=n))
            rt.predict("m", np.zeros((2, DIM), np.float32))  # warm
            rate = drive(rt, "m", seed=n)
            per = rt.stats("m")["replicas"]
            spread = [per[i]["flushes"] for i in sorted(per)]
            print(f"  replicas={n}: {rate:9.0f} rows/s  "
                  f"(flushes per replica: {spread})")

    # ---- act 2: one faulting replica degrades only itself
    fi = FaultInjector(seed=0)
    with Runtime(max_wait_us=500.0,
                 breaker=dict(fail_threshold=1, reset_after_s=60.0),
                 engine_opts=dict(min_bucket=8, max_batch=64),
                 fault_injector=fi) as rt:
        rt.publish("m", art, PublishSpec(exact=model, replicas=3))
        rng = np.random.default_rng(0)
        rt.predict("m", 0.3 * rng.standard_normal((2, DIM)).astype(np.float32))
        fi.fail_next(FaultInjector.replica_site(ENGINE_STEP, 1), 1)
        failed = 0
        for _ in range(8):
            try:
                _, valid = rt.predict(
                    "m",
                    0.3 * rng.standard_normal((4, DIM)).astype(np.float32))
                assert valid.all()          # siblings keep the FAST path
            except Exception:
                failed += 1
        per = rt.stats("m")["replicas"]
        states = {i: per[i]["breaker_state"] for i in sorted(per)}
        print(f"\n[isolation] scripted fault on replica 1: {failed} request "
              f"failed, breakers now {states} — healthy replicas never "
              f"degraded to the exact path")

    # ---- act 3: head-sharded extreme multiclass
    mesh = Mesh(np.array(jax.local_devices()), ("heads",))
    small = make_model(5, k=10)
    small_art = maclaurin.compile(small)
    ref = SVMEngine(small_art, min_bucket=64, max_batch=256)
    shd = SVMEngine(small_art, head_mesh=mesh, min_bucket=64, max_batch=256)
    Z = np.random.default_rng(1).standard_normal((64, DIM)).astype(np.float32)
    r_ref, r_shd = ref.submit(Z), shd.submit(Z)
    agree = float(np.mean(np.asarray(r_ref.labels) == np.asarray(r_shd.labels)))
    pad = shd._serve_artifact.meta.get("padded_heads", 10)
    print(f"\n[sharding] K=10 over {ndev} shards (padded to {pad} heads): "
          f"argmax parity vs unsharded = {agree:.3f}")

    big = make_model(7, k=4096, d=32)
    big_art = maclaurin.compile(big)
    eng = SVMEngine(big_art, head_mesh=mesh, min_bucket=256, max_batch=256)
    Zb = np.random.default_rng(2).standard_normal((256, 32)).astype(np.float32)
    eng.submit(Zb).block_until_ready()          # compile outside the timing
    t0 = time.perf_counter()
    res = eng.submit(Zb)
    res.values
    dt = time.perf_counter() - t0
    print(f"  K=4096 d=32: 256 rows scored in {dt * 1e3:.1f} ms "
          f"({res.values.shape[1]} score columns, heads sharded {ndev}-way)")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
