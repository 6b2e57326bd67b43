"""Serving demo (paper §4-§5): train -> compile -> serve.

The three stages are deliberately separable:

1. TRAIN an exact RBF model (training side, heavyweight).
2. COMPILE it with ``compile_model(svm, budget)`` — the paper's §4
   verification run across every approximation family (maclaurin
   quadratic form, §3.2 poly-2 expansion, random Fourier features) at
   every storage dtype (f32 and int8-quantized): each candidate is
   measured for error vs the exact expansion and serving latency on
   this host, and the cheapest artifact within the accuracy budget
   wins. The artifact is saved to an ``.npz`` file.
3. SERVE the artifact file in an ``SVMEngine`` — the engine never sees a
   training-side object; a real deployment would run this stage in a
   different process (the load below goes through the same bytes).

The engine pads every batch into a power-of-two shape bucket so repeated
traffic never recompiles, scores all heads through the family's fused
backend path, and enforces the family's accuracy contract at run time
(Eq 3.11 per-row envelope for the quadratic forms; the compile-time
held-out estimate for fourier), re-scoring violating rows exactly.

    PYTHONPATH=src python examples/svm_serving.py

This demo serves ONE model to ONE caller; for the multi-tenant layer —
content-addressed registry, alias hot-swap, async micro-batching across
concurrent clients — see ``examples/svm_runtime.py``.
"""

import os
import tempfile

import numpy as np
import jax.numpy as jnp

from repro.core import Budget, CompiledArtifact, compile_model, gamma_max
from repro.core import families
from repro.data.synthetic import make_blobs
from repro.serve.svm_engine import SVMEngine
from repro.svm import train_lssvm


def main():
    # 1. train (exact model, O(n_sv d) per prediction)
    X, y = make_blobs(600, 16, seed=3, separation=2.5)
    gamma = 0.8 * float(gamma_max(jnp.asarray(X)))
    model = train_lssvm(jnp.asarray(X), jnp.asarray(y), jnp.float32(gamma), jnp.float32(10.0))

    # 2. compile: measure every family against the budget, keep the cheapest
    artifact = compile_model(model, Budget(max_err=0.05, metric="mean_abs"))
    if artifact.meta.get("validity") != "per-row":
        # the out-of-envelope demo below exercises the PER-ROW fallback;
        # if this host's latency measurements crowned fourier (per-artifact
        # validity), pin the compilation to the quadform families instead
        artifact = compile_model(model, Budget(max_err=0.05, metric="mean_abs"),
                                 families=("maclaurin", "poly2"))
    report = artifact.meta["compile_report"]
    print(f"compiled families (budget mean_abs <= {report['limit']:.3g}):")
    for row in report["families"]:
        chosen = (row["family"] == report["chosen"]
                  and row.get("dtype") == report["chosen_dtype"])
        marker = "->" if chosen else "  "
        tag = f"{row['family']}[{row.get('dtype', '?')}]"
        if "skipped" in row:
            print(f"  {marker} {tag:18s} skipped: {row['skipped']}")
            continue
        print(f"  {marker} {tag:18s} err={row['mean_abs']:.4g} "
              f"latency={row['latency_ms']:.3f}ms bytes={row['artifact_bytes']}"
              f"{'' if row['meets_budget'] else '  (over budget)'}")

    path = os.path.join(tempfile.gettempdir(), "svm_artifact.npz")
    artifact.save(path)
    print(f"artifact -> {path} ({os.path.getsize(path)} bytes on disk)\n")

    # int8 variant of the same model: ~4x smaller serialized artifact, a
    # distinct content digest (the registry can hold both), and its own
    # measured quantization error in the meta.
    # recompile a CLEAN f32 parent rather than reusing the winner: the
    # winner's meta embeds the measured-latency compile_report, so its
    # digest is not the stable registry identity of the f32 variant
    fam = families.get_family(artifact.family)
    f32_art = fam.compile(model)
    q8_art = fam.compile(model, dtype="int8")
    print(f"int8 variant of {artifact.family!r}: "
          f"weight arrays {f32_art.nbytes()} -> {q8_art.nbytes()} bytes "
          f"({f32_art.nbytes() / q8_art.nbytes():.2f}x smaller; this demo "
          f"model is tiny, so the ~2 KB npz header hides most of it on "
          f"disk — see the model_size benchmark for real footprints), "
          f"quant err mean={q8_art.meta['quant_mean_abs_err']:.2e} "
          f"max={q8_art.meta['quant_max_abs_err']:.2e}, "
          f"digest {f32_art.digest()[:12]} vs {q8_art.digest()[:12]}\n")

    # 3. serve: reload from bytes (no training objects needed) and stream
    served = CompiledArtifact.load(path)
    engine = SVMEngine(served, model)      # exact model only for the fallback

    rng = np.random.default_rng(0)
    print("serving 20 batches; batch 9 and 14 contain out-of-envelope rows")
    for b in range(20):
        Z = rng.standard_normal((64, 16)).astype(np.float32)
        if b in (9, 14):
            Z[:5] *= 25.0  # rows violating the accuracy contract
        f, valid = engine.predict(jnp.asarray(Z))
        flag = "" if valid.all() else f"  <- {int((~valid).sum())} rows fell back to exact"
        print(f"batch {b:2d}: mean|f|={np.abs(f).mean():.3f}{flag}")

    s = engine.stats
    print(f"\nstats: {s.instances} instances in {s.batches} batches "
          f"served by the {engine.family!r} family; "
          f"fallback rate {100*s.fallback_rate:.2f}% "
          f"(accuracy contract held with the fast path for the rest)")
    print(f"shape buckets hit: {dict(sorted(s.bucket_hits.items()))}; "
          f"compiled step variants: {engine.jit_cache_size()} "
          f"(zero steady-state recompiles); "
          f"padding overhead {100*s.padding_overhead:.1f}%")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
