"""CI gate: compiled artifacts must be byte-deterministic ACROSS processes.

The registry content-addresses artifacts by the SHA-256 of their
deterministic npz bytes; everything above it (dedupe, lazy directory
indexing, alias hot-swap, int8-vs-f32 variant identity) assumes the same
model + seed compiles to bit-identical bytes in any process. A stray
nondeterminism — an unseeded rng, dict-order leakage into the meta JSON,
platform-dependent quantization rounding — would silently fork digests
between the process that saved an artifact and the one that loads it.

This script compiles one seeded model under EVERY (family, dtype)
candidate in two separate interpreter processes and fails if any digest
differs (it also checks the int8 digest actually differs from the f32
one, so the quantized variants stay distinct registry entries).

Usage: ``python tools/check_artifact_determinism.py`` (spawns its own
children; needs ``src`` importable or on PYTHONPATH). The parent imports
only ``os``/``subprocess``/``sys`` and must never touch JAX: on a TPU
host a process that has touched JAX holds the chip, and the children
could then not reach it.
"""

from __future__ import annotations

import os
import subprocess
import sys

# (label, family, dtype, extra compile opts). The label keys the digest
# comparison — "fourier" appears twice (dense and structured), and the
# structured-Fastfood int8 layout (sign/int8/int16/f16 narrowing) has its
# own cross-process bit-determinism to prove.
CASES = [
    ("maclaurin", "maclaurin", "float32", {}),
    ("maclaurin-q8", "maclaurin", "int8", {}),
    ("poly2", "poly2", "float32", {}),
    ("poly2-q8", "poly2", "int8", {}),
    ("fourier", "fourier", "float32", {}),
    ("fourier-q8", "fourier", "int8", {}),
    ("fastfood", "fourier", "float32", {"structured": True}),
    ("fastfood-q8", "fourier", "int8", {"structured": True}),
]

# f32/int8 variant pairs whose digests must stay DISTINCT registry entries.
VARIANT_PAIRS = [
    ("maclaurin", "maclaurin-q8"),
    ("poly2", "poly2-q8"),
    ("fourier", "fourier-q8"),
    ("fastfood", "fastfood-q8"),
]


def emit() -> None:
    """Child mode: print '<label> <digest>' per candidate."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core import gamma_max
    from repro.core.families import get_family
    from repro.core.rbf import SVMModel

    rng = np.random.default_rng(42)
    X = rng.standard_normal((96, 24)).astype(np.float32) * 0.5
    ay = rng.standard_normal((4, 96)).astype(np.float32) * 0.5
    b = jnp.asarray(0.1 * rng.standard_normal(4).astype(np.float32))
    svm = SVMModel(
        X=jnp.asarray(X), alpha_y=jnp.asarray(ay),
        b=b, gamma=jnp.float32(0.8 * float(gamma_max(jnp.asarray(X)))),
    )
    for label, family, dtype, opts in CASES:
        art = get_family(family).compile(
            svm, dtype=dtype, seed=7, num_features=128, **opts
        )
        print(f"{label} {art.digest()}")


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = dict(os.environ)
    extra = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src + (os.pathsep + extra if extra else "")

    def run() -> dict[str, str]:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--emit"],
            check=True, capture_output=True, text=True, env=env,
        ).stdout
        digests = {}
        for line in out.strip().splitlines():
            label, digest = line.split()
            digests[label] = digest
        return digests

    first, second = run(), run()
    problems = []
    for label, _, _, _ in CASES:
        if first[label] != second[label]:
            problems.append(
                f"{label}: digest differs across processes "
                f"({first[label][:16]} vs {second[label][:16]})"
            )
    for f32_label, q8_label in VARIANT_PAIRS:
        if first.get(f32_label) == first.get(q8_label):
            problems.append(f"{f32_label}: int8 digest equals f32 digest")
    if problems:
        print(f"[determinism] {len(problems)} violation(s):")
        for p in problems:
            print(f"  FAIL {p}")
        return 1
    print(f"[determinism] OK — {len(CASES)} (family, dtype, opts) artifacts "
          f"compile to identical digests in two separate processes")
    return 0


if __name__ == "__main__":
    if "--emit" in sys.argv:
        emit()
    else:
        sys.exit(main())
