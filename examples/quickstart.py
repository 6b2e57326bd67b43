"""Quickstart: the paper in 60 seconds.

Train an LS-SVM with an RBF kernel, collapse it to the (c, v, M) quadratic
form (2nd-order Maclaurin, paper §3), check the validity bound (Eq 3.11),
and compare accuracy + size + speed.

    PYTHONPATH=src python examples/quickstart.py
"""

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    approximate,
    approx_decision_function_checked,
    decision_function,
    gamma_max,
)
from repro.core.maclaurin import approx_model_bytes
from repro.core.rbf import model_bytes
from repro.data.synthetic import make_blobs
from repro.svm import train_lssvm


def main():
    X, y = make_blobs(800, 24, seed=0, separation=2.5)
    Xtr, ytr, Xte, yte = X[:600], y[:600], X[600:], y[600:]

    gm = float(gamma_max(jnp.asarray(X)))
    gamma = 0.8 * gm
    print(f"data: d=24 n_train=600; gamma_MAX={gm:.4f} (Eq 3.11); using gamma={gamma:.4f}")

    model = train_lssvm(jnp.asarray(Xtr), jnp.asarray(ytr), jnp.float32(gamma), jnp.float32(10.0))
    print(f"exact model: n_sv={model.n_sv} (LS-SVM: every point is a SV), "
          f"{model_bytes(model)/1024:.0f} KiB")

    approx = approximate(model)
    print(f"approx model: c + v^T z + z^T M z with M {approx.M.shape}, "
          f"{approx_model_bytes(approx)/1024:.1f} KiB "
          f"({model_bytes(model)/approx_model_bytes(approx):.0f}x smaller)")

    Z = jnp.asarray(Xte)
    f_exact = np.asarray(decision_function(model, Z))
    f_hat, valid = approx_decision_function_checked(approx, Z)
    f_hat = np.asarray(f_hat)
    print(f"bound holds for {100*np.asarray(valid).mean():.1f}% of test points")
    print(f"exact accuracy:  {(np.sign(f_exact) == yte).mean():.3f}")
    print(f"approx accuracy: {(np.sign(f_hat) == yte).mean():.3f}")
    print(f"label diff:      {(np.sign(f_hat) != np.sign(f_exact)).mean()*100:.2f}% "
          f"(paper: <1% under the bound)")

    exact_fn = jax.jit(decision_function)
    from repro.core.maclaurin import approx_decision_function
    fast_fn = jax.jit(approx_decision_function)
    jax.block_until_ready(exact_fn(model, Z)); jax.block_until_ready(fast_fn(approx, Z))
    t0 = time.perf_counter(); jax.block_until_ready(exact_fn(model, Z)); t_e = time.perf_counter() - t0
    t0 = time.perf_counter(); jax.block_until_ready(fast_fn(approx, Z)); t_a = time.perf_counter() - t0
    print(f"prediction time: exact {1e3*t_e:.2f} ms vs approx {1e3*t_a:.2f} ms "
          f"-> {t_e/max(t_a,1e-9):.1f}x faster")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
