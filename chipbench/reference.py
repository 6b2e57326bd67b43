"""The plain reference: the exact RBF decision function, independent of
the program under test.

    f_k(z) = sum_i alpha_ki exp(-gamma ||x_i - z||^2) + b_k

in ``jax.numpy`` at float32 under ``jax.default_matmul_precision
("highest")``, in blocks of rows so that any pool fits. It takes the
support vectors, coefficients and gamma the benchmark drew from the seed,
never the program's compiled artifact, and imports nothing from the
program.

``control_scores`` is the same function one precision step down: both
matrix products take bfloat16 operands with float32 accumulation, what
one MXU pass at the default precision computes, the step that would
tempt a later change. The rounding is an explicit ``reduce_precision``,
which the compiler keeps (a pair of casts it may drop as excess
precision). The comparison in ``check.py`` has to fail it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 2048


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("low",))
def _block(Z, X, alpha, b, gamma, low):
    mx = _bf16 if low else (lambda a: a)               # the products' operands
    cross = jnp.dot(mx(Z), mx(X).T)
    d2 = jnp.sum(Z * Z, axis=1)[:, None] + jnp.sum(X * X, axis=1)[None, :] - 2.0 * cross
    k = jnp.exp(-gamma * jnp.maximum(d2, 0.0))
    return jnp.dot(mx(k), mx(alpha).T) + b[None, :]


def _scores(X, alpha, b, gamma, Z, low: bool) -> np.ndarray:
    X = jnp.asarray(X, jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32).reshape(-1, X.shape[0])   # (K, n_sv)
    b = jnp.asarray(b, jnp.float32).reshape(-1)
    gamma = jnp.float32(gamma)
    Z = np.asarray(Z, np.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, Z.shape[0], BLOCK_ROWS):
            out.append(np.asarray(_block(jnp.asarray(Z[start:start + BLOCK_ROWS]),
                                         X, alpha, b, gamma, low)))
    return np.concatenate(out) if out else np.zeros((0, alpha.shape[0]), np.float32)


def exact_scores(X, alpha, b, gamma, Z) -> np.ndarray:
    """(n, K) exact decision values in float32 at "highest" precision."""
    return _scores(X, alpha, b, gamma, Z, False)


def control_scores(X, alpha, b, gamma, Z) -> np.ndarray:
    """(n, K) decision values from bfloat16 operands: the control."""
    return _scores(X, alpha, b, gamma, Z, True)


def envelope_valid(X, gamma, Z) -> np.ndarray:
    """Eq 3.11 of the paper per row: ||x_M||^2 ||z||^2 < 1 / (16 gamma^2),
    with x_M the support vector of largest norm."""
    X = np.asarray(X, np.float64)
    Z = np.asarray(Z, np.float64)
    msq = float(np.max(np.sum(X * X, axis=1)))
    return msq * np.sum(Z * Z, axis=1) < 1.0 / (16.0 * float(gamma) ** 2)
