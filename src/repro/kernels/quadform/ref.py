"""Pure-jnp oracles for the approximated-model prediction kernel (Eq 3.8).

``quadform_predict_ref`` is the single-head oracle; ``quadform_heads_ref``
is the DELIBERATELY-UNFUSED multi-head oracle (a vmap of K independent
single-head evaluations — K separate reads of each Hessian).  Both exist
so the fused implementations (Pallas kernel and the backend's single-GEMM
XLA path) have something slow-but-obviously-correct to be tested against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def eq311_valid(z_sq, gamma, msq):
    """Per-head Eq 3.11 mask (n, K): valid iff ||x_M||^2 ||z||^2 < 1/(16 g^2).

    z_sq: (n,) or an (n, 1) column, gamma/msq: (K,) or (1, K) rows. The
    single definition shared by the Pallas kernel (which stays 2-D), the
    XLA backend path and the vmap oracle (plain jnp so it can run inside a
    kernel body). The max() guards gamma == 0 (degenerate head) without
    producing inf.
    """
    z_col = z_sq if z_sq.ndim == 2 else z_sq[:, None]
    gamma, msq = gamma.reshape(1, -1), msq.reshape(1, -1)
    rhs = 0.0625 / jnp.maximum(gamma * gamma, 1e-30)
    return msq * z_col < rhs


def quadform_predict_ref(Z, M, v, c, b, gamma):
    """f_hat(Z) = exp(-gamma ||z||^2)(c + v^T z + z^T M z) + b.

    Z: (n, d), M: (d, d), v: (d,). Returns (f_hat (n,), z_sq (n,)).
    z_sq is exposed so callers can check the Eq 3.11 bound for free.
    """
    z_sq = jnp.sum(Z * Z, axis=-1)
    g_hat = c + Z @ v + jnp.sum((Z @ M) * Z, axis=-1)
    return jnp.exp(-gamma * z_sq) * g_hat + b, z_sq


def quadform_heads_ref(Z, M_all, V, c, b, gamma, msq):
    """Per-head vmap oracle for the fused multi-head path.

    M_all: (K, d, d), V: (K, d), c/b/gamma/msq: (K,).
    Returns (scores (n, K), z_sq (n,), valid (n, K)) exactly like the fused
    implementations, but evaluates each head independently.
    """
    scores, z_sqs = jax.vmap(
        lambda Mk, vk, ck, bk, gk: quadform_predict_ref(Z, Mk, vk, ck, bk, gk)
    )(M_all, V, c, b, gamma)                               # (K, n), (K, n)
    z_sq = z_sqs[0]
    return scores.T, z_sq, eq311_valid(z_sq, gamma, msq)
