"""Second-order Maclaurin approximation of RBF kernel expansions (§3).

Collapses f(z) = sum_i alpha_i y_i exp(-gamma ||x_i - z||^2) + b into the
fixed-size quadratic form (Eq 3.8)

    f_hat(z) = exp(-gamma ||z||^2) (c + v^T z + z^T M z) + b

with (Eq 3.7, matrix form):

    c = sum_i alpha_y_i exp(-gamma ||x_i||^2)            -- g(0)
    v = X^T w,   w_i = 2 gamma   alpha_y_i exp(-gamma ||x_i||^2)   -- gradient
    M = X^T D X, D_ii = 2 gamma^2 alpha_y_i exp(-gamma ||x_i||^2)  -- Hessian

(our X is (n_sv, d) row-major, hence the transposes relative to the paper's
column-major X). Construction is a single GEMM — the paper's ATLAS argument,
our MXU argument. Prediction is O(d^2) independent of n_sv.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import backend
from repro.core.rbf import SVMModel

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ApproxModel:
    """The approximated model: three scalars, a d-vector and a d x d matrix.

    ``max_sv_sq_norm`` stores ||x_M||^2 of the max-norm SV so the validity
    bound (Eq 3.11) can be checked at prediction time for free.
    """

    c: Array
    v: Array          # (d,)
    M: Array          # (d, d), symmetric
    b: Array
    gamma: Array
    max_sv_sq_norm: Array

    @property
    def d(self) -> int:
        return self.v.shape[0]

    def num_parameters(self) -> int:
        """Stored scalars: c, v, M, b, gamma, ||x_M||^2 (Table-3 accounting)."""
        return self.v.size + self.M.size + 4


@jax.jit
def approximate(model: SVMModel) -> ApproxModel:
    """Build (c, v, M) from an exact model. One pass; cost O(n_sv d^2) GEMM."""
    X, ay, gamma = model.X, model.alpha_y, model.gamma
    sv_sq_norms = jnp.sum(X * X, axis=-1)                      # (n_sv,)
    base = ay * jnp.exp(-gamma * sv_sq_norms)                  # alpha_y e^{-g||x||^2}
    c = jnp.sum(base)
    w = 2.0 * gamma * base                                     # (n_sv,)
    # f32 at HIGHEST: a TPU's default matmul rounds to bf16, which costs
    # the collapsed model ~7x its error on mnist (compile time only)
    hi = jax.lax.Precision.HIGHEST
    v = jnp.dot(X.T, w, precision=hi)                          # (d,)
    dvals = 2.0 * gamma**2 * base                              # D diagonal
    M = jnp.einsum("i,ij,ik->jk", dvals, X, X, precision=hi)   # X^T D X
    return ApproxModel(
        c=c,
        v=v,
        M=M,
        b=model.b,
        gamma=gamma,
        max_sv_sq_norm=jnp.max(sv_sq_norms),
    )


def _as_heads(model: ApproxModel):
    """One ApproxModel viewed as a K=1 stack for the fused backend path."""
    one = lambda x: jnp.reshape(x, (1,))
    return (
        model.M[None],
        model.v[None],
        one(model.c),
        one(model.b),
        one(model.gamma),
        one(model.max_sv_sq_norm),
    )


@jax.jit
def approx_decision_function(model: ApproxModel, Z: Array) -> Array:
    """f_hat(Z) per Eq 3.8. O(d^2) per row. Dispatched via repro.core.backend
    (Pallas kernel on TPU, fused single-GEMM XLA elsewhere)."""
    scores, _, _ = backend.quadform_heads(Z, *_as_heads(model))
    return scores[:, 0]


@jax.jit
def approx_decision_function_checked(model: ApproxModel, Z: Array) -> tuple[Array, Array]:
    """f_hat(Z) plus the per-instance validity flag of Eq 3.11.

    valid[i] == True guarantees every term in the linear combination had
    relative error < 3.05% (conservative, via Cauchy-Schwarz). The check is
    free: ||z||^2 is already needed for the exp(-gamma ||z||^2) factor.
    """
    scores, _, valid = backend.quadform_heads(Z, *_as_heads(model))
    return scores[:, 0], valid[:, 0]


def approx_predict_labels(model: ApproxModel, Z: Array) -> Array:
    return jnp.where(approx_decision_function(model, Z) >= 0, 1, -1)


def approx_model_bytes(model: ApproxModel) -> int:
    """In-memory size of the approximated model (Table-3 analogue)."""
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(model)
    )


@partial(jax.jit, static_argnames=())
def hybrid_decision_function(
    approx: ApproxModel, exact: SVMModel, Z: Array
) -> tuple[Array, Array]:
    """Beyond-paper hybrid: approx fast path, exact fallback where Eq 3.11 fails.

    Returns (values, used_fast_path mask). Rows violating the bound are
    re-evaluated exactly, preserving the paper's accuracy guarantee without
    globally abandoning the speedup. With data-dependent gather this would be
    ragged; we keep it dense (select) so it stays jit/TPU friendly — the
    exact pass prices at the full batch, so the engine layer batches
    violating rows separately (see repro.serve.svm_engine).
    """
    from repro.core.rbf import decision_function

    f_hat, valid = approx_decision_function_checked(approx, Z)
    f_exact = decision_function(exact, Z)
    return jnp.where(valid, f_hat, f_exact), valid
