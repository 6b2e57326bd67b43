"""Degree-2 polynomial kernel models and their exact quadratic-form expansion.

Section 3.2 of the paper contrasts the *approximated* RBF model with an
*exact* degree-2 polynomial kernel model

    kappa(x_i, x_j) = (gamma x_i^T x_j + beta)^2            (Eq 3.12)

whose decision function expands exactly (Eqs 3.13-3.16, beta fixed at 1
to expose the correspondence) into the same quadratic form minus the
exp(-gamma ||z||^2) envelope and with different 2nd-order weighting:

    RBF approx:  w_i = 2 gamma a_i e^{-g||x_i||^2},  D_ii = 2 gamma^2 a_i e^{-g||x_i||^2}
    poly-2:      w_i = 2 beta gamma a_i,             D_ii = gamma^2 a_i

This module implements both the kernel-sum form and the collapsed quadratic
form of the poly-2 model (the collapse is *exact* here), used in tests to
verify the §3.2 equivalences.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.maclaurin import ApproxModel

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Poly2Model:
    """Exact kernel-expansion model with the degree-2 polynomial kernel."""

    X: Array          # (n_sv, d)
    alpha_y: Array    # (n_sv,)
    b: Array
    gamma: Array
    beta: Array


def poly2_kernel(Xa: Array, Xb: Array, gamma: Array, beta: Array) -> Array:
    return (gamma * (Xa @ Xb.T) + beta) ** 2


@jax.jit
def decision_function(model: Poly2Model, Z: Array) -> Array:
    """Exact kernel-sum form: O(n_sv d) per row."""
    K = poly2_kernel(Z, model.X, model.gamma, model.beta)
    return K @ model.alpha_y + model.b


@jax.jit
def collapse(model: Poly2Model) -> ApproxModel:
    """Exact O(d^2) collapse of a poly-2 model (Eqs 3.14-3.16, general beta).

    (gamma x^T z + beta)^2 = beta^2 + 2 beta gamma x^T z + gamma^2 (x^T z)^2
      c = beta^2 sum_i a_i
      w_i = 2 beta gamma a_i      -> v = X^T w
      D_ii = gamma^2 a_i          -> M = X^T D X

    Returned as an ApproxModel with gamma=0 so that the exp(-gamma ||z||^2)
    envelope in approx_decision_function degenerates to 1 — making the
    relation of §3.2 executable: the ONLY differences vs an approximated RBF
    model are the envelope and the (2x, e^{-g||x||^2}) re-weightings.
    """
    X, ay = model.X, model.alpha_y
    c = model.beta**2 * jnp.sum(ay)
    w = 2.0 * model.beta * model.gamma * ay
    v = X.T @ w
    dvals = model.gamma**2 * ay
    M = jnp.einsum("i,ij,ik->jk", dvals, X, X)
    sv_sq = jnp.sum(X * X, axis=-1)
    return ApproxModel(
        c=c,
        v=v,
        M=M,
        b=model.b,
        gamma=jnp.zeros_like(model.gamma),  # kills the envelope: exp(0)=1
        max_sv_sq_norm=jnp.max(sv_sq),
    )


@jax.jit
def collapse_rbf_as_poly2(model) -> ApproxModel:
    """Approximate an exact RBF model by the §3.2 poly-2 expansion.

    The remark under Eq 3.16 run in reverse: fold the SV-side exponential
    into the support values (``equivalent_poly2_alphas``), expand
    e^{2 gamma x^T z} as (1 + gamma x^T z)^2 — the beta = 1 poly-2 kernel —
    and KEEP the exp(-gamma ||z||^2) envelope:

        f(z) ~ e^{-g||z||^2} sum_i a_i' (1 + 2 g x_i^T z + g^2 (x_i^T z)^2) + b

        c = sum_i a_i',  w_i = 2 gamma a_i',  D_ii = gamma^2 a_i'

    Identical serving cost to the Maclaurin collapse (same quadratic form,
    same Eq 3.11 envelope check) but the per-term relative error bound is
    ``POLY2_REL_ERR_AT_HALF`` (7.26%) instead of 3.05% — the second-order
    coefficient is x^2/4, not x^2/2. This is the second point of the
    approximation-family axis, not a replacement for ``collapse`` (which
    is the EXACT collapse of a genuinely poly-2-trained model).
    """
    X, gamma = model.X, model.gamma
    sv_sq = jnp.sum(X * X, axis=-1)
    a2 = equivalent_poly2_alphas(model.alpha_y, sv_sq, gamma)
    c = jnp.sum(a2)
    hi = jax.lax.Precision.HIGHEST          # as in maclaurin.approximate
    v = jnp.dot(X.T, 2.0 * gamma * a2, precision=hi)
    M = jnp.einsum("i,ij,ik->jk", gamma**2 * a2, X, X, precision=hi)
    return ApproxModel(
        c=c,
        v=v,
        M=M,
        b=model.b,
        gamma=gamma,                       # envelope + Eq 3.11 check stay live
        max_sv_sq_norm=jnp.max(sv_sq),
    )


def equivalent_poly2_alphas(alpha_y_rbf: Array, sv_sq_norms: Array, gamma: Array) -> Array:
    """The paper's remark: alpha_i^(2D) = alpha_i^(RBF) e^{-gamma ||x_i||^2}.

    Folding the SV-side exponential scaling into the poly-2 support values
    makes the two models' c/v terms (beta=1) match up to the documented
    2x second-order weighting and the test-side envelope.
    """
    return alpha_y_rbf * jnp.exp(-gamma * sv_sq_norms)
