"""The plain reference against the program's exact decision function, at
a tiny size on the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from repro.core import bounds
from repro.core.rbf import SVMModel, decision_function


def _model(heads, n_sv=48, d=12, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-0.8, 0.8, (n_sv, d)).astype(np.float32)
    alpha = rng.uniform(-1, 1, (heads, n_sv)).astype(np.float32)
    b = rng.normal(0, 0.01, heads).astype(np.float32)
    gamma = 0.5 / (4.0 * float(np.max(np.sum(X * X, axis=1))))
    Z = rng.uniform(-0.8, 0.8, (37, d)).astype(np.float32)
    return X, alpha, b, gamma, Z


@pytest.mark.parametrize("heads", [1, 3])
def test_exact_scores_match_the_programs_rbf(heads):
    X, alpha, b, gamma, Z = _model(heads)
    got = reference.exact_scores(X, alpha, b, gamma, Z)
    assert got.shape == (Z.shape[0], heads)
    for k in range(heads):
        svm = SVMModel(X=jnp.asarray(X), alpha_y=jnp.asarray(alpha[k]), b=jnp.float32(b[k]),
                       gamma=jnp.float32(gamma))
        want = np.asarray(decision_function(svm, jnp.asarray(Z)))
        np.testing.assert_allclose(got[:, k], want, rtol=1e-5, atol=1e-5)


def test_blocks_do_not_change_the_answer(monkeypatch):
    X, alpha, b, gamma, Z = _model(2)
    whole = reference.exact_scores(X, alpha, b, gamma, Z)
    monkeypatch.setattr(reference, "BLOCK_ROWS", 8)
    np.testing.assert_array_equal(reference.exact_scores(X, alpha, b, gamma, Z), whole)


def test_control_is_a_lower_precision():
    X, alpha, b, gamma, Z = _model(2)
    exact = reference.exact_scores(X, alpha, b, gamma, Z)
    low = reference.control_scores(X, alpha, b, gamma, Z)
    gap = np.max(np.abs(low - exact)) / np.max(np.abs(exact))
    assert 1e-4 < gap < 0.5


def test_envelope_matches_eq_3_11():
    X, _, _, gamma, Z = _model(1)
    Z = np.concatenate([Z, 40.0 * Z[:5]])
    got = reference.envelope_valid(X, gamma, Z)
    msq = np.max(np.sum(X * X, axis=1))
    want = np.asarray(bounds.bound_holds(jnp.float32(msq), jnp.sum(jnp.asarray(Z) ** 2, axis=1),
                                         jnp.float32(gamma)))
    np.testing.assert_array_equal(got, want)
    assert got[:-5].all() and not got[-5:].any()
