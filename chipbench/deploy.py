"""A deployment from its configuration file and a seed.

The benchmark draws the support vectors, the coefficients and the rows it
sends from ``--seed`` itself, on the device, in one jitted call each, as
the configuration's feature kind prescribes (copied from the shapes of
the paper's Table 1 data sets). The program is handed only the exact
model and the rows; ``reference.py`` computes from the same drawn arrays.

    gamma = gamma_ratio x gamma_max,  gamma_max = 1 / (4 max_i ||x_i||^2)

The ratio (the paper's gamma over its gamma_max) sets the approximation
error and how far rows sit inside the Eq 3.11 envelope; gamma itself
follows from the synthetic support vectors' norms.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Model:
    """The exact RBF model the benchmark drew: device arrays, (K, n_sv)
    coefficients and (K,) biases whatever K is."""

    X: jax.Array
    alpha: jax.Array
    b: jax.Array
    gamma: float
    heads: int


def key_for(seed: int, stream: int):
    """A JAX key from a seed of any size (past 32 bits too) and a stream id."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def _features(key, n: int, d: int, kind: str):
    k1, k2 = jax.random.split(key)
    if kind == "pixels":            # mnist-like: [0, 1] values, about 81% zeros
        keep = jax.random.uniform(k1, (n, d)) < 0.19
        return jnp.where(keep, jax.random.uniform(k2, (n, d)), 0.0)
    raise ValueError(f"unknown feature kind {kind!r}")


@functools.partial(jax.jit, static_argnames=("n_sv", "d", "heads", "kind"))
def _draw_model(key, ratio, *, n_sv, d, heads, kind):
    kx, ka, ks, kb = jax.random.split(key, 4)
    X = _features(kx, n_sv, d, kind)
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (heads, n_sv)), 1.0, -1.0)
    alpha = jax.random.uniform(ka, (heads, n_sv)) * sign
    alpha = alpha - jnp.mean(alpha, axis=1, keepdims=True)   # sum alpha_i y_i = 0
    b = 0.01 * jax.random.normal(kb, (heads,))
    gamma = ratio / (4.0 * jnp.max(jnp.sum(X * X, axis=1)))
    return X, alpha, b, gamma


@functools.partial(jax.jit, static_argnames=("n", "d", "kind"))
def _draw_pool(key, *, n, d, kind):
    return _features(key, n, d, kind)


def make_model(cfg: dict, seed: int) -> Model:
    X, alpha, b, gamma = _draw_model(
        key_for(seed, 0), jnp.float32(cfg["gamma_ratio"]), n_sv=int(cfg["n_sv"]),
        d=int(cfg["d"]), heads=int(cfg["heads"]), kind=cfg["features"])
    return Model(X=X, alpha=alpha, b=b, gamma=float(gamma), heads=int(cfg["heads"]))


def make_pool(cfg: dict, seed: int, rows: int) -> np.ndarray:
    """(rows, d) distinct request rows from the support vectors' own
    distribution."""
    Z = _draw_pool(key_for(seed, 1), n=int(rows), d=int(cfg["d"]), kind=cfg["features"])
    return np.asarray(Z, np.float32)


def program_model(model: Model):
    """The exact model in the program's own type (binary: one head)."""
    from repro.core.rbf import SVMModel

    one = model.heads == 1
    return SVMModel(X=model.X, alpha_y=model.alpha[0] if one else model.alpha,
                    b=model.b[0] if one else model.b, gamma=jnp.float32(model.gamma))


def publish(runtime, cfg: dict, model: Model, options: dict, alias: str):
    """Compile the configuration's pinned family and dtype, publish it with
    its exact model, and warm the buckets the cell uses (all of the
    engine's, unless the cell's ``warm_buckets`` names some). Returns the
    served engines."""
    from repro.core import families
    from repro.serve import PublishSpec

    svm = program_model(model)
    art = families.get_family(cfg["family"]).compile(svm, dtype=cfg["dtype"])
    buckets = options.get("warm_buckets")
    runtime.publish(alias, art, PublishSpec(exact=svm, warmup=False if buckets else None))
    _, engines = runtime.registry.get_engines(alias)
    if buckets:
        for engine in engines:
            engine.warmup(list(buckets))
    return engines
