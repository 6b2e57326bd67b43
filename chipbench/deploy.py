"""A deployment from its configuration file and a seed.

The benchmark draws the support vectors, the coefficients and the rows it
sends from ``--seed`` itself, on the device, in one jitted call each, as
the configuration's feature kind prescribes. The kinds follow the feature
character of the paper's Table 1 data sets (the configuration's
``features``):

  binary        a9a: d = 123, one-hot codes of the 14 attributes of UCI
                Adult, one 1 in each attribute's group of columns
  pixels        mnist: about 19% of entries uniform in [0, 1], the rest 0
  dense         ijcnn1, sensit: uniform in [-0.8, 0.8]
  standardized  epsilon: standard normal rows scaled to unit L2 norm

The program is handed only the exact model and the rows; ``reference.py``
computes from the same drawn arrays.

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


# a9a's columns, attribute by attribute (the LIBSVM binary data page: the
# 6 continuous attributes of UCI Adult cut into quantiles, the 8
# categorical ones one column per category): age 5, workclass 8, fnlwgt 5,
# education 16, education-num 5, marital-status 7, occupation 14,
# relationship 6, race 5, sex 2, capital-gain 2, capital-loss 2,
# hours-per-week 5, native-country 41. Every drawn row has 14 ones, so
# ||x||^2 = 14 as in a9a's rows with no missing attribute.
A9A_GROUPS = (5, 8, 5, 16, 5, 7, 14, 6, 5, 2, 2, 2, 5, 41)
_A9A_GROUP = np.repeat(np.arange(len(A9A_GROUPS)), A9A_GROUPS)
_A9A_CATEGORY = np.concatenate([np.arange(g) for g in A9A_GROUPS])


def _features(key, n: int, d: int, kind: str):
    k1, k2 = jax.random.split(key)
    if kind == "binary":
        if d != len(_A9A_GROUP):
            raise ValueError(f"binary rows are a9a's {len(_A9A_GROUP)} columns, not d={d}")
        pick = jax.random.randint(k1, (n, len(A9A_GROUPS)), 0, np.asarray(A9A_GROUPS))
        return (pick[:, _A9A_GROUP] == _A9A_CATEGORY).astype(jnp.float32)
    if kind == "pixels":
        keep = jax.random.uniform(k1, (n, d)) < 0.19
        return jnp.where(keep, jax.random.uniform(k2, (n, d)), 0.0)
    if kind == "dense":
        return jax.random.uniform(k1, (n, d), minval=-0.8, maxval=0.8)
    if kind == "standardized":
        x = jax.random.normal(k1, (n, d))
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)
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
