"""Sweep the serving kernels' tile sizes and record the winners.

For each shape bucket the sweep times the dispatched serving primitive
(``backend.quadform_heads``, ``backend.rbf_scores`` for the exact
fallback, ``backend.fastfood_score`` for structured fourier) under
candidate ``TileConfig``s. The analytic prior
(``repro.kernels.common.cost``) first prunes each list to the
``PRIOR_KEEP`` cheapest-predicted candidates. The kernel's default is always measured,
so the recorded pick is never slower than the default
(``autotune.autotune``). The winners are merged into the table at
``--out`` (default: the checked-in ``tuning_table.json`` that the engine
reads back), under the platform the sweep ran on.

On a host without a TPU the dispatched path is XLA and ignores block
sizes, so the spread there is timing noise. Run it on the chip for a
``tpu`` entry::

    PYTHONPATH=src python tools/sweep_tiles.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import approximate, backend, families, gamma_max
from repro.core.rbf import SVMModel
from repro.kernels.common import TileConfig, autotune, cost, tuning

BLOCK_N = (64, 128, 256, 512)
BLOCK_M = (64, 128, 256, 512)
PRIOR_KEEP = 3  # measured candidates per sweep, besides the default
SOURCE = "tools/sweep_tiles.py"


def _block_n_candidates(n: int) -> list[TileConfig]:
    """``BLOCK_N`` clamped to ``n`` and deduplicated, so a small bucket
    still gets a real sweep instead of only the appended default."""
    return [TileConfig(block_n=bn) for bn in sorted({min(bn, n) for bn in BLOCK_N})]


def sweep(
    *,
    d: int = 64,
    n_sv: int = 512,
    buckets: tuple[int, ...] = (32, 256, 1024),
    batch: int = 256,
    num_features: int = 2048,
    out: str = tuning.TABLE_PATH,
) -> list[tuple[str, str]]:
    """Sweep every kernel at these shapes, merge the winners into ``out``.

    ``quadform`` is swept at each of ``buckets``, the exact fallback and
    structured fourier at ``batch`` rows. Returns the (kernel, shape key)
    of every entry it recorded.
    """
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((n_sv, d)).astype(np.float32) * 0.5)
    ay = jnp.asarray(rng.standard_normal(n_sv).astype(np.float32))
    gamma = jnp.float32(float(gamma_max(X)) * 0.8)
    m = SVMModel(X=X, alpha_y=ay, b=jnp.float32(0.1), gamma=gamma)
    rows = lambda n: jnp.asarray(rng.standard_normal((n, d)).astype(np.float32) * 0.3)
    recorded = []

    def tune(kernel, key, score, Z, candidates, prior):
        """Time ``score(Z, config)`` under each candidate and record the pick."""

        def build(cfg):
            step = jax.jit(lambda Zb: score(Zb, cfg))
            return lambda: step(Z)

        autotune.autotune(
            kernel,
            key,
            build,
            candidates,
            source=SOURCE,
            prior=prior,
            prior_keep=PRIOR_KEEP,
        )
        recorded.append((kernel, key))

    am = approximate(m)
    one = lambda x: jnp.reshape(jnp.asarray(x, jnp.float32), (1,))
    scalars = (am.c, am.b, am.gamma, am.max_sv_sq_norm)
    quadform = (am.M[None], am.v[None], *map(one, scalars))
    for n in buckets:
        tune(
            "quadform",
            tuning.shape_key(d=d, k=1, n=n),
            lambda Zb, cfg: backend.quadform_heads(Zb, *quadform, config=cfg),
            rows(n),
            _block_n_candidates(n),
            lambda cfg, n=n: cost.quadform_tile_seconds(cfg, n=n, d=d, k=1),
        )

    # the exact fallback: the SV stream's tile
    tune(
        "rbf_pred",
        tuning.shape_key(d=d, m=n_sv, n=batch),
        lambda Zb, cfg: backend.rbf_scores(Zb, X, ay, m.gamma, m.b, config=cfg),
        rows(batch),
        [TileConfig(block_n=256, block_m=bm) for bm in BLOCK_M],
        lambda cfg: cost.rbf_tile_seconds(cfg, n=batch, d=d, m=n_sv),
    )

    # structured fourier: the Z tile of the fused FWHT scorer, under the key
    # the family's tile_lookup resolves at serve time
    fourier = families.get_family("fourier")
    a = fourier.compile(m, num_features=num_features, structured=True).arrays
    ff = [a[k] for k in ("ff_b", "ff_g", "ff_perm", "ff_scale", "phase", "weights")]
    ff.append(a["b"])
    tune(
        "fwht",
        tuning.shape_key(d=d, f=num_features, n=batch),
        lambda Zb, cfg: backend.fastfood_score(Zb, *ff, config=cfg),
        rows(batch),
        _block_n_candidates(batch),
        lambda cfg: cost.fwht_tile_seconds(
            cfg, n=batch, d=d, f=num_features, k=a["weights"].shape[0]
        ),
    )

    tuning.save_table(out)
    return recorded


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--out",
        default=tuning.TABLE_PATH,
        help="tuning table to merge the winners into (default: the checked-in one)",
    )
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()
    recorded = sweep(out=args.out)
    table = tuning.load_table(args.out)["entries"][tuning.platform()]
    for kernel, key in recorded:
        print(json.dumps({"kernel": kernel, "key": key, **table[kernel][key]}))
    print(f"tuning table -> {args.out}")
