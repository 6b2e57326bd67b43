"""Measure-don't-guess block-size selection.

``sweep`` times one kernel family over a list of candidate
``TileConfig``s on the live device and returns every measurement;
``autotune`` additionally records the winner into the tuning registry so
subsequent ``tuning.lookup`` calls (and therefore the serving engine)
pick it up. The candidate list should always INCLUDE the current default
— then the tuned pick is never slower than the default by construction
(argmin over a set containing it).

The timing loop is best-of-N wall clock with warmup, same discipline as
``benchmarks/common.timeit`` (kept separate: ``benchmarks`` sits outside
``src`` and the kernel layer must not import upward).
"""

from __future__ import annotations

import time
from typing import Callable, Iterable

import jax

from repro.kernels.common import tuning
from repro.kernels.common.config import TileConfig


def measure(fn: Callable[[], object], *, repeats: int = 5, warmup: int = 2) -> float:
    """Best-of-N wall-clock seconds of a nullary callable; blocks on results."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(
    build: Callable[[TileConfig], Callable[[], object]],
    candidates: Iterable[TileConfig],
    *,
    repeats: int = 5,
    warmup: int = 2,
) -> list[dict]:
    """Time ``build(config)()`` for every candidate.

    ``build`` returns a nullary callable closing over pre-staged operands
    (so compile time and host->device transfer stay out of the timing).
    Returns one row per candidate: {"config": TileConfig, "ms": float}.
    """
    rows = []
    for cfg in candidates:
        fn = build(cfg)
        rows.append({"config": cfg, "ms": 1e3 * measure(fn, repeats=repeats, warmup=warmup)})
    return rows


def prune_candidates(
    candidates: list[TileConfig],
    default: TileConfig,
    prior: Callable[[TileConfig], float],
    keep: int,
) -> list[TileConfig]:
    """The ``keep`` cheapest-predicted candidates, default ALWAYS kept.

    ``prior`` maps a config to a predicted cost (e.g. the analytic
    terms in ``repro.kernels.common.cost`` — ``quadform_tile_seconds``
    and friends). Pruning only decides what gets MEASURED; keeping the
    default in the measured set preserves autotune's never-worse-than-
    default guarantee even under a badly mis-calibrated prior.
    """
    ranked = sorted(candidates, key=prior)
    kept = set(ranked[: max(1, int(keep))])
    kept.add(default)
    return [c for c in candidates if c in kept]


def autotune(
    kernel: str,
    key: str,
    build: Callable[[TileConfig], Callable[[], object]],
    candidates: Iterable[TileConfig],
    *,
    repeats: int = 5,
    warmup: int = 2,
    source: str | None = None,
    prior: Callable[[TileConfig], float] | None = None,
    prior_keep: int | None = None,
) -> tuple[TileConfig, list[dict]]:
    """Sweep, pick the fastest, record it for (kernel, platform(), key).

    Returns (winner, all sweep rows). The default config for ``kernel``
    is appended to the candidates if absent, so the recorded winner can
    only tie or beat it. With ``prior`` + ``prior_keep``, only the
    ``prior_keep`` candidates with the cheapest predicted cost are
    measured (``prune_candidates``) — rank-and-prune, never
    pick-by-prediction: the winner is still chosen by measurement over a
    set that includes the default.
    """
    cands = list(candidates)
    default = tuning.lookup(kernel)
    if default not in cands:
        cands.append(default)
    if prior is not None and prior_keep is not None:
        cands = prune_candidates(cands, default, prior, prior_keep)
    rows = sweep(build, cands, repeats=repeats, warmup=warmup)
    winner = min(rows, key=lambda r: r["ms"])
    default_ms = next(r["ms"] for r in rows if r["config"] == default)
    tuning.record(
        kernel,
        key,
        winner["config"],
        measured_ms=winner["ms"],
        default_ms=default_ms,
        source=source,
    )
    return winner["config"], rows
