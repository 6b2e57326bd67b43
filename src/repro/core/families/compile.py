"""``compile_model`` — the paper's §4 verification protocol as the
entry point of the serving stack.

The paper validates the Maclaurin approximation BEFORE deploying it by
scoring sample data against the exact model. ``compile_model`` runs that
protocol across every registered approximation family: compile each
candidate, measure its error against the exact expansion and its serving
latency on the live device, and return the CHEAPEST artifact whose
error meets the budget. The full per-family report ships inside the
winner's meta (``compile_report``) so the decision is auditable from the
artifact file alone.

Latency is measured, not modeled (the paper's own methodology — and the
ordering genuinely differs across hosts: the quadform families win at
small d, fourier's O(F d) can win at large d where d^2 explodes, and on
TPU the fused kernels shift the crossover again).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.families.base import CompiledArtifact, exact_scores, stack_heads
from repro.core.rbf import SVMModel
from repro.kernels.common import autotune, cost


@dataclasses.dataclass(frozen=True)
class Budget:
    """The accuracy envelope a servable artifact must meet.

    ``max_err`` bounds the chosen error ``metric`` ("mean_abs" or
    "max_abs") of family scores vs the exact expansion on the
    verification sample. ``relative=True`` scales the bound by the mean
    |exact score| so one budget works across differently-scaled models.

    ``min_valid`` (optional) additionally requires the candidate's §4
    validity verdict to cover at least that fraction of the sample rows.
    Error and validity are different axes: a Maclaurin artifact can
    score a drifted sample accurately yet flag every row invalid — at
    serve time all of it would route through the exact fallback, so the
    artifact is "correct" but never FAST on that traffic. A caller whose
    goal is fast-path coverage (e.g. the ``DriftGuard`` recompiling
    against drifted traffic) sets ``min_valid`` to make the search skip
    such candidates in favor of one whose envelope fits the sample.
    """

    max_err: float
    metric: str = "mean_abs"
    relative: bool = False
    min_valid: float | None = None

    def __post_init__(self):
        if self.metric not in ("mean_abs", "max_abs"):
            raise ValueError(f"unknown budget metric {self.metric!r}")
        if self.min_valid is not None and not 0.0 <= self.min_valid <= 1.0:
            raise ValueError(f"min_valid must be in [0, 1], got {self.min_valid}")

    def limit(self, exact_scale: float) -> float:
        return self.max_err * (exact_scale if self.relative else 1.0)


def compile_model(
    svm: SVMModel,
    budget: Budget,
    *,
    sample=None,
    sample_n: int = 256,
    families: tuple[str, ...] | None = None,
    dtypes: tuple[str, ...] = ("float32", "int8"),
    seed: int = 0,
    family_opts: dict | None = None,
    timing_repeats: int = 5,
    cost_margin: float | None = 4.0,
) -> CompiledArtifact:
    """Compile ``svm`` under every candidate (family, dtype); return the
    fastest artifact meeting ``budget`` on the verification sample.

    Quantized variants are CANDIDATE POINTS in the same search: each
    family is compiled at every entry of ``dtypes`` (int8 adds its
    measured quantization error on top of the approximation error, and
    the combined error vs the exact expansion is what the budget gates),
    so a caller who can absorb the extra ~1e-3 error gets the ~4x smaller
    artifact without asking. ``sample=None`` synthesizes held-out points
    around the support vectors (``fourier.holdout_sample`` —
    deterministic in ``seed``). ``family_opts`` maps family name -> extra
    compile kwargs (e.g. ``{"fourier": {"num_features": 4096,
    "structured": True}}``); combinations a family rejects are skipped
    and noted in the report — the grid always carries a row (measured,
    pruned or typed-skip) for every (family, dtype) cell.
    Raises ``ValueError`` listing every measured error when no candidate
    fits the budget — the caller's recourse is a bigger fourier basis, a
    looser budget, or serving the exact model.

    ``cost_margin`` enables analytic cost PRE-pruning: once some measured
    candidate meets the budget, a later candidate whose analytically
    predicted cost (``repro.kernels.common.cost.family_candidate_seconds``)
    exceeds ``cost_margin`` x the predicted cost of the best budget-meeting
    candidate so far is skipped without compiling or timing it. Predicted
    costs are compared only to OTHER predicted costs (never to measured
    milliseconds — the prior's absolute scale is hardware-fantasy, its
    RANKING is what's trusted), pruning never fires before a real
    candidate exists, and candidates the prior cannot model are always
    measured. ``cost_margin=None`` disables pruning (exhaustive search).
    """
    from repro.core import families as _families
    from repro.core.families import quantize

    names = families or tuple(_families.FAMILIES)
    for dt in dtypes:
        quantize.check_dtype(dt)
    opts = family_opts or {}

    if sample is None:
        sample = _families.fourier.holdout_sample(svm, seed, sample_n)
    Z = jnp.asarray(np.asarray(sample, np.float32))

    k_heads = stack_heads(svm)[2]
    exact = exact_scores(svm, Z)                                    # (n, K)
    exact_scale = float(jnp.mean(jnp.abs(exact)))
    limit = budget.limit(exact_scale)

    n_sample, d_in = int(Z.shape[0]), int(Z.shape[1])
    best_predicted: float | None = None   # cheapest predicted cost among
    report = []                           # budget-meeting MEASURED candidates
    candidates: list[tuple[float, CompiledArtifact]] = []
    for name in names:
        fam = _families.get_family(name)
        for dt in dtypes:
            predicted = None
            if cost_margin is not None:
                predicted = cost.family_candidate_seconds(
                    name, dt, n=n_sample, d=d_in, k=int(k_heads),
                    num_features=opts.get(name, {}).get("num_features"),
                    structured=bool(opts.get(name, {}).get("structured")),
                )
            if (
                cost_margin is not None
                and predicted is not None
                and best_predicted is not None
                and predicted > cost_margin * best_predicted
            ):
                report.append({
                    "family": name, "dtype": dt,
                    "skipped": "pruned_by_cost",
                    "predicted_cost_s": predicted,
                    "meets_budget": False,
                })
                continue
            # caller opts override the defaults (so family_opts={'fourier':
            # {'seed': 7}} is legal); the shared sample doubles as fourier's
            # held-out set so it is not regenerated and re-scored inside
            # compile. Families that need neither absorb them via **_opts.
            try:
                art = fam.compile(
                    svm,
                    **{
                        "seed": seed,
                        "holdout": np.asarray(Z),
                        "dtype": dt,
                        **opts.get(name, {}),
                    },
                )
            except NotImplementedError as e:
                report.append({
                    "family": name, "dtype": dt, "skipped": str(e),
                    "meets_budget": False,
                })
                continue
            scores, valid = fam.score(art, Z)
            err = jnp.abs(scores - exact)
            measured = {
                "mean_abs": float(jnp.mean(err)),
                "max_abs": float(jnp.max(err)),
            }
            # fraction of sample rows the candidate would fast-path at
            # serve time (per-row mask for the quadform families, the
            # per-artifact verdict broadcast for fourier)
            valid_fraction = float(jnp.mean(jnp.asarray(valid, jnp.float32)))
            step = jax.jit(lambda Zb, _f=fam, _a=art: _f.score(_a, Zb)[0])
            latency_ms = 1e3 * autotune.measure(
                lambda: step(Z), repeats=timing_repeats, warmup=2
            )
            ok = measured[budget.metric] <= limit and (
                budget.min_valid is None or valid_fraction >= budget.min_valid
            )
            row = {
                "family": name,
                "dtype": art.dtype,
                **measured,
                "valid_fraction": round(valid_fraction, 4),
                "latency_ms": round(latency_ms, 4),
                # in-memory array bytes: constant-time, and the serialized
                # npz tracks it within ~2 KB of header (measured per
                # variant in the model_size benchmark) — serializing all
                # six candidates just to report file sizes would copy
                # tens of MB per compile for large models
                "artifact_bytes": art.nbytes(),
                "meets_budget": ok,
            }
            if predicted is not None:
                row["predicted_cost_s"] = predicted
            for key in ("quant_mean_abs_err", "quant_max_abs_err"):
                if key in art.meta:
                    row[key] = art.meta[key]
            report.append(row)
            if ok:
                candidates.append((latency_ms, art))
                if predicted is not None and (
                    best_predicted is None or predicted < best_predicted
                ):
                    best_predicted = predicted

    if not candidates:
        raise ValueError(
            f"no family meets {budget} (limit {limit:.4g}) on the "
            f"verification sample: "
            + ", ".join(
                f"{r['family']}[{r.get('dtype', '?')}]: "
                + (f"{r[budget.metric]:.4g}" if budget.metric in r else "skipped")
                for r in report
            )
        )
    latency_ms, winner = min(candidates, key=lambda t: t[0])
    return winner.with_meta(
        compile_report={
            "budget": dataclasses.asdict(budget),
            "limit": limit,
            "exact_mean_abs_score": exact_scale,
            "sample_n": int(Z.shape[0]),
            "families": report,
            "chosen": winner.family,
            "chosen_dtype": winner.dtype,
        }
    )
