"""The ``fourier`` family — random Fourier features for the Gaussian kernel.

Rahimi & Recht's estimator: with frequencies W ~ N(0, 2 gamma I) and
phases p ~ U[0, 2 pi),

    k(x, z) = e^{-gamma ||x - z||^2}  ~  (2/F) sum_f cos(w_f.x + p_f) cos(w_f.z + p_f)

so the whole expansion collapses into per-head weight vectors at compile
time:

    weights[k, f] = (2/F) sum_i alpha_y[k, i] cos(w_f . x_i + p_f)
    f_k(z)       ~  weights[k] . cos(W z + p) + b_k

Prediction is O(F d) (dense) or O(F log d) with ``structured=True`` — the
Fastfood construction (Le et al. 2013): W is never materialized; each
stack of d' = 2^ceil(log2 d) features is S H G Pi H B with diagonal
B (signs), G (Gaussian), scaling S and a permutation Pi, applied via the
in-place Walsh-Hadamard transform. Construction cost drops from O(F d)
memory to O(F), the projection from O(F d) to O(F log d) FLOPs.

Unlike the quadform families there is NO per-row validity bound — the
estimator's error is probabilistic in F, uniform over the whole domain
rather than gated by an envelope around the origin. The accuracy contract
is therefore established at COMPILE time, paper-§4 style: a held-out
sample (caller-provided or synthesized around the SVs) is scored against
the exact expansion and the measured error ships in the artifact meta
(``holdout_mean_abs_err`` / ``holdout_max_abs_err``). The serving engine
falls back per ARTIFACT, not per row: if the estimate violates
``err_tolerance`` every row takes the exact path.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import backend
from repro.core.families import quantize
from repro.core.families.base import (
    PAD_HEAD_BIAS,
    CompiledArtifact,
    base_meta,
    exact_scores,
    stack_heads,
)
from repro.core.rbf import SVMModel
from repro.kernels.common import TileConfig, tuning
from repro.kernels.fwht import ref as _fwht_ref

NAME = "fourier"
TILE_KERNEL = "rff_score"
TILE_KERNEL_Q8 = "rff_score_q8"
TILE_KERNEL_FF = "fwht"
TILE_KERNEL_FF_Q8 = "fwht_q8"

DEFAULT_NUM_FEATURES = 1024
DEFAULT_HOLDOUT_N = 256


# ------------------------------------------------------------ construction


def compile(                                                   # noqa: A001
    svm: SVMModel,
    *,
    num_features: int = DEFAULT_NUM_FEATURES,
    structured: bool = False,
    dtype: str = "float32",
    seed: int = 0,
    err_tolerance: float | None = None,
    holdout=None,
    holdout_n: int = DEFAULT_HOLDOUT_N,
    **_opts,
) -> CompiledArtifact:
    """Sample features, fold the expansion into per-head weights, measure
    the held-out error, and pack the servable arrays.

    ``structured=True`` rounds ``num_features`` up to a whole number of
    Fastfood stacks (each 2^ceil(log2 d) wide). ``dtype="int8"``
    quantizes the big operands — dense: the projection matrix
    (per-feature-row scales) and the (K, F) readout (per-head scales);
    structured: the G/S diagonals (per-stack scales, folded into one
    combined multiplier), the readout, plus lossless narrowing of the
    sign diagonal, permutation indices and phase — and the held-out
    error below is then measured on the QUANTIZED artifact, so the
    meta's accuracy contract describes what actually ships.
    """
    quantize.check_dtype(dtype)
    X = np.asarray(svm.X, np.float32)
    gamma = float(svm.gamma)
    ay2, b, k, multiclass = stack_heads(svm)
    d = X.shape[1]
    rng = np.random.default_rng(seed)

    if structured:
        arrays, f, proj_meta = _fastfood_arrays(rng, d, num_features, gamma)
        proj_x = _fastfood_project(
            jnp.asarray(X), arrays["ff_b"], arrays["ff_g"],
            arrays["ff_perm"], arrays["ff_scale"],
        )
    else:
        f = int(num_features)
        W = rng.normal(0.0, np.sqrt(2.0 * gamma), size=(f, d)).astype(np.float32)
        arrays = {"W": jnp.asarray(W)}
        proj_x = jnp.asarray(X) @ arrays["W"].T
        proj_meta = {"projection": "dense"}

    phase = jnp.asarray(
        rng.uniform(0.0, 2.0 * np.pi, size=(f,)).astype(np.float32)
    )
    phi_x = jnp.cos(proj_x + phase[None, :])                   # (n_sv, F)
    weights = (2.0 / f) * (ay2.astype(jnp.float32) @ phi_x)    # (K, F)

    arrays.update(
        phase=phase, weights=weights, b=b.astype(jnp.float32)
    )
    art = CompiledArtifact(
        family=NAME,
        arrays=arrays,
        meta=base_meta(
            d=d, num_heads=k, multiclass=multiclass,
            kind="rff", validity="global", num_features=f, seed=int(seed),
            **proj_meta,
        ),
    )

    Zh = holdout if holdout is not None else holdout_sample(svm, seed, holdout_n)
    Zh = jnp.asarray(np.asarray(Zh, np.float32))
    if dtype == quantize.INT8_DTYPE:
        art = quantize_rff_artifact(art, holdout=Zh)

    # §4-style pre-serving verification: measure the estimator on held-out
    # points and ship the verdict with the artifact. For int8 the verdict
    # is measured on the QUANTIZED artifact — the accuracy contract must
    # describe the arrays being served, not their f32 parent.
    exact = exact_scores(svm, Zh)
    approx, _ = score(art, Zh)
    err = jnp.abs(approx - exact)
    mean_err = float(jnp.mean(err))
    max_err = float(jnp.max(err))
    return art.with_meta(
        holdout_n=int(Zh.shape[0]),
        holdout_mean_abs_err=mean_err,
        holdout_max_abs_err=max_err,
        err_tolerance=err_tolerance,
        valid_globally=bool(err_tolerance is None or mean_err <= err_tolerance),
    )


def quantize_rff_artifact(
    art: CompiledArtifact, *, holdout=None
) -> CompiledArtifact:
    """Int8 variant of a dense-projection RFF artifact.

    W — the O(F d) bulk — goes int8 with one scale per feature row (each
    row's scale folds onto its projection column post-GEMM); the per-head
    readout weights go int8 with per-head scales (the feature axis is the
    readout's CONTRACTION axis, so any finer grouping could not fold);
    phase and bias stay f32. Measured quantization error vs the f32
    parent rides in the meta when ``holdout`` is given. Fastfood-
    projection artifacts route to ``quantize_fastfood_artifact``.
    """
    if art.meta.get("projection") == "fastfood":
        return quantize_fastfood_artifact(art, holdout=holdout)
    a = art.arrays
    w_q, w_scale = quantize.quantize_rows(a["W"])            # (F,d), (F,)
    wt_q, wt_scale = quantize.quantize_rows(a["weights"])    # (K,F), (K,)
    q_art = CompiledArtifact(
        family=art.family,
        arrays={
            "W": w_q, "W_scale": w_scale,
            "weights": wt_q, "weights_scale": wt_scale,
            "phase": a["phase"], "b": a["b"],
        },
        meta={**art.meta, "dtype": quantize.INT8_DTYPE},
    )
    if holdout is not None:
        q_art = q_art.with_meta(
            **quantize.measure_quant_error(art, q_art, holdout)
        )
    return q_art


def quantize_fastfood_artifact(
    art: CompiledArtifact, *, holdout=None
) -> CompiledArtifact:
    """Int8 variant of a structured (Fastfood) RFF artifact.

    A Fastfood artifact has no O(F d) operand, so the footprint win comes
    from narrowing EVERY array that scales with F or K:

      * ``ff_b``: exact +-1 signs -> int8, lossless, no scale;
      * ``ff_g`` / ``ff_scale``: int8 with one scale per stack row
        (``quantize_rows``). Both diagonals multiply elementwise on the
        same transform columns, so their per-stack scale PRODUCT folds
        once per stack on the transform output (``ff_stack_scale``, the
        analogue of rff_score_q8's post-GEMM fold) — the per-element int8
        codes reconstruct the shape, one f32 multiplier per stack
        reconstructs the magnitude;
      * ``ff_perm``: int16 when d' fits (lossless narrowing);
      * ``phase``: float16 — a phase offset into cos() needs ~1e-3 rad
        absolute accuracy, which f16 delivers over [0, 2 pi);
      * ``weights`` (K, F): int8 with per-head scales, exactly like the
        dense readout; ``b`` stays f32 (K values, argmax-critical).

    Codes and scales are computed on host in float64 with round-half-even
    (see ``quantize``), so the serialized bytes are deterministic and
    content-addressing survives. Measured quantization error vs the f32
    parent rides in the meta when ``holdout`` is given.
    """
    if art.meta.get("projection") != "fastfood":
        raise ValueError("not a fastfood-projection artifact")
    a = art.arrays
    g_q, g_scale = quantize.quantize_rows(a["ff_g"])         # (S,dd), (S,)
    s_q, s_scale = quantize.quantize_rows(a["ff_scale"])     # (S,dd), (S,)
    wt_q, wt_scale = quantize.quantize_rows(a["weights"])    # (K,F), (K,)
    stack_scale = (
        np.asarray(g_scale, np.float64) * np.asarray(s_scale, np.float64)
    ).astype(np.float32)
    q_art = CompiledArtifact(
        family=art.family,
        arrays={
            "ff_b": quantize.quantize_signs(a["ff_b"]),
            "ff_g": g_q,
            "ff_scale": s_q,
            "ff_stack_scale": jnp.asarray(stack_scale),
            "ff_perm": quantize.compact_perm(a["ff_perm"]),
            "phase": jnp.asarray(a["phase"], jnp.float16),
            "weights": wt_q, "weights_scale": wt_scale,
            "b": a["b"],
        },
        meta={**art.meta, "dtype": quantize.INT8_DTYPE},
    )
    if holdout is not None:
        q_art = q_art.with_meta(
            **quantize.measure_quant_error(art, q_art, holdout)
        )
    return q_art


def holdout_sample(svm: SVMModel, seed: int, n: int = DEFAULT_HOLDOUT_N):
    """Deterministic held-out points near the data manifold: SVs plus
    per-feature-scaled Gaussian jitter. Derived from ``seed`` so the
    compile-time verdict is reproducible from the artifact meta alone."""
    X = np.asarray(svm.X, np.float32)
    rng = np.random.default_rng(np.uint32(seed) ^ np.uint32(0x5EED))
    idx = rng.integers(0, X.shape[0], size=n)
    sigma = X.std(axis=0) + 1e-6
    return X[idx] + 0.5 * sigma[None, :] * rng.standard_normal(
        (n, X.shape[1])
    ).astype(np.float32)


def _fastfood_arrays(rng, d: int, num_features: int, gamma: float):
    """Sample the diagonal operators for ceil(F / d') Fastfood stacks.

    Each stack realizes d' = 2^ceil(log2 d) frequency rows S H G Pi H B
    whose norms match W ~ N(0, 2 gamma I): rows of H G Pi H B have norm
    ||g|| sqrt(d'), so S_ii = sqrt(2 gamma) chi_i / (||g|| sqrt(d')) with
    chi_i ~ chi(d') gives ||w_i|| = sqrt(2 gamma) chi_i, the Gaussian
    row-norm distribution.
    """
    dd = 1 << max(1, (d - 1).bit_length())                     # next pow2 >= d
    stacks = -(-int(num_features) // dd)
    f = stacks * dd
    B = rng.choice(np.float32([-1.0, 1.0]), size=(stacks, dd))
    G = rng.standard_normal((stacks, dd)).astype(np.float32)
    perm = np.stack([rng.permutation(dd) for _ in range(stacks)]).astype(np.int32)
    chi = np.sqrt(rng.chisquare(dd, size=(stacks, dd))).astype(np.float32)
    g_norm = np.linalg.norm(G, axis=-1, keepdims=True)
    scale = np.sqrt(2.0 * gamma) * chi / (g_norm * np.sqrt(dd))
    arrays = {
        "ff_b": jnp.asarray(B),
        "ff_g": jnp.asarray(G),
        "ff_perm": jnp.asarray(perm),
        "ff_scale": jnp.asarray(scale.astype(np.float32)),
    }
    return arrays, f, {"projection": "fastfood", "dd": dd, "stacks": stacks}


# The transform arithmetic lives in ``repro.kernels.fwht.ref`` — ONE
# butterfly implementation shared by the XLA formulation, the Pallas
# kernel body, and the compile-time projection here. These aliases keep
# the long-standing family-level names working.
fwht = _fwht_ref.fwht
_fastfood_project = _fwht_ref.fastfood_project


# ---------------------------------------------------------------- serving


def score(
    artifact: CompiledArtifact, Z, *, config: TileConfig | None = None
):
    """(scores (n, K), valid_rows (n,)).

    Every (projection, dtype) combination dispatches through the
    ``core/backend`` seam: dense via ``rff_score`` / ``rff_score_q8``,
    Fastfood via ``fastfood_score`` / ``fastfood_score_q8`` — the fused
    FWHT Pallas kernel on TPU, the algebraically identical XLA
    formulation elsewhere.

    ``valid_rows`` is the compile-time held-out verdict broadcast over
    the batch: there is no per-row envelope for RFF, so either every row
    is inside the accuracy contract or none is (engine falls back per
    artifact).
    """
    a = artifact.arrays
    if artifact.meta.get("projection") == "fastfood":
        if artifact.dtype == quantize.INT8_DTYPE:
            scores = backend.fastfood_score_q8(
                Z, a["ff_b"], a["ff_g"], a["ff_perm"], a["ff_scale"],
                a["ff_stack_scale"], a["phase"],
                a["weights"], a["weights_scale"], a["b"], config=config,
            )
        else:
            scores = backend.fastfood_score(
                Z, a["ff_b"], a["ff_g"], a["ff_perm"], a["ff_scale"],
                a["phase"], a["weights"], a["b"], config=config,
            )
    elif artifact.dtype == quantize.INT8_DTYPE:
        scores = backend.rff_score_q8(
            Z, a["W"], a["W_scale"], a["phase"],
            a["weights"], a["weights_scale"], a["b"], config=config,
        )
    else:
        scores = backend.rff_score(
            Z, a["W"], a["phase"], a["weights"], a["b"], config=config
        )
    valid = jnp.full(
        (scores.shape[0],), bool(artifact.meta.get("valid_globally", True))
    )
    return scores, valid


def pad_heads(artifact: CompiledArtifact, multiple: int) -> CompiledArtifact:
    """Pad the head axis up to a multiple of ``multiple`` (head sharding).

    Only the (K, F) readout, its per-head scales (int8) and the (K,)
    bias carry a head axis; padding heads get zero weights (int8 zero
    codes dequantize to exact zeros under any scale — scale 1 keeps the
    epilogue fold harmless) and the argmax-neutral ``PAD_HEAD_BIAS``.
    RFF validity is a per-artifact verdict, so padding cannot perturb it.
    """
    k = artifact.num_heads
    pad = (-k) % max(1, int(multiple))
    if pad == 0:
        return artifact
    a = artifact.arrays
    f = int(artifact.meta["num_features"])
    arrays = dict(a)
    if artifact.dtype == quantize.INT8_DTYPE:
        arrays["weights"] = jnp.concatenate(
            [a["weights"], jnp.zeros((pad, f), jnp.int8)]
        )
        arrays["weights_scale"] = jnp.concatenate(
            [a["weights_scale"], jnp.ones((pad,), jnp.float32)]
        )
    else:
        arrays["weights"] = jnp.concatenate(
            [a["weights"], jnp.zeros((pad, f), jnp.float32)]
        )
    arrays["b"] = jnp.concatenate(
        [a["b"], jnp.full((pad,), PAD_HEAD_BIAS, jnp.float32)]
    )
    return CompiledArtifact(
        family=NAME,
        arrays=arrays,
        meta={**artifact.meta, "padded_heads": k + pad},
    )


def score_sharded(
    artifact: CompiledArtifact, Z, *, mesh, config: TileConfig | None = None
):
    """``score`` with the (K, F) readout partitioned over ``mesh``.

    All four (projection, dtype) combinations serve: the per-row
    projection work — the dense GEMM, or Fastfood's O(F log d')
    butterflies, strictly cheaper to replicate — runs per shard, while
    the (K, F) readout, its int8 per-head scale epilogue and the bias
    partition over the mesh's first axis. The validity verdict is
    per-artifact meta, computed OUTSIDE the sharded region.
    """
    a = artifact.arrays
    if artifact.meta.get("projection") == "fastfood":
        if artifact.dtype == quantize.INT8_DTYPE:
            scores = backend.fastfood_score_q8_sharded(
                Z, a["ff_b"], a["ff_g"], a["ff_perm"], a["ff_scale"],
                a["ff_stack_scale"], a["phase"],
                a["weights"], a["weights_scale"], a["b"],
                mesh=mesh, config=config,
            )
        else:
            scores = backend.fastfood_score_sharded(
                Z, a["ff_b"], a["ff_g"], a["ff_perm"], a["ff_scale"],
                a["phase"], a["weights"], a["b"], mesh=mesh, config=config,
            )
    elif artifact.dtype == quantize.INT8_DTYPE:
        scores = backend.rff_score_q8_sharded(
            Z, a["W"], a["W_scale"], a["phase"],
            a["weights"], a["weights_scale"], a["b"],
            mesh=mesh, config=config,
        )
    else:
        scores = backend.rff_score_sharded(
            Z, a["W"], a["phase"], a["weights"], a["b"],
            mesh=mesh, config=config,
        )
    valid = jnp.full(
        (scores.shape[0],), bool(artifact.meta.get("valid_globally", True))
    )
    return scores, valid


def tile_lookup(artifact: CompiledArtifact, bucket: int) -> tuple[str, str]:
    q8 = artifact.dtype == quantize.INT8_DTYPE
    if artifact.meta.get("projection") == "fastfood":
        kernel = TILE_KERNEL_FF_Q8 if q8 else TILE_KERNEL_FF
    else:
        kernel = TILE_KERNEL_Q8 if q8 else TILE_KERNEL
    return kernel, tuning.shape_key(
        d=artifact.d, f=int(artifact.meta["num_features"]), n=bucket
    )
