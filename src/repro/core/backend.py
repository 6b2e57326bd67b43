"""Backend dispatch for the SVM prediction hot path.

One process-level decision, made here and nowhere else, of HOW the
serving primitives are evaluated:

  * the collapsed quadratic form (Eq 3.8), fused over K heads — the fast
    path of ``approx_decision_function*``, ``approx_ovr_predict`` and the
    maclaurin/poly2 artifact families;
  * fused random-Fourier-feature scoring (projection + cos + weight dot
    per Z tile) — the fourier family's fast path;
  * the exact RBF expansion (Eq 3.2) — the engine's accuracy fallback and
    every Table-1/2 oracle.

The FAMILY axis sits one level up: ``family_scores`` dispatches a
``CompiledArtifact`` (see ``repro.core.families``) to whichever primitive
its family serves through, so the engine and benchmarks never switch on
family names themselves.

Backends:

  * ``"pallas"`` — the kernels in ``repro.kernels.{quadform,rbf_pred}``:
    Hessians resident in VMEM, one MXU contraction scoring all K heads per
    Z tile, streaming SV tiles for the exact path.  Compiled natively on
    TPU; interpret mode elsewhere (correct but slow — tests only).
  * ``"xla"``   — algebraically identical single-GEMM jnp formulations
    that XLA fuses well on CPU/GPU: the (d, K*d) stacked-Hessian operand
    makes the K-head quadratic term ONE dot_general regardless of K.

Resolution order: ``set_backend(...)`` > ``$REPRO_SVM_BACKEND`` > auto
(pallas iff the default jax backend is TPU).  The choice is read at trace
time: functions already jit-compiled keep the backend they were traced
with — set it before first use (process start / test setup).

Tile sizes travel as a ``TileConfig`` from ``repro.kernels.common``:
callers that know their shape bucket (the serving engine) pass a resolved
config; ``config=None`` resolves the measured-or-default entry for the
operand shapes from the tuning registry right here, so every dispatch —
not just the engine's — benefits from the checked-in tuning table.

All scalars (c, b, gamma, ...) are traced values, so everything here
composes with outer jits over model pytrees.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.common import TileConfig, tuning
from repro.kernels.fwht.kernel import (
    fastfood_score_pallas,
    fastfood_score_q8_pallas,
)
from repro.kernels.fwht.ref import fastfood_score_q8_ref, fastfood_score_ref
from repro.kernels.quadform.kernel import (
    quadform_heads_pallas,
    quadform_heads_q8_pallas,
)
from repro.kernels.quadform.ref import eq311_valid
from repro.kernels.rbf_pred.kernel import rbf_predict_pallas
from repro.kernels.rff_score.kernel import rff_score_pallas, rff_score_q8_pallas

Array = jax.Array

_ENV_VAR = "REPRO_SVM_BACKEND"
_VALID = ("auto", "pallas", "xla")
_forced: str | None = None


def set_backend(name: str | None) -> str | None:
    """Force the backend for this process ("pallas" / "xla" / "auto" / None).

    Returns the previous forced value so tests can restore it.
    """
    global _forced
    if name is not None and name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    prev = _forced
    _forced = None if name in (None, "auto") else name
    return prev


def resolve() -> str:
    """The backend the next trace will use: "pallas" or "xla"."""
    choice = _forced or os.environ.get(_ENV_VAR, "auto")
    if choice not in _VALID:
        raise ValueError(f"${_ENV_VAR} must be one of {_VALID}, got {choice!r}")
    if choice == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return choice


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------- quadform


def quadform_heads_xla(Z, M_all, V, c, b, gamma, msq):
    """Fused K-head quadratic form as ONE XLA GEMM (not K).

    Identical math to the Pallas kernel: the K Hessians are laid out as a
    single (d, K*d) operand so the quadratic term of every head comes out
    of one dot_general, followed by a (n, K) row-dot, the thin Z @ V^T
    GEMM and the exp/bias/validity epilogue.
    """
    n, d = Z.shape
    k = M_all.shape[0]
    z_sq = jnp.sum(Z * Z, axis=-1)                          # (n,)
    m_kd = jnp.transpose(M_all, (1, 0, 2)).reshape(d, k * d)
    zm = (Z @ m_kd).reshape(n, k, d)                        # ONE GEMM, all heads
    quad = jnp.einsum("nkd,nd->nk", zm, Z)
    lin = Z @ V.T                                           # (n, K)
    env = jnp.exp(-z_sq[:, None] * gamma[None, :])
    scores = env * (c[None, :] + lin + quad) + b[None, :]
    return scores, z_sq, eq311_valid(z_sq, gamma, msq)


def quadform_heads(Z, M_all, V, c, b, gamma, msq, *, config: TileConfig | None = None):
    """Dispatching fused K-head scores.

    Z: (n, d); M_all: (K, d, d); V: (K, d); c/b/gamma/msq: (K,).
    Returns (scores (n, K), z_sq (n,), valid (n, K)) where valid is the
    per-head Eq 3.11 mask. ``config=None`` resolves the tuned (or default)
    ``TileConfig`` for this (d, K, n) bucket from the tuning registry.
    """
    if config is None:
        config = tuning.lookup(
            "quadform",
            tuning.shape_key(
                d=Z.shape[1], k=M_all.shape[0], n=tuning.bucket(Z.shape[0])
            ),
        )
    if resolve() == "pallas":
        return quadform_heads_pallas(
            Z, M_all, V, c, b, gamma, msq,
            config=config, interpret=_interpret(),
        )
    return quadform_heads_xla(Z, M_all, V, c, b, gamma, msq)


def quadform_heads_q8_xla(Z, M_q, col_scale, V, c, b, gamma, msq):
    """Int8-Hessian K-head quadratic form as one int8->f32 GEMM under XLA.

    The stacked int8 operand is upcast INSIDE the contraction (XLA fuses
    the convert into the GEMM loop on CPU — the weights stay int8 in
    memory); the per-(head, column) scales fold onto the (n, K, d) GEMM
    result with one broadcast multiply before the row-dot, exactly the
    math the Pallas tile performs in VMEM.
    """
    n, d = Z.shape
    k = M_q.shape[0]
    z_sq = jnp.sum(Z * Z, axis=-1)                          # (n,)
    m_kd = jnp.transpose(M_q, (1, 0, 2)).reshape(d, k * d)
    zm = (Z @ m_kd.astype(jnp.float32)).reshape(n, k, d)    # ONE GEMM, all heads
    zm = zm * col_scale[None, :, :]                         # fold dequant scales
    quad = jnp.einsum("nkd,nd->nk", zm, Z)
    lin = Z @ V.T                                           # (n, K)
    env = jnp.exp(-z_sq[:, None] * gamma[None, :])
    scores = env * (c[None, :] + lin + quad) + b[None, :]
    return scores, z_sq, eq311_valid(z_sq, gamma, msq)


def quadform_heads_q8(
    Z, M_q, col_scale, V, c, b, gamma, msq, *, config: TileConfig | None = None
):
    """Dispatching fused K-head scores off an int8-quantized Hessian.

    Z: (n, d); M_q: (K, d, d) int8; col_scale: (K, d) f32 per-column
    dequant scales; V: (K, d) f32 (already dequantized — it is thin);
    c/b/gamma/msq: (K,). Same return contract as ``quadform_heads``.
    ``config=None`` resolves the ``quadform_q8`` tuning family for this
    (d, K, n) bucket.
    """
    if config is None:
        config = tuning.lookup(
            "quadform_q8",
            tuning.shape_key(
                d=Z.shape[1], k=M_q.shape[0], n=tuning.bucket(Z.shape[0])
            ),
        )
    if resolve() == "pallas":
        return quadform_heads_q8_pallas(
            Z, M_q, col_scale, V, c, b, gamma, msq,
            config=config, interpret=_interpret(),
        )
    return quadform_heads_q8_xla(Z, M_q, col_scale, V, c, b, gamma, msq)


# Every head-sharded scorer below is a shard_map with check_vma=False: the
# per-shard primitive may be a pallas_call, whose out_shape carries no
# varying-across-mesh-axes type, and check_vma=True refuses it.


def quadform_heads_sharded(
    Z, M_all, V, c, b, gamma, msq, *, mesh, config: TileConfig | None = None
):
    """``quadform_heads`` with the K heads sharded over a device mesh.

    The stacked Hessian (K, d, d) — the operand that busts one device's
    memory in the extreme-multiclass regime — and every other per-head
    array are partitioned over ``mesh``'s first axis; Z is replicated.
    Each device runs the SAME fused per-shard primitive the single-
    device path uses (one GEMM for its K/shards heads), so tuning and
    backend choice apply per shard. Outputs stay head-sharded
    (``P(None, axis)``): a consumer reducing over heads (the engine's
    argmax) lets XLA insert the one cross-shard reduce at the end
    instead of gathering (n, K) scores to every device.

    K must divide evenly by the axis size — pad validity-neutral heads
    first (``families.*.pad_heads``). Returns (scores (n, K),
    valid (n, K)); ``z_sq`` is a per-shard by-product and is not
    returned (the per-head validity mask already encodes it).
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    shards = mesh.shape[axis]
    k = M_all.shape[0]
    if k % shards:
        raise ValueError(
            f"num_heads ({k}) must divide by mesh axis {axis!r} ({shards}); "
            f"pad validity-neutral heads first"
        )

    def _local(Zb, Ms, Vs, cs, bs, gs, ms):
        scores, _, valid = quadform_heads(
            Zb, Ms, Vs, cs, bs, gs, ms, config=config
        )
        return scores, valid

    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None),
                  P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(None, axis), P(None, axis)),
        check_vma=False,
    )
    return fn(Z, M_all, V, c, b, gamma, msq)


def quadform_heads_q8_sharded(
    Z, M_q, col_scale, V, c, b, gamma, msq, *, mesh,
    config: TileConfig | None = None,
):
    """``quadform_heads_q8`` with the K heads sharded over a device mesh.

    Same partitioning as the f32 path — the int8 stacked Hessian AND its
    per-(head, column) dequant scales carry the head axis, so both shard
    together and the scale fold happens inside each device's fused
    per-shard primitive (the scale epilogue never crosses the wire).
    Int8 sharding is where head sharding pays most: the same mesh holds a
    4x bigger K before the Hessian busts per-device memory.

    K must divide the axis size (pad validity-neutral heads first).
    Returns head-sharded (scores (n, K), valid (n, K)).
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    shards = mesh.shape[axis]
    k = M_q.shape[0]
    if k % shards:
        raise ValueError(
            f"num_heads ({k}) must divide by mesh axis {axis!r} ({shards}); "
            f"pad validity-neutral heads first"
        )

    def _local(Zb, Ms, cols, Vs, cs, bs, gs, ms):
        scores, _, valid = quadform_heads_q8(
            Zb, Ms, cols, Vs, cs, bs, gs, ms, config=config
        )
        return scores, valid

    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(axis, None, None), P(axis, None), P(axis, None),
                  P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(None, axis), P(None, axis)),
        check_vma=False,
    )
    return fn(Z, M_q, col_scale, V, c, b, gamma, msq)


# ------------------------------------------------------------ rff scoring


def rff_score_xla(Z, W, phase, weights, bias):
    """RFF scoring as two GEMMs with the cos epilogue between them.

    Identical math to the Pallas kernel; XLA materializes the (n, F)
    feature block between the projection and the weight contraction,
    which is fine on CPU/GPU where there is no small fast memory to keep
    it resident in.
    """
    phi = jnp.cos(Z @ W.T + phase[None, :])
    return phi @ weights.T + bias[None, :]


def rff_score(Z, W, phase, weights, bias, *, config: TileConfig | None = None):
    """Dispatching fused random-Fourier-feature scores.

    Z: (n, d); W: (F, d); phase: (F,); weights: (K, F) with the 2/F
    feature scaling folded in at compile time; bias: (K,). Returns
    per-head scores (n, K). ``config=None`` resolves the tuned (or
    default) ``TileConfig`` for this (d, F, n) bucket.
    """
    if config is None:
        config = tuning.lookup(
            "rff_score",
            tuning.shape_key(
                d=Z.shape[1], f=W.shape[0], n=tuning.bucket(Z.shape[0])
            ),
        )
    if resolve() == "pallas":
        return rff_score_pallas(
            Z, W, phase, weights, bias, config=config, interpret=_interpret()
        )
    return rff_score_xla(Z, W, phase, weights, bias)


def rff_score_sharded(
    Z, W, phase, weights, bias, *, mesh, config: TileConfig | None = None
):
    """``rff_score`` with the (K, F) readout sharded over a device mesh.

    The projection (W, phase) is per-row work and stays replicated —
    each device computes the (n, F) feature block for its shard of
    heads; only the readout weights and bias partition over ``mesh``'s
    first axis. That trades F·n duplicate flops per device for zero
    cross-device traffic before the final head reduce, the right trade
    whenever K·F (the readout) dominates F·d (the projection), i.e.
    exactly the extreme-multiclass regime head sharding exists for.

    K must divide evenly by the axis size (pad heads first). Returns
    head-sharded scores (n, K), spec ``P(None, axis)``.
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    shards = mesh.shape[axis]
    k = weights.shape[0]
    if k % shards:
        raise ValueError(
            f"num_heads ({k}) must divide by mesh axis {axis!r} ({shards}); "
            f"pad validity-neutral heads first"
        )

    def _local(Zb, Wf, ph, ws, bs):
        return rff_score(Zb, Wf, ph, ws, bs, config=config)

    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(axis, None), P(axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )
    return fn(Z, W, phase, weights, bias)


def rff_score_q8_xla(Z, W_q, w_scale, phase, weights_q, wt_scale, bias):
    """Int8-weights RFF scoring as two int8->f32 GEMMs under XLA; both
    quantized axes are GEMM output axes, so each scale is one broadcast
    multiply on the small result."""
    proj = (Z @ W_q.astype(jnp.float32).T) * w_scale[None, :]
    phi = jnp.cos(proj + phase[None, :])
    return (phi @ weights_q.astype(jnp.float32).T) * wt_scale[None, :] \
        + bias[None, :]


def rff_score_q8(
    Z, W_q, w_scale, phase, weights_q, wt_scale, bias,
    *, config: TileConfig | None = None,
):
    """Dispatching fused RFF scores off int8 projection + readout weights.

    Z: (n, d); W_q: (F, d) int8 with per-row scales w_scale (F,);
    weights_q: (K, F) int8 with per-head scales wt_scale (K,); phase (F,)
    and bias (K,) stay f32. Returns (n, K). ``config=None`` resolves the
    ``rff_score_q8`` tuning family for this (d, F, n) bucket.
    """
    if config is None:
        config = tuning.lookup(
            "rff_score_q8",
            tuning.shape_key(
                d=Z.shape[1], f=W_q.shape[0], n=tuning.bucket(Z.shape[0])
            ),
        )
    if resolve() == "pallas":
        return rff_score_q8_pallas(
            Z, W_q, w_scale, phase, weights_q, wt_scale, bias,
            config=config, interpret=_interpret(),
        )
    return rff_score_q8_xla(Z, W_q, w_scale, phase, weights_q, wt_scale, bias)


def rff_score_q8_sharded(
    Z, W_q, w_scale, phase, weights_q, wt_scale, bias,
    *, mesh, config: TileConfig | None = None,
):
    """``rff_score_q8`` with the int8 (K, F) readout sharded over a mesh.

    Partitioning mirrors ``rff_score_sharded``: the projection operands
    (W_q, w_scale, phase) replicate — per-row work — while the readout
    codes, their per-head scales and the bias shard over ``mesh``'s first
    axis, so the dequant scale-epilogue folds inside each shard's fused
    primitive. K must divide the axis size (pad heads first). Returns
    head-sharded scores (n, K), spec ``P(None, axis)``.
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    shards = mesh.shape[axis]
    k = weights_q.shape[0]
    if k % shards:
        raise ValueError(
            f"num_heads ({k}) must divide by mesh axis {axis!r} ({shards}); "
            f"pad validity-neutral heads first"
        )

    def _local(Zb, Wf, ws, ph, wq, wts, bs):
        return rff_score_q8(Zb, Wf, ws, ph, wq, wts, bs, config=config)

    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(axis, None), P(axis), P(axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )
    return fn(Z, W_q, w_scale, phase, weights_q, wt_scale, bias)


# ------------------------------------------------------- fastfood scoring


def fastfood_score_xla(Z, B, G, perm, scale, phase, weights, bias):
    """Structured (Fastfood) RFF scoring under XLA: the log-depth
    butterfly stages as reshape/concat ops, then one thin readout GEMM.
    Algebraically identical to the Pallas kernel (same ``fwht`` body)."""
    return fastfood_score_ref(Z, B, G, perm, scale, phase, weights, bias)


def fastfood_score(
    Z, B, G, perm, scale, phase, weights, bias,
    *, config: TileConfig | None = None,
):
    """Dispatching fused Fastfood scores.

    Z: (n, d); B/G/scale: (stacks, d') diagonal operators; perm:
    (stacks, d') int; phase: (F,) with F = stacks*d'; weights: (K, F)
    with the 2/F scaling folded at compile time; bias: (K,). Returns
    (n, K). ``config=None`` resolves the ``fwht`` tuning family for this
    (d, F, n) bucket.
    """
    if config is None:
        config = tuning.lookup(
            "fwht",
            tuning.shape_key(
                d=Z.shape[1], f=B.shape[0] * B.shape[1],
                n=tuning.bucket(Z.shape[0]),
            ),
        )
    if resolve() == "pallas":
        return fastfood_score_pallas(
            Z, B, G, perm, scale, phase, weights, bias,
            config=config, interpret=_interpret(),
        )
    return fastfood_score_xla(Z, B, G, perm, scale, phase, weights, bias)


def fastfood_score_q8_xla(
    Z, b_q, g_q, perm, s_q, stack_scale, phase, weights_q, wt_scale, bias
):
    """Int8-operator Fastfood scoring under XLA: diagonals upcast in
    registers (B is exact +-1 signs), the per-stack combined G*S scale
    folds once per stack on the transform output, and the readout is an
    int8->f32 GEMM with the per-head scale fold — the same epilogue
    placement as the Pallas tile."""
    return fastfood_score_q8_ref(
        Z, b_q, g_q, perm, s_q, stack_scale, phase, weights_q, wt_scale, bias
    )


def fastfood_score_q8(
    Z, b_q, g_q, perm, s_q, stack_scale, phase, weights_q, wt_scale, bias,
    *, config: TileConfig | None = None,
):
    """Dispatching fused Fastfood scores off int8 operators.

    b_q/g_q/s_q: (stacks, d') int8 (b_q holds exact +-1 signs);
    stack_scale: (stacks,) f32 combined G*S row scales; weights_q: (K, F)
    int8 with per-head scales wt_scale (K,); phase (F,) and bias (K,)
    f32 (phase may arrive f16 — it is upcast at trace time). Returns
    (n, K). ``config=None`` resolves the ``fwht_q8`` tuning family.
    """
    if config is None:
        config = tuning.lookup(
            "fwht_q8",
            tuning.shape_key(
                d=Z.shape[1], f=b_q.shape[0] * b_q.shape[1],
                n=tuning.bucket(Z.shape[0]),
            ),
        )
    if resolve() == "pallas":
        return fastfood_score_q8_pallas(
            Z, b_q, g_q, perm, s_q, stack_scale, phase,
            weights_q, wt_scale, bias,
            config=config, interpret=_interpret(),
        )
    return fastfood_score_q8_xla(
        Z, b_q, g_q, perm, s_q, stack_scale, phase, weights_q, wt_scale, bias
    )


def fastfood_score_sharded(
    Z, B, G, perm, scale, phase, weights, bias,
    *, mesh, config: TileConfig | None = None,
):
    """``fastfood_score`` with the (K, F) readout sharded over a mesh.

    The replication trade that makes dense-RFF head sharding worthwhile
    (``rff_score_sharded``) is STRICTLY BETTER here: the replicated
    per-shard work is the O(F log d') structured transform instead of an
    O(F d) GEMM, while the sharded operand — the (K, F) readout, the
    only O(K) memory in the artifact — is the same. K must divide the
    axis size (pad heads first). Returns head-sharded scores (n, K).
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    shards = mesh.shape[axis]
    k = weights.shape[0]
    if k % shards:
        raise ValueError(
            f"num_heads ({k}) must divide by mesh axis {axis!r} ({shards}); "
            f"pad validity-neutral heads first"
        )

    def _local(Zb, Bs, Gs, ps, ss, ph, ws, bs):
        return fastfood_score(Zb, Bs, Gs, ps, ss, ph, ws, bs, config=config)

    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(axis, None), P(axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )
    return fn(Z, B, G, perm, scale, phase, weights, bias)


def fastfood_score_q8_sharded(
    Z, b_q, g_q, perm, s_q, stack_scale, phase, weights_q, wt_scale, bias,
    *, mesh, config: TileConfig | None = None,
):
    """``fastfood_score_q8`` with the int8 readout sharded over a mesh.

    The O(F) int8 diagonals and phase replicate; the int8 (K, F) readout
    codes, their per-head scales and the bias partition over ``mesh``'s
    first axis — the scale-epilogue folds per shard, exactly like
    ``rff_score_q8_sharded``. K must divide the axis size (pad heads
    first). Returns head-sharded scores (n, K).
    """
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    shards = mesh.shape[axis]
    k = weights_q.shape[0]
    if k % shards:
        raise ValueError(
            f"num_heads ({k}) must divide by mesh axis {axis!r} ({shards}); "
            f"pad validity-neutral heads first"
        )

    def _local(Zb, bq, gq, ps, sq, ssc, ph, wq, wts, bs):
        return fastfood_score_q8(
            Zb, bq, gq, ps, sq, ssc, ph, wq, wts, bs, config=config
        )

    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(),
                  P(axis, None), P(axis), P(axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )
    return fn(
        Z, b_q, g_q, perm, s_q, stack_scale, phase, weights_q, wt_scale, bias
    )


# ------------------------------------------------------------- family axis


def family_scores(artifact, Z, *, config: TileConfig | None = None):
    """Score a ``CompiledArtifact`` through its family's serving primitive.

    Returns ``(scores (n, K), valid_rows (n,))`` — the family decides what
    "valid" means (per-row Eq 3.11 envelope for the quadform families, the
    compile-time held-out error verdict broadcast over rows for fourier).
    Thin front door over ``families.score_artifact`` (ONE implementation
    of the dispatch); the import is deferred because families call back
    into this module's primitives.
    """
    from repro.core import families

    return families.score_artifact(artifact, Z, config=config)


# -------------------------------------------------------------- exact RBF


def rbf_scores_xla(Z, X, alpha_y, gamma, b):
    """Exact expansion via the GEMM distance trick (what XLA fuses well),
    in f32 at HIGHEST like the ``rbf_pred`` kernel."""
    hi = jax.lax.Precision.HIGHEST
    sq_z = jnp.sum(Z * Z, axis=-1)[:, None]
    sq_x = jnp.sum(X * X, axis=-1)[None, :]
    d2 = jnp.maximum(sq_z + sq_x - 2.0 * jnp.dot(Z, X.T, precision=hi), 0.0)
    kmat = jnp.exp(-gamma * d2)
    if alpha_y.ndim == 2:                                   # (K, m) heads
        return jnp.dot(kmat, alpha_y.T, precision=hi) + jnp.reshape(b, (1, -1))
    return jnp.dot(kmat, alpha_y, precision=hi) + b


def rbf_scores(Z, X, alpha_y, gamma, b, *, config: TileConfig | None = None):
    """Dispatching exact decision values f(Z) = sum_i a_i K(x_i, z) + b.

    ``alpha_y`` (m,) with a scalar ``b`` gives (n,); (K, m) heads sharing
    the SVs, with ``b`` scalar or (K,), give (n, K) from ONE pass over the
    SVs. The Pallas path streams double-buffered SV tiles
    flash-attention-style (never materializes the (n, n_sv) kernel matrix
    in HBM). ``config=None`` resolves the tuned (or default)
    ``TileConfig`` for this (d, m, n) bucket from the tuning registry.
    """
    if config is None:
        config = tuning.lookup(
            "rbf_pred",
            tuning.shape_key(d=Z.shape[1], m=X.shape[0], n=tuning.bucket(Z.shape[0])),
        )
    if resolve() == "pallas":
        return rbf_predict_pallas(
            Z, X, alpha_y, gamma, b,
            config=config, interpret=_interpret(),
        )
    return rbf_scores_xla(Z, X, alpha_y, gamma, b)
