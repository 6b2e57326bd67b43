"""Pallas TPU kernel: fused random-Fourier-feature scoring.

For the fourier approximation family (random Fourier features of the
Gaussian kernel), each serving step is

    scores[z, k] = sum_f weights[k, f] * cos(W[f, :] . z + phase[f]) + b[k]

i.e. one (BN, d) @ (d, F) MXU projection, a VPU cos, and one thin
(BN, F) @ (F, K) contraction against the per-head weights — fused per Z
tile so the (BN, F) feature block never leaves VMEM (the XLA formulation
materializes phi in HBM between the two GEMMs; see
``repro.core.backend.rff_score_xla``).

Schedule: grid = (n_tiles,) over Z tiles only. W, phase and weights are
resident in VMEM across the whole batch (one HBM read each): per-step
working set is F*(d + K + 1) + BN*(d + F + K) f32 — at F = 2048, d <= 896,
BN = 256, K <= 16 that is ~10 MB, inside a v5e core's VMEM. Models whose
F*d alone busts VMEM should lower ``TileConfig.block_n`` or serve the
XLA path; a feature-axis grid (accumulating over F blocks) is the
designated follow-up if such artifacts show up.

Padding contract (what makes the fused path exact): padded feature rows
have ZERO weight columns, so their cos(0 + 0) = 1 contribution is
multiplied away; padded d columns are zero in both Z and W (dots exact);
padded batch rows are sliced off; padded heads carry zero weights/bias
and are sliced off.

Block sizes come from ``repro.kernels.common`` (``TileConfig.block_n``),
resolved per shape bucket by the tuning registry under the ``rff_score``
kernel name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import TileConfig, tiles, tuning


def _kernel(z_ref, w_ref, p_ref, wt_ref, b_ref, o_ref):
    z = z_ref[...]                           # (BN, d)
    w = w_ref[...]                           # (F, d) resident
    phase = p_ref[...]                       # (F,)
    wt = wt_ref[...]                         # (K, F) resident
    bias = b_ref[...]                        # (K,)
    proj = jax.lax.dot_general(
        z, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                        # (BN, F) MXU
    phi = jnp.cos(proj + phase[None, :])     # VPU, never leaves VMEM
    scores = jax.lax.dot_general(
        phi, wt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                        # (BN, K) MXU
    o_ref[...] = scores + bias[None, :]


def _kernel_q8(z_ref, w_ref, ws_ref, p_ref, wt_ref, wts_ref, b_ref, o_ref):
    """Int8-weights variant: the projection matrix and the per-head
    readout are int8; both quantized axes are OUTPUT axes of their GEMMs
    (feature rows for W, heads for the readout), so dequantization folds
    onto the small results — two VPU multiplies, no f32 weight copy."""
    z = z_ref[...]                           # (BN, d) f32
    w = w_ref[...]                           # (F, d) int8, resident
    w_scale = ws_ref[...]                    # (F,) per-feature-row scales
    phase = p_ref[...]                       # (F,)
    wt = wt_ref[...]                         # (K, F) int8, resident
    wt_scale = wts_ref[...]                  # (K,) per-head scales
    bias = b_ref[...]                        # (K,)
    proj = jax.lax.dot_general(
        z, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * w_scale[None, :]                     # fold row scales post-GEMM
    phi = jnp.cos(proj + phase[None, :])     # VPU, never leaves VMEM
    scores = jax.lax.dot_general(
        phi, wt.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * wt_scale[None, :]                    # fold head scales post-GEMM
    o_ref[...] = scores + bias[None, :]


def rff_score_q8_pallas(
    Z: jax.Array,
    W_q: jax.Array,
    w_scale: jax.Array,
    phase: jax.Array,
    weights_q: jax.Array,
    wt_scale: jax.Array,
    bias: jax.Array,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused RFF scores off int8 weights. Z: (n, d), W_q: (F, d) int8 with
    w_scale (F,), weights_q: (K, F) int8 with wt_scale (K,), phase (F,)
    and bias (K,) f32. Returns (n, K) — same contract as
    ``rff_score_pallas`` at a quarter of the resident-weight footprint.

    Padding keeps the f32 contract: padded feature rows are zero codes
    with zero weight columns (their cos(0)=1 is multiplied away); padded
    scales are zero, which only ever multiplies padded output."""
    config = config or tuning.lookup("rff_score_q8")
    n, d = Z.shape
    f, k = W_q.shape[0], weights_q.shape[0]
    config = config.clamp_block_n(n)
    block_n = config.block_n

    d_pad = tiles.lane_pad(d)
    f_pad = tiles.lane_pad(f)
    k_pad = max(tiles.SUBLANE, tiles.round_up(k, tiles.SUBLANE))
    n_pad = tiles.round_up(n, block_n)

    Zp = tiles.pad_tail(Z.astype(jnp.float32), n_pad, d_pad)
    Wp = tiles.pad_tail(W_q.astype(jnp.int8), f_pad, d_pad)
    wsp = tiles.pad_axis(w_scale.astype(jnp.float32), 0, f_pad)
    pp = tiles.pad_axis(phase.astype(jnp.float32), 0, f_pad)
    wtp = tiles.pad_tail(weights_q.astype(jnp.int8), k_pad, f_pad)
    wtsp = tiles.pad_axis(wt_scale.astype(jnp.float32), 0, k_pad)
    bp = tiles.pad_axis(bias.astype(jnp.float32), 0, k_pad)

    out = pl.pallas_call(
        _kernel_q8,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((f_pad, d_pad), lambda i: (0, 0)),   # resident
            pl.BlockSpec((f_pad,), lambda i: (0,)),
            pl.BlockSpec((f_pad,), lambda i: (0,)),
            pl.BlockSpec((k_pad, f_pad), lambda i: (0, 0)),   # resident
            pl.BlockSpec((k_pad,), lambda i: (0,)),
            pl.BlockSpec((k_pad,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, k_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        interpret=interpret,
        name="rff_score_q8",
    )(Zp, Wp, wsp, pp, wtp, wtsp, bp)
    return out[:n, :k]


def rff_score_pallas(
    Z: jax.Array,
    W: jax.Array,
    phase: jax.Array,
    weights: jax.Array,
    bias: jax.Array,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused RFF scores. Z: (n, d), W: (F, d), phase: (F,), weights: (K, F),
    bias: (K,). Returns (n, K) per-head scores."""
    config = config or tuning.lookup("rff_score")
    n, d = Z.shape
    f, k = W.shape[0], weights.shape[0]
    config = config.clamp_block_n(n)
    block_n = config.block_n

    d_pad = tiles.lane_pad(d)
    f_pad = tiles.lane_pad(f)
    k_pad = max(tiles.SUBLANE, tiles.round_up(k, tiles.SUBLANE))
    n_pad = tiles.round_up(n, block_n)

    Zp = tiles.pad_tail(Z.astype(jnp.float32), n_pad, d_pad)
    Wp = tiles.pad_tail(W.astype(jnp.float32), f_pad, d_pad)
    pp = tiles.pad_axis(phase.astype(jnp.float32), 0, f_pad)
    wtp = tiles.pad_tail(weights.astype(jnp.float32), k_pad, f_pad)
    bp = tiles.pad_axis(bias.astype(jnp.float32), 0, k_pad)

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((f_pad, d_pad), lambda i: (0, 0)),   # resident
            pl.BlockSpec((f_pad,), lambda i: (0,)),
            pl.BlockSpec((k_pad, f_pad), lambda i: (0, 0)),   # resident
            pl.BlockSpec((k_pad,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, k_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        interpret=interpret,
        name="rff_score",
    )(Zp, Wp, pp, wtp, bp)
    return out[:n, :k]
