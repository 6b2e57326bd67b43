"""Pallas TPU kernel: fused Fastfood (structured RFF) scoring.

For the fourier family's ``structured=True`` artifacts each serving step
is, per stack s of d' = 2^ceil(log2 d) features,

    proj_s = fwht(fwht(z * B_s)[Pi_s] * G_s) * S_s          (VPU butterflies)
    scores = cos(concat_s proj_s + phase) @ weights.T + b   (one thin MXU GEMM)

fused per Z tile so neither the (BN, d') transform intermediates nor the
(BN, F) feature block ever leave VMEM. The Walsh-Hadamard transform is
log2(d') statically-unrolled butterfly stages of adds/subtracts on the
resident tile — exactly the shifts-and-adds workload the VPU exists for;
the only MXU work left is the (BN, F) @ (F, K) readout.

Schedule: grid = (n_tiles,) over Z tiles only, like ``rff_score``. The
diagonal operators are O(F) and stay resident in VMEM across the whole
batch together with phase and the (K, F) readout: per-step working set is
F*(4 + K) + BN*(2 d' + F + K) f32-equivalents — at F = 2048, d' = 1024,
BN = 256, K = 16 that is ~4 MB, far inside a v5e core's VMEM (the dense
``rff_score`` needs F*d more for W; the structured path's whole point is
that it does not).

Algebraic identity: the stage arithmetic is ``ref.fwht``; the XLA
backend formulation computes the same H x through ``ref.fwht_xla``
(Kronecker-factored GEMMs — the faster schedule outside Pallas), and the
parity tests pin both to the explicit Sylvester Hadamard matrix.

Padding contract: Z's feature columns zero-pad to d' (a sign flip of
zero is zero, and H @ [x; 0] columns contribute nothing to the dots);
batch rows pad to a block multiple and are sliced off; heads pad to a
sublane multiple with zero weights/bias and are sliced off. F = stacks*d'
needs no padding by construction. d' < 128 lanes (models with d <= 64)
compiles but underfills the lane tile — small-d models should prefer the
dense path anyway (d^2 is tiny there).

Mosaic lowering: inside the kernel the butterfly stages are lane
rotations (``pltpu.roll``) plus a select on the lane index's bit h
(``_fwht_lanes``) — the reshape/concat schedule of ``ref.fwht`` does not
lower for h < 128. Mosaic has no lane gather across vregs (``jnp.take``
along the lanes is refused with "Shape mismatch in input, indices and
output"), so the permutation runs on the MXU (``_permute_lanes``): a 0/1
one-hot built in VMEM from the int32 index row, 128 output lanes at a
time, against the tile split into three bf16 parts (hi + mid + lo is
the f32 value exactly). Every output element is one nonzero product per
part, so the gather is exact — bit-identical to ``jnp.take``. That costs
3 * 2 * BN * d'^2 MXU flops per stack on top of the O(F log d')
butterflies; the transforms themselves stay O(F log d').

Block sizes come from ``TileConfig.block_n``, resolved per shape bucket
by the tuning registry under the ``fwht`` / ``fwht_q8`` kernel names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import TileConfig, tiles, tuning


def _fwht_lanes(x):
    """``ref.fwht`` over the lanes of a (BN, d') tile, in the same
    floating-point order: at stage h, lanes with bit h clear take
    x[i] + x[i + h] and the others x[i - h] - x[i]."""
    d = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    h = 1
    while h < d:
        up = pltpu.roll(x, d - h, 1)            # up[i] = x[i + h]
        down = pltpu.roll(x, h, 1)              # down[i] = x[i - h]
        x = jnp.where((lane & h) == 0, x + up, down - x)
        h *= 2
    return x


def _permute_lanes(t, p_ref, s):
    """t[:, perm[s]] for a (BN, d') f32 tile; ``p_ref`` holds the
    (stacks, d') int32 index rows (each 128-lane piece is loaded straight
    from VMEM, so it broadcasts down the one-hot's sublanes).

    Exact: t = hi + mid + lo with each part bf16, and every column of the
    0/1 one-hot holds a single 1, so each bf16 GEMM returns its part's
    gathered values unrounded and the f32 sum rebuilds t exactly.
    """
    d = t.shape[-1]
    hi = t.astype(jnp.bfloat16)
    rest = t - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    width = min(d, tiles.LANE)
    rows = jax.lax.broadcasted_iota(jnp.int32, (d, width), 0)
    cols = []
    for c in range(0, d, width):
        perm = p_ref[pl.ds(s, 1), pl.ds(c, width)]                 # (1, W)
        onehot = (rows == perm).astype(jnp.bfloat16)                  # (d', W)

        def gather(part, onehot=onehot):
            return jax.lax.dot_general(
                part, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        cols.append((gather(hi) + gather(mid)) + gather(lo))
    return jnp.concatenate(cols, axis=-1) if len(cols) > 1 else cols[0]


def _transform(z, B, G, p_ref, S):
    """The per-stack structured transform on a resident (BN, d') tile.

    Static Python loop over stacks — each iteration is 2 log2(d')
    butterfly stages + 3 diagonal multiplies on the VPU and one exact
    permutation on the MXU, all on VMEM-resident data. Returns the
    concatenated (BN, F) block in the same stack-major feature order as
    ``ref.fastfood_project``.
    """
    projs = []
    for s in range(B.shape[0]):
        t = _fwht_lanes(z * B[s:s + 1, :])
        t = _permute_lanes(t, p_ref, s)
        t = _fwht_lanes(t * G[s:s + 1, :])
        projs.append(t * S[s:s + 1, :])
    return jnp.concatenate(projs, axis=-1)


def _kernel(z_ref, b_ref, g_ref, p_ref, s_ref, ph_ref, wt_ref, bias_ref, o_ref):
    z = z_ref[...]                           # (BN, d') f32
    proj = _transform(
        z, b_ref[...], g_ref[...], p_ref, s_ref[...]
    )                                        # (BN, F), never leaves VMEM
    phi = jnp.cos(proj + ph_ref[...][None, :])
    scores = jax.lax.dot_general(
        phi, wt_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                        # (BN, K) MXU
    o_ref[...] = scores + bias_ref[...][None, :]


def _kernel_q8(z_ref, b_ref, g_ref, p_ref, s_ref, ss_ref, ph_ref,
               wt_ref, wts_ref, bias_ref, o_ref):
    """Int8-operator variant: B (exact signs), G and S are int8 codes; the
    per-stack product of the G and S row scales folds once onto each
    stack's transform output (both diagonals multiply the same columns),
    and the readout's per-head scales fold post-GEMM — same epilogue
    shape as ``rff_score_q8``."""
    z = z_ref[...]                           # (BN, d') f32
    B = b_ref[...].astype(jnp.float32)       # +-1, lossless upcast
    G = g_ref[...].astype(jnp.float32)
    ss = ss_ref[...]                         # (stacks,) combined G*S scales
    S = s_ref[...].astype(jnp.float32) * ss[:, None]
    proj = _transform(z, B, G, p_ref, S)
    phi = jnp.cos(proj + ph_ref[...][None, :])
    scores = jax.lax.dot_general(
        phi, wt_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * wts_ref[...][None, :]                # fold head scales post-GEMM
    o_ref[...] = scores + bias_ref[...][None, :]


def fastfood_score_pallas(
    Z: jax.Array,
    B: jax.Array,
    G: jax.Array,
    perm: jax.Array,
    scale: jax.Array,
    phase: jax.Array,
    weights: jax.Array,
    bias: jax.Array,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused Fastfood scores. Z: (n, d); B/G/scale: (stacks, d') f32
    diagonals; perm: (stacks, d') int; phase: (F,); weights: (K, F) with
    the 2/F scaling folded at compile time; bias: (K,). Returns (n, K) —
    the same contract as ``rff_score_pallas`` without ever materializing
    the implicit (F, d) projection matrix."""
    config = config or tuning.lookup("fwht")
    n, d = Z.shape
    stacks, dd = B.shape
    f, k = stacks * dd, weights.shape[0]
    config = config.clamp_block_n(n)
    block_n = config.block_n

    k_pad = max(tiles.SUBLANE, tiles.round_up(k, tiles.SUBLANE))
    n_pad = tiles.round_up(n, block_n)

    Zp = tiles.pad_tail(Z.astype(jnp.float32), n_pad, dd)
    wtp = tiles.pad_axis(weights.astype(jnp.float32), 0, k_pad)
    bp = tiles.pad_axis(bias.astype(jnp.float32), 0, k_pad)

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, dd), lambda i: (i, 0)),
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((f,), lambda i: (0,)),
            pl.BlockSpec((k_pad, f), lambda i: (0, 0)),       # resident
            pl.BlockSpec((k_pad,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, k_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        interpret=interpret,
        name="fwht",
    )(
        Zp, B.astype(jnp.float32), G.astype(jnp.float32),
        perm.astype(jnp.int32), scale.astype(jnp.float32),
        phase.astype(jnp.float32), wtp, bp,
    )
    return out[:n, :k]


def fastfood_score_q8_pallas(
    Z: jax.Array,
    b_q: jax.Array,
    g_q: jax.Array,
    perm: jax.Array,
    s_q: jax.Array,
    stack_scale: jax.Array,
    phase: jax.Array,
    weights_q: jax.Array,
    wt_scale: jax.Array,
    bias: jax.Array,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused Fastfood scores off int8 operators. b_q/g_q/s_q: (stacks, d')
    int8 (b_q is exact +-1 signs); stack_scale: (stacks,) f32 combined
    G*S row scales; weights_q: (K, F) int8 with per-head wt_scale (K,);
    phase and bias f32. Same contract as ``fastfood_score_pallas``.

    Padding keeps the f32 contract: padded heads carry zero codes, zero
    scales and zero bias, and are sliced off."""
    config = config or tuning.lookup("fwht_q8")
    n, d = Z.shape
    stacks, dd = b_q.shape
    f, k = stacks * dd, weights_q.shape[0]
    config = config.clamp_block_n(n)
    block_n = config.block_n

    k_pad = max(tiles.SUBLANE, tiles.round_up(k, tiles.SUBLANE))
    n_pad = tiles.round_up(n, block_n)

    Zp = tiles.pad_tail(Z.astype(jnp.float32), n_pad, dd)
    wtp = tiles.pad_axis(weights_q.astype(jnp.int8), 0, k_pad)
    wtsp = tiles.pad_axis(wt_scale.astype(jnp.float32), 0, k_pad)
    bp = tiles.pad_axis(bias.astype(jnp.float32), 0, k_pad)

    out = pl.pallas_call(
        _kernel_q8,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, dd), lambda i: (i, 0)),
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks, dd), lambda i: (0, 0)),     # resident
            pl.BlockSpec((stacks,), lambda i: (0,)),
            pl.BlockSpec((f,), lambda i: (0,)),
            pl.BlockSpec((k_pad, f), lambda i: (0, 0)),       # resident
            pl.BlockSpec((k_pad,), lambda i: (0,)),
            pl.BlockSpec((k_pad,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_n, k_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        interpret=interpret,
        name="fwht_q8",
    )(
        Zp, b_q.astype(jnp.int8), g_q.astype(jnp.int8),
        perm.astype(jnp.int32), s_q.astype(jnp.int8),
        stack_scale.astype(jnp.float32), phase.astype(jnp.float32),
        wtp, wtsp, bp,
    )
    return out[:n, :k]
