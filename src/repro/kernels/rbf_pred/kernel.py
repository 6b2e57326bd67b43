"""Pallas TPU kernel: exact RBF-expansion prediction for K heads sharing
one SV set, streaming over SVs with DOUBLE-BUFFERED support-vector tiles.

Computes f_k(Z) = sum_i a_ki exp(-gamma ||x_i - z||^2) + b_k without ever
materializing the (n x n_sv) kernel matrix in HBM (flash-attention-style
online accumulation). The pairwise distance is produced by one MXU GEMM
per (z-tile, sv-tile):

    d2 = ||z||^2 + ||x||^2 - 2 Z X^T

and the kernel tile exp(-gamma d2) feeds every head at once through a
second (BN, BM) @ (BM, KB) GEMM — one pass over the SVs serves all heads
of a one-vs-rest model (a vmap over heads would stream them K times).

Schedule: grid = (head_blocks, n_tiles). The SV matrix and its per-SV
rows stay in HBM (``memory_space=ANY``) and are streamed through a 2-slot
VMEM scratch by explicit async copies — while tile j is in the MXU, tile
j+1 is already in flight (the double-buffer pattern from the Pallas
guide), so the SV stream hides its own HBM latency instead of
serializing DMA-then-compute per tile. The per-Z-tile accumulator is a
fori_loop carry; the output block is written once. Heads past
``_MAX_HEAD_BLOCK`` split over the first grid axis.

TPU layout: nothing is 1-D. The per-SV rows — the head block's alpha_y
rows padded to a sublane multiple, then ||x||^2 (precomputed once per
call instead of once per Z tile) — travel as one
(head_blocks, m_tiles, KB + 8, BM) array whose tile is a leading-axis
slice, so each DMA moves a whole block; gamma sits in SMEM.

Precision: the MXU's default f32 contraction is one bf16 pass. With a
small gamma every kernel value sits near 1 and the decision value is a
small difference of such sums, which bf16 erases (mnist, gamma=1e-4, on
a v5e: mean error 4.6x the mean score). So both GEMMs run at HIGHEST,
the f32 the exact path promises.

VMEM working set per step (f32): 2*BN*d (Z tile) + 2*BM*d (X slots) +
2*(KB+8)*BM (SV-row slots) + BN*BM (kernel tile) + 3*BN*KB (accumulator
and output) — with BN=BM=256, d<=2048 and K<=16: ~9 MB. The loaded z and
x tiles and their bf16 halves at HIGHEST about double it: compiled for a
v5e, the kernel needs 7 MiB of scoped VMEM at d_pad 896, 19 MiB at 2,048
and 37 MiB at 4,096. ``_vmem_limit_bytes`` asks for that, never less than
the 16 MiB scoped default.

Block sizes come from ``repro.kernels.common`` (``TileConfig.block_n`` /
``block_m``), resolved per shape bucket by the tuning registry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import TileConfig, tiles, tuning


# Widest head block one grid step accumulates; more heads split over the
# grid (a lane multiple, so the output block stays legal on the tiling).
_MAX_HEAD_BLOCK = 256
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(g_ref, x_hbm, sv_hbm, z_ref, o_ref, x_slots, sv_slots, sem_x, sem_sv,
            *, m_tiles: int, block_m: int, block_h: int):
    z = z_ref[...]                      # (BN, d) resident for this grid step
    gamma = g_ref[0]                    # SMEM scalar — a traced operand
    hb = pl.program_id(0)
    z_sq = jnp.sum(z * z, axis=-1, keepdims=True)          # (BN, 1)

    def copy_x(slot, j):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(j * block_m, block_m)], x_slots.at[slot], sem_x.at[slot]
        )

    def copy_sv(slot, j):
        return pltpu.make_async_copy(
            sv_hbm.at[hb, j], sv_slots.at[slot], sem_sv.at[slot]
        )

    copy_x(0, 0).start()                # warm up: first SV tile in flight
    copy_sv(0, 0).start()

    def body(j, acc):
        slot = j % 2
        nxt = (j + 1) % 2

        @pl.when(j + 1 < m_tiles)
        def _prefetch():                # overlap: next tile DMAs during compute
            copy_x(nxt, j + 1).start()
            copy_sv(nxt, j + 1).start()

        copy_x(slot, j).wait()
        copy_sv(slot, j).wait()
        x = x_slots[slot]               # (BM, d)
        sv = sv_slots[slot]             # (KB + 8, BM): alpha_y rows, ||x||^2
        # MXU GEMMs + VPU epilogue, all in VMEM. Both GEMMs at HIGHEST
        # (see module docstring): this path is the exact one.
        dots = jax.lax.dot_general(
            z, x, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        )                               # (BN, BM)
        d2 = jnp.maximum(z_sq + sv[block_h:block_h + 1, :] - 2.0 * dots, 0.0)
        k = jnp.exp(-gamma * d2)
        return acc + jax.lax.dot_general(
            k, sv[:block_h, :], (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        )                               # (BN, KB)

    o_ref[...] = jax.lax.fori_loop(
        0, m_tiles, body, jnp.zeros(o_ref.shape, jnp.float32)
    )


_DEFAULT_VMEM = 16 << 20


def _vmem_limit_bytes(block_n: int, block_m: int, d_pad: int) -> int:
    """Scoped VMEM for one grid step: about 4.5 f32 copies of the
    (block_n + block_m, d_pad) rows (the double-buffered Z block and X
    slots, the loaded tiles, their bf16 halves) and 2 MiB for the rest."""
    return max(_DEFAULT_VMEM, 18 * (block_n + block_m) * d_pad + (2 << 20))


def rbf_predict_pallas(
    Z: jax.Array,
    X: jax.Array,
    alpha_y: jax.Array,
    gamma: float,
    b,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Padded + tiled pallas_call wrapper. Z: (n, d), X: (m, d); alpha_y:
    (m,) with a scalar b -> (n,), or (K, m) with b scalar or (K,) ->
    (n, K)."""
    config = config or tuning.lookup("rbf_pred")
    n, d = Z.shape
    m = X.shape[0]
    heads = alpha_y.reshape(-1, m).astype(jnp.float32)     # (K, m)
    k = heads.shape[0]
    config = config.clamp_block_n(n)
    block_n, block_m = config.block_n, config.block_m

    # Pad: d to lane multiple (zeros preserve norms/dots), m to block
    # (alpha=0 rows contribute exactly 0), n to block (rows sliced off),
    # heads to the head block (zero alphas, sliced off).
    block_h = tiles.round_up(k, tiles.SUBLANE)
    if block_h > _MAX_HEAD_BLOCK:
        block_h = _MAX_HEAD_BLOCK
    k_pad = tiles.round_up(k, block_h)
    d_pad = tiles.lane_pad(d)
    n_pad = tiles.round_up(n, block_n)
    m_pad = tiles.round_up(m, block_m)
    m_tiles, h_blocks = m_pad // block_m, k_pad // block_h
    Zp = tiles.pad_tail(Z.astype(jnp.float32), n_pad, d_pad)
    Xp = tiles.pad_tail(X.astype(jnp.float32), m_pad, d_pad)
    alphas = tiles.pad_tail(heads, k_pad, m_pad).reshape(h_blocks, block_h, m_pad)
    x_sq = jnp.broadcast_to(
        jnp.sum(Xp * Xp, axis=-1), (h_blocks, tiles.SUBLANE, m_pad)
    )
    sv_rows = jnp.concatenate([alphas, x_sq], axis=1)       # (hb, KB + 8, m_pad)
    sv_rows = sv_rows.reshape(h_blocks, block_h + tiles.SUBLANE, m_tiles, block_m)
    sv_rows = jnp.transpose(sv_rows, (0, 2, 1, 3))          # (hb, m_tiles, KB + 8, BM)

    out = pl.pallas_call(
        functools.partial(
            _kernel, m_tiles=m_tiles, block_m=block_m, block_h=block_h
        ),
        grid=(h_blocks, n_pad // block_n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # gamma
            pl.BlockSpec(memory_space=pl.ANY),              # X stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),              # SV rows stay in HBM
            pl.BlockSpec((block_n, d_pad), lambda h, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_h), lambda h, i: (i, h)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, block_m, d_pad), jnp.float32),   # X double buffer
            pltpu.VMEM((2, block_h + tiles.SUBLANE, block_m), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(block_n, block_m, d_pad)
        ),
        name="rbf_pred",
    )(jnp.reshape(jnp.asarray(gamma, jnp.float32), (1,)), Xp, sv_rows, Zp)
    scores = out[:n, :k] + jnp.reshape(jnp.asarray(b, jnp.float32), (1, -1))
    return scores if alpha_y.ndim == 2 else scores[:, 0]
