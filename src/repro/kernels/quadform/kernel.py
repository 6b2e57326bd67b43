"""Pallas TPU kernel: fused multi-head quadratic-form prediction (Eq 3.8).

For K collapsed heads (c_k, v_k, M_k) sharing one input batch Z,

    f_k(z) = exp(-gamma_k ||z||^2) (c_k + v_k^T z + z^T M_k z) + b_k

The K Hessians are TILED over a grid axis in head-blocks of ``block_k``
heads, so K*d^2 never has to fit VMEM at once (mnist K=10 at d=780 is
~32 MB of f32 Hessians, over a v5e core's 16 MiB scoped VMEM). Grid =
(head_blocks, n_tiles) with Z tiles innermost: each (block_k, d, d)
Hessian block is read from HBM exactly ONCE and stays resident while
every Z tile streams through back-to-back per-head MXU dots

    Z @ M_k -> (BN, d)   --row-dot Z-->   (BN, 1)      for each head in block

plus the thin per-head linear term and a fused exp/bias/validity
epilogue. The per-head dots have the same shape for any block_k, which
keeps the fp32 accumulation order fixed: head-blocks are independent —
every grid step writes its own (BN, BK) score tile, no cross-step
accumulation — so the tiled kernel is bit-for-bit identical to the
untiled one for any block_k.

TPU layout (the (8, 128) tiling rule): the head-block index is a LEADING
axis of every per-head operand and output, so the last two dims of each
block equal the array's whatever ``block_k`` is (see ``_heads_call``).
``TileConfig.resolve_block_k`` sizes the block against the whole
double-buffered grid step and the kernel is compiled with that same
``vmem_limit_mb``.

Scalar head parameters arrive as (4, BK) f32 rows per head-block (c, b,
gamma, ||x_M||^2) instead of baked-in Python floats, so the kernel can be
traced with model parameters as jit ARGUMENTS — the core API jits over
the model pytree; only the serving engine closes over a fixed model.

Outputs per batch row: (BN, K) scores and the per-head Eq 3.11 validity
mask — the accuracy-contract check is free because ||z||^2 already feeds
the exp envelope (``||z||^2`` itself is returned from outside the kernel).

Block sizes come from ``repro.kernels.common``: pass a ``TileConfig``
(the backend/tuning layer resolves one per shape bucket) or get the
kernel-family default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import TileConfig, tiles, tuning
from repro.kernels.quadform.ref import eq311_valid


def _heads_body(z, zm, v_ref, p, o_ref, valid_ref, *, block_k: int):
    """Shared tile body. ``zm(h)`` gives head h's (BN, d) product Z @ M_h
    (dequant scales folded for int8), ``v_ref`` the (BK, d) linear block,
    ``p`` the (4, BK) rows c, b, gamma, ||x_M||^2.

    A loop over the block's heads: one (BN, d) @ (d, d) MXU dot and two
    VPU row-dots each, the head's (BN, 1) columns selected into (BN, BK)
    accumulators. Every dot has the SAME shape for ANY block_k, so the
    fp32 accumulation order per head never depends on the tiling — tiled
    and untiled kernels are bit-for-bit identical. Everything stays 2-D:
    per-head scalars are (1, BK) rows.
    """
    c, bias, gamma, msq = (p[r:r + 1, :] for r in range(4))
    z_sq = jnp.sum(z * z, axis=-1, keepdims=True)             # (BN, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)

    def one_head(h, acc):
        quad, lin = acc
        q = jnp.sum(zm(h) * z, axis=-1, keepdims=True)         # (BN, 1)
        l = jnp.sum(z * v_ref[pl.ds(h, 1), :], axis=-1, keepdims=True)
        return jnp.where(head == h, q, quad), jnp.where(head == h, l, lin)

    zeros = jnp.zeros(o_ref.shape, jnp.float32)
    quad, lin = jax.lax.fori_loop(0, block_k, one_head, (zeros, zeros))
    env = jnp.exp(-z_sq * gamma)
    o_ref[...] = env * (c + lin + quad) + bias
    valid_ref[...] = eq311_valid(z_sq, gamma, msq).astype(jnp.float32)


def _heads_kernel(z_ref, m_ref, v_ref, p_ref, o_ref, valid_ref, *, block_k: int):
    z = z_ref[...]                                            # (BN, d)

    def zm(h):
        return jax.lax.dot_general(
            z, m_ref[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _heads_body(z, zm, v_ref, p_ref[...], o_ref, valid_ref, block_k=block_k)


def _heads_kernel_q8(z_ref, m_ref, s_ref, v_ref, p_ref, o_ref, valid_ref,
                     *, block_k: int):
    """Int8-Hessian variant: ``m_ref`` holds the int8 Hessian block,
    ``s_ref`` the per-(head, column) f32 scales. The dequantization is
    FUSED: each head's int8 slice is upcast in VMEM for its MXU dot and
    the scale folds onto the (BN, d) GEMM result — one VPU multiply per
    head; only one head's f32 copy exists at a time."""
    z = z_ref[...]                                            # (BN, d) f32

    def zm(h):
        out = jax.lax.dot_general(
            z, m_ref[h].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return out * s_ref[pl.ds(h, 1), :]                    # fold column scales

    _heads_body(z, zm, v_ref, p_ref[...], o_ref, valid_ref, block_k=block_k)


def _heads_call(kernel, Z, M, per_head, scalars, *, config, m_itemsize, interpret):
    """Pad, lay out and run one head-block-tiled quadform ``pallas_call``.

    Grid = (head_blocks, n_tiles), head-blocks OUTER: each Hessian block
    is fetched once and stays resident while every Z tile streams through.
    The head-block index is a leading axis of every per-head operand and
    output, so each block's last two dims equal the array's — legal on the
    TPU's (8, 128) tiling for any ``block_k``:

      M         (k_pad, d, d)        block (BK, d, d)
      per-head  (kb, BK, d)          block (-, BK, d)   V [and int8 scales]
      params    (kb, 4, BK)          block (-, 4, BK)   c, b, gamma, msq
      outputs   (kb, n_pad, BK)      block (-, BN, BK)  scores, valid
    """
    n, d = Z.shape
    k = M.shape[0]
    d_pad = tiles.lane_pad(d)
    config = config.clamp_block_n(n)
    block_n = config.block_n
    block_k = config.resolve_block_k(k, d_pad, m_itemsize=m_itemsize,
                                     per_head_rows=len(per_head))
    n_pad = tiles.round_up(n, block_n)
    k_pad = tiles.round_up(k, block_k)
    kb = k_pad // block_k

    Zp = tiles.pad_tail(Z.astype(jnp.float32), n_pad, d_pad)
    Mp = tiles.pad_axis(tiles.pad_tail(M, d_pad, d_pad), 0, k_pad)  # zero heads
    rows = [
        tiles.pad_tail(x.astype(jnp.float32), k_pad, d_pad).reshape(kb, block_k, d_pad)
        for x in per_head
    ]
    params = jnp.stack([jnp.ravel(x) for x in scalars]).astype(jnp.float32)
    params = tiles.pad_axis(params, 1, k_pad).reshape(4, kb, block_k)
    params = jnp.transpose(params, (1, 0, 2))                  # (kb, 4, BK)

    head_rows = pl.BlockSpec((None, block_k, d_pad), lambda j, i: (j, 0, 0))
    out_tile = pl.BlockSpec((None, block_n, block_k), lambda j, i: (j, i, 0))
    out_shape = jax.ShapeDtypeStruct((kb, n_pad, block_k), jnp.float32)
    scores, valid = pl.pallas_call(
        functools.partial(kernel, block_k=block_k),
        grid=(kb, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda j, i: (i, 0)),
            pl.BlockSpec((block_k, d_pad, d_pad), lambda j, i: (j, 0, 0)),
            *[head_rows] * len(rows),
            pl.BlockSpec((None, 4, block_k), lambda j, i: (j, 0, 0)),
        ],
        out_specs=[out_tile, out_tile],
        out_shape=[out_shape, out_shape],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=config.vmem_limit_mb << 20
        ),
        interpret=interpret,
        name="quadform_q8" if m_itemsize == 1 else "quadform",
    )(Zp, Mp, *rows, params)

    def heads_last(x):                                        # (kb, n, BK) -> (n, K)
        return jnp.transpose(x, (1, 0, 2)).reshape(n_pad, k_pad)[:n, :k]

    z_sq = jnp.sum(Zp[:n] * Zp[:n], axis=-1)
    return heads_last(scores), z_sq, heads_last(valid) > 0.0


def quadform_heads_pallas(
    Z: jax.Array,
    M_all: jax.Array,
    V: jax.Array,
    c: jax.Array,
    b: jax.Array,
    gamma: jax.Array,
    msq: jax.Array,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
):
    """Fused K-head scores, head-block tiled. Z: (n, d), M_all: (K, d, d),
    V: (K, d); c/b/gamma/msq: (K,). Returns (scores (n, K), z_sq (n,),
    valid (n, K))."""
    return _heads_call(
        _heads_kernel, Z, M_all.astype(jnp.float32), [V], [c, b, gamma, msq],
        config=config or tuning.lookup("quadform"), m_itemsize=4,
        interpret=interpret,
    )


def quadform_heads_q8_pallas(
    Z: jax.Array,
    M_q: jax.Array,
    col_scale: jax.Array,
    V: jax.Array,
    c: jax.Array,
    b: jax.Array,
    gamma: jax.Array,
    msq: jax.Array,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
):
    """Fused K-head scores off an int8 stacked Hessian. Z: (n, d),
    M_q: (K, d, d) int8, col_scale: (K, d) f32 (per-column dequant
    scales, already expanded from the stored per-group form), V: (K, d)
    f32; c/b/gamma/msq: (K,). Returns (scores (n, K), z_sq (n,),
    valid (n, K)) — same contract as ``quadform_heads_pallas``, the int8
    block streams from HBM at a quarter of the f32 bandwidth."""
    return _heads_call(
        _heads_kernel_q8, Z, M_q.astype(jnp.int8), [col_scale, V],
        [c, b, gamma, msq],
        config=config or tuning.lookup("quadform_q8"), m_itemsize=1,
        interpret=interpret,
    )


def quadform_predict_pallas(
    Z: jax.Array,
    M: jax.Array,
    v: jax.Array,
    c,
    b,
    gamma,
    *,
    config: TileConfig | None = None,
    interpret: bool = False,
):
    """Single-head wrapper (the original kernel API): K = 1 of the fused path.

    Returns (f_hat (n,), z_sq (n,)).  c/b/gamma may be Python floats or
    traced scalars.
    """
    one = lambda x: jnp.reshape(jnp.asarray(x, jnp.float32), (1,))
    scores, z_sq, _ = quadform_heads_pallas(
        Z, M[None], v[None], one(c), one(b), one(gamma), one(0.0),
        config=config, interpret=interpret,
    )
    return scores[:, 0], z_sq
