"""Analytic cost prior for the serving kernels' tile and family searches.

Per candidate ``TileConfig`` the weight-streaming traffic is a closed
form in the tile shape, so the autotuner can rank candidates and measure
only the plausibly-fast ones (``autotune.prune_candidates``), and
``compile_model`` can skip compiling candidates whose predicted cost is
hopeless (``family_candidate_seconds``). The prior ranks — measurement
still decides (the never-worse-than-default guarantee lives in
``kernels.common.autotune``, which always measures the default).
"""

from __future__ import annotations

# TPU v5e bf16 peak and HBM bandwidth. Ranking constants only: they order
# tile and family candidates and never stand for a measured rate. A peak
# table keyed by ``device_kind`` belongs with the chip benchmark.
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def predict_seconds(flops: float, bytes_accessed: float, wire: float = 0.0) -> float:
    """Roofline lower bound for one kernel invocation: the binding term."""
    t = max(flops / PEAK_FLOPS, bytes_accessed / HBM_BW)
    if wire:
        t = max(t, wire / ICI_BW)
    return t


def _row_blocks(n: int, block_n) -> int:
    """How many row tiles a batch of ``n`` splits into under ``block_n``."""
    n = max(1, int(n))
    b = int(block_n) if block_n else n
    b = max(1, min(b, n))
    return -(-n // b)


def quadform_tile_seconds(
    cfg, *, n: int, d: int, k: int, weight_bytes: int = 4
) -> float:
    """Analytic cost of one fused quadform step (Eq 3.8, all K heads).

    The (K, d, d) stacked Hessian is re-streamed once per row tile —
    the term that actually moves with ``block_n`` (bigger tiles amortize
    the weight traffic; FLOPs are tile-invariant). ``weight_bytes=1``
    models the int8 variants.
    """
    blocks = _row_blocks(n, getattr(cfg, "block_n", None) if cfg else None)
    flops = 2.0 * n * k * d * (d + 1)
    stream = float(blocks) * k * d * d * weight_bytes
    io = 4.0 * (n * d + n * k) + float(weight_bytes) * k * d
    return predict_seconds(flops, stream + io)


def rbf_tile_seconds(cfg, *, n: int, d: int, m: int) -> float:
    """Analytic cost of the exact streaming ``rbf_pred`` path (m SVs)."""
    blocks = _row_blocks(n, getattr(cfg, "block_n", None) if cfg else None)
    flops = 2.0 * n * m * d
    stream = float(blocks) * m * d * 4.0
    io = 4.0 * (n * d + n)
    return predict_seconds(flops, stream + io)


def rff_tile_seconds(
    cfg, *, n: int, d: int, f: int, k: int, weight_bytes: int = 4
) -> float:
    """Analytic cost of the fused RFF step (projection + readout GEMMs)."""
    blocks = _row_blocks(n, getattr(cfg, "block_n", None) if cfg else None)
    flops = 2.0 * n * f * (d + k)
    stream = float(blocks) * (f * d + k * f) * float(weight_bytes)
    io = 4.0 * (n * d + n * k)
    return predict_seconds(flops, stream + io)


def fwht_tile_seconds(
    cfg, *, n: int, d: int, f: int, k: int, weight_bytes: int = 4
) -> float:
    """Analytic cost of the fused Fastfood step (FWHT stacks + readout).

    Per row: each of the ``stacks`` = F / d' stacks runs two d'-wide
    Walsh-Hadamard transforms (log2(d') add stages each) plus the three
    diagonal multiplies and permutation — ~2 d' (log2 d' + 2) FLOPs per
    stack, i.e. O(F log d') in place of the dense path's O(F d) — then
    the same 2 F K readout GEMM as dense RFF. Streamed weights are the
    O(F) diagonals (4 arrays of F elements at ``weight_bytes``, plus the
    f32 phase) and the (K, F) readout, re-streamed once per row tile;
    ``weight_bytes=1`` models the int8 variant. The structured prior
    undercuts ``rff_tile_seconds`` wherever log2(d') << d — the
    compile-search ranking the paper's loglinear claim turns into.
    """
    blocks = _row_blocks(n, getattr(cfg, "block_n", None) if cfg else None)
    dd = 1 << max(1, (d - 1).bit_length())  # next pow2 >= d
    stacks = -(-int(f) // dd)
    fp = stacks * dd  # F rounded to stacks
    log_dd = max(1, dd.bit_length() - 1)
    flops = float(n) * (2.0 * stacks * dd * (log_dd + 2) + 2.0 * fp * k)
    stream = float(blocks) * (
        fp * (3.0 * weight_bytes + 4.0)  # B/G/S diagonals + phase
        + k * fp * weight_bytes  # readout
    )
    io = 4.0 * (n * d + n * k)
    return predict_seconds(flops, stream + io)


def family_candidate_seconds(
    family: str,
    dtype: str,
    *,
    n: int,
    d: int,
    k: int,
    num_features: int | None = None,
    structured: bool = False,
    cfg=None,
) -> float | None:
    """Predicted serving seconds for one ``compile_model`` candidate.

    Returns ``None`` for families without an analytic model — the caller
    must then measure (never prune on ignorance).
    """
    wb = 1 if dtype == "int8" else 4
    if family in ("maclaurin", "poly2"):
        return quadform_tile_seconds(cfg, n=n, d=d, k=k, weight_bytes=wb)
    if family == "fourier":
        f = int(num_features) if num_features else 1024  # fourier default
        if structured:
            return fwht_tile_seconds(cfg, n=n, d=d, f=f, k=k, weight_bytes=wb)
        return rff_tile_seconds(cfg, n=n, d=d, f=f, k=k, weight_bytes=wb)
    return None
