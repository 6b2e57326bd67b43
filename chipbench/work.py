"""Work counts: operations and bytes an algorithm needs, from the
configuration's shapes and the rows actually served.

They never look at the kernel's tiling, padding or buckets, so a count
reads the same work whatever implements it.
"""

from __future__ import annotations

F32 = 4


def quadform_flops(rows: int, heads: int, d: int) -> float:
    """The §3 quadratic form for ``rows`` rows and ``heads`` heads:
    per row and head, z^T M_k (2 d^2), its row-dot with z (2 d) and the
    linear term v_k^T z (2 d)."""
    return float(rows) * heads * (2.0 * d * d + 4.0 * d)


def quadform_bytes(rows: int, calls: int, heads: int, d: int) -> float:
    """Least HBM traffic: each call reads the (K, d, d) Hessians and (K, d)
    linear terms once; every row is read once (d floats) and writes its K
    scores and K validity flags."""
    per_call = heads * (d * d + d) * F32
    per_row = (d + 2 * heads) * F32
    return float(calls) * per_call + float(rows) * per_row


def exact_flops(rows: int, heads: int, n_sv: int, d: int) -> float:
    """The exact RBF expansion for ``rows`` rows and ``heads`` heads: per
    row, the cross term z . x_i for every support vector (2 n_sv d), the
    row's squared norm (2 d) and the K-head readout sum_i alpha_ki k_i
    (2 K n_sv). The support vectors' norms are the model's, computed once."""
    return float(rows) * (2.0 * n_sv * d + 2.0 * d + 2.0 * heads * n_sv)


def exact_bytes(rows: int, calls: int, heads: int, n_sv: int, d: int) -> float:
    """Least HBM traffic of the exact expansion: each call reads the
    (n_sv, d) support vectors and the (K, n_sv) coefficients once; every
    row is read once (d floats) and writes its K scores."""
    per_call = (n_sv * d + heads * n_sv) * F32
    per_row = (d + heads) * F32
    return float(calls) * per_call + float(rows) * per_row


def step_flops(rows: int, heads: int, d: int) -> float:
    """Operations per served row of the pinned maclaurin family, the
    quadratic term 2 K d^2 of the step that dominates it."""
    return float(rows) * 2.0 * heads * d * d


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute, t_memory = flops / peak_flops, nbytes / peak_bw
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
