"""Roofline-term derivation from the dry-run artifacts (deliverable g).

Per (arch x shape) cell on the single-pod 16x16 mesh:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device / HBM_bandwidth
    collective term = wire_bytes_per_device / ICI_link_bandwidth

cost_analysis() is already per-device (post-SPMD). Collective wire bytes
use ring-algorithm multipliers on the parsed per-device result sizes:

    all-reduce       2 (g-1)/g x bytes          (reduce-scatter + all-gather)
    all-gather       (g-1)/g x result bytes     (result = gathered buffer)
    reduce-scatter   (g-1)   x result bytes     (result = scattered shard)
    all-to-all       (g-1)/g x bytes
    collective-perm  1 x bytes

MODEL_FLOPS uses the classic estimators (6 N_active D for train,
2 N_active D for single forward) against global HLO FLOPs to expose
remat/dispatch overheads. Hardware constants per the brief (TPU v5e):
197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

The same three-term model doubles as the ANALYTIC PRIOR for the serving
kernels' tile search (``*_tile_seconds`` below): per candidate
``TileConfig`` the weight-streaming traffic is a closed form in the tile
shape, so the autotuner can rank candidates and measure only the
plausibly-fast ones, and ``compile_model`` can skip compiling candidates
whose predicted cost is hopeless (``family_candidate_seconds``). The
prior ranks — measurement still decides (the never-worse-than-default
guarantee lives in ``kernels.common.autotune``, which always measures
the default).
"""

from __future__ import annotations

import glob
import json
import os

# TPU v5e bf16 peak and HBM bandwidth. Ranking constants only: they order
# tile and family candidates and never stand for a measured rate. A peak
# table keyed by ``device_kind`` belongs with the chip benchmark.
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

RESULTS_DIR = "results/dryrun"


def wire_bytes(collective_ops: list[dict], default_group: int = 16) -> float:
    total = 0.0
    for op in collective_ops:
        g = op.get("group_size") or default_group
        b = op.get("total_bytes", op["bytes"] * op.get("count", 1))
        k = op["kind"]
        if k == "all-reduce":
            total += 2 * (g - 1) / g * b
        elif k == "all-gather":
            total += (g - 1) / g * b
        elif k == "reduce-scatter":
            total += (g - 1) * b
        elif k == "all-to-all":
            total += (g - 1) / g * b
        else:  # collective-permute
            total += b
    return total


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


def quadform_tile_seconds(cfg, *, n: int, d: int, k: int,
                          weight_bytes: int = 4) -> float:
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


def rff_tile_seconds(cfg, *, n: int, d: int, f: int, k: int,
                     weight_bytes: int = 4) -> float:
    """Analytic cost of the fused RFF step (projection + readout GEMMs)."""
    blocks = _row_blocks(n, getattr(cfg, "block_n", None) if cfg else None)
    flops = 2.0 * n * f * (d + k)
    stream = float(blocks) * (f * d + k * f) * float(weight_bytes)
    io = 4.0 * (n * d + n * k)
    return predict_seconds(flops, stream + io)


def fwht_tile_seconds(cfg, *, n: int, d: int, f: int, k: int,
                      weight_bytes: int = 4) -> float:
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
    dd = 1 << max(1, (d - 1).bit_length())                 # next pow2 >= d
    stacks = -(-int(f) // dd)
    fp = stacks * dd                                       # F rounded to stacks
    log_dd = max(1, dd.bit_length() - 1)
    flops = float(n) * (2.0 * stacks * dd * (log_dd + 2) + 2.0 * fp * k)
    stream = float(blocks) * (
        fp * (3.0 * weight_bytes + 4.0)                    # B/G/S diagonals + phase
        + k * fp * weight_bytes                            # readout
    )
    io = 4.0 * (n * d + n * k)
    return predict_seconds(flops, stream + io)


def family_candidate_seconds(
    family: str, dtype: str, *, n: int, d: int, k: int,
    num_features: int | None = None, structured: bool = False, cfg=None,
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


def model_flops(meta: dict) -> float:
    n = meta["active_params"]
    tokens = meta["global_batch"] * (
        1 if meta["kind"] == "decode" else meta["seq_len"]
    )
    mult = 6 if meta["kind"] == "train" else 2
    return mult * n * tokens


def analyze_cell(rec: dict) -> dict:
    n_dev = rec["n_devices"]
    t_compute = rec["cost"]["flops"] / PEAK_FLOPS
    t_memory = rec["cost"]["bytes_accessed"] / HBM_BW
    wb = wire_bytes(rec.get("collective_ops", []))
    t_coll = wb / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec)
    hlo_global = rec["cost"]["flops"] * n_dev
    useful = mf / hlo_global if hlo_global else 0.0
    # roofline fraction: useful work at peak vs the bounding term
    ideal = mf / n_dev / PEAK_FLOPS
    bound = max(terms.values())
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "kind": rec["kind"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": useful,
        "roofline_fraction": (ideal / bound) if bound else 0.0,
        "mem_gib_per_dev": rec["memory"]["peak_device_bytes"] / 2**30,
        "collectives": rec.get("collectives", {}),
        "rules": rec.get("rules", "default"),
    }


def load_all(mesh: str = "16x16", rules: str = "auto") -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, f"*__{mesh}.json"))):
        # exact arch__shape__mesh tags only — hillclimb variants carry
        # extra __suffixes and are excluded from the headline table
        with open(path) as f:
            rec = json.load(f)
        if rec.get("rules", "default") != rules:
            continue
        if rec["mesh"] != mesh:
            continue
        out.append(analyze_cell(rec))
    return out


def to_markdown(rows: list[dict]) -> str:
    hdr = (
        "| arch | shape | compute (s) | memory (s) | collective (s) | dominant | "
        "MODEL/HLO | roofline frac | mem GiB/dev |\n"
        "|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.2f} | {r['mem_gib_per_dev']:.1f} |"
        )
    return hdr + "\n".join(lines) + "\n"


def main():
    rows = load_all()
    os.makedirs("results", exist_ok=True)
    md = to_markdown(rows)
    with open("results/roofline.md", "w") as f:
        f.write(md)
    with open("results/roofline.json", "w") as f:
        json.dump(rows, f, indent=1)
    print(md)
    print(f"{len(rows)} cells analyzed -> results/roofline.md")


if __name__ == "__main__":
    main()
