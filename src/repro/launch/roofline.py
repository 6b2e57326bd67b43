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

The hardware constants come from ``repro.kernels.common.cost``, the
serving kernels' analytic cost prior, so each has one definition.
"""

from __future__ import annotations

import glob
import json
import os

from repro.kernels.common.cost import HBM_BW, ICI_BW, PEAK_FLOPS

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
