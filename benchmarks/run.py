"""Benchmark orchestrator: one module per paper table/figure + the
beyond-paper adaptation + the roofline summary (if dry-run results exist).

    PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import sys
import time
import traceback


def main():
    t0 = time.time()
    failures = []
    sections = []

    def section(name, fn):
        print(f"\n{'='*72}\n== {name}\n{'='*72}")
        try:
            fn()
            sections.append(name)
        except Exception:
            traceback.print_exc()
            failures.append(name)

    from benchmarks import fig1_error, table1_accuracy, table2_speed
    from benchmarks import table3_modelsize, maclaurin_attn_quality

    section("Fig 1 — Maclaurin exp relative error", fig1_error.run)
    section("Table 1 — accuracy / label-diff", table1_accuracy.run)
    section("Table 2 — prediction speed (measured, CPU)", table2_speed.run)
    section("Table 3 — model size", table3_modelsize.run)
    section("Beyond-paper — Maclaurin attention", maclaurin_attn_quality.run)

    def roofline():
        import glob
        if not glob.glob("results/dryrun/*.json"):
            print("no dry-run artifacts found; run: "
                  "PYTHONPATH=src python -m repro.launch.dryrun --all")
            return
        from repro.launch import roofline as rl
        rl.main()

    section("Roofline — 40-cell dry-run summary", roofline)

    print(f"\n{'='*72}")
    print(f"benchmarks done in {time.time()-t0:.1f}s; "
          f"{len(sections)} sections ok, {len(failures)} failed {failures or ''}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
