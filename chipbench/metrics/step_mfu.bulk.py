"""The whole step's share of the chip's peak in the bulk cells (layer:
model step): rows served per second of the traced window, times the
2 K d^2 operations per row of the pinned maclaurin family, over chips x
the bf16 peak."""

from chipbench import work


def read(run):
    rows = run.counters.get("served_rows", 0)
    if run.trace is None or rows <= 0 or run.trace.window_s <= 0:
        return None
    flops = work.step_flops(rows, int(run.config["heads"]), int(run.config["d"]))
    return 100.0 * flops / run.trace.window_s / (run.chips * run.peaks["bf16_flops"])
