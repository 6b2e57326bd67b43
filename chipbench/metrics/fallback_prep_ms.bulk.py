"""Host input of the exact fallback per result in the bulk cells (layer:
exact-path host input): ``svm_engine.fallback.gather/*`` (the rows
outside Eq 3.11 gathered into a staging buffer of their bucket's size)
plus ``svm_engine.fallback.put/*`` (the copy's issue), inside the
fallbacks wholly in the traced window, over their number
(``chipbench/fallback.py``). None where the fallbacks hold no stage
spans."""

from chipbench import fallback


def read(run):
    f = fallback.of(run)
    return None if f is None or f.staged == 0 else f.ms("gather", "put")
