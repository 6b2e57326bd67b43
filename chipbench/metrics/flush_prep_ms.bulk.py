"""Host input preparation per flush in the bulk cells (layer: host
input): ``runtime.flush.assemble`` (the concatenate of the drained
requests) plus ``svm_engine.pad/*`` (zero buffer and copy), inside the
flushes wholly in the traced window, over their number
(``chipbench/flush.py``)."""

from chipbench import flush


def read(run):
    f = flush.of(run)
    return None if f is None else f.ms("assemble") + f.ms("pad")
